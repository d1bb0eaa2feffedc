"""DataFeeder: convert python/numpy minibatches into executor feed dicts.

Parity: reference ``python/paddle/fluid/data_feeder.py:83`` (DataFeeder:
converts reader rows into LoDTensors per place; feed_parallel splits across
devices) — TPU-native: produces numpy arrays (the executor moves them to
device); ragged sequence rows are packed/padded via the sequence utilities
instead of LoD.
"""

import numpy as np

from .core import convert_dtype
from .framework import Variable, default_main_program

__all__ = ["DataFeeder"]


class _Converter:
    def __init__(self, shape, dtype):
        self.shape = tuple(shape) if shape is not None else None
        self.dtype = dtype
        self.rows = []

    def feed(self, item):
        self.rows.append(np.asarray(item, dtype=self.dtype))

    def done(self):
        arr = np.stack(self.rows) if self.rows else np.zeros((0,), self.dtype)
        if self.shape is not None and -1 not in self.shape[1:]:
            want = tuple(s for s in self.shape if s != -1)
            if arr.size and arr.shape[1:] != want[-len(arr.shape[1:]):]:
                try:
                    arr = arr.reshape((arr.shape[0],) + tuple(
                        s for s in self.shape[1:]))
                except ValueError:
                    pass
        return arr


class _SequenceConverter:
    """Ragged rows -> padded [batch, T, ...] + int32 [batch] lengths (the
    LoD replacement; ``pad_to`` fixes T for static-shape friendliness —
    per-batch max otherwise, which recompiles per distinct T)."""

    def __init__(self, shape, dtype, pad_to=None):
        self.shape = tuple(shape) if shape is not None else None
        self.dtype = dtype
        self.pad_to = pad_to
        self.rows = []

    def feed(self, item):
        arr = np.asarray(item, dtype=self.dtype)
        # scalar-per-step shape [D]=[1] declared: accept [T] and lift to [T,1]
        if self.shape is not None:
            trailing = tuple(s for s in self.shape[2:])
            if trailing == (1,) and arr.ndim == 1:
                arr = arr[:, None]
        self.rows.append(arr)

    def done(self):
        lens = np.asarray([r.shape[0] for r in self.rows], dtype=np.int32)
        t = int(self.pad_to) if self.pad_to else int(lens.max() if len(lens)
                                                     else 0)
        if len(self.rows) and any(r.shape[0] > t for r in self.rows):
            raise ValueError(
                "sequence longer than pad_to=%d" % t)
        trailing = self.rows[0].shape[1:] if self.rows else ()
        out = np.zeros((len(self.rows), t) + trailing, self.dtype)
        for i, r in enumerate(self.rows):
            out[i, :r.shape[0]] = r
        return out, lens


class DataFeeder:
    def __init__(self, feed_list, place=None, program=None, pad_to=None):
        self.feed_dtypes = []
        self.feed_names = []
        self.feed_shapes = []
        self.feed_lod_levels = []
        self.place = place
        self.pad_to = pad_to
        if program is None:
            program = default_main_program()
        for v in feed_list:
            if isinstance(v, str):
                v = program.global_block().var(v)
            assert isinstance(v, Variable)
            self.feed_names.append(v.name)
            self.feed_dtypes.append(v.dtype)
            self.feed_shapes.append(v.shape)
            self.feed_lod_levels.append(v.lod_level or 0)

    def feed(self, iterable, pad_to=None):
        """rows of tuples -> {name: batched ndarray}; sequence fields
        (lod_level>=1) additionally produce the '<name>@LEN' array.
        ``pad_to`` overrides the constructor's pad length for this batch
        — the per-bucket pad bound of ``reader.bucket_by_length``."""
        pad = pad_to if pad_to is not None else self.pad_to
        converters = [
            _SequenceConverter(shape, dtype, pad_to=pad)
            if lod >= 1 else _Converter(shape, dtype)
            for shape, dtype, lod in zip(
                self.feed_shapes, self.feed_dtypes, self.feed_lod_levels)
        ]
        for each_sample in iterable:
            assert len(each_sample) == len(converters), (
                "sample has %d fields, expected %d"
                % (len(each_sample), len(converters))
            )
            for item, conv in zip(each_sample, converters):
                conv.feed(item)
        out = {}
        for name, conv, lod in zip(self.feed_names, converters,
                                   self.feed_lod_levels):
            if lod >= 1:
                arr, lens = conv.done()
                out[name] = arr
                out[name + "@LEN"] = lens
            else:
                out[name] = conv.done()
        return out

    def prefetch(self, reader, capacity=2, place=None, shardings=None):
        """Overlapped input pipeline: a ``DevicePrefetcher`` that runs
        this feeder's row->array conversion AND the host->device transfer
        of step N+1 under compute of step N.  ``reader`` yields sample
        rows (a reader creator or iterable); ``shardings`` routes feeds
        onto a pjit mesh (``{name: Sharding}`` or one Sharding for all)
        so ParallelExecutor consumes them with zero extra copies."""
        from .reader import DevicePrefetcher

        if place is None:
            place = self.place
        if place is None and (shardings is None
                              or isinstance(shardings, dict)):
            # no place anywhere would stage nothing (host arrays pass
            # through, h2d lands back on the critical path): default to
            # the accelerator (when there is one) like
            # layers.double_buffer.  A partial shardings dict still
            # needs it for unlisted feeds.
            from .executor import default_place

            place = default_place()
        return DevicePrefetcher(
            reader, feeder=self, place=place,
            shardings=shardings, capacity=capacity)

    def feed_parallel(self, iterable, num_places=None):
        """Split one batch into per-device feeds (reference
        data_feeder.py:feed_parallel) — used by the mesh runtime for
        manual per-device feeding; pjit sharding usually replaces this."""
        import math

        rows = list(iterable)
        n = num_places or 1
        per = math.ceil(len(rows) / n)
        old_pad = self.pad_to
        try:
            if old_pad is None and any(l >= 1 for l in self.feed_lod_levels):
                # pad every slice to the global max so the per-device dicts
                # concatenate/stack consistently
                global_max = 0
                for row in rows:
                    for item, lod in zip(row, self.feed_lod_levels):
                        if lod >= 1:
                            global_max = max(global_max,
                                             np.asarray(item).shape[0])
                self.pad_to = global_max or None
            return [self.feed(rows[i * per:(i + 1) * per]) for i in range(n)]
        finally:
            self.pad_to = old_pad
