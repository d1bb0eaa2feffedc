"""Chunked record-file format (RecordIO-equivalent) — native C++ core.

Parity: reference ``paddle/fluid/recordio/`` (Header/Chunk/Writer/
Scanner, ``chunk.h:27``) + ``python/paddle/fluid/recordio_writer.py``
and the ``paddle.reader.creator.recordio`` reader creator.

The hot path is C++ (``librecordio.cpp``: chunked layout, zlib
compression, crc32 integrity, chunk-skip for sharded scans), compiled
on first use with g++ (``native_build``: the binary is keyed on the
source's content) and bound via ctypes — no pybind11 needed; records
cross the boundary as (ptr, len) views.  A pure-python codec of the
SAME on-disk format (``_pyimpl``) is the fallback when no compiler is
available — announced once with a warning, and visible as
``native_available() == False`` — and doubles as the cross-check oracle
in tests.

Chunk granularity is the sharding unit: ``num_chunks`` + per-chunk
skipping let the elastic master (paddle_tpu.cloud) lease chunk spans to
trainers, which is exactly how the reference's Go master partitions
recordio files (go/master/service.go partition over chunks).
"""

import ctypes
import os
import subprocess
import warnings

from ..native_build import build_shared

__all__ = ["Writer", "Scanner", "num_chunks", "reader_creator",
           "convert_reader_to_recordio_file", "native_available"]

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "librecordio.cpp")
_lib = None
_native_failed = False


def _load():
    global _lib, _native_failed
    if _lib is not None or _native_failed:
        return _lib
    try:
        lib = ctypes.CDLL(build_shared(_HERE, "librecordio", [_SRC],
                                       ldflags=["-lz"]))
        lib.rio_writer_open.restype = ctypes.c_void_p
        lib.rio_writer_open.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                        ctypes.c_uint64]
        lib.rio_writer_write.restype = ctypes.c_int
        lib.rio_writer_write.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                         ctypes.c_uint64]
        lib.rio_writer_flush.restype = ctypes.c_int
        lib.rio_writer_flush.argtypes = [ctypes.c_void_p]
        lib.rio_writer_close.restype = ctypes.c_int
        lib.rio_writer_close.argtypes = [ctypes.c_void_p]
        lib.rio_scanner_open.restype = ctypes.c_void_p
        lib.rio_scanner_open.argtypes = [ctypes.c_char_p]
        lib.rio_scanner_next.restype = ctypes.c_int
        lib.rio_scanner_next.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_uint64)]
        lib.rio_scanner_skip_chunk.restype = ctypes.c_int
        lib.rio_scanner_skip_chunk.argtypes = [ctypes.c_void_p]
        lib.rio_scanner_set_max_chunks.argtypes = [ctypes.c_void_p,
                                                   ctypes.c_uint64]
        lib.rio_scanner_close.argtypes = [ctypes.c_void_p]
        lib.rio_num_chunks.restype = ctypes.c_int64
        lib.rio_num_chunks.argtypes = [ctypes.c_char_p]
        _lib = lib
    except (OSError, subprocess.CalledProcessError) as e:
        _native_failed = True
        _lib = None
        warnings.warn(
            "recordio: native codec unavailable (%s: %s); using the "
            "pure-Python codec of the same format"
            % (type(e).__name__, str(e)[:200]), stacklevel=2)
    return _lib


def native_available():
    return _load() is not None


class Writer:
    """Record writer (reference recordio/writer.h + recordio_writer.py
    context manager).  ``compressor``: 'none' or 'zlib'."""

    def __init__(self, path, compressor="zlib", max_chunk_bytes=1 << 20):
        comp = {"none": 0, "zlib": 1}[compressor]
        lib = _load()
        if lib is not None:
            self._h = lib.rio_writer_open(
                os.fsencode(path), comp, int(max_chunk_bytes))
            if not self._h:
                raise IOError("cannot open %r for writing" % path)
            self._py = None
        else:
            from . import _pyimpl

            self._py = _pyimpl.PyWriter(path, comp, int(max_chunk_bytes))
            self._h = None

    def write(self, record):
        if isinstance(record, str):
            record = record.encode("utf-8")
        if self._py is not None:
            return self._py.write(record)
        if _lib.rio_writer_write(self._h, record, len(record)) != 0:
            raise IOError("recordio write failed")

    def flush_chunk(self):
        """Close the current chunk (controls sharding boundaries)."""
        if self._py is not None:
            return self._py.flush_chunk()
        if _lib.rio_writer_flush(self._h) != 0:
            raise IOError("recordio flush failed")

    def close(self):
        if self._py is not None:
            return self._py.close()
        if self._h is not None:
            rc = _lib.rio_writer_close(self._h)
            self._h = None
            if rc != 0:
                raise IOError("recordio close failed")

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


class Scanner:
    """Record iterator (reference recordio/scanner.h).  ``skip_chunks``
    fast-forwards whole chunks without decoding — the sharded-read path
    used with the elastic master's chunk leases."""

    def __init__(self, path, skip_chunks=0, max_chunks=0):
        """``skip_chunks`` fast-forwards, ``max_chunks`` caps decoded
        chunks (0 = unlimited): together they scan the chunk range
        [skip, skip+max) — the shard unit of the parallel multi-file
        readers and the elastic master's task leases."""
        lib = _load()
        if lib is not None:
            self._h = lib.rio_scanner_open(os.fsencode(path))
            if not self._h:
                raise IOError("cannot open %r" % path)
            self._py = None
            try:
                for _ in range(skip_chunks):
                    rc = lib.rio_scanner_skip_chunk(self._h)
                    if rc < 0:
                        raise IOError("corrupt recordio file %r" % path)
                    if rc == 0:
                        break
                if max_chunks:
                    lib.rio_scanner_set_max_chunks(self._h, max_chunks)
            except Exception:
                lib.rio_scanner_close(self._h)
                self._h = None
                raise
        else:
            from . import _pyimpl

            self._py = _pyimpl.PyScanner(path, skip_chunks, max_chunks)
            self._h = None

    def __iter__(self):
        if self._py is not None:
            yield from self._py
            return
        data = ctypes.c_char_p()
        length = ctypes.c_uint64()
        while True:
            rc = _lib.rio_scanner_next(self._h, ctypes.byref(data),
                                       ctypes.byref(length))
            if rc == 0:
                return
            if rc < 0:
                raise IOError("corrupt recordio file")
            yield ctypes.string_at(data, length.value)

    def close(self):
        if self._py is not None:
            return self._py.close()
        if self._h is not None:
            _lib.rio_scanner_close(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


def num_chunks(path):
    """Chunk count (the shard index the task-lease queue partitions)."""
    lib = _load()
    if lib is not None:
        n = lib.rio_num_chunks(os.fsencode(path))
        if n < 0:
            raise IOError("cannot index %r" % path)
        return n
    from . import _pyimpl

    return _pyimpl.py_num_chunks(path)


# ---------------------------------------------------------------------------
# reader-layer integration (python/paddle/reader/creator.py:recordio and
# fluid/recordio_writer.py parity)

def reader_creator(paths):
    """Reader over one or more record files; records are bytes."""
    if isinstance(paths, str):
        paths = [p for p in paths.split(",") if p]

    def reader():
        for p in paths:
            with Scanner(p) as s:
                yield from s

    return reader


def convert_reader_to_recordio_file(filename, reader_creator_fn,
                                    serializer=None, compressor="zlib",
                                    max_chunk_bytes=1 << 20,
                                    feeder=None):
    """Materialize a sample reader into a record file
    (fluid/recordio_writer.py parity).  ``serializer(sample) -> bytes``
    defaults to pickle."""
    import pickle

    if feeder is not None:
        raise NotImplementedError(
            "feeder-driven serialization is not supported; pass a "
            "serializer(sample)->bytes instead (default: pickle)")
    serializer = serializer or pickle.dumps
    n = 0
    with Writer(filename, compressor, max_chunk_bytes) as w:
        for sample in reader_creator_fn():
            w.write(serializer(sample))
            n += 1
    return n
