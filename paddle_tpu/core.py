"""Core scalar/dtype definitions shared by the whole framework.

Plays the role of the reference's ``paddle/fluid/framework/framework.proto``
VarType/data-type enums (framework.proto:104) plus ``platform/float16.h`` —
but TPU-native: dtypes are numpy/jax dtypes, bfloat16 is first-class (the MXU
native format), and there is no protobuf in the hot path (programs serialize
to a plain-dict format in ``framework.py``).
"""

import numpy as np

import ml_dtypes  # jax's bfloat16 comes from ml_dtypes

bfloat16 = np.dtype(ml_dtypes.bfloat16)


class VarType:
    """Variable kinds, mirroring the capability of VarDesc.VarType
    (reference framework.proto:104): dense tensors, parameter-like
    persistables, readers and step scopes are represented; LoD is replaced by
    packed segment metadata carried in ``Variable.lod_level`` plus explicit
    segment-id companions (see SURVEY.md §5 long-context notes)."""

    DENSE_TENSOR = "dense_tensor"
    SELECTED_ROWS = "selected_rows"  # sparse row-slice gradients
    READER = "reader"
    STEP_SCOPES = "step_scopes"
    RAW = "raw"


_DTYPE_ALIASES = {
    "float32": np.float32,
    "float64": np.float64,
    "float16": np.float16,
    "int32": np.int32,
    "int64": np.int64,
    "int16": np.int16,
    "int8": np.int8,
    "uint8": np.uint8,
    "bool": np.bool_,
    "bfloat16": bfloat16,
}


def convert_dtype(dtype):
    """Normalize user-provided dtype (str / np.dtype / jax dtype) to np.dtype."""
    if isinstance(dtype, str):
        if dtype not in _DTYPE_ALIASES:
            raise ValueError("unsupported dtype string: %r" % dtype)
        return np.dtype(_DTYPE_ALIASES[dtype])
    return np.dtype(dtype)


def long_dtype():
    """The canonical wide-integer dtype for in-graph index/count outputs.

    The reference emits int64 everywhere (framework.proto VarType INT64);
    under JAX with x64 disabled an explicit int64 request silently truncates
    to int32 and raises a UserWarning per call.  Policy: declared program
    dtype stays ``int64`` for API parity, but compute paths materialize
    ``int64`` only when x64 is enabled and ``int32`` otherwise — explicit,
    warning-free, and exact for every in-range value (ids/counts < 2^31).
    """
    import jax
    import jax.numpy as jnp

    return jnp.int64 if jax.config.jax_enable_x64 else jnp.int32


def materialize_dtype(dtype):
    """Dtype to materialize arrays with under the current x64 mode.

    64-bit requests (declared program dtypes keep int64/float64 for API
    parity with the reference) degrade explicitly to their 32-bit siblings
    when x64 is disabled, instead of relying on JAX's warn-and-truncate."""
    import jax

    d = convert_dtype(dtype)
    if not jax.config.jax_enable_x64:
        degrade = {np.dtype(np.int64): np.dtype(np.int32),
                   np.dtype(np.uint64): np.dtype(np.uint32),
                   np.dtype(np.float64): np.dtype(np.float32)}
        return degrade.get(d, d)
    return d


def dtype_is_floating(dtype):
    d = convert_dtype(dtype)
    if d == bfloat16:
        return True
    return np.issubdtype(d, np.floating)


def dtype_is_integer(dtype):
    d = convert_dtype(dtype)
    return np.issubdtype(d, np.integer) or d == np.bool_


class Tensor(object):
    """Host tensor shim (reference pybind ``core.Tensor`` surface:
    ``set``/``shape``/buffer protocol).  Device residency belongs to
    XLA; this stages a numpy array for feeding."""

    def __init__(self, array=None):
        self._array = None if array is None else np.asarray(array)

    def set(self, array, place=None):
        self._array = np.asarray(array)

    def shape(self):
        return () if self._array is None else tuple(self._array.shape)

    def _dtype(self):
        return None if self._array is None else self._array.dtype

    def __array__(self, dtype=None):
        if self._array is None:
            raise ValueError("Tensor is unset; call set() first")
        return (self._array.astype(dtype) if dtype is not None
                else self._array)
