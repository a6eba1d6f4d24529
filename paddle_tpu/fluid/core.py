"""Runtime core: places, dtypes, Scope, LoDTensor.

TPU-native replacement for the reference's C++ core exposed through pybind
(reference: paddle/fluid/pybind/pybind.cc, paddle/fluid/platform/place.h:26-58,
paddle/fluid/framework/scope.h:46, paddle/fluid/framework/lod_tensor.h:52-104).

Here the "device runtime" is JAX/XLA: a Place names a jax device class, a
Scope maps variable names to host/device arrays (jax.Array), and LoDTensor is
a thin ragged-batch wrapper (level-of-detail offsets + dense padded storage).
"""

from __future__ import annotations

import os
import threading

import numpy as np


# ---------------------------------------------------------------------------
# dtype enum — mirrors the proto VarType.Type numbering, which is the
# serialization contract (reference: paddle/fluid/framework/framework.proto:105-137).
# ---------------------------------------------------------------------------
class VarDesc(object):
    class VarType(object):
        BOOL = 0
        INT16 = 1
        INT32 = 2
        INT64 = 3
        FP16 = 4
        FP32 = 5
        FP64 = 6
        # Tensor-ish containers
        LOD_TENSOR = 7
        SELECTED_ROWS = 8
        FEED_MINIBATCH = 9
        FETCH_LIST = 10
        STEP_SCOPES = 11
        LOD_RANK_TABLE = 12
        LOD_TENSOR_ARRAY = 13
        PLACE_LIST = 14
        READER = 15
        RAW = 17
        TUPLE = 18
        SIZE_T = 19
        UINT8 = 20
        INT8 = 21
        # TPU-native extension: bf16 is the preferred mixed-precision dtype on
        # the MXU (the reference, CUDA-era, only had FP16).
        BF16 = 22


_DTYPE_TO_NP = {
    VarDesc.VarType.BOOL: np.bool_,
    VarDesc.VarType.INT16: np.int16,
    VarDesc.VarType.INT32: np.int32,
    VarDesc.VarType.INT64: np.int64,
    VarDesc.VarType.FP16: np.float16,
    VarDesc.VarType.FP32: np.float32,
    VarDesc.VarType.FP64: np.float64,
    VarDesc.VarType.UINT8: np.uint8,
    VarDesc.VarType.INT8: np.int8,
    VarDesc.VarType.SIZE_T: np.uint64,
}

_NP_TO_DTYPE = {np.dtype(v): k for k, v in _DTYPE_TO_NP.items()}


def dtype_to_np(dtype):
    """fluid dtype enum (or string / np.dtype) -> numpy dtype."""
    if dtype == VarDesc.VarType.BF16 or dtype == "bfloat16":
        import jax.numpy as jnp

        return jnp.bfloat16
    if isinstance(dtype, int):
        return np.dtype(_DTYPE_TO_NP[dtype])
    if isinstance(dtype, str):
        return np.dtype(dtype)
    return np.dtype(dtype)


def np_to_dtype(np_dtype):
    """numpy dtype (or string) -> fluid dtype enum."""
    if str(np_dtype) == "bfloat16":
        return VarDesc.VarType.BF16
    return _NP_TO_DTYPE[np.dtype(np_dtype)]


def convert_np_dtype_to_dtype_(np_dtype):
    return np_to_dtype(np_dtype)


def dtype_is_floating(dtype):
    if not isinstance(dtype, int):
        dtype = np_to_dtype(dtype)
    return dtype in (
        VarDesc.VarType.FP16,
        VarDesc.VarType.FP32,
        VarDesc.VarType.FP64,
        VarDesc.VarType.BF16,
    )


def dtype_name(dtype):
    if dtype == VarDesc.VarType.BF16:
        return "bfloat16"
    return np.dtype(_DTYPE_TO_NP[dtype]).name


# ---------------------------------------------------------------------------
# Places (reference: paddle/fluid/platform/place.h:26-58). On TPU the only
# real device class is the TPU chip grid managed by XLA; CPUPlace maps to the
# jax cpu backend (used by tests and as the reference backend).
# ---------------------------------------------------------------------------
class Place(object):
    _kind = "undefined"

    def __eq__(self, other):
        return type(self) is type(other) and getattr(
            self, "_device_id", None
        ) == getattr(other, "_device_id", None)

    def __hash__(self):
        return hash((self._kind, getattr(self, "_device_id", None)))

    def __repr__(self):
        return "%s()" % type(self).__name__


class CPUPlace(Place):
    _kind = "cpu"


class TPUPlace(Place):
    _kind = "tpu"

    def __init__(self, device_id=0):
        self._device_id = int(device_id)

    def __repr__(self):
        return "TPUPlace(%d)" % self._device_id


class CUDAPlace(TPUPlace):
    """Compatibility alias: scripts written against the reference swap
    ``CUDAPlace(0)`` for ``TPUPlace(0)``; accepting the old spelling makes the
    swap optional."""

    _kind = "tpu"


class CUDAPinnedPlace(CPUPlace):
    pass


def _jax_backend_for(place):
    """The jax platform a Place names: "tpu" or "cpu", nothing else."""
    return "tpu" if isinstance(place, TPUPlace) else "cpu"


def get_jax_device(place):
    """Place -> the jax Device it names, or raise: a TPUPlace never lands
    on another platform or another chip than the one asked for."""
    import jax

    backend = _jax_backend_for(place)
    try:
        # LOCAL devices: under jax.distributed (multi-process launch) the
        # global jax.devices() list starts with process 0's devices, and
        # placing eager values there from another process would create
        # non-addressable global arrays — a Place always names a device
        # THIS process owns (the reference's Place is per-process too)
        devices = jax.local_devices(backend=backend)
    except RuntimeError as e:
        raise RuntimeError(
            "%r: this process has no %r platform (jax default backend: %s)"
            % (place, backend, jax.default_backend())
        ) from e
    idx = getattr(place, "_device_id", 0)
    if not 0 <= idx < len(devices):
        raise ValueError(
            "%r: device index out of range, this process has %d %s device(s)"
            % (place, len(devices), backend)
        )
    return devices[idx]


def default_place():
    """The Place an entry point takes when its caller names none: chip 0
    where jax's default backend is the TPU, the CPU otherwise (tests pin
    JAX_PLATFORMS=cpu; a process on a chip host gets the chip)."""
    import jax

    return TPUPlace(0) if jax.default_backend() == "tpu" else CPUPlace()


def is_compiled_with_cuda():
    return False


def get_tpu_device_count():
    """Local TPU devices; 0 where the ``tpu`` platform is absent."""
    import jax

    try:
        return len(jax.local_devices(backend="tpu"))
    except RuntimeError:
        return 0


# ---------------------------------------------------------------------------
# LoDTensor — ragged sequence batch: dense storage + level-of-detail offsets
# (reference: paddle/fluid/framework/lod_tensor.h:52 LoD, :104 LoDTensor).
# ---------------------------------------------------------------------------
class LoDTensor(object):
    def __init__(self, array=None, lod=None, place=None):
        self._array = None if array is None else np.asarray(array)
        self._lod = [list(level) for level in (lod or [])]
        self._place = place or CPUPlace()

    # -- fluid pybind API surface (pybind.cc:402-539) --
    def set(self, array, place=None):
        self._array = np.asarray(array)
        if place is not None:
            self._place = place

    def set_lod(self, lod):
        self._lod = [list(level) for level in lod]

    def lod(self):
        return [list(level) for level in self._lod]

    def set_recursive_sequence_lengths(self, lengths):
        self._lod = [_lengths_to_offsets(level) for level in lengths]

    def recursive_sequence_lengths(self):
        return [_offsets_to_lengths(level) for level in self._lod]

    def has_valid_recursive_sequence_lengths(self):
        if not self._lod:
            return True
        try:
            n = self._lod[-1][-1]
        except IndexError:
            return False
        return self._array is None or n == self._array.shape[0]

    def shape(self):
        return list(self._array.shape) if self._array is not None else []

    def _dtype(self):
        return self._array.dtype if self._array is not None else None

    def __array__(self, dtype=None):
        a = np.asarray(self._array)
        return a.astype(dtype) if dtype is not None else a

    def numpy(self):
        return np.asarray(self._array)

    def __repr__(self):
        return "LoDTensor(shape=%s, lod=%s)" % (self.shape(), self._lod)


def _lengths_to_offsets(lengths):
    out = [0]
    for n in lengths:
        out.append(out[-1] + int(n))
    return out


def _offsets_to_lengths(offsets):
    return [int(offsets[i + 1] - offsets[i]) for i in range(len(offsets) - 1)]


class LoDTensorArray(list):
    """Array of LoDTensors (reference: framework/lod_tensor_array.h)."""


class SelectedRows(object):
    """Row-sparse tensor: (rows, value) pair used for embedding gradients
    (reference: paddle/fluid/framework/selected_rows.h:32)."""

    def __init__(self, rows=None, height=0, value=None):
        self.rows = list(rows or [])
        self.height = int(height)
        self.value = value  # np/jax array [len(rows), ...dims]

    def to_dense(self):
        import numpy as _np

        dense = _np.zeros((self.height,) + tuple(self.value.shape[1:]), self.value.dtype)
        _np.add.at(dense, _np.asarray(self.rows), _np.asarray(self.value))
        return dense


# ---------------------------------------------------------------------------
# Scope — hierarchical name -> variable-value map
# (reference: paddle/fluid/framework/scope.h:46).
# ---------------------------------------------------------------------------
class _ScopeVar(object):
    __slots__ = ("name", "value")

    def __init__(self, name, value=None):
        self.name = name
        self.value = value  # jax.Array | np.ndarray | LoDTensor | SelectedRows | py obj

    def get_tensor(self):
        if isinstance(self.value, LoDTensor):
            return self.value
        t = LoDTensor()
        if self.value is not None:
            t.set(np.asarray(self.value))
        # writes through: scope var now holds the LoDTensor wrapper
        self.value = t
        return t

    def set_value(self, value):
        self.value = value


class Scope(object):
    """Name -> ``_ScopeVar`` cell, with a parent to fall back on.

    ``_structure`` counts what changes WHICH cell a name means here:
    ``var`` creating a name (in a kid scope that shadows the parent's)
    and ``erase`` removing one. Setting a cell's value is not counted.
    Whoever keeps the cells ``find_var`` gave it (the executor's
    resolved-argument record) keeps ``structure_stamp()`` beside them:
    while the stamp reads the same, every name still means the cell it
    meant."""

    def __init__(self, parent=None):
        self._vars = {}
        self._parent = parent
        self._kids = []
        self._lock = threading.Lock()
        self._structure = 0

    def var(self, name):
        with self._lock:
            v = self._vars.get(name)
            if v is None:
                v = self._vars[name] = _ScopeVar(name)
                self._structure += 1
            return v

    def find_var(self, name):
        s = self
        while s is not None:
            v = s._vars.get(name)
            if v is not None:
                return v
            s = s._parent
        return None

    def find_local_var(self, name):
        """This scope's own cell of ``name`` (what ``set`` writes), or
        None where the name is the parent's or nobody's."""
        return self._vars.get(name)

    def structure_stamp(self):
        """The structure counters of this scope and of every scope
        ``find_var`` falls back on, outermost last."""
        stamp = [self._structure]
        s = self._parent
        while s is not None:
            stamp.append(s._structure)
            s = s._parent
        return tuple(stamp)

    def erase(self, names):
        with self._lock:
            for n in names:
                if self._vars.pop(n, None) is not None:
                    self._structure += 1

    def new_scope(self):
        kid = Scope(parent=self)
        self._kids.append(kid)
        return kid

    def drop_kids(self):
        self._kids = []

    def local_var_names(self):
        return list(self._vars)

    # -- convenience used by the executor --
    def get(self, name, default=None):
        v = self.find_var(name)
        return default if v is None else v.value

    def set(self, name, value):
        self.var(name).set_value(value)

    def has(self, name):
        return self.find_var(name) is not None


_global_scope = Scope()


def global_scope():
    return _global_scope


def _switch_scope(scope):
    global _global_scope
    old = _global_scope
    _global_scope = scope
    return old


# ---------------------------------------------------------------------------
# Flags — gflags-compatible registry lives in fluid/flags.py (reference:
# platform/flags.cc, python/paddle/fluid/__init__.py:162-210 env whitelist);
# these shims keep the core.* surface of the reference's pybind layer.
# ---------------------------------------------------------------------------


def globals_flags():
    from . import flags as _flags_mod

    return {"FLAGS_" + k: v for k, v in _flags_mod._flags.items()}


def get_flag(name):
    """Delegates to the gflags-compatible registry (fluid/flags.py)."""
    from . import flags as _flags_mod

    return _flags_mod.get_flag(name)


def set_flag(name, value):
    from . import flags as _flags_mod

    if not _flags_mod.is_registered(name):
        return  # unknown legacy flag names are accepted silently
    _flags_mod.set_flags({name: value})


def init_gflags(args):
    """reference: pybind.cc:1375 / framework::InitGflags — parse
    --FLAGS_x=y argv into the registry."""
    for a in args:
        a = a.lstrip("-")
        if "=" in a:
            k, v = a.split("=", 1)
            set_flag(k, v)


def init_glog(_prog):
    pass


def init_devices():
    pass
