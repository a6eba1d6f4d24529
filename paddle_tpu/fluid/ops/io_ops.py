"""Host-side save/load ops with the reference's binary tensor stream format.

Reference: paddle/fluid/operators/save_op.cc:25, load_op.cc,
save_combine_op.cc, load_combine_op.cc; serialization in
framework/tensor_util.cc TensorToStream / TensorFromStream:

    LoDTensor stream := uint32 version(0)
                        uint64 lod_level
                        { uint64 nbytes, size_t[] offsets } * lod_level
                        uint32 version(0)
                        int32  desc_size
                        VarType.TensorDesc proto (data_type=1, dims=2 packed)
                        raw tensor bytes

These are host ops: they split the XLA segment and read/write the Scope.
"""

from __future__ import annotations

import os
import struct

import numpy as np

from .. import core
from .registry import register_op

_NP_TO_PROTO = {
    np.dtype(np.bool_): core.VarDesc.VarType.BOOL,
    np.dtype(np.int16): core.VarDesc.VarType.INT16,
    np.dtype(np.int32): core.VarDesc.VarType.INT32,
    np.dtype(np.int64): core.VarDesc.VarType.INT64,
    np.dtype(np.float16): core.VarDesc.VarType.FP16,
    np.dtype(np.float32): core.VarDesc.VarType.FP32,
    np.dtype(np.float64): core.VarDesc.VarType.FP64,
    np.dtype(np.uint8): core.VarDesc.VarType.UINT8,
    np.dtype(np.int8): core.VarDesc.VarType.INT8,
    np.dtype(core.dtype_to_np("bfloat16")): core.VarDesc.VarType.BF16,
}
_PROTO_TO_NP = {v: k for k, v in _NP_TO_PROTO.items()}


def _encode_varint(value):
    out = b""
    while True:
        bits = value & 0x7F
        value >>= 7
        if value:
            out += bytes([bits | 0x80])
        else:
            out += bytes([bits])
            return out


def _decode_varint(buf, pos):
    result = 0
    shift = 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not (b & 0x80):
            return result, pos
        shift += 7


def _tensor_desc_bytes(arr):
    """VarType.TensorDesc{ data_type=1 (enum), dims=2 (packed int64) }."""
    dtype_enum = _NP_TO_PROTO[np.dtype(arr.dtype)]
    out = bytes([0x08]) + _encode_varint(dtype_enum)  # field 1, varint
    dims_payload = b"".join(_encode_varint(int(d)) for d in arr.shape)
    out += bytes([0x12]) + _encode_varint(len(dims_payload)) + dims_payload
    return out


def _parse_tensor_desc(buf):
    pos = 0
    dtype_enum = None
    dims = []
    while pos < len(buf):
        tag, pos = _decode_varint(buf, pos)
        field, wire = tag >> 3, tag & 7
        if field == 1 and wire == 0:
            dtype_enum, pos = _decode_varint(buf, pos)
        elif field == 2 and wire == 2:
            ln, pos = _decode_varint(buf, pos)
            end = pos + ln
            while pos < end:
                d, pos = _decode_varint(buf, pos)
                dims.append(d)
        elif field == 2 and wire == 0:  # unpacked fallback
            d, pos = _decode_varint(buf, pos)
            dims.append(d)
        else:
            raise ValueError("unexpected TensorDesc field %d" % field)
    return _PROTO_TO_NP[dtype_enum], dims


def serialize_lod_tensor(value):
    if isinstance(value, core.LoDTensor):
        arr = value.numpy()
        lod = value.lod()
    else:
        arr = np.asarray(value)
        lod = []
    from .. import native

    if native.available() and np.dtype(arr.dtype) in _NP_TO_PROTO:
        return native.serialize_tensor(arr, lod)
    return _serialize_lod_tensor_py(arr, lod)


def _stream_head(arr, lod):
    """Everything of a tensor stream before the raw tensor bytes."""
    out = struct.pack("<I", 0)  # version
    out += struct.pack("<Q", len(lod))
    for level in lod:
        level_arr = np.asarray(level, np.uint64)
        out += struct.pack("<Q", level_arr.nbytes)
        out += level_arr.tobytes()
    out += struct.pack("<I", 0)  # tensor version
    desc = _tensor_desc_bytes(arr)
    out += struct.pack("<i", len(desc))
    out += desc
    return out


def _serialize_lod_tensor_py(arr, lod):
    return _stream_head(arr, lod) + np.ascontiguousarray(arr).tobytes()


def _stream_chunks(value):
    """``serialize_lod_tensor(value)`` as chunks for
    ``_atomic_write_stream``: the head, then the array's own buffer. A
    served model's parameters are gigabytes, and the serializers above
    copy what they are given two to four times before a byte is written
    (10.3 GB took 36 s of a 151 s export; PERF.md, Findings PR 42)."""
    if isinstance(value, core.LoDTensor):
        arr, lod = value.numpy(), value.lod()
    else:
        arr, lod = np.asarray(value), []
    arr = np.asarray(arr, order="C")
    yield _stream_head(arr, lod)
    yield arr.reshape(-1).view(np.uint8)


def deserialize_lod_tensor(buf, pos=0):
    from .. import native

    if native.available():
        arr, lod, consumed = native.deserialize_tensor(buf, pos)
        t = core.LoDTensor(arr)
        t.set_lod(lod)
        return t, pos + consumed
    return _deserialize_lod_tensor_py(buf, pos)


def _deserialize_lod_tensor_py(buf, pos=0):
    (version,) = struct.unpack_from("<I", buf, pos)
    pos += 4
    assert version == 0, "unsupported tensor stream version %d" % version
    (lod_level,) = struct.unpack_from("<Q", buf, pos)
    pos += 8
    lod = []
    for _ in range(lod_level):
        (nbytes,) = struct.unpack_from("<Q", buf, pos)
        pos += 8
        level = np.frombuffer(buf, np.uint64, int(nbytes) // 8, pos)
        pos += int(nbytes)
        lod.append([int(x) for x in level])
    (tversion,) = struct.unpack_from("<I", buf, pos)
    pos += 4
    assert tversion == 0
    (desc_size,) = struct.unpack_from("<i", buf, pos)
    pos += 4
    np_dtype, dims = _parse_tensor_desc(buf[pos : pos + desc_size])
    pos += desc_size
    count = int(np.prod(dims)) if dims else 1
    arr = np.frombuffer(buf, np_dtype, count, pos).reshape(dims)
    pos += arr.nbytes
    t = core.LoDTensor(arr.copy())
    t.set_lod(lod)
    return t, pos


def _read_stream(f):
    """One tensor stream off an open file -> LoDTensor, the tensor bytes
    read straight into the array they stay in (``deserialize_lod_tensor``
    wants the whole file in memory and copies the tensor out of it up to
    three times)."""
    def take(fmt):
        size = struct.calcsize(fmt)
        got = f.read(size)
        if len(got) != size:
            raise ValueError("malformed tensor stream")
        return struct.unpack(fmt, got)

    version, lod_level = take("<IQ")
    if version != 0:
        raise ValueError("unsupported tensor stream version %d" % version)
    lod = []
    for _ in range(lod_level):
        (nbytes,) = take("<Q")
        lod.append([int(x) for x in take("<%dQ" % (nbytes // 8))])
    tversion, desc_size = take("<Ii")
    if tversion != 0:
        raise ValueError("unsupported tensor version %d" % tversion)
    desc = f.read(desc_size)
    if len(desc) != desc_size:
        raise ValueError("malformed tensor stream")
    np_dtype, dims = _parse_tensor_desc(desc)
    arr = np.empty(dims, np_dtype)
    into, at = memoryview(arr.reshape(-1).view(np.uint8)), 0
    while at < len(into):
        n = f.readinto(into[at:])
        if not n:
            raise ValueError("malformed tensor stream")
        at += n
    t = core.LoDTensor(arr)
    t.set_lod(lod)
    return t


# -- host op implementations -------------------------------------------------
def _ensure_dir(path):
    d = os.path.dirname(path)
    if d and not os.path.isdir(d):
        os.makedirs(d, exist_ok=True)


def _atomic_write(path, data):
    """Same-dir temp + fsync + os.replace so a SIGKILL mid-save never
    leaves a torn tensor file at the real path (save_op.cc wrote in
    place; paddle_tpu/checkpoint's atomic-commit contract extends down
    to these raw save ops too)."""
    _atomic_write_stream(path, (data,))


def _atomic_write_stream(path, chunks):
    """Atomic write fed chunk-by-chunk (a generator is fine): a combined
    multi-GB params file streams tensor-by-tensor instead of holding the
    whole payload in host RAM. A failure mid-stream removes the temp."""
    tmp = "%s.tmp.%d" % (path, os.getpid())
    try:
        with open(tmp, "wb") as f:
            for chunk in chunks:
                f.write(chunk)
            f.flush()
            os.fsync(f.fileno())
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    os.replace(tmp, path)


def _save_lower(ctx, op_):
    name = op_.input("X")[0]
    value = ctx.scope.get(name)
    if value is None:
        raise ValueError("save: variable %r not found in scope" % name)
    path = op_.attr("file_path")
    _ensure_dir(path)
    _atomic_write_stream(path, _stream_chunks(_to_host(value)))


def _load_lower(ctx, op_):
    name = op_.output("Out")[0]
    path = op_.attr("file_path")
    with open(path, "rb") as f:
        t = _read_stream(f)
    ctx.scope.set(name, t.numpy() if not t.lod() else t)


def _save_combine_lower(ctx, op_):
    names = op_.input("X")
    path = op_.attr("file_path")
    _ensure_dir(path)
    values = []
    for n in names:  # validate everything BEFORE the temp file opens
        value = ctx.scope.get(n)
        if value is None:
            raise ValueError("save_combine: %r not in scope" % n)
        values.append(value)
    _atomic_write_stream(
        path, (c for v in values for c in _stream_chunks(_to_host(v)))
    )


def _load_combine_lower(ctx, op_):
    names = op_.output("Out")
    path = op_.attr("file_path")
    with open(path, "rb") as f:
        for n in names:
            t = _read_stream(f)
            ctx.scope.set(n, t.numpy() if not t.lod() else t)


def _to_host(value):
    if isinstance(value, core.LoDTensor):
        return value
    return np.asarray(value)


register_op("save", lower=_save_lower, host=True)
register_op("load", lower=_load_lower, host=True)
register_op("save_combine", lower=_save_combine_lower, host=True)
register_op("load_combine", lower=_load_combine_lower, host=True)


def _print_lower(ctx, op_):
    name = op_.input("In")[0] if op_.input("In") else op_.input("X")[0]
    value = ctx.scope.get(name)
    phase = op_.attr("print_phase", "both") or "both"
    is_grad = bool(op_.attr("is_grad_print", False))
    # phase gate: the forward instance prints activations, the grad
    # instance (emitted by the grad maker) prints gradients
    should = phase == "both" or phase == ("backward" if is_grad else "forward")
    first_n = int(op_.attr("first_n", -1))
    if should and first_n >= 0:
        # counter lives ON the op object: no global dict to leak, and a
        # recycled id() can never inherit another op's budget
        seen = getattr(op_, "_print_seen", 0)
        op_._print_seen = seen + 1
        should = seen < first_n
    if should:
        message = op_.attr("message", "")
        summarize = int(op_.attr("summarize", 20))
        arr = np.asarray(value)
        shown = arr.ravel()[:summarize] if summarize >= 0 else arr
        parts = [message] if message else []
        if is_grad:
            parts.append("(grad)")
        if op_.attr("print_tensor_name", True):
            parts.append(name)
        if op_.attr("print_tensor_type", True):
            parts.append(str(arr.dtype))
        if op_.attr("print_tensor_shape", True):
            parts.append(str(list(arr.shape)))
        parts.append(str(shown))
        print(" ".join(parts))
    out_names = op_.output("Out")
    if out_names:
        ctx.scope.set(out_names[0], value)


def _print_grad_maker(op_):
    """The grad of print is another print (reference: print_op.cc
    PrintOpGradientMaker): it forwards the gradient unchanged (identity)
    and prints it when print_phase is 'backward'/'both'."""
    outs = op_.output("Out")
    ins = op_.input("In") or op_.input("X")  # legacy 'X'-slot programs
    if not outs or not ins:
        return []
    attrs = dict(op_.attrs)
    attrs["is_grad_print"] = True
    return [dict(
        type="print",
        inputs={"In": [outs[0] + "@GRAD"]},
        outputs={"Out": [ins[0] + "@GRAD"]},
        attrs=attrs,
    )]


register_op("print", lower=_print_lower, host=True,
            grad=_print_grad_maker)


def _feed_noop(ctx, op_):
    pass


def _fetch_noop(ctx, op_):
    name = op_.input("X")[0]
    out = op_.output("Out")
    if out:
        v = ctx.scope.get(name)
        ctx.scope.set(out[0], v)


register_op("feed", lower=_feed_noop, host=True)
register_op("fetch", lower=_fetch_noop, host=True)
