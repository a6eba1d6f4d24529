"""The save / load host ops stream a tensor: the head, then the array's
own bytes, and back into the array they stay in. The file is, byte for
byte, what ``serialize_lod_tensor`` gives (the reference's tensor stream);
``deserialize_lod_tensor`` reads what the ops write and the ops read what
it is given."""

import io
import os

import ml_dtypes
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import core
from paddle_tpu.fluid.ops import io_ops

_RS = np.random.RandomState(0)


def _lod_tensor():
    t = core.LoDTensor(_RS.rand(5, 2).astype(np.float32))
    t.set_lod([[0, 2, 5], [0, 1, 2, 3, 4, 5]])
    return t


CASES = {
    "float32": np.arange(12, dtype=np.float32).reshape(3, 4),
    "float64": _RS.rand(5, 2),
    "rank0": np.array(3.14, np.float32),
    "empty": np.zeros((0, 4), np.float32),
    "bfloat16": _RS.rand(7, 3).astype(ml_dtypes.bfloat16),
    "bool": np.array([True, False, True]),
    "int64": _RS.randint(0, 9, (4, 5)).astype(np.int64),
    "fortran_order": np.asfortranarray(_RS.rand(4, 6).astype(np.float32)),
    "strided": _RS.rand(6, 4).astype(np.float16)[::2],
    "lod": _lod_tensor(),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_streamed_bytes_are_the_serializers(name):
    value = CASES[name]
    want = io_ops.serialize_lod_tensor(value)
    got = b"".join(bytes(c) for c in io_ops._stream_chunks(value))
    assert got == want


@pytest.mark.parametrize("name", sorted(CASES))
def test_read_stream_is_deserialize(name):
    stream = io_ops.serialize_lod_tensor(CASES[name])
    f = io.BytesIO(stream + b"the next tensor")
    got = io_ops._read_stream(f)
    want, pos = io_ops.deserialize_lod_tensor(stream)
    assert f.tell() == pos == len(stream)
    assert got.lod() == want.lod()
    a, b = got.numpy(), want.numpy()
    assert (a.dtype, a.shape) == (b.dtype, b.shape)
    assert a.tobytes() == b.tobytes()
    assert a.flags.writeable


@pytest.mark.parametrize("cut", [0, 5, 20, -3])
def test_a_cut_stream_is_refused(cut):
    stream = io_ops.serialize_lod_tensor(CASES["float32"])
    with pytest.raises(ValueError, match="malformed tensor stream"):
        io_ops._read_stream(io.BytesIO(stream[:cut]))


@pytest.mark.parametrize("filename", [None, "all_in_one"])
def test_save_and_load_ops_round_trip_bfloat16(tmp_path, filename):
    main = fluid.Program()
    block = main.global_block()
    values = {"w_bf16": CASES["bfloat16"], "w_f32": CASES["float32"],
              "w_rank0": CASES["rank0"]}
    for name, v in values.items():
        block.create_var(name=name, shape=v.shape, dtype=v.dtype,
                         persistable=True)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        for name, v in values.items():
            scope.set(name, v)
        fluid.io.save_persistables(exe, str(tmp_path), main, filename)
        assert [n for n in os.listdir(tmp_path) if ".tmp." in n] == []
        if filename is None:
            with open(tmp_path / "w_bf16", "rb") as f:
                assert f.read() == io_ops.serialize_lod_tensor(
                    values["w_bf16"])
        for name, v in values.items():
            scope.set(name, np.zeros_like(v))
        fluid.io.load_persistables(exe, str(tmp_path), main, filename)
        for name, v in values.items():
            got = np.asarray(scope.get(name))
            assert (got.dtype, got.shape) == (v.dtype, v.shape)
            assert got.tobytes() == v.tobytes()
