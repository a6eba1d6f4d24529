"""A Place names a device or raises; entry points given no place follow
jax's default backend; kernel-or-reference is decided by the backend the
trace lowers FOR, not by jax.default_backend(); the compile cache is
placed by one helper."""

import importlib
import os

import jax
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import compile_cache, inference
from paddle_tpu.fluid import core, io_pipeline
from paddle_tpu.fluid.ops.registry import lowering_on
from paddle_tpu.models import gpt
from paddle_tpu.serving.decode import DecodeEngine

fa = importlib.import_module("paddle_tpu.kernels.flash_attention")


def _tiny_cfg():
    return gpt.GPTConfig.tiny(hidden_dropout=0.0, attention_dropout=0.0)


def test_tpu_place_without_a_tpu_raises_everywhere():
    place = fluid.TPUPlace(0)
    with pytest.raises(RuntimeError, match="no 'tpu' platform"):
        core.get_jax_device(place)
    with pytest.raises(RuntimeError, match="no 'tpu' platform"):
        fluid.Executor(place)
    with pytest.raises(RuntimeError, match="no 'tpu' platform"):
        DecodeEngine(_tiny_cfg(), place=place)
    # an explicit place no longer degrades to host batches in silence
    with pytest.raises(RuntimeError, match="no 'tpu' platform"):
        io_pipeline.resolve_device(place)
    assert io_pipeline.resolve_device(None) is None
    assert core.get_tpu_device_count() == 0


def test_tpu_place_index_names_that_device_or_raises(monkeypatch):
    two = jax.devices("cpu")[:2]
    monkeypatch.setattr(
        jax, "local_devices",
        lambda process_index=None, backend=None, host_id=None: list(two),
    )
    assert core.get_jax_device(fluid.TPUPlace(1)) is two[1]
    for idx in (2, 3, -1):  # no wrap-around onto a chip that was not named
        with pytest.raises(ValueError, match="out of range"):
            core.get_jax_device(fluid.TPUPlace(idx))


def test_no_place_follows_the_default_backend(monkeypatch, tmp_path):
    # pinned to the CPU (JAX_PLATFORMS=cpu): engine and predictor take it
    assert core.default_place() == fluid.CPUPlace()
    assert DecodeEngine(_tiny_cfg())._place == fluid.CPUPlace()
    config = inference.AnalysisConfig(str(tmp_path))
    assert config._place() == fluid.CPUPlace() and not config.use_gpu()
    # a process whose default backend is the TPU gets chip 0 ...
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert core.default_place() == fluid.TPUPlace(0)
    assert config._place() == fluid.TPUPlace(0) and config.use_gpu()
    # ... and here, where there is none behind the name, that raises
    with pytest.raises(RuntimeError, match="no 'tpu' platform"):
        DecodeEngine(_tiny_cfg())


def test_config_that_asks_for_the_tpu_and_finds_none_raises(tmp_path):
    config = inference.AnalysisConfig(str(tmp_path))
    config.enable_use_gpu(device_id=0)
    with pytest.raises(RuntimeError, match="no 'tpu' platform"):
        inference.create_paddle_predictor(config)
    config.disable_gpu()
    assert config._place() == fluid.CPUPlace()


def test_lowering_backend_decides_kernel_or_reference(monkeypatch):
    # outside any trace the process default is the only target there is
    assert fa.lowers_for_tpu() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert fa.lowers_for_tpu() is True
    # inside a trace the executor's Place decides, whatever jax defaults to
    with lowering_on("cpu"):
        assert fa.lowers_for_tpu() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    with lowering_on("tpu"):
        assert fa.lowers_for_tpu() is True
        q = np.ones((1, 2, 8, 8), np.float32)
        # lowering for the TPU means the kernel or an error — never a
        # quiet fall-back to the dense reference
        with pytest.raises(Exception):
            jax.block_until_ready(fa.flash_attention(q, q, q))
    assert fa.lowers_for_tpu() is False


def test_cpu_place_program_with_flash_takes_the_reference(monkeypatch):
    """On a chip host jax.default_backend() is "tpu" for every program; a
    CPUPlace program must still lower flash attention to the jnp
    reference (a Mosaic kernel cannot run in a CPU computation)."""
    from paddle_tpu.observability import xla_stats

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        q = fluid.layers.data(name="q", shape=[2, 16, 8], dtype="float32")
        out = fluid.layers.flash_attention(q, q, q, causal=True)
    rs = np.random.RandomState(0)
    x = rs.randn(2, 2, 16, 8).astype("float32")
    (got,) = fluid.Executor(fluid.CPUPlace()).run(
        main, feed={"q": x}, fetch_list=[out], scope=fluid.core.Scope())
    want = fa.reference_attention(x, x, x, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    label = xla_stats.program_label(main)
    (record,) = [r for r in xla_stats.get_records()
                 if r["kind"] == "compile" and r["key"]["program"] == label]
    assert record["census"]["pallas_calls"] == 0


@pytest.mark.parametrize("env_dir", ["/some/dir", None])
def test_compile_cache_helper_places_the_cache(monkeypatch, env_dir):
    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.__setitem__(name, value))
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    used = compile_cache.enable()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if env_dir:
        # jax reads the variable itself: no directory is set in code
        assert used == env_dir
        assert "jax_compilation_cache_dir" not in updates
    else:
        assert used == os.path.join(repo, ".jax_cache")
        assert updates["jax_compilation_cache_dir"] == used
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0
