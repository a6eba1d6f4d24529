"""chip_smoke.py rehearsed on the CPU: every phase runs at toy widths with
the Pallas kernels in interpret mode, the last line is well formed, and
the script never says "ok": true beside a platform other than tpu. Without
--rehearse and without a chip it runs nothing and fails."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(args, tmp_path):
    # the cache goes where the variable says: nothing this test compiles
    # lands in the checkout's own .jax_cache
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    return subprocess.run([sys.executable, SMOKE, *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=900)


@pytest.mark.parametrize("chips,phases", [
    (1, ["train", "serve", "resnet"]),
    (4, ["mesh_train", "mesh_serve"]),
])
def test_rehearsal_passes_every_phase(tmp_path, chips, phases):
    out = _run(["--rehearse", "--chips", str(chips)], tmp_path)
    assert out.returncode == 0, out.stdout + out.stderr[-4000:]
    lines = [json.loads(line) for line in out.stdout.splitlines()
             if line.startswith("{")]
    assert [line["phase"] for line in lines[:-1]] == phases
    assert all(line["ok"] for line in lines[:-1]), lines
    for line in lines[:-1]:
        assert {"device_kind", "compile_s", "peak_bytes_in_use",
                "native_lib"} <= set(line)
    last = json.loads(out.stdout.splitlines()[-1])
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] == chips
    # a rehearsal is not a chip run
    assert last["ok"] is False and last["rehearsal"] == "passed"
    assert os.listdir(str(tmp_path / "cache"))  # compiled into the named dir
    if chips == 1:
        serve = lines[1]
        assert serve["exact_tokens"] == serve["tokens"]  # CPU is bit-exact
        assert serve["steady_compiles"] == 0
    else:
        assert lines[0]["vars_split_4way"] > 0
        assert lines[1]["pools_split_4way"] > 0
        assert lines[1]["params_split_4way"] > 0


def test_without_a_chip_nothing_runs_and_the_exit_code_says_so(tmp_path):
    out = _run([], tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""  # no phase line, no result line
    assert "not a TPU" in out.stderr
