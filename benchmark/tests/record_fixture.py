#!/usr/bin/env python3
"""Records the small profiler trace the tests of the trace reduction read
(``tests/data/small.xplane.pb``): a few steps of a two-op jitted program
on one TPU chip. Run on the chip; writes to ``chiprun_out/``."""

import glob
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp


def main():
    @jax.jit
    def step(x, w):
        return jnp.tanh(x @ w) * 0.5

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    w = jnp.ones((1024, 1024), jnp.bfloat16)
    step(x, w).block_until_ready()
    tmp = tempfile.mkdtemp(prefix="fixture_")
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench_anchor"):
        time.perf_counter()
    for _ in range(5):
        with jax.profiler.TraceAnnotation("bench_step"):
            x = step(x, w)
            x.block_until_ready()
        time.sleep(0.002)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    os.makedirs("chiprun_out", exist_ok=True)
    shutil.copy(path, "chiprun_out/small.xplane.pb")
    print("wrote chiprun_out/small.xplane.pb", os.path.getsize(path))


if __name__ == "__main__":
    main()
