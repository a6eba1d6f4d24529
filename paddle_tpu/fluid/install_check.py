"""Installation self-check (reference:
python/paddle/fluid/install_check.py:45 run_check — build and run a tiny
fc model single-device and data-parallel, confirming the install works).

TPU-native: the single-device pass runs on the default place (the TPU
chip when visible, CPU otherwise); the parallel pass runs the same model
through CompiledProgram.with_data_parallel over the available devices.
"""

from __future__ import annotations

import logging

import numpy as np

__all__ = ["run_check"]


def run_check():
    """Verify the installation by training one step of a tiny fc model,
    single-device and data-parallel. Prints the reference's success
    message on completion."""
    print("Running Verify Fluid Program ... ")
    from . import core
    from . import layers
    from . import optimizer as opt_mod
    from .compiler import CompiledProgram
    from .executor import Executor, scope_guard
    from .framework import Program, program_guard
    from . import unique_name

    place = core.default_place()
    np_inp = np.array([[1.0, 2.0], [3.0, 4.0]], dtype=np.float32)

    def build():
        main, startup = Program(), Program()
        main.random_seed = startup.random_seed = 1
        with unique_name.guard(), program_guard(main, startup):
            inp = layers.data(name="inp", shape=[2], dtype="float32")
            fc = layers.fc(input=inp, size=3)
            loss = layers.reduce_sum(fc)
            opt_mod.SGD(learning_rate=0.01).minimize(
                loss, startup_program=startup
            )
        return main, startup, loss

    # single-device step
    main, startup, loss = build()
    exe = Executor(place)
    scope = core.Scope()
    with scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed={"inp": np_inp}, fetch_list=[loss])

    # data-parallel step (2 logical devices minimum)
    try:
        main, startup, loss = build()
        scope = core.Scope()
        with scope_guard(scope):
            exe.run(startup)
            compiled = CompiledProgram(main).with_data_parallel(
                loss_name=loss.name
            )
            import jax

            n = max(jax.local_device_count(), 1)
            batch = np.repeat(np_inp, max(n // 2, 1), axis=0)
            exe.run(compiled, feed={"inp": batch}, fetch_list=[loss])
        print(
            "Your Paddle Fluid works well on MUTIPLE GPU or CPU.\n"
            "Your Paddle Fluid is installed successfully! Let's start deep "
            "Learning with Paddle Fluid now"
        )
    except Exception as e:  # noqa: BLE001 - mirror the reference's fallback
        logging.warning(
            "Your Paddle Fluid has some problem with multiple devices(%s). "
            "The single-device check passed, so the install itself works."
            % e
        )
        print(
            "Your Paddle Fluid works well on SINGLE GPU or CPU.\n"
            "Your Paddle Fluid is installed successfully! Let's start deep "
            "Learning with Paddle Fluid now"
        )
    return 0
