"""Python side of the C API (paddle_tpu_c_api.cpp calls into this).

Holds (program, scope, executor, loss) sessions in a registry keyed by
handle; the C side only moves primitive buffers across the boundary."""

from __future__ import annotations

import os

# sessions run on CPUPlace: an embedding C host stays off the chip (which
# one process owns at a time) unless it exported a platform choice itself
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

_sessions = {}
_next = [1]


def _register(entry):
    h = _next[0]
    _next[0] += 1
    _sessions[h] = entry
    return h


def demo_program():
    """The reference train/demo program: linear regression + SGD
    (paddle/fluid/train/demo/demo_trainer.cc builds it from a saved model;
    here it is built directly so the demo is self-contained)."""
    import paddle_tpu.fluid as fluid

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[13], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(input=x, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.01).minimize(
            loss, startup_program=startup
        )
    scope = fluid.core.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    return _register(
        dict(exe=exe, program=main, scope=scope, fetch=loss)
    )


def load_program(path, kind):
    import paddle_tpu.fluid as fluid

    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    if kind == 1:
        program, feed_names, fetch_vars = fluid.io.load_inference_model(
            path, exe
        )
        fetch = fetch_vars[0]
    elif kind == 0:
        # a consolidated fluid.io.save(program, path) bundle:
        # path.pdmodel (program) + path.pdparams/.pdopt (state)
        from paddle_tpu.fluid import proto

        with open(path + ".pdmodel", "rb") as f:
            program = proto.program_from_bytes(f.read())
        # io.load restores into the global scope; run this session there
        scope = fluid.global_scope()
        fluid.io.load(program, path, exe)
        # first fetchable loss-like var: last mean output, else last var
        fetch = None
        for op_ in program.global_block().ops:
            if op_.type == "mean":
                fetch = program.global_block().vars[
                    op_.output("Out")[0]
                ]
        if fetch is None:
            raise ValueError("no loss (mean) op found in saved program")
    else:
        raise ValueError("unknown kind=%d" % kind)
    return _register(
        dict(exe=exe, program=program, scope=scope, fetch=fetch)
    )


def run_step(handle, feeds):
    s = _sessions[int(handle)]
    feed = {}
    for name, (buf, shape) in feeds.items():
        feed[name] = np.frombuffer(buf, np.float32).reshape(
            [int(v) for v in shape]
        ).copy()
    outs = s["exe"].run(
        s["program"], feed=feed, fetch_list=[s["fetch"]], scope=s["scope"]
    )
    return float(np.asarray(outs[0]).ravel()[0])
