"""NHWC internal conv layout (TPU fast path) must match the NCHW lowering
bit-for-bit in semantics — forward and gradients — since it is a pure
layout change (reference conv semantics: paddle/fluid/operators/conv_op.cc;
data_format handling in conv_cudnn_op.cu)."""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid.ops import nn_ops


def _run_conv_train(seed=7):
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[3, 16, 16], dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        conv = fluid.layers.conv2d(
            img,
            num_filters=8,
            filter_size=3,
            stride=2,
            padding=1,
            param_attr=fluid.ParamAttr(
                name="cw",
                initializer=fluid.initializer.UniformInitializer(
                    low=-0.1, high=0.1, seed=seed
                ),
            ),
            act="relu",
        )
        dw = fluid.layers.conv2d(
            conv,
            num_filters=8,
            filter_size=3,
            padding=1,
            groups=8,
            param_attr=fluid.ParamAttr(
                name="dw",
                initializer=fluid.initializer.UniformInitializer(
                    low=-0.1, high=0.1, seed=seed + 1
                ),
            ),
        )
        pool = fluid.layers.pool2d(dw, pool_size=2, pool_type="avg", pool_stride=2)
        fc = fluid.layers.fc(
            pool,
            size=10,
            param_attr=fluid.ParamAttr(
                name="fcw",
                initializer=fluid.initializer.UniformInitializer(
                    low=-0.1, high=0.1, seed=seed + 2
                ),
            ),
        )
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(fc, label)
        )
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    rs = np.random.RandomState(0)
    img_v = rs.rand(4, 3, 16, 16).astype("float32")
    label_v = rs.randint(0, 10, (4, 1)).astype("int64")
    losses = []
    for _ in range(3):
        (l,) = exe.run(
            main, feed={"img": img_v, "label": label_v}, fetch_list=[loss]
        )
        losses.append(float(np.asarray(l).ravel()[0]))
    scope = fluid.global_scope()
    w = np.asarray(scope.find_var("cw").get_tensor())
    return losses, w


def test_conv_nhwc_matches_nchw(monkeypatch):
    with fluid.scope_guard(fluid.Scope()):
        base_losses, base_w = _run_conv_train()
    monkeypatch.setattr(nn_ops, "_use_nhwc", lambda: True)
    with fluid.scope_guard(fluid.Scope()):
        nhwc_losses, nhwc_w = _run_conv_train()
    np.testing.assert_allclose(base_losses, nhwc_losses, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(base_w, nhwc_w, rtol=1e-5, atol=1e-6)
    assert base_losses[-1] < base_losses[0]  # it actually trains


def test_use_nhwc_flag_gate():
    from paddle_tpu.fluid import flags
    from paddle_tpu.fluid.ops.registry import lowering_on

    try:
        with lowering_on("tpu"):
            assert nn_ops._use_nhwc()
            flags.set_flags({"FLAGS_conv_nhwc": False})
            assert not nn_ops._use_nhwc()
            flags.set_flags({"FLAGS_conv_nhwc": True})
        with lowering_on("cpu"):
            assert not nn_ops._use_nhwc()
        assert not nn_ops._use_nhwc()  # outside any trace
    finally:
        flags.set_flags({"FLAGS_conv_nhwc": True})
