"""NN ops: convolution, pooling, batch norm, dropout, interpolation.

Reference kernels: paddle/fluid/operators/conv_op.cc (+conv_cudnn_op.cu),
pool_op.cc, batch_norm_op.cc, dropout_op.cc, conv_transpose_op.cc.
On TPU these lower to lax.conv_general_dilated / lax.reduce_window, which XLA
maps onto the MXU; layout stays NCHW at the API level (the contract) and XLA
picks the internal tiling.
"""

from __future__ import annotations

import numpy as np

from .. import core
from .registry import (
    SkipInferShape,
    in_var,
    op,
    register_op,
    same_shape_infer,
    set_out,
)


def _pair(v):
    if isinstance(v, (list, tuple)):
        return [int(x) for x in v]
    return [int(v), int(v)]


def _conv_out_dim(size, k, pad, stride, dilation=1):
    if size < 0:
        return -1
    eff = dilation * (k - 1) + 1
    return (size + 2 * pad - eff) // stride + 1


# ---------------------------------------------------------------------------
# conv2d / depthwise_conv2d
# ---------------------------------------------------------------------------
def _conv2d_infer(op_, block):
    x = in_var(op_, block, "Input")
    w = in_var(op_, block, "Filter")
    if x is None or w is None or len(x.shape) != 4:
        raise SkipInferShape()
    strides = _pair(op_.attr("strides", [1, 1]))
    pads = _pair(op_.attr("paddings", [0, 0]))
    dil = _pair(op_.attr("dilations", [1, 1]))
    n, _, h, wd = x.shape
    oc, _, kh, kw = w.shape
    set_out(
        op_,
        block,
        "Output",
        (
            n,
            oc,
            _conv_out_dim(h, kh, pads[0], strides[0], dil[0]),
            _conv_out_dim(wd, kw, pads[1], strides[1], dil[1]),
        ),
        x.dtype,
    )


def _use_nhwc():
    """NHWC internal conv layout on TPU: channels land on the lane (minor)
    dimension, which is what the MXU tiling wants — feeding NCHW makes XLA
    insert its own layout conversions around every conv. The API contract
    (Program-level shapes, feeds, saved weights) stays NCHW; transposes at
    the conv boundary are folded into XLA's layout assignment."""
    from .. import flags as _flags
    from .registry import lowering_backend

    return lowering_backend() == "tpu" and bool(
        _flags.get_flag("conv_nhwc", True)
    )


def _conv2d_lower(ctx, op_):
    import jax.lax as lax
    import jax.numpy as jnp

    x = ctx.in1(op_, "Input")
    w = ctx.in1(op_, "Filter")
    strides = _pair(op_.attr("strides", [1, 1]))
    pads = _pair(op_.attr("paddings", [0, 0]))
    dil = _pair(op_.attr("dilations", [1, 1]))
    groups = int(op_.attr("groups", 1)) or 1
    if op_.type == "depthwise_conv2d":
        groups = x.shape[1]
    if _use_nhwc():
        out = lax.conv_general_dilated(
            jnp.transpose(x, (0, 2, 3, 1)),
            jnp.transpose(w, (2, 3, 1, 0)),  # OIHW -> HWIO
            window_strides=strides,
            padding=[(pads[0], pads[0]), (pads[1], pads[1])],
            rhs_dilation=dil,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups,
            preferred_element_type=x.dtype,
        )
        out = jnp.transpose(out, (0, 3, 1, 2))
    else:
        out = lax.conv_general_dilated(
            x,
            w,
            window_strides=strides,
            padding=[(pads[0], pads[0]), (pads[1], pads[1])],
            rhs_dilation=dil,
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=groups,
            preferred_element_type=x.dtype,
        )
    ctx.out(op_, "Output", out)


register_op("conv2d", infer_shape=_conv2d_infer, lower=_conv2d_lower, grad="generic")
register_op(
    "depthwise_conv2d", infer_shape=_conv2d_infer, lower=_conv2d_lower, grad="generic"
)


def _conv2d_transpose_infer(op_, block):
    x = in_var(op_, block, "Input")
    w = in_var(op_, block, "Filter")
    if x is None or w is None or len(x.shape) != 4:
        raise SkipInferShape()
    strides = _pair(op_.attr("strides", [1, 1]))
    pads = _pair(op_.attr("paddings", [0, 0]))
    dil = _pair(op_.attr("dilations", [1, 1]))
    n, _, h, wd = x.shape
    _, oc_g, kh, kw = w.shape
    groups = int(op_.attr("groups", 1)) or 1
    oh = (h - 1) * strides[0] - 2 * pads[0] + dil[0] * (kh - 1) + 1 if h > 0 else -1
    ow = (wd - 1) * strides[1] - 2 * pads[1] + dil[1] * (kw - 1) + 1 if wd > 0 else -1
    set_out(op_, block, "Output", (n, oc_g * groups, oh, ow), x.dtype)


@op("conv2d_transpose", infer_shape=_conv2d_transpose_infer, grad="generic")
def _conv2d_transpose(ctx, op_):
    import jax.lax as lax

    x = ctx.in1(op_, "Input")
    w = ctx.in1(op_, "Filter")  # [in_c, out_c/groups, kh, kw]
    strides = _pair(op_.attr("strides", [1, 1]))
    pads = _pair(op_.attr("paddings", [0, 0]))
    dil = _pair(op_.attr("dilations", [1, 1]))
    groups = int(op_.attr("groups", 1)) or 1
    kh, kw = w.shape[2], w.shape[3]
    # transposed conv = lhs-dilated conv with flipped, transposed kernel
    pad_h = dil[0] * (kh - 1) - pads[0]
    pad_w = dil[1] * (kw - 1) - pads[1]
    w_t = np.flip if isinstance(w, np.ndarray) else None
    import jax.numpy as jnp

    wk = jnp.flip(w, axis=(2, 3))
    wk = jnp.swapaxes(wk, 0, 1)  # -> [out_c/groups, in_c, kh, kw]
    if groups > 1:
        # regroup: [g, oc/g, ic/g? ...] — reference groups conv_transpose rarely used
        ic = x.shape[1]
        wk = wk.reshape(groups, w.shape[1], ic // groups, kh, kw)
        wk = wk.reshape(groups * w.shape[1], ic // groups, kh, kw)
    out = lax.conv_general_dilated(
        x,
        wk,
        window_strides=(1, 1),
        padding=[(pad_h, pad_h), (pad_w, pad_w)],
        lhs_dilation=strides,
        rhs_dilation=dil,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=groups,
    )
    _ = w_t
    ctx.out(op_, "Output", out)


# ---------------------------------------------------------------------------
# pool2d
# ---------------------------------------------------------------------------
def _pool2d_infer(op_, block):
    x = in_var(op_, block, "X")
    if x is None or len(x.shape) != 4:
        raise SkipInferShape()
    n, c, h, w = x.shape
    if op_.attr("global_pooling", False) or op_.attr("adaptive", False) and _pair(op_.attr("ksize"))[0] == 1:
        set_out(op_, block, "Out", (n, c, 1, 1), x.dtype)
        return
    if op_.attr("adaptive", False):
        kh, kw = _pair(op_.attr("ksize"))
        set_out(op_, block, "Out", (n, c, kh, kw), x.dtype)
        return
    ksize = _pair(op_.attr("ksize"))
    strides = _pair(op_.attr("strides", [1, 1]))
    pads = _pair(op_.attr("paddings", [0, 0]))
    if op_.attr("ceil_mode", False):
        oh = -(-(h + 2 * pads[0] - ksize[0]) // strides[0]) + 1 if h > 0 else -1
        ow = -(-(w + 2 * pads[1] - ksize[1]) // strides[1]) + 1 if w > 0 else -1
    else:
        oh = (h + 2 * pads[0] - ksize[0]) // strides[0] + 1 if h > 0 else -1
        ow = (w + 2 * pads[1] - ksize[1]) // strides[1] + 1 if w > 0 else -1
    set_out(op_, block, "Out", (n, c, oh, ow), x.dtype)


@op("pool2d", infer_shape=_pool2d_infer, grad="generic")
def _pool2d(ctx, op_):
    import jax.lax as lax
    import jax.numpy as jnp

    x = ctx.in1(op_, "X")
    ptype = op_.attr("pooling_type", "max")
    if op_.attr("global_pooling", False):
        if ptype == "max":
            out = jnp.max(x, axis=(2, 3), keepdims=True)
        else:
            out = jnp.mean(x, axis=(2, 3), keepdims=True)
        ctx.out(op_, "Out", out)
        return
    if op_.attr("adaptive", False):
        kh, kw = _pair(op_.attr("ksize"))
        h, w = x.shape[2], x.shape[3]
        assert h % kh == 0 and w % kw == 0, (
            "adaptive pool requires divisible dims for static lowering"
        )
        xr = x.reshape(x.shape[0], x.shape[1], kh, h // kh, kw, w // kw)
        out = jnp.max(xr, axis=(3, 5)) if ptype == "max" else jnp.mean(xr, axis=(3, 5))
        ctx.out(op_, "Out", out)
        return
    ksize = _pair(op_.attr("ksize"))
    strides = _pair(op_.attr("strides", [1, 1]))
    pads = _pair(op_.attr("paddings", [0, 0]))
    dims = (1, 1, ksize[0], ksize[1])
    strd = (1, 1, strides[0], strides[1])
    padding = [(0, 0), (0, 0), (pads[0], pads[0]), (pads[1], pads[1])]
    if op_.attr("ceil_mode", False):
        h, w = x.shape[2], x.shape[3]
        oh = -(-(h + 2 * pads[0] - ksize[0]) // strides[0]) + 1
        ow = -(-(w + 2 * pads[1] - ksize[1]) // strides[1]) + 1
        need_h = (oh - 1) * strides[0] + ksize[0] - h - 2 * pads[0]
        need_w = (ow - 1) * strides[1] + ksize[1] - w - 2 * pads[1]
        padding = [
            (0, 0),
            (0, 0),
            (pads[0], pads[0] + max(need_h, 0)),
            (pads[1], pads[1] + max(need_w, 0)),
        ]
    if ptype == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        out = lax.reduce_window(
            x, np.asarray(init, x.dtype), lax.max, dims, strd, padding
        )
    else:
        ssum = lax.reduce_window(
            x, np.asarray(0, x.dtype), lax.add, dims, strd, padding
        )
        if op_.attr("exclusive", True):
            ones = jnp.ones_like(x)
            cnt = lax.reduce_window(
                ones, np.asarray(0, x.dtype), lax.add, dims, strd, padding
            )
            out = ssum / cnt
        else:
            out = ssum / float(ksize[0] * ksize[1])
    ctx.out(op_, "Out", out)


# ---------------------------------------------------------------------------
# batch_norm — mutates running Mean/Variance in place (outputs MeanOut/
# VarianceOut alias the input vars, as in the reference batch_norm_op.cc)
# ---------------------------------------------------------------------------
def _batch_norm_infer(op_, block):
    x = in_var(op_, block, "X")
    if x is None:
        raise SkipInferShape()
    set_out(op_, block, "Y", x.shape, x.dtype)
    c = x.shape[1] if len(x.shape) > 1 else x.shape[0]
    for slot in ("MeanOut", "VarianceOut", "SavedMean", "SavedVariance"):
        set_out(op_, block, slot, (c,), x.dtype)


@op("batch_norm", infer_shape=_batch_norm_infer, grad="generic",
    stateful_inputs=(("Mean", "MeanOut"), ("Variance", "VarianceOut")))
def _batch_norm(ctx, op_):
    import jax.numpy as jnp

    x = ctx.in1(op_, "X")
    scale = ctx.in1(op_, "Scale")
    bias = ctx.in1(op_, "Bias")
    mean = ctx.in1(op_, "Mean")
    var = ctx.in1(op_, "Variance")
    eps = float(op_.attr("epsilon", 1e-5))
    momentum = float(op_.attr("momentum", 0.9))
    is_test = bool(op_.attr("is_test", False))
    use_global = bool(op_.attr("use_global_stats", False)) or is_test
    layout = op_.attr("data_layout", "NCHW")
    ch_axis = 1 if layout == "NCHW" else x.ndim - 1
    axes = tuple(i for i in range(x.ndim) if i != ch_axis)
    bshape = tuple(x.shape[i] if i == ch_axis else 1 for i in range(x.ndim))

    # bf16-safe BN (the AMP gray-list contract): statistics accumulate in
    # fp32 (XLA fuses the upcast INTO the reduction — the [N,C,H,W]
    # activation never round-trips HBM in fp32), the normalize runs in the
    # input dtype so the whole conv-bn-relu chain stays bf16 on the MXU
    # path. State vars (Mean/Variance) keep their own (fp32) dtype.
    f32 = jnp.float32
    mean32 = mean.astype(f32)
    var32 = var.astype(f32)
    if use_global:
        use_mean, use_var = mean32, var32
        new_mean, new_var = mean32, var32
        saved_mean = jnp.zeros_like(mean32)
        saved_var = jnp.zeros_like(var32)
    else:
        bmean = jnp.mean(x, axis=axes, dtype=f32)
        bvar = jnp.mean(jnp.square(x.astype(f32)), axis=axes) - jnp.square(
            bmean
        )
        use_mean, use_var = bmean, bvar
        new_mean = mean32 * momentum + bmean * (1.0 - momentum)
        new_var = var32 * momentum + bvar * (1.0 - momentum)
        saved_mean = bmean
        saved_var = 1.0 / jnp.sqrt(bvar + eps)

    inv = 1.0 / jnp.sqrt(use_var + eps)
    # per-channel affine folded AND applied in fp32 (rounding g/b to bf16
    # before the multiply-add would inject an offset of up to ~|mean|/std
    # ulps per channel); only the final store drops to x.dtype — XLA fuses
    # this into one elementwise kernel with bf16-sized HBM traffic
    g = (scale.astype(f32) * inv).reshape(bshape)
    b = (bias.astype(f32) - scale.astype(f32) * use_mean * inv).reshape(bshape)
    y = (x.astype(f32) * g + b).astype(x.dtype)
    ctx.out(op_, "Y", y)
    ctx.out(op_, "MeanOut", new_mean.astype(mean.dtype))
    ctx.out(op_, "VarianceOut", new_var.astype(var.dtype))
    ctx.out(op_, "SavedMean", saved_mean)
    ctx.out(op_, "SavedVariance", saved_var)


@op("sync_batch_norm", infer_shape=_batch_norm_infer, grad="generic")
def _sync_batch_norm(ctx, op_):
    """Cross-replica batch norm: batch stats psum'd over the data axis
    (reference: operators/sync_batch_norm_op.cu — NCCL allreduce of
    sum/sum-of-squares; here lax.pmean over the mesh axis)."""
    import jax.lax as lax
    import jax.numpy as jnp

    axis = ctx.data_axis
    x = ctx.in1(op_, "X")
    scale = ctx.in1(op_, "Scale")
    bias = ctx.in1(op_, "Bias")
    mean = ctx.in1(op_, "Mean")
    var = ctx.in1(op_, "Variance")
    eps = float(op_.attr("epsilon", 1e-5))
    momentum = float(op_.attr("momentum", 0.9))
    is_test = bool(op_.attr("is_test", False))
    ch_axis = 1
    axes = tuple(i for i in range(x.ndim) if i != ch_axis)
    bshape = tuple(x.shape[i] if i == ch_axis else 1 for i in range(x.ndim))
    # same bf16-safe contract as _batch_norm: fp32 statistics (the
    # E[x^2]-E[x]^2 cancellation is catastrophic in bf16), fp32 affine,
    # output stored in x.dtype
    f32 = jnp.float32
    mean32, var32 = mean.astype(f32), var.astype(f32)
    if is_test:
        use_mean, use_var = mean32, var32
        new_mean, new_var = mean32, var32
        saved_mean = jnp.zeros_like(mean32)
        saved_var = jnp.zeros_like(var32)
    else:
        bmean = jnp.mean(x, axis=axes, dtype=f32)
        bsq = jnp.mean(jnp.square(x.astype(f32)), axis=axes)
        if axis is not None:
            bmean = lax.pmean(bmean, axis)
            bsq = lax.pmean(bsq, axis)
        bvar = bsq - jnp.square(bmean)
        use_mean, use_var = bmean, bvar
        new_mean = mean32 * momentum + bmean * (1.0 - momentum)
        new_var = var32 * momentum + bvar * (1.0 - momentum)
        saved_mean = bmean
        saved_var = 1.0 / jnp.sqrt(bvar + eps)
    inv = 1.0 / jnp.sqrt(use_var + eps)
    g = (scale.astype(f32) * inv).reshape(bshape)
    b = (bias.astype(f32) - scale.astype(f32) * use_mean * inv).reshape(bshape)
    y = (x.astype(f32) * g + b).astype(x.dtype)
    ctx.out(op_, "Y", y)
    ctx.out(op_, "MeanOut", new_mean.astype(mean.dtype))
    ctx.out(op_, "VarianceOut", new_var.astype(var.dtype))
    ctx.out(op_, "SavedMean", saved_mean)
    ctx.out(op_, "SavedVariance", saved_var)


def _instance_norm_like(ctx, op_, axes_fn):
    import jax.numpy as jnp

    x = ctx.in1(op_, "X")
    eps = float(op_.attr("epsilon", 1e-5))
    axes = axes_fn(x)
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=axes, keepdims=True)
    y = (x - mean) / jnp.sqrt(var + eps)
    scale = ctx.in1(op_, "Scale", optional=True)
    bias = ctx.in1(op_, "Bias", optional=True)
    bshape = (1, x.shape[1]) + (1,) * (x.ndim - 2)
    if scale is not None:
        y = y * scale.reshape(bshape)
    if bias is not None:
        y = y + bias.reshape(bshape)
    ctx.out(op_, "Y", y)
    ctx.out(op_, "SavedMean", mean.reshape(mean.shape[:2]))
    ctx.out(op_, "SavedVariance", var.reshape(var.shape[:2]))


@op("instance_norm", infer_shape=same_shape_infer("X", "Y"), grad="generic")
def _instance_norm(ctx, op_):
    _instance_norm_like(ctx, op_, lambda x: tuple(range(2, x.ndim)))


@op("group_norm", infer_shape=same_shape_infer("X", "Y"), grad="generic")
def _group_norm(ctx, op_):
    import jax.numpy as jnp

    x = ctx.in1(op_, "X")
    groups = int(op_.attr("groups", 1))
    eps = float(op_.attr("epsilon", 1e-5))
    n, c = x.shape[0], x.shape[1]
    xr = x.reshape((n, groups, c // groups) + tuple(x.shape[2:]))
    axes = tuple(range(2, xr.ndim))
    mean = jnp.mean(xr, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(xr - mean), axis=axes, keepdims=True)
    y = ((xr - mean) / jnp.sqrt(var + eps)).reshape(x.shape)
    scale = ctx.in1(op_, "Scale", optional=True)
    bias = ctx.in1(op_, "Bias", optional=True)
    bshape = (1, c) + (1,) * (x.ndim - 2)
    if scale is not None:
        y = y * scale.reshape(bshape)
    if bias is not None:
        y = y + bias.reshape(bshape)
    ctx.out(op_, "Y", y)
    ctx.out(op_, "Mean", mean.reshape((n, groups)))
    ctx.out(op_, "Variance", var.reshape((n, groups)))


# ---------------------------------------------------------------------------
# dropout — custom grad via saved Mask (reference: dropout_op.cc)
# ---------------------------------------------------------------------------
def _dropout_infer(op_, block):
    x = in_var(op_, block, "X")
    if x is None:
        raise SkipInferShape()
    set_out(op_, block, "Out", x.shape, x.dtype)
    set_out(op_, block, "Mask", x.shape, x.dtype)


def _dropout_grad_maker(op_):
    return [
        dict(
            type="dropout_grad",
            inputs={
                "Mask": op_.output("Mask"),
                "Out@GRAD": [n + "@GRAD" for n in op_.output("Out")],
            },
            outputs={"X@GRAD": [n + "@GRAD" for n in op_.input("X")]},
            attrs=dict(op_.attrs),
        )
    ]


@op("dropout", infer_shape=_dropout_infer, grad=_dropout_grad_maker)
def _dropout(ctx, op_):
    import jax
    import jax.numpy as jnp

    x = ctx.in1(op_, "X")
    p = float(op_.attr("dropout_prob", 0.5))
    is_test = bool(op_.attr("is_test", False))
    impl = op_.attr("dropout_implementation", "downgrade_in_infer")
    if is_test:
        out = x if impl == "upscale_in_train" else x * np.asarray(1.0 - p, x.dtype)
        ctx.out(op_, "Out", out)
        ctx.out(op_, "Mask", jnp.ones_like(x))
        return
    keep = jax.random.bernoulli(ctx.next_key(), 1.0 - p, x.shape)
    if impl == "upscale_in_train":
        mask = keep.astype(x.dtype) / np.asarray(max(1.0 - p, 1e-12), x.dtype)
    else:
        mask = keep.astype(x.dtype)
    ctx.out(op_, "Out", x * mask)
    ctx.out(op_, "Mask", mask)


@op("dropout_grad")
def _dropout_grad(ctx, op_):
    mask = ctx.in1(op_, "Mask")
    dout = ctx.in1(op_, "Out@GRAD")
    ctx.out(op_, "X@GRAD", dout * mask)


# ---------------------------------------------------------------------------
# misc NN
# ---------------------------------------------------------------------------
@op("relu_grad")  # fast path: avoids vjp re-trace for the hottest activation
def _relu_grad(ctx, op_):
    import jax.numpy as jnp

    out = ctx.in1(op_, "Out")
    dout = ctx.in1(op_, "Out@GRAD")
    ctx.out(op_, "X@GRAD", jnp.where(out > 0, dout, jnp.zeros_like(dout)))


@op("lrn", infer_shape=same_shape_infer("X"), grad="generic")
def _lrn(ctx, op_):
    import jax.lax as lax
    import jax.numpy as jnp

    x = ctx.in1(op_, "X")
    n = int(op_.attr("n", 5))
    k = float(op_.attr("k", 2.0))
    alpha = float(op_.attr("alpha", 1e-4))
    beta = float(op_.attr("beta", 0.75))
    sq = jnp.square(x)
    half = n // 2
    acc = lax.reduce_window(
        sq,
        np.asarray(0, x.dtype),
        lax.add,
        (1, n, 1, 1),
        (1, 1, 1, 1),
        [(0, 0), (half, n - 1 - half), (0, 0), (0, 0)],
    )
    mid = k + alpha * acc
    ctx.out(op_, "MidOut", mid)
    ctx.out(op_, "Out", x / jnp.power(mid, beta))


def _interp_out_hw(op_, x):
    oh = int(op_.attr("out_h", 0))
    ow = int(op_.attr("out_w", 0))
    scale = op_.attr("scale", 0.0)
    if (not oh or not ow) and scale:
        oh, ow = int(x.shape[2] * scale), int(x.shape[3] * scale)
    return oh, ow


def _src_coords(out_n, in_n, align_corners, align_mode):
    """Paddle interp_op.h coordinate mapping: align_corners uses the
    corner-anchored ratio (in-1)/(out-1); else align_mode==1 is the legacy
    src = dst*scale, align_mode==0 the half-pixel mapping."""
    import jax.numpy as jnp

    d = jnp.arange(out_n, dtype=jnp.float32)
    if align_corners:
        ratio = (in_n - 1.0) / (out_n - 1.0) if out_n > 1 else 0.0
        return d * ratio
    ratio = in_n / float(out_n)
    if align_mode == 1:
        return d * ratio
    return jnp.maximum((d + 0.5) * ratio - 0.5, 0.0)


@op("interp_nearest", grad="generic")
@op("nearest_interp", grad="generic")
def _nearest_interp(ctx, op_):
    import jax.numpy as jnp

    x = ctx.in1(op_, "X")
    oh, ow = _interp_out_hw(op_, x)
    ac = bool(op_.attr("align_corners", True))
    sy = _src_coords(oh, x.shape[2], ac, 1)
    sx = _src_coords(ow, x.shape[3], ac, 1)
    iy = (jnp.round(sy) if ac else jnp.floor(sy)).astype(jnp.int32)
    ix = (jnp.round(sx) if ac else jnp.floor(sx)).astype(jnp.int32)
    iy = jnp.clip(iy, 0, x.shape[2] - 1)
    ix = jnp.clip(ix, 0, x.shape[3] - 1)
    ctx.out(op_, "Out", x[:, :, iy][:, :, :, ix])


@op("bilinear_interp", grad="generic")
def _bilinear_interp(ctx, op_):
    import jax.numpy as jnp

    x = ctx.in1(op_, "X")
    oh, ow = _interp_out_hw(op_, x)
    ac = bool(op_.attr("align_corners", True))
    am = int(op_.attr("align_mode", 1))
    sy = _src_coords(oh, x.shape[2], ac, am)
    sx = _src_coords(ow, x.shape[3], ac, am)
    y0 = jnp.clip(jnp.floor(sy).astype(jnp.int32), 0, x.shape[2] - 1)
    x0 = jnp.clip(jnp.floor(sx).astype(jnp.int32), 0, x.shape[3] - 1)
    y1 = jnp.clip(y0 + 1, 0, x.shape[2] - 1)
    x1 = jnp.clip(x0 + 1, 0, x.shape[3] - 1)
    wy = (sy - y0).astype(x.dtype)[None, None, :, None]
    wx = (sx - x0).astype(x.dtype)[None, None, None, :]
    g = lambda yy, xx: x[:, :, yy][:, :, :, xx]  # noqa: E731
    out = (
        g(y0, x0) * (1 - wy) * (1 - wx)
        + g(y1, x0) * wy * (1 - wx)
        + g(y0, x1) * (1 - wy) * wx
        + g(y1, x1) * wy * wx
    )
    ctx.out(op_, "Out", out)


# -- op-gap closure batch (OPS_AUDIT.md): fc / indexed pooling / unpool -----
def _fc_infer(op_, block):
    x = in_var(op_, block, "Input")
    w = in_var(op_, block, "W")
    ncd = int(op_.attr("in_num_col_dims", 1))
    set_out(op_, block, "Out", list(x.shape[:ncd]) + [w.shape[-1]], x.dtype)


@op("fc", infer_shape=_fc_infer, grad="generic")
def _fc(ctx, op_):
    """Op-level fc (reference: fc_op.cc): flatten by in_num_col_dims, x.W
    (+bias) (+relu). The Python fc layer composes mul+elementwise_add; this
    op exists for fused-program and inference-model parity."""
    import jax.numpy as jnp

    x = ctx.in1(op_, "Input")
    w = ctx.in1(op_, "W")
    ncd = int(op_.attr("in_num_col_dims", 1))
    lead = x.shape[:ncd]
    x2 = x.reshape((int(np.prod(lead)) if lead else 1, -1))
    out = x2 @ w.reshape(x2.shape[1], -1)
    b = ctx.in1(op_, "Bias", optional=True)
    if b is not None:
        out = out + b.reshape(1, -1)
    if op_.attr("activation_type", "") == "relu":
        out = jnp.maximum(out, 0)
    ctx.out(op_, "Out", out.reshape(tuple(lead) + (w.shape[-1],)))


def _pool_with_index_infer(op_, block):
    x = in_var(op_, block, "X")
    k = len(x.shape) - 2
    ksize = [int(v) for v in op_.attr("ksize")]
    if op_.attr("global_pooling", False):
        ksize = [1] * k
        shape = list(x.shape[:2]) + ksize
    elif op_.attr("adaptive", False):
        shape = list(x.shape[:2]) + ksize
    else:
        strides = [int(v) for v in op_.attr("strides", [1] * k)]
        pads = [int(v) for v in op_.attr("paddings", [0] * k)]
        shape = list(x.shape[:2]) + [
            _conv_out_dim(x.shape[2 + i], ksize[i], pads[i], strides[i])
            for i in range(k)
        ]
    set_out(op_, block, "Out", shape, x.dtype)
    set_out(op_, block, "Mask", shape, core.VarDesc.VarType.INT32)


def _max_pool_with_index(ctx, op_, nd):
    """max_pool{2,3}d_with_index (reference: pool_with_index_op.cc).

    TPU scheme: extract windows as patches (a strided gather XLA fuses),
    then argmax over the patch axis — Out via take_along_axis so the
    generic vjp routes gradients through the selected elements, Mask holds
    flat spatial indices like the reference kernel."""
    import jax.lax as lax
    import jax.numpy as jnp

    x = ctx.in1(op_, "X")  # [N, C, *spatial]
    spatial = x.shape[2:]
    k = [int(v) for v in op_.attr("ksize")]
    if op_.attr("adaptive", False) and not op_.attr("global_pooling", False):
        # adaptive: bins of size spatial/ksize (divisibility required for a
        # static lowering, same contract as pool2d's adaptive path)
        for i in range(nd):
            assert spatial[i] % k[i] == 0, (
                "adaptive max_pool_with_index needs divisible dims"
            )
        bins = list(k)
        k = [spatial[i] // bins[i] for i in range(nd)]
        strides = list(k)
        pads = [0] * nd
    elif op_.attr("global_pooling", False):
        k = list(spatial)
        strides = [1] * nd
        pads = [0] * nd
    else:
        strides = [int(v) for v in op_.attr("strides", [1] * nd)]
        pads = [int(v) for v in op_.attr("paddings", [0] * nd)]
    n, c = x.shape[:2]
    neg = jnp.asarray(np.finfo(np.float32).min, x.dtype)
    xp = jnp.pad(
        x,
        [(0, 0), (0, 0)] + [(p, p) for p in pads],
        constant_values=neg,
    )
    # window index grid -> gather patches [N, C, *out, prod(k)]
    out_dims = [
        (spatial[i] + 2 * pads[i] - k[i]) // strides[i] + 1 for i in range(nd)
    ]
    # window start coordinates per output position, in padded space
    grids = jnp.meshgrid(
        *[jnp.arange(out_dims[i]) * strides[i] for i in range(nd)], indexing="ij"
    )
    pshape = [xp.shape[2 + i] for i in range(nd)]
    xf = xp.reshape(n, c, -1)
    patch_list = []
    for off in np.ndindex(*k):
        pos = jnp.zeros_like(grids[0])
        for i in range(nd):
            pos = pos * pshape[i] + (grids[i] + off[i])
        patch_list.append(xf[:, :, pos.reshape(-1)])
    patches = jnp.stack(patch_list, axis=-1)  # [N, C, prod(out), K]
    amax = jnp.argmax(patches, axis=-1)  # [N, C, prod(out)]
    out = jnp.take_along_axis(patches, amax[..., None], axis=-1)[..., 0]
    # mask: flat index into the UNPADDED input, reference contract
    koffs = np.stack([o.reshape(-1) for o in np.meshgrid(*[np.arange(ki) for ki in k], indexing="ij")], 0)  # [nd, K]
    koffs = jnp.asarray(koffs)
    per_dim = []
    for i in range(nd):
        base_i = grids[i].reshape(-1)[None, :]  # [1, prod(out)]
        off_i = koffs[i][:, None]  # [K, 1]
        per_dim.append(base_i + off_i - pads[i])  # padded -> unpadded coord
    sel = jnp.stack(per_dim, 0)  # [nd, K, prod(out)]
    flat_unpad = jnp.zeros(sel.shape[1:], jnp.int32)
    for i in range(nd):
        flat_unpad = flat_unpad * spatial[i] + sel[i].astype(jnp.int32)
    # pick the coordinate of the argmax patch element
    mask = jnp.take_along_axis(
        jnp.broadcast_to(flat_unpad.T[None, None], patches.shape),
        amax[..., None],
        axis=-1,
    )[..., 0]
    oshape = (n, c) + tuple(out_dims)
    ctx.out(op_, "Out", out.reshape(oshape))
    ctx.out(op_, "Mask", mask.reshape(oshape).astype(np.int32))


@op("max_pool2d_with_index", infer_shape=_pool_with_index_infer, grad="generic")
def _max_pool2d_with_index(ctx, op_):
    _max_pool_with_index(ctx, op_, 2)


@op("max_pool3d_with_index", infer_shape=_pool_with_index_infer, grad="generic")
def _max_pool3d_with_index(ctx, op_):
    _max_pool_with_index(ctx, op_, 3)


@op("unpool", grad="generic")
def _unpool(ctx, op_):
    """Max-unpool2d (reference: unpool_op.cc): scatter values back to the
    positions recorded in Indices."""
    import jax.numpy as jnp

    x = ctx.in1(op_, "X")  # [N, C, H, W]
    idx = ctx.in1(op_, "Indices").astype(jnp.int32)
    out_hw = [int(v) for v in op_.attr("unpooled_size", op_.attr("ksize", []))]
    n, c, h, w = x.shape
    oh, ow = out_hw[-2], out_hw[-1]
    zeros = jnp.zeros((n, c, oh * ow), x.dtype)
    out = zeros.at[
        jnp.arange(n)[:, None, None],
        jnp.arange(c)[None, :, None],
        idx.reshape(n, c, -1),
    ].set(x.reshape(n, c, -1))
    ctx.out(op_, "Out", out.reshape(n, c, oh, ow))


@op("spp", grad="generic")
def _spp(ctx, op_):
    """Spatial pyramid pooling (reference: spp_op.cc): pyramid_height
    levels of adaptive pooling, flattened + concatenated."""
    import jax.numpy as jnp

    x = ctx.in1(op_, "X")  # [N, C, H, W]
    levels = int(op_.attr("pyramid_height", 1))
    ptype = op_.attr("pooling_type", "max")
    n, c, h, w = x.shape
    outs = []
    for lv in range(levels):
        bins = 2 ** lv
        # reference uses ceil-mode kernel with padding; static approximation:
        # partition indices per bin via jnp.array_split semantics
        hb = [h * i // bins for i in range(bins + 1)]
        wb = [w * i // bins for i in range(bins + 1)]
        cells = []
        for i in range(bins):
            for j in range(bins):
                cell = x[:, :, hb[i]:max(hb[i + 1], hb[i] + 1), wb[j]:max(wb[j + 1], wb[j] + 1)]
                if ptype == "max":
                    cells.append(jnp.max(cell, axis=(2, 3)))
                else:
                    cells.append(jnp.mean(cell, axis=(2, 3)))
        outs.append(jnp.stack(cells, axis=-1).reshape(n, -1))
    ctx.out(op_, "Out", jnp.concatenate(outs, axis=1))


@op("depthwise_conv2d_transpose", grad="generic")
def _depthwise_conv2d_transpose(ctx, op_):
    """Per-channel transposed conv (reference: conv_transpose_op.cc
    registration depthwise_conv2d_transpose): lhs-dilated conv with
    feature_group_count = C."""
    import jax.lax as lax
    import jax.numpy as jnp

    x = ctx.in1(op_, "Input")  # [N, C, H, W]
    w = ctx.in1(op_, "Filter")  # [C, 1, kh, kw]
    strides = _pair(op_.attr("strides", [1, 1]))
    pads = _pair(op_.attr("paddings", [0, 0]))
    dil = _pair(op_.attr("dilations", [1, 1]))
    c = x.shape[1]
    kh, kw = w.shape[2], w.shape[3]
    # flip spatially; [C, 1, kh, kw] is already OIHW for groups=C
    wf = jnp.flip(w, axis=(2, 3)).reshape(c, 1, kh, kw)
    # transposed conv = conv with lhs_dilation=strides, padding k-1-p
    out = lax.conv_general_dilated(
        x,
        wf,
        window_strides=(1, 1),
        padding=[
            (dil[0] * (kh - 1) - pads[0], dil[0] * (kh - 1) - pads[0]),
            (dil[1] * (kw - 1) - pads[1], dil[1] * (kw - 1) - pads[1]),
        ],
        lhs_dilation=strides,
        rhs_dilation=dil,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=c,
    )
    ctx.out(op_, "Output", out)


# ---------------------------------------------------------------------------
# fused flash attention (Pallas kernel; the TPU-native counterpart of the
# reference's fused_multihead_matmul_op.cu CUDA kernel)
# ---------------------------------------------------------------------------
def _flash_attention_infer(op_, block):
    q = in_var(op_, block, "Q")
    set_out(op_, block, "Out", list(q.shape), q.dtype)


def _kernel_runs(interpret):
    """True when a flash op lowers to its Pallas kernel (the trace targets
    the TPU, or the op carries interpret=True) and not to the jnp
    reference, which GSPMD partitions like any other XLA code."""
    from ...kernels.flash_attention import lowers_for_tpu

    return bool(interpret) or lowers_for_tpu()


def _key_bias_dims(key_bias, B, N):
    """(key bias laid out for ``_per_shard``, its leading (batch, heads)
    dim count): a flat [B*N, S] mask unflattens so each dim can ride its
    own mesh axis; a broadcast [S] / [1, S] mask stays replicated."""
    if key_bias is None:
        return None, 0
    rows = key_bias.size // key_bias.shape[-1]
    if rows == B * N and N > 1:
        return key_bias.reshape(B, N, -1), 2
    if rows == B:
        return key_bias.reshape(B, -1), 1
    return key_bias, 0


def _shard_axes(B, N, interpret, batch_axis=True):
    """(mesh, (batch axis, heads axis)) for a flash op over [B, N, ...]
    operands in this trace. GSPMD cannot split a Mosaic custom call — jax
    refuses to lower one inside a multi-device jit, and gathering its
    operands to run it replicated would undo the sharding — so under a
    GSPMD mesh the kernel runs per shard (``_per_shard``): the batch dim
    rides the ``data`` axis, the heads dim the ``model`` axis, each only
    where the mesh has it and it divides the dim. ``batch_axis=False``
    keeps dim 0 whole (the paged pool's block dim belongs to no slot).
    mesh is None — a plain call — outside a GSPMD trace, and where the op
    lowers to its jnp reference, which GSPMD partitions like any other
    XLA code."""
    from .registry import lowering_mesh

    mesh = lowering_mesh()
    if mesh is None or not _kernel_runs(interpret):
        return None, (None, None)

    def axis(name, dim):
        size = mesh.shape.get(name, 1)
        return name if size > 1 and dim % size == 0 else None

    return mesh, (axis("data", B) if batch_axis else None, axis("model", N))


def _per_shard(fn, args, in_dims, out_dims, mesh, lead):
    """``fn(*args)`` on each device's own block, under the
    ``_shard_axes`` result ``(mesh, lead)``. ``in_dims`` gives, per
    operand, how many of its leading dims are (batch, heads): 2 =
    [B, N, ...], 1 = [B, ...], 0 = replicated, -1 = the heads lie side
    by side on its LAST dim (a paged pool's row) and nothing else is
    split; ``out_dims`` gives (that count, rank) per result. No mesh: a
    plain call."""
    if mesh is None:
        return fn(*args)
    from jax.sharding import PartitionSpec as P

    from ...parallel.mesh import shard_map

    def spec(ndims, rank):
        if ndims < 0:
            return P(*((None,) * (rank - 1) + (lead[1],)))
        return P(*(lead[:ndims] + (None,) * (rank - ndims)))

    out_specs = tuple(spec(n, rank) for n, rank in out_dims)
    return shard_map(
        fn, mesh,
        tuple(None if a is None else spec(n, a.ndim)
              for a, n in zip(args, in_dims)),
        out_specs if len(out_specs) > 1 else out_specs[0],
    )(*args)


def _shard_seed(seed, mesh, lead):
    """Inside ``_per_shard``: fold the shard's position on the sharding
    axes into the dropout seed. The kernels hash (LOCAL head, row, col,
    seed), so without this every shard would drop the same entries of
    its own block. Axes that do not shard the call are left out:
    replicas must keep drawing one mask."""
    if seed is None or mesh is None:
        return seed
    import jax

    idx = 0
    for name in lead:
        if name is not None:
            idx = idx * mesh.shape[name] + jax.lax.axis_index(name)
    return seed + idx


def _repeat_key_heads(x, heads):
    """Grouped queries: ``x`` [B, kv_heads, S, D] with fewer heads than
    the queries' ``heads`` is repeated so that query head h reads key head
    h // (heads / kv_heads); the kernels take as many key heads as query
    heads. Under a gradient the repeat's transpose adds a key head's
    dK/dV up over its group."""
    import jax
    import jax.numpy as jnp

    if x.shape[1] == heads:
        return x
    with jax.named_scope("gqa_train"):
        return jnp.repeat(x, heads // x.shape[1], axis=1)


def _sum_key_heads(dx, kv_heads):
    """The transpose of ``_repeat_key_heads``: [B, heads, S, D] ->
    [B, kv_heads, S, D], float32 sums in ``dx``'s dtype."""
    import jax
    import jax.numpy as jnp

    b, heads, s, d = dx.shape
    if heads == kv_heads:
        return dx
    with jax.named_scope("gqa_train"):
        return dx.reshape(b, kv_heads, heads // kv_heads, s, d).sum(
            2, dtype=jnp.float32).astype(dx.dtype)


@op("flash_attention", infer_shape=_flash_attention_infer, grad="generic")
def _flash_attention(ctx, op_):
    """Online-softmax fused attention on [N, heads, S, d_head] inputs
    (paddle_tpu/kernels/flash_attention.py): the [S, S] score matrix never
    touches HBM. ``K``/``V`` may hold fewer heads than ``Q`` (grouped
    queries: a divisor of its count), see ``_repeat_key_heads``.
    Differentiable through the kernel's custom VJP, so the
    generic grad maker Just Works."""
    from ...kernels.flash_attention import flash_attention_lse

    import jax
    import jax.numpy as jnp

    q = ctx.in1(op_, "Q")
    k = ctx.in1(op_, "K")
    v = ctx.in1(op_, "V")
    kb_names = op_.inputs.get("KeyBias") or []
    key_bias = ctx.in1(op_, "KeyBias") if kb_names else None
    bias_names = op_.inputs.get("Bias") or []
    bias = ctx.in1(op_, "Bias") if bias_names else None
    scale = op_.attr("scale", 0.0)
    # interpret=True forces the Pallas kernels off-TPU (tests/FD sweep);
    # default (None) runs kernels on TPU, dense reference elsewhere
    interpret = bool(op_.attr("interpret", False)) or None
    # in-kernel attention dropout: the seed derives from the executor's
    # per-(program-seed, step) key stream, which the generic-grad vjp
    # replay re-threads (registry.py base_key note) — so the backward
    # kernels regenerate the forward's exact mask
    rate = float(op_.attr("dropout_rate", 0.0))
    seed = None
    if rate > 0.0 and not bool(op_.attr("is_test", False)):
        seed = jax.random.randint(
            ctx.next_key(), (1, 1), 0, 1 << 23
        ).astype(jnp.float32)
    B, N = q.shape[:2]
    k, v = _repeat_key_heads(k, N), _repeat_key_heads(v, N)
    mesh, lead = _shard_axes(B, N, interpret)
    if bias is not None and mesh is not None:
        raise NotImplementedError(
            "flash_attention with a general Bias under a GSPMD mesh: no "
            "model shards one; use KeyBias/causal or the dense path"
        )
    key_bias, kb_dims = _key_bias_dims(key_bias, B, N)

    def attend(q, k, v, key_bias, seed):
        return flash_attention_lse(
            q, k, v, key_bias=key_bias, bias=bias,
            causal=bool(op_.attr("causal", False)),
            scale=float(scale) if scale else None,
            dropout_rate=rate if seed is not None else 0.0,
            dropout_seed=_shard_seed(seed, mesh, lead),
            interpret=interpret,
        )

    out, lse = _per_shard(
        attend, (q, k, v, key_bias, seed), (2, 2, 2, kb_dims, 0),
        ((2, 4), (2, 3)), mesh, lead,
    )
    ctx.out(op_, "Out", out)
    # stash the softmax statistics + dropout seed as companions of the
    # output var: the flash_attention_grad lowering drives the backward
    # kernels from these residuals instead of replaying the forward
    # (XLA cannot CSE a replayed Pallas custom call; the reference's
    # fused attention saves its softmax stats the same way). Companions
    # live in the segment's lowering env — a grad op in a DIFFERENT
    # segment won't see them and falls back to the generic vjp replay.
    oname = op_.output("Out")[0]
    ctx.set(oname + "@FLASH_LSE", lse)
    if seed is not None:
        ctx.set(oname + "@FLASH_SEED", seed)


def _flash_decode_paged_infer(op_, block):
    q = in_var(op_, block, "Q")
    set_out(op_, block, "Out", list(q.shape), q.dtype)


@op("flash_decode_paged_attention", infer_shape=_flash_decode_paged_infer)
def _flash_decode_paged_attention(ctx, op_):
    """Paged decode-mode attention (kernels/flash_attention.py
    flash_decode_paged_attention): one live token per slot reads K/V
    THROUGH a fed [slots, max_blocks] block table over the shared
    [blocks, 1, block, heads*d_head] pool (a token's keys one row, its
    heads side by side) — on TPU the table rides scalar
    prefetch so the kernel's DMA chases the indirection without ever
    materializing the logical rows. The optional ``Lengths`` [slots]
    (live keys a slot) rides beside it: table entries past a slot's
    live blocks are neither fetched nor computed; without it every
    entry is live. Inference-only; no grad."""
    from ...kernels.flash_attention import flash_decode_paged_attention

    q = ctx.in1(op_, "Q")
    k, v = (pool.reshape(pool.shape[0], pool.shape[2], pool.shape[3])
            for pool in (ctx.in1(op_, "K"), ctx.in1(op_, "V")))
    tables = ctx.in1(op_, "Tables")
    kb_names = op_.inputs.get("KeyBias") or []
    key_bias = ctx.in1(op_, "KeyBias") if kb_names else None
    len_names = op_.inputs.get("Lengths") or []
    lengths = ctx.in1(op_, "Lengths") if len_names else None
    scale = op_.attr("scale", 0.0)
    interpret = bool(op_.attr("interpret", False)) or None
    B, N = q.shape[:2]
    key_bias, kb_dims = _key_bias_dims(key_bias, B, N)
    # heads only: the pool's block dim belongs to no slot, so slots (and
    # with them tables, lengths and a per-slot mask) stay whole; a shard
    # holds its heads' lanes of every pool row
    ctx.out(op_, "Out", _per_shard(
        lambda q, k, v, tables, kb, lengths: flash_decode_paged_attention(
            q, k, v, tables, key_bias=kb, lengths=lengths,
            scale=float(scale) if scale else None, interpret=interpret),
        (q, k, v, tables, key_bias, lengths), (2, -1, -1, 0, kb_dims, 0),
        ((2, 4),), *_shard_axes(B, N, interpret, batch_axis=False),
    ))


def _kv_cache_write_paged_infer(op_, block):
    c = in_var(op_, block, "Cache")
    set_out(op_, block, "Out", list(c.shape), c.dtype)


@op("kv_cache_write_paged", infer_shape=_kv_cache_write_paged_infer)
def _kv_cache_write_paged(ctx, op_):
    """Block-table KV scatter. ``Cache`` is ONE shared [blocks, r0,
    block, r1] pool for every slot AND the prefix index, a token's row
    ``[r0, r1]`` being what the model says it is (``cache_kinds.py``:
    ``[1, hidden]`` for GPT, ``[1, 640]`` latent); ``New`` carries
    each slot's token window [slots, r0, T, r1]; ``Tables``
    [slots, max_blocks] int32 maps a slot's logical block number to a
    physical pool block; ``Pos`` [slots] is each slot's logical start
    position. Token j of slot s lands at pool block
    ``tables[s, (pos[s]+j) // block]`` offset ``(pos[s]+j) % block`` —
    all of it runtime DATA, so one compiled program serves every table
    layout (permuted, shared, COW-swapped) at 0 recompiles. O(written
    bytes) scatter; duplicate targets (inactive slots parked on the
    sink block) are garbage-by-contract and never read unmasked.
    Inference-only — no gradient registered."""
    import jax.numpy as jnp

    cache = ctx.in1(op_, "Cache")
    new = ctx.in1(op_, "New").astype(cache.dtype)
    tables = ctx.in1(op_, "Tables").astype(jnp.int32)
    pos = ctx.in1(op_, "Pos").reshape(-1).astype(jnp.int32)
    S, heads, T, d_head = new.shape
    block = int(cache.shape[2])
    # absolute logical positions per (slot, token): [S, T]
    abs_pos = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    blk_log = abs_pos // block                       # logical block no.
    off = (abs_pos % block).reshape(-1)              # [S*T] in-block off
    blk_phys = jnp.take_along_axis(tables, blk_log, axis=1).reshape(-1)
    new_flat = new.transpose(0, 2, 1, 3).reshape(S * T, heads, d_head)
    out = cache.at[blk_phys, :, off, :].set(
        new_flat, mode="drop", unique_indices=False
    )
    ctx.out(op_, "Out", out)


def _kv_cache_gather_paged_infer(op_, block):
    c = in_var(op_, block, "Cache")
    t = in_var(op_, block, "Tables")
    S, max_blocks = int(t.shape[0]), int(t.shape[1])
    heads, blk, d_head = (int(c.shape[1]), int(c.shape[2]),
                          int(c.shape[3]))
    set_out(op_, block, "Out", [S, heads, max_blocks * blk, d_head],
            c.dtype)


@op("kv_cache_gather_paged", infer_shape=_kv_cache_gather_paged_infer)
def _kv_cache_gather_paged(ctx, op_):
    """Materialize each slot's logical cache row THROUGH its block
    table: Out[s] = concat(pool[tables[s, b]] for b) reshaped to
    [slots, r0, max_blocks*block, r1] (``[r0, r1]`` a token's row, as in
    ``kv_cache_write_paged``) — the read half of the
    paged step/window programs. Tables are runtime data; O(gathered
    bytes). Positions beyond a slot's live length read whatever the
    mapped blocks hold (sink garbage included) — the caller's additive
    key bias masks them, same contract as the contiguous pool.
    Inference-only."""
    import jax.numpy as jnp

    cache = ctx.in1(op_, "Cache")
    tables = ctx.in1(op_, "Tables").astype(jnp.int32)
    S, max_blocks = tables.shape
    heads, blk, d_head = cache.shape[1], cache.shape[2], cache.shape[3]
    rows = cache[tables]                # [S, max_blocks, heads, blk, d]
    ctx.out(op_, "Out", rows.transpose(0, 2, 1, 3, 4).reshape(
        S, heads, max_blocks * blk, d_head
    ))


def _kv_cache_block_copy_infer(op_, block):
    c = in_var(op_, block, "Cache")
    set_out(op_, block, "Out", list(c.shape), c.dtype)


@op("kv_cache_block_copy", infer_shape=_kv_cache_block_copy_infer)
def _kv_cache_block_copy(ctx, op_):
    """Whole-block pool-internal copy: Out = Cache with
    ``Cache[Dst[i]] = Cache[Src[i]]`` for each i — the copy-on-write
    primitive (a shared block's partial tail is duplicated into a fresh
    block before the owner writes into it). Src/Dst are fed int32
    vectors (runtime data); only their (static) count is shape. A
    Src==Dst pair degenerates to an identity write, so callers may pad
    with no-op pairs to reuse one compiled count. Inference-only."""
    import jax.numpy as jnp

    cache = ctx.in1(op_, "Cache")
    src = ctx.in1(op_, "Src").reshape(-1).astype(jnp.int32)
    dst = ctx.in1(op_, "Dst").reshape(-1).astype(jnp.int32)
    ctx.out(op_, "Out", cache.at[dst].set(cache[src], mode="drop"))


@op("flash_attention_grad")
def _flash_attention_grad(ctx, op_):
    """Backward through the flash kernels from the forward's SAVED
    residuals (Out + @FLASH_LSE/@FLASH_SEED companions) — the forward
    kernel never re-runs. The generic vjp replay (still the fallback)
    re-traces the forward, which XLA CSE's for pure ops but not for
    Pallas custom calls: counting custom-calls in the lowered BERT/GPT
    step showed the forward kernel executing twice per layer. The
    reference's fused attention kernels save softmax statistics for
    their backward for the same reason."""
    from ...kernels.flash_attention import flash_attention_bwd_from_residuals
    from .registry import _generic_grad_lower

    interpret = bool(op_.attr("interpret", False))
    on_kernel_path = _kernel_runs(interpret)
    oname = (op_.inputs.get("Out") or [None])[0]
    lse = ctx.get_opt(oname + "@FLASH_LSE") if oname else None
    rate = float(op_.attr("dropout_rate", 0.0))
    dropout_live = rate > 0.0 and not bool(op_.attr("is_test", False))
    seed = ctx.get_opt(oname + "@FLASH_SEED") if oname else None
    has_general_bias = bool(
        [n for n in (op_.inputs.get("Bias") or []) if n]
    )
    if (
        not on_kernel_path          # dense-math vjp is CSE-able, replay is free
        or has_general_bias         # [S,S]-bias path keeps the replay
        or lse is None              # grad landed in a different XLA segment
        or (dropout_live and seed is None)
    ):
        return _generic_grad_lower(ctx, op_)

    q = ctx.in1(op_, "Q")
    k = ctx.in1(op_, "K")
    v = ctx.in1(op_, "V")
    key_bias = ctx.in1(op_, "KeyBias", optional=True)
    out = ctx.in1(op_, "Out")
    dout = ctx.in1(op_, "Out@GRAD")
    scale = op_.attr("scale", 0.0)
    B, N = q.shape[:2]
    kv_heads = k.shape[1]
    k, v = _repeat_key_heads(k, N), _repeat_key_heads(v, N)
    mesh, lead = _shard_axes(B, N, interpret)
    kb_in, kb_dims = _key_bias_dims(key_bias, B, N)

    def kernel(q, k, v, kb, seed, out, lse, dout):
        dq, dk, dv, dkb = flash_attention_bwd_from_residuals(
            q, k, v, kb, _shard_seed(seed, mesh, lead), out, lse, dout,
            causal=bool(op_.attr("causal", False)),
            scale=float(scale) if scale else None,
            dropout_rate=rate if dropout_live else 0.0,
            interpret=interpret or None,
        )
        # [b*n, Sk] -> [b, n, Sk]: batch and heads each ride their axis
        return dq, dk, dv, dkb.reshape(q.shape[0], q.shape[1], -1)

    dq, dk, dv, dkb = _per_shard(
        kernel,
        (q, k, v, kb_in, seed if dropout_live else None, out,
         lse.reshape(B, N, -1), dout),
        (2, 2, 2, kb_dims, 0, 2, 2, 2),
        ((2, 4), (2, 4), (2, 4), (2, 3)), mesh, lead,
    )
    dkb = dkb.reshape(B * N, -1)
    ctx.out(op_, "Q@GRAD", dq)
    ctx.out(op_, "K@GRAD", _sum_key_heads(dk, kv_heads))
    ctx.out(op_, "V@GRAD", _sum_key_heads(dv, kv_heads))
    kb_grad_names = [
        n for n in (op_.outputs.get("KeyBias@GRAD") or []) if n
    ]
    if key_bias is not None and kb_grad_names:
        # unbroadcast [B*N, Sk] onto the raw key-bias shape. The forward
        # normalization collapses ANY accepted raw shape to (r0, Sk) with
        # r0 in {1, B, B*N} before broadcasting, so the gradient sums the
        # broadcast axes back down to (r0, Sk) and reshapes to raw.
        B, N = q.shape[0], q.shape[1]
        Sk = k.shape[2]
        full = dkb.reshape(B, N, Sk)
        raw = tuple(key_bias.shape)
        r0 = 1
        for dim in raw[:-1]:
            r0 *= int(dim)
        if r0 == B * N:
            d = dkb
        elif r0 == B and N > 1:
            d = full.sum(1)
        else:  # r0 == 1 (the normalize contract admits no other value)
            d = full.sum((0, 1))[None]
        ctx.out(op_, "KeyBias@GRAD", d.reshape(raw).astype(key_bias.dtype))
