"""Math ops: activations, elementwise (with fluid axis-broadcast semantics),
matmul family, reductions, losses, normalization.

Reference kernels: paddle/fluid/operators/activation_op.cc, elementwise/
(broadcast engine elementwise_op_function.h), mul_op.cc, matmul_op.cc,
reduce_ops/, softmax_op.cc, cross_entropy_op.cc,
softmax_with_cross_entropy_op.cc, layer_norm_op.cc, mean_op.cc, clip_op.cc.
On TPU all of these are single jnp/lax expressions that XLA fuses; the
reference's hand-written CUDA broadcast/reduction machinery is unnecessary.
"""

from __future__ import annotations

import numpy as np

from .registry import (
    SkipInferShape,
    in_var,
    op,
    register_op,
    same_shape_infer,
    set_out,
)


# ---------------------------------------------------------------------------
# activations — one registrar for the whole family
# (reference: operators/activation_op.cc registers ~30 of these)
# ---------------------------------------------------------------------------
def _register_activation(name, fn, grad=True):
    def lower(ctx, op_, _fn=fn):
        ctx.out(op_, "Out", _fn(ctx.in1(op_, "X"), op_))

    register_op(
        name,
        infer_shape=same_shape_infer("X"),
        lower=lower,
        grad="generic" if grad else None,
    )


def _jnp():
    import jax.numpy as jnp

    return jnp


def _jnn():
    import jax.nn

    return jax.nn


_ACTIVATIONS = {
    "relu": lambda x, a: _jnn().relu(x),
    "sigmoid": lambda x, a: _jnn().sigmoid(x),
    "logsigmoid": lambda x, a: _jnn().log_sigmoid(x),
    "tanh": lambda x, a: _jnp().tanh(x),
    "tanh_shrink": lambda x, a: x - _jnp().tanh(x),
    "sqrt": lambda x, a: _jnp().sqrt(x),
    "rsqrt": lambda x, a: 1.0 / _jnp().sqrt(x),
    "abs": lambda x, a: _jnp().abs(x),
    "ceil": lambda x, a: _jnp().ceil(x),
    "floor": lambda x, a: _jnp().floor(x),
    "round": lambda x, a: _jnp().round(x),
    "cos": lambda x, a: _jnp().cos(x),
    "sin": lambda x, a: _jnp().sin(x),
    "acos": lambda x, a: _jnp().arccos(x),
    "asin": lambda x, a: _jnp().arcsin(x),
    "atan": lambda x, a: _jnp().arctan(x),
    "reciprocal": lambda x, a: 1.0 / x,
    "square": lambda x, a: x * x,
    "exp": lambda x, a: _jnp().exp(x),
    "log": lambda x, a: _jnp().log(x),
    "softplus": lambda x, a: _jnn().softplus(x),
    "softsign": lambda x, a: _jnn().soft_sign(x),
    "softshrink": lambda x, a: _softshrink(x, a.attr("lambda", 0.5)),
    "hard_shrink": lambda x, a: _hard_shrink(x, a.attr("threshold", 0.5)),
    "hard_sigmoid": lambda x, a: _jnp().clip(
        a.attr("slope", 0.2) * x + a.attr("offset", 0.5), 0.0, 1.0
    ),
    "hard_swish": lambda x, a: x
    * _jnp().clip(x + a.attr("offset", 3.0), 0.0, a.attr("threshold", 6.0))
    / a.attr("scale", 6.0),
    "brelu": lambda x, a: _jnp().clip(
        x, a.attr("t_min", 0.0), a.attr("t_max", 24.0)
    ),
    "leaky_relu": lambda x, a: _jnn().leaky_relu(x, a.attr("alpha", 0.02)),
    "elu": lambda x, a: _jnn().elu(x, a.attr("alpha", 1.0)),
    "relu6": lambda x, a: _jnp().clip(x, 0.0, a.attr("threshold", 6.0)),
    "pow": lambda x, a: _jnp().power(x, np.asarray(a.attr("factor", 1.0), x.dtype)),
    "stanh": lambda x, a: a.attr("scale_b", 1.7159)
    * _jnp().tanh(a.attr("scale_a", 0.67) * x),
    "swish": lambda x, a: x * _jnn().sigmoid(a.attr("beta", 1.0) * x),
    "gelu": lambda x, a: _jnn().gelu(x, approximate=bool(a.attr("approximate", False))),
    "thresholded_relu": lambda x, a: _jnp().where(
        x > a.attr("threshold", 1.0), x, _jnp().zeros_like(x)
    ),
    "soft_relu": lambda x, a: _jnp().log(
        1.0
        + _jnp().exp(_jnp().clip(x, -a.attr("threshold", 40.0), a.attr("threshold", 40.0)))
    ),
    "erf": lambda x, a: _erf(x),
}


def _softshrink(x, lam):
    jnp = _jnp()
    return jnp.where(x > lam, x - lam, jnp.where(x < -lam, x + lam, jnp.zeros_like(x)))


def _hard_shrink(x, t):
    jnp = _jnp()
    return jnp.where(jnp.abs(x) > t, x, jnp.zeros_like(x))


def _erf(x):
    import jax

    return jax.scipy.special.erf(x)


for _name, _fn in _ACTIVATIONS.items():
    _register_activation(_name, _fn)


@op("prelu", infer_shape=same_shape_infer("X"), grad="generic")
def _prelu(ctx, op_):
    jnp = _jnp()
    x = ctx.in1(op_, "X")
    alpha = ctx.in1(op_, "Alpha")
    mode = op_.attr("mode", "all")
    if mode == "channel":
        alpha = alpha.reshape((1, -1) + (1,) * (x.ndim - 2))
    elif mode == "all":
        alpha = alpha.reshape(())
    ctx.out(op_, "Out", jnp.where(x > 0, x, alpha * x))


# ---------------------------------------------------------------------------
# elementwise binary ops with fluid axis-broadcast semantics
# (reference: operators/elementwise/elementwise_op_function.h — Y is
# broadcast against X starting at `axis`; axis==-1 aligns trailing dims)
# ---------------------------------------------------------------------------
def _broadcast_y(x, y, axis):
    if x.shape == y.shape:
        return y
    if axis == -1 or axis is None:
        axis = x.ndim - y.ndim
    # strip trailing size-1 dims of y (fluid allows y rank > needed with 1s)
    yshape = list(y.shape)
    while yshape and yshape[-1] == 1 and len(yshape) + axis > x.ndim:
        yshape = yshape[:-1]
    new_shape = [1] * axis + yshape + [1] * (x.ndim - axis - len(yshape))
    return y.reshape(new_shape)


def _ew_infer(op_, block):
    v = in_var(op_, block, "X")
    if v is None:
        raise SkipInferShape()
    shape = v.shape
    y = in_var(op_, block, "Y")
    # Paddle broadcasting: Y broadcasts over X, so X's rank dominates —
    # except the degenerate x=[1]-style case where Y carries the shape
    if y is not None and y.shape and len(y.shape) > len(shape):
        shape = y.shape
    elif (
        y is not None
        and y.shape
        and len(y.shape) == len(shape)
        and any(s in (1, -1) for s in shape)
    ):
        shape = tuple(
            ys if xs == 1 and ys != 1 else xs
            for xs, ys in zip(shape, y.shape)
        )
    set_out(op_, block, "Out", shape, v.dtype)


def _register_elementwise(name, fn, grad="generic"):
    def lower(ctx, op_, _fn=fn):
        x = ctx.in1(op_, "X")
        y = ctx.in1(op_, "Y")
        yb = _broadcast_y(x, y, int(op_.attr("axis", -1)))
        ctx.out(op_, "Out", _fn(x, yb))

    register_op(name, infer_shape=_ew_infer, lower=lower, grad=grad)


_register_elementwise("elementwise_add", lambda x, y: x + y)
_register_elementwise("elementwise_sub", lambda x, y: x - y)
_register_elementwise("elementwise_mul", lambda x, y: x * y)
_register_elementwise("elementwise_div", lambda x, y: x / y)
_register_elementwise("elementwise_max", lambda x, y: _jnp().maximum(x, y))
_register_elementwise("elementwise_min", lambda x, y: _jnp().minimum(x, y))
_register_elementwise("elementwise_pow", lambda x, y: _jnp().power(x, y))
_register_elementwise(
    "elementwise_mod", lambda x, y: _jnp().mod(x, y), grad=None
)
_register_elementwise(
    "elementwise_floordiv", lambda x, y: _jnp().floor_divide(x, y), grad=None
)


# ---------------------------------------------------------------------------
# matmul family
# ---------------------------------------------------------------------------
def _mul_infer(op_, block):
    x = in_var(op_, block, "X")
    y = in_var(op_, block, "Y")
    if x is None or y is None or not x.shape or not y.shape:
        raise SkipInferShape()
    xnc = int(op_.attr("x_num_col_dims", 1))
    ync = int(op_.attr("y_num_col_dims", 1))
    set_out(op_, block, "Out", tuple(x.shape[:xnc]) + tuple(y.shape[ync:]),
            op_.attr("out_dtype", None) or x.dtype)  # a VarType enum


def _copy_to_tp(axis_name):
    """Megatron's `f` operator: identity forward, psum backward over the
    tensor-parallel axis. Placed on the input of a column-parallel matmul so
    the replicated activation's gradient sums the per-shard partials —
    differentiating our grad-op graph through it via jax.vjp reproduces
    exactly Megatron-LM's hand-written backward all-reduce."""
    import functools

    import jax

    @functools.partial(jax.custom_vjp)
    def f(x):
        return x

    def fwd(x):
        return x, None

    def bwd(_, g):
        return (jax.lax.psum(g, axis_name),)

    f.defvjp(fwd, bwd)
    return f


def _tp_axes(ctx, w_name, ndims=2):
    """-> (row_axis, col_axis) mesh-axis names from the weight's dist_attr
    (None when unsharded or not under a mesh)."""
    spec = ctx.dist_spec(w_name) if w_name else None
    if not spec or not ctx.mesh_axes:
        return None, None
    spec = tuple(spec) + (None,) * (ndims - len(spec))
    row = spec[-2] if ndims >= 2 else None
    col = spec[-1]
    row = row if row in ctx.mesh_axes else None
    col = col if col in ctx.mesh_axes else None
    return row, col


@op("mul", infer_shape=_mul_infer, grad="generic")
def _mul(ctx, op_):
    import jax.lax as lax

    jnp = _jnp()
    x = ctx.in1(op_, "X")
    y = ctx.in1(op_, "Y")
    xnc = int(op_.attr("x_num_col_dims", 1))
    ync = int(op_.attr("y_num_col_dims", 1))
    w_names = op_.inputs.get("Y") or [None]
    row_axis, col_axis = _tp_axes(ctx, w_names[0])
    if col_axis is not None:
        # column-parallel: local matmul on the weight shard; grads of the
        # replicated input psum over the TP axis (custom_vjp identity)
        x = _copy_to_tp(col_axis)(x)
    xm = x.reshape((int(np.prod(x.shape[:xnc])), -1))
    ym = y.reshape((int(np.prod(y.shape[:ync])), -1))
    # ``out_dtype``: the accumulator's dtype kept for the result (float32
    # logits from bfloat16 operands); absent, the operands' own
    out_dtype = op_.attr("out_dtype", None)
    if out_dtype:
        from .. import core as _core

        out_dtype = _core.dtype_to_np(out_dtype)
    out = jnp.dot(xm, ym, preferred_element_type=out_dtype)
    if row_axis is not None:
        # row-parallel: each shard holds a slice of the contraction dim —
        # partial products sum over the TP axis (Megatron's `g` operator);
        # vjp of psum is identity per shard, which is the correct backward
        out = lax.psum(out, row_axis)
    ctx.out(op_, "Out", out.reshape(tuple(x.shape[:xnc]) + tuple(y.shape[ync:])))


def _matmul_infer(op_, block):
    x = in_var(op_, block, "X")
    y = in_var(op_, block, "Y")
    if x is None or y is None or not x.shape or not y.shape:
        raise SkipInferShape()
    xs = list(x.shape)
    ys = list(y.shape)
    if len(xs) == 1 and len(ys) == 1:
        set_out(op_, block, "Out", (1,), x.dtype)
        return
    if op_.attr("transpose_X", False) and len(xs) > 1:
        xs[-1], xs[-2] = xs[-2], xs[-1]
    if op_.attr("transpose_Y", False) and len(ys) > 1:
        ys[-1], ys[-2] = ys[-2], ys[-1]
    # numpy matmul rank rules: 1-D operands get a broadcast dim that is
    # dropped from the result
    if len(xs) == 1:
        set_out(op_, block, "Out", tuple(ys[:-2]) + (ys[-1],), x.dtype)
        return
    if len(ys) == 1:
        set_out(op_, block, "Out", tuple(xs[:-1]), x.dtype)
        return
    batch = xs[:-2] if len(xs) > len(ys) else ys[:-2]
    set_out(op_, block, "Out", tuple(batch) + (xs[-2], ys[-1]), x.dtype)


@op("matmul", infer_shape=_matmul_infer, grad="generic")
def _matmul(ctx, op_):
    import jax.lax as lax

    jnp = _jnp()
    x = ctx.in1(op_, "X")
    y = ctx.in1(op_, "Y")
    w_names = op_.inputs.get("Y") or [None]
    row_axis, col_axis = _tp_axes(ctx, w_names[0])
    if op_.attr("transpose_X", False):
        x = jnp.swapaxes(x, -1, -2) if x.ndim > 1 else x
    if op_.attr("transpose_Y", False):
        y = jnp.swapaxes(y, -1, -2) if y.ndim > 1 else y
        row_axis, col_axis = col_axis, row_axis
    if col_axis is not None:
        x = _copy_to_tp(col_axis)(x)
    out = jnp.matmul(x, y)
    if row_axis is not None:
        out = lax.psum(out, row_axis)
    alpha = float(op_.attr("alpha", 1.0))
    if alpha != 1.0:
        out = out * np.asarray(alpha, out.dtype)
    ctx.out(op_, "Out", out)


@op("bmm", grad="generic")
def _bmm(ctx, op_):
    ctx.out(op_, "Out", _jnp().matmul(ctx.in1(op_, "X"), ctx.in1(op_, "Y")))


@op("dot", grad="generic")
def _dot(ctx, op_):
    jnp = _jnp()
    x = ctx.in1(op_, "X")
    y = ctx.in1(op_, "Y")
    ctx.out(op_, "Out", jnp.sum(x * y, axis=-1, keepdims=True))


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------
def _reduce_infer(op_, block):
    v = in_var(op_, block, "X")
    if v is None:
        raise SkipInferShape()
    dims = op_.attr("dim", [0])
    keep = op_.attr("keep_dim", False)
    if op_.attr("reduce_all", False):
        shape = [1] * len(v.shape) if keep else [1]
    else:
        dims = [d % len(v.shape) for d in dims]
        shape = [
            (1 if i in dims else s) if keep else s
            for i, s in enumerate(v.shape)
            if keep or i not in dims
        ]
        if not shape:
            shape = [1]
    set_out(op_, block, "Out", shape, v.dtype)


def _register_reduce(name, fn, grad="generic"):
    def lower(ctx, op_, _fn=fn):
        x = ctx.in1(op_, "X")
        if op_.attr("reduce_all", False):
            axes = tuple(range(x.ndim))
        else:
            axes = tuple(d % x.ndim for d in op_.attr("dim", [0]))
        keep = bool(op_.attr("keep_dim", False))
        out = _fn(x, axes, keep)
        if not keep and out.ndim == 0:
            out = out.reshape((1,))
        ctx.out(op_, "Out", out)

    register_op(name, infer_shape=_reduce_infer, lower=lower, grad=grad)


_register_reduce("reduce_sum", lambda x, a, k: _jnp().sum(x, axis=a, keepdims=k))
_register_reduce("reduce_mean", lambda x, a, k: _jnp().mean(x, axis=a, keepdims=k))
_register_reduce("reduce_max", lambda x, a, k: _jnp().max(x, axis=a, keepdims=k))
_register_reduce("reduce_min", lambda x, a, k: _jnp().min(x, axis=a, keepdims=k))
_register_reduce("reduce_prod", lambda x, a, k: _jnp().prod(x, axis=a, keepdims=k))
_register_reduce(
    "reduce_all", lambda x, a, k: _jnp().all(x, axis=a, keepdims=k), grad=None
)
_register_reduce(
    "reduce_any", lambda x, a, k: _jnp().any(x, axis=a, keepdims=k), grad=None
)


def _mean_infer(op_, block):
    v = in_var(op_, block, "X")
    if v is None:
        raise SkipInferShape()
    set_out(op_, block, "Out", (1,), v.dtype)


@op("mean", infer_shape=_mean_infer, grad="generic")
def _mean(ctx, op_):
    ctx.out(op_, "Out", _jnp().mean(ctx.in1(op_, "X")).reshape((1,)))


@op("squared_l2_norm", infer_shape=_mean_infer, grad="generic")
def _squared_l2_norm(ctx, op_):
    x = ctx.in1(op_, "X")
    ctx.out(op_, "Out", _jnp().sum(x * x).reshape((1,)))


@op("frobenius_norm", infer_shape=_mean_infer, grad="generic")
def _frobenius_norm(ctx, op_):
    x = ctx.in1(op_, "X")
    ctx.out(op_, "Out", _jnp().sqrt(_jnp().sum(x * x)).reshape((1,)))


# ---------------------------------------------------------------------------
# softmax / losses
# ---------------------------------------------------------------------------
@op("softmax", infer_shape=same_shape_infer("X"), grad="generic")
def _softmax(ctx, op_):
    x = ctx.in1(op_, "X")
    ctx.out(op_, "Out", _jnn().softmax(x, axis=int(op_.attr("axis", -1))))


@op("log_softmax", infer_shape=same_shape_infer("X"), grad="generic")
def _log_softmax(ctx, op_):
    x = ctx.in1(op_, "X")
    ctx.out(op_, "Out", _jnn().log_softmax(x, axis=int(op_.attr("axis", -1))))


def _xent_infer(op_, block):
    x = in_var(op_, block, "X")
    if x is None:
        raise SkipInferShape()
    set_out(op_, block, "Out", tuple(x.shape[:-1]) + (1,), x.dtype)


@op("cross_entropy", infer_shape=_xent_infer, grad="generic")
def _cross_entropy(ctx, op_):
    jnp = _jnp()
    x = ctx.in1(op_, "X")
    label = ctx.in1(op_, "Label")
    soft = bool(op_.attr("soft_label", False))
    ignore_index = int(op_.attr("ignore_index", -100))
    logp = jnp.log(jnp.clip(x, 1e-15, 1.0))
    if soft:
        out = -jnp.sum(label * logp, axis=-1, keepdims=True)
    else:
        lab = label
        if lab.shape and lab.shape[-1] == 1:
            lab = lab.reshape(lab.shape[:-1])
        safe_lab = jnp.where(lab == ignore_index, jnp.zeros_like(lab), lab)
        picked = jnp.take_along_axis(
            logp, safe_lab[..., None].astype(np.int32), axis=-1
        )
        out = jnp.where(
            lab[..., None] == ignore_index, jnp.zeros_like(picked), -picked
        )
    ctx.out(op_, "Out", out)


def _swce_infer(op_, block):
    x = in_var(op_, block, "Logits")
    if x is None:
        raise SkipInferShape()
    set_out(op_, block, "Loss", tuple(x.shape[:-1]) + (1,), x.dtype)
    set_out(op_, block, "Softmax", x.shape, x.dtype)


def _swce_grad_maker(op_):
    # custom maker: grad needs Softmax + Loss@GRAD + Label only
    return [
        dict(
            type="softmax_with_cross_entropy_grad",
            inputs={
                "Label": op_.input("Label"),
                "Softmax": op_.output("Softmax"),
                "Loss@GRAD": [n + "@GRAD" for n in op_.output("Loss")],
            },
            outputs={
                "Logits@GRAD": [n + "@GRAD" for n in op_.input("Logits")]
            },
            attrs=dict(op_.attrs),
        )
    ]


@op("softmax_with_cross_entropy", infer_shape=_swce_infer, grad=_swce_grad_maker)
def _softmax_with_cross_entropy(ctx, op_):
    jnp = _jnp()
    logits = ctx.in1(op_, "Logits")
    label = ctx.in1(op_, "Label")
    soft = bool(op_.attr("soft_label", False))
    axis = int(op_.attr("axis", -1))
    logp = _jnn().log_softmax(logits, axis=axis)
    sm = jnp.exp(logp)
    if soft:
        loss = -jnp.sum(label * logp, axis=axis, keepdims=True)
    else:
        lab = label
        if lab.shape and lab.shape[-1] == 1:
            lab = lab.reshape(lab.shape[:-1])
        ignore_index = int(op_.attr("ignore_index", -100))
        safe_lab = jnp.where(lab == ignore_index, jnp.zeros_like(lab), lab)
        loss = -jnp.take_along_axis(
            logp, safe_lab[..., None].astype(np.int32), axis=axis
        )
        loss = jnp.where(
            lab[..., None] == ignore_index, jnp.zeros_like(loss), loss
        )
    ctx.out(op_, "Loss", loss)
    ctx.out(op_, "Softmax", sm)


@op("softmax_with_cross_entropy_grad")
def _softmax_with_cross_entropy_grad(ctx, op_):
    jnp = _jnp()
    sm = ctx.in1(op_, "Softmax")
    label = ctx.in1(op_, "Label")
    dloss = ctx.in1(op_, "Loss@GRAD")
    soft = bool(op_.attr("soft_label", False))
    if soft:
        dlogits = (sm - label) * dloss
    else:
        lab = label
        if lab.shape and lab.shape[-1] == 1:
            lab = lab.reshape(lab.shape[:-1])
        ignore_index = int(op_.attr("ignore_index", -100))
        safe_lab = jnp.where(lab == ignore_index, jnp.zeros_like(lab), lab)
        onehot = _jnn().one_hot(safe_lab, sm.shape[-1], dtype=sm.dtype)
        dlogits = (sm - onehot) * dloss
        dlogits = jnp.where(
            (lab == ignore_index)[..., None], jnp.zeros_like(dlogits), dlogits
        )
    ctx.out(op_, "Logits@GRAD", dlogits)


@op("sigmoid_cross_entropy_with_logits", infer_shape=same_shape_infer("X"), grad="generic")
def _sigmoid_xent(ctx, op_):
    jnp = _jnp()
    x = ctx.in1(op_, "X")
    label = ctx.in1(op_, "Label")
    ignore_index = int(op_.attr("ignore_index", -100))
    loss = _jnp().maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    if ignore_index != -100:
        loss = jnp.where(label == ignore_index, jnp.zeros_like(loss), loss)
    if op_.attr("normalize", False):
        n = jnp.maximum(jnp.sum((label != ignore_index).astype(x.dtype)), 1.0)
        loss = loss / n
    ctx.out(op_, "Out", loss)


@op("square_error_cost", infer_shape=same_shape_infer("X"), grad="generic")
def _square_error_cost(ctx, op_):
    x = ctx.in1(op_, "X")
    y = ctx.in1(op_, "Y")
    d = x - y
    ctx.out(op_, "Out", d * d)


@op("huber_loss", grad="generic")
def _huber_loss(ctx, op_):
    jnp = _jnp()
    x = ctx.in1(op_, "X")  # prediction
    y = ctx.in1(op_, "Y")  # label
    delta = float(op_.attr("delta", 1.0))
    r = y - x
    a = jnp.abs(r)
    loss = jnp.where(a <= delta, 0.5 * r * r, delta * (a - 0.5 * delta))
    ctx.out(op_, "Out", loss)
    ctx.out(op_, "Residual", r)


@op("smooth_l1_loss", grad="generic")
def _smooth_l1_loss(ctx, op_):
    jnp = _jnp()
    x = ctx.in1(op_, "X")
    y = ctx.in1(op_, "Y")
    sigma = float(op_.attr("sigma", 1.0))
    s2 = sigma * sigma
    d = x - y
    a = jnp.abs(d)
    val = jnp.where(a < 1.0 / s2, 0.5 * d * d * s2, a - 0.5 / s2)
    ctx.out(op_, "Diff", d)
    ctx.out(op_, "Out", jnp.sum(val, axis=tuple(range(1, val.ndim)), keepdims=False).reshape((-1, 1)))


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------
def _layer_norm_infer(op_, block):
    v = in_var(op_, block, "X")
    if v is None:
        raise SkipInferShape()
    ax = int(op_.attr("begin_norm_axis", 1))
    set_out(op_, block, "Y", v.shape, v.dtype)
    rows = v.shape[:ax]
    set_out(op_, block, "Mean", rows, v.dtype)
    set_out(op_, block, "Variance", rows, v.dtype)


@op("layer_norm", infer_shape=_layer_norm_infer, grad="generic")
def _layer_norm(ctx, op_):
    jnp = _jnp()
    x = ctx.in1(op_, "X")
    ax = int(op_.attr("begin_norm_axis", 1))
    eps = float(op_.attr("epsilon", 1e-5))
    axes = tuple(range(ax, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=axes, keepdims=True)
    inv = 1.0 / jnp.sqrt(var + eps)
    y = (x - mean) * inv
    scale = ctx.in1(op_, "Scale", optional=True)
    bias = ctx.in1(op_, "Bias", optional=True)
    feat_shape = (1,) * ax + tuple(x.shape[ax:])
    if scale is not None:
        y = y * scale.reshape(feat_shape)
    if bias is not None:
        y = y + bias.reshape(feat_shape)
    ctx.out(op_, "Y", y)
    ctx.out(op_, "Mean", mean.reshape(x.shape[:ax]))
    ctx.out(op_, "Variance", var.reshape(x.shape[:ax]))


@op("l2_normalize", infer_shape=same_shape_infer("X"), grad="generic")
def _l2_normalize(ctx, op_):
    jnp = _jnp()
    x = ctx.in1(op_, "X")
    ax = int(op_.attr("axis", -1))
    eps = float(op_.attr("epsilon", 1e-10))
    norm = jnp.sqrt(jnp.sum(x * x, axis=ax, keepdims=True))
    ctx.out(op_, "Out", x / jnp.maximum(norm, eps))
    ctx.out(op_, "Norm", norm)


# ---------------------------------------------------------------------------
# clipping / misc
# ---------------------------------------------------------------------------
@op("clip", infer_shape=same_shape_infer("X"), grad="generic")
def _clip(ctx, op_):
    x = ctx.in1(op_, "X")
    ctx.out(op_, "Out", _jnp().clip(x, op_.attr("min"), op_.attr("max")))


@op("clip_by_norm", infer_shape=same_shape_infer("X"), grad="generic")
def _clip_by_norm(ctx, op_):
    jnp = _jnp()
    x = ctx.in1(op_, "X")
    max_norm = float(op_.attr("max_norm"))
    norm = jnp.sqrt(jnp.sum(x * x))
    scale = jnp.where(norm > max_norm, max_norm / jnp.maximum(norm, 1e-12), 1.0)
    ctx.out(op_, "Out", x * scale.astype(x.dtype))


@op("isfinite")
def _isfinite(ctx, op_):
    jnp = _jnp()
    xs = ctx.ins(op_, "X")
    ok = jnp.asarray(True)
    for x in xs:
        ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(x)))
    ctx.out(op_, "Out", ok.reshape((1,)))


@op("maximum", grad="generic")
def _maximum(ctx, op_):
    ctx.out(op_, "Out", _jnp().maximum(ctx.in1(op_, "X"), ctx.in1(op_, "Y")))


@op("cumsum", grad="generic")
def _cumsum(ctx, op_):
    jnp = _jnp()
    x = ctx.in1(op_, "X")
    ax = op_.attr("axis", -1)
    out = jnp.cumsum(x, axis=int(ax))
    if op_.attr("reverse", False):
        out = jnp.flip(jnp.cumsum(jnp.flip(x, int(ax)), axis=int(ax)), int(ax))
    if op_.attr("exclusive", False):
        out = out - x
    ctx.out(op_, "Out", out)


@op("sign", infer_shape=same_shape_infer("X"))
def _sign(ctx, op_):
    ctx.out(op_, "Out", _jnp().sign(ctx.in1(op_, "X")))


@op("label_smooth", grad="generic")
def _label_smooth(ctx, op_):
    jnp = _jnp()
    x = ctx.in1(op_, "X")
    eps = float(op_.attr("epsilon", 0.1))
    prior = ctx.in1(op_, "PriorDist", optional=True)
    k = x.shape[-1]
    if prior is not None:
        out = (1.0 - eps) * x + eps * prior.reshape((1,) * (x.ndim - 1) + (k,))
    else:
        out = (1.0 - eps) * x + eps / k
    ctx.out(op_, "Out", out.astype(x.dtype))


@op("maxout", grad="generic")
def _maxout(ctx, op_):
    jnp = _jnp()
    x = ctx.in1(op_, "X")  # NCHW
    groups = int(op_.attr("groups"))
    n, c, h, w = x.shape
    ctx.out(op_, "Out", jnp.max(x.reshape(n, c // groups, groups, h, w), axis=2))


@op("sampling_id")
def _sampling_id(ctx, op_):
    import jax

    x = ctx.in1(op_, "X")  # [batch, classes] probabilities
    ctx.out(
        op_,
        "Out",
        jax.random.categorical(ctx.next_key(), _jnp().log(x + 1e-20), axis=-1).astype(
            np.int64
        ),
    )


@op("uniform_random_batch_size_like")
def _uniform_random_bsl(ctx, op_):
    import jax

    from .. import core as _core

    ref = ctx.in1(op_, "Input")
    shape = [int(s) for s in op_.attr("shape", [])]
    shape[int(op_.attr("output_dim_idx", 0))] = ref.shape[int(op_.attr("input_dim_idx", 0))]
    dt = _core.dtype_to_np(op_.attr("dtype", 5))
    ctx.out(
        op_,
        "Out",
        jax.random.uniform(
            ctx.next_key(),
            shape,
            dt,
            minval=float(op_.attr("min", -1.0)),
            maxval=float(op_.attr("max", 1.0)),
        ),
    )


@op("unfold", grad="generic")
def _unfold(ctx, op_):
    import jax.lax as lax

    jnp = _jnp()
    x = ctx.in1(op_, "X")  # NCHW
    ks = op_.attr("kernel_sizes")
    st = op_.attr("strides", [1, 1])
    pd = op_.attr("paddings", [0, 0, 0, 0])
    dl = op_.attr("dilations", [1, 1])
    n, c, h, w = x.shape
    patches = lax.conv_general_dilated_patches(
        x,
        filter_shape=tuple(ks),
        window_strides=tuple(st),
        padding=[(pd[0], pd[2]), (pd[1], pd[3])],
        rhs_dilation=tuple(dl),
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )
    ctx.out(op_, "Y", patches.reshape(n, c * ks[0] * ks[1], -1))


# -- op-gap closure batch (OPS_AUDIT.md): similarity / products -------------
def _cos_sim_infer(op_, block):
    v = in_var(op_, block, "X")
    set_out(op_, block, "Out", [v.shape[0], 1], v.dtype)
    set_out(op_, block, "XNorm", [v.shape[0], 1], v.dtype)
    yv = in_var(op_, block, "Y")
    set_out(op_, block, "YNorm", [yv.shape[0], 1], yv.dtype)


@op("cos_sim", infer_shape=_cos_sim_infer, grad="generic")
def _cos_sim(ctx, op_):
    """Row-wise cosine similarity (reference: cos_sim_op.cc); Y may have
    batch 1 and broadcast against X."""
    import jax.numpy as jnp

    x = ctx.in1(op_, "X")
    y = ctx.in1(op_, "Y")
    xn = jnp.sqrt(jnp.sum(x * x, axis=1, keepdims=True))
    yn = jnp.sqrt(jnp.sum(y * y, axis=1, keepdims=True))
    num = jnp.sum(x * y, axis=1, keepdims=True)
    ctx.out(op_, "Out", num / (xn * yn + 1e-12))
    ctx.out(op_, "XNorm", xn)
    ctx.out(op_, "YNorm", yn)


def _squared_l2_distance_infer(op_, block):
    v = in_var(op_, block, "X")
    set_out(op_, block, "Out", [v.shape[0], 1], v.dtype)
    set_out(op_, block, "sub_result", list(v.shape), v.dtype)


@op("squared_l2_distance", infer_shape=_squared_l2_distance_infer, grad="generic")
def _squared_l2_distance(ctx, op_):
    import jax.numpy as jnp

    x = ctx.in1(op_, "X")
    y = ctx.in1(op_, "Y")
    sub = x - y
    ctx.out(op_, "sub_result", sub)
    ctx.out(op_, "Out", jnp.sum(sub * sub, axis=tuple(range(1, sub.ndim))).reshape(-1, 1))


def _bilinear_tp_infer(op_, block):
    x = in_var(op_, block, "X")
    w = in_var(op_, block, "Weight")
    set_out(op_, block, "Out", [x.shape[0], w.shape[0]], x.dtype)


@op("bilinear_tensor_product", infer_shape=_bilinear_tp_infer, grad="generic")
def _bilinear_tensor_product(ctx, op_):
    """out[b, k] = x[b] . W[k] . y[b]^T (+ bias)
    (reference: bilinear_tensor_product_op.cc)."""
    import jax.numpy as jnp

    x = ctx.in1(op_, "X")  # [B, M]
    y = ctx.in1(op_, "Y")  # [B, N]
    w = ctx.in1(op_, "Weight")  # [K, M, N]
    out = jnp.einsum("bm,kmn,bn->bk", x, w, y)
    b = ctx.in1(op_, "Bias", optional=True)
    if b is not None:
        out = out + b.reshape(1, -1)
    ctx.out(op_, "Out", out)


@op("add_position_encoding", infer_shape=same_shape_infer("X"), grad="generic")
def _add_position_encoding(ctx, op_):
    """out = alpha*x + beta*sinusoid(pos) (reference:
    add_position_encoding_op.cc; Transformer positional encoding)."""
    import jax.numpy as jnp

    x = ctx.in1(op_, "X")  # [B, T, D]
    alpha = float(op_.attr("alpha", 1.0))
    beta = float(op_.attr("beta", 1.0))
    b, t, d = x.shape
    half = d // 2
    rest = d - half  # odd D: cos block carries the extra column
    pos = jnp.arange(t, dtype=jnp.float32)[:, None]  # [T, 1]
    wavelen = lambda n: jnp.power(  # noqa: E731
        10000.0, jnp.arange(n, dtype=jnp.float32) / max(half, 1)
    )
    enc = jnp.concatenate(
        [jnp.sin(pos / wavelen(half)), jnp.cos(pos / wavelen(rest))], axis=1
    )  # [T, D]
    ctx.out(op_, "Out", alpha * x + beta * enc[None].astype(x.dtype))


@op("similarity_focus")
def _similarity_focus(ctx, op_):
    """Similarity-focus mask (reference: similarity_focus_op.cc): per
    selected channel, greedily pick the largest remaining cell whose row AND
    column are both unused, mark it, and retire that row+column — repeated
    min(H, W) times (the reference walks cells in descending order with
    row/col exclusivity)."""
    import jax.numpy as jnp

    x = ctx.in1(op_, "X")  # [B, C, H, W] (axis must be 1 per reference)
    axis = int(op_.attr("axis", 1))
    idx = [int(i) for i in op_.attr("indexes", [])]
    if axis != 1:
        raise NotImplementedError("similarity_focus: only axis=1 supported")
    bsz, c, h, w = x.shape
    neg = jnp.asarray(np.finfo(np.float32).min, x.dtype)
    mask = jnp.zeros((bsz, h, w), x.dtype)
    for ch in idx:
        fm = x[:, ch]  # [B, H, W]
        row_used = jnp.zeros((bsz, h), bool)
        col_used = jnp.zeros((bsz, w), bool)
        for _ in range(min(h, w)):  # static trip count; XLA unrolls
            avail = (~row_used)[:, :, None] & (~col_used)[:, None, :]
            fa = jnp.where(avail, fm, neg)
            flat = jnp.argmax(fa.reshape(bsz, -1), axis=1)
            ri, ci = flat // w, flat % w
            mask = mask.at[jnp.arange(bsz), ri, ci].set(1)
            row_used = row_used.at[jnp.arange(bsz), ri].set(True)
            col_used = col_used.at[jnp.arange(bsz), ci].set(True)
    ctx.out(op_, "Out", jnp.broadcast_to(mask[:, None], x.shape).astype(x.dtype))


@op("fsp", grad="generic")
def _fsp(ctx, op_):
    """FSP (flow of solution procedure) matrix for distillation
    (reference: fsp_op.cc): out[n, ci, cj] = mean_hw x[n,ci,h,w]*y[n,cj,h,w]."""
    import jax.numpy as jnp

    x = ctx.in1(op_, "X")  # [N, C1, H, W]
    y = ctx.in1(op_, "Y")  # [N, C2, H, W]
    hw = x.shape[2] * x.shape[3]
    ctx.out(op_, "Out", jnp.einsum("nihw,njhw->nij", x, y) / hw)
