"""Dense math ops: elementwise (w/ axis broadcast), activations, matmul.

Capability parity: reference `paddle/fluid/operators/elementwise/`,
`activation_op.cc`, `matmul_op.cc`, `mul_op.cc`.  Each op here is ONE pure
JAX lowering — XLA supplies the CPU/TPU kernels and the fusion that the
reference implemented by hand (elementwise CUDA kernels, fused activations).
"""

import jax
import jax.numpy as jnp

from ..core.registry import register_op


def _paddle_bcast(x, y, axis):
    """Reference broadcast rule (elementwise_op.h): align Y to X at `axis`."""
    if x.ndim == y.ndim:
        return x, y
    if y.ndim > x.ndim:  # numpy-style fallback
        return x, y
    if axis is None or axis == -1:
        axis = x.ndim - y.ndim
    new_shape = (1,) * axis + y.shape + (1,) * (x.ndim - axis - y.ndim)
    return x, y.reshape(new_shape)


def _register_elementwise(name, fn):
    @register_op(
        "elementwise_" + name, inputs=["X", "Y"], outputs=["Out"]
    )
    def _lower(ctx, ins, attrs, fn=fn):
        x, y = ins["X"][0], ins["Y"][0]
        x, y = _paddle_bcast(x, y, attrs.get("axis", -1))
        return {"Out": [fn(x, y)]}


_register_elementwise("add", jnp.add)
_register_elementwise("sub", jnp.subtract)
_register_elementwise("mul", jnp.multiply)
_register_elementwise("div", jnp.divide)
_register_elementwise("pow", jnp.power)
_register_elementwise("max", jnp.maximum)
_register_elementwise("min", jnp.minimum)
_register_elementwise("mod", jnp.mod)
_register_elementwise("floordiv", jnp.floor_divide)


# -- activations (cf. activation_op.cc) --------------------------------------

_ACTIVATIONS = {
    "relu": jax.nn.relu,
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "exp": jnp.exp,
    "log": jnp.log,
    "sqrt": jnp.sqrt,
    "rsqrt": jax.lax.rsqrt,
    "abs": jnp.abs,
    "square": jnp.square,
    "reciprocal": lambda x: 1.0 / x,
    "floor": jnp.floor,
    "ceil": jnp.ceil,
    "round": jnp.round,
    "sin": jnp.sin,
    "cos": jnp.cos,
    "softplus": jax.nn.softplus,
    "softsign": jax.nn.soft_sign,
    "silu": jax.nn.silu,
    "erf": jax.lax.erf,
    "sign": jnp.sign,
    "logsigmoid": jax.nn.log_sigmoid,
}


def _register_activation(name, fn):
    @register_op(name, inputs=["X"], outputs=["Out"])
    def _lower(ctx, ins, attrs, fn=fn):
        return {"Out": [fn(ins["X"][0])]}


for _name, _fn in _ACTIVATIONS.items():
    _register_activation(_name, _fn)


@register_op("leaky_relu", inputs=["X"], outputs=["Out"])
def _leaky_relu(ctx, ins, attrs):
    alpha = attrs.get("alpha", 0.02)
    x = ins["X"][0]
    return {"Out": [jnp.where(x >= 0, x, alpha * x)]}


@register_op("elu", inputs=["X"], outputs=["Out"])
def _elu(ctx, ins, attrs):
    return {"Out": [jax.nn.elu(ins["X"][0], alpha=attrs.get("alpha", 1.0))]}


@register_op("gelu", inputs=["X"], outputs=["Out"])
def _gelu(ctx, ins, attrs):
    approx = attrs.get("approximate", False)
    return {"Out": [jax.nn.gelu(ins["X"][0], approximate=approx)]}


@register_op("hard_sigmoid", inputs=["X"], outputs=["Out"])
def _hard_sigmoid(ctx, ins, attrs):
    slope = attrs.get("slope", 0.2)
    offset = attrs.get("offset", 0.5)
    return {"Out": [jnp.clip(ins["X"][0] * slope + offset, 0.0, 1.0)]}


@register_op("swish", inputs=["X"], outputs=["Out"])
def _swish(ctx, ins, attrs):
    beta = attrs.get("beta", 1.0)
    x = ins["X"][0]
    return {"Out": [x * jax.nn.sigmoid(beta * x)]}


@register_op("relu6", inputs=["X"], outputs=["Out"])
def _relu6(ctx, ins, attrs):
    return {"Out": [jnp.clip(ins["X"][0], 0.0, attrs.get("threshold", 6.0))]}


@register_op("pow", inputs=["X"], outputs=["Out"])
def _pow(ctx, ins, attrs):
    return {"Out": [jnp.power(ins["X"][0], attrs.get("factor", 1.0))]}


@register_op("scale", inputs=["X"], outputs=["Out"])
def _scale(ctx, ins, attrs):
    x = ins["X"][0]
    s = attrs.get("scale", 1.0)
    b = attrs.get("bias", 0.0)
    if attrs.get("bias_after_scale", True):
        out = x * s + b
    else:
        out = (x + b) * s
    return {"Out": [out.astype(x.dtype)]}


@register_op("clip", inputs=["X"], outputs=["Out"])
def _clip(ctx, ins, attrs):
    return {"Out": [jnp.clip(ins["X"][0], attrs["min"], attrs["max"])]}


@register_op("softmax", inputs=["X"], outputs=["Out"])
def _softmax(ctx, ins, attrs):
    return {"Out": [jax.nn.softmax(ins["X"][0], axis=attrs.get("axis", -1))]}


@register_op("log_softmax", inputs=["X"], outputs=["Out"])
def _log_softmax(ctx, ins, attrs):
    return {"Out": [jax.nn.log_softmax(ins["X"][0], axis=attrs.get("axis", -1))]}


# -- matmul family -----------------------------------------------------------


@register_op("matmul", inputs=["X", "Y"], outputs=["Out"])
def _matmul(ctx, ins, attrs):
    """cf. matmul_op.cc: optional transposes + alpha, batched by leading dims.

    TPU note: this is the MXU path; executor-level precision policy decides
    bf16 accumulation (see amp).  We keep the contraction in one jnp.matmul
    so XLA tiles it onto the systolic array.
    """
    x, y = ins["X"][0], ins["Y"][0]
    tx = attrs.get("transpose_X", attrs.get("transpose_x", False))
    ty = attrs.get("transpose_Y", attrs.get("transpose_y", False))
    alpha = attrs.get("alpha", 1.0)
    if tx:
        x = jnp.swapaxes(x, -1, -2) if x.ndim > 1 else x
    if ty:
        y = jnp.swapaxes(y, -1, -2) if y.ndim > 1 else y
    out = jnp.matmul(x, y)
    if alpha != 1.0:
        out = out * alpha
    return {"Out": [out]}


@register_op("mul", inputs=["X", "Y"], outputs=["Out"])
def _mul(ctx, ins, attrs):
    """cf. mul_op.cc: flatten X to 2D at x_num_col_dims, Y at y_num_col_dims."""
    x, y = ins["X"][0], ins["Y"][0]
    xn = attrs.get("x_num_col_dims", 1)
    yn = attrs.get("y_num_col_dims", 1)
    x2 = x.reshape((-1, _prod(x.shape[xn:])))
    y2 = y.reshape((int(_prod(y.shape[:yn])), -1))
    out2 = jnp.matmul(x2, y2)
    out_shape = x.shape[:xn] + y.shape[yn:]
    return {"Out": [out2.reshape(out_shape)]}


def _prod(xs):
    r = 1
    for v in xs:
        r *= int(v)
    return r


@register_op("dot", inputs=["X", "Y"], outputs=["Out"])
def _dot(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    return {"Out": [jnp.sum(x * y, axis=-1, keepdims=True)]}


@register_op("matmul_bias_act", inputs=["X", "Y", "Bias"], outputs=["Out"])
def _matmul_bias_act(ctx, ins, attrs):
    """Fused-epilogue GEMM: matmul + bias add + activation in one op.

    The target of `fluid.ir.MatmulBiasActFusePass` (which rewrites the
    matmul/mul -> elementwise_add -> act chains the `unfused-epilogue`
    lint flags) and of `nn.functional.fused_linear`.  On TPU, plain
    untransposed 128-tileable shapes lower to the pallas fused-epilogue
    kernel (`ops.pallas.matmul.matmul_bias_act`, custom-VJP fused
    backward); everything else lowers to the jnp composition XLA fuses
    itself — numerically the same contraction either way (f32
    accumulation).

    attrs: ``act_type`` in {none, relu, tanh, gelu} (+``approximate``
    for the tanh gelu), and ONE of the two source-op attr conventions —
    mul-style ``x_num_col_dims``/``y_num_col_dims`` flattening, or
    matmul-style ``transpose_X``/``transpose_Y``/``alpha``."""
    import jax as _jax

    x, w = ins["X"][0], ins["Y"][0]
    bias = ins["Bias"][0] if ins.get("Bias") else None
    act = attrs.get("act_type", "none")
    if act not in ("none", "relu", "tanh", "gelu"):
        # validated on EVERY path: the batched/naive branches below
        # would otherwise silently return un-activated output for an
        # activation the pallas path raises on
        raise ValueError(
            "matmul_bias_act act_type must be one of "
            "('none', 'relu', 'tanh', 'gelu'), got %r" % act)
    approx = attrs.get("approximate", False)
    xn = attrs.get("x_num_col_dims")
    if xn is not None:                      # mul-style flatten
        yn = attrs.get("y_num_col_dims", 1)
        out_shape = x.shape[:xn] + w.shape[yn:]
        x2 = x.reshape((-1, _prod(x.shape[xn:])))
        w2 = w.reshape((int(_prod(w.shape[:yn])), -1))
        alpha, tx, ty = 1.0, False, False
    else:                                   # matmul-style
        tx = attrs.get("transpose_X", attrs.get("transpose_x", False))
        ty = attrs.get("transpose_Y", attrs.get("transpose_y", False))
        alpha = attrs.get("alpha", 1.0)
        x2 = jnp.swapaxes(x, -1, -2) if (tx and x.ndim > 1) else x
        w2 = jnp.swapaxes(w, -1, -2) if (ty and w.ndim > 1) else w
        out_shape = None                    # jnp.matmul shape as-is

    from ...ops import dispatch
    from ...ops.pallas.matmul import matmul_bias_act, naive_matmul_bias_act

    if _jax.default_backend() != "tpu":
        reason = "backend is not a TPU"
    elif not (x2.ndim == 2 and w2.ndim == 2 and not tx and not ty
              and alpha == 1.0):
        reason = "not a plain 2-D x @ w"
    elif x2.shape[0] % 128 or x2.shape[1] % 128 or w2.shape[1] % 128:
        reason = "M, K, N %s are not all multiples of 128" % (
            x2.shape + w2.shape[1:],)
    elif act == "gelu" and not approx:
        # the kernel's epilogue would need erf in VMEM; refuse here so a
        # user's step never meets the compiler's error
        reason = "exact gelu: the Pallas TPU lowering has no erf"
    else:
        reason = None
    dispatch.record("matmul_bias_act", "xla composition" if reason
                    else "pallas", reason or "fused GEMM shape rules met")
    if reason is None:
        out = matmul_bias_act(x2, w2, bias, activation=act,
                              approximate=approx)
    else:
        if x2.ndim == 2 and w2.ndim == 2 and alpha == 1.0:
            out = naive_matmul_bias_act(x2, w2, bias, activation=act,
                                        approximate=approx)
        else:
            out = jnp.matmul(x2, w2)
            if alpha != 1.0:
                out = out * alpha
            if bias is not None:
                out = out + bias
            if act == "gelu":
                out = _jax.nn.gelu(out, approximate=approx)
            elif act == "relu":
                out = _jax.nn.relu(out)
            elif act == "tanh":
                out = jnp.tanh(out)
    if out_shape is not None:
        out = out.reshape(out_shape)
    return {"Out": [out]}


@register_op("bmm", inputs=["X", "Y"], outputs=["Out"])
def _bmm(ctx, ins, attrs):
    return {"Out": [jnp.matmul(ins["X"][0], ins["Y"][0])]}


@register_op("addmm", inputs=["Input", "X", "Y"], outputs=["Out"])
def _addmm(ctx, ins, attrs):
    alpha = attrs.get("Alpha", 1.0)
    beta = attrs.get("Beta", 1.0)
    return {
        "Out": [beta * ins["Input"][0] + alpha * jnp.matmul(ins["X"][0], ins["Y"][0])]
    }


@register_op("sum", inputs=["X"], outputs=["Out"])
def _sum(ctx, ins, attrs):
    """Multi-input elementwise add (grad accumulation; cf. sum_op.cc)."""
    xs = ins["X"]
    out = xs[0]
    for x in xs[1:]:
        out = out + x
    return {"Out": [out]}


# ---------------------------------------------------------------------------
# 2.x math tail (reference elementwise_fmax/fmin, remainder, heaviside,
# logit, nansum/nanmean, amax/amin, median/quantile, std/var ops)
# ---------------------------------------------------------------------------


@register_op("elementwise_fmax", inputs=["X", "Y"], outputs=["Out"])
def _fmax(ctx, ins, attrs):
    return {"Out": [jnp.fmax(ins["X"][0], ins["Y"][0])]}


@register_op("elementwise_fmin", inputs=["X", "Y"], outputs=["Out"])
def _fmin(ctx, ins, attrs):
    return {"Out": [jnp.fmin(ins["X"][0], ins["Y"][0])]}


@register_op("remainder", inputs=["X", "Y"], outputs=["Out"], grad=None)
def _remainder(ctx, ins, attrs):
    return {"Out": [jnp.remainder(ins["X"][0], ins["Y"][0])]}


@register_op("heaviside", inputs=["X", "Y"], outputs=["Out"], grad=None)
def _heaviside(ctx, ins, attrs):
    return {"Out": [jnp.heaviside(ins["X"][0], ins["Y"][0])]}


@register_op("logit", inputs=["X"], outputs=["Out"])
def _logit(ctx, ins, attrs):
    eps = float(attrs.get("eps", 0.0))
    x = ins["X"][0]
    if eps > 0:
        x = jnp.clip(x, eps, 1.0 - eps)
    return {"Out": [jnp.log(x) - jnp.log1p(-x)]}


@register_op("logaddexp", inputs=["X", "Y"], outputs=["Out"])
def _logaddexp(ctx, ins, attrs):
    return {"Out": [jnp.logaddexp(ins["X"][0], ins["Y"][0])]}


def _axis_of(attrs):
    a = attrs.get("axis", attrs.get("dim", None))
    if a in (None, [], ()):
        return None
    return tuple(a) if isinstance(a, (list, tuple)) else int(a)


@register_op("nansum", inputs=["X"], outputs=["Out"], grad=None)
def _nansum(ctx, ins, attrs):
    return {"Out": [jnp.nansum(ins["X"][0], axis=_axis_of(attrs),
                               keepdims=bool(attrs.get("keep_dim", False)))]}


@register_op("nanmean", inputs=["X"], outputs=["Out"], grad=None)
def _nanmean(ctx, ins, attrs):
    return {"Out": [jnp.nanmean(ins["X"][0], axis=_axis_of(attrs),
                                keepdims=bool(attrs.get("keep_dim", False)))]}


@register_op("reduce_amax", inputs=["X"], outputs=["Out"], grad=None)
def _amax(ctx, ins, attrs):
    return {"Out": [jnp.amax(ins["X"][0], axis=_axis_of(attrs),
                             keepdims=bool(attrs.get("keep_dim", False)))]}


@register_op("reduce_amin", inputs=["X"], outputs=["Out"], grad=None)
def _amin(ctx, ins, attrs):
    return {"Out": [jnp.amin(ins["X"][0], axis=_axis_of(attrs),
                             keepdims=bool(attrs.get("keep_dim", False)))]}


@register_op("median", inputs=["X"], outputs=["Out"], grad=None)
def _median(ctx, ins, attrs):
    return {"Out": [jnp.median(ins["X"][0], axis=_axis_of(attrs),
                               keepdims=bool(attrs.get("keep_dim", False)))]}


@register_op("quantile", inputs=["X"], outputs=["Out"], grad=None)
def _quantile(ctx, ins, attrs):
    q = attrs["q"]
    return {"Out": [jnp.quantile(
        ins["X"][0], jnp.asarray(q), axis=_axis_of(attrs),
        keepdims=bool(attrs.get("keep_dim", False)))]}


@register_op("reduce_std", inputs=["X"], outputs=["Out"])
def _std(ctx, ins, attrs):
    return {"Out": [jnp.std(
        ins["X"][0], axis=_axis_of(attrs),
        ddof=1 if attrs.get("unbiased", True) else 0,
        keepdims=bool(attrs.get("keep_dim", False)))]}


@register_op("reduce_var", inputs=["X"], outputs=["Out"])
def _var(ctx, ins, attrs):
    return {"Out": [jnp.var(
        ins["X"][0], axis=_axis_of(attrs),
        ddof=1 if attrs.get("unbiased", True) else 0,
        keepdims=bool(attrs.get("keep_dim", False)))]}


@register_op("brelu", inputs=["X"], outputs=["Out"])
def _brelu(ctx, ins, attrs):
    lo = float(attrs.get("t_min", 0.0))
    hi = float(attrs.get("t_max", 24.0))
    return {"Out": [jnp.clip(ins["X"][0], lo, hi)]}


@register_op("soft_relu", inputs=["X"], outputs=["Out"])
def _soft_relu(ctx, ins, attrs):
    t = float(attrs.get("threshold", 40.0))
    x = jnp.clip(ins["X"][0], -t, t)
    return {"Out": [jnp.log1p(jnp.exp(x))]}


@register_op("logcumsumexp", inputs=["X"], outputs=["Out"])
def _logcumsumexp(ctx, ins, attrs):
    axis = int(attrs.get("axis", -1))
    x = ins["X"][0]
    m = jnp.max(x, axis=axis, keepdims=True)
    return {"Out": [jnp.log(jnp.cumsum(jnp.exp(x - m), axis=axis)) + m]}


@register_op("gcd", inputs=["X", "Y"], outputs=["Out"], grad=None)
def _gcd(ctx, ins, attrs):
    return {"Out": [jnp.gcd(ins["X"][0], ins["Y"][0])]}


@register_op("lcm", inputs=["X", "Y"], outputs=["Out"], grad=None)
def _lcm(ctx, ins, attrs):
    return {"Out": [jnp.lcm(ins["X"][0], ins["Y"][0])]}


@register_op("addcmul", inputs=["Input", "Tensor1", "Tensor2"],
             outputs=["Out"])
def _addcmul(ctx, ins, attrs):
    v = float(attrs.get("value", 1.0))
    return {"Out": [ins["Input"][0]
                    + v * ins["Tensor1"][0] * ins["Tensor2"][0]]}


@register_op("lerp", inputs=["X", "Y", "Weight"], outputs=["Out"])
def _lerp(ctx, ins, attrs):
    x, y, w = ins["X"][0], ins["Y"][0], ins["Weight"][0]
    return {"Out": [x + w * (y - x)]}


@register_op("i0", inputs=["X"], outputs=["Out"])
def _i0(ctx, ins, attrs):
    from jax.scipy.special import i0

    return {"Out": [i0(ins["X"][0])]}


@register_op("i1", inputs=["X"], outputs=["Out"])
def _i1(ctx, ins, attrs):
    from jax.scipy.special import i1

    return {"Out": [i1(ins["X"][0])]}


@register_op("isinf", inputs=["X"], outputs=["Out"], grad=None)
def _isinf(ctx, ins, attrs):
    return {"Out": [jnp.isinf(ins["X"][0])]}


@register_op("l1_norm", inputs=["X"], outputs=["Out"])
def _l1_norm(ctx, ins, attrs):
    return {"Out": [jnp.sum(jnp.abs(ins["X"][0]))]}


@register_op("frobenius_norm", inputs=["X"], outputs=["Out"])
def _frobenius_norm(ctx, ins, attrs):
    axis = attrs.get("axis")
    return {"Out": [jnp.sqrt(jnp.sum(
        ins["X"][0] ** 2,
        axis=tuple(axis) if axis else None,
        keepdims=bool(attrs.get("keep_dim", False))))]}


@register_op("modified_huber_loss", inputs=["X", "Y"],
             outputs=["Out", "IntermediateVal"], no_grad_slots=("Y",))
def _modified_huber_loss(ctx, ins, attrs):
    """cf. modified_huber_loss_op.cc: binary classification loss on
    margin z = (2y-1)*x: max(0,1-z)^2 for z >= -1, else -4z."""
    x = ins["X"][0].reshape(-1)
    y = ins["Y"][0].reshape(-1).astype(x.dtype)
    z = (2.0 * y - 1.0) * x
    loss = jnp.where(z >= -1.0, jnp.maximum(0.0, 1.0 - z) ** 2, -4.0 * z)
    return {"Out": [loss[:, None]], "IntermediateVal": [z[:, None]]}


@register_op("clip_by_norm", inputs=["X"], outputs=["Out"])
def _clip_by_norm(ctx, ins, attrs):
    x = ins["X"][0]
    mx = float(attrs["max_norm"])
    norm = jnp.sqrt(jnp.sum(x * x))
    return {"Out": [jnp.where(norm > mx, x * (mx / jnp.maximum(norm, 1e-12)),
                              x)]}
