"""Tensor manipulation ops: reshape/transpose/concat/..., fill, cast, compare.

Capability parity: reference `paddle/fluid/operators/` reshape_op.cc,
transpose_op.cc, concat_op.cc, split_op.cc, slice_op.cc, cast_op.cc,
fill_constant_op.cc, gather_op.cc, one_hot_op.cc, compare ops in
controlflow/, assign_op.cc, expand_op.cc, stack_op.cc.
"""

import jax
import jax.numpy as jnp

from ..core.dtypes import to_jnp
from ..core.registry import register_op


@register_op("reshape2", inputs=["X"], outputs=["Out"])
def _reshape(ctx, ins, attrs):
    x = ins["X"][0]
    shape = list(attrs["shape"])
    # paddle semantics: 0 means "copy input dim", -1 inferred
    shape = [x.shape[i] if s == 0 else s for i, s in enumerate(shape)]
    return {"Out": [x.reshape(shape)]}


register_op("reshape", inputs=["X"], outputs=["Out"])(_reshape)


@register_op("transpose2", inputs=["X"], outputs=["Out"])
def _transpose(ctx, ins, attrs):
    return {"Out": [jnp.transpose(ins["X"][0], attrs["axis"])]}


register_op("transpose", inputs=["X"], outputs=["Out"])(_transpose)


@register_op("flatten2", inputs=["X"], outputs=["Out"])
def _flatten(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", 1)
    lead = 1
    for s in x.shape[:axis]:
        lead *= int(s)
    return {"Out": [x.reshape((lead, -1))]}


register_op("flatten", inputs=["X"], outputs=["Out"])(_flatten)


@register_op("flatten_contiguous_range", inputs=["X"], outputs=["Out"])
def _flatten_range(ctx, ins, attrs):
    x = ins["X"][0]
    start = attrs.get("start_axis", 1)
    stop = attrs.get("stop_axis", -1)
    if stop < 0:
        stop += x.ndim
    mid = 1
    for s in x.shape[start : stop + 1]:
        mid *= int(s)
    return {"Out": [x.reshape(x.shape[:start] + (mid,) + x.shape[stop + 1 :])]}


@register_op("squeeze2", inputs=["X"], outputs=["Out"])
def _squeeze(ctx, ins, attrs):
    axes = attrs.get("axes", [])
    x = ins["X"][0]
    if not axes:
        return {"Out": [jnp.squeeze(x)]}
    axes = tuple(a if a >= 0 else a + x.ndim for a in axes)
    return {"Out": [jnp.squeeze(x, axis=axes)]}


register_op("squeeze", inputs=["X"], outputs=["Out"])(_squeeze)


@register_op("unsqueeze2", inputs=["X"], outputs=["Out"])
def _unsqueeze(ctx, ins, attrs):
    x = ins["X"][0]
    for a in sorted(attrs["axes"]):
        x = jnp.expand_dims(x, a)
    return {"Out": [x]}


register_op("unsqueeze", inputs=["X"], outputs=["Out"])(_unsqueeze)


@register_op("concat", inputs=["X"], outputs=["Out"])
def _concat(ctx, ins, attrs):
    return {"Out": [jnp.concatenate(ins["X"], axis=attrs.get("axis", 0))]}


@register_op("split", inputs=["X"], outputs=["Out"])
def _split(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", 0)
    num = attrs.get("num", 0)
    sections = attrs.get("sections", [])
    if sections:
        idx = []
        acc = 0
        for s in sections[:-1]:
            acc += s
            idx.append(acc)
        outs = jnp.split(x, idx, axis=axis)
    else:
        outs = jnp.split(x, num, axis=axis)
    return {"Out": list(outs)}


@register_op("stack", inputs=["X"], outputs=["Y"])
def _stack(ctx, ins, attrs):
    return {"Y": [jnp.stack(ins["X"], axis=attrs.get("axis", 0))]}


@register_op("unstack", inputs=["X"], outputs=["Y"])
def _unstack(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", 0)
    n = x.shape[axis]
    outs = [jnp.squeeze(a, axis) for a in jnp.split(x, n, axis=axis)]
    return {"Y": outs}


@register_op("slice", inputs=["Input"], outputs=["Out"])
def _slice(ctx, ins, attrs):
    x = ins["Input"][0]
    axes = attrs["axes"]
    starts = attrs["starts"]
    ends = attrs["ends"]
    idx = [slice(None)] * x.ndim
    for a, s, e in zip(axes, starts, ends):
        idx[a] = slice(s, e)
    out = x[tuple(idx)]
    for a in sorted(attrs.get("decrease_axis", []), reverse=True):
        out = jnp.squeeze(out, a)
    return {"Out": [out]}


@register_op("strided_slice", inputs=["Input"], outputs=["Out"])
def _strided_slice(ctx, ins, attrs):
    x = ins["Input"][0]
    idx = [slice(None)] * x.ndim
    for a, s, e, st in zip(attrs["axes"], attrs["starts"], attrs["ends"], attrs["strides"]):
        idx[a] = slice(s, e, st)
    return {"Out": [x[tuple(idx)]]}


@register_op("cast", inputs=["X"], outputs=["Out"])
def _cast(ctx, ins, attrs):
    return {"Out": [ins["X"][0].astype(to_jnp(attrs["out_dtype"]))]}


@register_op("assign", inputs=["X"], outputs=["Out"])
def _assign(ctx, ins, attrs):
    return {"Out": [ins["X"][0]]}


@register_op("fill_constant", inputs=[], outputs=["Out"])
def _fill_constant(ctx, ins, attrs):
    shape = tuple(attrs.get("shape", []))
    return {"Out": [jnp.full(shape, attrs["value"], dtype=to_jnp(attrs.get("dtype", "float32")))]}


@register_op("assign_value", inputs=[], outputs=["Out"], grad=None)
def _assign_value(ctx, ins, attrs):
    import numpy as np

    arr = np.array(attrs["values"], dtype=to_jnp(attrs.get("dtype", "float32"))).reshape(
        attrs["shape"]
    )
    return {"Out": [jnp.asarray(arr)]}


@register_op("fill_constant_batch_size_like", inputs=["Input"], outputs=["Out"], grad=None)
def _fill_cbsl(ctx, ins, attrs):
    x = ins["Input"][0]
    shape = list(attrs["shape"])
    in_idx = attrs.get("input_dim_idx", 0)
    out_idx = attrs.get("output_dim_idx", 0)
    shape[out_idx] = x.shape[in_idx]
    return {"Out": [jnp.full(tuple(shape), attrs["value"], dtype=to_jnp(attrs.get("dtype", "float32")))]}


@register_op("fill_zeros_like", inputs=["X"], outputs=["Out"])
def _fill_zeros_like(ctx, ins, attrs):
    return {"Out": [jnp.zeros_like(ins["X"][0])]}


@register_op("fill_any_like", inputs=["X"], outputs=["Out"])
def _fill_any_like(ctx, ins, attrs):
    dtype = attrs.get("dtype")
    out = jnp.full_like(ins["X"][0], attrs["value"], dtype=to_jnp(dtype) if dtype else None)
    return {"Out": [out]}


@register_op("gather", inputs=["X", "Index"], outputs=["Out"], no_grad_slots=("Index",))
def _gather(ctx, ins, attrs):
    return {"Out": [jnp.take(ins["X"][0], ins["Index"][0], axis=attrs.get("axis", 0))]}


@register_op("gather_nd", inputs=["X", "Index"], outputs=["Out"], no_grad_slots=("Index",))
def _gather_nd(ctx, ins, attrs):
    x, index = ins["X"][0], ins["Index"][0]
    return {"Out": [x[tuple(index[..., i] for i in range(index.shape[-1]))]]}


@register_op("scatter", inputs=["X", "Ids", "Updates"], outputs=["Out"], no_grad_slots=("Ids",))
def _scatter(ctx, ins, attrs):
    x, ids, upd = ins["X"][0], ins["Ids"][0], ins["Updates"][0]
    if attrs.get("overwrite", True):
        return {"Out": [x.at[ids].set(upd)]}
    return {"Out": [x.at[ids].add(upd)]}


@register_op("index_select", inputs=["X", "Index"], outputs=["Out"], no_grad_slots=("Index",))
def _index_select(ctx, ins, attrs):
    return {"Out": [jnp.take(ins["X"][0], ins["Index"][0], axis=attrs.get("dim", 0))]}


@register_op("one_hot", inputs=["X"], outputs=["Out"], grad=None)
def _one_hot(ctx, ins, attrs):
    x = ins["X"][0]
    depth = attrs["depth"]
    if x.ndim >= 2 and x.shape[-1] == 1:
        x = jnp.squeeze(x, -1)
    return {"Out": [jax.nn.one_hot(x, depth, dtype=jnp.float32)]}


register_op("one_hot_v2", inputs=["X"], outputs=["Out"], grad=None)(_one_hot)


@register_op("expand", inputs=["X"], outputs=["Out"])
def _expand(ctx, ins, attrs):
    x = ins["X"][0]
    times = attrs["expand_times"]
    return {"Out": [jnp.tile(x, times)]}


@register_op("expand_as", inputs=["X", "Y"], outputs=["Out"], no_grad_slots=("Y",))
def _expand_as(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    return {"Out": [jnp.broadcast_to(x, y.shape)]}


@register_op("tile", inputs=["X"], outputs=["Out"])
def _tile(ctx, ins, attrs):
    return {"Out": [jnp.tile(ins["X"][0], attrs["repeat_times"])]}


@register_op("pad", inputs=["X"], outputs=["Out"])
def _pad(ctx, ins, attrs):
    x = ins["X"][0]
    p = attrs["paddings"]
    pairs = [(p[2 * i], p[2 * i + 1]) for i in range(x.ndim)]
    return {"Out": [jnp.pad(x, pairs, constant_values=attrs.get("pad_value", 0.0))]}


@register_op("arange", inputs=[], outputs=["Out"], grad=None)
def _arange(ctx, ins, attrs):
    return {
        "Out": [
            jnp.arange(
                attrs["start"], attrs["end"], attrs.get("step", 1),
                dtype=to_jnp(attrs.get("dtype", "int64")),
            )
        ]
    }


@register_op("linspace", inputs=[], outputs=["Out"], grad=None)
def _linspace(ctx, ins, attrs):
    return {
        "Out": [
            jnp.linspace(
                attrs["start"], attrs["stop"], attrs["num"],
                dtype=to_jnp(attrs.get("dtype", "float32")),
            )
        ]
    }


# -- comparison / logical (cf. operators/controlflow/compare_op.cc) ----------

def _register_compare(name, fn):
    @register_op(name, inputs=["X", "Y"], outputs=["Out"], grad=None)
    def _lower(ctx, ins, attrs, fn=fn):
        return {"Out": [fn(ins["X"][0], ins["Y"][0])]}


_register_compare("equal", jnp.equal)
_register_compare("not_equal", jnp.not_equal)
_register_compare("less_than", jnp.less)
_register_compare("less_equal", jnp.less_equal)
_register_compare("greater_than", jnp.greater)
_register_compare("greater_equal", jnp.greater_equal)
_register_compare("logical_and", jnp.logical_and)
_register_compare("logical_or", jnp.logical_or)
_register_compare("logical_xor", jnp.logical_xor)


@register_op("logical_not", inputs=["X"], outputs=["Out"], grad=None)
def _logical_not(ctx, ins, attrs):
    return {"Out": [jnp.logical_not(ins["X"][0])]}


@register_op("isfinite", inputs=["X"], outputs=["Out"], grad=None)
def _isfinite(ctx, ins, attrs):
    return {"Out": [jnp.all(jnp.isfinite(ins["X"][0]))]}


@register_op("isnan", inputs=["X"], outputs=["Out"], grad=None)
def _isnan(ctx, ins, attrs):
    return {"Out": [jnp.isnan(ins["X"][0])]}


@register_op("where", inputs=["Condition", "X", "Y"], outputs=["Out"], no_grad_slots=("Condition",))
def _where(ctx, ins, attrs):
    return {"Out": [jnp.where(ins["Condition"][0], ins["X"][0], ins["Y"][0])]}


@register_op("shape", inputs=["Input"], outputs=["Out"], grad=None)
def _shape(ctx, ins, attrs):
    return {"Out": [jnp.array(ins["Input"][0].shape, dtype=jnp.int32)]}


@register_op("triu", inputs=["X"], outputs=["Out"])
def _triu(ctx, ins, attrs):
    return {"Out": [jnp.triu(ins["X"][0], k=attrs.get("diagonal", 0))]}


@register_op("tril", inputs=["X"], outputs=["Out"])
def _tril(ctx, ins, attrs):
    return {"Out": [jnp.tril(ins["X"][0], k=attrs.get("diagonal", 0))]}


@register_op("cumsum", inputs=["X"], outputs=["Out"])
def _cumsum(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", -1)
    out = jnp.cumsum(x, axis=axis)
    if attrs.get("reverse", False):
        out = jnp.flip(jnp.cumsum(jnp.flip(x, axis), axis=axis), axis)
    if attrs.get("exclusive", False):
        out = out - x
    return {"Out": [out]}


@register_op("increment", inputs=["X"], outputs=["Out"], grad=None)
def _increment(ctx, ins, attrs):
    x = ins["X"][0]
    # preserve x's dtype: int counters must not be promoted to float
    return {"Out": [x + jnp.asarray(attrs.get("step", 1.0), x.dtype)]}


@register_op("print", inputs=["In"], outputs=["Out"])
def _print(ctx, ins, attrs):
    """Periodic fetch printer (cf. reference operators/print_op.cc /
    layers.Print): passes X through and prints message + summarized values
    from inside the compiled program via jax.debug.print (the TPU-safe
    analogue of the reference's host-side tensor printer)."""
    import jax

    x = ins["In"][0]
    message = str(attrs.get("message", ""))
    summarize = int(attrs.get("summarize", 20))
    show_shape = bool(attrs.get("print_tensor_shape", True))
    shape = tuple(x.shape)
    flat = x.reshape(-1)
    head = flat[: summarize if summarize > 0 else flat.shape[0]]

    # host callback, NOT jax.debug.print: the user message is arbitrary
    # text (its braces must not reach a format-string parser)
    def _emit(v):
        import numpy as _np

        if show_shape:
            print("%s shape=%s values=%s" % (message, shape, _np.asarray(v)),
                  flush=True)
        else:
            print("%s %s" % (message, _np.asarray(v)), flush=True)

    jax.debug.callback(_emit, head)
    return {"Out": [x]}


# -- tensor array (LoDTensorArray capability, static-shape redesign) ---------
# Capability parity: reference LoDTensorArray + controlflow
# `write_to_array`/`read_from_array` ops (`operators/controlflow/
# lod_array_ops` family, `lod_tensor_array.h`).  TPU-first: the array is a
# PREALLOCATED [capacity, ...] dense tensor (XLA has no growable storage);
# writes are dynamic_update_slice, reads dynamic_slice — both work with a
# runtime index inside while_loop bodies.


@register_op("tensor_array_write", inputs=["Array", "I", "X"],
             outputs=["Out"], no_grad_slots=("I",))
def _tensor_array_write(ctx, ins, attrs):
    arr, i, x = ins["Array"][0], ins["I"][0], ins["X"][0]
    import jax

    idx = i.reshape(()).astype(jnp.int32)
    out = jax.lax.dynamic_update_slice(
        arr, x[None].astype(arr.dtype),
        (idx,) + (jnp.int32(0),) * (arr.ndim - 1),
    )
    # a write past capacity-1 is CLAMPED (dynamic_update_slice semantics)
    # where the reference grows the array; under FLAGS_check_nan_inf
    # poison the overflowing write so the divergence is detectable instead
    # of silently corrupting the last slot
    from ..flags import get_flags

    if (get_flags(["FLAGS_check_nan_inf"])["FLAGS_check_nan_inf"]
            and jnp.issubdtype(out.dtype, jnp.floating)):
        out = jnp.where(idx < arr.shape[0], out,
                        jnp.full_like(out, jnp.nan))
    return {"Out": [out]}


@register_op("tensor_array_read", inputs=["Array", "I"], outputs=["Out"],
             no_grad_slots=("I",))
def _tensor_array_read(ctx, ins, attrs):
    arr, i = ins["Array"][0], ins["I"][0]
    import jax

    idx = i.reshape(()).astype(jnp.int32)
    out = jax.lax.dynamic_slice(
        arr, (idx,) + (jnp.int32(0),) * (arr.ndim - 1),
        (1,) + arr.shape[1:],
    )
    return {"Out": [out[0]]}


# ---------------------------------------------------------------------------
# tensor/loss breadth tail (reference crop_tensor_op.cc, unbind_op.cc,
# size_op.cc, gather_tree_op.cc, partial_sum/concat, center_loss_op.cc,
# teacher_student_sigmoid_loss_op.cc, fsp_op.cc,
# squared_l2_distance_op.cc)
# ---------------------------------------------------------------------------


@register_op("crop_tensor", inputs=["X"], outputs=["Out"])
def _crop_tensor(ctx, ins, attrs):
    x = ins["X"][0]
    offsets = attrs.get("offsets") or [0] * x.ndim
    shape = attrs["shape"]
    shape = [x.shape[i] - offsets[i] if s in (-1, 0) else s
             for i, s in enumerate(shape)]
    import jax

    return {"Out": [jax.lax.dynamic_slice(x, tuple(offsets), tuple(shape))]}


@register_op("unbind", inputs=["X"], outputs=["Out"], grad=None)
def _unbind(ctx, ins, attrs):
    x = ins["X"][0]
    axis = int(attrs.get("axis", 0))
    n = x.shape[axis]
    return {"Out": [jnp.squeeze(s, axis)
                    for s in jnp.split(x, n, axis=axis)]}


@register_op("size", inputs=["Input"], outputs=["Out"], grad=None)
def _size(ctx, ins, attrs):
    import numpy as _np

    return {"Out": [jnp.asarray(int(_np.prod(ins["Input"][0].shape)),
                                jnp.int64)]}


@register_op("gather_tree", inputs=["Ids", "Parents"], outputs=["Out"],
             grad=None)
def _gather_tree(ctx, ins, attrs):
    """cf. gather_tree_op.cc (beam search backtrace): walk parents from
    the last step to recover full beams."""
    import jax

    ids, parents = ins["Ids"][0], ins["Parents"][0]  # [T, B, W]
    T = ids.shape[0]
    beams = jnp.arange(ids.shape[2])[None, :].repeat(ids.shape[1], 0)

    def step(beam, t):
        out = jnp.take_along_axis(ids[t], beam, axis=1)
        prev = jnp.take_along_axis(parents[t], beam, axis=1)
        return prev, out

    _, outs = jax.lax.scan(step, beams, jnp.arange(T - 1, -1, -1))
    return {"Out": [outs[::-1]]}


@register_op("masked_fill", inputs=["X", "Mask"], outputs=["Out"],
             no_grad_slots=("Mask",))
def _masked_fill(ctx, ins, attrs):
    x, m = ins["X"][0], ins["Mask"][0]
    return {"Out": [jnp.where(m.astype(bool), jnp.asarray(
        attrs.get("value", 0.0), x.dtype), x)]}


def _partial_cols(ins, attrs):
    """Column windows for partial_sum/partial_concat.  length < 0 means
    'to the end'; a NEGATIVE start whose window reaches the axis end
    also slices to the end (python end=0 would mean position 0)."""
    start = int(attrs.get("start_index", 0))
    length = int(attrs.get("length", -1))
    parts = []
    for x in ins["X"]:
        if length < 0 or (start < 0 and start + length >= 0):
            end = x.shape[1]
        else:
            end = start + length
        parts.append(x[:, start:end])
    return parts


@register_op("partial_sum", inputs=["X"], outputs=["Out"])
def _partial_sum(ctx, ins, attrs):
    return {"Out": [sum(_partial_cols(ins, attrs))]}


@register_op("partial_concat", inputs=["X"], outputs=["Out"])
def _partial_concat(ctx, ins, attrs):
    return {"Out": [jnp.concatenate(_partial_cols(ins, attrs), axis=1)]}


@register_op("center_loss",
             inputs=["X", "Label", "Centers", "CenterUpdateRate"],
             outputs=["Loss", "SampleCenterDiff", "CentersOut"],
             no_grad_slots=("Label", "Centers", "CenterUpdateRate"),
             stateful_out_slots=("CentersOut",))
def _center_loss(ctx, ins, attrs):
    """cf. center_loss_op.cc: pull features toward running class centers;
    centers update by the mean diff of their batch members."""
    x = ins["X"][0]                     # [N, D]
    label = ins["Label"][0].reshape(-1)
    centers = ins["Centers"][0]         # [C, D]
    alpha = ins["CenterUpdateRate"][0].reshape(-1)[0]
    diff = x - centers[label]
    loss = 0.5 * jnp.sum(diff * diff, axis=1, keepdims=True)
    if attrs.get("need_update", True):
        cnt = jnp.zeros((centers.shape[0],), jnp.float32).at[label].add(1.0)
        upd = jnp.zeros_like(centers).at[label].add(diff)
        centers = centers + alpha * upd / (cnt[:, None] + 1.0)
    return {"Loss": [loss], "SampleCenterDiff": [diff],
            "CentersOut": [centers]}


@register_op("dice_loss", inputs=["X", "Label"], outputs=["Out"],
             no_grad_slots=("Label",))
def _dice_loss(ctx, ins, attrs):
    """cf. layers/loss dice_loss: 1 - 2|X∩L| / (|X|+|L|) per batch row."""
    x = ins["X"][0]
    label = ins["Label"][0].astype(x.dtype)
    eps = float(attrs.get("epsilon", 1e-5))
    red = tuple(range(1, x.ndim))
    inter = jnp.sum(x * label, axis=red)
    union = jnp.sum(x, axis=red) + jnp.sum(label, axis=red)
    return {"Out": [1.0 - (2 * inter + eps) / (union + eps)]}


@register_op("teacher_student_sigmoid_loss", inputs=["X", "Label"],
             outputs=["Y"], no_grad_slots=("Label",))
def _ts_sigmoid_loss(ctx, ins, attrs):
    """cf. teacher_student_sigmoid_loss_op.cc (CTR distillation)."""
    x = ins["X"][0].reshape(-1)
    label = ins["Label"][0].reshape(-1)
    soft_max_up = float(attrs.get("soft_max_up_bound", 15.0))
    soft_max_lo = float(attrs.get("soft_max_lower_bound", -15.0))
    xc = jnp.clip(x, soft_max_lo, soft_max_up)
    # teacher part (label in (0,1)): sigmoid CE against the soft label;
    # student part (label 0/1): plain logistic loss
    ce = jnp.maximum(xc, 0) - xc * label + jnp.log1p(jnp.exp(-jnp.abs(xc)))
    return {"Y": [ce[:, None]]}


@register_op("npair_loss", inputs=["Anchor", "Positive", "Labels"],
             outputs=["Out"], no_grad_slots=("Labels",))
def _npair_loss(ctx, ins, attrs):
    """cf. layers npair_loss: cross-entropy over anchor-positive
    similarities + L2 reg."""
    import jax

    a = ins["Anchor"][0]
    p = ins["Positive"][0]
    labels = ins["Labels"][0].reshape(-1)
    l2 = float(attrs.get("l2_reg", 0.002))
    sim = a @ p.T                       # [N, N]
    t = (labels[:, None] == labels[None, :]).astype(a.dtype)
    t = t / jnp.sum(t, axis=1, keepdims=True)
    xe = -jnp.sum(t * jax.nn.log_softmax(sim, axis=1), axis=1)
    reg = l2 * (jnp.sum(a * a) + jnp.sum(p * p)) / a.shape[0]
    return {"Out": [jnp.mean(xe) + reg]}


@register_op("fsp", inputs=["X", "Y"], outputs=["Out"])
def _fsp(ctx, ins, attrs):
    """cf. fsp_op.cc (distillation flow matrix): per-sample normalized
    Gram matrix between two feature maps."""
    x, y = ins["X"][0], ins["Y"][0]     # [N, C1, H, W], [N, C2, H, W]
    n, c1, h, w = x.shape
    c2 = y.shape[1]
    xf = x.reshape(n, c1, h * w)
    yf = y.reshape(n, c2, h * w)
    return {"Out": [jnp.einsum("nch,ndh->ncd", xf, yf) / (h * w)]}


@register_op("squared_l2_distance", inputs=["X", "Y"],
             outputs=["Out", "sub_result"])
def _squared_l2_distance(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    sub = x - y
    red = tuple(range(1, sub.ndim))
    return {"Out": [jnp.sum(sub * sub, axis=red, keepdims=False)[:, None]],
            "sub_result": [sub]}


@register_op("take", inputs=["X", "Index"], outputs=["Out"],
             no_grad_slots=("Index",))
def _take(ctx, ins, attrs):
    """cf. take (2.x): flat-index gather with clip/wrap modes."""
    x, idx = ins["X"][0].reshape(-1), ins["Index"][0]
    mode = attrs.get("mode", "raise")
    n = x.shape[0]
    if mode == "wrap":
        idx = jnp.mod(idx, n)
    else:  # raise / clip both clamp under jit (no host asserts)
        idx = jnp.clip(idx, -n, n - 1)
    return {"Out": [x[idx.astype(jnp.int32)]]}


@register_op("index_add", inputs=["X", "Index", "AddValue"],
             outputs=["Out"], no_grad_slots=("Index",))
def _index_add(ctx, ins, attrs):
    axis = int(attrs.get("axis", 0))
    x, idx, v = ins["X"][0], ins["Index"][0], ins["AddValue"][0]
    x = jnp.moveaxis(x, axis, 0)
    v = jnp.moveaxis(v, axis, 0)
    out = x.at[idx.astype(jnp.int32)].add(v)
    return {"Out": [jnp.moveaxis(out, 0, axis)]}


@register_op("index_put", inputs=["X", "Index", "Value"],
             outputs=["Out"], no_grad_slots=("Index",))
def _index_put(ctx, ins, attrs):
    x, v = ins["X"][0], ins["Value"][0]
    idx = tuple(i.astype(jnp.int32) for i in ins["Index"])
    if attrs.get("accumulate", False):
        return {"Out": [x.at[idx].add(v)]}
    return {"Out": [x.at[idx].set(v)]}


@register_op("fill_diagonal", inputs=["X"], outputs=["Out"], grad=None)
def _fill_diagonal(ctx, ins, attrs):
    x = ins["X"][0]
    v = attrs.get("value", 0.0)
    n = min(x.shape[-2], x.shape[-1])
    i = jnp.arange(n)
    return {"Out": [x.at[..., i, i].set(jnp.asarray(v, x.dtype))]}


@register_op("diagonal", inputs=["Input"], outputs=["Out"])
def _diagonal(ctx, ins, attrs):
    return {"Out": [jnp.diagonal(
        ins["Input"][0], offset=int(attrs.get("offset", 0)),
        axis1=int(attrs.get("axis1", 0)),
        axis2=int(attrs.get("axis2", 1)))]}


@register_op("rot90", inputs=["X"], outputs=["Out"])
def _rot90(ctx, ins, attrs):
    axes = attrs.get("axes", [0, 1])
    return {"Out": [jnp.rot90(ins["X"][0], k=int(attrs.get("k", 1)),
                              axes=tuple(axes))]}


@register_op("pad_constant_like", inputs=["X", "Y"], outputs=["Out"],
             no_grad_slots=("X",))
def _pad_constant_like(ctx, ins, attrs):
    """cf. pad_constant_like_op.cc: pad Y up to X's shape."""
    x, y = ins["X"][0], ins["Y"][0]
    cfg = tuple((0, int(a) - int(b)) for a, b in zip(x.shape, y.shape))
    return {"Out": [jnp.pad(y, cfg, constant_values=float(
        attrs.get("pad_value", 0.0)))]}


@register_op("shuffle_batch", inputs=["X"], outputs=["Out", "ShuffleIdx"],
             needs_rng=True, grad=None)
def _shuffle_batch(ctx, ins, attrs):
    """cf. shuffle_batch_op.cc: random permutation of dim-0 rows."""
    import jax

    x = ins["X"][0]
    perm = jax.random.permutation(ctx.rng(), x.shape[0])
    return {"Out": [x[perm]], "ShuffleIdx": [perm.astype(jnp.int64)]}


@register_op("sampling_id", inputs=["X"], outputs=["Out"],
             needs_rng=True, grad=None)
def _sampling_id(ctx, ins, attrs):
    """cf. sampling_id_op.cc: sample one category per row of a
    probability matrix."""
    import jax

    p = ins["X"][0]
    ids = jax.random.categorical(ctx.rng(), jnp.log(p + 1e-20), axis=-1)
    return {"Out": [ids.astype(jnp.int64)]}


@register_op("uniform_random_batch_size_like", inputs=["Input"],
             outputs=["Out"], needs_rng=True, grad=None)
def _uniform_random_bsl(ctx, ins, attrs):
    import jax

    from ..core.dtypes import to_jnp

    x = ins["Input"][0]
    shape = list(attrs["shape"])
    shape[int(attrs.get("output_dim_idx", 0))] = x.shape[
        int(attrs.get("input_dim_idx", 0))]
    from .random_ops import step_seeded_key

    return {"Out": [jax.random.uniform(
        step_seeded_key(ctx, attrs), tuple(shape),
        dtype=to_jnp(attrs.get("dtype", "float32")),
        minval=float(attrs.get("min", -1.0)),
        maxval=float(attrs.get("max", 1.0)))]}


@register_op("batch_fc", inputs=["Input", "W", "Bias"], outputs=["Out"])
def _batch_fc(ctx, ins, attrs):
    """cf. batch_fc_op.cc: per-slot fc — [S, B, I] x [S, I, O] + [S, 1, O]."""
    x, w = ins["Input"][0], ins["W"][0]
    out = jnp.einsum("sbi,sio->sbo", x, w)
    if ins.get("Bias"):
        out = out + ins["Bias"][0]
    return {"Out": [out]}


@register_op("expand_v2", inputs=["X"], outputs=["Out"])
def _expand_v2(ctx, ins, attrs):
    """cf. expand_v2_op.cc: broadcast to `shape`; -1 keeps the input dim
    (input aligned to the right of shape)."""
    x = ins["X"][0]
    shape = [int(s) for s in attrs["shape"]]
    in_shape = (1,) * (len(shape) - x.ndim) + x.shape
    target = tuple(
        int(i) if s == -1 else s for s, i in zip(shape, in_shape))
    return {"Out": [jnp.broadcast_to(x, target)]}
