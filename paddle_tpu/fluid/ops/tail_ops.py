"""Op-tail batch 1: math / tensor / misc ops closing the registry gap vs
the reference operator library (round-4 audit list).

Each op cites its reference file; semantics are pinned by the numpy
oracles in tests/test_tail_ops.py.  Ops whose reference output shape is
data-dependent (unique, where_index, ctc_align) are redesigned to a
STATIC padded shape — documented per op — because XLA requires static
shapes; this mirrors the repo-wide LoD->padding design decision.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.registry import register_op


# ---------------------------------------------------------------------------
# elementwise / small tensor ops
# ---------------------------------------------------------------------------


@register_op("tril_triu", inputs=["X"], outputs=["Out"])
def _tril_triu(ctx, ins, attrs):
    """cf. tril_triu_op.cc: lower/upper triangle with `diagonal` offset."""
    x = ins["X"][0]
    diag = int(attrs.get("diagonal", 0))
    if bool(attrs.get("lower", True)):
        return {"Out": [jnp.tril(x, k=diag)]}
    return {"Out": [jnp.triu(x, k=diag)]}


@register_op("multiplex", inputs=["X", "Ids"], outputs=["Out"],
             no_grad_slots=("Ids",))
def _multiplex(ctx, ins, attrs):
    """cf. multiplex_op.cc: out[i] = X[Ids[i]][i] (row-wise candidate
    select across the input list)."""
    xs = jnp.stack(ins["X"], axis=0)            # [K, B, ...]
    ids = ins["Ids"][0].reshape(-1).astype(jnp.int32)  # [B]
    rows = jnp.arange(xs.shape[1])
    return {"Out": [xs[ids, rows]]}


@register_op("minus", inputs=["X", "Y"], outputs=["Out"])
def _minus(ctx, ins, attrs):
    """cf. minus_op.cc: Out = X - Y."""
    return {"Out": [ins["X"][0] - ins["Y"][0]]}


@register_op("reverse", inputs=["X"], outputs=["Out"])
def _reverse(ctx, ins, attrs):
    """cf. reverse_op.cc: flip along the `axis` list."""
    axes = attrs.get("axis", [0])
    axes = [axes] if isinstance(axes, int) else list(axes)
    return {"Out": [jnp.flip(ins["X"][0], axis=tuple(int(a) for a in axes))]}


@register_op("eye", inputs=[], outputs=["Out"])
def _eye(ctx, ins, attrs):
    """cf. eye_op.cc."""
    from ..core.dtypes import to_jnp

    n = int(attrs["num_rows"])
    m = int(attrs.get("num_columns", -1))
    m = n if m < 0 else m
    return {"Out": [jnp.eye(n, m, dtype=to_jnp(attrs.get("dtype",
                                                         "float32")))]}


@register_op("diag", inputs=["Diagonal"], outputs=["Out"])
def _diag(ctx, ins, attrs):
    """cf. diag_op.cc: 1-D diagonal -> square matrix."""
    return {"Out": [jnp.diag(ins["Diagonal"][0].reshape(-1))]}


@register_op("fill", inputs=[], outputs=["Out"])
def _fill(ctx, ins, attrs):
    """cf. fill_op.cc: materialize attr `value` data with attr `shape`."""
    import numpy as np

    from ..core.dtypes import to_jnp

    shape = tuple(int(s) for s in attrs["shape"])
    vals = np.asarray(attrs["value"], dtype=np.float64).reshape(shape)
    return {"Out": [jnp.asarray(vals, dtype=to_jnp(attrs.get("dtype",
                                                             "float32")))]}


@register_op("fill_zeros_like2", inputs=["X"], outputs=["Out"])
def _fill_zeros_like2(ctx, ins, attrs):
    """cf. fill_zeros_like_op.cc (v2 carries an explicit dtype attr)."""
    from ..core.dtypes import to_jnp

    dt = attrs.get("dtype")
    x = ins["X"][0]
    return {"Out": [jnp.zeros(x.shape, to_jnp(dt) if dt else x.dtype)]}


@register_op("range", inputs=["Start", "End", "Step"], outputs=["Out"],
             grad=None)
def _range(ctx, ins, attrs):
    """cf. range_op.cc.  XLA needs a static length, so Start/End/Step must
    be graph-time constants (fill_constant feeds or attr fallback)."""
    import numpy as np

    def _concrete(slot, attr):
        if ins.get(slot):
            v = ins[slot][0]
            try:
                return float(np.asarray(jax.core.concrete_or_error(
                    None, v, "range op needs concrete Start/End/Step "
                    "(data-dependent lengths cannot be staged to XLA)")))
            except TypeError:
                return float(np.asarray(v).reshape(()))
        return float(attrs[attr])

    start = _concrete("Start", "start")
    end = _concrete("End", "end")
    step = _concrete("Step", "step")
    out = jnp.arange(start, end, step)
    if ins.get("Start"):
        out = out.astype(ins["Start"][0].dtype)
    return {"Out": [out]}


@register_op("unique", inputs=["X"], outputs=["Out", "Index"], grad=None)
def _unique(ctx, ins, attrs):
    """cf. unique_op.cc.  STATIC redesign: Out is padded to len(X) (the
    reference emits a variable-length tensor); trailing slots repeat the
    first unique value.  Index (the orig->unique map) is exact."""
    x = ins["X"][0].reshape(-1)
    out, inv = jnp.unique(x, return_inverse=True, size=x.shape[0],
                          fill_value=x[0])
    return {"Out": [out], "Index": [inv.astype(jnp.int32)]}


@register_op("unique_with_counts", inputs=["X"],
             outputs=["Out", "Index", "Count"], grad=None)
def _unique_with_counts(ctx, ins, attrs):
    """cf. unique_with_counts_op.cc (same static-padding redesign)."""
    x = ins["X"][0].reshape(-1)
    out, inv, cnt = jnp.unique(x, return_inverse=True, return_counts=True,
                               size=x.shape[0], fill_value=x[0])
    return {"Out": [out], "Index": [inv.astype(jnp.int32)],
            "Count": [cnt.astype(jnp.int32)]}


@register_op("where_index", inputs=["Condition"], outputs=["Out"],
             grad=None)
def _where_index(ctx, ins, attrs):
    """cf. where_index_op.cc (np.nonzero).  STATIC redesign: padded to
    numel rows with -1 (the true count = rows with index >= 0)."""
    c = ins["Condition"][0]
    out = jnp.argwhere(c, size=c.size, fill_value=-1)
    return {"Out": [out.astype(jnp.int64)]}


@register_op("is_empty", inputs=["X"], outputs=["Out"], grad=None)
def _is_empty(ctx, ins, attrs):
    """cf. is_empty_op.cc."""
    return {"Out": [jnp.asarray(ins["X"][0].size == 0)]}


@register_op("gaussian_random_batch_size_like", inputs=["Input"],
             outputs=["Out"], needs_rng=True, grad=None)
def _gaussian_random_bsl(ctx, ins, attrs):
    """cf. gaussian_random_batch_size_like_op.cc (batch_size_like.h:49)."""
    from ..core.dtypes import to_jnp

    x = ins["Input"][0]
    shape = list(attrs["shape"])
    shape[int(attrs.get("output_dim_idx", 0))] = x.shape[
        int(attrs.get("input_dim_idx", 0))]
    out = float(attrs.get("mean", 0.0)) + float(attrs.get("std", 1.0)) \
        * jax.random.normal(ctx.rng(), tuple(shape),
                            dtype=to_jnp(attrs.get("dtype", "float32")))
    return {"Out": [out]}


@register_op("bilinear_tensor_product", inputs=["X", "Y", "Weight", "Bias"],
             outputs=["Out"])
def _bilinear_tensor_product(ctx, ins, attrs):
    """cf. bilinear_tensor_product_op.cc: out[b,o] = x[b] W[o] y[b]^T."""
    x, y, w = ins["X"][0], ins["Y"][0], ins["Weight"][0]
    out = jnp.einsum("bm,omn,bn->bo", x, w, y)
    if ins.get("Bias"):
        out = out + ins["Bias"][0]
    return {"Out": [out]}


@register_op("cross_entropy2", inputs=["X", "Label"],
             outputs=["Y", "MatchX", "XShape"], no_grad_slots=("Label",))
def _cross_entropy2(ctx, ins, attrs):
    """cf. cross_entropy2_op.cc: hard-label CE over an already-normalized
    probability input; MatchX saves the matched prob for the backward."""
    x, label = ins["X"][0], ins["Label"][0]
    lab = label.reshape(label.shape[:-1]).astype(jnp.int32)
    match = jnp.take_along_axis(x, lab[..., None], axis=-1)
    y = -jnp.log(jnp.maximum(match, 1e-20))
    return {"Y": [y], "MatchX": [match],
            "XShape": [jnp.zeros((len(x.shape) + 1,), jnp.int64)]}


@register_op("conv_shift", inputs=["X", "Y"], outputs=["Out"])
def _conv_shift(ctx, ins, attrs):
    """cf. conv_shift_op.cc: circular correlation — out[b,i] =
    sum_j x[b, (i + j - N//2) mod M] * y[b, j]."""
    x, y = ins["X"][0], ins["Y"][0]
    M, N = x.shape[1], y.shape[1]
    idx = (jnp.arange(M)[:, None] + jnp.arange(N)[None, :] - N // 2) % M
    return {"Out": [jnp.einsum("bmn,bn->bm", x[:, idx], y)]}


@register_op("bpr_loss", inputs=["X", "Label"], outputs=["Out"],
             no_grad_slots=("Label",))
def _bpr_loss(ctx, ins, attrs):
    """cf. bpr_loss_op.cc (Bayesian Personalized Ranking): per row,
    -mean_j log(sigmoid(x[label] - x[j != label]))."""
    x, label = ins["X"][0], ins["Label"][0]
    B, C = x.shape
    lab = label.reshape(-1).astype(jnp.int32)
    pos = jnp.take_along_axis(x, lab[:, None], axis=1)
    diff = pos - x
    lognd = jnp.logaddexp(0.0, -diff)           # -log(sigmoid(diff))
    mask = jnp.arange(C)[None, :] != lab[:, None]
    out = jnp.sum(jnp.where(mask, lognd, 0.0), axis=1, keepdims=True) \
        / jnp.maximum(C - 1, 1)
    return {"Out": [out]}


@register_op("cvm", inputs=["X", "CVM"], outputs=["Y"],
             no_grad_slots=("CVM",))
def _cvm(ctx, ins, attrs):
    """cf. cvm_op.cc: the first two feature columns are (show, click);
    use_cvm=True keeps them log-transformed, False drops them."""
    x = ins["X"][0]
    if bool(attrs.get("use_cvm", True)):
        show = jnp.log(x[:, 0:1] + 1.0)
        ctr = jnp.log(x[:, 1:2] + 1.0) - jnp.log(x[:, 0:1] + 1.0)
        return {"Y": [jnp.concatenate([show, ctr, x[:, 2:]], axis=1)]}
    return {"Y": [x[:, 2:]]}


@register_op("hash", inputs=["X"], outputs=["Out"], grad=None)
def _hash(ctx, ins, attrs):
    """cf. hash_op.cc: num_hash rows of (xxhash(x_row, seed=i) % mod_by).
    The hash family here is a splitmix-style integer mix — a documented
    redesign (the exact xxhash bits are not a semantic contract; tests
    pin THIS mix)."""
    x = ins["X"][0].astype(jnp.uint32)
    num_hash = int(attrs.get("num_hash", 1))
    mod_by = int(attrs.get("mod_by", 1))

    def mix(v, seed):
        v = (v + jnp.uint32(seed)) * jnp.uint32(0x9E3779B1)
        v = v ^ (v >> 15)
        v = v * jnp.uint32(0x85EBCA77)
        v = v ^ (v >> 13)
        return v

    rows = []
    for i in range(num_hash):
        h = jnp.zeros(x.shape[:-1], jnp.uint32)
        for j in range(x.shape[-1]):
            h = mix(h ^ x[..., j], i * 0x2545F491 + j + 1)
        rows.append((h % jnp.uint32(mod_by)).astype(jnp.int64))
    out = jnp.stack(rows, axis=-1)[..., None]     # [.., num_hash, 1]
    return {"Out": [out.reshape(x.shape[:-1] + (num_hash, 1))]}


@register_op("seed", inputs=[], outputs=["Out"], needs_rng=True, grad=None)
def _seed(ctx, ins, attrs):
    """cf. seed_op.cc: emit the configured (or a generated) seed."""
    s = int(attrs.get("seed", 0))
    if s != 0:
        return {"Out": [jnp.asarray([s], jnp.int32)]}
    r = jax.random.randint(ctx.rng(), (1,), 1, 2 ** 31 - 1)
    return {"Out": [r.astype(jnp.int32)]}


@register_op("get_tensor_from_selected_rows", inputs=["X"], outputs=["Out"])
def _get_tensor_from_selected_rows(ctx, ins, attrs):
    """cf. get_tensor_from_selected_rows_op.cc: in this design sparse
    rows are already dense (ids, rows) pairs folded by the optimizer
    path, so this is the identity on the dense value."""
    return {"Out": [ins["X"][0]]}


@register_op("merge_selected_rows", inputs=["X", "RowIds"], outputs=["Out"],
             no_grad_slots=("RowIds",))
def _merge_selected_rows(ctx, ins, attrs):
    """cf. merge_selected_rows_op.cc: sum rows with duplicate ids.  Takes
    the (values, row_ids) pair of this design's sparse-rows convention
    and returns values with duplicates accumulated onto the FIRST
    occurrence (later duplicates zeroed)."""
    vals, ids = ins["X"][0], ins["RowIds"][0].reshape(-1)
    # accumulate every row onto the first row holding the same id
    same = ids[None, :] == ids[:, None]
    first_idx = jnp.argmax(same, axis=1)         # first occurrence per row
    out = jnp.zeros_like(vals).at[first_idx].add(vals)
    return {"Out": [out]}


@register_op("lod_array_length", inputs=["X"], outputs=["Out"], grad=None)
def _lod_array_length(ctx, ins, attrs):
    """cf. lod_array_length_op.cc over this design's fixed-capacity
    tensor array (count of written slots)."""
    arr = ins["X"]
    return {"Out": [jnp.asarray([len(arr)], jnp.int64)]}


@register_op("max_sequence_len", inputs=["RankTable"], outputs=["Out"],
             grad=None)
def _max_sequence_len(ctx, ins, attrs):
    """cf. max_sequence_len_op.cc: with padded batches the max length is
    the time dimension of the packed tensor."""
    return {"Out": [jnp.asarray([ins["RankTable"][0].shape[1]],
                                jnp.int64)]}


@register_op("fake_init", inputs=[], outputs=["Out"], grad=None)
def _fake_init(ctx, ins, attrs):
    """cf. fake_init_op.cc: placeholder init (PS-mode vars) — zeros."""
    from ..core.dtypes import to_jnp

    return {"Out": [jnp.zeros(tuple(int(s) for s in attrs["shape"]),
                              to_jnp(attrs.get("dtype", "float32")))]}


@register_op("delete_var", inputs=["X"], outputs=[], grad=None)
def _delete_var(ctx, ins, attrs):
    """cf. delete_var_op.cc: buffer frees are XLA's job — no-op."""
    return {}


# ---------------------------------------------------------------------------
# optimizer-support ops
# ---------------------------------------------------------------------------


@register_op(
    "average_accumulates",
    inputs=["param", "in_sum_1", "in_sum_2", "in_sum_3",
            "in_num_accumulates", "in_old_num_accumulates",
            "in_num_updates"],
    outputs=["out_sum_1", "out_sum_2", "out_sum_3", "out_num_accumulates",
             "out_old_num_accumulates", "out_num_updates"],
    grad=None,
)
def _average_accumulates(ctx, ins, attrs):
    """cf. average_accumulates_op.h AccumulateAverage: sum_1 accumulates
    params; every 16384 updates it folds into sum_2; when the window
    closes (num_accumulates >= min_window and >= num_updates *
    average_window capped at max_window) everything folds into sum_3 and
    the accumulators reset."""
    p = ins["param"][0]
    s1, s2, s3 = ins["in_sum_1"][0], ins["in_sum_2"][0], ins["in_sum_3"][0]
    na = ins["in_num_accumulates"][0].reshape(())
    ona = ins["in_old_num_accumulates"][0].reshape(())
    nu = ins["in_num_updates"][0].reshape(())
    avg_win = float(attrs.get("average_window", 0))
    max_avg = int(attrs.get("max_average_window", 2 ** 31 - 1))
    min_avg = int(attrs.get("min_average_window", 10000))
    K_MAX = 16384

    nu = nu + 1
    na = na + 1
    s1 = s1 + p
    fold12 = (nu % K_MAX) == 0
    s2 = jnp.where(fold12, s2 + s1, s2)
    s1 = jnp.where(fold12, jnp.zeros_like(s1), s1)
    window = jnp.minimum(
        jnp.float32(max_avg), nu.astype(jnp.float32) * avg_win)
    close = (na >= min_avg) & (na.astype(jnp.float32) >= window)
    s3 = jnp.where(close, s1 + s2, s3)
    s1 = jnp.where(close, jnp.zeros_like(s1), s1)
    s2 = jnp.where(close, jnp.zeros_like(s2), s2)
    ona = jnp.where(close, na, ona)
    na = jnp.where(close, jnp.zeros_like(na), na)
    shape1 = ins["in_num_accumulates"][0].shape
    return {
        "out_sum_1": [s1], "out_sum_2": [s2], "out_sum_3": [s3],
        "out_num_accumulates": [na.reshape(shape1)],
        "out_old_num_accumulates": [ona.reshape(shape1)],
        "out_num_updates": [nu.reshape(shape1)],
    }


@register_op(
    "proximal_adagrad",
    inputs=["Param", "Moment", "Grad", "LearningRate"],
    outputs=["ParamOut", "MomentOut"], grad=None,
)
def _proximal_adagrad(ctx, ins, attrs):
    """cf. proximal_adagrad_op.cc: adagrad step then the proximal L1/L2
    shrinkage prox_param / (1 + lr_adj * l2) with soft-threshold l1."""
    p, m, g = ins["Param"][0], ins["Moment"][0], ins["Grad"][0]
    lr = ins["LearningRate"][0].reshape(())
    l1 = float(attrs.get("l1", 0.0))
    l2 = float(attrs.get("l2", 0.0))
    m = m + g * g
    lr_adj = lr * jax.lax.rsqrt(m)
    prox = p - lr_adj * g
    out = jnp.sign(prox) * jnp.maximum(jnp.abs(prox) - lr_adj * l1, 0.0) \
        / (1.0 + lr_adj * l2)
    return {"ParamOut": [out], "MomentOut": [m]}


@register_op(
    "proximal_gd",
    inputs=["Param", "Grad", "LearningRate"],
    outputs=["ParamOut"], grad=None,
)
def _proximal_gd(ctx, ins, attrs):
    """cf. proximal_gd_op.cc."""
    p, g = ins["Param"][0], ins["Grad"][0]
    lr = ins["LearningRate"][0].reshape(())
    l1 = float(attrs.get("l1", 0.0))
    l2 = float(attrs.get("l2", 0.0))
    prox = p - lr * g
    out = jnp.sign(prox) * jnp.maximum(jnp.abs(prox) - lr * l1, 0.0) \
        / (1.0 + lr * l2)
    return {"ParamOut": [out]}


@register_op("assert", inputs=["Cond", "Data"], outputs=["Out"], grad=None)
def _assert_op(ctx, ins, attrs):
    """cf. operators/assert_op.cc: host-checked assertion — when Cond is
    false, print the message + summarized Data and raise."""
    import jax

    cond = ins["Cond"][0]
    data = ins["Data"] if ins.get("Data") else []
    message = str(attrs.get("message", ""))
    summarize = int(attrs.get("summarize", 10))

    def _check(c, *vals):
        import numpy as _np

        if not _np.asarray(c).all():
            parts = [message] if message else []
            for v in vals:
                parts.append(str(_np.asarray(v).reshape(-1)[:summarize]))
            raise RuntimeError(
                "Assert failed: %s" % (" ".join(parts) or "<no message>"))

    heads = [d.reshape(-1)[:summarize] for d in data]
    jax.debug.callback(_check, cond, *heads)
    return {"Out": [cond]}
