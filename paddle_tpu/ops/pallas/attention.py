"""Flash attention: online-softmax pallas kernels with a custom VJP.

Forward streams K/V blocks through VMEM with running (m, l, acc) statistics
so the [S, S] score matrix never touches HBM — HBM traffic is linear in S
instead of quadratic (the reason the naive composition stalls on long
sequences; cf. PAPERS.md flash-attention).  The forward also emits the row
log-sum-exp, so the backward never rebuilds full scores: dQ accumulates in
a row-parallel kernel, dK/dV (and the padding-bias gradient) in a
column-parallel kernel, each recomputing P blockwise from (Q, K, LSE) —
the standard flash backward, O(S) memory end to end.

Layouts: the kernels are head-major, [B, H, S, D] flattened to
[BH, S, D].  "BSHD" ([B, S, H, D], the natural output of a [B,S,HD] qkv
projection reshape) is transposed to that around them: Mosaic refuses a
(1, bq, 1, d) block, whose last two dims neither divide (8, 128) nor
equal the array's.
Supported in-kernel:
  - causal masking,
  - a broadcastable additive bias of shape [BH, 1, Sk] (padding masks),
  - packed-batch segment ids ([BH, Sq], [BH, Sk]): token i attends token j
    only when their segment ids are equal.  This is the in-graph LoD story
    (reference `framework/lod_tensor.h:52,104`): several variable-length
    sequences packed into one row stay isolated without an O(S^2) mask in
    HBM — the mask is rebuilt blockwise from two O(S) id vectors.
Richer biases fall back to the naive path in ops/attention.py.  Sequences
that no supported block size divides also fall back (never silently
truncate).

Set `interpret=True` (or run on CPU — auto-detected) to run the same
kernels through the pallas interpreter for testing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# shapes already warned about falling back to the naive composition
_FALLBACK_WARNED: set = set()


def _pick_block(s):
    for b in (512, 256, 128):
        if s % b == 0:
            return b
    return None


def _block_sizes(sq, sk, block_q=None, block_k=None):
    # explicit arguments (the autotuner / callers who measured their
    # shape) are a hard contract: they win over the heuristic, and an
    # invalid choice raises instead of silently falling back — a tuner
    # must never time a different grid than the one it thinks it
    # requested.  A side NOT given explicitly keeps the heuristic.
    bq = _pick_block(sq) if block_q is None else int(block_q)
    bk = _pick_block(sk) if block_k is None else int(block_k)
    if (block_q is not None or block_k is not None) and (
            not bq or not bk or sq % bq or sk % bk):
        raise ValueError(
            "explicit flash-attention block sizes (block_q=%r, "
            "block_k=%r) must divide the padded sequence lengths "
            "(Sq=%d, Sk=%d)" % (block_q, block_k, sq, sk))
    return bq, bk


def _apply_masks(s, bias_ref, qseg_ref, kseg_ref, causal, i, j, bq, bk,
                 coff=0):
    """Common pre-softmax masking: additive bias, segment ids, causal.

    Segment-id tiles use the TPU-friendly layouts: q ids lane-broadcast
    [bq, 128], kv ids sublane-broadcast [8, bk] (blocks must tile by
    (8, 128) on TPU; an O(S) id vector alone cannot)."""
    if bias_ref is not None:
        s = s + bias_ref[0, 0, :].astype(jnp.float32)[None, :]
    if qseg_ref is not None:
        qs = jnp.tile(qseg_ref[0], (1, bk // 128))  # [bq, bk]
        ks = kseg_ref[0, 0:1, :]  # [1, bk]
        s = jnp.where(qs == ks, s, NEG_INF)
    if causal:
        # bottom-right aligned (reference tril(k=Sk-Sq) semantics): row i
        # attends cols <= i + (Sk - Sq); coff = Sk - Sq (original lengths)
        rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(rows + coff >= cols, s, NEG_INF)
    return s


def _split_refs(refs, has_bias, has_seg):
    """Unpack a kernel's positional refs: q, k, v, [bias], [qseg, kseg],
    then the remaining out/scratch refs as `tail`."""
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    idx = 3
    bias_ref = qseg_ref = kseg_ref = None
    if has_bias:
        bias_ref = refs[idx]
        idx += 1
    if has_seg:
        qseg_ref, kseg_ref = refs[idx], refs[idx + 1]
        idx += 2
    return q_ref, k_ref, v_ref, bias_ref, qseg_ref, kseg_ref, refs[idx:]


def _recompute_lse(s):
    """Full-row logsumexp from a score tile that covers the whole row
    (single-block schedule) — matches the forward's dead-row handling."""
    m = jnp.max(s, axis=1)
    safe_m = jnp.where(m <= NEG_INF / 2, 0.0, m)
    l = jnp.sum(jnp.where(s <= NEG_INF / 2, 0.0,
                          jnp.exp(s - safe_m[:, None])), axis=1)
    return jnp.where(m <= NEG_INF / 2, NEG_INF,
                     safe_m + jnp.log(jnp.maximum(l, 1e-30)))


def _row_spec(rows, d, pos):
    """BlockSpec for a row-blocked [BH, S, D] tensor.
    pos: which positional grid arg (1 or 2) carries this tensor's row
    block index — the fwd/dq grids are (g, i, j), the dkv grid (g, j, i)."""
    if pos == 1:
        return pl.BlockSpec((1, rows, d), lambda g, a, b: (g, a, 0))
    return pl.BlockSpec((1, rows, d), lambda g, a, b: (g, b, 0))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(*refs, scale, causal, bq, bk, nk, has_bias, has_seg,
                coff=0, emit_lse=True):
    (q_ref, k_ref, v_ref, bias_ref, qseg_ref, kseg_ref, tail) = _split_refs(
        refs, has_bias, has_seg
    )
    o_ref, lse_ref, m_ref, l_ref, acc_ref = tail
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    i = pl.program_id(1)

    def _compute():
        q = q_ref[0].astype(jnp.float32)  # [bq, d]
        k = k_ref[0].astype(jnp.float32)  # [bk, d]
        v = v_ref[0].astype(jnp.float32)  # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [bq, bk]
        s = _apply_masks(s, bias_ref, qseg_ref, kseg_ref, causal, i, j,
                         bq, bk, coff)

        m_prev = m_ref[:, 0]  # [bq]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])  # [bq, bk]
        corr = jnp.exp(m_prev - m_new)  # [bq]
        l_new = l_ref[:, 0] * corr + jnp.sum(p, axis=1)
        acc_ref[...] = (
            acc_ref[...].astype(jnp.float32) * corr[:, None]
            + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        ).astype(acc_ref.dtype)
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    if causal:  # skip blocks entirely above the (offset) diagonal
        pl.when((j * bk) <= (i * bq + bq - 1 + coff))(_compute)
    else:
        _compute()

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_ref[:, 0]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o = acc_ref[...].astype(jnp.float32) / safe_l[:, None]
        # a row whose every score was masked (m stuck at NEG_INF) has been
        # accumulating p = exp(0) = 1 garbage; emit zeros, keep lse at
        # NEG_INF so the backward zeroes it too
        dead = m_ref[:, 0] <= NEG_INF / 2
        o_ref[0, :, :] = jnp.where(dead[:, None], 0.0, o).astype(o_ref.dtype)
        if emit_lse:
            lse = jnp.where(dead, NEG_INF, m_ref[:, 0] + jnp.log(safe_l))
            lse_ref[0, :, :] = jnp.broadcast_to(lse[:, None],
                                                lse_ref.shape[1:])
        else:
            # single-block schedule: the backward recomputes lse from the
            # full score row — emit a token buffer instead of the [sq,128]
            # broadcast residual (saves ~3 full-tensor passes per layer)
            lse_ref[0, :, :] = jnp.zeros(lse_ref.shape[1:], jnp.float32)


def _fwd(q, k, v, bias, qseg, kseg, n_head, scale, causal, interpret,
         coff=0, block_q=None, block_k=None):
    """Returns (out, lse); out is [bh,sq,d]; lse is the [bh,sq,128]
    row-broadcast residual, EXCEPT on the single-block schedule
    (nq==nk==1) where it is a (bh,8,128) zero token and the fused
    backward kernel recomputes lse from the full score row.

    qseg: [B, sq, 128] lane-broadcast ids; kseg: [B, 8, sk] sublane-
    broadcast (B = bh // n_head; the index map divides by n_head so the
    ids are not replicated per head in HBM)."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq, bk = _block_sizes(sq, sk, block_q, block_k)
    nq, nk = sq // bq, sk // bk
    has_bias, has_seg = bias is not None, qseg is not None
    h = n_head

    in_specs = [
        _row_spec(bq, d, 1),
        _row_spec(bk, d, 2),
        _row_spec(bk, d, 2),
    ]
    args = [q, k, v]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, 1, bk), lambda b, i, j: (b, 0, j)))
        args.append(bias)
    if has_seg:
        in_specs.append(
            pl.BlockSpec((1, bq, 128), lambda b, i, j: (b // h, i, 0))
        )
        in_specs.append(
            pl.BlockSpec((1, 8, bk), lambda b, i, j: (b // h, 0, j))
        )
        args.extend([qseg, kseg])

    emit_lse = not (nq == 1 and nk == 1)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, bq=bq, bk=bk, nk=nk,
        has_bias=has_bias, has_seg=has_seg, coff=coff, emit_lse=emit_lse,
    )
    lse_rows = bq if emit_lse else 8
    with jax.named_scope("flash_attention"):
        out, lse = pl.pallas_call(
            kernel,
            grid=(bh, nq, nk),
            in_specs=in_specs,
            out_specs=[
                _row_spec(bq, d, 1),
                pl.BlockSpec((1, lse_rows, 128),
                             (lambda b, i, j: (b, i, 0)) if emit_lse
                             else (lambda b, i, j: (b, 0, 0))),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
                jax.ShapeDtypeStruct(
                    (bh, sq if emit_lse else 8, 128), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, 128), jnp.float32),  # running row max
                pltpu.VMEM((bq, 128), jnp.float32),  # running row sum
                pltpu.VMEM((bq, d), jnp.float32),  # output accumulator
            ],
            interpret=interpret,
            name="flash_attention_fwd",
        )(*args)
    return out, lse


# ---------------------------------------------------------------------------
# backward: dq (row-parallel) and dk/dv/dbias (column-parallel)
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(*refs, scale, causal, bq, bk, nk, has_bias, has_seg,
                   coff=0):
    (q_ref, k_ref, v_ref, bias_ref, qseg_ref, kseg_ref, tail) = _split_refs(
        refs, has_bias, has_seg
    )
    o_ref, do_ref, lse_ref, dq_ref, acc_ref = tail
    j = pl.program_id(2)
    i = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        o = o_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        s = _apply_masks(s, bias_ref, qseg_ref, kseg_ref, causal, i, j,
                         bq, bk, coff)
        lse = lse_ref[0, :, 0]  # [bq] logsumexp rows
        # explicit zero where masked: with a fully-masked row lse is
        # NEG_INF and exp(s - lse) would resurrect p = 1
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - lse[:, None]))
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk]
        delta = jnp.sum(do * o, axis=1)  # [bq]
        ds = p * (dp - delta[:, None]) * scale
        acc_ref[...] = (
            acc_ref[...].astype(jnp.float32) + jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        ).astype(acc_ref.dtype)

    if causal:
        pl.when((j * bk) <= (i * bq + bq - 1 + coff))(_compute)
    else:
        _compute()

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[0, :, :] = acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, bq, bk, nq, has_bias, has_seg,
                    coff=0):
    (q_ref, k_ref, v_ref, bias_ref, qseg_ref, kseg_ref, tail) = _split_refs(
        refs, has_bias, has_seg
    )
    if has_bias:
        o_ref, do_ref, lse_ref, dk_ref, dv_ref, db_ref = tail[:6]
        dk_acc, dv_acc, db_acc = tail[6:]
    else:
        o_ref, do_ref, lse_ref, dk_ref, dv_ref = tail[:5]
        dk_acc, dv_acc = tail[5:]
        db_ref = db_acc = None
    i = pl.program_id(2)  # q block index (inner loop)
    j = pl.program_id(1)  # k block index

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
        if db_acc is not None:
            db_acc[...] = jnp.zeros_like(db_acc)

    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        o = o_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        s = _apply_masks(s, bias_ref, qseg_ref, kseg_ref, causal, i, j,
                         bq, bk, coff)
        lse = lse_ref[0, :, 0]
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - lse[:, None]))
        dv_acc[...] = (
            dv_acc[...].astype(jnp.float32) + jax.lax.dot_general(
                p, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        ).astype(dv_acc.dtype)  # [bk, d]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        delta = jnp.sum(do * o, axis=1)
        ds_raw = p * (dp - delta[:, None])  # d bias (unscaled) [bq, bk]
        ds = ds_raw * scale
        dk_acc[...] = (
            dk_acc[...].astype(jnp.float32) + jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        ).astype(dk_acc.dtype)  # [bk, d]
        if db_acc is not None:
            db_acc[0:1, :] = db_acc[0:1, :] + jnp.sum(ds_raw, axis=0)[None, :]

    if causal:
        pl.when((j * bk) <= (i * bq + bq - 1 + coff))(_compute)
    else:
        _compute()

    @pl.when(i == nq - 1)
    def _finalize():
        dk_ref[0, :, :] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, :, :] = dv_acc[...].astype(dv_ref.dtype)
        if db_ref is not None:
            db_ref[0, 0, :] = db_acc[0, :].astype(db_ref.dtype)


def _with_seg_cotangents(dq, dk, dv, dbias, qseg, kseg):
    """Integer segment-id inputs take float0 cotangents (shared tail of
    both backward schedules)."""
    dqseg = (np.zeros(qseg.shape, jax.dtypes.float0)
             if qseg is not None else None)
    dkseg = (np.zeros(kseg.shape, jax.dtypes.float0)
             if kseg is not None else None)
    return dq, dk, dv, dbias, dqseg, dkseg


def _row_spec1(rows, d):
    """Single-grid-axis BlockSpec (the fused single-block backward)."""
    return pl.BlockSpec((1, rows, d), lambda g: (g, 0, 0))


def _bwd_fused_kernel(*refs, scale, causal, bq, bk, has_bias, has_seg,
                      coff=0):
    """Single-block schedule (nq == nk == 1): dq, dk, dv (and dbias) in
    ONE kernel.  The two-kernel flash backward recomputes the score
    matrix, softmax, and dP twice — once row-parallel for dQ, once
    column-parallel for dK/dV; when one block covers the whole row there
    is no accumulation across blocks, so a fused kernel shares s/p/dp/ds
    and does 5 matmuls instead of 7 (plus one exp instead of two).
    This is the flagship S=512 shape's schedule."""
    (q_ref, k_ref, v_ref, bias_ref, qseg_ref, kseg_ref, tail) = _split_refs(
        refs, has_bias, has_seg
    )
    if has_bias:
        o_ref, do_ref, dq_ref, dk_ref, dv_ref, db_ref = tail
    else:
        o_ref, do_ref, dq_ref, dk_ref, dv_ref = tail
        db_ref = None
    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    o = o_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    s = _apply_masks(s, bias_ref, qseg_ref, kseg_ref, causal, 0, 0,
                     bq, bk, coff)
    lse = _recompute_lse(s)
    p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - lse[:, None]))
    dv = jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [bk, d]
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [bq, bk]
    delta = jnp.sum(do * o, axis=1)  # [bq]
    ds_raw = p * (dp - delta[:, None])
    ds = ds_raw * scale
    dq_ref[0, :, :] = jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(dq_ref.dtype)
    dk_ref[0, :, :] = jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(dk_ref.dtype)
    dv_ref[0, :, :] = dv.astype(dv_ref.dtype)
    if db_ref is not None:
        db_ref[0, 0, :] = jnp.sum(ds_raw, axis=0).astype(db_ref.dtype)


def _bwd_fused(q, k, v, bias, qseg, kseg, out, g, h, scale, causal,
               interpret, coff, bq, bk, bh):
    has_bias, has_seg = bias is not None, qseg is not None
    in_specs = [
        _row_spec1(bq, q.shape[-1]),   # q
        _row_spec1(bk, q.shape[-1]),   # k
        _row_spec1(bk, q.shape[-1]),   # v
    ]
    args = [q, k, v]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, 1, bk), lambda g_: (g_, 0, 0)))
        args.append(bias)
    if has_seg:
        in_specs.append(
            pl.BlockSpec((1, bq, 128), lambda g_: (g_ // h, 0, 0)))
        in_specs.append(
            pl.BlockSpec((1, 8, bk), lambda g_: (g_ // h, 0, 0)))
        args.extend([qseg, kseg])
    in_specs += [
        _row_spec1(bq, q.shape[-1]),   # o
        _row_spec1(bq, q.shape[-1]),   # do
    ]
    args += [out, g]   # lse is recomputed in-kernel: no residual input
    out_specs = [
        _row_spec1(bq, q.shape[-1]),   # dq
        _row_spec1(bk, q.shape[-1]),   # dk
        _row_spec1(bk, q.shape[-1]),   # dv
    ]
    out_shape = [
        jax.ShapeDtypeStruct(q.shape, q.dtype),
        jax.ShapeDtypeStruct(k.shape, k.dtype),
        jax.ShapeDtypeStruct(v.shape, v.dtype),
    ]
    if has_bias:
        out_specs.append(pl.BlockSpec((1, 1, bk), lambda g_: (g_, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct(bias.shape, bias.dtype))
    with jax.named_scope("flash_attention"):
        res = pl.pallas_call(
            functools.partial(
                _bwd_fused_kernel, scale=scale, causal=causal, bq=bq,
                bk=bk, has_bias=has_bias, has_seg=has_seg, coff=coff,
            ),
            grid=(bh,),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            interpret=interpret,
            name="flash_attention_bwd_fused",
        )(*args)
    if has_bias:
        dq, dk, dv, dbias = res
    else:
        (dq, dk, dv), dbias = res, None
    return dq, dk, dv, dbias


# ---------------------------------------------------------------------------
# custom-vjp wrapper
# ---------------------------------------------------------------------------


def flash_attention(q, k, v, bias=None, segment_ids=None, scale=None,
                    causal=False, interpret=None, layout="BHSD",
                    block_q=None, block_k=None):
    """q/k/v: [B, H, S, D] (layout="BHSD") or [B, S, H, D] ("BSHD":
    transposed to head-major around the kernels).  bias: None or
    broadcastable [B, 1/H, 1, Sk].
    segment_ids: None, a [B, S] int array (self-attention packing), or a
    (q_seg [B, Sq], kv_seg [B, Sk]) pair — attention is confined to equal
    segment ids.

    ``block_q``/``block_k`` pin the kernel's q/k block sizes explicitly
    (the knob ``paddle_tpu.tune.search_flash_blocks`` searches); they
    must divide the PADDED sequence lengths (multiples of 128) or a
    ValueError is raised.  Default None keeps the built-in heuristic
    (largest of 512/256/128 that divides).

    Sequences not divisible by the 128-lane block are PADDED up to it
    (padded keys masked by bias / a sentinel segment id, padded query
    rows sliced off) so the kernel fast path is kept; the head dim is
    never split (its block always equals the full dim) so any 64-multiple
    works — non-64-multiples run the naive composition (never silently
    truncates either way)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if layout == "BSHD":
        # Mosaic requires the last-two block dims to divide (8, 128) or
        # equal the array dims — a (1, bq, 1, d) head-sliced block is
        # illegal, so the BSHD API transposes to head-major around the
        # kernel (XLA fuses these with neighbours; measured cheaper than
        # strided sublane reads inside the kernel)
        out = flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), bias=bias, segment_ids=segment_ids,
            scale=scale, causal=causal, interpret=interpret, layout="BHSD",
            block_q=block_q, block_k=block_k)
        return out.transpose(0, 2, 1, 3)
    b, h, sq, d = q.shape
    sk = k.shape[2]

    def _pad_s(x, p):
        return jnp.pad(x, ((0, 0), (0, 0), (0, p), (0, 0)))

    # pad seq lengths up to the 128 block so _pick_block always succeeds
    sq_orig, sk_orig = sq, sk
    pq, pk = (-sq) % 128, (-sk) % 128
    if (pq or pk) and d % 64 == 0:
        from ..attention import NEG_INF as _NI
        from ..attention import normalize_segment_ids as _norm

        q = _pad_s(q, pq)
        k = _pad_s(k, pk)
        v = _pad_s(v, pk)
        if pk:
            # mask padded keys for every query (additive bias row)
            key_pad = jnp.concatenate(
                [jnp.zeros((1, 1, 1, sk), jnp.float32),
                 jnp.full((1, 1, 1, pk), _NI, jnp.float32)], axis=-1
            )
            if bias is None:
                bias = key_pad
            else:
                bias = jnp.pad(
                    jnp.broadcast_to(bias, (b, bias.shape[1], 1, sk)),
                    ((0, 0), (0, 0), (0, 0), (0, pk)),
                ) + key_pad
        if segment_ids is not None:
            qseg0, kseg0 = _norm(segment_ids)
            # sentinels differ so padded q rows match nothing (they emit
            # zeros and are sliced off below)
            segment_ids = (
                jnp.pad(qseg0.astype(jnp.int32), ((0, 0), (0, pq)),
                        constant_values=-2),
                jnp.pad(kseg0.astype(jnp.int32), ((0, 0), (0, pk)),
                        constant_values=-1),
            )
        sq, sk = sq + pq, sk + pk

    bq, bk = _block_sizes(sq, sk, block_q, block_k)
    if bq is None or bk is None:
        import warnings

        from ..attention import _naive_attention, _segment_bias

        key = ("naive-fallback", sq, sk, d)
        if key not in _FALLBACK_WARNED:
            _FALLBACK_WARNED.add(key)
            warnings.warn(
                "flash_attention falling back to the O(S^2) naive path for "
                "shape (Sq=%d, Sk=%d, D=%d): head dim must be a multiple "
                "of 64 for the pallas kernel. This is a PERFORMANCE "
                "fallback, not an error — pad the head dim to fix it."
                % (sq_orig, sk_orig, d),
                stacklevel=2,
            )
        if segment_ids is not None:
            sb = _segment_bias(segment_ids)
            bias = sb if bias is None else bias + sb
        return _naive_attention(q, k, v, bias, scale, causal)

    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)
    bf = None
    if bias is not None:
        bf = jnp.broadcast_to(bias, (b, h, 1, sk)).reshape(b * h, 1, sk)
    qsegf = ksegf = None
    if segment_ids is not None:
        from ..attention import normalize_segment_ids

        qseg, kseg = normalize_segment_ids(segment_ids)
        # TPU-tileable broadcast layouts (see _apply_masks)
        qsegf = jnp.broadcast_to(
            qseg.astype(jnp.int32)[:, :, None], (b, sq, 128)
        )
        ksegf = jnp.broadcast_to(
            kseg.astype(jnp.int32)[:, None, :], (b, 8, sk)
        )

    coff = sk_orig - sq_orig  # bottom-right causal alignment (original S)
    out = _flash_core(qf, kf, vf, bf, qsegf, ksegf, h, scale, causal,
                      interpret, coff, block_q, block_k)
    out = out.reshape(b, h, sq, d)
    return out[:, :, :sq_orig] if sq != sq_orig else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11, 12))
def _flash_core(q, k, v, bias, qseg, kseg, n_head, scale, causal, interpret,
                coff, block_q=None, block_k=None):
    out, _ = _fwd(q, k, v, bias, qseg, kseg, n_head, scale, causal,
                  interpret, coff, block_q, block_k)
    return out


def _flash_core_fwd(q, k, v, bias, qseg, kseg, n_head, scale, causal,
                    interpret, coff, block_q=None, block_k=None):
    out, lse = _fwd(q, k, v, bias, qseg, kseg, n_head, scale, causal,
                    interpret, coff, block_q, block_k)
    return out, (q, k, v, bias, qseg, kseg, out, lse)


def _flash_core_bwd(n_head, scale, causal, interpret, coff, block_q,
                    block_k, res, g):
    q, k, v, bias, qseg, kseg, out, lse2d = res
    h = n_head
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq, bk = _block_sizes(sq, sk, block_q, block_k)
    nq, nk = sq // bq, sk // bk
    has_bias, has_seg = bias is not None, qseg is not None

    if nq == 1 and nk == 1:     # lse recomputed in-kernel (see _fwd)
        dq, dk, dv, dbias = _bwd_fused(
            q, k, v, bias, qseg, kseg, out, g, h, scale, causal,
            interpret, coff, bq, bk, bh)
        return _with_seg_cotangents(dq, dk, dv, dbias, qseg, kseg)

    def _lse_spec(order):
        if order == "ij":
            return pl.BlockSpec((1, bq, 128), lambda b, i, j: (b, i, 0))
        return pl.BlockSpec((1, bq, 128), lambda b, j, i: (b, i, 0))

    dq_specs = [
        _row_spec(bq, d, 1),  # q
        _row_spec(bk, d, 2),  # k
        _row_spec(bk, d, 2),  # v
    ]
    args = [q, k, v]
    if has_bias:
        dq_specs.append(pl.BlockSpec((1, 1, bk), lambda b, i, j: (b, 0, j)))
        args.append(bias)
    if has_seg:
        dq_specs.append(
            pl.BlockSpec((1, bq, 128), lambda b, i, j: (b // h, i, 0))
        )
        dq_specs.append(
            pl.BlockSpec((1, 8, bk), lambda b, i, j: (b // h, 0, j))
        )
        args.extend([qseg, kseg])
    dq_specs += [
        _row_spec(bq, d, 1),  # o
        _row_spec(bq, d, 1),  # do
        _lse_spec("ij"),  # lse rows
    ]
    args += [out, g, lse2d]
    with jax.named_scope("flash_attention"):
        dq = pl.pallas_call(
            functools.partial(
                _bwd_dq_kernel, scale=scale, causal=causal, bq=bq, bk=bk,
                nk=nk, has_bias=has_bias, has_seg=has_seg, coff=coff,
            ),
            grid=(bh, nq, nk),
            in_specs=dq_specs,
            out_specs=_row_spec(bq, d, 1),
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
            interpret=interpret,
            name="flash_attention_bwd_dq",
        )(*args)

    # column-parallel pass: lse/o/do blocks follow the INNER grid dim (i)
    kv_specs = [
        _row_spec(bq, d, 2),  # q
        _row_spec(bk, d, 1),  # k
        _row_spec(bk, d, 1),  # v
    ]
    if has_bias:
        kv_specs.append(pl.BlockSpec((1, 1, bk), lambda b, j, i: (b, 0, j)))
    if has_seg:
        kv_specs.append(
            pl.BlockSpec((1, bq, 128), lambda b, j, i: (b // h, i, 0))
        )
        kv_specs.append(
            pl.BlockSpec((1, 8, bk), lambda b, j, i: (b // h, 0, j))
        )
    kv_specs += [
        _row_spec(bq, d, 2),  # o
        _row_spec(bq, d, 2),  # do
        _lse_spec("ji"),  # lse
    ]
    out_specs = [
        _row_spec(bk, d, 1),  # dk
        _row_spec(bk, d, 1),  # dv
    ]
    out_shape = [
        jax.ShapeDtypeStruct(k.shape, k.dtype),
        jax.ShapeDtypeStruct(v.shape, v.dtype),
    ]
    scratch = [
        pltpu.VMEM((bk, d), jnp.float32),
        pltpu.VMEM((bk, d), jnp.float32),
    ]
    if has_bias:
        out_specs.append(pl.BlockSpec((1, 1, bk), lambda b, j, i: (b, 0, j)))
        out_shape.append(jax.ShapeDtypeStruct((bh, 1, sk), bias.dtype))
        scratch.append(pltpu.VMEM((8, bk), jnp.float32))
    with jax.named_scope("flash_attention"):
        res = pl.pallas_call(
            functools.partial(
                _bwd_dkv_kernel, scale=scale, causal=causal, bq=bq, bk=bk,
                nq=nq, has_bias=has_bias, has_seg=has_seg, coff=coff,
            ),
            grid=(bh, nk, nq),
            in_specs=kv_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=scratch,
            interpret=interpret,
            name="flash_attention_bwd_dkv",
        )(*args)
    if has_bias:
        dk, dv, dbias = res
    else:
        (dk, dv), dbias = res, None

    return _with_seg_cotangents(dq, dk, dv, dbias, qseg, kseg)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)
