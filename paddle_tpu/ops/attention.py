"""Scaled-dot-product attention: pallas flash kernel on TPU, jnp oracle
elsewhere.

The naive composition materializes the [B, H, S, S] score matrix in HBM —
fine for short S, quadratic HBM traffic for long S.  The pallas kernel
(flash attention, cf. PAPERS.md) streams K/V blocks through VMEM with an
online softmax so HBM traffic stays linear in S.

Packed batches (in-graph LoD parity, reference `framework/lod_tensor.h:52`):
`segment_ids` confines attention to tokens with equal ids — the pallas path
rebuilds the mask blockwise from O(S) id vectors; the naive path expands it
to an additive bias.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import dispatch

NEG_INF = -1e30


def normalize_segment_ids(segment_ids):
    """Accept a single [B, S] id array (self-attention) or a (q, kv) pair;
    return the explicit (q_seg, kv_seg) pair.  The ONE place the two
    accepted forms are interpreted — every consumer takes the pair."""
    if segment_ids is None:
        return None
    if isinstance(segment_ids, (tuple, list)):
        qseg, kseg = segment_ids
        return qseg, kseg
    return segment_ids, segment_ids


def _segment_bias(segment_ids):
    """[B,1,Sq,Sk] additive bias from segment ids (0 allowed, -inf blocked)."""
    qseg, kseg = normalize_segment_ids(segment_ids)
    same = qseg[:, None, :, None] == kseg[:, None, None, :]
    return jnp.where(same, 0.0, NEG_INF).astype(jnp.float32)


def _naive_attention(q, k, v, bias, scale, causal):
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if causal:
        qs, ks = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((qs, ks), jnp.bool_), k=ks - qs)
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1)
    # hard-zero heavily masked entries: a fully-masked row would otherwise
    # softmax to uniform and emit mean(V); now it emits zeros
    probs = jnp.where(logits <= NEG_INF / 2, 0.0, probs)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


def naive_attention_with_layout(q, k, v, bias, scale, causal,
                                layout="BHSD"):
    """Single place that adapts the BHSD-native naive composition to a
    BSHD caller (used by the dispatch below)."""
    if layout == "BSHD":
        out = _naive_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), bias, scale, causal)
        return out.transpose(0, 2, 1, 3)
    return _naive_attention(q, k, v, bias, scale, causal)


def _naive_reason(q, k, bias, layout="BHSD"):
    """The rule that sends this call to the naive composition, or None
    when the flash kernel takes it."""
    if jax.default_backend() != "tpu":
        return "backend is not a TPU"
    # the head dim is never split (its block equals the full dim), so any
    # 64-multiple works — 64 is BERT/GPT's head size and is MXU-packable;
    # the in-kernel bias path only handles row-broadcast (padding-mask)
    # biases.  Non-128-divisible sequence lengths are fine — the kernel
    # pads to the block and slices (flash_attention pad path); below ~192
    # the naive composition wins.
    s_ax = -2 if layout == "BHSD" else -3
    sq, dim = q.shape[s_ax], q.shape[-1]
    sk = k.shape[s_ax]
    if bias is not None and bias.shape[-2] != 1:
        return "bias is not a row-broadcast (padding-mask) bias"
    if dim % 64:
        return "head_dim %d is not a multiple of 64" % dim
    if sq < 192 or sk < 192:
        return "sequence (q %d, k %d) shorter than 192" % (sq, sk)
    return None


def scaled_dot_product_attention(q, k, v, bias=None, segment_ids=None,
                                 scale=None, causal=False, layout="BHSD"):
    """q/k/v: [batch, heads, seq, head_dim] (layout="BHSD") or
    [batch, seq, heads, head_dim] ("BSHD" — the TPU-fast layout: the
    pallas kernel reads it natively so no head transpose is ever
    materialized).  segment_ids: None, [B, S], or (q_seg, kv_seg) —
    attention stays within equal segment ids (packing)."""
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    reason = _naive_reason(q, k, bias, layout)
    dispatch.record("attention", "naive" if reason else "pallas",
                    reason or "flash kernel shape rules met")
    if reason is None:
        from .pallas.attention import flash_attention

        return flash_attention(q, k, v, bias=bias, segment_ids=segment_ids,
                               scale=scale, causal=causal, layout=layout)
    if segment_ids is not None:
        sb = _segment_bias(segment_ids)
        bias = sb if bias is None else bias + sb
    return naive_attention_with_layout(q, k, v, bias, scale, causal, layout)
