"""Single-token attention over a fixed-shape KV cache (the decode step).

Autoregressive decoding asks a shape the training flash kernel never
sees: ONE query token per sequence against a [slots, max_len, H, D]
cache of which only the first ``lengths[slot]`` positions are real.
The arithmetic intensity is ~1 FLOP per cache byte — the step is HBM-
bandwidth bound (see ``analysis.perf.decode_step_cost``), so the kernel
exists to stream the cache through VMEM exactly once with an online
softmax, never materializing the [slots, H, max_len] score tensor in
HBM and never reading past what a block of the length mask kills.

Layout: the cache is the engine's native [N, T, H, D] (slot-major,
sequence, heads, head_dim — the BSHD discipline of PR 11, so prefill's
flash output K/V slices copy straight in with no transpose).  The
query is [N, H, D] (one token per slot).  Per slot the kernel computes

    s[h, t] = scale * sum_d q[h, d] * k[t, h, d]      (t < lengths[n])
    out[h, :] = softmax_t(s[h, :]) @ v[:, h, :]

with a [H, bk] score tile per cache block — heads are the sublane axis,
so a 12-head model still feeds the MXU 12 rows per block instead of
one.  Free slots (lengths == 0) emit zeros, exactly like the flash
kernel's dead-row handling.

On CPU (or ``interpret=True``) the same kernel runs through the pallas
interpreter; the jnp oracle below is the reference the tests pin both
paths against.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import dispatch

NEG_INF = -1e30

__all__ = ["decode_attention", "decode_attention_reference"]


def decode_attention_reference(q, k_cache, v_cache, lengths, scale=None):
    """jnp oracle: q [N, H, D], k/v_cache [N, T, H, D], lengths [N].

    Attends positions ``t < lengths[n]``; a slot with length 0 emits
    zeros (matches the kernel's dead-row handling)."""
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    s = jnp.einsum("nhd,nthd->nht", q.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * scale
    t = jnp.arange(k_cache.shape[1])
    valid = t[None, :] < lengths[:, None]              # [N, T]
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    safe_m = jnp.where(m <= NEG_INF / 2, 0.0, m)
    p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - safe_m))
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.maximum(l, 1e-30)
    out = jnp.einsum("nht,nthd->nhd", p, v_cache.astype(jnp.float32))
    dead = (m <= NEG_INF / 2)                          # [N, H, 1]
    return jnp.where(dead, 0.0, out).astype(q.dtype)


# K and V blocks are double-buffered in VMEM, whose scoped limit is
# 16 MiB on a v5e; the rest is left to the kernel's own temporaries
_KV_VMEM_BUDGET = 12 << 20


def kv_block_vmem_bytes(rows, h, d, dtype):
    """VMEM held by the pipelined K and V blocks of ``rows`` cache rows:
    two arrays, two buffers each, with the [H, D] minor dims padded to
    the dtype's (sublane, 128) tile."""
    itemsize = jnp.dtype(dtype).itemsize
    sublane = 8 * (4 // itemsize)
    h_pad = -(-h // sublane) * sublane
    d_pad = -(-d // 128) * 128
    return 4 * rows * h_pad * d_pad * itemsize


def _pick_block_k(t, h, d, dtype):
    """Largest standard block that divides ``t`` and fits the budget."""
    for b in (512, 256, 128):
        if t % b == 0 and kv_block_vmem_bytes(b, h, d, dtype) \
                <= _KV_VMEM_BUDGET:
            return b
    return None


def _online_softmax_block(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                          *, length, j, nblocks, scale, bk):
    """One cache block of a slot's decode attention: fold rows
    ``j*bk .. (j+1)*bk`` (those below ``length``) into the running
    (m, l, acc) online-softmax statistics, and emit on the last block.

    Shared by the dense and the paged kernel.  The score and value
    products are broadcast-multiply-reduce on the VPU over the cache's
    native [bk, H, D] block: a one-token query has no non-contracting
    dimension to give the MXU (Mosaic refuses that ``dot_general``), and
    keeping the block's layout needs no in-kernel transpose.  Scores
    stay [bk, H, 1] so the heads never leave the sublane axis."""

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                   # [H, D]
    k = k_ref[0].astype(jnp.float32)                   # [bk, H, D]
    v = v_ref[0].astype(jnp.float32)                   # [bk, H, D]
    s = jnp.sum(k * q[None], axis=-1, keepdims=True) * scale  # [bk, H, 1]
    pos = jax.lax.broadcasted_iota(jnp.int32, (bk, 1, 1), 0) + j * bk
    live = pos < length
    s = jnp.where(live, s, NEG_INF)

    m_prev = m_ref[:, :1]                              # [H, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
    # a fully masked block leaves m at NEG_INF, where exp(s - m) = 1:
    # dead rows are zeroed by the mask, not by the exponent
    p = jnp.where(live, jnp.exp(s - m_new[None]), 0.0)  # [bk, H, 1]
    corr = jnp.exp(m_prev - m_new)
    l_new = l_ref[:, :1] * corr + jnp.sum(p, axis=0)
    acc_ref[...] = acc_ref[...] * corr + jnp.sum(p * v, axis=0)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == nblocks - 1)
    def _finalize():
        l = l_ref[:, :1]
        out = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
        dead = m_ref[:, :1] <= NEG_INF / 2             # empty slot
        o_ref[0] = jnp.where(dead, 0.0, out).astype(o_ref.dtype)


def _kernel(lengths_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, scale, bk, nk):
    """Grid (N, nk): per slot, stream cache blocks through
    `_online_softmax_block`."""
    _online_softmax_block(
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
        length=lengths_ref[pl.program_id(0)], j=pl.program_id(1),
        nblocks=nk, scale=scale, bk=bk)


def _stat_scratch(h, d):
    return [
        pltpu.VMEM((h, 128), jnp.float32),   # running row max
        pltpu.VMEM((h, 128), jnp.float32),   # running row sum
        pltpu.VMEM((h, d), jnp.float32),     # output accumulator
    ]


def _pallas_decode(q, k_cache, v_cache, lengths, scale, interpret,
                   block_k=None):
    n, t, h, d = k_cache.shape
    # no standard divisor: run the whole cache as one block.  Fine in
    # interpret mode (tests at any max_len); on real TPU the auto
    # dispatch only takes this path when a 128-multiple block divides T
    # (_reference_reason), so an explicit caller owns the tiling
    # constraint.
    bk = block_k or _pick_block_k(t, h, d, k_cache.dtype) or t
    if t % bk:
        raise ValueError(
            "block_k=%d does not divide cache length %d" % (bk, t))
    nk = t // bk
    kernel = functools.partial(_kernel, scale=scale, bk=bk, nk=nk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,          # lengths: the mask is built in-kernel
        grid=(n, nk),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda g, j, ln: (g, 0, 0)),
            pl.BlockSpec((1, bk, h, d), lambda g, j, ln: (g, j, 0, 0)),
            pl.BlockSpec((1, bk, h, d), lambda g, j, ln: (g, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda g, j, ln: (g, 0, 0)),
        scratch_shapes=_stat_scratch(h, d),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, h, d), q.dtype),
        interpret=interpret,
    )(lengths, q, k_cache, v_cache)


def _reference_reason(k_cache):
    """The rule that sends this call to the jnp reference, or None when
    the kernel takes it."""
    if jax.default_backend() != "tpu":
        return "backend is not a TPU"
    _, t, h, d = k_cache.shape
    if d % 64:
        return "head_dim %d is not a multiple of 64" % d
    if _pick_block_k(t, h, d, k_cache.dtype) is None:
        return ("no 128-multiple block divides cache length %d and fits "
                "%d MiB of VMEM at H=%d, D=%d"
                % (t, _KV_VMEM_BUDGET >> 20, h, d))
    return None


def decode_attention(q, k_cache, v_cache, lengths, scale=None,
                     interpret=None, block_k=None):
    """One decode step of attention over the cache.

    q: [N, H, D] (the current token's projected queries, one per slot);
    k_cache/v_cache: [N, T, H, D]; lengths: [N] int — positions
    ``t < lengths[n]`` are attended (the engine writes the current
    token's K/V at index ``lengths-1`` BEFORE calling, so the token
    attends to itself).  Returns [N, H, D]."""
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    lengths = lengths.astype(jnp.int32)
    if interpret is None:
        reason = _reference_reason(k_cache)
        dispatch.record("decode_attention",
                        "reference" if reason else "pallas",
                        reason or "dense decode kernel shape rules met")
        if reason:
            return decode_attention_reference(q, k_cache, v_cache,
                                              lengths, scale)
    return _pallas_decode(q, k_cache, v_cache, lengths, scale,
                          bool(interpret), block_k=block_k)
