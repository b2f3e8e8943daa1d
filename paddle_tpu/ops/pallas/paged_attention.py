"""Attention over a PAGED KV cache (PagedAttention, Kwon et al. 2023,
re-expressed under the repo's fixed-shape discipline).

The store is a block pool ``[num_blocks, block_size, H, D]`` shared by
every slot; a per-slot block table ``[N, max_blocks]`` int32 maps the
slot's logical block j to a physical pool block.  All shapes are static
— the table is DATA, so the decode executable count stays pinned at one
no matter how blocks migrate between requests.

Three entry points:

* `paged_decode_attention` — one query token per slot against the
  slot's table-mapped blocks.  On TPU this is a pallas kernel with the
  block table as a SCALAR-PREFETCH operand: the grid is
  ``(N, max_blocks)`` and the K/V BlockSpec index maps read
  ``tables[n, j]`` to pick the physical block each step streams through
  VMEM — the gather never materializes a dense ``[N, T, H, D]`` view in
  HBM, and blocks past ``ceil(len/bs)`` are skipped by the length mask
  exactly like the dense kernel's masked tail.  CPU (or
  ``interpret=True``) runs the same kernel through the interpreter;
  the jnp oracle is the reference both paths are pinned against.
* `paged_gather_kv` — the dense ``[N, T, H, D]`` view of a slot's
  blocks (table gather + reshape), used by the chunked-prefill path
  and the int8 dequant fallback.
* `chunked_attention_reference` — C query rows per slot over a dense
  cache view with per-row causal limits ``t <= start + i`` (the
  chunked-prefill / speculative-verify math; C == 1 degrades to the
  decode reference bit-for-bit).

int8 KV: pools may be int8 with per-row per-head scales
``[num_blocks, block_size, H]`` (``quantize_kv``/``dequantize_kv``).
Quantized pools take the gather-dequant reference path — the
documented-tolerance policy (`PADDLE_TPU_FLASH_ACC` discipline) is
owned by the engine flag that opts a cache into int8.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import dispatch
from .decode_attention import (
    _KV_VMEM_BUDGET,
    _online_softmax_block,
    _stat_scratch,
    decode_attention_reference,
    kv_block_vmem_bytes,
)

NEG_INF = -1e30

__all__ = [
    "chunked_attention_reference",
    "dequantize_kv",
    "paged_decode_attention",
    "paged_decode_attention_reference",
    "paged_gather_kv",
    "quantize_kv",
]


# ---------------------------------------------------------------------------
# int8 KV quantization (per-row, per-head scales)
# ---------------------------------------------------------------------------


def quantize_kv(x, axis=-1):
    """Symmetric int8 quantization of KV rows with per-head scales.

    x [..., H, D] float -> (q int8 [..., H, D], scale f32 [..., H])
    where ``scale = amax(|x|, D) / 127`` (floored away from zero so an
    all-zero row round-trips to exact zeros)."""
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=axis)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.float32)


def dequantize_kv(q, scale):
    """Inverse of `quantize_kv`: int8 [..., H, D] * f32 [..., H]."""
    return q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)


# ---------------------------------------------------------------------------
# gather / references
# ---------------------------------------------------------------------------


def paged_gather_kv(pool, tables, scale_pool=None):
    """Dense [N, T, H, D] view of each slot's table-mapped blocks.

    pool [NB, bs, H, D]; tables [N, max_blocks] int32; T = max_blocks *
    bs.  With ``scale_pool`` [NB, bs, H] given the pool is int8 and the
    view is dequantized f32."""
    n, nb = tables.shape
    bs, h, d = pool.shape[1], pool.shape[2], pool.shape[3]
    g = pool[tables]                       # [N, nb, bs, H, D]
    g = g.reshape(n, nb * bs, h, d)
    if scale_pool is not None:
        s = scale_pool[tables].reshape(n, nb * bs, h)
        g = dequantize_kv(g, s)
    return g


def paged_decode_attention_reference(q, k_pool, v_pool, tables, lengths,
                                     scale=None, k_scale=None,
                                     v_scale=None):
    """jnp oracle: q [N, H, D]; pools [NB, bs, H, D]; tables
    [N, max_blocks]; lengths [N].  Equals the dense decode reference on
    the gathered view — the property the paged engine's exactness test
    leans on."""
    k = paged_gather_kv(k_pool, tables, k_scale)
    v = paged_gather_kv(v_pool, tables, v_scale)
    return decode_attention_reference(q, k, v, lengths, scale)


def chunked_attention_reference(q, k_cache, v_cache, start, n_real=None,
                                scale=None):
    """C query rows per slot over a dense cache view with per-row
    causal limits: row i attends cache positions ``t <= start + i``.

    q [N, C, H, D]; k/v_cache [N, T, H, D]; start [N] int32 (position
    of row 0 — its K/V must already be IN the cache, like the decode
    step's write-then-attend contract).  C == 1 is exactly the decode
    reference.  Rows past ``n_real`` (when given) compute over the same
    mask but their output is garbage the caller ignores — they exist
    only to keep shapes static."""
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    n, c, h, d = q.shape
    t = k_cache.shape[1]
    s = jnp.einsum("nchd,nthd->nhct", q.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * scale
    pos = jnp.arange(t, dtype=jnp.int32)
    limit = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    valid = pos[None, None, :] <= limit[:, :, None]      # [N, C, T]
    s = jnp.where(valid[:, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    safe_m = jnp.where(m <= NEG_INF / 2, 0.0, m)
    p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - safe_m))
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.maximum(l, 1e-30)
    out = jnp.einsum("nhct,nthd->nchd", p,
                     v_cache.astype(jnp.float32))
    dead = jnp.transpose(m <= NEG_INF / 2, (0, 2, 1, 3))   # [N, C, H, 1]
    return jnp.where(dead, 0.0, out).astype(q.dtype)


# ---------------------------------------------------------------------------
# pallas kernel: block table as scalar prefetch
# ---------------------------------------------------------------------------


def _paged_kernel(tables_ref, lengths_ref, q_ref, k_ref, v_ref,
                  o_ref, m_ref, l_ref, acc_ref, *, scale, bs, nb):
    """Grid (N, nb): per slot, stream TABLE-MAPPED pool blocks through
    the dense kernel's online-softmax block update.  The index maps
    already routed k_ref/v_ref to pool block ``tables[n, j]``; in here
    only the length mask remains — positions ``j*bs + o >= lengths[n]``
    are killed, so blocks wholly past the length contribute nothing
    (their p rows are exactly zero)."""
    del tables_ref                      # consumed by the index maps
    _online_softmax_block(
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
        length=lengths_ref[pl.program_id(0)], j=pl.program_id(1),
        nblocks=nb, scale=scale, bk=bs)


def _pallas_paged(q, k_pool, v_pool, tables, lengths, scale, interpret):
    n, h, d = q.shape
    bs = int(k_pool.shape[1])
    nb = int(tables.shape[1])
    kernel = functools.partial(_paged_kernel, scale=scale, bs=bs, nb=nb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # tables, lengths
        grid=(n, nb),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda g, j, tab, ln: (g, 0, 0)),
            # the paged gather: logical block j of slot g IS pool block
            # tables[g, j] — the indirection lives in the index map
            # (grid indices first, then the scalar-prefetch refs)
            pl.BlockSpec((1, bs, h, d),
                         lambda g, j, tab, ln: (tab[g, j], 0, 0, 0)),
            pl.BlockSpec((1, bs, h, d),
                         lambda g, j, tab, ln: (tab[g, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda g, j, tab, ln: (g, 0, 0)),
        scratch_shapes=_stat_scratch(h, d),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, h, d), q.dtype),
        interpret=interpret,
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q, k_pool, v_pool)


def _reference_reason(k_pool, quantized):
    """The rule that sends this call to the gather reference, or None
    when the kernel takes it."""
    if quantized:
        return "int8 pool: the kernel reads float blocks only"
    if jax.default_backend() != "tpu":
        return "backend is not a TPU"
    _, bs, h, d = (int(x) for x in k_pool.shape)
    if d % 64:
        return "head_dim %d is not a multiple of 64" % d
    if bs % 128:
        return "block_size %d is not a multiple of 128" % bs
    need = kv_block_vmem_bytes(bs, h, d, k_pool.dtype)
    if need > _KV_VMEM_BUDGET:
        return ("blocks of %d rows at H=%d, D=%d need %d MiB of VMEM, "
                "over the %d MiB budget"
                % (bs, h, d, need >> 20, _KV_VMEM_BUDGET >> 20))
    return None


def paged_decode_attention(q, k_pool, v_pool, tables, lengths,
                           scale=None, interpret=None, k_scale=None,
                           v_scale=None):
    """One decode step of attention through the block table.

    q [N, H, D]; pools [NB, bs, H, D]; tables [N, max_blocks] int32;
    lengths [N] (positions ``t < lengths[n]`` attended — the engine
    writes the current token's K/V BEFORE calling, decode-kernel
    contract).  int8 pools (``k_scale``/``v_scale`` given) and
    non-TPU-tileable block sizes take the gather reference path."""
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    lengths = jnp.asarray(lengths).astype(jnp.int32)
    tables = jnp.asarray(tables).astype(jnp.int32)
    if interpret is None or k_scale is not None:
        reason = _reference_reason(k_pool, k_scale is not None)
        dispatch.record("paged_decode_attention",
                        "gather reference" if reason else "pallas",
                        reason or "paged decode kernel shape rules met")
        if reason:
            return paged_decode_attention_reference(
                q, k_pool, v_pool, tables, lengths, scale,
                k_scale=k_scale, v_scale=v_scale)
    return _pallas_paged(q, k_pool, v_pool, tables, lengths, scale,
                         bool(interpret))
