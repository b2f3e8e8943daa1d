"""Attention over a PAGED KV cache (PagedAttention, Kwon et al. 2023,
re-expressed under the repo's fixed-shape discipline).

The store is a block pool shared by every slot, one array per layer;
a per-slot block table ``[N, max_blocks]`` int32 maps the slot's
logical block j to a physical pool block.  All shapes are static — the
table is DATA, so the decode executable count stays pinned at one no
matter how blocks migrate between requests.  The engine holds a pool
MERGED as ``[num_blocks, block_size, H*D]`` (`generation.kv_cache` says
why); the kernel reads ``[num_blocks, block_size, H, D]``.

Entry points:

* `cached_attention` — what every cached forward calls (decode, chunk,
  verify; dense and paged; one chip or a head shard): write the new
  rows into the layer's merged cache arrays (`kv_write`, the one cache
  write, which the prefill uses too), then attend over them as they
  lie (`merged_attention`).
* `paged_decode_attention` — one query token per slot against the
  slot's table-mapped blocks of a ``[NB, bs, H, D]`` pool.  On TPU
  this is a pallas kernel with the
  block table as a SCALAR-PREFETCH operand: the grid is
  ``(N, max_blocks)`` and the K/V BlockSpec index maps read
  ``tables[n, j]`` to pick the physical block each step streams through
  VMEM — the gather never materializes a dense ``[N, T, H, D]`` view in
  HBM, and blocks past ``ceil(len/bs)`` are skipped by the length mask
  exactly like the dense kernel's masked tail.  CPU (or
  ``interpret=True``) runs the same kernel through the interpreter;
  the jnp oracle is the reference both paths are pinned against.
* `paged_gather_kv` — the dense view of a slot's blocks in the pool's
  own form (table gather, then ONE reshape of the view, never of the
  pool), used by the gather reference, the cached forward and the int8
  dequant fallback.
* `chunked_attention_reference` — C query rows per slot over a dense
  ``[N, T, H, D]`` cache view with per-row causal limits
  ``t <= start + i`` (the chunked-prefill / speculative-verify math;
  C == 1 degrades to the decode reference bit-for-bit).

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
    "cached_attention",
    "chunked_attention_reference",
    "dequantize_kv",
    "kv_write",
    "merged_attention",
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
    """Dense view of each slot's table-mapped blocks, in the pool's own
    form: [N, T, H, D] of a pool [NB, bs, H, D], [N, T, H*D] of a
    merged pool [NB, bs, H*D] (the heads are never split on a POOL:
    that would be a pass over all of it).  tables [N, max_blocks]
    int32; T = max_blocks * bs.  With ``scale_pool`` [NB, bs, H] given
    the pool is int8 and the view is dequantized f32."""
    n, nb = tables.shape
    bs = pool.shape[1]
    g = pool[tables]                       # [N, nb, bs, ...]
    g = g.reshape((n, nb * bs) + pool.shape[2:])
    if scale_pool is not None:
        h = scale_pool.shape[-1]
        s = scale_pool[tables].reshape(n, nb * bs, h)
        g = dequantize_kv(g.reshape(n, nb * bs, h, -1), s).reshape(g.shape)
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


# rows of the block-diagonal query (C*H) up to which `merged_attention`
# keeps the view merged: the measured crossover (see there)
_BLOCK_DIAGONAL_ROWS = 128


def merged_attention(q, k_view, v_view, start, scale=None):
    """`chunked_attention_reference` over cache views that keep the
    heads MERGED: q [N, C, H, D]; k/v_view [N, T, H*D] (a dense cache
    as it is held, or `paged_gather_kv` of a merged pool);
    start [N].  What every cached forward runs.

    Splitting the heads of a view (``[N, T, H*D] -> [N, T, H, D]``)
    costs a pass over it into a layout padded to twice its size when D
    is 64, and the products then read that.  Instead the QUERIES are
    spread block-diagonally, row (c, h) holding ``q[c, h]`` in head h's
    D columns and zeros elsewhere: scores are one ``[C*H, H*D] x
    [H*D, T]`` matmul a slot over the view as it lies, the context one
    ``[C*H, T] x [T, H*D]`` matmul whose diagonal blocks are the
    answer.  The zeros add nothing to a sum (the same products summed
    as in the split form).  Both matmuls ask for float32 precision
    (``Precision.HIGHEST``): a one-token query's products were float32
    multiply-reduces on the vector unit before the cache was merged,
    and the layout must not change what a float32 cache buys (against
    float64 on the chip: 4e-7 like the split form's, 1.4e-3 in one
    bfloat16 pass; PERF.md section 6, PR 27).

    The block-diagonal form does H times the split form's work.  That
    is free while ``C*H`` stays within the 128 rows the MXU pads a
    matmul to anyway; past them the split form wins, and more the wider
    the call (one slot's chunk at H = 16 on a v5e: the two tie at 128
    rows, 0.092 against 0.064 ms a layer at 256, a 256-token chunk of
    GPT-2-medium 19.7 against 6.0 ms).  Such a call is a prefill chunk
    (one slot, so a small view to split) and runs
    `chunked_attention_reference` as it always has; decode (H rows) and
    speculative verify ((k+1)*H) stay merged."""
    n, c, h, d = q.shape
    t = k_view.shape[1]
    if c * h > _BLOCK_DIAGONAL_ROWS:
        return chunked_attention_reference(
            q, k_view.reshape(n, t, h, d), v_view.reshape(n, t, h, d),
            start, scale=scale)
    if scale is None:
        scale = float(d) ** -0.5
    exact = jax.lax.Precision.HIGHEST
    eye = jnp.eye(h, dtype=jnp.float32)
    q_bd = (q.astype(jnp.float32)[:, :, :, None, :]
            * eye[None, None, :, :, None]).reshape(n, c * h, h * d)
    s = jnp.einsum("nrm,ntm->nrt", q_bd, k_view.astype(jnp.float32),
                   precision=exact).reshape(n, c, h, t) * scale
    pos = jnp.arange(t, dtype=jnp.int32)
    limit = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    valid = pos[None, None, :] <= limit[:, :, None]      # [N, C, T]
    s = jnp.where(valid[:, :, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)               # [N, C, H, 1]
    safe_m = jnp.where(m <= NEG_INF / 2, 0.0, m)
    p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - safe_m))
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.maximum(l, 1e-30)
    full = jnp.einsum("nrt,ntm->nrm", p.reshape(n, c * h, t),
                      v_view.astype(jnp.float32), precision=exact)
    out = jnp.sum(full.reshape(n, c, h, h, d) * eye[None, None, :, :, None],
                  axis=3)                                # [N, C, H, D]
    return jnp.where(m <= NEG_INF / 2, 0.0, out).astype(q.dtype)


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


# ---------------------------------------------------------------------------
# the cached forward's attention: one write, then attend
# ---------------------------------------------------------------------------


def kv_write(arrays, i0, i1, k_rows, v_rows):
    """THE cache write: row r of ``k_rows``/``v_rows`` [R, H, D] goes to
    ``[i0[r], i1[r]]`` of one layer's cache arrays (block and offset of
    a paged pool, slot and position of a dense cache).

    ``arrays`` is the layer's ``(k, v)`` of ``[A, B, H*D]``, or for an
    int8 pool ``(k, v, k_scale, v_scale)`` with scales ``[A, B, H]``:
    rows are quantized on the way in.  Each result is a scatter into
    its operand, so with the operand donated the write is in place and
    the result is the step's output (decode, prefill, chunk, verify and
    the draft model's dense cache all write through here)."""
    r, h, d = k_rows.shape
    if len(arrays) == 4:
        k, v, k_scale, v_scale = arrays
        k_rows, k_s = quantize_kv(k_rows)
        v_rows, v_s = quantize_kv(v_rows)
        scales = (k_scale.at[i0, i1].set(k_s), v_scale.at[i0, i1].set(v_s))
    else:
        (k, v), scales = arrays, ()
    return (k.at[i0, i1].set(k_rows.reshape(r, h * d).astype(k.dtype)),
            v.at[i0, i1].set(v_rows.reshape(r, h * d).astype(v.dtype)),
            *scales)


def cached_attention(q, k_new, v_new, cache, scale=None):
    """Decode/chunk attention over one layer's cache: write the C new
    tokens' K/V at positions ``pos..pos+C-1``, then attend row i over
    positions ``<= pos+i`` (C == 1 is the classic decode step; C > 1 is
    a chunked-prefill / speculative-verify call).  Fixed shapes
    throughout — each (C,) config compiles once.

    q, k_new, v_new [B, C, H, D] (H the local heads under tensor
    parallelism).  Cache tuple forms, arrays merged as
    `generation.kv_cache` holds them:

    * dense  — ``(k_cache, v_cache, pos)`` with ``[B, T, H*D]`` arrays;
    * paged  — ``(k_pool, v_pool, pos, tables, block_size)`` with
      ``[NB, bs, H*D]`` pools and a ``[B, max_blocks]`` int32 block
      table: writes scatter through the table, attention gathers
      through it;
    * paged int8 — ``(k_pool, v_pool, k_scale, v_scale, pos, tables,
      block_size)``: int8 pools + per-row per-head f32 scales
      ``[NB, bs, H]``, rows quantized on write and dequantized on read.

    Returns ``(ctx [B, C, H, D], updated cache arrays)``, the arrays in
    the order the tuple carried them.  Attention runs over the arrays
    as they lie (`merged_attention`), for a paged pool over the gather
    of the slots' blocks."""
    if len(cache) not in (3, 5, 7):
        raise ValueError(
            "cache tuple must have 3 (dense), 5 (paged) or 7 "
            "(paged int8) entries, got %d" % len(cache))
    dense = len(cache) == 3
    n_arr = 4 if len(cache) == 7 else 2
    arrays = tuple(jnp.asarray(a) for a in cache[:n_arr])
    pos = jnp.asarray(cache[n_arr]).astype(jnp.int32)
    b, c, h, d = q.shape
    if scale is None:
        scale = float(d) ** -0.5
    p = pos[:, None] + jnp.arange(c, dtype=jnp.int32)[None]     # [B, C]
    if dense:
        i0 = jnp.broadcast_to(jnp.arange(b, dtype=jnp.int32)[:, None],
                              (b, c))
        i1 = p
    else:
        # position p -> pool block tables[n, p // bs], row p % bs.
        # Inactive slots' tables are all-zero, so their garbage rows
        # land in the reserved block nobody reads.
        tables = jnp.asarray(cache[n_arr + 1]).astype(jnp.int32)
        bs = int(cache[n_arr + 2])
        logical = jnp.clip(p // bs, 0, tables.shape[1] - 1)
        i0 = jnp.take_along_axis(tables, logical, axis=1)
        i1 = p % bs
    arrays = kv_write(arrays, i0.ravel(), i1.ravel(),
                      k_new.reshape(b * c, h, d),
                      v_new.reshape(b * c, h, d))
    k_view, v_view = arrays[:2]
    if not dense:
        k_scale, v_scale = arrays[2:] if n_arr == 4 else (None, None)
        k_view = paged_gather_kv(k_view, tables, k_scale)
        v_view = paged_gather_kv(v_view, tables, v_scale)
    if c == 1:
        # the decode kernels read [.., H, D] blocks: on a merged cache
        # that is a relayout of all of it every step, three times the
        # cost of attending over it as it lies (27.2 against 8.6 ms a
        # step dense, 11.5 paged; PERF.md section 6, PR 27)
        dispatch.record(
            "decode_attention" if dense else "paged_decode_attention",
            "reference" if dense else "gather reference",
            "the cache's heads are merged; the kernel reads [.., H, D]")
    return merged_attention(q, k_view, v_view, pos, scale=scale), arrays
