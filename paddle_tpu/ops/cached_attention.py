"""Attention over a PAGED KV cache (PagedAttention, Kwon et al. 2023,
re-expressed under the repo's fixed-shape discipline).

The store is a block pool shared by every slot, one array per layer;
a per-slot block table ``[N, max_blocks]`` int32 maps the slot's
logical block j to a physical pool block.  All shapes are static — the
table is DATA, so the decode executable count stays pinned at one no
matter how blocks migrate between requests.  The engine holds a pool
MERGED as ``[num_blocks, block_size, H*D]`` (`generation.kv_cache` says
why).  No Pallas kernel reads it: two that read ``[.., H, D]`` blocks
cost a relayout of every layer's cache each step (27.2 ms against 8.6
dense and 11.5 paged; PERF.md section 6, PR 27).

Entry points:

* `cached_attention` — what every cached forward calls (decode, chunk,
  verify; dense and paged; one chip or a head shard): write the new
  rows into the layer's merged cache arrays (`kv_write`, the one cache
  write, which the prefill uses too), then attend over the LIVE part
  of them as they lie (`_attend_live`): the live slots, longest first
  in groups, each group in chunks of positions up to its longest
  member's extent, folded into an online softmax.  Which slots are
  live and how far each reaches is read on the device from the step's
  own operands (positions, zeroed table rows, a dense cache's mask;
  `walk_plan`, which the host runs too for
  `generation_attn_walk_share`), so the loops' trip counts are data
  and one executable serves every load; no view of slots x positions
  is ever made.  `merged_attention` is the same math over a whole view:
  the reference the walk is pinned against, and the path of a call too
  wide for the block-diagonal form (a prefill chunk: one slot).
* `paged_gather_kv` — the dense view of a slot's blocks in the pool's
  own form (table gather, then ONE reshape of the view, never of the
  pool), used by the paged reference and a wide prefill chunk.
* `decode_attention_reference`, `paged_decode_attention_reference`,
  `chunked_attention_reference` — the plain jnp forms over split-head
  ``[.., H, D]`` caches that the tests hold the walk to: one query
  token per slot over a dense cache, the same through a block table,
  and C query rows per slot with per-row causal limits
  ``t <= start + i`` (the chunked-prefill / speculative-verify math,
  which a wide prefill chunk also runs; C == 1 degrades to the decode
  reference bit-for-bit).

int8 KV: pools may be int8 with per-row per-head scales
``[num_blocks, block_size, H]`` (``quantize_kv``/``dequantize_kv``),
rows quantized on write and dequantized a chunk at a time on read.  The
looser tolerance that buys is opted into by the engine flag that makes
a cache int8, never by default.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import dispatch

NEG_INF = -1e30

__all__ = [
    "attention_walk_share",
    "cached_attention",
    "chunked_attention_reference",
    "decode_attention_reference",
    "dequantize_kv",
    "kv_write",
    "merged_attention",
    "paged_decode_attention_reference",
    "paged_gather_kv",
    "quantize_kv",
    "walk_geometry",
    "walk_plan",
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


def decode_attention_reference(q, k_cache, v_cache, lengths, scale=None):
    """jnp oracle: q [N, H, D], k/v_cache [N, T, H, D], lengths [N].

    Attends positions ``t < lengths[n]``; a slot with length 0 emits
    zeros (like the flash kernel's dead rows)."""
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


def _row_limits(start, c, granule=1):
    """Last cache position each of a call's C rows attends, [N, C]: row
    i, at position ``start + i``, sees ``t <= start + i`` (causal), or
    with a mask ``granule`` B > 1 to the end of its own block of B,
    ``t < ((start + i) // B + 1) * B`` (the rows of one block see each
    other: a block-diffusion model's mask)."""
    at = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    if granule == 1:
        return at
    return (at // granule + 1) * granule - 1


def chunked_attention_reference(q, k_cache, v_cache, start, n_real=None,
                                scale=None, granule=1):
    """C query rows per slot over a dense cache view with per-row
    causal limits: row i attends cache positions ``t <= start + i``
    (`_row_limits`; a ``granule`` widens that to the row's mask block).
    A cache of G < H heads is read grouped: query head j reads head
    ``j // (H / G)``.

    q [N, C, H, D]; k/v_cache [N, T, H, D]; start [N] int32 (position
    of row 0 — its K/V must already be IN the cache, like the decode
    step's write-then-attend contract).  C == 1 is exactly the decode
    reference.  Rows past ``n_real`` (when given) compute over the same
    mask but their output is garbage the caller ignores — they exist
    only to keep shapes static."""
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    n, c, h, d = q.shape
    t, g = k_cache.shape[1:3]
    if g != h:
        # the H / G query heads of a group against their one cache head
        # (head j = group j // (H / G)): no copy of the cache a head
        s = jnp.einsum("ncgrd,ntgd->ngrct",
                       q.astype(jnp.float32).reshape(n, c, g, h // g, d),
                       k_cache.astype(jnp.float32)).reshape(n, h, c, t)
        s = s * scale
    else:
        s = jnp.einsum("nchd,nthd->nhct", q.astype(jnp.float32),
                       k_cache.astype(jnp.float32)) * scale
    pos = jnp.arange(t, dtype=jnp.int32)
    limit = _row_limits(start, c, granule)
    valid = pos[None, None, :] <= limit[:, :, None]      # [N, C, T]
    s = jnp.where(valid[:, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    safe_m = jnp.where(m <= NEG_INF / 2, 0.0, m)
    p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - safe_m))
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.maximum(l, 1e-30)
    if g != h:
        out = jnp.einsum("ngrct,ntgd->ncgrd",
                         p.reshape(n, g, h // g, c, t),
                         v_cache.astype(jnp.float32)).reshape(n, c, h, d)
    else:
        out = jnp.einsum("nhct,nthd->nchd", p,
                         v_cache.astype(jnp.float32))
    dead = jnp.transpose(m <= NEG_INF / 2, (0, 2, 1, 3))   # [N, C, H, 1]
    return jnp.where(dead, 0.0, out).astype(q.dtype)


# rows of the block-diagonal query (C*H) up to which `merged_attention`
# keeps the view merged: the measured crossover (see there)
_BLOCK_DIAGONAL_ROWS = 128


def _head_of(h, g):
    """[H, G] float32, 1 where query head j reads cache head ``j // (H /
    G)``: the identity for a cache of as many heads as the queries."""
    if g == h:
        return jnp.eye(h, dtype=jnp.float32)
    return (jnp.arange(h)[:, None] // (h // g)
            == jnp.arange(g)[None, :]).astype(jnp.float32)


def _spread_heads(q, g=None):
    """q [N, C, H, D] -> the block-diagonal queries [N, C*H, G*D] over a
    cache of G heads (default H): row (c, h) holds ``q[c, h]`` in the D
    columns of the cache head it reads, zeros elsewhere."""
    n, c, h, d = q.shape
    g = h if g is None else g
    eye = _head_of(h, g)
    return (q.astype(jnp.float32)[:, :, :, None, :]
            * eye[None, None, :, :, None]).reshape(n, c * h, g * d)


def _own_heads(full, c, h, d):
    """The diagonal blocks of a block-diagonal product: full
    [N, C*H, G*D] -> [N, C, H, D], row (c, h) keeping the columns of the
    cache head it reads."""
    g = full.shape[-1] // d
    eye = _head_of(h, g)
    return jnp.sum(full.reshape(-1, c, h, g, d)
                   * eye[None, None, :, :, None], axis=3)


def merged_attention(q, k_view, v_view, start, scale=None, granule=1):
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
    g = k_view.shape[2] // d
    if c * h > _BLOCK_DIAGONAL_ROWS:
        return chunked_attention_reference(
            q, k_view.reshape(n, t, g, d), v_view.reshape(n, t, g, d),
            start, scale=scale, granule=granule)
    if scale is None:
        scale = float(d) ** -0.5
    exact = jax.lax.Precision.HIGHEST
    s = jnp.einsum("nrm,ntm->nrt", _spread_heads(q, g),
                   k_view.astype(jnp.float32),
                   precision=exact).reshape(n, c, h, t) * scale
    pos = jnp.arange(t, dtype=jnp.int32)
    limit = _row_limits(start, c, granule)
    valid = pos[None, None, :] <= limit[:, :, None]      # [N, C, T]
    s = jnp.where(valid[:, :, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)               # [N, C, H, 1]
    safe_m = jnp.where(m <= NEG_INF / 2, 0.0, m)
    p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - safe_m))
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.maximum(l, 1e-30)
    full = jnp.einsum("nrt,ntm->nrm", p.reshape(n, c * h, t),
                      v_view.astype(jnp.float32), precision=exact)
    out = _own_heads(full, c, h, d)
    return jnp.where(m <= NEG_INF / 2, 0.0, out).astype(q.dtype)


# ---------------------------------------------------------------------------
# the walk: attention over the LIVE part of a cache
# ---------------------------------------------------------------------------

# Slots a group and positions a chunk of the walk (`_attend_live`),
# placed by the engine's own decode step on a v5e (16 slots of 1024 at
# GPT-2-medium's widths, blocks of 16; ms a step back to back with 1
# slot live at 300 tokens / 4 live at 100-700 / all 16 live at 100-700;
# the whole-table form 11.1 / 11.4 / 12.4, a step with nobody live
# 4.25; PERF.md section 6, PR 30):
#   2 x 128  4.37 / 5.07 / 9.11      4 x 256  4.78 / 5.14 / 8.80
#   4 x 128  4.66 / 5.18 / 8.40      8 x 256  6.28 / 7.45 / 9.82
#   8 x 128  4.96 / 6.92 / 8.74      4 x 512  5.42 / 6.44 / 10.71
#  16 x 128  7.40 / 10.58 / 11.15   16 x 512  7.53 / 10.72 / 11.37
# An iteration costs what it fetches (5.7 us for 4 slots x 128
# positions of K and V, 4 MB), not a launch: small groups win while
# few slots are live, and of 2, 4 and 8 slots a group 4 is the fastest
# with every slot live and gives up 0.3 ms to 2 with one.
_WALK_SLOTS = 4
_WALK_POSITIONS = 128


def walk_geometry(slots, positions, block_size=None):
    """``(G, L)``: the slots of a group and the positions of a chunk
    for a cache of ``slots`` x ``positions``.  A paged cache
    (``positions = max_blocks * block_size``) is walked in whole
    blocks.  A dense one is cut into chunks that tile it, whole
    (8, 128) tiles of rows each, so that ``[N, T, H*D]`` taken as
    ``[N*T/L, L, H*D]`` is the same bytes: the longest such L within
    `_WALK_POSITIONS`, or all T where there is none."""
    if block_size is None:
        chunk = next((l for l in range(min(_WALK_POSITIONS, positions), 7, -1)
                      if positions % l == 0 and l % 8 == 0), positions)
    else:
        chunk = min(max(_WALK_POSITIONS // block_size, 1) * block_size,
                    positions)
    return min(_WALK_SLOTS, slots), chunk


def walk_plan(extent, group, chunk, xp=jnp):
    """Which slots the walk visits and how far: the ONE definition, run
    on the device for the loops' trip counts (``xp=jnp``) and on the
    host for `generation_attn_walk_share` (``xp=numpy``).

    extent [N] int: positions a slot attends over, 0 for a dead one.
    The slots are padded with dead ones to a multiple of ``group`` and
    ordered longest first (ties by index; comparisons and sums only, so
    both array libraries give the same order).  Returns ``(order, rank,
    chunks)``: ``order[r]`` the slot of rank r, ``rank`` its inverse,
    ``chunks[g]`` the chunks group g (ranks ``g*group ..``) walks, its
    longest member's.  The groups with ``chunks > 0`` are the first
    ``ceil(live / group)``."""
    pad = -extent.shape[0] % group
    if pad:
        extent = xp.concatenate([extent, xp.zeros(pad, extent.dtype)])
    idx = xp.arange(extent.shape[0], dtype=xp.int32)
    ahead = (extent[None, :] > extent[:, None]) | (
        (extent[None, :] == extent[:, None]) & (idx[None, :] < idx[:, None]))
    rank = ahead.sum(axis=1).astype(xp.int32)
    order = ((rank[None, :] == idx[:, None]) * idx[None, :]).sum(
        axis=1).astype(xp.int32)
    chunks = -(-extent[order[::group]] // chunk)
    return order, rank, chunks.astype(xp.int32)


def attention_walk_share(extent, rows, positions, block_size=None):
    """Positions `cached_attention` fetches for a step over those it
    would for the whole cache: groups x G x chunks x L over slots x
    positions, from the host's copy of the step's operands.  ``extent``
    (numpy) as in `walk_plan`, unclipped is fine; ``rows`` = C*H of
    the call: past `_BLOCK_DIAGONAL_ROWS` the whole view is read, 1.0."""
    if rows > _BLOCK_DIAGONAL_ROWS:
        return 1.0
    slots = extent.shape[0]
    group, chunk = walk_geometry(slots, positions, block_size)
    _, _, chunks = walk_plan(np.clip(extent, 0, positions), group, chunk,
                             xp=np)
    return float(group * chunk * chunks.sum()) / (slots * positions)


def _attend_live(q, start, live, pools, tables, group, chunk, scale,
                 granule=1):
    """`merged_attention` without the view: q [N, C, H, D] (C*H within
    `_BLOCK_DIAGONAL_ROWS`); start [N] int32, row i of slot n attends
    ``t <= start[n] + i`` (with a ``granule``, to the end of its mask
    block: `_row_limits`); live [N] bool, a dead slot attends nothing
    and returns 0.  ``pools`` is ``(k, v)`` of ``[NB, bs, G*D]`` (G
    cache heads, each read by H / G query heads), or
    ``(k, v, k_scale, v_scale)`` with int8 pools and ``[NB, bs, G]``
    scales; ``tables [N, max_blocks]`` int32 maps a slot's positions to
    blocks.

    The slots are walked in groups of ``group`` in the order of
    `walk_plan` (longest first), the first ``ceil(live / group)``
    groups only; a group walks chunks of ``chunk`` positions (whole
    blocks, fetched through its rows of the table and dequantized a
    chunk at a time) up to its longest member's extent, folding each
    into the online-softmax state (m, l, acc) of its rows.  Both trip
    counts are data: one executable for every load.  Where the table's
    end clamps the last chunk, the mask leaves out what the chunk
    before it covered.  The padding slots' table rows are zeros, like a
    dead slot's.  The same block-diagonal queries and
    float32-precision products as `merged_attention`; a slot whose
    group walks past its own extent folds fully masked chunks in, which
    change nothing (corr = 1, p = 0), so a slot's result does not
    depend on who shares its group."""
    n, c, h, d = q.shape
    r, hd = c * h, pools[0].shape[-1]
    g = hd // d
    exact = jax.lax.Precision.HIGHEST
    q_bd = _spread_heads(q, g)
    start = jnp.where(live, start, -c)
    bs, blocks = pools[0].shape[1], tables.shape[1]
    cb = chunk // bs
    order, rank, chunks = walk_plan(
        jnp.clip(start + c, 0, blocks * bs), group, chunk)
    pad = order.shape[0] - n
    # a dead slot's rows start at -c: every limit is below 0
    limit = jnp.repeat(_row_limits(start, c, granule), h, axis=1)  # [N, C*H]
    # the padding slots: dead rows with nothing to attend
    q_bd = jnp.pad(q_bd, ((0, pad), (0, 0), (0, 0)))[order]
    limit = jnp.pad(limit, ((0, pad), (0, 0)), constant_values=-1)[order]
    tables = jnp.pad(tables, ((0, pad), (0, 0)))[order]
    offs = jnp.arange(chunk, dtype=jnp.int32)

    k_scale, v_scale = pools[2:] if len(pools) == 4 else (None, None)

    def fetch(pool, scales, ids):
        x = pool[ids].reshape(group, chunk, hd)
        if scales is None:
            return x.astype(jnp.float32)
        return dequantize_kv(
            x.reshape(group, chunk, g, d),
            scales[ids].reshape(group, chunk, g)).reshape(x.shape)

    def walk_group(g, out):
        at = g * group
        rows = jax.lax.dynamic_slice(tables, (at, 0), (group, blocks))
        q_g = jax.lax.dynamic_slice(q_bd, (at, 0, 0), (group, r, hd))
        lim = jax.lax.dynamic_slice(limit, (at, 0), (group, r))[..., None]

        def fold_chunk(j, state):
            m, l, acc = state
            j0 = jnp.minimum(j * cb, blocks - cb)
            ids = jax.lax.dynamic_slice(rows, (0, j0), (group, cb))
            k = fetch(pools[0], k_scale, ids)
            v = fetch(pools[1], v_scale, ids)
            s = jnp.einsum("grm,gtm->grt", q_g, k, precision=exact) * scale
            t = j0 * bs + offs
            valid = (t >= j * chunk) & (t <= lim)           # [G, C*H, L]
            s = jnp.where(valid, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            # a fully masked chunk leaves m where it was, exp(s - m) = 1
            # there: masked rows are zeroed by the mask, not the exponent
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * corr + jnp.einsum("grt,gtm->grm", p, v,
                                          precision=exact)
            return m_new, l, acc

        _, l, acc = jax.lax.fori_loop(
            0, chunks[g], fold_chunk,
            (jnp.full((group, r, 1), NEG_INF, jnp.float32),
             jnp.zeros((group, r, 1), jnp.float32),
             jnp.zeros((group, r, hd), jnp.float32)))
        ctx = acc / jnp.where(l == 0.0, 1.0, l)             # dead rows: 0
        return jax.lax.dynamic_update_slice(out, ctx, (at, 0, 0))

    full = jax.lax.fori_loop(
        0, jnp.sum(chunks > 0), walk_group,
        jnp.zeros((n + pad, r, hd), jnp.float32))[rank[:n]]
    return _own_heads(full, c, h, d).astype(q.dtype)


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


def cached_attention(q, k_new, v_new, cache, scale=None, granule=1):
    """Decode/chunk attention over one layer's cache: write the C new
    tokens' K/V at positions ``pos..pos+C-1``, then attend row i over
    positions ``<= pos+i`` (C == 1 is the classic decode step; C > 1 is
    a chunked-prefill / speculative-verify call).  With a mask
    ``granule`` B > 1 row i attends to the end of its own block of B
    positions (`_row_limits`): the C = B rows of a block-diffusion
    model's step, written first, then each seeing all of them.  Fixed
    shapes throughout — each (C,) config compiles once.

    q [B, C, H, D] (H the local heads under tensor parallelism); k_new,
    v_new [B, C, G, D], G = H or, for grouped K/V heads, a divisor of
    it (query head j reads cache head ``j // (H / G)``; the cache
    arrays are then G*D wide).  Cache tuple forms, arrays merged as
    `generation.kv_cache` holds them:

    * dense  — ``(k_cache, v_cache, pos)`` with ``[B, T, H*D]`` arrays,
      or ``(k_cache, v_cache, pos, live)`` with a ``[B]`` bool (None:
      every slot): a slot that is not live attends nothing (a dense
      cache has no table row to zero);
    * paged  — ``(k_pool, v_pool, pos, tables, block_size)`` with
      ``[NB, bs, H*D]`` pools and a ``[B, max_blocks]`` int32 block
      table: writes scatter through the table, and a slot whose table
      row is all zeros (`GenerationEngine._decode_tables`) is not live;
    * paged int8 — ``(k_pool, v_pool, k_scale, v_scale, pos, tables,
      block_size)``: int8 pools + per-row per-head f32 scales
      ``[NB, bs, H]``, rows quantized on write and dequantized on read.

    Returns ``(ctx [B, C, H, D], updated cache arrays)``, the arrays in
    the order the tuple carried them.  The attend half walks the arrays
    as they lie (`_attend_live`): live slots only, longest first in
    groups, each group in chunks of positions up to its longest
    member's ``pos + C``; a chunk is fetched through the table
    (``pool[tables[slots, j*cb:(j+1)*cb]]``, dequantized if int8; a
    dense cache is walked as the pool its chunks make, in order).  No
    view of slots x positions exists, and a dead slot's row of ctx is
    0.  A call wider
    than `_BLOCK_DIAGONAL_ROWS` (a prefill chunk: one slot) keeps
    `merged_attention` over that slot's whole view."""
    if len(cache) not in (3, 4, 5, 7):
        raise ValueError(
            "cache tuple must have 3 or 4 (dense), 5 (paged) or 7 "
            "(paged int8) entries, got %d" % len(cache))
    dense = len(cache) in (3, 4)
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
    g = k_new.shape[2]
    arrays = kv_write(arrays, i0.ravel(), i1.ravel(),
                      k_new.reshape(b * c, g, d),
                      v_new.reshape(b * c, g, d))
    if c == 1:
        # nothing is chosen here: the count is what
        # `decode_attn_kernel_share.serve` reads (0% with it, null
        # without), and goes with that metric (ROADMAP D10)
        dispatch.record(
            "decode_attention", "walk",
            "no decode kernel exists: the live part of the merged cache "
            "is walked as it lies")
    if c * h > _BLOCK_DIAGONAL_ROWS:
        k_view, v_view = arrays[:2]
        if not dense:
            k_scale, v_scale = arrays[2:] if n_arr == 4 else (None, None)
            k_view = paged_gather_kv(k_view, tables, k_scale)
            v_view = paged_gather_kv(v_view, tables, v_scale)
        return merged_attention(q, k_view, v_view, pos, scale=scale,
                                granule=granule), arrays

    if dense:
        live = cache[3] if len(cache) == 4 else None
        live = (jnp.ones((b,), bool) if live is None
                else jnp.asarray(live).astype(bool))
        positions = arrays[0].shape[1]
        group, chunk = walk_geometry(b, positions)
        # a dense cache is a pool whose blocks are its chunks, in order
        # (the same bytes: `walk_geometry` cuts it in whole tiles)
        per = positions // chunk
        pools = tuple(a.reshape(b * per, chunk, g * d) for a in arrays)
        tables = jnp.arange(b * per, dtype=jnp.int32).reshape(b, per)
    else:
        live = jnp.any(tables != 0, axis=1)
        group, chunk = walk_geometry(b, tables.shape[1] * bs, bs)
        pools = arrays
    ctx = _attend_live(q, pos, live, pools, tables, group, chunk, scale,
                       granule)
    return ctx, arrays
