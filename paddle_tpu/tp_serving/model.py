"""Shard-local functional TransformerLM forward for tensor-parallel
serving — the math each chip runs inside `jax.shard_map` over the
``("tp",)`` mesh.

This mirrors the single-chip lowering op for op (`models.transformer_lm`
through `fluid/ops`): f32 LayerNorm (eps 1e-5), erf gelu, the flattened
``mul`` matmul for Linear, the same attention dispatch
(`ops.attention.scaled_dot_product_attention` for prefill,
`ops.pallas.decode_attention` / `paged_attention` for cached decode),
and tied-embedding logits.  Each shard holds ``H/tp`` heads and
``I/tp`` FFN columns; per-head attention math and column-parallel
matmuls are bit-exact per shard, and the only place the floating-point
reduction order differs from the single-chip engine is the
``lax.psum`` closing each row-parallel projection (out_proj, fc2) —
two all-reduces per layer, after which activations are replicated, so
sampling sees identical logits on every chip.  Token-identity against
the single-chip engine is drilled empirically at fixed seeds
(`tests/test_tp_serving.py`), the same discipline PR 17 documented for
the chunk/verify reference paths.

All functions here take the LOCAL parameter shards (see
`tp_serving.layout`: the fused qkv output axis is pre-grouped so the
local thirds are this shard's q/k/v) and local KV cache arrays
(``H/tp`` on the heads axis); scalars/tables/tokens arrive replicated.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops.attention import scaled_dot_product_attention
from ..ops.pallas.decode_attention import decode_attention
from ..ops.pallas.paged_attention import (
    chunked_attention_reference,
    paged_decode_attention,
    paged_gather_kv,
    quantize_kv,
)

__all__ = ["cached_forward", "prefill_forward"]

AXIS = "tp"


def _linear(x, w, b=None):
    """The ``mul`` op's lowering: flatten to 2D, one matmul, reshape;
    broadcast bias add on the last axis."""
    out = jnp.matmul(x.reshape(-1, x.shape[-1]), w)
    out = out.reshape(x.shape[:-1] + (w.shape[-1],))
    return out if b is None else out + b


def _layer_norm(x, scale, bias, eps=1e-5):
    """`fluid.ops.nn_ops._ln_fwd_impl` forward (f32, rsqrt)."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    y = y * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def _qkv_split(p, li, x, h_loc, d_head):
    """Local fused-qkv projection -> ``[B, S, h_loc, Dh]`` triple.
    The local weight's columns are this shard's ``[q | k | v]`` after
    `layout.prepare_tp_params`, so thirds slice exactly like the
    single-chip fused projection."""
    pre = "blocks.%d.attn." % li
    qkv = _linear(x, p[pre + "qkv_proj.weight"], p[pre + "qkv_proj.bias"])
    d_loc = h_loc * d_head
    b, s = qkv.shape[0], qkv.shape[1]

    def split(lo):
        return qkv[..., lo:lo + d_loc].reshape(b, s, h_loc, d_head)

    return split(0), split(d_loc), split(2 * d_loc)


def _close_row_parallel(partial, bias):
    """Row-parallel epilogue: ONE all-reduce, then the replicated
    bias.  The two calls per layer (attention out_proj, FFN fc2) are
    the layer's only collectives."""
    return jax.lax.psum(partial, AXIS) + bias


def _attn_prefill(p, li, x, h_loc, d_head):
    """Causal self-attention over this shard's heads; returns the
    block's attention output (replicated, post-psum) and the local
    ``(k, v)`` rows ``[B, S, h_loc, Dh]`` for the cache."""
    q, k, v = _qkv_split(p, li, x, h_loc, d_head)
    ctx = scaled_dot_product_attention(
        q, k, v, scale=d_head ** -0.5, causal=True, layout="BSHD")
    b, s = ctx.shape[0], ctx.shape[1]
    pre = "blocks.%d.attn." % li
    part = _linear(ctx.reshape(b, s, h_loc * d_head),
                   p[pre + "out_proj.weight"])
    return _close_row_parallel(part, p[pre + "out_proj.bias"]), (k, v)


def _attn_cached(p, li, x, cache, h_loc, d_head):
    """`models.bert.MultiHeadAttention._decode_with_cache` ported to
    local head shards: write the C new rows, attend row i over
    positions ``<= pos+i``.  Cache tuple forms are the model's (dense /
    paged / paged-int8), with all arrays carrying ``h_loc`` heads."""
    q, k, v = _qkv_split(p, li, x, h_loc, d_head)
    scale = d_head ** -0.5
    c_len = q.shape[1]
    if len(cache) == 3:                              # dense
        k_cache, v_cache, pos = cache
        pos = jnp.asarray(pos).astype(jnp.int32)

        def write_rows(cbuf, new, s):
            return jax.lax.dynamic_update_slice(cbuf, new, (s, 0, 0))

        k_cache = jax.vmap(write_rows)(jnp.asarray(k_cache), k, pos)
        v_cache = jax.vmap(write_rows)(jnp.asarray(v_cache), v, pos)
        if c_len == 1:
            ctx = decode_attention(q[:, 0], k_cache, v_cache, pos + 1,
                                   scale=scale)[:, None]
        else:
            ctx = chunked_attention_reference(q, k_cache, v_cache, pos,
                                              scale=scale)
        new_cache = (k_cache, v_cache)
    else:                                            # paged / paged int8
        if len(cache) == 5:
            k_pool, v_pool, pos, tables, bs = cache
            k_scale = v_scale = None
        else:
            k_pool, v_pool, k_scale, v_scale, pos, tables, bs = cache
        bs = int(bs)
        pos = jnp.asarray(pos).astype(jnp.int32)
        tables = jnp.asarray(tables).astype(jnp.int32)
        nb = int(tables.shape[1])
        pp = pos[:, None] + jnp.arange(c_len, dtype=jnp.int32)[None]
        logical = jnp.clip(pp // bs, 0, nb - 1)
        bi = jnp.take_along_axis(tables, logical, axis=1).ravel()
        off = (pp % bs).ravel()
        k_pool = jnp.asarray(k_pool)
        v_pool = jnp.asarray(v_pool)
        k_rows = k.reshape(-1, h_loc, d_head)
        v_rows = v.reshape(-1, h_loc, d_head)
        if k_scale is not None:
            k_q, k_s = quantize_kv(k_rows)
            v_q, v_s = quantize_kv(v_rows)
            k_pool = k_pool.at[bi, off].set(k_q)
            v_pool = v_pool.at[bi, off].set(v_q)
            k_scale = jnp.asarray(k_scale).at[bi, off].set(k_s)
            v_scale = jnp.asarray(v_scale).at[bi, off].set(v_s)
        else:
            k_pool = k_pool.at[bi, off].set(k_rows.astype(k_pool.dtype))
            v_pool = v_pool.at[bi, off].set(v_rows.astype(v_pool.dtype))
        if c_len == 1:
            ctx = paged_decode_attention(
                q[:, 0], k_pool, v_pool, tables, pos + 1, scale=scale,
                k_scale=k_scale, v_scale=v_scale)[:, None]
        else:
            k_dense = paged_gather_kv(k_pool, tables, k_scale)
            v_dense = paged_gather_kv(v_pool, tables, v_scale)
            ctx = chunked_attention_reference(q, k_dense, v_dense, pos,
                                              scale=scale)
        new_cache = ((k_pool, v_pool) if k_scale is None
                     else (k_pool, v_pool, k_scale, v_scale))
    b = ctx.shape[0]
    pre = "blocks.%d.attn." % li
    part = _linear(ctx.reshape(b, c_len, h_loc * d_head),
                   p[pre + "out_proj.weight"])
    return _close_row_parallel(part, p[pre + "out_proj.bias"]), new_cache


def _ffn(p, li, x):
    """Column-parallel fc1 + erf gelu, row-parallel fc2 + psum."""
    pre = "blocks.%d." % li
    h = _linear(x, p[pre + "fc1.weight"], p[pre + "fc1.bias"])
    part = _linear(jax.nn.gelu(h, approximate=False),
                   p[pre + "fc2.weight"])
    return _close_row_parallel(part, p[pre + "fc2.bias"])


def _block(p, li, x, cache, use_cache, h_loc, d_head):
    pre = "blocks.%d." % li
    h1 = _layer_norm(x, p[pre + "ln1.weight"], p[pre + "ln1.bias"])
    if cache is None:
        a, kv = _attn_prefill(p, li, h1, h_loc, d_head)
        kv = kv if use_cache else None
    else:
        a, kv = _attn_cached(p, li, h1, cache, h_loc, d_head)
    x = x + a
    h2 = _layer_norm(x, p[pre + "ln2.weight"], p[pre + "ln2.bias"])
    x = x + _ffn(p, li, h2)
    return x, kv


def _embed(p, ids, pos_ids):
    return (p["word.weight"][jnp.asarray(ids, jnp.int32)]
            + p["position.weight"][jnp.asarray(pos_ids, jnp.int32)])


def _finalize(p, h):
    h = _layer_norm(h, p["ln_f.weight"], p["ln_f.bias"])
    return jnp.matmul(h, jnp.swapaxes(p["word.weight"], -1, -2))


def prefill_forward(p, ids, pos_ids, cfg, tp):
    """Full causal forward; returns ``(logits, [(k, v), ...])`` with
    per-layer LOCAL kv rows (`TransformerLM.forward(use_cache=True)`
    contract, heads axis sharded)."""
    h_loc = cfg.num_heads // tp
    h = _embed(p, ids, pos_ids)
    kvs = []
    for li in range(cfg.num_layers):
        h, kv = _block(p, li, h, None, True, h_loc, cfg.head_dim)
        kvs.append(kv)
    return _finalize(p, h), kvs


def cached_forward(p, ids, pos_ids, caches, cache_positions, cfg, tp,
                   block_tables=None, block_size=None):
    """Decode/chunk/verify forward over stacked LOCAL cache arrays
    (`TransformerLM.forward(caches=...)` contract): S tokens per row
    written at ``cache_positions..+S-1``; returns ``(logits, updated
    stacks)``."""
    h_loc = cfg.num_heads // tp
    stacks = [jnp.asarray(c) for c in caches]
    out_rows = [[] for _ in stacks]
    h = _embed(p, ids, pos_ids)
    for li in range(cfg.num_layers):
        per_layer = tuple(s[li] for s in stacks)
        if block_tables is None:
            cache = per_layer + (cache_positions,)
        else:
            cache = per_layer + (cache_positions, block_tables,
                                 block_size)
        h, updated = _block(p, li, h, cache, False, h_loc, cfg.head_dim)
        for rows, arr in zip(out_rows, updated):
            rows.append(arr)
    return _finalize(p, h), tuple(jnp.stack(rows) for rows in out_rows)
