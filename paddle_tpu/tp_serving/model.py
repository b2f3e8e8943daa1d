"""Shard-local functional TransformerLM forward for tensor-parallel
serving — the math each chip runs inside `jax.shard_map` over the
``("tp",)`` mesh.

This mirrors the single-chip lowering op for op (`models.transformer_lm`
through `fluid/ops`): f32 LayerNorm (eps 1e-5), erf gelu, the flattened
``mul`` matmul for Linear, the same attention dispatch
(`ops.attention.scaled_dot_product_attention` for prefill,
`ops.cached_attention.cached_attention` for cached decode),
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
local thirds are this shard's q/k/v) and local KV cache arrays (one
per layer, ``(H/tp) * Dh`` of the merged last dimension: a contiguous
split of it is a split by heads); scalars/tables/tokens arrive
replicated.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops.attention import scaled_dot_product_attention
from ..ops.cached_attention import cached_attention

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
    """`models.bert.MultiHeadAttention._decode_with_cache` on local
    head shards: the same `cached_attention` (write the C new rows,
    attend row i over positions ``<= pos+i``), its cache arrays
    carrying ``h_loc * d_head`` of the merged dimension."""
    q, k, v = _qkv_split(p, li, x, h_loc, d_head)
    ctx, new_cache = cached_attention(q, k, v, cache,
                                      scale=d_head ** -0.5)
    b, c_len = ctx.shape[0], ctx.shape[1]
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
                   block_tables=None, block_size=None, cache_live=None):
    """Decode/chunk/verify forward over the per-layer LOCAL cache
    arrays (`TransformerLM.forward(caches=...)` contract: one tuple of
    arrays per layer): S tokens per row written at
    ``cache_positions..+S-1``, each layer into its own arrays; returns
    ``(logits, updated per-layer tuples)``."""
    h_loc = cfg.num_heads // tp
    tail = ((cache_positions, cache_live) if block_tables is None
            else (cache_positions, block_tables, block_size))
    out = []
    h = _embed(p, ids, pos_ids)
    for li, mine in enumerate(caches):
        h, updated = _block(p, li, h, tuple(mine) + tail, False, h_loc,
                            cfg.head_dim)
        out.append(tuple(updated))
    return _finalize(p, h), out
