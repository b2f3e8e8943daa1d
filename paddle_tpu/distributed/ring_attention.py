"""Ring attention: exact attention over sequence-sharded Q/K/V.

Long-context capability (SURVEY §5: absent in the reference — a new design
goal per PAPERS.md ring attention / blockwise parallel transformers).

Each `sp` shard holds S/n of the sequence.  K/V blocks rotate around the
ring via `ppermute` on ICI while Q stays resident; partial attention
outputs merge with online-softmax statistics, so the result is EXACT
attention with O(S/n) local memory and fully overlappable p2p traffic.

Use inside shard_map over the `sp` mesh axis (see tests/test_ring_attention
and ShardedTrainStep's sequence-parallel mode).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _block_attn(q, k, v, scale, mask=None):
    """Blockwise attention partials: returns (numerator, rowmax, rowsum).

    q: [B, H, Sq, D]; k/v: [B, H, Sk, D]; mask: [Sq, Sk] additive or None.
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if mask is not None:
        s = s + mask
    m = jnp.max(s, axis=-1)  # [B, H, Sq]
    # guard fully-masked rows: exp(NEG_INF - NEG_INF) would be 1
    m_safe = jnp.maximum(m, NEG_INF / 2)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    l = jnp.sum(p, axis=-1)  # [B, H, Sq]
    num = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return num, m_safe, l


def _merge(acc, m, l, num_b, m_b, l_b):
    m_new = jnp.maximum(m, m_b)
    c1 = jnp.exp(m - m_new)
    c2 = jnp.exp(m_b - m_new)
    acc = acc * c1[..., None] + num_b * c2[..., None]
    l = l * c1 + l_b * c2
    return acc, m_new, l


def ring_attention(q, k, v, axis_name="sp", scale=None, causal=False):
    """Exact attention with K/V ring rotation.

    q/k/v: the LOCAL sequence shard, [B, H, S_local, D].  Must be called
    inside shard_map/pjit-manual with `axis_name` mapped.  With causal=True
    the GLOBAL sequence order is shard-major: shard i owns positions
    [i*S_local, (i+1)*S_local).
    """
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    s_loc = q.shape[2]

    b, h, _, d = q.shape
    # mark the accumulators as device-varying on the ring axis (shard_map
    # tracks varying-vs-replicated; a constant init would type-clash with
    # the per-shard scan carry)
    _vary = lambda x: jax.lax.pcast(x, axis_name, to="varying")
    acc = _vary(jnp.zeros((b, h, s_loc, d), jnp.float32))
    m = _vary(jnp.full((b, h, s_loc), NEG_INF / 2, jnp.float32))
    l = _vary(jnp.zeros((b, h, s_loc), jnp.float32))

    perm = [(i, (i + 1) % n) for i in range(n)]

    rows = jax.lax.broadcasted_iota(jnp.int32, (s_loc, s_loc), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (s_loc, s_loc), 1)

    def body(carry, step):
        k_cur, v_cur, acc, m, l = carry
        owner = (my - step) % n  # which shard's K/V we hold this step
        if causal:
            # owner > my: future block, fully masked; owner == my: triangular
            tri = jnp.where(rows >= cols, 0.0, NEG_INF)
            full = jnp.zeros_like(tri)
            blocked = jnp.full_like(tri, NEG_INF)
            mask = jnp.where(
                owner == my, tri, jnp.where(owner < my, full, blocked)
            )
        else:
            mask = None
        num_b, m_b, l_b = _block_attn(q, k_cur, v_cur, scale, mask)
        acc, m, l = _merge(acc, m, l, num_b, m_b, l_b)
        # rotate K/V around the ring (overlaps with next block's compute
        # under XLA's async collective scheduling)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, acc, m, l), None

    (k_f, v_f, acc, m, l), _ = jax.lax.scan(
        body, (k, v, acc, m, l), jnp.arange(n)
    )
    safe_l = jnp.where(l == 0.0, 1.0, l)
    return (acc / safe_l[..., None]).astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh, axis_name="sp", scale=None,
                           causal=False):
    """Convenience wrapper: shard_map ring_attention over [B,H,S,D] arrays
    whose sequence dim is sharded on `axis_name` (other dims replicated)."""
    from jax.sharding import PartitionSpec as P

    spec = P(None, None, axis_name, None)
    fn = functools.partial(
        ring_attention, axis_name=axis_name, scale=scale, causal=causal
    )
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )(q, k, v)
