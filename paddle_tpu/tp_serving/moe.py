"""Expert-parallel MoE serving: `models.MoEFFN` experts partitioned
over the ``("tp",)`` mesh with EXPLICIT all-to-all dispatch/combine.

The training-side story shards the expert dim under GSPMD and lets the
partitioner infer the all-to-alls; for serving we write them out with
`lax.all_to_all` so the collective count and payload are pinned — two
tiled a2as per call (dispatch + combine), each moving the per-chip
``[E, cap, d]`` expert buffer, which `ep_moe_comm_bytes` prices with
`analysis.comm.collective_wire_bytes` and
`tests/test_zero_comm.py`-style drills pin against compiled HLO.

Layout: tokens shard over ``tp`` (``[T/N, d]`` per chip), experts
shard over ``tp`` (``E/N`` per chip — each chip stores only its
experts' ``w1/b1/w2/b2`` slices: the memory win).  Gating is computed
shard-locally on the chip that owns the token, with capacity
``int(cf · top_k · t_loc / E + 1)`` per (source chip, expert) pair —
the GShard buffer shape, per source.  With ample capacity (no drops)
the output matches the single-chip `switch_moe` lowering to fp
tolerance; under pressure, drop behaviour differs from the global
single-chip capacity exactly the way per-chip GShard dispatch does.

wire math per chip per call (f32): ``2 · (N-1)/N · E·cap·d·4`` bytes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P

__all__ = ["build_ep_moe", "ep_moe_comm_bytes", "moe_params",
           "record_expert_load"]

AXIS = "tp"


def moe_params(moe):
    """Pull the `models.MoEFFN` weights into the plain-array dict
    `build_ep_moe` consumes."""
    return {
        "gate": np.asarray(moe.gate.data),
        "w1": np.asarray(moe.w1.data), "b1": np.asarray(moe.b1.data),
        "w2": np.asarray(moe.w2.data), "b2": np.asarray(moe.b2.data),
    }


def ep_moe_comm_bytes(tokens, d_model, num_experts, mesh_size, *,
                      capacity_factor=1.25, top_k=1, dtype_bytes=4):
    """Per-chip wire bytes for ONE EP-MoE call (dispatch + combine),
    the estimate the HLO drill pins exactly: each a2a moves the local
    ``[E, cap, d]`` buffer, ring factor ``(N-1)/N``."""
    from ..analysis.comm import collective_wire_bytes

    t_loc = tokens // mesh_size
    cap = int(capacity_factor * top_k * t_loc / num_experts + 1)
    buf = num_experts * cap * d_model * dtype_bytes
    one = collective_wire_bytes("all-to-all", buf, mesh_size)
    return {"capacity": cap, "buffer_bytes": buf,
            "per_a2a_wire_bytes": one, "wire_bytes": 2 * one}


def build_ep_moe(mesh, num_experts, *, capacity_factor=1.25, top_k=1,
                 expert_stats=False):
    """Build the jitted expert-parallel MoE apply:
    ``fn(params, x) -> y`` with ``x [T, d]`` (T divisible by the mesh
    size) and params from `moe_params`.  Routing math mirrors the
    `switch_moe` lowering shard-locally; expert compute runs on the
    chip owning the expert after the dispatch all-to-all.

    ``expert_stats=True`` (opt-in: the return signature changes)
    returns ``fn(params, x) -> (y, counts)`` where ``counts`` is the
    ``[mesh_size, E]`` per-source-chip dispatched-token counts —
    reduced from the dispatch one-hots already in hand, so the
    collective count stays EXACTLY two a2as (the HLO drill's pin);
    the cross-chip sum happens on the host (`record_expert_load`)."""
    n = int(np.prod(mesh.devices.shape))
    e = int(num_experts)
    if e % n:
        raise ValueError("num_experts=%d not divisible by mesh size %d"
                         % (e, n))
    top_k = int(top_k)

    def body(params, x):
        xf = x.astype(jnp.float32)                    # [t_loc, d]
        t_loc, d = xf.shape
        cap = int(capacity_factor * top_k * t_loc / e + 1)
        logits = xf @ params["gate"].astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)

        masked = probs
        chosen, gates = [], []
        for _ in range(top_k):
            exp_r = jnp.argmax(masked, axis=-1)
            chosen.append(exp_r)
            gates.append(jnp.take_along_axis(
                probs, exp_r[:, None], axis=1)[:, 0])
            masked = masked * (1.0 - jax.nn.one_hot(exp_r, e))
        if top_k > 1:
            denom = sum(gates) + 1e-9
            gates = [g / denom for g in gates]

        onehots = [jax.nn.one_hot(c, e, dtype=jnp.int32)
                   for c in chosen]
        stacked = jnp.concatenate(onehots, axis=0)
        pos_all = jnp.cumsum(stacked, axis=0) * stacked - 1

        xin = jnp.zeros((e, cap, d), jnp.float32)
        disps = []
        for r in range(top_k):
            pos_r = jnp.sum(pos_all[r * t_loc:(r + 1) * t_loc]
                            * onehots[r], axis=-1)
            keep = pos_r < cap
            disp = (
                onehots[r].astype(jnp.float32)[:, :, None]
                * jax.nn.one_hot(jnp.where(keep, pos_r, cap), cap + 1,
                                 dtype=jnp.float32)[:, None, :cap]
            )
            disps.append(disp)
            xin = xin + jnp.einsum("tec,td->ecd", disp, xf)

        # dispatch: send each expert-chunk to its owner chip; arrive
        # grouped by source -> [e_loc, n·cap, d] expert-major buffers
        e_loc = e // n
        xin = jax.lax.all_to_all(xin, AXIS, split_axis=0,
                                 concat_axis=0, tiled=True)
        xin = xin.reshape(n, e_loc, cap, d).transpose(1, 0, 2, 3)
        xin = xin.reshape(e_loc, n * cap, d)

        h = jnp.einsum("ecd,edh->ech", xin,
                       params["w1"].astype(jnp.float32))
        h = jax.nn.gelu(h + params["b1"].astype(jnp.float32)[:, None, :])
        y = jnp.einsum("ech,ehd->ecd", h,
                       params["w2"].astype(jnp.float32))
        y = y + params["b2"].astype(jnp.float32)[:, None, :]

        # combine: route each source chip's rows back home
        y = y.reshape(e_loc, n, cap, d).transpose(1, 0, 2, 3)
        y = y.reshape(e, cap, d)
        y = jax.lax.all_to_all(y, AXIS, split_axis=0,
                               concat_axis=0, tiled=True)

        out = jnp.zeros((t_loc, d), jnp.float32)
        for r in range(top_k):
            out = out + jnp.einsum("tec,ecd->td", disps[r], y) \
                * gates[r][:, None]
        if not expert_stats:
            return out.astype(x.dtype)
        # per-expert tokens actually dispatched (capacity drops already
        # zeroed in disp) — [1, E] per chip, concatenating to [N, E]
        counts = sum(jnp.einsum("tec->e", disp) for disp in disps)
        return out.astype(x.dtype), counts[None, :]

    param_specs = {
        "gate": P(),                       # replicated router
        "w1": P("tp", None, None), "b1": P("tp", None),
        "w2": P("tp", None, None), "b2": P("tp", None),
    }
    out_specs = (P("tp", None), P("tp", None)) if expert_stats \
        else P("tp", None)
    mapped = jax.shard_map(
        body, mesh=mesh, in_specs=(param_specs, P("tp", None)),
        out_specs=out_specs, check_vma=False)
    return jax.jit(mapped)


def record_expert_load(counts, registry=None, name="ep_moe"):
    """Fold one call's expert-token counts (the ``expert_stats=True``
    second output: ``[N, E]`` per-source-chip, or an already-summed
    ``[E]``) into the metrics registry:

      * ``ep_moe_expert_tokens_total{moe,expert}`` counters, and
      * ``ep_moe_hot_expert_imbalance{moe}`` — max/mean of this call's
        per-expert load (1.0 = perfectly balanced; the hot-expert
        gauge capacity tuning watches).

    Returns ``{"counts": [per-expert totals], "imbalance": float}``.
    The sum over source chips happens HERE, on the host — the device
    graph keeps its two-a2a collective pin."""
    c = np.asarray(counts, dtype=np.float64)
    if c.ndim == 2:
        c = c.sum(axis=0)
    if c.ndim != 1:
        raise ValueError("counts must be [E] or [N, E], got shape %r"
                         % (np.shape(counts),))
    if registry is None:
        from ..observability.metrics import default_registry

        registry = default_registry()
    m_tokens = registry.counter(
        "ep_moe_expert_tokens_total",
        "tokens dispatched per expert (capacity drops excluded)",
        ("moe", "expert"))
    g_imb = registry.gauge(
        "ep_moe_hot_expert_imbalance",
        "max/mean per-expert load of the last recorded call",
        ("moe",))
    for i, v in enumerate(c):
        if v:
            m_tokens.labels(name, str(i)).inc(float(v))
    mean = float(c.mean()) if c.size else 0.0
    imbalance = float(c.max() / mean) if mean > 0 else 0.0
    g_imb.labels(name).set(imbalance)
    return {"counts": c.tolist(), "imbalance": imbalance}
