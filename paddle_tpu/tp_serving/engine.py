"""`TPGenerationEngine`: the PR-15/17 generation engine with its four
traced functions (prefill / decode / chunk / verify) rebuilt as
tensor-parallel programs over a one-axis ``Mesh(("tp",))``.

Everything host-side is INHERITED unchanged — scheduling, block
accounting, prefix cache, chunked prefill, speculative decoding,
admission, metrics, hot-swap — and so are the step functions' bodies
(sampling, the cache write): the subclass overrides the served model's
two forwards with the shard-local functional forward
(`tp_serving.model`) and wraps each body in `jax.shard_map`, with
IDENTICAL positional signatures, so every call site, the compile-count
pin, and the one-executable-per-config invariant carry over verbatim.  Weights enter through `tp_serving.layout`: column
shards for qkv/fc1, row shards for out_proj/fc2 (two all-reduces per
layer — one per sub-layer), replicated embeddings/norms; the KV cache
(dense and the paged block pool alike, one array per layer) shards its
merged ``H*Dh`` dimension, which is a split by HEADS, so each chip
stores ``1/tp`` of the pool and of the attention weights — the "serve models bigger than one chip" claim, priced by
`analysis.perf.decode_step_cost(tp=...)`.

The draft model of speculative decoding stays replicated (it is small
by construction); only the target model's calls are sharded.

`snapshot_params` / `swap_params` translate between the canonical
state-dict layout and the shard-major qkv grouping at the boundary, so
`paddle_tpu.rl`'s promotion gate round-trips bit-exactly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..generation.engine import GenerationEngine
from ..generation.kv_cache import flatten_layers, group_layers
from . import model as tp_model
from .layout import (
    prepare_tp_params,
    restore_tp_params,
    tp_param_specs,
    validate_tp,
)

__all__ = ["TPGenerationEngine", "tp_mesh"]


def tp_mesh(tp, devices=None):
    """A ``("tp",)`` mesh over the first ``tp`` local devices."""
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) < tp:
        raise ValueError("tp=%d needs %d devices, have %d"
                         % (tp, tp, len(devices)))
    return Mesh(np.asarray(devices[:tp]), ("tp",))


class TPGenerationEngine(GenerationEngine):
    """See module docstring.  ``tp`` is the tensor-parallel degree;
    ``mesh`` (optional) must be a one-axis ``("tp",)`` mesh of size
    ``tp``.  All other knobs are the base engine's."""

    def __init__(self, model, *, tp, mesh=None, name="tpgen", **kwargs):
        cfg = model.cfg
        self.tp = validate_tp(cfg, int(tp))
        self._mesh = mesh if mesh is not None else tp_mesh(self.tp)
        if tuple(self._mesh.axis_names) != ("tp",):
            raise ValueError("mesh axes must be ('tp',), got %r"
                             % (tuple(self._mesh.axis_names),))
        if self._mesh.devices.size != self.tp:
            raise ValueError("mesh has %d devices, tp=%d"
                             % (self._mesh.devices.size, self.tp))
        self._param_specs = tp_param_specs(
            model.state_dict().keys())
        super().__init__(model, name=name, **kwargs)
        # the traced fns receive params per CALL; store them in the
        # shard-major qkv grouping the shard-local forward slices
        self._params = {
            k: jnp.asarray(v)
            for k, v in prepare_tp_params(self._params, cfg,
                                          self.tp).items()}
        # commit the KV arrays to their steady-state shardings NOW:
        # fresh jnp.zeros is single-device-uncommitted while every
        # traced call returns mesh-committed arrays, and jit keys on
        # that — without this the SECOND call of each prefill bucket
        # would get a second executable, breaking the
        # one-executable-per-config pin.  (The specs end in "tp", the
        # canonical form traced outputs carry: a trailing None would be
        # the same sharding but a DIFFERENT jit key.)
        self.cache.update(*(
            jax.device_put(a, NamedSharding(self._mesh, s))
            for a, s in zip(self.cache.arrays(), self._cache_specs())))
        # and the per-slot operands the device chains from step to step
        # to the form a step's outputs come back in (replicated over the
        # mesh), for the same reason
        self._dev = tuple(jax.device_put(a, NamedSharding(self._mesh, P()))
                          for a in self._dev)

    # -- sharding plumbing -------------------------------------------------
    def _cache_specs(self):
        """Every KV array shards over its LAST dimension: the merged
        ``H*Dh`` of a layer's ``[*, *, H*Dh]`` pool or dense cache (a
        contiguous split of it is a split by heads) and the ``H`` of an
        int8 pool's ``[NB, bs, H]`` scales."""
        return (P(None, None, "tp"),) * self._nc

    def _wrap_step(self, body):
        """shard_map a step function's body: params tree +
        heads-sharded cache operands + replicated host operands in;
        cache arrays + replicated token outputs (sampling runs
        post-psum on identical logits, so every shard computes the
        same tokens)."""
        def sharded(params, *args):
            cache_specs = self._cache_specs()
            in_specs = ((self._param_specs,) + cache_specs
                        + (P(),) * (len(args) - self._nc))
            out_specs = cache_specs + (P(),) * (
                2 if self.return_logprobs else 1)
            return jax.shard_map(body, mesh=self._mesh, in_specs=in_specs,
                                 out_specs=out_specs,
                                 check_vma=False)(params, *args)

        return sharded

    # -- the served model's two forwards, shard-local ----------------------
    def _forward_cached(self, params, ids, pos, arrays, cache_positions,
                        where):
        logits, layers = tp_model.cached_forward(
            params, ids, pos, group_layers(arrays, self.cfg.num_layers),
            cache_positions, self.cfg, self.tp,
            **self._cache_index(where))
        return logits, flatten_layers(layers)

    def _forward_prefill(self, params, tokens, bucket):
        pos = jnp.arange(bucket, dtype=jnp.int32)[None]
        return tp_model.prefill_forward(params, tokens, pos, self.cfg,
                                        self.tp)

    # -- comm pricing (analysis.comm) --------------------------------------
    def decode_comm_estimate(self, dtype_bytes=4):
        """The static price of one decode step's collectives: two ring
        all-reduces per layer over the ``[slots, hidden]`` activations.
        `decode_hlo` + `analysis.comm.hlo_collective_stats` must agree
        EXACTLY — the PR-13 estimate-vs-compiled discipline."""
        from ..analysis.comm import collective_wire_bytes

        payload = self.slots * self.cfg.hidden_size * dtype_bytes
        one = collective_wire_bytes("all-reduce", payload, self.tp)
        L = self.cfg.num_layers
        return {
            "tp": self.tp,
            "all_reduce_count": 2 * L,
            "payload_bytes": payload,
            "per_all_reduce_wire_bytes": one,
            "per_layer_wire_bytes": 2 * one,
            "comm_bytes_per_step": 2 * L * one,
        }

    def decode_hlo_comm_check(self, dtype_bytes=4):
        """Lower the decode executable and pin its PER-LAYER
        all-reduces (result buffer == the ``[slots, hidden]``
        activation — the row/fc2 closes) against
        `decode_comm_estimate`: count must be ``2*num_layers`` and
        wire bytes must match EXACTLY.  Output-resharding collectives
        (the sampled-token gather the partitioner emits, a few bytes)
        carry a different result signature and are reported separately
        as ``other_wire_bytes``."""
        from ..analysis.comm import (
            collective_wire_bytes,
            hlo_collectives,
        )

        est = self.decode_comm_estimate(dtype_bytes)
        rows = hlo_collectives(self.decode_hlo())
        layer = [r for r in rows if r["kind"] == "all-reduce"
                 and r["result_bytes"] == est["payload_bytes"]]
        wire = sum(collective_wire_bytes("all-reduce",
                                         r["result_bytes"], self.tp)
                   for r in layer)
        other = sum(collective_wire_bytes(
            r["kind"], r["result_bytes"], self.tp) for r in rows
            if r not in layer)
        return {
            **est,
            "hlo_all_reduce_count": len(layer),
            "hlo_wire_bytes": wire,
            "other_wire_bytes": other,
            "count_match": len(layer) == est["all_reduce_count"],
            "wire_match": wire == est["comm_bytes_per_step"],
        }

    # -- hot-swap boundary (canonical layout outside, shard-major in) -----
    def snapshot_params(self):
        with self._lock:
            canon = restore_tp_params(self._params, self.cfg, self.tp)
            return {k: np.asarray(v) for k, v in canon.items()}

    def swap_params(self, params):
        staged = prepare_tp_params(
            {k: np.asarray(v) for k, v in params.items()},
            self.cfg, self.tp)
        super().swap_params(staged)

    # -- introspection -----------------------------------------------------
    def stats(self):
        out = super().stats()
        out["tp"] = {
            "degree": self.tp,
            "devices": [str(d) for d in
                        self._mesh.devices.ravel().tolist()],
            "kv_heads_per_shard": self.cfg.num_heads // self.tp,
            "all_reduces_per_layer": 2,
        }
        return out
