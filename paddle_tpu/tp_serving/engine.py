"""`TPGenerationEngine`: the PR-15/17 generation engine with its four
traced functions (prefill / decode / chunk / verify) rebuilt as
tensor-parallel programs over a one-axis ``Mesh(("tp",))``.

Everything host-side is INHERITED unchanged — scheduling, block
accounting, prefix cache, chunked prefill, speculative decoding,
admission, metrics, hot-swap: the subclass only overrides the
``_make_*_fn`` factories to return `jax.shard_map`
wrappings of the shard-local functional forward (`tp_serving.model`)
with IDENTICAL positional signatures, so every call site, the
compile-count pin, and the one-executable-per-config invariant carry
over verbatim.  Weights enter through `tp_serving.layout`: column
shards for qkv/fc1, row shards for out_proj/fc2 (two all-reduces per
layer — one per sub-layer), replicated embeddings/norms; the KV cache
(dense stacks and the paged block pool alike) shards over the HEADS
axis, so each chip stores ``1/tp`` of the pool and of the attention
weights — the "serve models bigger than one chip" claim, priced by
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
from ..generation.sampling import sample_tokens, token_logprobs
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
        # one-executable-per-config pin.  Trailing-None specs are
        # trimmed to match the canonical form traced outputs carry
        # (P(...,'tp',None) and P(...,'tp') are the same sharding but
        # DIFFERENT jit keys).
        def _canon(spec):
            parts = list(spec)
            while parts and parts[-1] is None:
                parts.pop()
            return NamedSharding(self._mesh, P(*parts))

        self.cache.update(*(jax.device_put(a, _canon(s)) for a, s in
                            zip(self.cache.arrays(),
                                self._cache_specs())))

    # -- sharding plumbing -------------------------------------------------
    def _cache_specs(self):
        """KV arrays shard over the heads axis: pool/stack layouts are
        ``[L, *, *, H, Dh]`` and int8 scale stacks ``[L, NB, bs, H]``."""
        kv = P(None, None, None, "tp", None)
        if self.paged and self.cache.quantized:
            return (kv, kv, P(None, None, None, "tp"),
                    P(None, None, None, "tp"))
        return (kv, kv)

    def _tp_wrap(self, body, n_host):
        """shard_map a traced-fn body: params tree + heads-sharded
        cache operands + ``n_host`` replicated host operands in; cache
        arrays + replicated token outputs (sampling runs post-psum on
        identical logits, so every shard computes the same tokens)."""
        cache_specs = self._cache_specs()
        in_specs = ((self._param_specs,) + cache_specs
                    + (P(),) * n_host)
        out_specs = cache_specs + (P(),) * (
            2 if self.return_logprobs else 1)
        return jax.shard_map(body, mesh=self._mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    # -- traced-function factories (same signatures as the base) ----------
    def _make_decode_fn(self):
        cfg, tp, nc = self.cfg, self.tp, self._nc
        if not self.paged:
            def decode(params, k_stack, v_stack, lengths, tokens, keys,
                       steps, temp, top_k, top_p):
                logits, (k2, v2) = tp_model.cached_forward(
                    params, tokens[:, None].astype(jnp.int32),
                    lengths[:, None].astype(jnp.int32),
                    (k_stack, v_stack), lengths, cfg, tp)
                nxt = sample_tokens(logits[:, 0], keys, steps, temp,
                                    top_k, top_p)
                if self.return_logprobs:
                    return k2, v2, nxt, token_logprobs(logits[:, 0], nxt)
                return k2, v2, nxt

            return self._tp_wrap(decode, 7)

        bs = self.block_size

        def decode(params, *args):
            arrays = args[:nc]
            (lengths, tokens, keys, steps, temp, top_k, top_p,
             tables) = args[nc:]
            logits, new_arrays = tp_model.cached_forward(
                params, tokens[:, None].astype(jnp.int32),
                lengths[:, None].astype(jnp.int32), arrays, lengths,
                cfg, tp, block_tables=tables, block_size=bs)
            nxt = sample_tokens(logits[:, 0], keys, steps, temp,
                                top_k, top_p)
            if self.return_logprobs:
                return (*new_arrays, nxt,
                        token_logprobs(logits[:, 0], nxt))
            return (*new_arrays, nxt)

        return self._tp_wrap(decode, 8)

    def _make_prefill_fn(self, bucket):
        cfg, tp, nc = self.cfg, self.tp, self._nc
        if not self.paged:
            def prefill(params, k_stack, v_stack, tokens, length, slot,
                        key, temp, top_k, top_p):
                pos = jnp.arange(bucket, dtype=jnp.int32)[None]
                logits, kvs = tp_model.prefill_forward(
                    params, tokens, pos, cfg, tp)
                for li, (k, v) in enumerate(kvs):
                    idx = (li, slot, 0, 0, 0)
                    k_stack = jax.lax.dynamic_update_slice(
                        k_stack, k.astype(k_stack.dtype)[None], idx)
                    v_stack = jax.lax.dynamic_update_slice(
                        v_stack, v.astype(v_stack.dtype)[None], idx)
                last = jax.lax.dynamic_index_in_dim(
                    logits[0], length - 1, axis=0)
                tok0 = sample_tokens(last, key[None],
                                     jnp.zeros((1,), jnp.int32),
                                     temp[None], top_k[None],
                                     top_p[None])[0]
                if self.return_logprobs:
                    return (k_stack, v_stack, tok0,
                            token_logprobs(last, tok0[None])[0])
                return k_stack, v_stack, tok0

            return self._tp_wrap(prefill, 7)

        from ..ops.pallas.paged_attention import quantize_kv

        bs = self.block_size
        quant = self.cache.quantized

        def prefill(params, *args):
            arrays = args[:nc]
            tokens, length, table, key, temp, top_k, top_p = args[nc:]
            pos = jnp.arange(bucket, dtype=jnp.int32)[None]
            logits, kvs = tp_model.prefill_forward(
                params, tokens, pos, cfg, tp)
            p = jnp.arange(bucket, dtype=jnp.int32)
            logical = jnp.clip(p // bs, 0, table.shape[1] - 1)
            bi = table[0][logical]
            off = p % bs
            if quant:
                k_pool, v_pool, k_sc, v_sc = arrays
            else:
                k_pool, v_pool = arrays
            for li, (k, v) in enumerate(kvs):
                k_rows = k[0]
                v_rows = v[0]
                if quant:
                    kq, ks = quantize_kv(k_rows)
                    vq, vs = quantize_kv(v_rows)
                    k_pool = k_pool.at[li, bi, off].set(kq)
                    v_pool = v_pool.at[li, bi, off].set(vq)
                    k_sc = k_sc.at[li, bi, off].set(ks)
                    v_sc = v_sc.at[li, bi, off].set(vs)
                else:
                    k_pool = k_pool.at[li, bi, off].set(
                        k_rows.astype(k_pool.dtype))
                    v_pool = v_pool.at[li, bi, off].set(
                        v_rows.astype(v_pool.dtype))
            last = jax.lax.dynamic_index_in_dim(
                logits[0], length - 1, axis=0)
            tok0 = sample_tokens(last, key[None],
                                 jnp.zeros((1,), jnp.int32),
                                 temp[None], top_k[None], top_p[None])[0]
            out = (k_pool, v_pool, k_sc, v_sc) if quant \
                else (k_pool, v_pool)
            if self.return_logprobs:
                return (*out, tok0, token_logprobs(last, tok0[None])[0])
            return (*out, tok0)

        return self._tp_wrap(prefill, 7)

    def _make_chunk_fn(self, width):
        cfg, tp, nc = self.cfg, self.tp, self._nc
        bs = self.block_size

        def chunk(params, *args):
            arrays = args[:nc]
            (tokens, start, table, last_index, key, temp, top_k,
             top_p) = args[nc:]
            pos = start + jnp.arange(width, dtype=jnp.int32)[None]
            logits, new_arrays = tp_model.cached_forward(
                params, tokens, pos, arrays, jnp.reshape(start, (1,)),
                cfg, tp, block_tables=table, block_size=bs)
            last = jax.lax.dynamic_index_in_dim(
                logits[0], last_index, axis=0)
            tok = sample_tokens(last, key[None],
                                jnp.zeros((1,), jnp.int32),
                                temp[None], top_k[None], top_p[None])[0]
            if self.return_logprobs:
                return (*new_arrays, tok,
                        token_logprobs(last, tok[None])[0])
            return (*new_arrays, tok)

        return self._tp_wrap(chunk, 8)

    def _make_verify_fn(self):
        cfg, tp, nc = self.cfg, self.tp, self._nc
        bs = self.block_size
        s_len = self.draft_len + 1

        def verify(params, *args):
            arrays = args[:nc]
            (lengths, tok_in, keys, steps, temp, top_k, top_p,
             tables) = args[nc:]
            pos = (lengths[:, None]
                   + jnp.arange(s_len, dtype=jnp.int32)[None])
            logits, new_arrays = tp_model.cached_forward(
                params, tok_in, pos, arrays, lengths, cfg, tp,
                block_tables=tables, block_size=bs)
            toks = jnp.stack(
                [sample_tokens(logits[:, i], keys, steps + i, temp,
                               top_k, top_p) for i in range(s_len)],
                axis=1)
            if self.return_logprobs:
                lps = jnp.stack(
                    [token_logprobs(logits[:, i], toks[:, i])
                     for i in range(s_len)], axis=1)
                return (*new_arrays, toks, lps)
            return (*new_arrays, toks)

        return self._tp_wrap(verify, 8)

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
