"""Decoder-only LM of gated sparse experts, for `paddle_tpu.generation`.

The block, for hidden state ``h`` (all norms RMSNorm, no bias anywhere):

    h <- h + Attn(RMSNorm(h));   h <- h + Experts(RMSNorm(h))

* **Attn**: ``num_heads`` query heads over ``num_kv_heads`` grouped K/V
  heads of ``head_dim`` (query head j reads K/V head ``j // (H/G)``); q
  and k RMS-normalised over each head's ``head_dim`` with a learned
  gain, then rotary positions (rotate-half, base ``rope_theta``);
  scores ``q.k / sqrt(head_dim)``.  The mask has a granule
  ``block_length`` B: position i sees position j iff ``j // B <= i // B``
  (causal between blocks of B, both ways inside one; B = 1 is the
  causal mask).
* **Experts** (`GatedExperts`): ``p = softmax(W_r x)`` over all
  ``num_experts``; the ``num_experts_per_tok`` largest, renormalised to
  sum 1 (``norm_topk_prob``); ``y = sum_e w_e W_down^e(silu(W_gate^e x)
  * W_up^e x)``.  No capacity, no dropped token.  The layer is TOLD
  which experts it holds (``experts=``): it routes over all of them and
  computes its own experts' share of ``y``, which is what one chip of
  an expert-parallel layer does (the shares of all holders add up to
  the whole).
* **Head**: ``logits = W_head RMSNorm(h)``, untied.

Weights are held in ``cfg.dtype`` (bfloat16 for serving) and made ON THE
DEVICE from a seed (`MoEDecoderLM.seeded`): products take operands of
that type and accumulate in float32; the residual stream, the norms,
the router and the softmaxes are float32.

The forward contract is `models.TransformerLM`'s (``use_cache`` prefill,
``caches`` decode/chunk), so `GenerationEngine` serves it through the
same cache, tables and step functions; a cached call of C rows lets the
rows of one mask block see each other (`ops.cached_attention`'s
``granule``).  Asked for it (``aux=True``), a forward also hands back
``{"expert_counts": [layers, experts] int32}``, how many live rows
visited each expert; `MoEDecoderLM.step_observer` turns a step's into
the layer's own metrics.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..fluid import dygraph
from ..fluid.dygraph.varbase import ParamBase

NEG_INF = -1e30
_F32 = jnp.float32


class MoEDecoderConfig:
    def __init__(self, vocab_size=32000, hidden_size=2048, num_layers=6,
                 num_heads=32, num_kv_heads=4, head_dim=128,
                 moe_intermediate_size=768, num_experts=128,
                 num_experts_per_tok=8, norm_topk_prob=True,
                 rope_theta=1e6, rms_norm_eps=1e-6,
                 max_position_embeddings=32768, initializer_range=0.02,
                 block_length=1, mask_token_id=0, dtype="bfloat16"):
        if num_heads % num_kv_heads:
            raise ValueError("num_heads %d is no multiple of num_kv_heads %d"
                             % (num_heads, num_kv_heads))
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.moe_intermediate_size = moe_intermediate_size
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.norm_topk_prob = norm_topk_prob
        self.rope_theta = float(rope_theta)
        self.rms_norm_eps = float(rms_norm_eps)
        self.max_position_embeddings = max_position_embeddings
        self.initializer_range = initializer_range
        self.block_length = int(block_length)
        self.mask_token_id = int(mask_token_id)
        self.dtype = str(dtype)

    @staticmethod
    def tiny(**over):
        """For tests: every mechanism at a size the CPU runs."""
        kw = dict(vocab_size=128, hidden_size=64, num_layers=2, num_heads=8,
                  num_kv_heads=2, head_dim=16, moe_intermediate_size=32,
                  num_experts=8, num_experts_per_tok=2,
                  max_position_embeddings=128, block_length=4,
                  mask_token_id=127, dtype="float32")
        kw.update(over)
        return MoEDecoderConfig(**kw)


# -- the mathematics, on arrays ------------------------------------------


def rms_norm(x, gain, eps):
    x = x.astype(_F32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * gain.astype(_F32))


def rotary(x, pos, theta):
    """Rotate-half rotary embedding: x [B, S, H, D], pos [B, S]."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=_F32) / half)
    ang = pos.astype(_F32)[..., None] * inv                  # [B, S, D/2]
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _mm(x, w):
    """x @ w with the operands in the weights' type, summed in float32."""
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=_F32)


def block_mask_attention(q, k, v, granule, scale):
    """Attention of a whole sequence under the block mask, plain XLA:
    q [B, S, H, D]; k, v [B, S, G, D] (G grouped heads); position i sees
    j iff ``j // granule <= i // granule``.  The prompt's path: S x S
    scores a head (134 MB for 32 heads at S = 1024), float32 products."""
    with jax.named_scope("block_mask_attention"):
        b, s, h, d = q.shape
        g = k.shape[2]
        exact = jax.lax.Precision.HIGHEST
        qg = q.astype(_F32).reshape(b, s, g, h // g, d)
        sc = jnp.einsum("bsgrd,btgd->bgrst", qg, k.astype(_F32),
                        precision=exact) * scale
        blk = jnp.arange(s, dtype=jnp.int32) // granule
        sc = jnp.where(blk[None, :] <= blk[:, None], sc, NEG_INF)
        p = jax.nn.softmax(sc, axis=-1)
        ctx = jnp.einsum("bgrst,btgd->bsgrd", p, v.astype(_F32),
                         precision=exact)
        return ctx.reshape(b, s, h, d)


# -- layers ----------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _normal(shape, std, dtype):
    return jax.jit(lambda k: (jax.random.normal(k, shape, _F32)
                              * std).astype(dtype))


def _param(key, shape, std, dtype, name):
    """A parameter made on the device: normal(0, std) in ``dtype`` (one
    jitted program a shape, so no float32 copy of a large one outlives
    its making)."""
    return ParamBase(_normal(tuple(shape), float(std), str(dtype))(key),
                     name=name)


# A seeded router is drawn this many times wider than the other weights.
# At their spread (0.02) the experts' probabilities lie so close that the
# 8th and 9th largest weigh the same, and which of the two is chosen
# turns on the last bit of a bfloat16 product; five times wider the
# chosen experts weigh 0.62, 0.17, 0.08 ... 0.012 of the sum, as a
# trained router's do.  Which experts are chosen, the load and every
# byte and operation are the same.
ROUTER_SPREAD = 5.0


def _ones(shape, dtype, name):
    return ParamBase(jnp.ones(shape, dtype), name=name)


class GatedExperts(dygraph.Layer):
    """The routed experts of one layer (see the module docstring).
    ``experts``: the ids this layer HOLDS (default: all); the router and
    the top-k run over all ``num_experts`` whatever is held, and the
    result is the held experts' share."""

    def __init__(self, cfg, key, experts=None, name="experts"):
        super().__init__()
        self.cfg = cfg
        self.held = tuple(range(cfg.num_experts) if experts is None
                          else (int(e) for e in experts))
        d, f, dt = cfg.hidden_size, cfg.moe_intermediate_size, cfg.dtype
        std = cfg.initializer_range
        kr, kg, ku, kd = jax.random.split(key, 4)
        # every holder draws the whole layer from the key and keeps its
        # own experts' rows: shares of one key are shares of one layer
        take = jnp.asarray(self.held, jnp.int32)
        full = len(self.held) == cfg.num_experts

        def held(k, shape, nm):
            p = _param(k, (cfg.num_experts,) + shape, std, dt,
                       name + "." + nm)
            return p if full else ParamBase(p.data[take],
                                            name=name + "." + nm)

        self.router = _param(kr, (d, cfg.num_experts), ROUTER_SPREAD * std,
                             dt, name + ".router")
        self.w_gate = held(kg, (d, f), "w_gate")
        self.w_up = held(ku, (d, f), "w_up")
        self.w_down = held(kd, (f, d), "w_down")

    def route(self, x):
        """x [T, d] float32 -> (weights [T, k] float32 summing to 1 a
        row, expert ids [T, k]): softmax over ALL experts, the k largest,
        renormalised."""
        with jax.named_scope("moe_router"):
            logits = jnp.dot(x, self.router.data.astype(_F32),
                             precision=jax.lax.Precision.HIGHEST)
            top_p, top_i = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                         self.cfg.num_experts_per_tok)
            if self.cfg.norm_topk_prob:
                top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
            return top_p, top_i

    def forward(self, x, live=None):
        """x [T, d] float32; live [T] bool or None (every row).  Returns
        ``(y [T, d] float32, counts [num_experts] int32)``: the held
        experts' share of the layer's output, and how many live rows
        chose each expert.

        Every held expert runs on every row and the routing weight (0
        for an expert a row did not choose) multiplies its hidden
        activation: two matmuls, ``[T, d] x [d, E*f]`` and ``[T, E*f] x
        [E*f, d]``, that read each weight once.  At T <= 128 rows the
        chip's time for that is the weights' bytes (128 FLOPs a byte
        against a ridge of 240); a prompt of S rows pays E / k times the
        chosen experts' arithmetic."""
        cfg = self.cfg
        top_p, top_i = self.route(x)
        with jax.named_scope("moe_router"):
            ids = jnp.arange(cfg.num_experts, dtype=top_i.dtype)
            chose = top_i[:, :, None] == ids                    # [T, k, E]
            seen = chose if live is None else chose & live[:, None, None]
            counts = jnp.sum(seen, axis=(0, 1), dtype=jnp.int32)
            dense = jnp.sum(jnp.where(chose, top_p[:, :, None], 0.0),
                            axis=1)                             # [T, E]
            w = dense if len(self.held) == cfg.num_experts \
                else dense[:, jnp.asarray(self.held, jnp.int32)]
        with jax.named_scope("moe_experts"):
            dt = self.w_gate.data.dtype
            xb = x.astype(dt)
            gate = jnp.einsum("td,edf->tef", xb, self.w_gate.data,
                              preferred_element_type=_F32)
            up = jnp.einsum("td,edf->tef", xb, self.w_up.data,
                            preferred_element_type=_F32)
            act = (jax.nn.silu(gate) * up * w[:, :, None]).astype(dt)
            y = jnp.einsum("tef,efd->td", act, self.w_down.data,
                           preferred_element_type=_F32)
        return y, counts


class MoEDecoderBlock(dygraph.Layer):
    def __init__(self, cfg, key, name="block", experts=None):
        super().__init__()
        self.cfg = cfg
        d, dt, std = cfg.hidden_size, cfg.dtype, cfg.initializer_range
        hq = cfg.num_heads * cfg.head_dim
        hk = cfg.num_kv_heads * cfg.head_dim
        kq, kk, kv, ko, ke = jax.random.split(key, 5)
        self.ln1 = _ones((d,), dt, name + ".ln1")
        self.wq = _param(kq, (d, hq), std, dt, name + ".wq")
        self.wk = _param(kk, (d, hk), std, dt, name + ".wk")
        self.wv = _param(kv, (d, hk), std, dt, name + ".wv")
        self.q_norm = _ones((cfg.head_dim,), dt, name + ".q_norm")
        self.k_norm = _ones((cfg.head_dim,), dt, name + ".k_norm")
        self.wo = _param(ko, (hq, d), std, dt, name + ".wo")
        self.ln2 = _ones((d,), dt, name + ".ln2")
        self.experts = GatedExperts(cfg, ke, experts=experts,
                                    name=name + ".experts")

    def qkv(self, x, pos):
        """Normed hidden [B, S, d], positions [B, S] -> q [B, S, H, D],
        k, v [B, S, G, D]: projections, per-head RMSNorm of q and k,
        rotary; k and v rounded to the type the cache holds."""
        cfg = self.cfg
        b, s, _ = x.shape
        dt = self.wk.data.dtype
        q = _mm(x, self.wq.data).reshape(b, s, cfg.num_heads, cfg.head_dim)
        k = _mm(x, self.wk.data).reshape(b, s, cfg.num_kv_heads,
                                         cfg.head_dim)
        v = _mm(x, self.wv.data).reshape(b, s, cfg.num_kv_heads,
                                         cfg.head_dim)
        q = rotary(rms_norm(q, self.q_norm.data, cfg.rms_norm_eps), pos,
                   cfg.rope_theta)
        k = rotary(rms_norm(k, self.k_norm.data, cfg.rms_norm_eps), pos,
                   cfg.rope_theta)
        return q, k.astype(dt), v.astype(dt)

    def forward(self, h, pos, cache=None, live=None):
        """h [B, S, d] float32, pos [B, S].  ``cache``: None (the whole
        sequence under the block mask) or one of `cached_attention`'s
        tuples.  Returns ``(h, (k, v) rows or updated cache arrays,
        expert counts)``."""
        from ..ops.cached_attention import cached_attention

        cfg = self.cfg
        b, s, d = h.shape
        q, k, v = self.qkv(rms_norm(h, self.ln1.data, cfg.rms_norm_eps), pos)
        scale = cfg.head_dim ** -0.5
        if cache is None:
            ctx, kv = block_mask_attention(q, k, v, cfg.block_length,
                                           scale), (k, v)
        else:
            ctx, kv = cached_attention(q, k, v, cache, scale=scale,
                                       granule=cfg.block_length)
        h = h + _mm(ctx.reshape(b, s, -1), self.wo.data)
        x = rms_norm(h, self.ln2.data, cfg.rms_norm_eps).reshape(b * s, d)
        y, counts = self.experts(
            x, None if live is None else jnp.repeat(live, s))
        return h + y.reshape(b, s, d), kv, counts


class MoEDecoderLM(dygraph.Layer):
    """See the module docstring.  ``experts``: the expert ids every layer
    holds (default all): the chip's share of an expert-parallel layer."""

    def __init__(self, cfg, seed=0, experts=None):
        super().__init__()
        self.cfg = cfg
        keys = jax.random.split(jax.random.PRNGKey(int(seed)),
                                cfg.num_layers + 2)
        std, dt = cfg.initializer_range, cfg.dtype
        self.embed = _param(keys[0], (cfg.vocab_size, cfg.hidden_size),
                            std, dt, "embed")
        self.blocks = dygraph.LayerList(
            [MoEDecoderBlock(cfg, keys[2 + i], name="blocks.%d" % i,
                             experts=experts)
             for i in range(cfg.num_layers)])
        self.norm = _ones((cfg.hidden_size,), dt, "norm")
        self.head = _param(keys[1], (cfg.hidden_size, cfg.vocab_size),
                           std, dt, "head")

    @classmethod
    def seeded(cls, cfg, seed, experts=None):
        """The model with its weights made on the device from ``seed``
        (needs no host copy: 8.7 GB of bfloat16 at six published
        layers would be 17 GB of float32 there)."""
        with dygraph.guard():
            return cls(cfg, seed=seed, experts=experts)

    def forward(self, input_ids, position_ids, caches=None,
                cache_positions=None, use_cache=False, block_tables=None,
                block_size=None, cache_live=None, aux=False):
        """`models.TransformerLM.forward`'s contract: ``logits [B, S, V]``
        float32, with ``caches`` also the updated cache arrays, with
        ``use_cache`` the layers' ``(k, v)`` rows ``[B, S, G, D]``; with
        ``aux`` a third value, ``{"expert_counts": [layers, experts]}``
        (the live rows' visits; every row's without a cache)."""
        from ..fluid.dygraph import to_variable

        ids = jnp.asarray(input_ids.data).astype(jnp.int32)
        pos = jnp.asarray(position_ids.data).astype(jnp.int32)
        ids = ids.reshape(pos.shape)
        h = self.embed.data[ids].astype(_F32)
        live = tail = None
        if caches is not None:
            if block_tables is None:
                tail = (cache_positions, cache_live)
                live = cache_live
            else:
                tail = (cache_positions, block_tables, block_size)
                live = jnp.any(jnp.asarray(block_tables) != 0, axis=1)
        kvs, counts = [], []
        for i, block in enumerate(self.blocks):
            cache = None if caches is None else tuple(caches[i]) + tail
            h, kv, c = block(h, pos, cache=cache, live=live)
            kvs.append(tuple(kv))
            counts.append(c)
        logits = to_variable(_mm(
            rms_norm(h, self.norm.data, self.cfg.rms_norm_eps),
            self.head.data))
        out = (logits, kvs) if caches is not None or use_cache else (logits,)
        if aux:
            out += ({"expert_counts": jnp.stack(counts)},)
        return out if len(out) > 1 else logits

    def step_observer(self, reg, engine):
        """What a serving engine calls with the ``aux`` of every step
        (fetched, as numpy): the experts' own metrics, in the engine's
        registry ``reg`` under its label.  ``generation_moe_experts_touched_total``: the
        experts a step's live rows visited at least once, summed over
        the layers; ``generation_moe_load_max_over_mean``: the busiest
        expert's visits over the mean expert's, the layers' mean."""
        touched = reg.counter(
            "generation_moe_experts_touched_total",
            "Experts a step's live rows visited at least once, summed "
            "over the layers", labelnames=("engine",)).labels(engine)
        load = reg.histogram(
            "generation_moe_load_max_over_mean",
            "A step's busiest expert's visits over the mean expert's, "
            "the layers' mean", labelnames=("engine",),
            buckets=(1.0, 1.5, 2.0, 3.0, 4.0, 6.0, 8.0, 16.0)).labels(engine)

        def observe(aux):
            counts = aux["expert_counts"]
            if counts.any():
                touched.inc(int((counts > 0).sum()))
                load.observe(float((counts.max(axis=1)
                                    / counts.mean(axis=1)).mean()))

        return observe
