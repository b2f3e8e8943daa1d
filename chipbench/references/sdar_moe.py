"""SDAR-MoE (JetLM/SDAR-30B-A3B-Chat, ``model_type: sdar_moe``), plain
jnp in float32: the Qwen3-MoE block (RMSNorm, grouped-query attention
with per-head q/k RMSNorm and rotate-half rotary positions, top-k of
softmax-routed gated experts, untied head) under the block-diffusion
mask (Arriola et al., arXiv:2503.09573; SDAR, arXiv:2510.06303):
position i sees position j iff ``j // B <= i // B``.  No kernel, no
cache, no batching; the mask is an explicit array.

Two entry points: `forward_logits`, a sequence under the mask, and
`reveal_logprobs`, which scores every token a block-diffusion decoder
served at the pass that revealed it, all reveal states of a request in
ONE masked forward.  The logits at position i score the token AT
position i (a masked position predicts itself).

Parameters come under the program's names (`models/moe_decoder.py`) and
in the type the program holds them; a layer widens them to float32 as it
uses them (an expert at a time), so no float32 copy of the model exists.

Departures from the published description, each also at its line:
experts are looped over ALL of them under the routing mask instead of
gathered a token (the same sum: an expert a token did not choose has
weight 0); attention runs a K/V group at a time; the head runs on the
scored rows only.  All three bound memory, none changes a value.
"""

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


def sub(p, prefix):
    return {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def rotary(x, pos, theta):
    """x [S, H, D], pos [S]: rotate-half, inverse frequencies
    ``theta ** (-2i / D)``, no scaling."""
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None] * theta ** (
        -jnp.arange(half, dtype=jnp.float32) / half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(x, p, pos, mask, cfg):
    heads, groups, dim = (cfg["num_attention_heads"],
                          cfg["num_key_value_heads"], cfg["head_dim"])
    s = x.shape[0]
    f32 = jnp.float32
    q = (x @ p["wq"].astype(f32)).reshape(s, heads, dim)
    k = (x @ p["wk"].astype(f32)).reshape(s, groups, dim)
    v = (x @ p["wv"].astype(f32)).reshape(s, groups, dim)
    q = rotary(rms_norm(q, p["q_norm"].astype(f32), cfg["rms_norm_eps"]),
               pos, cfg["rope_theta"])
    k = rotary(rms_norm(k, p["k_norm"].astype(f32), cfg["rms_norm_eps"]),
               pos, cfg["rope_theta"])

    def group(qkv):     # departure: one K/V group of heads at a time
        qg, kg, vg = qkv                    # [S, H/G, D], [S, D], [S, D]
        sc = jnp.einsum("srd,td->rst", qg, kg) * dim ** -0.5
        pr = jax.nn.softmax(jnp.where(mask[None], sc, NEG_INF), axis=-1)
        return jnp.einsum("rst,td->srd", pr, vg)

    ctx = jax.lax.map(group, (
        q.reshape(s, groups, heads // groups, dim).transpose(1, 0, 2, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))    # [G, S, H/G, D]
    return ctx.transpose(1, 0, 2, 3).reshape(s, heads * dim) \
        @ p["wo"].astype(f32)


def experts(x, p, cfg, held=None):
    """``sum_e w_e W_down^e(silu(W_gate^e x) * W_up^e x)`` over the
    ``num_experts_per_tok`` experts of largest softmax probability,
    their probabilities renormalised to sum 1 (``norm_topk_prob``).
    ``held``: the expert ids whose rows ``p`` holds (default all); the
    sum then runs over those alone, the share of the layer they give."""
    f32 = jnp.float32
    n, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(x @ p["experts.router"].astype(f32), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    if cfg["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    w = jnp.sum(jnp.where(top_i[:, :, None] == jnp.arange(n), top_p[:, :, None],
                          0.0), axis=1)                         # [S, E]
    held = jnp.arange(n) if held is None else jnp.asarray(held)

    def one(y, e):      # departure: every held expert, under the mask w
        wg, wu, wd, we = e
        h = jax.nn.silu(x @ wg.astype(f32)) * (x @ wu.astype(f32))
        return y + we[:, None] * (h @ wd.astype(f32)), None

    return jax.lax.scan(one, jnp.zeros_like(x), (
        p["experts.w_gate"], p["experts.w_up"], p["experts.w_down"],
        w[:, held].T))[0]


def layer(x, p, pos, mask, cfg, held=None):
    """One decoder layer: x [S, d] float32, ``p`` that layer's
    parameters, pos [S], mask [S, S] bool (row sees column)."""
    eps = cfg["rms_norm_eps"]
    f32 = jnp.float32
    x = x + attention(rms_norm(x, p["ln1"].astype(f32), eps), p, pos, mask,
                      cfg)
    return x + experts(rms_norm(x, p["ln2"].astype(f32), eps), p, cfg, held)


class _Frozen(dict):
    """The configuration's numbers as a hashable static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
        "num_experts", "num_experts_per_tok", "norm_topk_prob",
        "rms_norm_eps", "rope_theta")

_layer = jax.jit(layer, static_argnums=(4, 5))


def hidden(p, ids, pos, mask, config):
    """The last layer's output for token ``ids`` at positions ``pos``
    under ``mask``; one jitted layer serves every layer."""
    cfg = _Frozen({k: config[k] for k in KEYS})
    x = p["embed"][ids].astype(jnp.float32)
    for i in range(config["num_hidden_layers"]):
        x = _layer(x, sub(p, "blocks.%d." % i), pos, mask, cfg, None)
    return x


@jax.jit
def _head(x, gain, head, eps):
    f32 = jnp.float32
    return jax.nn.log_softmax(
        rms_norm(x, gain.astype(f32), eps) @ head.astype(f32), axis=-1)


def log_probs(p, x, config):
    """Final RMSNorm, untied head, log-softmax of rows ``x``."""
    return _head(x, p["norm"], p["head"], config["rms_norm_eps"])


def block_mask(n, block_length):
    blk = np.arange(n) // block_length
    return blk[None, :] <= blk[:, None]


def forward_logits(p, ids, config, block_length):
    """``[S, vocab]`` log-probabilities of a sequence under the block
    mask: row i scores the token at position i."""
    ids = jnp.asarray(ids, jnp.int32)
    n = ids.shape[0]
    with jax.default_matmul_precision("highest"):
        x = hidden(p, ids, jnp.arange(n, dtype=jnp.int32),
                   jnp.asarray(block_mask(n, block_length)), config)
        return log_probs(p, x, config)


def reveal_states(prompt, tokens, reveal_pass, block_length, mask_id):
    """The inputs a block-diffusion decoder saw, pass by pass: for every
    generated block and each of its denoise passes t, the block as it
    stood before that pass (tokens revealed at passes < t, and what the
    prompt left over, in place; the mask token elsewhere).  Returns
    ``[(block start, [B ids], [(offset in block, index into tokens)
    scored at this pass])]``.  ``reveal_pass[g]``: the pass of its block
    at which generated token g was revealed (under the ``sequential``
    rule, `sequential_passes`)."""
    b, n_p = block_length, len(prompt)
    seq = list(prompt) + list(tokens)
    out = []
    for start in range(n_p - n_p % b, len(seq), b):
        at = [(j, start + j - n_p) for j in range(b)
              if n_p <= start + j < len(seq)]           # generated here
        for t in range(1 + max(reveal_pass[g] for _, g in at)):
            ids = [seq[start + j] if start + j < n_p
                   or (start + j < len(seq) and reveal_pass[start + j - n_p] < t)
                   else mask_id for j in range(b)]
            out.append((start, ids,
                        [(j, g) for j, g in at if reveal_pass[g] == t]))
    return out


def sequential_passes(n_prompt, n_tokens, block_length, steps):
    """`reveal_states`' ``reveal_pass`` under the ``sequential`` rule:
    a pass reveals the ``block_length / steps`` leftmost masked
    positions, so the pass follows from the position."""
    per = block_length // steps
    given = n_prompt % block_length     # what the prompt left the first block
    out = []
    for at in range(n_prompt, n_prompt + n_tokens):
        in_first = at - at % block_length < n_prompt
        out.append((at % block_length - (given if in_first else 0)) // per)
    return out


def reveal_logprobs(p, prompt, tokens, reveal_pass, config, block_length,
                    mask_id, pad_rows=1, pad_scored=64):
    """The log-probability each served token had at the pass that
    revealed it, ``[len(tokens)]``, in one masked forward: the clean
    sequence under the block mask, and beside it one copy of every
    generated block for each of its denoise passes (`reveal_states`),
    each copy seeing the clean blocks before its own and itself (the
    mask a block-diffusion model is trained under).  Rows are padded to
    a multiple of ``pad_rows`` with rows that see only themselves, the
    scored rows to a multiple of ``pad_scored`` (few shapes to compile)."""
    b = block_length
    seq = list(prompt) + list(tokens)
    states = reveal_states(prompt, tokens, reveal_pass, b, mask_id)
    n = len(seq)
    rows = n + b * len(states)
    total = -(-rows // pad_rows) * pad_rows
    ids = np.full(total, mask_id, np.int32)
    pos = np.zeros(total, np.int32)
    mask = np.eye(total, dtype=bool)
    ids[:n], pos[:n] = seq, np.arange(n)
    mask[:n, :n] = block_mask(n, b)
    scored = []                         # (row, index into tokens)
    for c, (start, block, at) in enumerate(states):
        r0 = n + b * c
        ids[r0:r0 + b] = block
        pos[r0:r0 + b] = start + np.arange(b)
        mask[r0:r0 + b, :start] = True          # the clean blocks before
        mask[r0:r0 + b, r0:r0 + b] = True       # and its own copy
        scored += [(r0 + j, g) for j, g in at]
    with jax.default_matmul_precision("highest"):
        x = hidden(p, jnp.asarray(ids), jnp.asarray(pos), jnp.asarray(mask),
                   config)
        # departure: the head on the scored rows only
        take = np.zeros(-(-len(scored) // pad_scored) * pad_scored, np.int32)
        take[:len(scored)] = [r for r, _ in scored]
        lp = np.asarray(log_probs(p, x[jnp.asarray(take)], config))
    out = np.zeros(len(tokens), np.float32)
    for i, (_, g) in enumerate(scored):
        out[g] = lp[i, tokens[g]]
    return out


def reveal_logprobs_naive(p, prompt, tokens, reveal_pass, config,
                          block_length, mask_id):
    """`reveal_logprobs` the slow way, one forward a reveal state: the
    clean tokens before the block, then the block as it stood, under
    the block mask.  What the tests hold the one-forward scorer to."""
    seq = list(prompt) + list(tokens)
    out = np.zeros(len(tokens), np.float32)
    for start, block, at in reveal_states(prompt, tokens, reveal_pass,
                                          block_length, mask_id):
        lp = np.asarray(forward_logits(p, seq[:start] + block, config,
                                       block_length))
        for j, g in at:
            out[g] = lp[start + j, tokens[g]]
    return out
