"""BERT (Devlin et al., 2018) pretraining forward and loss, plain jnp.

Post-LN encoder, learned absolute positions, exact (erf) gelu, MLM head
on the masked positions with the decoder tied to the word embeddings,
NSP head on the tanh-pooled first token.  Departures from the published
model, shared with the program: LayerNorm epsilon 1e-5 (published 1e-12),
and the MLM loss is normalised by ``sum(weights) + 1e-6``.  No dropout:
the reference is the deterministic forward."""

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def layer_norm(x, w, b):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * w + b


def gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / jnp.sqrt(2.0).astype(x.dtype)))


def attention(x, p, heads, causal):
    """Multi-head self-attention from a fused [D, 3D] projection whose
    columns are q | k | v, each head-major.  ``p``: one attention
    module's parameters."""
    b, s, d = x.shape
    qkv = x @ p["qkv_proj.weight"] + p["qkv_proj.bias"]
    q, k, v = (t.reshape(b, s, heads, d // heads)
               for t in jnp.split(qkv, 3, axis=-1))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.float32(d // heads))
    if causal:
        scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores,
                           -jnp.inf)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    return ctx.reshape(b, s, d) @ p["out_proj.weight"] + p["out_proj.bias"]


def ffn(x, p):
    return gelu(x @ p["fc1.weight"] + p["fc1.bias"]) @ p["fc2.weight"] \
        + p["fc2.bias"]


def sub(p, prefix):
    """The parameters under ``prefix``, named without it."""
    return {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}


def encoder_layer(x, p, heads):
    """One post-LN encoder layer; ``p``: that layer's parameters."""
    x = layer_norm(x + attention(x, sub(p, "attn."), heads, False),
                   p["ln1.weight"], p["ln1.bias"])
    return layer_norm(x + ffn(x, p), p["ln2.weight"], p["ln2.bias"])


def embed(p, batch):
    e = "bert.embeddings."
    x = (p[e + "word.weight"][batch["input_ids"]]
         + p[e + "position.weight"][batch["position_ids"]]
         + p[e + "token_type.weight"][batch["token_type_ids"]])
    return layer_norm(x, p[e + "ln.weight"], p[e + "ln.bias"])


def heads_loss(p, x, batch):
    """MLM + NSP loss from the encoder's output ``x``."""
    e = "bert.embeddings."
    pooled = jnp.tanh(x[:, 0] @ p["bert.pooler.weight"]
                      + p["bert.pooler.bias"])
    h = jnp.take_along_axis(x, batch["masked_positions"][..., None], axis=1)
    h = layer_norm(gelu(h @ p["mlm_transform.weight"]
                        + p["mlm_transform.bias"]),
                   p["mlm_ln.weight"], p["mlm_ln.bias"])
    logp = jax.nn.log_softmax(h @ p[e + "word.weight"].T + p["mlm_bias"])
    nll = -jnp.take_along_axis(logp, batch["mlm_labels"][..., None],
                               axis=-1)[..., 0]
    w = batch["mlm_weights"]
    mlm = (nll * w).sum() / (w.sum() + 1e-6)
    nsp_logp = jax.nn.log_softmax(pooled @ p["nsp.weight"] + p["nsp.bias"])
    nsp = -jnp.take_along_axis(nsp_logp, batch["nsp_labels"], axis=-1).mean()
    return mlm + nsp


def pretrain_loss(p, batch, *, layers, heads, layer_fn=encoder_layer):
    """MLM + NSP loss of ``batch`` (the program's batch keys) under the
    float32 parameters ``p`` (the program's parameter names).  A caller
    that jits passes its jitted ``layer_fn``: every layer then runs the
    one small program, where jitting this whole function would unroll
    them all into one large executable."""
    x = embed(p, batch)
    for i in range(layers):
        x = layer_fn(x, sub(p, "bert.encoder.%d." % i), heads)
    return heads_loss(p, x, batch)
