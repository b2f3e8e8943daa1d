"""GPT-2-style decoder-only language model (Radford et al., 2019), plain
jnp: learned absolute positions, pre-LN blocks, causal attention, head
tied to the word embeddings, final LayerNorm (epsilon 1e-5 as published).

Departure shared with the program's `TransformerLM`: exact (erf) gelu
where GPT-2 has the tanh approximation ``gelu_new``."""

import jax
import jax.numpy as jnp

from chipbench.references.bert import attention, ffn, layer_norm, sub


def block(x, p, heads):
    """One pre-LN decoder block; ``p``: that block's parameters."""
    x = x + attention(layer_norm(x, p["ln1.weight"], p["ln1.bias"]),
                      sub(p, "attn."), heads, True)
    return x + ffn(layer_norm(x, p["ln2.weight"], p["ln2.bias"]), p)


def log_probs(p, x):
    """Final LayerNorm, tied head, log-softmax."""
    x = layer_norm(x, p["ln_f.weight"], p["ln_f.bias"])
    return jax.nn.log_softmax(x @ p["word.weight"].T)


def next_token_logprobs(p, ids, *, layers, heads, block_fn=block):
    """``[B, S, vocab]`` float32 log-probabilities of the next token after
    each position of token ids ``[B, S]``, under the float32 parameters
    ``p`` (the program's parameter names).  A caller that jits passes its
    jitted ``block_fn``, so that every block runs the one small program."""
    s = ids.shape[1]
    x = p["word.weight"][ids] + p["position.weight"][jnp.arange(s)][None]
    for i in range(layers):
        x = block_fn(x, sub(p, "blocks.%d." % i), heads)
    return log_probs(p, x)
