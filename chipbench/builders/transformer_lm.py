"""A decoder-only language model through the program's
`models.TransformerLM` (cut down from `chip_smoke.py` ``lm_model``)."""

import numpy as np

from chipbench.common import need, say
from chipbench.references import transformer_lm as reference

# The engine multiplies float32 operands at the chip's default precision
# (one bfloat16 pass); the reference multiplies them in float32.  With
# random weights the logits have a standard deviation near 0.64, and the
# rounding of the products moves a token's log-probability by up to 2e-2
# over 24 layers: the largest difference over the 6 requests (600-1,000
# served tokens) a run compares read 1.2e-2 to 1.7e-2 over 13 seeds on the
# chip, and 2.0e-2 once at a higher rate: the lower reading (PERF.md,
# PR 34).  The upper reading is a fault's: a token altered where it is
# emitted moves it by 0.65 at the least and 2.6 at the median (the chip,
# PR 34), a token scored at the wrong position, a missing layer or a
# cache row out of place by 0.3 and more (PR 25).  The limit stands 2.5
# times above the one and 6 times below the other.  The reference
# computed in bfloat16 throughout reads 1.5e-2 to 1.8e-2, the same as
# the engine, which is arithmetically such a program: weights or a cache
# *held* in another type than the configuration states are refused by
# `holds_stated_precision`, exactly, not by this number.
LOGPROB_ATOL = 5e-2


def build(config, seed):
    from paddle_tpu import models
    from paddle_tpu.fluid import dygraph

    cfg = models.TransformerLMConfig(
        vocab_size=config["vocab_size"], hidden_size=config["n_embd"],
        num_layers=config["n_layer"], num_heads=config["n_head"],
        intermediate_size=config["n_inner"],
        max_position_embeddings=config["n_positions"], dropout=0.0,
        initializer_range=config["initializer_range"])
    with dygraph.guard():
        np.random.seed((seed + 7) % 2 ** 32)
        return models.TransformerLM(cfg)


def holds_stated_precision(config, cache):
    """The served model is held as the configuration's ``precision``
    states it: the KV cache (``engine.stats()["cache"]``) in that type
    and at no fewer bytes than slots x max_len tokens need, and the
    floating-point arrays on the device (weights and cache are nearly all
    of them) in the weights' type, at no fewer bytes than weights and
    cache together.  A narrower copy of either is another configuration,
    with a file of its own, not a faster run of this one."""
    import jax

    want, serving = config["precision"], config["serving"]
    item = np.dtype(want["kv_cache"]).itemsize
    cache_bytes = (2 * config["n_layer"] * config["n_embd"] * item
                   * serving["slots"] * serving["max_len"])
    weight_bytes = n_params(config) * np.dtype(want["weights"]).itemsize
    held = {}
    for a in jax.live_arrays():
        if np.issubdtype(a.dtype, np.inexact) or a.dtype.name == "bfloat16":
            held[a.dtype.name] = held.get(a.dtype.name, 0) + a.nbytes
    other = sum(n for name, n in held.items() if name != want["weights"])
    say("precision", stated=want, cache_dtype=cache["dtype"],
        cache_kv_dtype=cache.get("kv_dtype"), cache_bytes=cache["bytes"],
        cache_bytes_needed=cache_bytes, weight_bytes_needed=weight_bytes,
        float_bytes_on_device=held)
    ok = need(cache["dtype"] == want["kv_cache"]
              and cache.get("kv_dtype", cache["dtype"]) == want["kv_cache"],
              "the KV cache is held as %s/%s, the configuration states %s"
              % (cache["dtype"], cache.get("kv_dtype"), want["kv_cache"]))
    ok &= need(cache["bytes"] >= cache_bytes,
               "the KV cache holds %d bytes, fewer than the %d that %d "
               "slots of %d tokens need in %s"
               % (cache["bytes"], cache_bytes, serving["slots"],
                  serving["max_len"], want["kv_cache"]))
    ok &= need(held.get(want["weights"], 0) >= weight_bytes + cache_bytes
               and other <= 0.01 * sum(held.values()),
               "floating-point arrays on the device are %r: not the "
               "weights and cache in %s alone" % (held, want["weights"]))
    return ok


def n_params(config):
    """Parameters of the decoder as published: embeddings (the head is
    tied), and per layer attention, FFN and two LayerNorms."""
    d, f = config["n_embd"], config["n_inner"]
    layer = 4 * d * d + 4 * d + 2 * d * f + f + d + 4 * d
    return ((config["vocab_size"] + config["n_positions"]) * d
            + config["n_layer"] * layer + 2 * d)


def reference_logprobs(model, config, sequences, pad_to):
    """For each ``(prompt, generated)`` pair, the plain reference's
    log-probability of every generated token given what came before it
    (teacher-forced on the engine's own tokens), float32 products."""
    import jax
    import jax.numpy as jnp

    params = {k: v.data for k, v in model.state_dict().items()}
    block = jax.jit(reference.block, static_argnums=2)

    def score(params, ids):
        return reference.next_token_logprobs(
            params, ids[None], layers=config["n_layer"],
            heads=config["n_head"], block_fn=block)[0]

    out = []
    with jax.default_matmul_precision("highest"):
        for prompt, generated in sequences:
            seq = list(prompt) + list(generated)
            ids = np.zeros(pad_to, np.int32)
            ids[:len(seq)] = seq        # causal: padding cannot reach back
            lp = np.asarray(score(params, jnp.asarray(ids)))
            out.append([float(lp[len(prompt) + i - 1, tok])
                        for i, tok in enumerate(generated)])
    return out
