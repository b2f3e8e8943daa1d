"""SDAR-30B-A3B-Chat's decoder (``model_type: sdar_moe``) through the
program's `models.MoEDecoderLM`, served by block diffusion.

**Serving a block-diffusion model.**  The configuration's
``block_length`` is the model's mask granule; `GenerationEngine` reads it
off the model and generates by diffusion over blocks of that many
tokens.  Two keys of ``serving`` say how: ``denoising_steps`` (a divisor
of the block: a pass reveals ``block_length / denoising_steps``
positions) and ``remasking`` (``sequential``, ``low_confidence_static``
or ``low_confidence_dynamic``).  A prompt's whole blocks go into the
cache 128 rows a scheduler iteration (the engine's ``prefill_chunk``),
between the passes of the live streams.  A streamed token's ``logprob`` is the raw
log-softmax it had at the pass that revealed it, at its own position (a
masked position predicts itself).  What ``/metrics`` and the per-layer
readers count as a step (``generation_itl_ms``, ``decode_steps``) is one
call of ``generation_block_step``: one pass over the block of every live
slot; tokens are counted when streamed (``generation_tokens_total``),
passes, commits and reveals by ``generation_block_passes_total``,
``generation_block_commits_total``, ``generation_tokens_revealed_total``.
"""

import numpy as np

from chipbench.common import need, say
from chipbench.references import sdar_moe as reference

# What `correct` compares: the engine's streamed log-probability of every
# token of 6 served greedy requests (bfloat16 weights and cache, products
# of bfloat16 operands summed in float32) against the plain float32
# reference's at the pass that revealed it (`reference.reveal_logprobs`),
# the largest difference over their 800 to 1,500 tokens.
# Lower reading, sound runs on the chip (PERF.md section 6, PR 35): 0.013
# to 0.043 over 25 runs of the cell on 21 seeds at 2 to 7 requests/s, and
# 0.023, 0.027 over two seeds served in one process; with prompts in
# 128-row chunks 0.019 to 0.044 over 17 more runs on 8 seeds; the median
# token differs by 0.003 and the 99th percentile by 0.012, bfloat16
# rounding through six layers.
# Upper reading, planted faults, each on two seeds on the chip, the
# engine's tokens scored by the reference with the fault in it: the
# largest of a token's eight experts left out 0.93 and 1.11, the third
# 0.22 and 0.29; a row of the block that cannot see the row after it 0.21
# and 0.36; q normalisation skipped 0.29 and 0.25; every token scored one
# pass early 0.26 and 0.26.  The limit stands 2.3 times above the one and
# 2.1 times below the lowest of the other.  Two faults read UNDER it, and
# why: the smallest of the eight experts left out 0.039 and 0.042 (it
# weighs 0.012 of the sum), and one position in four scored one pass early
# 0.072 and 0.087 (with random weights attention is spread over hundreds
# of positions, and whether ONE neighbour shows its token or the mask
# token moves little); the float32 CPU tests catch both to 1e-5.  With the
# router drawn at the other weights' spread (0.02) a sound run read 0.055
# to 0.083 and these faults 0.085 to 0.145: the 8th and 9th expert then
# weigh the same 0.085, rounding swaps them between engine and reference,
# and no limit stood between (the configuration's `departs_from_source`).
# The control in the precision below, on the chip: the ENGINE serving
# with its weights rounded a second time in place, bfloat16 cut to
# float8_e4m3's three mantissa bits, against this reference on the weights
# as stated reads 0.258 over 781 tokens of six requests: not held
# (`_scratch`-style control, PERF.md section 6); `holds_stated_precision`
# refuses a narrower TYPE besides (types and bytes, exact).
# Not told apart by this number: WHICH pass revealed a token (above: one
# position in four a pass early reads under the limit).  The engine's
# record of it (`RequestHandle.reveal_passes`) equals
# `reference.sequential_passes` exactly in tests/test_moe_decoder.py; the
# harness hands a builder prompts, tokens and log-probabilities only
# (PERF.md section 7 names the two edits that would bring it here).
LOGPROB_ATOL = 0.1

PAD_ROWS = 1024     # rows of the reference's one masked forward

# keys of the published config this builder cannot vary: the value the
# program's block has built in
FIXED = {"hidden_act": "silu", "attention_bias": False,
         "decoder_sparse_step": 1, "mlp_only_layers": [],
         "rope_scaling": None, "use_sliding_window": False,
         "tie_word_embeddings": False}


def model_config(config):
    from paddle_tpu import models

    for key, value in FIXED.items():
        if config[key] != value:
            raise ValueError("%s = %r: models.MoEDecoderLM has %r only"
                             % (key, config[key], value))
    return models.MoEDecoderConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_experts=config["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        rope_theta=config["rope_theta"], rms_norm_eps=config["rms_norm_eps"],
        max_position_embeddings=config["max_position_embeddings"],
        initializer_range=config["initializer_range"],
        block_length=config["block_length"],
        mask_token_id=config["mask_token_id"],
        dtype=config["precision"]["weights"])


def build(config, seed):
    """The model with its weights made on the device from the seed, in
    the type the configuration states (no host copy)."""
    from paddle_tpu import models

    return models.MoEDecoderLM.seeded(model_config(config), seed)


def n_params(config):
    """Parameters held: embedding and untied head, and per layer the
    attention, its two per-head gains, the two norms, the router and
    every expert's three matrices; the final norm."""
    d, dim = config["hidden_size"], config["head_dim"]
    q, kv = config["num_attention_heads"] * dim, \
        config["num_key_value_heads"] * dim
    layer = (2 * d * q + 2 * d * kv + 2 * dim + 2 * d
             + d * config["num_experts"]
             + 3 * config["num_experts"] * d * config["moe_intermediate_size"])
    return (2 * config["vocab_size"] * d + d
            + config["num_hidden_layers"] * layer)


def holds_stated_precision(config, cache):
    """Weights and KV cache are held as ``precision`` states them: the
    cache (``engine.stats()["cache"]``) in that type, rows of
    ``num_key_value_heads`` heads, at no fewer bytes than slots x max_len
    tokens need; the floating-point arrays on the device in the weights'
    type at no fewer bytes than weights and cache together."""
    import jax

    want, serving = config["precision"], config["serving"]
    item = np.dtype(want["kv_cache"]).itemsize
    cache_bytes = (2 * config["num_hidden_layers"]
                   * config["num_key_value_heads"] * config["head_dim"]
                   * item * serving["slots"] * serving["max_len"])
    weight_bytes = n_params(config) * np.dtype(want["weights"]).itemsize
    held = {}
    for a in jax.live_arrays():
        if np.issubdtype(a.dtype, np.inexact) or a.dtype.name == "bfloat16":
            held[a.dtype.name] = held.get(a.dtype.name, 0) + a.nbytes
    other = sum(n for name, n in held.items() if name != want["weights"])
    say("precision", stated=want, cache_dtype=cache["dtype"],
        cache_heads=cache["heads"], cache_bytes=cache["bytes"],
        cache_bytes_needed=cache_bytes, weight_bytes_needed=weight_bytes,
        float_bytes_on_device=held)
    ok = need(cache["dtype"] == want["kv_cache"]
              and cache.get("kv_dtype", cache["dtype"]) == want["kv_cache"]
              and cache["heads"] == config["num_key_value_heads"],
              "the KV cache is held as %s x %d heads, the configuration "
              "states %s x %d" % (cache["dtype"], cache["heads"],
                                  want["kv_cache"],
                                  config["num_key_value_heads"]))
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


def reference_logprobs(model, config, sequences, pad_to):
    """For each served ``(prompt, tokens)`` pair, the plain float32
    reference's log-probability of every token at the pass that revealed
    it (`reference.reveal_logprobs`, one masked forward a request).  The
    pass follows from the position under the ``sequential`` rule, the
    only one a cell can be scored under without the engine's own record
    of the order.  Rows (the sequence and its reveal-state copies, about
    prompt + 5 x output) are padded to a multiple of `PAD_ROWS` and the
    scored rows to one of 512, so that a run compiles few shapes of the
    one jitted layer and one of the head; ``pad_to`` pads a plain
    sequence, which this is not, and at a tiny size stands in for
    both."""
    serving = config["serving"]
    if serving["remasking"] != "sequential":
        raise ValueError("the served tokens can be scored under the "
                         "sequential rule only, not %r" % serving["remasking"])
    params = {k: v.data for k, v in model.state_dict().items()}
    rows, scored = (PAD_ROWS, 512) if pad_to >= 256 else (pad_to, pad_to)
    out = []
    for prompt, tokens in sequences:
        passes = reference.sequential_passes(
            len(prompt), len(tokens), config["block_length"],
            serving["denoising_steps"])
        out.append([float(x) for x in reference.reveal_logprobs(
            params, prompt, tokens, passes, config, config["block_length"],
            config["mask_token_id"], pad_rows=rows, pad_scored=scored)])
    return out
