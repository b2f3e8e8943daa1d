"""Bytes and operations of a block-diffusion step of the gated-expert
decoder, for the rooflines `block_step_roofline.serve` and
`moe_experts_roofline.serve`: what a step MUST move or compute, from the
configuration and the engine's own counts, never what an implementation
happens to do (the program's dense expert products read every expert and
multiply every row by it; neither is counted here)."""

import numpy as np

from chipbench.common import counter_delta, histogram


def step_means(obs):
    """(experts touched, cache rows, live slots) a block step, the
    window's means, from the engine's counters; None for a program that
    counts none."""
    steps = histogram(obs, "generation_itl_ms")
    touched = counter_delta(obs, "generation_moe_experts_touched_total")
    if not steps or not touched:
        return None
    n = steps["count"]
    return (touched / n,
            counter_delta(obs, "generation_block_cache_rows_total") / n,
            counter_delta(obs, "generation_block_passes_total") / n)


def _item(config):
    return np.dtype(config["precision"]["weights"]).itemsize


def expert_bytes(config):
    """One expert's three matrices (gate, up, down)."""
    return (3 * config["hidden_size"] * config["moe_intermediate_size"]
            * _item(config))


def dense_layer_bytes(config):
    """A layer's weights every step reads whatever is routed: q, k, v and
    o projections, the per-head gains, the two norms and the router."""
    d, dim = config["hidden_size"], config["head_dim"]
    q = config["num_attention_heads"] * dim
    kv = config["num_key_value_heads"] * dim
    return (2 * d * q + 2 * d * kv + 2 * dim + 2 * d
            + d * config["num_experts"]) * _item(config)


def head_bytes(config):
    """The untied head and the final norm (the embedding is read a row a
    token: counted with the rows)."""
    d = config["hidden_size"]
    return (config["vocab_size"] * d + d) * _item(config)


def cache_row_bytes(config):
    """K and V of one position in every layer."""
    return (2 * config["num_hidden_layers"] * config["num_key_value_heads"]
            * config["head_dim"]
            * np.dtype(config["precision"]["kv_cache"]).itemsize)


def block_step_bytes(config, experts_touched, cache_rows, rows):
    """Bytes one step must move: the experts its live rows visit
    (``experts_touched``, summed over the layers), every layer's dense
    weights, the head, the live cache rows it attends over, and its
    ``rows`` embedding rows."""
    return (experts_touched * expert_bytes(config)
            + config["num_hidden_layers"] * dense_layer_bytes(config)
            + head_bytes(config) + cache_rows * cache_row_bytes(config)
            + rows * config["hidden_size"] * _item(config))


def experts_bytes(config, experts_touched, rows):
    """Bytes the expert products of a step must move: the visited
    experts' weights, and each row's input and output in every layer."""
    return (experts_touched * expert_bytes(config)
            + 2 * rows * config["num_hidden_layers"] * config["hidden_size"]
            * _item(config))


def experts_flops(config, rows):
    """Operations of the expert products for ``rows`` tokens: each row
    through ``num_experts_per_tok`` experts of three matrices, in every
    layer (2 a multiply-add)."""
    return (2 * rows * config["num_hidden_layers"]
            * config["num_experts_per_tok"] * 3 * config["hidden_size"]
            * config["moe_intermediate_size"])
