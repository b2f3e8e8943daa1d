"""Median wall time of a prefill call as the engine clocks it
(`generation_prefill_ms`, window only)."""

from chipbench.common import histogram


def read(obs):
    h = histogram(obs, "generation_prefill_ms")
    return h and h["p50"]
