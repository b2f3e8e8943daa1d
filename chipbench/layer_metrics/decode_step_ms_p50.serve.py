"""Median wall time of a decode step as the engine clocks it
(`generation_itl_ms`, window only)."""

from chipbench.common import histogram


def read(obs):
    h = histogram(obs, "generation_itl_ms")
    return h and h["p50"]
