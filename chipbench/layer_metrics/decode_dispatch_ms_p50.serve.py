"""Median host time of one decode dispatch, as the engine clocks its
span `generation.decode_dispatch` (`generation_dispatch_ms`, one
observation a dispatch, window only): the token or block merge, the
operands, the step's call and the bookkeeping after it.  Where an
engine drafts and verifies, its draft and verify dispatches are in the
same median; no cell runs one."""

from chipbench.common import histogram


def read(obs):
    h = histogram(obs, "generation_dispatch_ms")
    return h and h["p50"]
