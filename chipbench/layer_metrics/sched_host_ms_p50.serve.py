"""Median host time of a scheduler step that decoded: the step's duration
less its time inside the device calls (prefill, decode dispatch, decode
fetch), as the engine clocks it (`generation_sched_host_ms`, window only)."""

from chipbench.common import histogram


def read(obs):
    h = histogram(obs, "generation_sched_host_ms")
    return h and h["p50"]
