"""The longest the scheduler waited for the engine lock in the window
(`generation_lock_wait_ms`, span `generation.lock_wait`, one observation
a step, its largest): HTTP handlers take that lock to admit a request."""

from chipbench.common import histogram


def read(obs):
    h = histogram(obs, "generation_lock_wait_ms")
    return h and h["max"]
