"""p95 of a request's wait from the entry of `submit` (before the engine's
lock) to the pop from the pending queue that admits it
(`generation_queue_wait_ms`, one observation a request, window only).  At
48 requests a window it is the third-largest wait, and the wait has two
modes (the handler takes the lock before the loop does, or waits out a
step): it says which mode the tail was in, and bounds no gain smaller than
a step (PERF.md, PR 26)."""

from chipbench.common import histogram


def read(obs):
    h = histogram(obs, "generation_queue_wait_ms")
    return h and h["p95"]
