"""p95 of the time an HTTP handler took from its parsed body to
`fleet.submit` returning (`generation_front_admit_ms`, window only): it
holds the wait for the engine's lock, which the scheduler keeps through
its device calls."""

from chipbench.common import histogram


def read(obs):
    h = histogram(obs, "generation_front_admit_ms")
    return h and h["p95"]
