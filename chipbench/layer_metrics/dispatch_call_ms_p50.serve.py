"""Median time of the decode step's jitted call inside a dispatch, until
it returns: its arguments flattened, the host operands put on the
device and the program enqueued (`generation_dispatch_call_ms`, span
`generation.dispatch_call`, one observation a dispatch, window only)."""

from chipbench.common import histogram


def read(obs):
    h = histogram(obs, "generation_dispatch_call_ms")
    return h and h["p50"]
