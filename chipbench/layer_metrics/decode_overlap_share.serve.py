"""Percent of the window's decode steps (plain, block or verify) that
were dispatched while the step before was un-fetched:
`generation_decode_overlapped_total` over
`generation_decode_steps_total`, both as they grew in the window."""

from chipbench.common import counter_delta


def read(obs):
    steps = counter_delta(obs, "generation_decode_steps_total")
    if not steps:
        return None
    return 100.0 * counter_delta(
        obs, "generation_decode_overlapped_total") / steps
