"""Percent of the window's decode steps (plain, block or verify) whose
dispatch wrote rows the host had changed over the device's per-slot
state, in one `generation_state_merge` call:
`generation_state_merges_total` over `generation_decode_steps_total`,
both as they grew in the window.  None for a program without the
counter, whose dispatch puts every per-slot operand on every step."""

from chipbench.common import counter_delta, counter_value


def read(obs):
    steps = counter_delta(obs, "generation_decode_steps_total")
    if not steps or counter_value(obs["counters_after"],
                                  "generation_state_merges_total") is None:
        return None
    return 100.0 * counter_delta(
        obs, "generation_state_merges_total") / steps
