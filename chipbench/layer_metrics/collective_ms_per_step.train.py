"""Device time a step's collectives (all-gather, reduce-scatter,
all-reduce, ...) are in flight on a chip, the chips' mean: the union of
their intervals (`chipbench/collectives.py`) as a share of the traced
steps, times the median step time.  Nothing on one chip."""

from chipbench.program_trace import ms_per_step


def read(obs):
    return ms_per_step(obs, "collective_s", "in_flight")
