"""How unevenly a step's rows load the experts: the busiest expert's
visits over the mean expert's, the layers' mean, one observation a step
(`generation_moe_load_max_over_mean`, from the `[layers, experts]` count
the step hands back); the window's mean."""

from chipbench.common import histogram


def read(obs):
    h = histogram(obs, "generation_moe_load_max_over_mean")
    return h and h["sum"] / h["count"]
