"""XLA compilations inside the measured window; 0 is expected."""

from chipbench.common import counter_delta


def read(obs):
    return counter_delta(obs, "xla_compilations_total")
