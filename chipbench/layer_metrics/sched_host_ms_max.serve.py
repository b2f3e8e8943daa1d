"""The longest stretch one scheduler step spent away from the device in the
window (`generation_sched_host_ms`, its largest observation): a single
stall of the serving process, which no percentile of the gaps shows."""

from chipbench.common import histogram


def read(obs):
    h = histogram(obs, "generation_sched_host_ms")
    return h and h["max"]
