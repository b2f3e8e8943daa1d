"""Share of the decode and verify steps with a live sampling row, whose
sampler runs its selections and a draw; the others' sampler is one argmax
(`generation_sampling_step_share`, one observation of 0 or 1 a step,
window only)."""

from chipbench.common import histogram


def read(obs):
    h = histogram(obs, "generation_sampling_step_share")
    return h and 100.0 * h["sum"] / h["count"]
