"""Device time a step spends in the kernels `flash_attention_bwd_*` (the
fused backward, or dq and dkv), every layer: their share of the traced
steps times the median step time."""

from chipbench.program_trace import ms_per_step


def read(obs):
    return ms_per_step(obs, "kernel_s", "flash_attention_bwd_")
