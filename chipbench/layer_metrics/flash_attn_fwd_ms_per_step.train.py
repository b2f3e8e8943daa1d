"""Device time a step spends in the kernel `flash_attention_fwd`, every
layer: its share of the traced steps times the median step time."""

from chipbench.program_trace import ms_per_step


def read(obs):
    return ms_per_step(obs, "kernel_s", "flash_attention_fwd")
