"""Median device time of one execution of the block step (one pass over
the block of every live slot), from the `XLA Modules` events of the
program `generation_block_step`."""

from chipbench.program_trace import module_ms_p50


def read(obs):
    return module_ms_p50(obs, "generation_block_step")
