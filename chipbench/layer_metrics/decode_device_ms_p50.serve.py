"""Median device time of one execution of the decode step, from the
`XLA Modules` events of the program `generation_decode`."""

from chipbench.program_trace import module_ms_p50


def read(obs):
    return module_ms_p50(obs, "generation_decode")
