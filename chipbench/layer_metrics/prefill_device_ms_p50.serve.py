"""Median device time of one prefill execution, from the `XLA Modules`
events of the programs `generation_prefill_<bucket>` (and
`generation_prefill_chunk_<width>`), the buckets pooled."""

from chipbench.program_trace import module_ms_p50


def read(obs):
    return module_ms_p50(obs, "generation_prefill_")
