"""Time to first token, median (see ttft_ms_p95.serve)."""

from chipbench import stats


def read(obs):
    return stats.percentile(obs["samples"]["ttft_ms"], 50)
