"""Median host-clock time between two successive fetched losses."""

from chipbench import stats


def read(obs):
    return stats.percentile(obs["samples"]["step_ms"], 50)
