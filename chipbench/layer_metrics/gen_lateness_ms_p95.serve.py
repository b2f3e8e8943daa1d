"""How late the load generator sent: actual send time minus due time, p95.
A starved generator must not pass for a fast server."""

from chipbench import stats


def read(obs):
    return stats.percentile(obs["samples"]["lateness_ms"], 95)
