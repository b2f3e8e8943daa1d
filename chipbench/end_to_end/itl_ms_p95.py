"""95th percentile over all gaps between successive streamed tokens at the
HTTP client, the requests due in the window pooled."""

from chipbench import stats


def read(obs):
    return stats.percentile(obs["samples"]["itl_ms"], 95)
