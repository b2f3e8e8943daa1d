"""Time to first token, p95: from the instant a request was due to its first
streamed token at the HTTP client, over the requests due in the window.  A
per-layer reading while a window holds only some tens of requests (PERF.md)."""

from chipbench import stats


def read(obs):
    return stats.percentile(obs["samples"]["ttft_ms"], 95)
