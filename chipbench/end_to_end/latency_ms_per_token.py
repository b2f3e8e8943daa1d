"""Normalized latency, as the Orca and vLLM papers report it: a request's
time from the instant it was due to its last streamed token, over its
output tokens; the mean over the requests due in the window.  Below the
knee it is the mean gap between tokens plus a share of the time to first
token; once requests wait for a slot all of the wait goes into it."""


def read(obs):
    v = obs["samples"]["latency_ms_per_token"]
    return sum(v) / len(v) if v else None
