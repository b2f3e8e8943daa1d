"""p95 of the time from the engine putting a token on its request's queue
to the handler having written its chunk to the socket
(`generation_stream_lag_ms`, one observation a streamed token, window
only)."""

from chipbench.common import histogram


def read(obs):
    h = histogram(obs, "generation_stream_lag_ms")
    return h and h["p95"]
