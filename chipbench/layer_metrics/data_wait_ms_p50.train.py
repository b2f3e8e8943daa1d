"""Median time the training loop waited for its next batch
(`io_step_wait_ms` of the `io.DevicePrefetcher`, window only)."""

from chipbench.common import histogram


def read(obs):
    h = histogram(obs, "io_step_wait_ms")
    return h and h["p50"]
