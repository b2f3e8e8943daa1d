"""Median host time of one `ShardedTrainStep` call: batch placement and
the dispatch of the step, until the call returns
(`train_step_dispatch_ms`, window only)."""

from chipbench.common import histogram


def read(obs):
    h = histogram(obs, "train_step_dispatch_ms")
    return h and h["p50"]
