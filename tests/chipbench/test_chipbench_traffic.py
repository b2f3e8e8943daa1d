"""Traffic generation, percentiles and the due-time arithmetic: plain
Python, reproducible from the seed."""

import collections
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import loadgen, stats, traffic  # noqa: E402

MIX = json.load(open(os.path.join(REPO, "chipbench", "traffic",
                                  "chat-steady.json")))


def test_percentile_interpolates_like_numpy():
    import numpy as np

    values = [3.0, 1.0, 4.0, 1.5, 9.0, 2.6]
    for p in (0, 50, 95, 100):
        assert stats.percentile(values, p) == pytest.approx(
            float(np.percentile(values, p)))
    assert stats.percentile([], 95) is None
    assert stats.summary([1.0, 2.0, 3.0]) == {
        "n": 3, "p50": 2.0, "p95": pytest.approx(2.9), "max": 3.0}
    assert stats.summary([])["max"] is None


def test_the_same_seed_gives_the_same_requests():
    a = traffic.requests(MIX, 50257, 2 ** 31 + 11, 20.0)
    b = traffic.requests(MIX, 50257, 2 ** 31 + 11, 20.0)
    assert a == b
    assert a != traffic.requests(MIX, 50257, 12, 20.0)


def window(reqs):
    return [r for r in reqs if r["body"]["request_id"].startswith("w")]


def test_every_seed_offers_the_same_work_in_another_order():
    a = traffic.requests(MIX, 50257, 1, 50.0)
    b = traffic.requests(MIX, 50257, 2, 50.0)
    lens = lambda reqs, key: collections.Counter(  # noqa: E731
        key(r["body"]) for r in reqs)
    # the window of every seed holds the same requests, the ramp too
    for part in (window, lambda reqs: [r for r in reqs
                                       if r not in window(reqs)]):
        assert lens(part(a), lambda r: len(r["prompt"])) == lens(
            part(b), lambda r: len(r["prompt"]))
        assert lens(part(a), lambda r: r["max_new_tokens"]) == lens(
            part(b), lambda r: r["max_new_tokens"])
    assert [r["body"]["max_new_tokens"] for r in a] != [
        r["body"]["max_new_tokens"] for r in b]
    gaps = lambda reqs: sorted(round(y["due"] - x["due"], 9)  # noqa: E731
                               for x, y in zip(reqs, reqs[1:]))
    assert gaps(window(a)) != [] and len(window(a)) == round(
        MIX["rate_per_s"] * 50.0)
    # one set of gaps, but for the pair that straddles the first one's halves
    assert len(set(gaps(window(a))) & set(gaps(window(b)))) >= len(
        window(a)) - 4


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11, 2147480001])
def test_at_the_cell_s_rate_every_seed_holds_one_multiset_of_work(seed):
    """Prompt lengths, output lengths, gaps and ``attempted`` of the
    re-based cell's 50 s window: one multiset whatever the seed."""
    def work(s):
        reqs = window(traffic.requests(MIX, 50257, s, 50.0))
        due = [r["due"] for r in reqs]
        return (sorted(len(r["body"]["prompt"]) for r in reqs),
                sorted(r["body"]["max_new_tokens"] for r in reqs),
                sorted(round(b - a, 9) for a, b in zip(due, due[1:])),
                sum(1 for r in reqs if "temperature" in r["body"]))
    prompts, outputs, gaps, sampled = work(seed)
    want = work(1)
    assert len(prompts) == round(MIX["rate_per_s"] * 50.0) >= 200
    assert (prompts, outputs, sampled) == (want[0], want[1], want[3])
    # the gaps are one set, but for the one that joins the halves of the
    # first gap (the stretch begins half-way through it)
    common_gaps = collections.Counter(gaps) & collections.Counter(want[2])
    assert sum(common_gaps.values()) >= len(gaps) - 2
    # the longest request of the mix lives well inside the ramp and drain
    assert MIX["ramp_s"] >= 5.0 and MIX["drain_s"] >= MIX["ramp_s"]


def test_the_window_s_requests_are_due_inside_it_and_the_ramp_s_before():
    reqs = traffic.requests(MIX, 50257, 7, 50.0)
    ramp = MIX["ramp_s"]
    assert [r["due"] for r in reqs] == sorted(r["due"] for r in reqs)
    for r in reqs:
        if r["body"]["request_id"].startswith("w"):
            assert ramp < r["due"] < ramp + 50.0
        else:
            assert r["body"]["request_id"].startswith("ramp")
            assert 0.0 < r["due"] < ramp
    assert len(reqs) - len(window(reqs)) == round(MIX["rate_per_s"] * ramp)
    ids = [r["body"]["request_id"] for r in reqs]
    assert len(set(ids)) == len(ids)


def test_lengths_follow_the_traffic_file():
    reqs = window(traffic.requests(MIX, 50257, 5, 120.0))
    prompts = sorted(len(r["body"]["prompt"]) for r in reqs)
    outs = sorted(r["body"]["max_new_tokens"] for r in reqs)
    p, o = MIX["prompt_tokens"], MIX["output_tokens"]
    assert prompts[0] >= p["min"] and prompts[-1] <= p["max"]
    assert outs[0] >= o["min"] and outs[-1] <= o["max"]
    assert abs(prompts[len(prompts) // 2] - p["median"]) <= 3
    assert abs(outs[len(outs) // 2] - o["median"]) <= 3
    assert all(0 <= t < 50257 for r in reqs for t in r["body"]["prompt"])
    sampled = [r for r in reqs if r["body"].get("temperature")]
    assert len(sampled) == len(reqs) // 2


def test_gaps_are_shuffled_whole_so_bursts_come_as_in_independent_draws():
    import random

    # the order is a plain permutation: somewhere in a few seeds two of
    # the eight shortest gaps of 48 stand side by side (a burst), which
    # dealing one value an octile to every round of eight could not give
    n, hits = 48, 0
    short = set(sorted(traffic.exponential_quantiles(n, 1.0))[:8])
    for seed in range(20):
        order = traffic.shuffled(traffic.exponential_quantiles(n, 1.0),
                                 random.Random(seed))
        assert sorted(order) == sorted(traffic.exponential_quantiles(n, 1.0))
        hits += any(a in short and b in short
                    for a, b in zip(order, order[1:]))
    assert hits >= 10
    due = traffic.arrivals(n, 50.0, random.Random(3))
    assert 0.0 < due[0] and due[-1] < 50.0 and due == sorted(due)
    assert due[-1] - due[0] < 50.0


def test_quantile_draws_are_deterministic():
    assert traffic.lognormal_quantiles(5, 100, 0.5, 16, 512) == \
        traffic.lognormal_quantiles(5, 100, 0.5, 16, 512)
    gaps = traffic.exponential_quantiles(100, 0.25)
    assert sum(gaps) == pytest.approx(25.0)
    assert min(gaps) > 0


def test_a_record_keeps_token_stamps_and_terminal_state():
    rec = {"id": "r0", "due": 10.0, "send": 10.001, "end": 11.0,
           "status": 200, "error": None,
           "stamps": [10.2, 10.25, 10.3, 10.31],
           "lines": [b'{"index": 0, "token": 7, "logprob": -1.0}\n',
                     b'{"index": 1, "token": 9, "logprob": -2.0}\n',
                     b'{"event": "restart"}\n',
                     b'{"done": true, "reason": "length", "n_tokens": 2}\n']}
    got = loadgen.parse(rec)
    assert got["tokens"] == [7, 9] and got["token_times"] == [10.2, 10.25]
    assert got["done"] is True and got["error"] is None
    # time to first token counts from when the request was due
    assert 1e3 * (got["token_times"][0] - got["due"]) == pytest.approx(200.0)
    shed = dict(rec, status=503, stamps=[10.1],
                lines=[b'{"error": "full", "shed": true}\n'])
    got = loadgen.parse(shed)
    assert got["tokens"] == [] and not got["done"] and got["error"] == "full"


def test_the_load_generator_imports_neither_jax_nor_numpy():
    import subprocess

    code = ("import sys; import chipbench.loadgen, chipbench.traffic; "
            "bad = [m for m in ('jax', 'numpy') if m in sys.modules]; "
            "sys.exit(1 if bad else 0)")
    assert subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          timeout=60).returncode == 0
