"""Each runner end to end at a tiny size on the CPU, through the function
the command line calls (``require_chip=False``).  The tiny configurations,
traffic and the extra per-layer metric are files of their own under
``tests/chipbench/``, named by ``BENCHMARK_tiny.json``: added the way a
later PR adds a configuration, a cell and a metric, with no edit to a
file that was there.  Nothing here asserts a time."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import run  # noqa: E402

TINY = "tests/chipbench/BENCHMARK_tiny.json"
SEED = 2 ** 31 + 5              # the driver's seeds are large


def cell(workload, trace, capsys, seconds=1.0):
    record = run.run_cell(workload, SEED, seconds, trace,
                          require_chip=False, benchmark=TINY)
    out = capsys.readouterr().out
    json.dumps(record)                          # the last line is JSON
    assert set(record) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert set(record["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert record["correct"] is True, out
    assert record["failed"] == 0 and record["attempted"] > 0
    bench_ = run.load_json(REPO, TINY)
    group = "per_layer" if trace else "end_to_end"
    known = {m["name"]: m["unit"]
             for m in run.metrics_of(bench_, group, workload)}
    for name, m in record["metrics"].items():
        assert m["unit"] == known[name] and isinstance(m["value"], float)
    return record, out


@pytest.mark.parametrize("workload", ["tiny-train-1dev", "tiny-train-4dev"])
def test_train_cell_end_to_end(workload, capsys):
    record, out = cell(workload, 0, capsys)
    assert set(record["metrics"]) == {"setup_s", "train_tokens_per_s"}
    assert record["metrics"]["train_tokens_per_s"]["value"] > 0
    assert "[reference]" in out and "[warmup]" in out
    if workload.endswith("4dev"):
        assert "[opt-state]" in out and "holders=4" in out


def test_train_cell_traced_reports_layer_metrics(capsys):
    record, out = cell("tiny-train-1dev", 1, capsys)
    # the CPU trace has no device plane and no peak: those readers find
    # nothing and their metrics are left out, not invented
    assert set(record["metrics"]) == {
        "step_ms_p50.train", "data_wait_ms_p50.train",
        "window_compilations.train", "steps_in_window.tiny"}
    assert record["metrics"]["window_compilations.train"]["value"] == 0
    assert "breakdown" not in record and "busy_s" not in record["device"]
    assert "[tracing-overhead]" in out


def test_serve_cell_end_to_end(capsys):
    record, out = cell("tiny-chat", 0, capsys, seconds=3.0)
    assert set(record["metrics"]) == {"setup_s", "itl_ms_p95",
                                      "latency_ms_per_token"}
    assert "max_logprob_diff" in out and "[precision]" in out
    # the window's requests are one set for every seed
    bench_ = run.load_json(REPO, TINY)
    mix = run.load_json(REPO, run.find(REPO, bench_, "traffic",
                                       "tiny-chat.json"))
    assert record["attempted"] == round(mix["rate_per_s"] * 3.0)


def test_serve_cell_traced_reports_layer_metrics(capsys):
    record, out = cell("tiny-chat", 1, capsys, seconds=3.0)
    assert set(record["metrics"]) == {
        "window_compilations.serve", "decode_step_ms_p50.serve",
        "slot_occupancy_mean.serve", "kv_pool_live_share.serve",
        "prefill_ms_p50.serve",
        "decode_attn_kernel_share.serve", "shed_share.serve",
        "gen_lateness_ms_p95.serve", "ttft_ms_p95.serve", "ttft_ms_p50.serve",
        "tokens_per_s.serve"}
    assert record["metrics"]["window_compilations.serve"]["value"] == 0
    assert record["metrics"]["shed_share.serve"]["value"] == 0
    assert record["metrics"]["decode_attn_kernel_share.serve"]["value"] == 0


def test_a_cache_narrower_than_the_configuration_states_is_not_correct(
        capsys):
    from chipbench.builders import transformer_lm

    config = run.load_json(REPO, "tests/chipbench/configs/tiny-lm.json")
    cache = {"dtype": "float32", "kv_dtype": "float32", "bytes": 10 ** 9}
    assert transformer_lm.holds_stated_precision(
        dict(config, precision={"weights": "float32",
                                "kv_cache": "float32"}),
        dict(cache, dtype="bfloat16", kv_dtype="bfloat16")) is False
    assert transformer_lm.holds_stated_precision(
        config, dict(cache, kv_dtype="int8")) is False
    assert transformer_lm.holds_stated_precision(
        config, dict(cache, bytes=1000)) is False
    assert "[check] FAILED=" in capsys.readouterr().out


def test_a_failed_check_gives_correct_false_not_an_exception(
        capsys, monkeypatch):
    from chipbench.builders import transformer_lm

    monkeypatch.setattr(transformer_lm, "LOGPROB_ATOL", -1.0)
    record = run.run_cell("tiny-chat", 3, 2.0, 0, require_chip=False,
                          benchmark=TINY)
    assert record["correct"] is False
    assert "[check] FAILED=" in capsys.readouterr().out
