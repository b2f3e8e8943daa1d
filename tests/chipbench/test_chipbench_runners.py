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
                           "device", "checks"}
    for compared in record["checks"].values():
        assert set(compared) == {"value", "limit"}
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
        "attn_walk_share.serve", "sampling_step_share.serve",
        "shed_share.serve",
        "gen_lateness_ms_p95.serve", "ttft_ms_p95.serve", "ttft_ms_p50.serve",
        "tokens_per_s.serve"}
    assert record["metrics"]["window_compilations.serve"]["value"] == 0
    assert record["metrics"]["shed_share.serve"]["value"] == 0
    # the two shares of the decode steps, in percent: the engine's own
    # counters (PRs 30 and 32), read since the decode-kernel share went
    for name in ("attn_walk_share.serve", "sampling_step_share.serve"):
        assert 0 < record["metrics"][name]["value"] <= 100


def test_the_four_chip_traffic_file_runs_on_four_virtual_devices(
        capsys, monkeypatch):
    """`chipbench/traffic/mlm-s512-b192-zero2.json` itself through
    `kinds/train.py`, its ZeRO stage, AMP and optimizer as written and
    only its sizes cut to the tiny configuration's."""
    real = run.load_json(REPO, "chipbench/traffic/mlm-s512-b192-zero2.json")
    one = run.load_json(REPO, "chipbench/traffic/mlm-s512-b48-zero0.json")
    assert {k: v for k, v in real.items() if real[k] != one[k]} == {
        "why": real["why"], "global_batch": 192, "zero_stage": 2}
    load = run.load_cell

    def cut(*a, **k):
        bench_, cell_, config, _ = load(*a, **k)
        return bench_, cell_, config, dict(
            real, global_batch=8, seq_len=32, masked=8, batch_pool=3,
            warmup_steps=3, traced_steps=3, reference_rows=2)

    monkeypatch.setattr(run, "load_cell", cut)
    record, out = cell("tiny-train-4dev", 0, capsys)
    assert record["metrics"]["train_tokens_per_s"]["value"] > 0
    assert "[opt-state]" in out and "holders=4" in out
    real_cell = {w["name"]: w for w in run.load_json(
        REPO, "BENCHMARK.json")["workloads"]}["bert-base-pretrain-zero2-4chip"]
    assert real_cell["chips"] == 4
    assert real_cell["traffic"] == "mlm-s512-b192-zero2"


def test_a_traced_run_without_a_reduced_trace_raises_on_the_chip():
    record = {"device": {"platform": "tpu"}}
    with pytest.raises(RuntimeError) as err:
        run.attach_trace(record, None, on_chip=True,
                         workload="some-cell", kind="serve")
    for word in ("some-cell", "'serve'", "busy_s", "window_s"):
        assert word in str(err.value)
    # off the chip (the rehearsal) the line goes without them
    run.attach_trace(record, None, on_chip=False, workload="c", kind="serve")
    assert record == {"device": {"platform": "tpu"}}
    red = {"busy_s": 1.5, "window_s": 2.0, "device_ops": [["fusion", 1.0]],
           "idle_gaps": [["generation.decode_fetch", 0.4]]}
    run.attach_trace(record, red, on_chip=True, workload="c", kind="serve")
    assert record["device"] == {"platform": "tpu", "busy_s": 1.5,
                                "window_s": 2.0}
    assert set(record["breakdown"]) == {"device_ops", "idle_gaps"}


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


def test_a_served_token_altered_where_it_is_emitted_is_not_correct(
        capsys, monkeypatch):
    """The rest of a run with the timed path broken underneath: every
    request's second token is another one than the engine chose, as the
    stream and the client see it.  The reference, teacher-forced on what
    was served, does not give that token the engine's log-probability."""
    from chipbench.builders import transformer_lm
    from paddle_tpu.generation import engine

    emit = engine.RequestHandle._emit

    def altered(self, index, token, logprob=None):
        return emit(self, index, (token + 1) % 128 if index == 1 else token,
                    logprob)

    monkeypatch.setattr(engine.RequestHandle, "_emit", altered)
    record = run.run_cell("tiny-chat", 2 ** 31 + 9, 2.0, 0,
                          require_chip=False, benchmark=TINY)
    assert record["correct"] is False
    assert record["failed"] == 0        # every stream is whole and in range
    compared = record["checks"]["served_logprob_diff_max"]
    assert compared["limit"] == transformer_lm.LOGPROB_ATOL
    assert compared["value"] > 3 * compared["limit"]
    assert list(record)[-1] == "checks"     # the line's last key
    captured = capsys.readouterr()
    assert "[check] FAILED=" in captured.out
    assert captured.err.rstrip().splitlines()[-1].startswith(
        "[compared] served_logprob_diff_max = ")


def test_a_failed_check_gives_correct_false_not_an_exception(
        capsys, monkeypatch):
    from chipbench.builders import transformer_lm

    monkeypatch.setattr(transformer_lm, "LOGPROB_ATOL", -1.0)
    record = run.run_cell("tiny-chat", 3, 2.0, 0, require_chip=False,
                          benchmark=TINY)
    assert record["correct"] is False
    assert "[check] FAILED=" in capsys.readouterr().out
