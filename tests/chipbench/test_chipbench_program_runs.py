"""The new per-layer metrics through the harness itself, at a tiny size on
the CPU (``require_chip=False``), with a benchmark file of their own
(``BENCHMARK_program.json``: the tiny configurations and traffic of
``BENCHMARK_tiny.json`` under cell names of its own, because a cell's name
is its run directory and another worker may be running the tiny cells, and
the twelve new metrics).  A CPU session has no device plane, so the
metrics read from the device trace are left out, not invented; those read
from the program's counters are there.  Nothing here asserts a time."""

import math
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import run  # noqa: E402

PROGRAM = "tests/chipbench/BENCHMARK_program.json"
SEED = 2 ** 31 + 11
COUNTERS = {
    "program-chat": {"front_admit_ms_p95.serve", "stream_lag_ms_p95.serve",
                  "queue_wait_ms_p95.serve", "sched_host_ms_p50.serve",
                  "sched_host_ms_max.serve"},
    "program-train-1dev": {"dispatch_ms_p50.train"},
}


@pytest.mark.parametrize("workload,seconds", [("program-chat", 3.0),
                                               ("program-train-1dev", 1.0)])
def test_traced_cell_reports_the_counters_and_no_device_number(
        workload, seconds, capsys):
    record = run.run_cell(workload, SEED, seconds, 1, require_chip=False,
                          benchmark=PROGRAM)
    assert record["correct"] is True, capsys.readouterr().out
    assert record["failed"] == 0
    kind = workload.split("-")[1] == "chat" and "serve" or "train"
    assert set(record["metrics"]) == COUNTERS[workload] | {
        "window_compilations." + kind}
    for name in COUNTERS[workload]:
        m = record["metrics"][name]
        assert m["unit"] == "ms" and math.isfinite(m["value"])
        assert m["value"] >= 0
    assert record["metrics"]["window_compilations." + kind]["value"] == 0
    if kind == "serve":
        assert record["metrics"]["sched_host_ms_max.serve"]["value"] >= \
            record["metrics"]["sched_host_ms_p50.serve"]["value"]


def test_the_program_benchmark_lists_the_new_metrics_as_the_real_one_does():
    bench = run.load_json(REPO, PROGRAM)
    real = {m["name"]: m for m in
            run.load_json(REPO, "BENCHMARK.json")["per_layer"]}
    mine = {m["name"]: m for m in bench["per_layer"]}
    assert set.union(*COUNTERS.values()) <= set(mine) <= set(real)
    for name, m in mine.items():
        assert {k: v for k, v in m.items() if k != "workloads"} == {
            k: v for k, v in real[name].items() if k != "workloads"}
        assert callable(run.load_reader(REPO, bench, "per_layer", name))
