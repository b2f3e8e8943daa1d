"""The yardstick's fixed parts: FLOPs, peaks, and BENCHMARK.json resolving
to files by the names it gives."""

import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import flops, peaks, run  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCHMARKS = ["BENCHMARK.json", "tests/chipbench/BENCHMARK_tiny.json"]


def test_bert_base_flops_equal_bench_py_and_are_14_5_tflop_a_step():
    import bench
    from paddle_tpu.fluid import dygraph

    config = run.load_json(REPO, "chipbench/configs/bert-base-pretrain.json")
    job = run.load_json(REPO, "chipbench/traffic/mlm-s512-b48-zero0.json")
    builder = __import__("chipbench.builders.bert", fromlist=["x"])
    with dygraph.guard():
        model = builder.build(config, 0)
        params = {k: v.data for k, v in model.state_dict().items()}
    shapes = {k: tuple(v.shape) for k, v in params.items()}
    want, trunk, _ = bench._flops_per_step(model.bert.cfg, params, 48, 512, 80)
    assert flops.bert_trunk_params(shapes) == trunk
    got = builder.flops_per_step(config, job, shapes)
    assert got == pytest.approx(want, rel=1e-12)
    assert got == pytest.approx(14.5e12, rel=0.01)


def test_attention_flops_closed_form():
    fwd = flops.attention_flops(batch=2, heads=3, seq=8, head_dim=4,
                                causal=False, backward=False)
    assert fwd == 4 * 2 * 3 * 8 * 8 * 4
    assert flops.attention_flops(batch=2, heads=3, seq=8, head_dim=4,
                                 causal=True, backward=True) == 1.5 * fwd


def test_peaks_know_the_v5e_and_refuse_an_unknown_chip():
    assert peaks.chip_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.chip_peaks("TPU v9 imaginary")


@pytest.mark.parametrize("benchmark", BENCHMARKS)
def test_every_name_in_the_benchmark_resolves_to_a_file(benchmark):
    bench_ = run.load_json(REPO, benchmark)
    assert set(bench_) == {"command", "paths", "run_seconds", "configs",
                           "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"]: m for m in bench_["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    cells = [w["name"] for w in bench_["workloads"]]
    for w in bench_["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        _, cell, config, traffic = run.load_cell(REPO, w["name"], benchmark)
        assert traffic["kind"] in ("train", "serve")
        __import__(config["builder"], fromlist=["x"])
        reported = [m for m in run.metrics_of(bench_, "end_to_end", w["name"])]
        assert len(reported) >= 2      # setup_s and one more
        assert run.metrics_of(bench_, "per_layer", w["name"])
    for c in bench_["configs"]:
        assert NAME.match(c["name"])
        assert any(c["file"].startswith(p + "/") for p in bench_["paths"])
        assert run.load_json(REPO, c["file"])["reduced"] == c["reduced"]
    for m in bench_["end_to_end"] + bench_["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in bench_["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench_["end_to_end"]:
        assert callable(run.load_reader(REPO, bench_, "end_to_end",
                                        m["name"]))
    for m in bench_["per_layer"]:
        assert callable(run.load_reader(REPO, bench_, "per_layer",
                                        m["name"]))
        moved = e2e[m["moves"]]
        assert set(m.get("workloads", cells)) <= set(
            moved.get("workloads", cells))
    four = sum(w["chips"] == 4 for w in bench_["workloads"])
    if benchmark == "BENCHMARK.json":
        assert four <= max(1, len(cells) // 4)
        assert len(json.dumps(bench_)) < 64 * 1024


def test_the_command_line_fails_off_the_chip_and_prints_no_result():
    bench_ = run.load_json(REPO, "BENCHMARK.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        bench_["command"] + ["--workload", bench_["workloads"][0]["name"],
                             "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert '"metrics"' not in proc.stdout


def test_the_command_line_has_no_tiny_or_cpu_switch():
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--help"], cwd=REPO,
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0
    options = set(re.findall(r"--[a-z-]+", proc.stdout))
    assert options == {"--help", "--workload", "--seed", "--seconds",
                       "--trace"}
