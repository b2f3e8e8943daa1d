"""The block-diffusion serving builder (`chipbench/builders/sdar_moe.py`)
end to end at a tiny size on the CPU, beside `tiny-lm`: a configuration,
a traffic mix, a cell and `BENCHMARK_tiny_sdar.json`, all files of their
own; its fault tests; the hand counts of `moe_cost`; and the scope reader
on hand-made events.  Nothing here asserts a time."""

import importlib.util
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import moe_cost, run, scope_trace  # noqa: E402

TINY = "tests/chipbench/BENCHMARK_tiny_sdar.json"
CELL = "tiny-chat-blocks"
SEED = 2 ** 31 + 5
PUBLISHED = "chipbench/configs/sdar-30b-a3b-serve.json"


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(REPO, "chipbench", "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_block_diffusion_cell_end_to_end(capsys):
    record = run.run_cell(CELL, SEED, 2.0, 0, require_chip=False,
                          benchmark=TINY)
    out = capsys.readouterr().out
    json.dumps(record)
    assert record["correct"] is True, out
    assert record["failed"] == 0 and record["attempted"] > 0
    assert set(record["metrics"]) == {"setup_s", "itl_ms_p95",
                                      "latency_ms_per_token"}
    # float32 on both sides here: the served log-probabilities are the
    # reference's up to the order of float32 sums
    assert record["checks"]["served_logprob_diff_max"]["value"] < 1e-5
    assert "[after-window] reference_check_s=" in out


def test_block_diffusion_cell_traced_reports_its_counter_metrics(capsys):
    record = run.run_cell(CELL, SEED + 1, 2.0, 1, require_chip=False,
                          benchmark=TINY)
    assert record["correct"] is True, capsys.readouterr().out
    got = record["metrics"]
    # the CPU session has no device plane: the trace readers say nothing
    assert set(got) == {"window_compilations.serve",
                        "decode_step_ms_p50.serve", "prefill_ms_p50.serve",
                        "attn_walk_share.serve",
                        "denoise_passes_per_token.serve",
                        "moe_load_max_over_mean.serve"}
    assert got["window_compilations.serve"]["value"] == 0
    # 4 steps a block of 4 and a commit a block but the last: near 1.25
    # (a first block that a prompt's remainder shortens costs its commit
    # for fewer tokens; a window's edges cut requests anywhere)
    assert 0.9 <= got["denoise_passes_per_token.serve"]["value"] <= 1.6
    assert 1.0 <= got["moe_load_max_over_mean.serve"]["value"] <= 8.0


def test_a_token_revealed_at_another_pass_is_not_correct(capsys,
                                                         monkeypatch):
    """The timed path broken underneath: the engine reveals the RIGHTMOST
    masked position where the configuration states `sequential`, so
    every token is drawn at another pass than the reference scores it
    at (other neighbours are still masked).  Streams are whole and in
    range; `correct` is false."""
    import jax.numpy as jnp

    from chipbench.builders import sdar_moe
    from paddle_tpu.generation import engine

    def rightmost(masked, logprobs, count, rule, threshold):
        return masked & (jnp.cumsum(masked[:, ::-1], axis=1)[:, ::-1]
                         <= count)

    monkeypatch.setattr(engine, "choose_reveals", rightmost)
    record = run.run_cell(CELL, SEED + 2, 2.0, 0, require_chip=False,
                          benchmark=TINY)
    assert record["correct"] is False
    assert record["failed"] == 0
    compared = record["checks"]["served_logprob_diff_max"]
    assert compared["limit"] == sdar_moe.LOGPROB_ATOL
    assert compared["value"] > 2 * compared["limit"]
    assert "[check] FAILED=" in capsys.readouterr().out


def test_a_served_token_altered_where_it_is_emitted_is_not_correct(
        monkeypatch):
    from paddle_tpu.generation import engine

    emit = engine.RequestHandle._emit

    def altered(self, index, token, logprob=None):
        return emit(self, index, (token + 1) % 127 if index == 1 else token,
                    logprob)

    monkeypatch.setattr(engine.RequestHandle, "_emit", altered)
    record = run.run_cell(CELL, SEED + 3, 2.0, 0, require_chip=False,
                          benchmark=TINY)
    assert record["correct"] is False and record["failed"] == 0


def test_a_cache_of_other_heads_or_type_is_not_the_stated_precision(capsys):
    from chipbench.builders import sdar_moe

    config = run.load_json(REPO, "tests/chipbench/configs/tiny-sdar.json")
    cache = {"dtype": "float32", "kv_dtype": "float32", "heads": 2,
             "bytes": 10 ** 9}
    assert sdar_moe.holds_stated_precision(
        config, dict(cache, dtype="bfloat16", kv_dtype="bfloat16")) is False
    assert sdar_moe.holds_stated_precision(
        config, dict(cache, heads=8)) is False
    assert sdar_moe.holds_stated_precision(
        config, dict(cache, bytes=1000)) is False
    assert "[check] FAILED=" in capsys.readouterr().out


def test_the_builder_refuses_a_confidence_rule_and_an_unbuilt_key():
    from chipbench.builders import sdar_moe

    config = run.load_json(REPO, "tests/chipbench/configs/tiny-sdar.json")
    serving = dict(config["serving"], remasking="low_confidence_static")
    with pytest.raises(ValueError, match="sequential"):
        sdar_moe.reference_logprobs(None, dict(config, serving=serving),
                                    [], 16)
    with pytest.raises(ValueError, match="hidden_act"):
        sdar_moe.model_config(dict(config, hidden_act="gelu"))


def test_the_published_configuration_holds_the_published_widths():
    config = run.load_json(REPO, PUBLISHED)
    for key, value in {
            "hidden_size": 2048, "num_attention_heads": 32,
            "num_key_value_heads": 4, "head_dim": 128, "num_experts": 128,
            "num_experts_per_tok": 8, "moe_intermediate_size": 768,
            "vocab_size": 151936, "rope_theta": 1000000,
            "num_hidden_layers": 6}.items():
        assert config[key] == value
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 48}
    assert config["precision"] == {"weights": "bfloat16",
                                   "kv_cache": "bfloat16"}
    bench = run.load_json(REPO, "BENCHMARK.json")
    cells = [w for w in bench["workloads"]
             if w["config"] == "sdar-30b-a3b-serve"]
    assert [(w["name"], w["chips"]) for w in cells] == [
        ("sdar-30b-a3b-chat-steady", 1)]


def test_moe_cost_against_a_hand_count():
    """Six published layers, by hand.  An expert: 3 x 2048 x 768 x 2 B =
    9,437,184 B.  A layer's dense weights: q and o 2 x 2048 x 4096, k and
    v 2 x 2048 x 512, gains 256, norms 4096, router 262,144 parameters =
    19,140,864 x 2 B.  Head and final norm (151,936 + 1) x 2048 x 2 B.
    A cache row: 2 x 6 x 4 x 128 x 2 B = 12,288 B."""
    from chipbench.builders import sdar_moe

    config = run.load_json(REPO, PUBLISHED)
    assert moe_cost.expert_bytes(config) == 9437184
    assert moe_cost.dense_layer_bytes(config) == 2 * 19140864
    assert moe_cost.head_bytes(config) == 151937 * 2048 * 2
    assert moe_cost.cache_row_bytes(config) == 12288
    assert sdar_moe.n_params(config) == 4361055744      # the issue's count
    # every expert of every layer visited, no cache, no rows: the weights
    # less the embedding
    assert moe_cost.block_step_bytes(config, 6 * 128, 0, 0) == 2 * (
        4361055744 - 151936 * 2048)
    assert moe_cost.block_step_bytes(config, 0, 10, 4) - \
        moe_cost.block_step_bytes(config, 0, 0, 0) == 10 * 12288 + 4 * 4096
    assert moe_cost.experts_bytes(config, 100, 64) == \
        100 * 9437184 + 2 * 64 * 6 * 4096
    assert moe_cost.experts_flops(config, 1) == 2 * 6 * 8 * 3 * 2048 * 768


def test_the_rooflines_read_the_engines_counts():
    """Hand-made counters: 100 steps, 38,400 experts touched (64 a layer
    a step), 16 live slots of 500 rows; a 12 ms step then moves its bytes
    at 46.5% of the HBM peak."""
    config = run.load_json(REPO, PUBLISHED)

    def counter(value):
        return {"series": [{"labels": {}, "value": value}]}

    obs = {"config": config, "peaks": {"hbm_bytes_per_s": 819e9},
           "counters_before": {},
           "counters_after": {
               "generation_itl_ms": {"series": [
                   {"labels": {}, "count": 100, "sum": 1500.0, "p50": 15.0}]},
               "generation_moe_experts_touched_total": counter(38400),
               "generation_block_cache_rows_total": counter(800000),
               "generation_block_passes_total": counter(1600)}}
    step = reader("block_step_roofline.serve")
    assert moe_cost.step_means(obs) == (384.0, 8000.0, 16.0)
    assert step.read(obs) is None                   # untraced: no time
    need = moe_cost.block_step_bytes(config, 384, 8000, 64)
    assert need == 384 * 9437184 + 6 * 2 * 19140864 + 151937 * 4096 \
        + 8000 * 12288 + 64 * 4096
    assert 100.0 * need / 819e9 / 12e-3 == pytest.approx(46.5, abs=0.1)
    obs["counters_after"].pop("generation_moe_experts_touched_total")
    assert moe_cost.step_means(obs) is None     # a program that counts none


def test_scope_times_count_inside_the_named_programs_executions_only():
    paths = {"%fusion.1 = f32[] fusion(": "jit(step)/moe_experts/dot_general",
             "%fusion.2 = f32[] fusion(": "jit(step)/moe_router/top_k",
             "%fusion.3 = f32[] fusion(": "jit(step)/dot_general"}
    roots = {"%fusion.3 = f32[] fusion(": ["jit(step)/moe_experts/mul"]}
    ops = [("%fusion.1 = f32[] fusion(", 100, 160),     # inside run 1
           ("%fusion.2 = f32[] fusion(", 160, 170),
           ("%fusion.3 = f32[] fusion(", 170, 190),     # by its root
           ("%fusion.1 = f32[] fusion(", 300, 350),     # inside a prefill
           ("%fusion.1 = f32[] fusion(", 500, 540)]     # inside run 2
    modules = [("jit_generation_block_step(123)", 100, 200),
               ("jit_generation_prefill_64(7)", 300, 400),
               ("jit_generation_block_step(123)", 500, 600)]
    trace = {"ops": {0: ops}, "modules": {0: modules}, "paths": paths,
             "roots": roots}
    scopes, runs, each = scope_trace.by_scope(trace,
                                              "generation_block_step")
    assert runs == 2 and each == [100, 100]
    assert scopes == {"moe_experts": 60 + 20 + 40, "moe_router": 10}
    assert scope_trace.scope_of(
        "jit(f)/jit(main)/moe_experts/sampling/argmax") == "sampling"
    assert scope_trace.scope_of("jit(f)/moe_experts_x/dot") is None
    assert scope_trace.scope_ms_per_execution(
        {"trace": None}, "moe_experts", "generation_block_step") is None
