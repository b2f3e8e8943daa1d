"""paddle_tpu.tune — the measured compiler autotuner.

What must hold (ISSUE 11 acceptance):
  * determinism — the SECOND search of the same program+mesh+chip+jax
    is served entirely from the tuning cache: cache_hit, zero candidate
    compiles (asserted via the PR-4 ``xla_compilations_total``
    accumulator), same winner;
  * invalidation — a different jax version or chip spec re-opens the
    search (different cache key);
  * safety — a candidate broken by a seeded bad pass is EXCLUDED with
    the offending pass named, and is never compiled or timed;
  * usefulness — on a zoo workload the winner's measured step time is
    <= the measured default under the same harness, and where a known
    lever exists (bucket ladders, flash blocks) the winner is STRICTLY
    better.
"""

import json
import os
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import models, tune
from paddle_tpu.fluid import ir, layers
from paddle_tpu.observability import default_registry


def _compiles():
    return default_registry().counter(
        "xla_compilations_total",
        "XLA backend compilations (jax.monitoring)").value


def _conv_bn_relu():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("img", shape=[8, 16, 16, 16],
                        append_batch_size=False)
        c = layers.conv2d(x, num_filters=32, filter_size=3, padding=1,
                          data_format="NHWC")
        bn = layers.batch_norm(c, data_layout="NHWC")
        out = layers.relu(bn)
    return main, out


# ---------------------------------------------------------------------------
# candidate spaces
# ---------------------------------------------------------------------------


def test_default_pipelines_enumerate_registry():
    pipes = tune.default_pass_pipelines()
    assert [] in pipes                      # the baseline is never optional
    assert ["batch_norm_act_fuse"] in pipes
    assert ["dead_op_elimination"] in pipes


def test_flash_block_candidates_divisors_default_first():
    cands = tune.flash_block_candidates(512, 512)
    pairs = [(c.params["block_q"], c.params["block_k"]) for c in cands]
    assert pairs[0] == (512, 512)           # heuristic default leads
    assert set(pairs) == {(a, b) for a in (512, 256, 128)
                          for b in (512, 256, 128)}
    # non-divisible lengths restrict the grid
    assert all(c.params["block_q"] != 512
               for c in tune.flash_block_candidates(256, 512))


def test_ladder_candidates_default_exact_and_quantile_cap():
    cands = tune.ladder_candidates(32, traffic=[3, 3, 7])
    labels = [c.label for c in cands]
    assert labels[0].startswith("ladder-pow2")
    exact = next(c for c in cands if "exact" in c.label)
    assert exact.params["batch_buckets"] == [3, 7, 32]
    # >8 distinct sizes: quantile-capped, max_batch always present
    many = tune.ladder_candidates(64, traffic=list(range(1, 40)))
    exact = next(c for c in many if "exact" in c.label)
    assert len(exact.params["batch_buckets"]) <= 9
    assert exact.params["batch_buckets"][-1] == 64


class _StubMesh:
    axis_names = ("dp", "mp")

    def __init__(self, sizes):
        self.shape = dict(zip(self.axis_names, sizes))

    def axis_size(self, name):
        return self.shape[name]


def test_sharding_candidates_need_mesh_and_big_weights():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[8, 512], append_batch_size=False)
        w = main.global_block.create_parameter("tn.big", shape=[512, 2048])
        layers.matmul(x, w)
    assert tune.sharding_candidates(main, None) == []
    assert tune.sharding_candidates(main, _StubMesh((1, 1))) == []
    cands = tune.sharding_candidates(main, _StubMesh((1, 4)),
                                     min_bytes=1 << 20)
    assert len(cands) == 1
    assert cands[0].params["sharding"] == {
        "axis": "mp", "vars": ["tn.big"], "dim": -1}
    # below the size floor nothing shards
    assert tune.sharding_candidates(main, _StubMesh((1, 4)),
                                    min_bytes=1 << 30) == []


# ---------------------------------------------------------------------------
# tuning cache
# ---------------------------------------------------------------------------


def test_cache_roundtrip_and_corruption(tmp_path):
    cache = tune.TuningCache(str(tmp_path))
    parts = tune.cache_key_parts("w1", platform="cpu", jax_version="1.0")
    assert cache.get(parts) is None
    path = cache.put(parts, {"kind": "program", "params": {"pipeline": []}},
                     extra={"default_s": 1.0})
    entry = cache.get(parts)
    assert entry["winner"]["params"] == {"pipeline": []}
    assert entry["default_s"] == 1.0
    # corruption is a miss, never an error
    with open(path, "w") as f:
        f.write("{not json")
    assert cache.get(parts) is None
    cache.put(parts, {"kind": "program", "params": {}})
    assert cache.invalidate(parts) is True
    assert cache.get(parts) is None


def test_cache_key_sensitivity(tmp_path):
    base = dict(platform="cpu", jax_version="1.0")
    k0 = tune.TuningCache.key(tune.cache_key_parts("w", **base))
    assert tune.TuningCache.key(tune.cache_key_parts("w", **base)) == k0
    assert tune.TuningCache.key(
        tune.cache_key_parts("w", platform="tpu", jax_version="1.0")) != k0
    assert tune.TuningCache.key(
        tune.cache_key_parts("w", platform="cpu", jax_version="2.0")) != k0
    assert tune.TuningCache.key(
        tune.cache_key_parts("w", mesh=_StubMesh((2, 4)), **base)) != k0


def test_cache_rejects_key_part_drift(tmp_path):
    """An entry whose stored key_parts do not match the request is a
    miss — the filename alone is never trusted."""
    cache = tune.TuningCache(str(tmp_path))
    parts = tune.cache_key_parts("w1", platform="cpu", jax_version="1.0")
    path = cache.put(parts, {"kind": "program", "params": {}})
    with open(path) as f:
        entry = json.load(f)
    entry["key_parts"]["jax"] = "drifted"
    with open(path, "w") as f:
        json.dump(entry, f)
    assert cache.get(parts) is None


# ---------------------------------------------------------------------------
# program search: determinism, invalidation, exclusion, pruning, budget
# ---------------------------------------------------------------------------


def test_search_cache_determinism_zero_recompiles(tmp_path):
    main, out = _conv_bn_relu()
    rep1 = tune.search(main, [out.name], cache_dir=str(tmp_path), k=2,
                       warmup=1)
    assert not rep1.cache_hit and rep1.cache_stored
    assert rep1.winner is not None and rep1.default_s is not None
    # the winner is never worse than the measured default (argmin over a
    # space that always contains the default)
    assert rep1.winner.measured_s <= rep1.default_s + 1e-12

    before = _compiles()
    rep2 = tune.search(main, [out.name], cache_dir=str(tmp_path), k=2,
                       warmup=1)
    assert rep2.cache_hit
    assert _compiles() == before, \
        "a cache hit must compile no candidates"
    assert rep2.winner.params["pipeline"] == rep1.winner.params["pipeline"]
    assert rep2.results == []               # nothing enumerated either
    # the winner re-applies cleanly (and is re-verified on apply)
    from paddle_tpu import analysis

    tuned = tune.tuned_program(main, rep2)
    analysis.assert_program_valid(tuned)


def test_search_cache_invalidated_by_jax_and_chip(tmp_path):
    from paddle_tpu.analysis.perf import ChipSpec

    main, out = _conv_bn_relu()
    kw = dict(cache_dir=str(tmp_path), k=1, warmup=1)
    rep1 = tune.search(main, [out.name], jax_version="9.9.9", **kw)
    assert not rep1.cache_hit
    assert tune.search(main, [out.name], jax_version="9.9.9",
                       **kw).cache_hit
    # a jax upgrade re-opens the search
    rep3 = tune.search(main, [out.name], jax_version="10.0.0", **kw)
    assert not rep3.cache_hit
    # so does a different chip spec
    rep4 = tune.search(main, [out.name], jax_version="9.9.9",
                       chip=ChipSpec("other-chip", 1e12, 1e11), **kw)
    assert not rep4.cache_hit


class _BreakerPass(ir.Pass):
    """Deletes a mid-chain producer: verification must catch it."""

    name = "tune_test_breaker"

    def apply(self, program):
        del program.global_block.ops[1]
        return program


def test_broken_pass_candidate_excluded_with_name(tmp_path):
    main, out = _conv_bn_relu()
    space = tune.SearchSpace(
        pipelines=[[], ["batch_norm_act_fuse"], [_BreakerPass()]],
        donate=(True,), sharding=False)
    rep = tune.search(main, [out.name], space=space,
                      cache_dir=str(tmp_path), k=1, warmup=1)
    broken = [r for r in rep.results if r.status == "excluded"]
    assert len(broken) == 1
    assert "tune_test_breaker" in broken[0].error
    # excluded means excluded: never measured, never the winner
    assert broken[0].measured_s is None and broken[0].compiles is None
    assert rep.winner.params["pipeline"] != ["tune_test_breaker"]
    # and the original program was never mutated
    assert [o.type for o in main.global_block.ops][-1] == "relu"


class _OpInflaterPass(ir.Pass):
    """Appends N redundant heavy matmuls: statically, obviously worse."""

    name = "tune_test_inflater"

    def apply(self, program):
        block = program.global_block
        src = None
        for op in block.ops:
            if op.type == "conv2d":
                src = op.all_output_names()[0]
        v = block._find_var_recursive(src)
        for i in range(20):
            name = "inflate.%d" % i
            block.create_var(name=name, shape=v.shape, dtype=v.dtype)
            block.append_op(
                type="scale", inputs={"X": [src]}, outputs={"Out": [name]},
                attrs={"scale": 1.0, "bias": 0.0,
                       "bias_after_scale": True})
        # keep them alive so dead-op hygiene can't undo the bloat
        block.append_op(
            type="sum", inputs={"X": ["inflate.%d" % i for i in range(20)]},
            outputs={"Out": [src + ".bloat"]}, attrs={})
        out = block.create_var(name=src + ".bloat", shape=v.shape,
                               dtype=v.dtype)
        del out
        program._bump()
        return program


def test_statically_worse_candidate_pruned_never_compiled(tmp_path):
    main, out = _conv_bn_relu()
    space = tune.SearchSpace(
        pipelines=[[], [_OpInflaterPass()]], donate=(True,),
        sharding=False)
    rep = tune.search(main, [out.name], space=space,
                      cache_dir=str(tmp_path), k=1, warmup=1,
                      prune_ratio=1.2)
    pruned = [r for r in rep.results if r.status == "pruned"]
    assert len(pruned) == 1
    assert pruned[0].params["pipeline"] == ["tune_test_inflater"]
    assert pruned[0].measured_s is None     # never compiled, never timed
    assert pruned[0].est_time_s > rep.winner.est_time_s


def test_budget_limits_search_but_baseline_always_runs(tmp_path):
    main, out = _conv_bn_relu()
    rep = tune.search(main, [out.name], cache_dir=str(tmp_path), k=1,
                      warmup=1, budget_s=0.0)
    by_status = rep.counts()
    assert by_status.get("timed") == 1      # the measured baseline
    assert by_status.get("skipped_budget", 0) >= 1
    assert rep.winner.params["pipeline"] == []


def test_dead_op_elimination_keeps_fetches():
    """The tuner protects the fetch list in every pipeline it tries —
    dead-op elimination must not delete the chain feeding the fetch."""
    main, out = _conv_bn_relu()
    rep = tune.search(main, [out.name], use_cache=False, k=1, warmup=1)
    dce = [r for r in rep.results
           if r.params.get("pipeline") == ["dead_op_elimination"]]
    assert dce and dce[0].status == "timed"
    assert rep.winner.params.get("keep") == [out.name]


# ---------------------------------------------------------------------------
# zoo end-to-end (acceptance): winner <= default, exclusion, cache
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_zoo_resnet_search_winner_not_worse_and_cached(tmp_path):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("img", shape=[2, 3, 32, 32],
                        append_batch_size=False)
        out = models.resnet18(num_classes=5)(x)
    rep = tune.search(main, [out.name], cache_dir=str(tmp_path), k=3,
                      warmup=1)
    assert rep.winner is not None
    assert rep.winner.measured_s <= rep.default_s + 1e-12
    assert rep.winner.compiles is None or rep.winner.compiles >= 0
    d = rep.to_dict()
    assert d["schema_version"] == 1
    assert d["winner"]["status"] == "timed"
    assert all(c["status"] in ("timed", "pruned", "excluded",
                               "skipped_budget") for c in d["candidates"])
    # second run: pure cache, zero compiles, applies cleanly
    before = _compiles()
    rep2 = tune.search(main, [out.name], cache_dir=str(tmp_path), k=3,
                       warmup=1)
    assert rep2.cache_hit and _compiles() == before
    from paddle_tpu import analysis

    analysis.assert_program_valid(tune.tuned_program(main, rep2))


# ---------------------------------------------------------------------------
# flash-attention block search
# ---------------------------------------------------------------------------


def test_search_flash_blocks_winner_and_cache(tmp_path):
    shape = (1, 2, 256, 64)
    rep = tune.search_flash_blocks(shape, interpret=True, k=2, warmup=1,
                                   cache_dir=str(tmp_path))
    assert rep.winner is not None
    bq, bk = rep.winner.params["block_q"], rep.winner.params["block_k"]
    assert bq in (128, 256) and bk in (128, 256)
    assert rep.winner.measured_s <= rep.default_s + 1e-12
    before = _compiles()
    rep2 = tune.search_flash_blocks(shape, interpret=True, k=2, warmup=1,
                                    cache_dir=str(tmp_path))
    assert rep2.cache_hit and _compiles() == before
    assert rep2.winner.params == rep.winner.params
    # the winner drives the kernel (correctness is test_pallas_attention's
    # job; here: the tuned call accepts the tuned blocks)
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.attention import flash_attention

    q = jnp.zeros((1, 2, 256, 64), jnp.float32)
    flash_attention(q, q, q, interpret=True, block_q=bq, block_k=bk)


# ---------------------------------------------------------------------------
# bucket-ladder search: a known lever must win STRICTLY
# ---------------------------------------------------------------------------


class _RowCostRunner:
    """Deterministic service-time model: cost grows with padded rows —
    the shape of the real padding tax, without timer flakiness."""

    def __init__(self, per_row_s=4e-4):
        self.per_row_s = per_row_s
        self.calls = []

    def run(self, feed):
        rows = next(iter(feed.values())).shape[0]
        self.calls.append(rows)
        time.sleep(self.per_row_s * rows)
        return [np.zeros((rows, 2), np.float32)]


def test_ladder_search_exact_ladder_strictly_beats_pow2(tmp_path):
    runner = _RowCostRunner()
    traffic = [3] * 12   # every request is 3 rows: pow2 pads to 4
    rep = tune.search_bucket_ladder(
        runner, {"x": np.zeros((1, 8), np.float32)}, traffic,
        max_batch=8, workload="rowcost", k=2, cache_dir=str(tmp_path))
    assert rep.winner.params["batch_buckets"][0] == 3
    assert rep.winner.measured_s < rep.default_s   # strictly better
    before_calls = len(runner.calls)
    rep2 = tune.search_bucket_ladder(
        runner, {"x": np.zeros((1, 8), np.float32)}, traffic,
        max_batch=8, workload="rowcost", k=2, cache_dir=str(tmp_path))
    assert rep2.cache_hit
    assert len(runner.calls) == before_calls   # nothing re-measured


def test_ladder_search_without_workload_does_not_cache(tmp_path):
    runner = _RowCostRunner(per_row_s=1e-5)
    rep = tune.search_bucket_ladder(
        runner, {"x": np.zeros((1, 4), np.float32)}, [2, 2], max_batch=4,
        k=1, cache_dir=str(tmp_path))
    assert rep.cache_path is None and not rep.cache_stored
    assert os.listdir(str(tmp_path)) == []


def test_inference_server_autotune_adopts_winner_ladder(tmp_path):
    from paddle_tpu.inference.server import InferenceServer

    runner = _RowCostRunner()
    server = InferenceServer(runner, max_batch=8, name="tune-test")
    try:
        rep = server.autotune(
            {"x": np.zeros((1, 8), np.float32)}, traffic=[3] * 12,
            workload="server-rowcost", k=2, cache_dir=str(tmp_path))
        assert rep.winner is not None
        assert server._batch_buckets == rep.winner.params["batch_buckets"]
        assert server._batch_buckets[0] == 3
        # the adopted ladder was AOT-warmed through the predictor
        assert 3 in runner.calls
    finally:
        server.unregister_metrics()


# ---------------------------------------------------------------------------
# step-variant search
# ---------------------------------------------------------------------------


def test_search_step_orders_and_caches(tmp_path):
    costs = {"default": 0.010, "remat": 0.015, "fast": 0.005}
    built = []

    def build_and_time(params):
        built.append(params["name"])
        return costs[params["name"]]

    variants = [(n, {"name": n}) for n in ("default", "remat", "fast")]
    rep = tune.search_step(build_and_time, variants, workload="steptest",
                           cache_dir=str(tmp_path))
    assert rep.winner.params["name"] == "fast"
    assert rep.default_s == 0.010
    assert rep.speedup == pytest.approx(2.0)
    rep2 = tune.search_step(build_and_time, variants, workload="steptest",
                            cache_dir=str(tmp_path))
    assert rep2.cache_hit
    assert built == ["default", "remat", "fast"]   # nothing rebuilt
    # a variant that dies is excluded, not fatal
    def dying(params):
        if params["name"] == "remat":
            raise RuntimeError("OOM")
        return costs[params["name"]]

    rep3 = tune.search_step(dying, variants, workload="steptest2",
                            cache_dir=str(tmp_path))
    assert rep3.counts() == {"timed": 2, "excluded": 1}
    assert rep3.winner.params["name"] == "fast"


# ---------------------------------------------------------------------------
# CompiledProgram.with_autotune through the Executor
# ---------------------------------------------------------------------------


def test_compiled_program_with_autotune_runs_and_caches(tmp_path):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4, 16, 8, 8], append_batch_size=False)
        c = layers.conv2d(x, num_filters=8, filter_size=3, padding=1)
        bn = layers.batch_norm(c)
        out = layers.relu(bn)
    exe = fluid.Executor()
    exe.run(startup, feed={}, fetch_list=[])
    feed = {"x": np.random.RandomState(0).randn(
        4, 16, 8, 8).astype(np.float32)}
    ref = exe.run(main, feed=feed, fetch_list=[out])

    compiled = fluid.CompiledProgram(main).with_autotune(
        cache_dir=str(tmp_path), k=1)
    got = exe.run(compiled, feed=feed, fetch_list=[out])
    np.testing.assert_allclose(got[0], ref[0], rtol=1e-5, atol=1e-5)
    rep = compiled._tune_report
    assert rep is not None and not rep.cache_hit
    assert rep.winner.measured_s <= rep.default_s + 1e-12
    # the tuned clone is reused, not re-searched, on later runs — the
    # SAME object, so the executor's id-keyed jit cache never retraces
    (tuned_first,) = compiled._tuned_programs.values()
    exe.run(compiled, feed=feed, fetch_list=[out])
    assert list(compiled._tuned_programs.values()) == [tuned_first]

    # a FRESH facade (think: restarted process) hits the tuning cache
    compiled2 = fluid.CompiledProgram(main).with_autotune(
        cache_dir=str(tmp_path), k=1)
    before = _compiles()
    got2 = exe.run(compiled2, feed=feed, fetch_list=[out])
    np.testing.assert_allclose(got2[0], ref[0], rtol=1e-5, atol=1e-5)
    assert compiled2._tune_report.cache_hit
    # the only compile allowed is the winner's own executor lowering —
    # zero candidate compiles (the winner equals a pipeline the executor
    # may still have to build once for THIS executor's cache)
    assert _compiles() - before <= 1


# ---------------------------------------------------------------------------
# operator CLI
# ---------------------------------------------------------------------------


def _load_tool(name):
    import importlib.util

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(repo, "tools", "%s.py" % name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_autotune_cli_program_json_roundtrip(tmp_path, capsys):
    at = _load_tool("autotune")
    main, out = _conv_bn_relu()
    path = str(tmp_path / "prog.json")
    with open(path, "w") as f:
        f.write(main.to_json())
    cache = str(tmp_path / "cache")

    assert at.main([path, "--fetch", out.name, "--k", "1",
                    "--cache-dir", cache, "--json"]) == 0
    d = json.loads(capsys.readouterr().out)
    assert d["schema_version"] == 1
    assert d["kind"] == "program" and d["cache_hit"] is False
    assert d["winner"]["status"] == "timed"
    assert d["counts"].get("timed", 0) >= 2
    statuses = {c["status"] for c in d["candidates"]}
    assert statuses <= {"timed", "pruned", "excluded", "skipped_budget"}

    # second invocation: served from cache, text mode says HIT
    assert at.main([path, "--fetch", out.name, "--k", "1",
                    "--cache-dir", cache]) == 0
    assert "cache: HIT" in capsys.readouterr().out

    # unreadable model -> rc 1
    assert at.main([str(tmp_path / "nope.json"), "--fetch", "x"]) == 1
    capsys.readouterr()


def test_autotune_cli_flash_mode(tmp_path, capsys):
    at = _load_tool("autotune")
    assert at.main(["--flash", "1,2,128,64", "--k", "1",
                    "--cache-dir", str(tmp_path / "c"), "--json"]) == 0
    d = json.loads(capsys.readouterr().out)
    assert d["kind"] == "flash_blocks"
    assert d["winner"]["params"]["block_q"] == 128
    # malformed shape -> rc 1
    assert at.main(["--flash", "1,2,128"]) == 1
    capsys.readouterr()


def test_autotune_cli_reports_excluded_pass_by_name(tmp_path, capsys):
    """The acceptance loop end to end through the operator CLI: a
    registered-but-broken pass in a --pipelines candidate shows up in
    the --json report as excluded WITH the pass named, and the healthy
    winner still emerges."""
    at = _load_tool("autotune")

    @ir.register_pass
    class _CliBreakerPass(ir.Pass):
        name = "tune_cli_breaker"

        def apply(self, program):
            del program.global_block.ops[1]
            return program

    try:
        main, out = _conv_bn_relu()
        path = str(tmp_path / "prog.json")
        with open(path, "w") as f:
            f.write(main.to_json())
        assert at.main([path, "--fetch", out.name, "--k", "1",
                        "--cache-dir", str(tmp_path / "c"), "--json",
                        "--pipelines",
                        ";batch_norm_act_fuse;tune_cli_breaker"]) == 0
        d = json.loads(capsys.readouterr().out)
        excluded = [c for c in d["candidates"]
                    if c["status"] == "excluded"]
        assert len(excluded) == 1
        assert excluded[0]["params"]["pipeline"] == ["tune_cli_breaker"]
        assert "tune_cli_breaker" in excluded[0]["error"]
        assert excluded[0]["measured_s"] is None
        assert d["winner"]["status"] == "timed"
        assert d["winner"]["params"]["pipeline"] != ["tune_cli_breaker"]
    finally:
        ir._PASS_REGISTRY.pop("tune_cli_breaker", None)


# ---------------------------------------------------------------------------
# cache-identity hardening (review findings): fetch set, flash grid /
# interpret mode, ladder feed contract, and excluded-default honesty
# ---------------------------------------------------------------------------


def test_different_fetch_set_is_a_different_workload(tmp_path):
    """A winner searched (and DCE-keep-protected) for one fetch set must
    not serve a different fetch set from the cache — a cached dead-op
    pipeline would delete the new fetch's producer."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4, 8], append_batch_size=False)
        a = layers.relu(x)
        b = layers.sigmoid(x)
    kw = dict(cache_dir=str(tmp_path), k=1, warmup=1)
    rep1 = tune.search(main, [a.name], **kw)
    assert not rep1.cache_hit
    # same program, superset fetch: MISS, and the tuned clone keeps both
    rep2 = tune.search(main, [a.name, b.name], **kw)
    assert not rep2.cache_hit
    tuned = tune.tuned_program(main, rep2)
    produced = {n for op in tuned.global_block.ops
                for n in op.all_output_names()}
    assert a.name in produced and b.name in produced
    # and the original fetch set still hits its own entry
    assert tune.search(main, [a.name], **kw).cache_hit
    # belt-and-braces: tuned_program(fetch_list=...) re-binds "keep"
    tuned2 = tune.tuned_program(main, rep1, fetch_list=[a.name, b.name])
    produced2 = {n for op in tuned2.global_block.ops
                 for n in op.all_output_names()}
    assert b.name in produced2


def test_flash_grid_and_interpret_are_cache_identity(tmp_path):
    shape = (1, 1, 256, 64)
    kw = dict(interpret=True, k=1, warmup=1, cache_dir=str(tmp_path))
    rep = tune.search_flash_blocks(shape, **kw)
    assert not rep.cache_hit
    # a constrained grid is a different workload: re-search, and the
    # winner honors the constraint
    rep2 = tune.search_flash_blocks(shape, grid=(128,), **kw)
    assert not rep2.cache_hit
    assert rep2.winner.params == {"block_q": 128, "block_k": 128}
    # unconstrained call still hits its own entry
    assert tune.search_flash_blocks(shape, **kw).cache_hit


def test_ladder_feed_contract_is_cache_identity(tmp_path):
    runner = _RowCostRunner(per_row_s=1e-5)
    example = {"x": np.zeros((1, 8), np.float32)}
    kw = dict(max_batch=8, workload="contract", k=1,
              cache_dir=str(tmp_path))
    rep = tune.search_bucket_ladder(runner, example, [2, 2], **kw)
    assert not rep.cache_hit
    rep2 = tune.search_bucket_ladder(
        runner, example, [2, 2], ragged_dims={"x": {1: [4, 8]}}, **kw)
    assert not rep2.cache_hit        # different feed contract: re-search
    assert tune.search_bucket_ladder(runner, example, [2, 2],
                                     **kw).cache_hit


def test_excluded_default_is_not_impersonated(tmp_path):
    """When the default variant itself dies, default_s/speedup must be
    None — not whichever candidate happened to time first."""
    def build_and_time(params):
        if params["name"] == "default":
            raise RuntimeError("default OOM")
        return {"remat": 0.015, "fast": 0.005}[params["name"]]

    variants = [(n, {"name": n}) for n in ("default", "remat", "fast")]
    rep = tune.search_step(build_and_time, variants,
                           workload="nodefault", cache_dir=str(tmp_path))
    assert rep.winner.params["name"] == "fast"
    assert rep.default_s is None and rep.speedup is None
    assert rep.counts() == {"excluded": 1, "timed": 2}


def test_chip_spec_in_non_program_cache_keys(tmp_path, monkeypatch):
    """flash/ladder/step keys must carry the resolved chip spec (the
    cache contract): a different PADDLE_TPU_PEAK_FLOPS — how a mixed
    fleet distinguishes generations — re-opens the search."""
    shape = (1, 1, 128, 64)
    kw = dict(interpret=True, k=1, warmup=1, cache_dir=str(tmp_path))
    assert not tune.search_flash_blocks(shape, **kw).cache_hit
    assert tune.search_flash_blocks(shape, **kw).cache_hit
    monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "9e13")
    monkeypatch.setenv("PADDLE_TPU_HBM_BW", "5e11")
    assert not tune.search_flash_blocks(shape, **kw).cache_hit


def test_feed_dtype_in_program_workload(tmp_path):
    main, out = _conv_bn_relu()
    kw = dict(cache_dir=str(tmp_path), k=1, warmup=1)
    spec32 = {"img": ((8, 16, 16, 16), "float32")}
    assert not tune.search(main, [out.name], feed_specs=spec32,
                           **kw).cache_hit
    # ndarray-valued specs hash shape AND dtype
    arr32 = {"img": np.zeros((8, 16, 16, 16), np.float32)}
    assert tune.search(main, [out.name], feed_specs=arr32, **kw).cache_hit
    arr16 = {"img": np.zeros((8, 16, 16, 16), np.float16)}
    assert not tune.search(main, [out.name], feed_specs=arr16,
                           **kw).cache_hit


def test_ladder_search_clamps_oversize_traffic(tmp_path):
    """Traffic entries beyond max_batch must not compile buckets the
    serving path can never dispatch."""
    runner = _RowCostRunner(per_row_s=1e-5)
    rep = tune.search_bucket_ladder(
        runner, {"x": np.zeros((1, 4), np.float32)}, [2, 64],
        max_batch=8, workload="oversize", k=1, cache_dir=str(tmp_path))
    assert max(runner.calls) <= 8
    for r in rep.results:
        if r.status == "timed":
            assert all(int(b) <= 8 for b in r.detail["per_bucket_s"])


def test_executor_autotune_memo_keys_on_feed_shapes(tmp_path):
    """A pipeline tuned at one batch size must not silently serve a
    different batch size — and alternating shapes must reuse STABLE
    clone objects (no per-run re-clone)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[-1, 8], append_batch_size=False)
        out = layers.relu(layers.fc(x, 4))
    exe = fluid.Executor()
    exe.run(startup, feed={}, fetch_list=[])
    compiled = fluid.CompiledProgram(main).with_autotune(
        cache_dir=str(tmp_path), k=1)
    f1 = {"x": np.zeros((2, 8), np.float32)}
    f2 = {"x": np.zeros((16, 8), np.float32)}
    exe.run(compiled, feed=f1, fetch_list=[out])
    exe.run(compiled, feed=f2, fetch_list=[out])
    assert len(compiled._tuned_programs) == 2   # per-shape entries
    before = dict(compiled._tuned_programs)
    exe.run(compiled, feed=f1, fetch_list=[out])
    exe.run(compiled, feed=f2, fetch_list=[out])
    # same objects reused: the executor's id-keyed jit cache stays warm
    assert compiled._tuned_programs == before


def test_server_autotune_incumbent_ladder_competes(tmp_path):
    """A hand-tuned server ladder is always a candidate: autotune can
    only keep or beat the incumbent, never regress it unmeasured."""
    from paddle_tpu.inference.server import InferenceServer

    # 2 ms a row: a padded row has to outweigh a host whose other cores
    # run the rest of the suite (at 0.4 ms a sleep's jitter can hide it)
    runner = _RowCostRunner(per_row_s=2e-3)
    incumbent = [5, 8]      # hand-tuned; distinct from every enumerated
    server = InferenceServer(runner, max_batch=8,  # candidate ladder
                             batch_buckets=list(incumbent),
                             name="tune-incumbent")
    try:
        rep = server.autotune(
            {"x": np.zeros((1, 8), np.float32)}, traffic=[3] * 12,
            workload="incumbent", k=2, cache_dir=str(tmp_path))
        labels = {r.label for r in rep.results}
        assert any("extra" in l for l in labels), labels
        # the incumbent serves bucket 3 exactly; the adopted ladder must
        # serve size-3 traffic at bucket 3 too (keep-or-beat)
        from paddle_tpu.inference.batching import pick_bucket

        assert pick_bucket(3, server._batch_buckets) == 3
    finally:
        server.unregister_metrics()


def test_flash_constrained_grid_reports_no_false_default(tmp_path):
    """When the grid excludes the heuristic default, default_s is None —
    the report never cites another candidate as 'default'."""
    rep = tune.search_flash_blocks(
        (1, 1, 512, 64), grid=(256, 128), interpret=True, k=1, warmup=1,
        cache_dir=str(tmp_path))
    assert rep.winner is not None
    assert rep.default_s is None and rep.speedup is None


def test_executor_autotune_memo_never_wholesale_clears(tmp_path):
    """Cycling >32 feed shapes must not evict the live entries' object
    identity wholesale (the jit cache keys on id(program))."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[-1, 4], append_batch_size=False)
        out = layers.relu(layers.fc(x, 2))
    exe = fluid.Executor()
    exe.run(startup, feed={}, fetch_list=[])
    compiled = fluid.CompiledProgram(main).with_autotune(
        cache_dir=str(tmp_path), k=1,
        space=tune.SearchSpace(pipelines=[[]], donate=(True,),
                               sharding=False))
    for b in range(1, 35):
        exe.run(compiled, feed={"x": np.zeros((b, 4), np.float32)},
                fetch_list=[out])
    assert len(compiled._tuned_programs) <= 32
    # the most recent entries survived (no wholesale clear)
    survivors = {k[2][0][1][0] for k in compiled._tuned_programs}
    assert 34 in survivors


def test_candidate_space_is_cache_identity(tmp_path):
    """A winner from one pipeline space must not answer a search over a
    different space — and a space containing configured Pass INSTANCES
    never touches the cache at all (not reconstructible later)."""
    main, out = _conv_bn_relu()
    kw = dict(cache_dir=str(tmp_path), k=1, warmup=1)
    s1 = tune.SearchSpace(pipelines=[[]], donate=(True,), sharding=False)
    assert not tune.search(main, [out.name], space=s1, **kw).cache_hit
    assert tune.search(main, [out.name], space=s1, **kw).cache_hit
    # a wider names-only space re-opens the search
    s2 = tune.SearchSpace(pipelines=[[], ["batch_norm_act_fuse"]],
                          donate=(True,), sharding=False)
    assert not tune.search(main, [out.name], space=s2, **kw).cache_hit
    # an instance-bearing space bypasses the cache entirely
    before = sorted(os.listdir(str(tmp_path)))
    s3 = tune.SearchSpace(pipelines=[[], [_BreakerPass()]],
                          donate=(True,), sharding=False)
    rep = tune.search(main, [out.name], space=s3, **kw)
    assert not rep.cache_hit and not rep.cache_stored
    assert sorted(os.listdir(str(tmp_path))) == before


def test_configured_pass_instances_do_not_collapse(tmp_path):
    """Two differently-.set() instances of the SAME registered pass are
    distinct candidates: each is applied and measured on its own clone,
    and the winner re-materializes from its measured instance."""
    applied = []

    @ir.register_pass
    class _KnobPass(ir.Pass):
        name = "tune_test_knob"

        def apply(self, program):
            applied.append(self.get("knob"))
            return program

    try:
        main, out = _conv_bn_relu()
        p1 = ir.get_pass("tune_test_knob").set("knob", 1)
        p2 = ir.get_pass("tune_test_knob").set("knob", 2)
        space = tune.SearchSpace(pipelines=[[], [p1], [p2]],
                                 donate=(True,), sharding=False)
        rep = tune.search(main, [out.name], space=space, use_cache=False,
                          k=1, warmup=1)
        # both configurations were actually applied (no dedup collapse)
        assert applied.count(1) == 1 and applied.count(2) == 1
        assert rep.counts()["timed"] == 3
        # the winner re-applies its OWN instance (attrs preserved)
        applied.clear()
        tune.tuned_program(main, rep)
        if rep.winner.params["pipeline"] == ["tune_test_knob"]:
            assert applied in ([1], [2])
    finally:
        ir._PASS_REGISTRY.pop("tune_test_knob", None)


def test_step_variant_set_is_cache_identity(tmp_path):
    costs = {"default": 0.01, "fast": 0.005, "faster": 0.003}

    def bt(params):
        return costs[params["name"]]

    v2 = [(n, {"name": n}) for n in ("default", "fast")]
    v3 = [(n, {"name": n}) for n in ("default", "fast", "faster")]
    kw = dict(workload="varset", cache_dir=str(tmp_path))
    assert not tune.search_step(bt, v2, **kw).cache_hit
    assert tune.search_step(bt, v2, **kw).cache_hit
    # a new variant re-opens the search and can win
    rep = tune.search_step(bt, v3, **kw)
    assert not rep.cache_hit
    assert rep.winner.params["name"] == "faster"


def test_ladder_cache_hits_on_proportional_traffic(tmp_path):
    """A restarted server tunes against a longer but proportionally
    identical traffic log: same distribution, same cache entry."""
    runner = _RowCostRunner(per_row_s=1e-5)
    example = {"x": np.zeros((1, 4), np.float32)}
    kw = dict(max_batch=8, workload="prop", k=1, cache_dir=str(tmp_path))
    assert not tune.search_bucket_ladder(
        runner, example, [1, 1, 2], **kw).cache_hit
    assert tune.search_bucket_ladder(
        runner, example, [1, 1, 1, 1, 2, 2], **kw).cache_hit
    # a genuinely shifted mix re-opens the search
    assert not tune.search_bucket_ladder(
        runner, example, [1, 2, 2], **kw).cache_hit


# ---------------------------------------------------------------------------
# PR 11: fused-GEMM block search + the new passes in the default space
# ---------------------------------------------------------------------------


def test_default_pipelines_include_fusion_passes():
    pipes = tune.default_pass_pipelines()
    assert ["matmul_bias_act_fuse"] in pipes
    assert ["transpose_fold"] in pipes
    # the all-passes pipeline keeps fuse-then-clean order
    full = max(pipes, key=len)
    assert full.index("matmul_bias_act_fuse") < full.index(
        "dead_op_elimination")
    assert full.index("transpose_fold") < full.index(
        "dead_op_elimination")


def test_gemm_block_candidates_divisors_default_first():
    cands = tune.gemm_block_candidates(512, 512, 512)
    triples = [(c.params["block_m"], c.params["block_n"],
                c.params["block_k"]) for c in cands]
    assert triples[0] == (512, 512, 512)    # heuristic default leads
    assert set(triples) == {(a, b, c) for a in (512, 256, 128)
                            for b in (512, 256, 128)
                            for c in (512, 256, 128)}
    # a non-512-divisible dim restricts its axis of the grid — args are
    # (m, k, n), the same order as search_gemm_blocks/matmul_bias_act
    assert all(c.params["block_k"] != 512
               for c in tune.gemm_block_candidates(512, 256, 512))
    assert all(c.params["block_n"] != 512
               for c in tune.gemm_block_candidates(512, 512, 256))


def test_search_gemm_blocks_winner_and_cache(tmp_path):
    kw = dict(activation="gelu", grid=(256, 128), interpret=True,
              k_times=1, warmup=1, cache_dir=str(tmp_path))
    rep = tune.search_gemm_blocks(256, 256, 256, **kw)
    assert not rep.cache_hit
    timed = [r for r in rep.results if r.status == "timed"]
    assert timed and rep.winner is not None
    assert set(rep.winner.params) == {"block_m", "block_n", "block_k"}
    # same shape+grid hits the cache; a different activation re-opens it
    rep2 = tune.search_gemm_blocks(256, 256, 256, **kw)
    assert rep2.cache_hit
    assert rep2.winner.params == rep.winner.params
    kw3 = dict(kw)
    kw3["activation"] = "relu"
    assert not tune.search_gemm_blocks(256, 256, 256, **kw3).cache_hit


def test_search_gemm_blocks_winner_params_drive_the_kernel(tmp_path):
    """The winner's params slot straight into matmul_bias_act — and an
    invalid triple for the shape would raise, so a winner that runs IS
    the grid that was timed."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.matmul import matmul_bias_act

    rep = tune.search_gemm_blocks(
        256, 256, 256, activation="relu", grid=(128,), interpret=True,
        k_times=1, warmup=1, cache_dir=str(tmp_path))
    p = rep.winner.params
    x = jnp.zeros((256, 256), jnp.float32)
    w = jnp.zeros((256, 256), jnp.float32)
    out = matmul_bias_act(x, w, activation="relu", interpret=True,
                          block_m=p["block_m"], block_n=p["block_n"],
                          block_k=p["block_k"])
    assert out.shape == (256, 256)
