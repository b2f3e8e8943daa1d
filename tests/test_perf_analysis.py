"""paddle_tpu.analysis.perf — static cost model, perf lint rules, and
the pass-pipeline ranker.

Method mirrors test_static_analysis.py: for every perf rule, build a
known-good program, seed exactly the hazard (a cancelled transpose pair,
an f32 upcast, a tiny matmul, an undonated buffer, ...) and assert the
exact diagnostic code + provenance — then assert a clean program stays
quiet.  The cost model itself is anchored to ground truth: static FLOPs
must agree with XLA's own `cost_analysis()` over the model zoo (exact
for plain matmul chains, within 15% for the matmul/conv-dominated
models), so the estimator registry cannot silently drift.
"""

import json
import os

import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import analysis, models
from paddle_tpu.analysis import perf
from paddle_tpu.analysis.perf_rules import PadWasteRule
from paddle_tpu.fluid import layers


CHIP = perf.ChipSpec("test-chip", 100e12, 1e12)


def _lint(program, rules, **kw):
    return analysis.lint_program(program, rules=rules, **kw)


# ---------------------------------------------------------------------------
# cost model: closed-form exactness + report structure
# ---------------------------------------------------------------------------


def _matmul_chain():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[32, 64], append_batch_size=False)
        w1 = main.global_block.create_parameter("pc.w1", shape=[64, 128])
        w2 = main.global_block.create_parameter("pc.w2", shape=[128, 16])
        out = layers.matmul(layers.matmul(x, w1), w2)
    return main, out


def test_matmul_flops_exact():
    main, _ = _matmul_chain()
    rep = perf.program_cost(main, chip=CHIP)
    assert rep.total_flops == 2 * 32 * 64 * 128 + 2 * 32 * 128 * 16


def test_movement_ops_cost_zero_flops_but_move_bytes():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[16, 64], append_batch_size=False)
        layers.transpose(x, [1, 0])
    rep = perf.program_cost(main, chip=CHIP)
    e = [c for c in rep.entries if c.op_type == "transpose2"][0]
    assert e.flops == 0
    assert e.bytes == 2 * 16 * 64 * 4  # read + write, f32
    assert e.bound == "memory"


def test_dynamic_dims_substituted():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[-1, 64], append_batch_size=False)
        w = main.global_block.create_parameter("pc.wd", shape=[64, 32])
        layers.matmul(x, w)
    r8 = perf.program_cost(main, chip=CHIP, dynamic_dim=8)
    r16 = perf.program_cost(main, chip=CHIP, dynamic_dim=16)
    assert r16.total_flops == 2 * r8.total_flops


def test_roofline_bound_labels():
    main, _ = _matmul_chain()
    # absurdly slow HBM: everything becomes memory-bound
    slow = perf.ChipSpec("slow-hbm", 100e12, 1e3)
    rep = perf.program_cost(main, chip=slow)
    assert all(e.bound == "memory" for e in rep.entries)
    fast = perf.ChipSpec("fast-hbm", 1e6, 1e15)
    rep = perf.program_cost(main, chip=fast)
    assert all(e.bound == "compute" for e in rep.entries
               if e.flops)


def test_cost_report_dict_and_rollups():
    main, _ = _matmul_chain()
    rep = perf.program_cost(main, chip=CHIP)
    d = rep.to_dict()
    assert d["schema_version"] == perf.CostReport.SCHEMA_VERSION
    assert d["totals"]["flops"] == rep.total_flops
    assert d["totals"]["op_count"] == len(d["ops"])
    assert d["by_op_type"][0]["op_type"] == "matmul"
    assert json.loads(json.dumps(d)) == d  # JSON-serializable
    assert rep.dominant(1)[0].op_type == "matmul"
    assert "matmul" in rep.format()


def test_cond_bills_branches_once_and_container_nothing():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[64, 64], append_batch_size=False)
        pred = layers.reduce_sum(x) > 0.0
        layers.cond(pred, lambda: layers.relu(x), lambda: x * 2.0)
    rep = perf.program_cost(main, chip=CHIP)
    cond_entries = [e for e in rep.entries if e.op_type == "cond"]
    assert cond_entries and cond_entries[0].flops == 0
    assert cond_entries[0].bytes == 0
    # each branch's real sub-block op appears exactly once — the
    # serialized attr dicts mirroring them are NOT re-counted
    assert len([e for e in rep.entries if e.op_type == "relu"]) == 1
    assert len([e for e in rep.entries if e.op_type == "scale"]) == 1


def test_recompute_segment_attr_only_ops_are_billed():
    # recompute_segment REPLACES its ops: they exist only in attrs and
    # must still be counted (unlike cond/while, whose attr dicts mirror
    # real sub-block ops)
    from paddle_tpu.fluid.framework import Operator

    main, _ = _matmul_chain()
    b = main.global_block
    mm = [op for op in b.ops if op.type == "matmul"][0]
    seg = Operator(b, "recompute_segment",
                   inputs={"X": mm.all_input_names()},
                   outputs={"Out": mm.all_output_names()},
                   attrs={"ops": [mm.to_dict()],
                          "in_names": mm.all_input_names(),
                          "out_names": mm.all_output_names()})
    b.ops[b.ops.index(mm)] = seg
    rep = perf.program_cost(main, chip=CHIP)
    # the wrapped matmul's flops survive the rewrite
    assert rep.total_flops == 2 * 32 * 64 * 128 + 2 * 32 * 128 * 16
    assert [e for e in rep.entries if e.op_type == "recompute_segment"
            ][0].flops == 0


def test_default_lint_excludes_perf_rules():
    # pre-perf-catalog behavior preserved: a clean-but-tiny program
    # yields zero findings from the default lint_program call
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        t1 = layers.data("t1", shape=[2, 3], append_batch_size=False)
        t2 = main.global_block.create_parameter("dl.w", shape=[3, 5])
        out = layers.matmul(t1, t2)
    assert not analysis.lint_program(main, fetch_names=[out.name])
    assert analysis.lint_program(
        main, fetch_names=[out.name],
        categories=("program", "perf")).by_code("tiny-matmul")


def test_cost_report_by_layer_uses_provenance():
    with analysis.provenance():
        main, _ = _matmul_chain()
    rep = perf.program_cost(main, chip=CHIP)
    layers_ = rep.by_layer()
    me = os.path.basename(__file__)
    assert any(me in g["layer"] for g in layers_), layers_


# ---------------------------------------------------------------------------
# validation harness: static FLOPs vs XLA cost_analysis (ground truth)
# ---------------------------------------------------------------------------


def test_plain_matmul_chain_matches_xla_exactly():
    main, out = _matmul_chain()
    val = perf.validate_cost_model(main, [out.name])
    if val is None:
        pytest.skip("backend reports no cost analysis")
    assert val["rel_err"] < 1e-9, val


def _zoo_resnet():
    x = layers.data("img", shape=[-1, 3, 32, 32], append_batch_size=False)
    return [models.resnet18(num_classes=7)(x)]


def _zoo_lenet():
    x = layers.data("img", shape=[-1, 1, 28, 28], append_batch_size=False)
    return [models.LeNet5()(x)]


def _zoo_bert():
    # matmul-dominated sizing (hidden 128): the acceptance shape; the
    # degenerate .tiny() config is elementwise-dominated and sits at
    # ~19% (erf-expansion accounting), checked separately below
    cfg = models.BertConfig(
        vocab_size=512, hidden_size=128, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=512,
        max_position_embeddings=128, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0)
    B, S = 4, 64
    mk = lambda n: layers.data(  # noqa: E731
        n, shape=[B, S], append_batch_size=False, dtype="int64")
    logits, nsp = models.BertForPretraining(cfg)(
        mk("ids"), mk("seg"), mk("pos"), mk("mask"))
    return [logits, nsp]


def _zoo_transformer():
    cfg = models.TransformerConfig.tiny()
    mk = lambda n: layers.data(  # noqa: E731
        n, shape=[2, 8], append_batch_size=False, dtype="int64")
    return [models.Transformer(cfg)(
        mk("src"), mk("srcp"), mk("tgt"), mk("tgtp"))]


def _zoo_moe():
    x = layers.data("x", shape=[2, 4, 16], append_batch_size=False)
    out = models.MoEFFN(16, 32, num_experts=4)(x)
    return list(out) if isinstance(out, (list, tuple)) else [out]


_ZOO = [
    ("lenet", _zoo_lenet, 0.15),
    ("resnet", _zoo_resnet, 0.15),
    ("bert", _zoo_bert, 0.15),
    ("transformer", _zoo_transformer, 0.15),
    ("moe", _zoo_moe, 0.15),
]


@pytest.mark.parametrize("name,builder,tol", _ZOO,
                         ids=[n for n, _b, _t in _ZOO])
def test_static_flops_agree_with_xla(name, builder, tol):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        fetches = builder()
    val = perf.validate_cost_model(main, [f.name for f in fetches])
    if val is None:
        pytest.skip("backend reports no cost analysis")
    assert val["rel_err"] <= tol, "%s: %r" % (name, val)


@pytest.mark.slow
def test_static_flops_vgg_agrees_with_xla():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("img", shape=[-1, 3, 32, 32],
                        append_batch_size=False)
        out = models.VGG(depth=16, num_classes=5, in_channels=3)(x)
    val = perf.validate_cost_model(main, [out.name])
    if val is None:
        pytest.skip("backend reports no cost analysis")
    assert val["rel_err"] <= 0.15, val


# ---------------------------------------------------------------------------
# perf lint rules: seed exactly one hazard each, assert the exact code
# ---------------------------------------------------------------------------


def _attention_with_transposes():
    """The [B,S,H,D]->[B,H,S,D]->attention->[B,S,H,D] relayout pattern."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        q = layers.data("q", shape=[2, 16, 4, 32], append_batch_size=False)
        k = layers.data("k", shape=[2, 16, 4, 32], append_batch_size=False)
        v = layers.data("v", shape=[2, 16, 4, 32], append_batch_size=False)
        qt = layers.transpose(q, [0, 2, 1, 3])
        kt = layers.transpose(k, [0, 2, 1, 3])
        vt = layers.transpose(v, [0, 2, 1, 3])
        scores = layers.matmul(qt, kt, transpose_y=True)
        probs = layers.softmax(scores)
        ctx = layers.matmul(probs, vt)
        out = layers.transpose(ctx, [0, 2, 1, 3])
    return main, out


def test_layout_transpose_hazard_fires_with_provenance():
    with analysis.provenance():
        main, _out = _attention_with_transposes()
    diags = _lint(main, ["layout-transpose-hazard"])
    hits = diags.by_code("layout-transpose-hazard")
    assert hits, diags.format()
    assert hits[0].op_type in ("transpose2", "transpose")
    assert hits[0].provenance, "diagnostic must carry the op callsite"
    assert os.path.basename(__file__) in hits[0].provenance[0]


def test_layout_transpose_hazard_survives_diamond_def_chain():
    # the transposed value feeds the matmul AND a residual add: the
    # un-crossed path through the add must not mask the crossed one
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4, 8, 16], append_batch_size=False)
        w = main.global_block.create_parameter("dd.w", shape=[8, 8])
        t1 = layers.transpose(x, [0, 2, 1])          # [4, 16, 8]
        v = layers.scale(t1, scale=2.0)
        b = layers.matmul(v, w)                      # [4, 16, 8]
        d = b + v                                    # residual: v reused
        layers.transpose(d, [0, 2, 1])
    hits = _lint(main, ["layout-transpose-hazard"])
    assert hits.by_code("layout-transpose-hazard"), hits.format()


def test_layout_transpose_hazard_quiet_without_cancellation():
    # single transpose, no inverse downstream: no hazard
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        q = layers.data("q", shape=[2, 16, 4, 32], append_batch_size=False)
        qt = layers.transpose(q, [0, 2, 1, 3])
        layers.reduce_sum(qt)
    assert not _lint(main, ["layout-transpose-hazard"])


def test_dtype_promotion_fires_on_f32_in_bf16_region():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[8, 64], append_batch_size=False,
                        dtype="bfloat16")
        y = layers.data("y", shape=[8, 64], append_batch_size=False,
                        dtype="float32")
        with analysis.provenance():
            x + y
    hits = _lint(main, ["dtype-promotion"]).by_code("dtype-promotion")
    assert hits and hits[0].op_type == "elementwise_add"
    assert set(hits[0].var_names) == {"x", "y"}
    assert hits[0].provenance


def test_dtype_promotion_quiet_on_uniform_dtypes():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[8, 64], append_batch_size=False,
                        dtype="bfloat16")
        y = layers.data("y", shape=[8, 64], append_batch_size=False,
                        dtype="bfloat16")
        x + y
    assert not _lint(main, ["dtype-promotion"])


def test_unfused_epilogue_fires_on_matmul_bias_act():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        a = layers.data("a", shape=[64, 256], append_batch_size=False)
        w = main.global_block.create_parameter("pe.w", shape=[256, 512])
        b = main.global_block.create_parameter("pe.b", shape=[512])
        with analysis.provenance():
            h = layers.matmul(a, w)
        layers.gelu(h + b)
    hits = _lint(main, ["unfused-epilogue"]).by_code("unfused-epilogue")
    assert hits and hits[0].op_type == "matmul"
    assert "gelu" in hits[0].message
    assert hits[0].provenance


def test_unfused_epilogue_quiet_when_intermediate_reused():
    # bias-add output consumed twice: fusing would recompute — no finding
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        a = layers.data("a", shape=[64, 256], append_batch_size=False)
        w = main.global_block.create_parameter("pe2.w", shape=[256, 512])
        b = main.global_block.create_parameter("pe2.b", shape=[512])
        h = layers.matmul(a, w) + b
        layers.gelu(h)
        layers.reduce_sum(h)
    assert not _lint(main, ["unfused-epilogue"])


def test_tiny_matmul_fires_below_mxu_tile():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        t1 = layers.data("t1", shape=[2, 3], append_batch_size=False)
        t2 = main.global_block.create_parameter("pt.w", shape=[3, 5])
        with analysis.provenance():
            layers.matmul(t1, t2)
    hits = _lint(main, ["tiny-matmul"]).by_code("tiny-matmul")
    assert hits and hits[0].op_type == "matmul"
    assert hits[0].provenance


def test_tiny_matmul_quiet_at_mxu_shapes():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[256, 256], append_batch_size=False)
        w = main.global_block.create_parameter("pt2.w", shape=[256, 256])
        layers.matmul(x, w)
    assert not _lint(main, ["tiny-matmul"])


def test_pad_waste_fires_on_coarse_ladder():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        s = layers.data("seq", shape=[-1, -1, 64], append_batch_size=False)
        layers.reduce_sum(s)
    rule = PadWasteRule(ladders={"seq": {1: [8, 64]}})
    hits = _lint(main, [rule]).by_code("pad-waste")
    # axis 1 ladder [8, 64]: worst case is a length-1 request padding to
    # the first bucket, 1 - 1/8 = 88% padding
    assert hits and hits[0].var_names == ("seq",)
    assert "88%" in hits[0].message
    # default powers-of-two ladder stays under the 50% budget
    assert not _lint(main, [PadWasteRule()])


def test_pad_waste_threshold_catches_default_ladder():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        s = layers.data("seq", shape=[-1, 64], append_batch_size=False)
        layers.reduce_sum(s)
    assert _lint(main, [PadWasteRule(threshold=0.3)]).by_code("pad-waste")


def test_missed_donation_fires_on_same_shape_feed_output():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[256, 256], append_batch_size=False)
        out = layers.relu(x)
    hits = _lint(main, ["missed-donation"],
                 fetch_names=[out.name]).by_code("missed-donation")
    assert hits and hits[0].var_names == ("x", out.name)


def test_missed_donation_quiet_on_shape_mismatch_or_live_input():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[256, 256], append_batch_size=False)
        out = layers.reduce_sum(x)          # different shape
    assert not _lint(main, ["missed-donation"], fetch_names=[out.name])
    # and without a fetch list the rule cannot judge: stays quiet
    assert not _lint(main, ["missed-donation"])


# ---------------------------------------------------------------------------
# mixed-dtype-matmul producer attribution (the def-chain walk)
# ---------------------------------------------------------------------------


def test_mixed_dtype_matmul_names_promoting_cast():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[8, 32], append_batch_size=False,
                        dtype="bfloat16")
        w = main.global_block.create_parameter(
            "md.w", shape=[32, 16], dtype="bfloat16")
        w32 = layers.cast(w, "float32")
        w32r = layers.reshape(w32, [32, 16])   # dtype-preserving hop
        layers.matmul(x, w32r)
    hits = _lint(main, ["mixed-dtype-matmul"]).by_code("mixed-dtype-matmul")
    assert hits, "promotion must fire"
    # the walk crosses the reshape and lands on the cast that upcast
    assert "'cast'" in hits[0].message, hits[0].message
    assert "float32" in hits[0].message


def test_mixed_dtype_matmul_names_parameter_origin():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[8, 32], append_batch_size=False,
                        dtype="bfloat16")
        w = main.global_block.create_parameter(
            "md2.w", shape=[32, 16], dtype="float32")
        layers.matmul(x, w)
    hits = _lint(main, ["mixed-dtype-matmul"]).by_code("mixed-dtype-matmul")
    assert hits and "parameter" in hits[0].message
    assert "'md2.w'" in hits[0].message


def test_mixed_dtype_matmul_param_behind_passthrough_blames_param():
    # an f32 parameter reaching the matmul through a dtype-preserving
    # reshape must be blamed itself — not the reshape hop
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[8, 32], append_batch_size=False,
                        dtype="bfloat16")
        w = main.global_block.create_parameter(
            "md3.w", shape=[16, 32], dtype="float32")
        wr = layers.reshape(w, [32, 16])
        layers.matmul(x, wr)
    hits = _lint(main, ["mixed-dtype-matmul"]).by_code("mixed-dtype-matmul")
    assert hits and "parameter" in hits[0].message
    assert "'md3.w'" in hits[0].message
    # blamed the producer-less endpoint, not a dtype-preserving op
    assert "introduced by" not in hits[0].message


# ---------------------------------------------------------------------------
# rule catalog hygiene
# ---------------------------------------------------------------------------


def test_perf_rules_registered_under_perf_category():
    from paddle_tpu.analysis import lint_rules

    perf_rules = set(lint_rules(category="perf"))
    assert {"layout-transpose-hazard", "dtype-promotion",
            "unfused-epilogue", "tiny-matmul", "pad-waste",
            "missed-donation"} <= perf_rules
    # the correctness catalog is unchanged by the perf additions
    assert "dead-op" in lint_rules(category="program")
    assert not perf_rules & set(lint_rules(category="program"))


def test_model_zoo_stays_clean_under_perf_rules():
    # perf findings are advisory: never error-severity
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        fetches = _zoo_transformer()
    diags = analysis.lint_program(
        main, fetch_names=[f.name for f in fetches],
        categories=("perf",))
    assert not diags.errors(), diags.format()


# ---------------------------------------------------------------------------
# pass-pipeline ranking
# ---------------------------------------------------------------------------


def _conv_bn_relu():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("img", shape=[8, 16, 16, 16],
                        append_batch_size=False)
        c = layers.conv2d(x, num_filters=32, filter_size=3, padding=1,
                          data_format="NHWC")
        bn = layers.batch_norm(c, data_layout="NHWC")
        layers.relu(bn)
    return main


def test_rank_pass_pipelines_prefers_fusion():
    main = _conv_bn_relu()
    n_ops = len(main.global_block.ops)
    ranked = perf.rank_pass_pipelines(
        main, [[], ["batch_norm_act_fuse"]], chip=CHIP)
    assert ranked[0].pipeline == ("batch_norm_act_fuse",)
    assert ranked[0].time_s < ranked[1].time_s
    # candidates ran on clones: the original program is untouched
    assert len(main.global_block.ops) == n_ops
    d = ranked[0].to_dict()
    assert d["pipeline"] == ["batch_norm_act_fuse"] and d["error"] is None


def test_rank_pass_pipelines_excludes_broken_candidate():
    from paddle_tpu.fluid import ir

    class _BreakerPass(ir.Pass):
        name = "test_breaker"

        def apply(self, program):
            # strand a var: delete the op that produces the relu input
            del program.global_block.ops[1]
            return program

    main = _conv_bn_relu()
    ranked = perf.rank_pass_pipelines(
        main, [[_BreakerPass()], []], chip=CHIP, verify=True)
    assert ranked[0].pipeline == ()          # healthy baseline wins
    assert ranked[-1].report is None         # breaker excluded
    assert ranked[-1].error and "test_breaker" in ranked[-1].error


# ---------------------------------------------------------------------------
# CLIs: program_cost + program_lint perf surface
# ---------------------------------------------------------------------------


def _load_tool(name):
    import importlib.util

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(repo, "tools", "%s.py" % name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_program_cost_cli_json_roundtrip(tmp_path, capsys):
    pc = _load_tool("program_cost")
    main, _ = _matmul_chain()
    path = str(tmp_path / "prog.json")
    with open(path, "w") as f:
        f.write(main.to_json())

    assert pc.main([path, "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    # the documented schema round-trips
    assert out["schema_version"] == 1
    assert out["model"] == path
    assert out["totals"]["flops"] == 2 * 32 * 64 * 128 + 2 * 32 * 128 * 16
    assert out["chip"]["peak_flops"] > 0
    assert out["by_op_type"][0]["op_type"] == "matmul"
    assert all(set(o) >= {"block_idx", "op_idx", "op_type", "flops",
                          "bytes", "time_s", "bound"} for o in out["ops"])
    assert out["within_budget"] is None

    # --no-ops drops the per-op array, text mode prints the table
    assert pc.main([path, "--json", "--no-ops"]) == 0
    assert "ops" not in json.loads(capsys.readouterr().out)
    assert pc.main([path]) == 0
    assert "matmul" in capsys.readouterr().out


def test_program_cost_cli_budget_rc(tmp_path, capsys):
    pc = _load_tool("program_cost")
    main, _ = _matmul_chain()
    path = str(tmp_path / "prog.json")
    with open(path, "w") as f:
        f.write(main.to_json())
    assert pc.main([path, "--budget-ms", "1e-12", "--json"]) == 1
    assert json.loads(capsys.readouterr().out)["within_budget"] is False
    assert pc.main([path, "--budget-ms", "1e6"]) == 0


def test_program_lint_cli_perf_flags(tmp_path, capsys):
    pl = _load_tool("program_lint")
    main, _out = _attention_with_transposes()
    path = str(tmp_path / "prog.json")
    with open(path, "w") as f:
        f.write(main.to_json())
    feeds = "q,k,v"

    # without --perf the hazard rules do not run
    assert pl.main([path, "--feed", feeds, "--fetch", _out.name,
                    "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["schema_version"] == pl.SCHEMA_VERSION
    assert {"diagnostics", "summary"} <= set(out)
    codes = {d["code"] for d in out["diagnostics"]}
    assert "layout-transpose-hazard" not in codes

    # --perf runs them (warnings: rc stays 0)
    assert pl.main([path, "--feed", feeds, "--fetch", _out.name,
                    "--json", "--perf"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert "layout-transpose-hazard" in {
        d["code"] for d in out["diagnostics"]}

    # --budget-ms below the estimate flips rc 1 and reports the numbers
    assert pl.main([path, "--feed", feeds, "--fetch", _out.name,
                    "--json", "--budget-ms", "1e-12"]) == 1
    out = json.loads(capsys.readouterr().out)
    assert out["budget"]["within_budget"] is False
    assert out["budget"]["estimated_ms"] > 0


def test_program_lint_cli_perf_composes_with_explicit_rules(tmp_path,
                                                            capsys):
    pl = _load_tool("program_lint")
    main, _out = _attention_with_transposes()
    path = str(tmp_path / "prog.json")
    with open(path, "w") as f:
        f.write(main.to_json())
    assert pl.main([path, "--feed", "q,k,v", "--fetch", _out.name,
                    "--rules", "dead-op", "--perf", "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert "layout-transpose-hazard" in {
        d["code"] for d in out["diagnostics"]}


def test_program_lint_cli_max_pad_waste(tmp_path, capsys):
    pl = _load_tool("program_lint")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        s = layers.data("seq", shape=[-1, 64], append_batch_size=False)
        out = layers.reduce_sum(s)
    path = str(tmp_path / "prog.json")
    with open(path, "w") as f:
        f.write(main.to_json())
    # powers-of-two ladder worst case is just under 0.5: a 0.3 budget
    # fires and flips rc even though the finding is a warning
    assert pl.main([path, "--feed", "seq", "--fetch", out.name,
                    "--json", "--max-pad-waste", "0.3"]) == 1
    outj = json.loads(capsys.readouterr().out)
    assert "pad-waste" in {d["code"] for d in outj["diagnostics"]}
    assert pl.main([path, "--feed", "seq", "--fetch", out.name,
                    "--max-pad-waste", "0.6"]) == 0
    capsys.readouterr()


# ---------------------------------------------------------------------------
# PR 11: rule<->pass linkage (fix hints), reshape/cast see-through, and
# the fused-GEMM cost estimator
# ---------------------------------------------------------------------------


def test_unfused_epilogue_sees_through_reshape_and_carries_fix():
    """The BERT FFN can emit a reshape between matmul and add — pure
    data movement must not hide the fusion candidate, and the finding
    names the pass that fixes it."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        a = layers.data("a", shape=[8, 16, 32], append_batch_size=False)
        w = main.global_block.create_parameter("rsh.w", shape=[32, 64])
        b = main.global_block.create_parameter("rsh.b", shape=[64])
        mm = layers.mul(a, w, x_num_col_dims=2)
        r = layers.reshape(mm, [128, 64])
        layers.gelu(layers.elementwise_add(r, b, axis=1))
    hits = _lint(main, ["unfused-epilogue"]).by_code("unfused-epilogue")
    assert hits, "reshape hid the epilogue chain"
    assert hits[0].fix == "matmul_bias_act_fuse"
    assert "interposed" in hits[0].message


def test_unfused_epilogue_sees_through_cast():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        a = layers.data("a", shape=[8, 32], append_batch_size=False)
        w = main.global_block.create_parameter("cst.w", shape=[32, 64])
        b = main.global_block.create_parameter("cst.b", shape=[64],
                                               dtype="float32")
        mm = layers.matmul(a, w)
        c = layers.cast(mm, "float32")
        layers.relu(layers.elementwise_add(c, b, axis=1))
    hits = _lint(main, ["unfused-epilogue"]).by_code("unfused-epilogue")
    # flagged — but the fuse pass declines cast hops (a cast changes
    # numerics inside the chain), so no fix hint is attached
    assert hits and hits[0].fix is None


def test_unfused_epilogue_reshape_with_fanout_stays_quiet():
    # the interposed reshape's output is consumed twice: not privately
    # fusable, no finding
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        a = layers.data("a", shape=[8, 16, 32], append_batch_size=False)
        w = main.global_block.create_parameter("rsf.w", shape=[32, 64])
        b = main.global_block.create_parameter("rsf.b", shape=[64])
        r = layers.reshape(layers.mul(a, w, x_num_col_dims=2), [128, 64])
        layers.gelu(layers.elementwise_add(r, b, axis=1))
        layers.reduce_sum(r)
    assert not _lint(main, ["unfused-epilogue"])


def test_layout_transpose_hazard_carries_fix():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("hx", shape=[2, 8, 16], append_batch_size=False)
        w = main.global_block.create_parameter("hz.w", shape=[16, 16])
        t1 = layers.transpose(x, [0, 2, 1])
        t1b = layers.transpose(t1, [0, 2, 1])
        layers.transpose(layers.matmul(t1b, w), [0, 2, 1])
    hits = _lint(main, ["layout-transpose-hazard"]).by_code(
        "layout-transpose-hazard")
    assert hits and hits[0].fix == "transpose_fold"
    assert hits[0].to_dict()["fix"] == "transpose_fold"


def test_matmul_bias_act_cost_is_one_pass_of_epilogue_bytes():
    """The fused op bills matmul FLOPs + one epilogue pass — NOT the
    unfused three-op [M,N] traffic — so the static ranker prefers the
    fusion (the estimator registered like batch_norm_act_fuse's)."""
    def build():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data("x", shape=[64, 128], append_batch_size=False)
            w = main.global_block.create_parameter("fcost.w",
                                                   shape=[128, 256])
            b = main.global_block.create_parameter("fcost.b", shape=[256])
            layers.gelu(layers.elementwise_add(
                layers.mul(x, w), b, axis=1))
        return main

    main = build()
    from paddle_tpu.fluid import ir

    fused = ir.clone_and_apply(main, ["matmul_bias_act_fuse"],
                               verify=True)
    rep_unfused = perf.program_cost(main, chip=CHIP)
    rep_fused = perf.program_cost(fused, chip=CHIP)
    # matmul FLOPs identical; epilogue flops preserved within the op
    assert rep_fused.total_flops == pytest.approx(
        rep_unfused.total_flops, rel=1e-6)
    # but the [M,N] intermediate no longer round-trips: strictly fewer
    # bytes moved, strictly less estimated time
    assert rep_fused.total_bytes < rep_unfused.total_bytes
    assert rep_fused.total_time_s < rep_unfused.total_time_s


def test_rank_pass_pipelines_prefers_matmul_bias_act_fuse():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[64, 128], append_batch_size=False)
        w = main.global_block.create_parameter("frank.w",
                                               shape=[128, 256])
        b = main.global_block.create_parameter("frank.b", shape=[256])
        layers.gelu(layers.elementwise_add(layers.mul(x, w), b, axis=1))
    ranked = perf.rank_pass_pipelines(
        main, [[], ["matmul_bias_act_fuse"]], chip=CHIP)
    assert ranked[0].pipeline == ("matmul_bias_act_fuse",)
    assert ranked[0].time_s < ranked[1].time_s


def test_chip_spec_detect_reads_the_one_table_and_refuses_unknown_chips():
    """`ChipSpec.detect` prices from `xla_cost.CHIP_PEAKS`, keyed by
    device_kind: the v5e row for a v5e, the TARGET chip (named as such)
    on a host with no accelerator, and an error — never v5e figures —
    for an accelerator the table does not list."""
    from paddle_tpu.observability import xla_cost

    row = xla_cost.CHIP_PEAKS["TPU v5 lite"]
    v5e = perf.ChipSpec.detect(device_kind="TPU v5 lite")
    assert (v5e.name, v5e.peak_flops, v5e.hbm_bw, v5e.ici_bw, v5e.host_bw) \
        == ("tpu-v5e", row["peak_flops"], row["hbm_bw"], row["ici_bw"],
            row["host_bw"])
    assert perf.V5E.to_dict() == v5e.to_dict()
    here = perf.ChipSpec.detect()                # the CPU backend
    assert here.name == "tpu-v5e (target)"
    assert here.peak_flops == v5e.peak_flops
    with pytest.raises(xla_cost.UnknownDeviceError, match="TPU v9"):
        perf.ChipSpec.detect(device_kind="TPU v9")
    # explicit figures still win over the table, axis by axis
    assert perf.ChipSpec.detect(peak_flops=1e12).peak_flops == 1e12
