"""CI perf-regression gate: static roofline budgets for zoo models.

`tools/program_cost.py --budget-ms` prices each model on an EXPLICIT
chip (--peak-flops/--hbm-bw), so the gate is platform-independent: it
fails when a future pass or lowering change inflates a model's static
FLOPs/bytes past its pinned budget — the cheap, deterministic tier-1
cousin of the measured autotuner (the cost model was anchored to XLA's
own cost_analysis within ~1%% on these models in PERF.md round 8).

Budgets are ~2.5x the estimates at pin time (see table below), so
normal estimator recalibration never trips them but an accidental
op-count/shape blowup (a fusion pass gone wrong, a transpose storm, a
de-optimized lowering) does.  If a budget fires after an INTENTIONAL
model/estimator change, re-pin it in this file with the new measured
estimate — that is the review moment the gate exists to create.
"""

import importlib.util
import os

import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import models
from paddle_tpu.fluid import layers

# the gate's fixed pricing chip — NOT a real platform on purpose
PEAK_FLOPS = "1e14"
HBM_BW = "1e12"

# model -> (builder, budget_ms).  Estimates at pin time (2026-08-04):
# lenet 0.0050 ms, resnet18 0.0565 ms, bert-small 0.0281 ms.
_GATE = {}


def _gate(name, budget_ms):
    def deco(fn):
        _GATE[name] = (fn, budget_ms)
        return fn
    return deco


@_gate("lenet", 0.015)
def _build_lenet():
    x = layers.data("img", shape=[-1, 1, 28, 28], append_batch_size=False)
    return models.LeNet5()(x)


@_gate("resnet18", 0.15)
def _build_resnet18():
    x = layers.data("img", shape=[-1, 3, 32, 32], append_batch_size=False)
    return models.resnet18(num_classes=7)(x)


@_gate("bert_small", 0.08)
def _build_bert_small():
    cfg = models.BertConfig(
        vocab_size=512, hidden_size=128, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=512,
        max_position_embeddings=128, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0)
    mk = lambda n: layers.data(  # noqa: E731
        n, shape=[4, 64], append_batch_size=False, dtype="int64")
    logits, _nsp = models.BertForPretraining(cfg)(
        mk("ids"), mk("seg"), mk("pos"), mk("mask"))
    return logits


def _program_cost_tool():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "program_cost", os.path.join(repo, "tools", "program_cost.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _dump(name, tmp_path):
    builder, budget = _GATE[name]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        builder()
    path = str(tmp_path / ("%s.json" % name))
    with open(path, "w") as f:
        f.write(main.to_json())
    return path, budget


@pytest.mark.parametrize("name", sorted(_GATE), ids=sorted(_GATE))
def test_zoo_model_within_static_roofline_budget(name, tmp_path, capsys):
    pc = _program_cost_tool()
    path, budget = _dump(name, tmp_path)
    rc = pc.main([path, "--budget-ms", str(budget),
                  "--peak-flops", PEAK_FLOPS, "--hbm-bw", HBM_BW])
    out = capsys.readouterr().out
    assert rc == 0, (
        "%s blew its static roofline budget (%.3f ms): a pass or "
        "lowering change inflated the program's estimated cost.\n%s"
        % (name, budget, out))
    assert "within" in out


@pytest.mark.parametrize("name", sorted(_GATE), ids=sorted(_GATE))
def test_gate_actually_binds(name, tmp_path, capsys):
    """A vacuous gate is worse than none: the same model must FAIL a
    near-zero budget, proving the estimate is non-trivial and the rc
    contract holds."""
    pc = _program_cost_tool()
    path, _budget = _dump(name, tmp_path)
    rc = pc.main([path, "--budget-ms", "1e-9",
                  "--peak-flops", PEAK_FLOPS, "--hbm-bw", HBM_BW])
    capsys.readouterr()
    assert rc == 1


# ---------------------------------------------------------------------------
# lint-cleanliness gate: the perf hazards the PR-11 passes eliminate
# must STAY eliminated — re-introducing an unfused FFN epilogue or a
# head-transpose pair fails tier-1
# ---------------------------------------------------------------------------


def _perf_findings(program, codes):
    from paddle_tpu import analysis

    diags = analysis.lint_program(program, categories=("perf",))
    return [d for d in diags if d.code in codes]


def test_zoo_bert_lints_clean_after_fusion_passes():
    """Zoo BERT carries fusable FFN epilogues (the gate binds), and
    after MatmulBiasActFusePass + TransposeFoldPass — verified after
    each pass — it emits ZERO unfused-epilogue / layout-transpose-
    hazard findings."""
    from paddle_tpu.fluid import ir

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        _GATE["bert_small"][0]()
    codes = ("unfused-epilogue", "layout-transpose-hazard")
    before = _perf_findings(main, codes)
    assert any(d.code == "unfused-epilogue" for d in before), (
        "gate is vacuous: the unfused BERT FFN no longer emits the "
        "epilogue chain the fusion pass exists for")
    for d in before:
        assert d.fix in ("matmul_bias_act_fuse", "transpose_fold")
    fused = ir.clone_and_apply(
        main, ["matmul_bias_act_fuse", "transpose_fold"], verify=True)
    after = _perf_findings(fused, codes)
    assert not after, (
        "zoo BERT still lints dirty after the fusion passes:\n"
        + "\n".join(d.format() for d in after))


def _bert_small_params():
    """Parameter name -> numpy-shaped zeros for the zoo BERT config —
    the tensors a dp=8 training step communicates."""
    import numpy as np

    from paddle_tpu.fluid import dygraph

    cfg = models.BertConfig(
        vocab_size=512, hidden_size=128, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=512,
        max_position_embeddings=128, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0)
    with dygraph.guard():
        model = models.BertForPretraining(cfg)
        return {k: np.zeros(v.shape, np.float32)
                for k, v in model.state_dict().items()}


# collective-bytes budget for zoo BERT on a dp=8 mesh: the static comm
# model's per-step wire bytes (reduce-scatter + all-gather + scalar
# all-reduce at ZeRO-2).  Estimate at pin time (2026-08-04): 3.59 MB;
# budget ~2.5x so recalibration never trips it but a replication
# regression (a pass/lowering change that re-replicates gradients or
# doubles the gather set) does.
_COMM_BUDGET_BYTES = 9.0e6


def test_zoo_bert_dp8_collective_bytes_within_budget():
    from paddle_tpu.distributed import zero as zero_mod

    layouts = zero_mod.plan_layouts(_bert_small_params(), 8)
    est = zero_mod.zero_comm_estimate(layouts, 2, 8,
                                      state_slots_per_param=2)
    assert 0 < est["wire_bytes_total"] <= _COMM_BUDGET_BYTES, (
        "zoo BERT dp=8 ZeRO-2 step wants %.2f MB on the wire "
        "(budget %.2f MB): a layout or estimator change inflated "
        "collective traffic — re-pin only if intentional"
        % (est["wire_bytes_total"] / 1e6, _COMM_BUDGET_BYTES / 1e6))
    # binds-check: a near-zero budget must fail
    assert est["wire_bytes_total"] > 1e3


def test_replicated_gradient_lint_gate():
    """The replicated-gradient hazard gate: an optimizer program on a
    dp=8 mesh with unsharded grads MUST lint dirty (the ZeRO-2 value
    proposition stays visible), and the same program without a mesh
    stays clean (no false alarms on single-chip CI)."""
    from paddle_tpu import distributed as dist

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("gx", shape=[-1, 64], append_batch_size=False)
        y = layers.data("gy", shape=[-1, 1], append_batch_size=False)
        pred = layers.fc(x, size=1, param_attr="gate_fc.w")
        loss = layers.reduce_mean(layers.square(pred - y))
        fluid.optimizer.AdamOptimizer(1e-3).minimize(loss)
    clean = _perf_findings(main, ("replicated-gradient",))
    assert not clean, "rule fired without a mesh: false alarm"
    mesh = dist.auto_mesh(8)
    with dist.mesh_guard(mesh):
        dirty = _perf_findings(main, ("replicated-gradient",))
    assert len(dirty) == 1, "gate is vacuous: hazard not flagged"
    assert dirty[0].fix == "zero_stage>=2"


def test_zoo_bert_bhsd_layout_folds_clean():
    """A head-major (BHSD) attention block, as a model ported from a
    framework that keeps heads major writes it, materializes the exact
    [B,S,H,D]<->[B,H,S,D] transpose pairs the hazard rule flags;
    TransposeFoldPass must cancel every one (flash layout attr flip)
    and survive verification."""
    from paddle_tpu.fluid import ir
    from paddle_tpu.fluid.layers.common import append_simple_op

    b, s, h, d = 4, 64, 4, 32
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[b, s, h * d], append_batch_size=False)

        def heads():
            proj = layers.fc(x, h * d, num_flatten_dims=2)
            return layers.transpose(
                layers.reshape(proj, [0, s, h, d]), [0, 2, 1, 3])

        ctx = append_simple_op(
            "flash_attention", {"Q": heads(), "K": heads(), "V": heads()},
            {"scale": d ** -0.5, "causal": False, "layout": "BHSD"})
        ctx = layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                             [0, s, h * d])
        layers.fc(layers.gelu(layers.fc(ctx, 4 * h * d, num_flatten_dims=2)),
                  h * d, num_flatten_dims=2)
    hazards = _perf_findings(main, ("layout-transpose-hazard",))
    assert hazards, "BHSD build emitted no transpose hazard: gate vacuous"
    assert _perf_findings(main, ("unfused-epilogue",))
    folded = ir.clone_and_apply(
        main, ["transpose_fold", "matmul_bias_act_fuse"], verify=True)
    assert not _perf_findings(
        folded, ("layout-transpose-hazard", "unfused-epilogue"))
    ops = folded.global_block.ops
    assert "transpose2" not in [op.type for op in ops]
    assert [op.attrs["layout"] for op in ops
            if op.type == "flash_attention"] == ["BSHD"]


# ---------------------------------------------------------------------------
# host-exchange-bytes budget: the recsys path's fourth roofline axis
# (fluid.host_embedding pull/push traffic priced via OpCost.host_bytes)
# ---------------------------------------------------------------------------

# zoo CTR model: batch 256 x 16 ids into a [200k, 32] host table.  The
# static upper bound bills one row per looked-up id both ways (pull f32
# row + push f32 grad row + ids): 256*16 * (32*4 + 32*4 + 16) = 1.11 MB
# per step.  Budget ~2.5x so estimator recalibration never trips it but
# an accidental double-exchange (a lowering that re-pulls, a layout
# change that inflates the row payload) does.
_HOSTEX_BUDGET_BYTES = 2.8e6


def _build_ctr_recsys():
    ids = layers.data("ids", shape=[256, 16], dtype="int64",
                      append_batch_size=False)
    emb = layers.embedding(ids, size=[200_000, 32], is_distributed=True,
                           param_attr="gate_ctr.emb")
    pooled = layers.reduce_mean(emb, dim=1)
    h = layers.fc(pooled, size=64, act="relu", param_attr="gate_ctr.w")
    return layers.fc(h, size=1, param_attr="gate_ctr.out")


def test_zoo_recsys_host_exchange_bytes_within_budget():
    from paddle_tpu.analysis import perf

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        _build_ctr_recsys()
    chip = perf.ChipSpec(
        "gate", float(PEAK_FLOPS), float(HBM_BW), host_bw=1.6e10)
    rep = perf.program_cost(main, chip=chip)
    host = rep.total_host_bytes
    assert 0 < host <= _HOSTEX_BUDGET_BYTES, (
        "zoo recsys step wants %.2f MB across the host link (budget "
        "%.2f MB): an exchange or lowering change inflated the "
        "distributed-embedding traffic — re-pin only if intentional"
        % (host / 1e6, _HOSTEX_BUDGET_BYTES / 1e6))
    # binds-check: the estimate is non-trivial (at least one full
    # pull+push of every looked-up row) and prices against host_bw —
    # the lookup op must be host-bound on this chip
    assert host >= 256 * 16 * (32 * 4 + 32 * 4)
    lookup = [e for e in rep.entries if e.op_type == "lookup_table"]
    assert lookup and lookup[0].bound == "host"
    # ... and the dimension reaches the CLI gate: totals + chip carry it
    d = rep.to_dict()
    assert d["totals"]["host_bytes"] == host
    assert d["chip"]["host_bw"] == 1.6e10


def test_host_exchange_dimension_off_for_dense_embedding():
    """A plain in-HBM embedding must NOT be billed host traffic — the
    dimension prices only the is_distributed host-table path."""
    from paddle_tpu.analysis import perf

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = layers.data("dids", shape=[8, 4], dtype="int64",
                          append_batch_size=False)
        layers.embedding(ids, size=[100, 8], param_attr="gate_dense.emb")
    rep = perf.program_cost(main)
    assert rep.total_host_bytes == 0


# ---------------------------------------------------------------------------
# SIGKILL-mid-stream drill: delta-checkpoint restore loses at most one
# checkpoint window
# ---------------------------------------------------------------------------

STREAM_CRASH_WORKER = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "streaming_crash_worker.py")


def test_sigkill_mid_stream_restores_within_one_window(tmp_path):
    """Train 3 windows committing a delta checkpoint per window, then
    SIGKILL mid-window-4 (post-commit work in flight, no cleanup).
    Restore must land EXACTLY on the window-3 commit — at most one
    window of events lost — and the restored table must be
    bit-identical to an uninterrupted run truncated at that commit
    (same digest), proving replay correctness, not just liveness."""
    import json as _json
    import subprocess
    import sys as _sys

    root = str(tmp_path / "ck")
    p = subprocess.run(
        [_sys.executable, STREAM_CRASH_WORKER, "train", root, "8", "3"],
        capture_output=True, text=True)
    assert p.returncode == -9, (p.returncode, p.stderr[-500:])

    p = subprocess.run(
        [_sys.executable, STREAM_CRASH_WORKER, "restore", root, "0"],
        capture_output=True, text=True)
    assert p.returncode == 0, p.stderr[-500:]
    got = _json.loads(p.stdout.strip().splitlines()[-1])
    # window 4 was half-trained when the kill landed; the committed
    # chain ends at window 3 — exactly one window boundary behind
    assert got["window"] == 3
    assert got["events_done"] == 3 * 4 * 8     # windows x steps x batch

    # ground truth: an uninterrupted 3-window run's table digest
    p = subprocess.run(
        [_sys.executable, STREAM_CRASH_WORKER, "train",
         str(tmp_path / "ck2"), "3"],
        capture_output=True, text=True)
    assert p.returncode == 0, p.stderr[-500:]
    want = _json.loads(p.stdout.strip().splitlines()[-1])
    assert got["digest"] == want["digest"], (
        "restored table diverges from the uninterrupted run: delta "
        "replay is lossy or misordered")


# decode-step HBM-bytes budget for the generation engine on zoo
# BERT-small shapes (L=4, h=256, V=8k) at slots=8, cache_len=512: KV
# read 2*4*8*512*256*4 = 32 MB + params ~10.5 MB per step.  Estimate at
# pin time (2026-08-04): 42.9 MB; budget ~2.5x so a cache-layout or
# estimator regression (e.g. re-reading the cache per layer pass, or a
# recompute-prefix fallback sneaking into the decode path) trips it.
_DECODE_BUDGET_BYTES = 110e6


def test_generation_decode_step_hbm_bytes_within_budget():
    from paddle_tpu.analysis.perf import ChipSpec, decode_step_cost

    chip = ChipSpec("pinned", 197e12, 819e9)   # platform-independent
    cost = decode_step_cost(
        num_layers=4, hidden_size=256, num_heads=4, vocab_size=8000,
        intermediate_size=1024, slots=8, cache_len=512, chip=chip)
    assert cost.bound == "memory", (
        "decode step should be HBM-bound; got %r" % cost.bound)
    assert 0 < cost.bytes <= _DECODE_BUDGET_BYTES, (
        "decode step wants %.1f MB of HBM traffic (budget %.1f MB): a "
        "cache-layout or estimator change inflated the per-token read "
        "— re-pin only if intentional"
        % (cost.bytes / 1e6, _DECODE_BUDGET_BYTES / 1e6))
    # binds-check: a near-zero budget must fail
    assert cost.bytes > 1e3
    # the KV read must dominate growth in cache_len (the quantity the
    # budget exists to guard)
    longer = decode_step_cost(
        num_layers=4, hidden_size=256, num_heads=4, vocab_size=8000,
        intermediate_size=1024, slots=8, cache_len=1024, chip=chip)
    assert longer.kv_read_bytes == 2 * cost.kv_read_bytes


def test_generation_paged_decode_kv_bytes_beat_dense():
    """PR-17 gate: at the long-prompt/short-output mix (dense must
    provision cache_len=max_len while live sequences average far
    shorter), the paged decode step's KV traffic must be STRICTLY
    below dense — the headline paged win, priced by the estimator the
    CI runs on every platform.  int8 KV must beat f32 paged even after
    paying the per-head scale reads."""
    from paddle_tpu.analysis.perf import ChipSpec, decode_step_cost

    chip = ChipSpec("pinned", 197e12, 819e9)
    shape = dict(num_layers=4, hidden_size=256, num_heads=4,
                 vocab_size=8000, intermediate_size=1024, slots=8,
                 chip=chip)
    dense = decode_step_cost(cache_len=512, **shape)
    paged = decode_step_cost(cache_len=512, paged=True, mean_len=96,
                             block_size=16, **shape)
    assert paged.paged and not dense.paged
    assert paged.kv_read_bytes < dense.kv_read_bytes, (
        "paged KV read (%.2f MB) must be strictly below dense "
        "(%.2f MB) at mean_len 96 vs cache_len 512"
        % (paged.kv_read_bytes / 1e6, dense.kv_read_bytes / 1e6))
    # the exact ratio: dense reads cache_len rows, paged reads
    # ceil(mean/bs)*bs = 96 rows
    assert paged.kv_read_bytes * 512 == dense.kv_read_bytes * 96
    assert paged.bytes < dense.bytes
    # int8 halves-and-then-some the paged read even with scale reads
    i8 = decode_step_cost(cache_len=512, paged=True, mean_len=96,
                          block_size=16, kv_dtype_bytes=1, **shape)
    assert i8.kv_read_bytes < paged.kv_read_bytes
    assert i8.kv_dtype_bytes == 1
    # serialization carries the paged fields for the report pipeline
    d = paged.to_dict()
    assert d["paged"] is True and d["block_size"] == 16


def test_generation_tp_decode_comm_closed_form():
    """PR-18 gate: `decode_step_cost(tp=...)` prices one chip of the
    tensor-parallel decode.  The per-step wire bytes are the Megatron
    two-all-reduces-per-layer closed form
    ``2 * L * ringfactor(tp) * slots * h * dtype`` — at tp=2 the ring
    factor ``2(N-1)/N`` is exactly 1, so ``comm_bytes`` must equal
    ``2*L*slots*h*dtype`` to the byte (the same number
    `TPGenerationEngine.decode_hlo_comm_check` pins against compiled
    HLO in tests/test_tp_serving.py)."""
    from paddle_tpu.analysis.perf import ChipSpec, decode_step_cost

    chip = ChipSpec("pinned", 197e12, 819e9, ici_bw=4.5e10)
    shape = dict(num_layers=4, hidden_size=256, num_heads=4,
                 vocab_size=8000, intermediate_size=1024, slots=8,
                 cache_len=512, chip=chip)
    base = decode_step_cost(**shape)
    assert base.tp == 1 and base.comm_bytes == 0

    tp2 = decode_step_cost(tp=2, **shape)
    assert tp2.comm_bytes == 2 * 4 * 8 * 256 * 4       # 2·L·slots·h·4
    # tp=4 pays the 2(N-1)/N = 1.5 ring factor on the same payload
    tp4 = decode_step_cost(tp=4, **shape)
    assert tp4.comm_bytes == 1.5 * tp2.comm_bytes
    # sharding divides the per-chip KV read and layer weights...
    assert tp2.kv_read_bytes * 2 == base.kv_read_bytes
    assert tp2.bytes < base.bytes
    # ...but never the replicated embedding/LM-head read
    assert tp2.bytes > base.bytes / 2
    # validation and serialization
    with pytest.raises(ValueError):
        decode_step_cost(tp=3, **shape)                # 4 heads % 3
    d = tp2.to_dict()
    assert d["tp"] == 2 and d["comm_bytes"] == tp2.comm_bytes
    # an ICI-starved chip must flip the binding term to "ici"
    starved = decode_step_cost(
        tp=2, **{**shape, "chip": ChipSpec("starved", 197e12, 819e9,
                                           ici_bw=1e3)})
    assert starved.bound == "ici"
    assert starved.time_s >= tp2.time_s


def test_serving_observability_layer_within_step_budget():
    """PR-19 gate: what the observability layer adds to the serving hot
    path — one disabled-tracer check per emitted token and one
    `SLOEngine.record` per finished request (both O(1): an attribute
    read, a locked deque append) — must cost under 2%% of a measured
    bare decode step, generously assuming EVERY slot both emits a token
    AND completes a request in the same step.  Percentiles, burn rates
    and alert edges run in `evaluate()`, which only the /slo scrape and
    the cron probe call — never the decode loop."""
    import time

    import numpy as np

    import paddle_tpu
    from paddle_tpu.fluid import dygraph
    from paddle_tpu.observability import trace as T
    from paddle_tpu.observability.metrics import MetricsRegistry
    from paddle_tpu.observability.slo import SLOEngine

    gen = paddle_tpu.generation
    T.disable_tracing()
    try:
        with dygraph.guard():
            np.random.seed(0)
            lm = models.TransformerLM(models.TransformerLMConfig.tiny())
        slots = 4
        eng = gen.GenerationEngine(lm, slots=slots, max_len=64,
                                   prefill_buckets=[8], max_queue=16)
        for i in range(slots):
            eng.submit(gen.GenerationRequest([1 + i, 2, 3],
                                             max_new_tokens=48))
        for _ in range(8):          # warm prefill bucket + decode step
            eng.step()
        n_steps = 24                # 8 + 24 < 48: slots stay occupied
        t0 = time.perf_counter()
        for _ in range(n_steps):
            eng.step()
        step_s = (time.perf_counter() - t0) / n_steps
        eng.run_until_idle()

        def per_call(fn, n=20000):
            t0 = time.perf_counter()
            for _ in range(n):
                fn()
            return (time.perf_counter() - t0) / n

        slo = SLOEngine(registry=MetricsRegistry(), window=512)
        sample = {"request_id": "r0", "trace_id": "t0", "t_wall": 1.0,
                  "outcome": "ok", "ttft_ms": 50.0, "itl_ms": 5.0,
                  "n_tokens": 8, "duration_ms": 90.0}
        cost_record = per_call(lambda: slo.record(sample))

        tr = T.default_tracer()
        assert not tr.enabled

        def token_guard():              # the engine's per-token check
            if tr.enabled:
                tr.async_instant("token", "t0", cat="generation")
        cost_guard = per_call(token_guard)

        budget = 0.02 * step_s
        per_step = slots * (cost_guard + cost_record)
        assert per_step < budget, (
            "observability hot path costs %.3fus/step against a %.3fus "
            "budget (2%% of a %.3fms bare step)"
            % (per_step * 1e6, budget * 1e6, step_s * 1e3))
        # binds-check: the same predicate must FAIL for a cost that is
        # obviously not O(1) bookkeeping (1ms per slot per step)
        assert slots * 1e-3 > budget
    finally:
        T.disable_tracing()


def test_disagg_decode_worker_never_prefills():
    """PR-18 role-separation gate: in a `tp_serving.DisaggPair`, the
    decode worker adopts prefilled KV (`inject_prefilled`) and decodes
    — its prefill buckets stay at jit-cache size 0 for the life of the
    process, and the prefill worker symmetrically never traces the
    decode step.  This is the executable-set pin the DistServe split
    exists to buy: phase isolation you can assert, not just hope for."""
    import numpy as np

    import paddle_tpu
    from paddle_tpu.fluid import dygraph

    gen = paddle_tpu.generation
    tps = paddle_tpu.tp_serving
    cfg = models.TransformerLMConfig.tiny()
    with dygraph.guard():
        np.random.seed(0)
        lm = models.TransformerLM(cfg)
    kw = dict(max_len=64, prefill_buckets=[8], max_queue=32,
              block_size=16, kv_blocks=10)
    pair = tps.DisaggPair(gen.GenerationEngine(lm, slots=2, **kw),
                          gen.GenerationEngine(lm, slots=2, **kw))
    handles = [pair.submit(gen.GenerationRequest(
        [1 + i, 2, 3], max_new_tokens=3)) for i in range(3)]
    pair.run_until_idle()
    for h in handles:
        assert len(h.result(timeout=30.0)) == 3
    dex = pair.decode.stats()["executables"]
    assert dex["decode_step"] == 1
    assert all(v == 0 for v in dex["prefill"].values()), (
        "decode worker traced a prefill bucket: %r" % dex)
    pex = pair.prefill.stats()["executables"]
    assert pex["decode_step"] == 0, (
        "prefill worker traced the decode step: %r" % pex)
    assert pex["prefill"][8] == 1


def test_lock_wrapper_overhead_within_step_budget():
    """Concurrency-sanitizer gate: every hot-path lock in the fleet is a
    named `observability.locks` wrapper, so the DISABLED-mode cost (one
    registry-hot check + the raw acquire) is paid on every acquisition
    all the time.  Pin: the overhead a generous 16 wrapped
    acquire/release pairs per decode step add over bare threading.Locks
    must stay under 2%% of a measured bare decode step.
    Uses the bench's own `measure()` so the gate and the published
    number can never drift apart."""
    import sys as _sys
    import time

    import numpy as np

    import paddle_tpu
    from paddle_tpu.fluid import dygraph

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if os.path.join(repo, "benchmarks") not in _sys.path:
        _sys.path.insert(0, os.path.join(repo, "benchmarks"))
    import concurrency_bench

    gen = paddle_tpu.generation
    with dygraph.guard():
        np.random.seed(0)
        lm = models.TransformerLM(models.TransformerLMConfig.tiny())
    slots = 4
    eng = gen.GenerationEngine(lm, slots=slots, max_len=64,
                               prefill_buckets=[8], max_queue=16)
    for i in range(slots):
        eng.submit(gen.GenerationRequest([1 + i, 2, 3],
                                         max_new_tokens=48))
    for _ in range(8):              # warm prefill bucket + decode step
        eng.step()
    n_steps = 24                    # 8 + 24 < 48: slots stay occupied
    t0 = time.perf_counter()
    for _ in range(n_steps):
        eng.step()
    step_s = (time.perf_counter() - t0) / n_steps
    eng.run_until_idle()

    # overhead = wrapped minus raw, measured back-to-back so suite-load
    # contention (which hits a pure-Python spin far harder than the XLA
    # step) cancels as common mode; min over attempts pins the
    # intrinsic cost — noise only ever inflates a spin measurement
    m = min((concurrency_bench.measure(pairs=50_000) for _ in range(3)),
            key=lambda r: r["overhead_s"])
    budget = 0.02 * step_s
    per_step = concurrency_bench.LOCKS_PER_STEP * m["overhead_s"]
    assert per_step < budget, (
        "disabled lock wrappers add %.3fus/step (%d pairs at +%.0fns "
        "each over a bare threading.Lock) against a %.3fus budget "
        "(2%% of a %.3fms bare step)"
        % (per_step * 1e6, concurrency_bench.LOCKS_PER_STEP,
           m["overhead_s"] * 1e9, budget * 1e6, step_s * 1e3))
    # binds-check: a lock that cost 50us per pair (a syscall, a log
    # write) would blow the same budget
    assert concurrency_bench.LOCKS_PER_STEP * 50e-6 > budget


def test_concurrency_lint_strict_gate():
    """Tier-1 gate: the static thread-safety lint over the shipped
    paddle_tpu/ tree is clean under --strict — zero errors, zero
    non-waived warnings.  Any new nested-lock order or blocking call
    under a lock must either follow the declared hierarchy or carry an
    explicit `# concurrency-ok[...]` waiver with a reason."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "concurrency_lint_gate",
        os.path.join(repo, "tools", "concurrency_lint.py"))
    cli = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cli)
    assert cli.main(["--strict"]) == 0
