"""Span tracer / flight recorder / XLA cost attribution / fleet
timeline (paddle_tpu.observability.trace and friends).

Covers the PR-6 acceptance drills: chrome-trace schema validity +
nesting for a served HTTP request and a 3-step hapi fit, trace-id
propagation across the serving dispatch/completion threads, the
SIGTERM flight-recorder dump, the disabled-tracing overhead budget,
straggler detection in the fleet view, and the trace_summary CLI."""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.observability import trace as T
from paddle_tpu.observability.metrics import Counter, MetricsRegistry

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

VALID_PH = {"X", "i", "C", "b", "e", "n", "M"}


def validate_chrome_trace(obj):
    """The schema chrome://tracing and Perfetto actually require of the
    event kinds this repo emits."""
    assert isinstance(obj, dict) and isinstance(obj["traceEvents"], list)
    for ev in obj["traceEvents"]:
        assert ev["ph"] in VALID_PH, ev
        assert isinstance(ev["name"], str) and ev["name"]
        assert isinstance(ev["pid"], int)
        if ev["ph"] == "M":
            continue
        assert isinstance(ev["ts"], int) and ev["ts"] >= 0, ev
        assert isinstance(ev["tid"], int)
        if ev["ph"] == "X":
            assert isinstance(ev["dur"], int) and ev["dur"] >= 0, ev
        if ev["ph"] == "i":
            assert ev.get("s") in ("t", "p", "g"), ev
        if ev["ph"] in ("b", "e", "n"):
            assert isinstance(ev["id"], str) and ev["id"], ev
        if ev["ph"] == "C":
            assert all(isinstance(v, (int, float))
                       for v in ev["args"].values()), ev
    return obj


def spans(events, name=None, cat=None):
    return [e for e in events if e.get("ph") == "X"
            and (name is None or e["name"] == name)
            and (cat is None or e.get("cat") == cat)]


def _contains(outer, inner):
    """inner's interval nests inside outer's, on the same track."""
    return (outer["pid"] == inner["pid"] and outer["tid"] == inner["tid"]
            and outer["ts"] <= inner["ts"]
            and inner["ts"] + inner.get("dur", 0)
            <= outer["ts"] + outer["dur"])


@pytest.fixture
def tracer():
    tr = T.enable_tracing()
    tr.clear()
    yield tr
    T.disable_tracing()
    T.default_tracer().clear()


# ---------------------------------------------------------------------------
# tracer primitives + golden schema
# ---------------------------------------------------------------------------


def test_abandoned_span_emits_nothing(tracer):
    """abandon() inside a with-block must suppress the event — a
    cancelled operation leaves no phantom span in the timeline."""
    with tracer.span("kept"):
        pass
    with tracer.span("doomed") as s:
        s.abandon()
    names = [e["name"] for e in tracer.events() if e.get("ph") == "X"]
    assert "kept" in names and "doomed" not in names


def test_span_nesting_schema_and_roundtrip(tracer, tmp_path):
    with T.span("outer", cat="app", args={"k": 1}):
        time.sleep(0.002)
        with T.span("inner"):
            time.sleep(0.001)
        T.instant("mark", args={"x": 2})
    T.counter_event("depth", {"q": 3})
    ct = validate_chrome_trace(tracer.chrome_trace())
    (outer,) = spans(ct["traceEvents"], "outer")
    (inner,) = spans(ct["traceEvents"], "inner")
    assert _contains(outer, inner)
    assert outer["dur"] >= inner["dur"] > 0
    assert outer["args"]["k"] == 1
    # instants/counters landed with the right phase
    phs = {e["ph"] for e in ct["traceEvents"]}
    assert {"X", "i", "C"} <= phs
    # save/load roundtrip, plain and gzipped, both loadable
    for fname in ("t.json", "t.json.gz"):
        p = tracer.save(str(tmp_path / fname))
        evs, md = T.load_trace(p)
        assert len(evs) == len(ct["traceEvents"])
        assert md["clock"] == "perf_counter" and "anchor_unix_time" in md


def test_span_error_annotated_and_stack_unwound(tracer):
    with pytest.raises(ValueError):
        with T.span("dying"):
            raise ValueError("boom")
    (ev,) = spans(tracer.events(), "dying")
    assert ev["args"]["error"] == "ValueError"
    assert T.current_trace_id() is None     # stack fully unwound


def test_trace_id_inheritance_and_context(tracer):
    tid = T.new_trace_id()
    assert tid != T.new_trace_id()          # process-unique
    with T.trace_context(tid):
        assert T.current_trace_id() == tid
        with T.span("child"):
            pass                            # inherits the context id
    assert T.current_trace_id() is None
    (ev,) = spans(tracer.events(), "child")
    assert ev["args"]["trace_id"] == tid


def test_ring_is_bounded():
    tr = T.Tracer(capacity=32, enabled=True)
    for i in range(100):
        tr.instant("e%d" % i)
    evs = [e for e in tr.events() if e["ph"] == "i"]
    assert len(evs) == 32
    assert evs[-1]["name"] == "e99"         # newest survive


def test_merge_traces_aligns_ranks_on_wall_clock():
    shards = []
    for rank, skew in ((0, 0.0), (1, 5.0)):
        tr = T.Tracer(capacity=64, enabled=True)
        # fake a shard whose monotonic clock started `skew` seconds
        # earlier relative to wall time
        tr.anchor = (1000.0, skew)
        with tr.span("step"):
            pass
        shards.append((rank, tr.events(),
                       {"anchor_unix_time": tr.anchor[0],
                        "anchor_clock": tr.anchor[1]}))
    merged = validate_chrome_trace(T.merge_traces(shards))
    by_pid = {e["pid"]: e for e in spans(merged["traceEvents"], "step")}
    assert set(by_pid) == {0, 1}
    # rank 1's events happened 5s earlier on the common wall clock
    assert by_pid[0]["ts"] - by_pid[1]["ts"] == pytest.approx(5e6, rel=0.01)


# ---------------------------------------------------------------------------
# serving: per-request trace across the dispatch/completion threads
# ---------------------------------------------------------------------------


def _fc_server(tmp_path, **kw):
    from paddle_tpu.inference import AnalysisConfig, create_predictor
    from paddle_tpu.inference.server import InferenceServer

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[-1, 8], append_batch_size=False)
        pred = layers.fc(layers.fc(x, 16, act="relu"), 2)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    path = str(tmp_path / "fc.model")
    fluid.io.save_inference_model(path, ["x"], [pred], exe, main)
    predictor = create_predictor(AnalysisConfig(path))
    return InferenceServer(predictor, batch_timeout_ms=1, **kw)


def test_served_request_trace_end_to_end(tracer, tmp_path):
    """Acceptance drill: one served request produces a loadable trace
    whose async timeline walks queue -> pad+dispatch -> xla_compute ->
    slice under the request's trace id, with phases recorded from more
    than one thread."""
    server = _fc_server(tmp_path).start()
    try:
        outs, trace_id = server.infer_with_trace(
            {"x": np.ones((2, 8), np.float32)})
        assert outs[0].shape == (2, 2)
        assert trace_id.startswith("req-")
    finally:
        server.stop()
    p = tracer.save(str(tmp_path / "serving.trace.json"))
    evs, _md = T.load_trace(p)
    validate_chrome_trace({"traceEvents": evs})
    mine = [e for e in evs if e.get("id") == trace_id]
    assert mine, "no async events for the returned trace id"
    begins = [e["name"] for e in mine if e["ph"] == "b"]
    ends = [e["name"] for e in mine if e["ph"] == "e"]
    for phase in ("request", "queue", "pad+dispatch", "xla_compute",
                  "slice"):
        assert phase in begins and phase in ends, phase
    # phase order: each phase begins at/after the previous one's begin
    order = [e for e in mine if e["ph"] == "b" and e["name"] != "request"]
    assert [e["name"] for e in sorted(order, key=lambda e: e["ts"])] == \
        ["queue", "pad+dispatch", "xla_compute", "slice"]
    # the batch-side spans crossed the dispatcher/completion threads and
    # carry the trace id for the join
    batch_spans = spans(evs, cat="serving")
    carrying = [e for e in batch_spans
                if trace_id in (e.get("args", {}).get("trace_ids") or ())]
    assert {e["name"] for e in carrying} >= {"batch.pad", "batch.dispatch"}
    threads = {e["tid"] for e in batch_spans} | {e["tid"] for e in mine}
    assert len(threads) >= 2, "trace did not cross threads"


def test_http_response_carries_trace_id_and_trace_endpoint(tracer,
                                                           tmp_path):
    import urllib.request

    server = _fc_server(tmp_path).start()
    httpd = server.serve_http(port=0, block=False)
    try:
        base = "http://127.0.0.1:%d" % httpd.server_address[1]
        req = urllib.request.Request(
            base + "/predict",
            data=json.dumps(
                {"inputs": {"x": [[0.5] * 8] * 3}}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=10) as resp:
            out = json.loads(resp.read())
        assert len(out["outputs"][0]) == 3
        trace_id = out["trace_id"]
        assert trace_id.startswith("req-")
        # /stats names the recent request so a slow p99 is findable
        with urllib.request.urlopen(base + "/stats", timeout=10) as resp:
            stats = json.loads(resp.read())
        assert stats["tracing_enabled"] is True
        assert trace_id in [r["trace_id"] for r in stats["recent_requests"]]
        assert stats["slowest_recent"][0]["latency_ms"] > 0
        # GET /trace returns the loadable chrome trace with the request
        with urllib.request.urlopen(base + "/trace", timeout=10) as resp:
            ct = json.loads(resp.read())
        validate_chrome_trace(ct)
        assert any(e.get("id") == trace_id for e in ct["traceEvents"])
    finally:
        httpd.shutdown()
        server.stop()


def test_http_trace_endpoint_409_when_disabled(tmp_path):
    import urllib.error
    import urllib.request

    T.disable_tracing()
    server = _fc_server(tmp_path).start()
    httpd = server.serve_http(port=0, block=False)
    try:
        base = "http://127.0.0.1:%d" % httpd.server_address[1]
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/trace", timeout=10)
        assert ei.value.code == 409
        # trace ids are still allocated for correlation while disabled
        outs, trace_id = server.infer_with_trace(
            {"x": np.ones((1, 8), np.float32)})
        assert trace_id.startswith("req-")
    finally:
        httpd.shutdown()
        server.stop()


def test_serving_cost_attribution_and_mfu(tracer, tmp_path, monkeypatch):
    """warmup samples cost_analysis() per executable into gauges +
    /stats, and completed batches set the measured `mfu` gauge."""
    monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "1e12")
    reg = MetricsRegistry()
    server = _fc_server(tmp_path, metrics_registry=reg,
                        batch_buckets=[1, 2]).start()
    try:
        server.warmup({"x": np.ones((1, 8), np.float32)})
        stats = server.stats()
        costs = stats["executable_costs"]
        assert costs, "warmup sampled no executable costs"
        assert all("flops" in c for c in costs.values())
        fam = reg.get("xla_executable_flops")
        assert fam is not None and fam._series()
        server.infer({"x": np.ones((2, 8), np.float32)})
        fam = reg.get("mfu")
        assert fam is not None
        series = fam._series()
        assert series and all(0 < child.value < 1
                              for _lv, child in series)
    finally:
        server.stop()


def test_warmup_survives_metrics_name_collision(tmp_path):
    """Attribution is telemetry: a registry where the cost gauge name
    already exists as an incompatible family must not crash warmup."""
    reg = MetricsRegistry()
    reg.counter("xla_executable_flops", "collides")   # wrong type
    server = _fc_server(tmp_path, metrics_registry=reg,
                        batch_buckets=[1]).start()
    try:
        server.warmup({"x": np.ones((1, 8), np.float32)})   # no raise
        # the gauges were skipped, the colliding family is untouched,
        # and the per-signature table (spans + /stats) still filled
        assert isinstance(reg.get("xla_executable_flops"), Counter)
        assert server.stats()["executable_costs"]
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# training: 3-step hapi fit trace (acceptance drill)
# ---------------------------------------------------------------------------


def _toy_model():
    import paddle_tpu.hapi as hp
    from paddle_tpu.fluid import dygraph

    class Net(dygraph.Layer):
        def __init__(self):
            super().__init__()
            self.fc = dygraph.Linear(4, 3)

        def forward(self, x):
            return self.fc(x)

    m = hp.Model(Net(), inputs=[hp.Input([None, 4], "float32", "x")],
                 labels=[hp.Input([None, 1], "int64", "y")])

    def loss_fn(pred, y):
        return layers.reduce_mean(
            layers.square(pred - layers.cast(y, "float32")))

    m.prepare(optimizer=fluid.optimizer.SGDOptimizer(0.01),
              loss_function=loss_fn)
    return m


def test_three_step_fit_trace_nests_step_budget(tracer, tmp_path):
    m = _toy_model()
    x = np.zeros((24, 4), np.float32)
    y = np.zeros((24, 1), np.int64)
    m.fit((x, y), batch_size=8, epochs=1, verbose=0, shuffle=False)
    p = tracer.save(str(tmp_path / "fit.trace.json"))
    evs, _md = T.load_trace(p)
    validate_chrome_trace({"traceEvents": evs})
    steps = spans(evs, "step", cat="train")
    assert len(steps) == 3
    waits = spans(evs, "data_wait", cat="train")
    runs = spans(evs, "executor.run", cat="executor")
    for i, st in enumerate(sorted(steps, key=lambda e: e["ts"])):
        assert st["args"]["step"] == i
        # the step span carries the StepTimer budget...
        for comp in ("data_wait", "compile", "compute", "host_overhead",
                     "step_time"):
            assert comp in st["args"], comp
        # ...and nests the data_wait + executor spans by containment
        assert any(_contains(st, w) for w in waits)
        assert any(_contains(st, r) for r in runs)
    # first (cache-miss) run attributes compile; steady state does not
    runs = sorted(runs, key=lambda e: e["ts"])
    assert runs[0]["args"]["compile_ms"] >= runs[-1]["args"]["compile_ms"]
    assert runs[-1]["args"]["compute_ms"] > 0


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def test_flight_recorder_sigterm_drill(tmp_path):
    """Acceptance drill: SIGTERM a training subprocess mid-run; the
    process must still die by signal AND leave one loadable dump holding
    the last steps."""
    dump_dir = str(tmp_path / "flight")
    ready = str(tmp_path / "ready")
    env = dict(os.environ, FLT_DUMP_DIR=dump_dir, FLT_READY=ready,
               JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    p = subprocess.Popen([sys.executable,
                          os.path.join(HERE, "flight_worker.py")], env=env)
    try:
        deadline = time.time() + 120
        while not os.path.exists(ready):
            assert time.time() < deadline, "worker never trained 3 steps"
            assert p.poll() is None, "worker died before the drill"
            time.sleep(0.05)
        p.send_signal(signal.SIGTERM)
        rc = p.wait(timeout=60)
    finally:
        if p.poll() is None:
            p.kill()
            p.wait(timeout=30)
    assert rc == -signal.SIGTERM    # exit semantics preserved
    dumps = [f for f in os.listdir(dump_dir)
             if f.endswith(".trace.json")]
    assert len(dumps) == 1
    evs, md = T.load_trace(os.path.join(dump_dir, dumps[0]))
    validate_chrome_trace({"traceEvents": evs})
    assert md["flight_recorder"] is True
    assert "SIGTERM" in md["reason"]
    assert "metrics_snapshot" in md
    # the span ring held the lead-up: real step spans...
    step_spans = spans(evs, "step", cat="train")
    assert len(step_spans) >= 3
    # ...and the scalar ring re-emitted the per-step budgets
    budget = [e for e in evs if e["ph"] == "C"
              and e["name"] == "step_budget_ms[flight.drill]"]
    assert len(budget) >= 3
    assert all("step_time" in e["args"] for e in budget)
    # the summarizer reads the dump and names the reason
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_summary.py"),
         os.path.join(dump_dir, dumps[0]), "--json"],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    summary = json.loads(r.stdout)
    assert "SIGTERM" in summary["metadata"]["reason"]
    assert any(row["name"] == "step"
               for row in summary["top_spans_by_self_time"])


def test_flight_recorder_dumps_on_first_failed_step(tmp_path):
    """A step exiting with an exception triggers ONE dump (not one per
    subsequent failure), in-process, without signal hooks."""
    from paddle_tpu.observability import StepTimer
    from paddle_tpu.observability.flight_recorder import FlightRecorder

    rec = FlightRecorder(dump_dir=str(tmp_path)).install(
        signals=(), catch_unhandled=False)
    try:
        timer = StepTimer(name="failing.loop")
        with timer.step():
            pass                     # a good step first
        for _ in range(3):           # then a dying loop
            with pytest.raises(RuntimeError):
                with timer.step():
                    raise RuntimeError("NaN guard tripped")
        dumps = [f for f in os.listdir(str(tmp_path))
                 if f.endswith(".trace.json")]
        assert len(dumps) == 1       # first failure only
        evs, md = T.load_trace(str(tmp_path / dumps[0]))
        assert "failed step" in md["reason"]
        assert "failing.loop" in md["reason"]
        # the dump contains the CRASHING step's own span (closed before
        # the failure hook fired), error-annotated
        failed = [e for e in spans(evs, "step", cat="train")
                  if e.get("args", {}).get("error") == "RuntimeError"]
        assert failed, "dump is missing the failing step's span"
    finally:
        rec.uninstall()
        T.disable_tracing()
        T.default_tracer().clear()


def test_flight_recorder_uninstall_restores_hooks(tmp_path):
    from paddle_tpu.observability.flight_recorder import FlightRecorder

    prev = signal.getsignal(signal.SIGTERM)
    rec = FlightRecorder(dump_dir=str(tmp_path)).install()
    assert signal.getsignal(signal.SIGTERM) is not prev
    rec.uninstall()
    assert signal.getsignal(signal.SIGTERM) is prev
    T.disable_tracing()
    T.default_tracer().clear()


def test_flight_recorder_install_keeps_frozen_capture(tmp_path):
    """install() arms the flight capacity only on a VIRGIN ring — a
    capture the user recorded and froze with disable_tracing() must
    survive installing the recorder afterwards."""
    from paddle_tpu.observability.flight_recorder import FlightRecorder

    T.enable_tracing()
    T.default_tracer().clear()
    with T.span("precious"):
        pass
    T.disable_tracing()
    rec = FlightRecorder(dump_dir=str(tmp_path)).install(
        signals=(), catch_unhandled=False)
    try:
        names = [e["name"] for e in T.default_tracer().events()
                 if e.get("ph") == "X"]
        assert "precious" in names
    finally:
        rec.uninstall()
        T.disable_tracing()
        T.default_tracer().clear()


def test_flight_recorder_one_dump_per_unwind(tmp_path):
    """One death can pass through several hooks — a Ctrl-C unwinds via
    signal handler, failed-step hook AND excepthook.  Only the FIRST
    automatic trigger dumps; the rest are suppressed."""
    from paddle_tpu.observability import StepTimer
    from paddle_tpu.observability.flight_recorder import FlightRecorder

    rec = FlightRecorder(dump_dir=str(tmp_path)).install(
        signals=(), catch_unhandled=False)
    rec._prev_excepthook = lambda *a: None   # silence the chain
    try:
        timer = StepTimer(name="dying.loop")
        err = RuntimeError("boom")
        with pytest.raises(RuntimeError):
            with timer.step():
                raise err
        # the same exception then reaches the excepthook chain
        rec._on_unhandled(RuntimeError, err, None)
        dumps = [f for f in os.listdir(str(tmp_path))
                 if f.endswith(".trace.json")]
        assert len(dumps) == 1
        _evs, md = T.load_trace(str(tmp_path / dumps[0]))
        assert "failed step" in md["reason"]     # first trigger won
        # an EXPLICIT dump() is never guarded
        p = rec.dump(reason="manual post-mortem")
        assert p is not None and os.path.exists(p)
    finally:
        rec.uninstall()
        T.disable_tracing()
        T.default_tracer().clear()


# ---------------------------------------------------------------------------
# overhead budget
# ---------------------------------------------------------------------------


def test_disabled_tracing_is_shared_noop_and_within_budget(monkeypatch):
    """With the ring off and no profiler session, instrumentation costs
    the hot paths calls, not objects: span() returns one shared null
    object and nothing it allocates outlives it, a steady decode step
    opens six spans (five on a dense cache) and a train step two.  The
    budget is in counts (a timing ratio fails under load); the
    nanoseconds per call are measured on the chip machine (PERF.md)."""
    import gc

    import jax

    from paddle_tpu import distributed as dist
    from paddle_tpu import generation as gen
    from paddle_tpu import models
    from paddle_tpu.fluid import dygraph
    from paddle_tpu.fluid.optimizer import SGDOptimizer

    T.disable_tracing()
    tr = T.default_tracer()
    assert tr.span("a") is tr.span("b")          # shared no-op object

    def open_spans(n):
        for _ in range(n):
            with tr.span("s", cat="train", args=None) as sp:
                sp.add_args(k=1)

    open_spans(100)
    gc.collect()
    gc.disable()
    try:
        before = sys.getallocatedblocks()
        open_spans(10000)
        kept = sys.getallocatedblocks() - before
    finally:
        gc.enable()
    assert kept <= 16, "%d blocks outlive 10000 disabled spans" % kept

    opened = []
    real = tr.span

    def counting(name, **kw):
        ctx = real(name, **kw)
        opened.append((name, ctx))
        return ctx

    monkeypatch.setattr(tr, "span", counting)
    with dygraph.guard():
        np.random.seed(0)
        lm = models.TransformerLM(models.TransformerLMConfig.tiny())
        per_step = {}
        for paged in (True, False):
            engine = gen.GenerationEngine(
                lm, slots=2, max_len=64, prefill_buckets=[8],
                paged=paged, metrics_registry=MetricsRegistry())
            for _ in range(2):
                engine.submit(gen.GenerationRequest([1, 2, 3],
                                                    max_new_tokens=8))
            engine.step()                # admits both, then decodes
            del opened[:]
            engine.step()                # a steady decode step
            per_step[paged] = [name for name, _ in opened]
        assert per_step[True] == [
            "generation.step", "generation.lock_wait", "generation.grow",
            "generation.decode_dispatch", "generation.decode_fetch",
            "generation.emit"]
        assert per_step[False] == [n for n in per_step[True]
                                   if n != "generation.grow"]

        class Net(dygraph.Layer):
            def __init__(self):
                super().__init__()
                self.fc = dygraph.Linear(4, 1)

            def forward(self, x):
                return self.fc(x)

        step = dist.ShardedTrainStep(
            Net(), SGDOptimizer(0.1),
            lambda m, b: layers.reduce_mean(layers.square(m(b["x"]))),
            dist.auto_mesh(1, devices=jax.devices()[:1]), zero_stage=0)
        state = step.init()
        batch = {"x": np.ones((2, 4), np.float32)}
        state, _ = step(state, batch)    # compiles
        del opened[:]
        state, loss = step(state, batch)
        float(loss)
    assert [name for name, _ in opened] == ["train.step_dispatch",
                                            "train.batch_put"]
    assert all(ctx is T._NULL_CTX for _, ctx in opened)


# ---------------------------------------------------------------------------
# fleet: straggler detection + merged timeline
# ---------------------------------------------------------------------------


def _publish_fleet(ws, step_ms_by_rank):
    from paddle_tpu.distributed.monitor import MetricsAggregator

    aggs = {}
    for rank, ms in step_ms_by_rank.items():
        reg = MetricsRegistry()
        h = reg.histogram("train_step_ms", "t",
                          labelnames=("loop",)).labels("fit")
        for _ in range(4):
            h.observe(ms)
        aggs[rank] = MetricsAggregator(
            ws, rank, len(step_ms_by_rank), registry=reg)
        aggs[rank].publish()
    return aggs


def test_straggler_detection_flags_and_recovers(tmp_path):
    ws = str(tmp_path)
    aggs = _publish_fleet(ws, {0: 100.0, 1: 105.0, 2: 98.0, 3: 320.0})
    reader_reg = MetricsRegistry()
    from paddle_tpu.distributed.monitor import MetricsAggregator

    reader = MetricsAggregator(ws, 0, 4, registry=reader_reg)
    strag = reader.fleet_snapshot()["stragglers"]
    assert strag["ranks"] == [3]
    assert strag["ratios"]["3"] == pytest.approx(320 / 102.5, rel=0.05)
    fam = reader_reg.get("straggler_ranks")
    assert [lv for lv, _c in fam._series()] == [("3",)]
    # rank 3 recovers -> flag and gauge series clear
    reg3 = MetricsRegistry()
    h = reg3.histogram("train_step_ms", "t",
                       labelnames=("loop",)).labels("fit")
    for _ in range(4):
        h.observe(101.0)
    MetricsAggregator(ws, 3, 4, registry=reg3).publish()
    strag = reader.fleet_snapshot()["stragglers"]
    assert strag["ranks"] == [] and not strag["ratios"]
    assert fam._series() == []
    # publisher restart whose count OVERTAKES the old one within a poll
    # window: the sum went backwards, so this must re-baseline, not
    # difference two processes' sums into a negative mean
    reg3b = MetricsRegistry()
    h = reg3b.histogram("train_step_ms", "t",
                        labelnames=("loop",)).labels("fit")
    for _ in range(6):                       # count 6 > previous 4
        h.observe(50.0)                      # sum 300 < previous 404
    MetricsAggregator(ws, 3, 4, registry=reg3b).publish()
    strag = reader.fleet_snapshot()["stragglers"]
    assert strag["median_step_ms"] > 0
    assert strag["ranks"] == []
    # a single-rank fleet never self-flags
    solo = MetricsAggregator(str(tmp_path / "solo"), 0, 1,
                             registry=reg3)
    solo.publish()
    assert solo.fleet_snapshot()["stragglers"]["ranks"] == []


def test_straggler_detection_two_rank_fleet(tmp_path):
    """Leave-one-out baseline: on a 2-rank fleet each rank is compared
    against the other.  With the candidate's own mean inside the
    median, the ratio 2m/(m+fast) could never reach the default 2.0
    factor no matter how slow the straggler got."""
    from paddle_tpu.distributed.monitor import MetricsAggregator

    ws = str(tmp_path)
    _publish_fleet(ws, {0: 100.0, 1: 1000.0})
    reader = MetricsAggregator(ws, 0, 2, registry=MetricsRegistry())
    strag = reader.fleet_snapshot()["stragglers"]
    assert strag["ranks"] == [1]
    assert strag["ratios"]["1"] == pytest.approx(10.0, rel=0.01)


def test_straggler_detection_windows_recent_steps(tmp_path):
    """Detection diffs (count, sum) between snapshots: a rank that
    degrades AFTER a long healthy run is flagged at the next look, even
    while its lifetime mean is still far under the threshold."""
    from paddle_tpu.distributed.monitor import MetricsAggregator

    ws = str(tmp_path)
    hists, aggs = {}, {}
    for rank in range(3):
        reg = MetricsRegistry()
        h = reg.histogram("train_step_ms", "t",
                          labelnames=("loop",)).labels("fit")
        for _ in range(100):
            h.observe(100.0)
        hists[rank] = h
        aggs[rank] = MetricsAggregator(ws, rank, 3, registry=reg)
        aggs[rank].publish()
    reader = MetricsAggregator(ws, 0, 3, registry=MetricsRegistry())
    assert reader.fleet_snapshot()["stragglers"]["ranks"] == []
    # rank 2 hits a failing interconnect: 10 slow steps on top of 100
    # fast ones.  Lifetime mean ~127ms (ratio ~1.3, under the 2.0
    # factor) — only the windowed mean (400ms, ratio 4) catches it.
    for _ in range(10):
        hists[2].observe(400.0)
    for rank in range(3):
        if rank != 2:
            hists[rank].observe(100.0)
        aggs[rank].publish()
    strag = reader.fleet_snapshot()["stragglers"]
    assert strag["ranks"] == [2]
    assert strag["ratios"]["2"] == pytest.approx(4.0, rel=0.05)


def test_fleet_trace_merge_ranks_to_pids(tmp_path):
    ws = str(tmp_path)
    aggs = _publish_fleet(ws, {0: 100.0, 1: 100.0, 2: 300.0})
    for rank, agg in aggs.items():
        tr = T.Tracer(capacity=64, enabled=True)
        with tr.span("step", cat="train", args={"rank": rank}):
            pass
        shard = agg.publish_trace(tracer=tr)
        assert os.path.exists(shard)
    merged = aggs[0].merge_fleet_trace(
        out_path=str(tmp_path / "fleet.trace.json"))
    validate_chrome_trace(merged)
    step_pids = {e["pid"] for e in spans(merged["traceEvents"], "step")}
    assert step_pids == {0, 1, 2}           # rank -> Perfetto pid
    names = {(e["pid"], e["args"]["name"])
             for e in merged["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert names >= {(0, "rank 0"), (1, "rank 1"), (2, "rank 2")}
    # the straggler instant is stamped on the slow rank's track
    instants = [e for e in merged["traceEvents"]
                if e["ph"] == "i" and e["name"] == "straggler"]
    assert [e["pid"] for e in instants] == [2]
    assert merged["metadata"]["stragglers"]["ranks"] == [2]
    # the merged file loads like any other trace
    evs, md = T.load_trace(str(tmp_path / "fleet.trace.json"))
    assert md["stragglers"]["ranks"] == [2] and len(evs) > 0


def test_merge_traces_skips_alignment_with_unanchored_shard():
    """A shard without the wall/mono anchor pair (e.g. a bare-array
    trace) disables alignment for the whole merge: shifting only the
    anchored shards would strand them a wall-clock epoch (~54 years)
    away from the unanchored ones."""
    tr = T.Tracer(capacity=64, enabled=True)
    with tr.span("a"):
        pass
    anchored = tr.chrome_trace()
    orig_ts = sorted(e["ts"] for e in anchored["traceEvents"]
                     if "ts" in e)
    bare = [{"ph": "X", "name": "b", "ts": 10, "dur": 5,
             "pid": 99, "tid": 0}]
    merged = T.merge_traces([
        (0, bare, {}),
        (1, anchored["traceEvents"], anchored["metadata"]),
    ])
    new_ts = sorted(e["ts"] for e in merged["traceEvents"]
                    if e["pid"] == 1 and "ts" in e)
    assert new_ts == orig_ts        # nobody was shifted


def test_enable_tracing_resize_keeps_tracer_identity():
    """enable_tracing(capacity=) resizes the ring IN PLACE: loops that
    fetched default_tracer() once (fit, TrainEpochRange) must keep
    reporting to the live ring after a flight-recorder install or a
    user resize mid-run."""
    tr0 = T.default_tracer()
    try:
        tr = T.enable_tracing(capacity=128)
        assert tr is tr0 and tr0._events.maxlen == 128
        with T.span("after-resize"):
            pass
        assert any(e["name"] == "after-resize" for e in tr0.events())
    finally:
        T.disable_tracing()
        T.enable_tracing(capacity=65536)
        T.disable_tracing()
        T.default_tracer().clear()


# ---------------------------------------------------------------------------
# trace_summary CLI
# ---------------------------------------------------------------------------


def test_trace_summary_cli(tracer, tmp_path):
    with T.span("step", cat="train"):
        with T.span("executor.run", cat="executor"):
            time.sleep(0.002)
        time.sleep(0.001)
    p = tracer.save(str(tmp_path / "t.json"))
    tool = os.path.join(REPO, "tools", "trace_summary.py")
    r = subprocess.run([sys.executable, tool, p, "--json"],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout)
    rows = {row["name"]: row for row in out["top_spans_by_self_time"]}
    assert rows["executor.run"]["self_ms"] >= 2
    # parent's self-time excludes the nested child
    assert rows["step"]["self_ms"] < rows["step"]["total_ms"]
    # human output mode + unreadable-file rc 1
    r = subprocess.run([sys.executable, tool, p],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0 and "top spans by self-time" in r.stdout
    bad = tmp_path / "bad.json"
    bad.write_text("not a trace")
    r = subprocess.run([sys.executable, tool, str(bad)],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 1


# ---------------------------------------------------------------------------
# xla cost attribution unit surface
# ---------------------------------------------------------------------------


def test_cost_analysis_normalization():
    from paddle_tpu.observability import xla_cost as XC

    class FakeCompiled:
        def __init__(self, ca):
            self._ca = ca

        def cost_analysis(self):
            if isinstance(self._ca, Exception):
                raise self._ca
            return self._ca

    assert XC.cost_analysis_of(FakeCompiled(
        {"flops": 10.0, "bytes accessed": 5.0,
         "bytes accessed0{}": 3.0, "not_a_number": "x"})) == \
        {"flops": 10.0, "bytes_accessed": 5.0}
    assert XC.cost_analysis_of(FakeCompiled(None)) is None
    assert XC.cost_analysis_of(FakeCompiled(RuntimeError("no"))) is None


def test_record_mfu_math_and_peak_resolution(monkeypatch):
    from paddle_tpu.observability import xla_cost as XC

    monkeypatch.delenv(XC.PEAK_FLOPS_ENV, raising=False)
    assert XC.peak_flops(explicit=5e12) == 5e12
    monkeypatch.setenv(XC.PEAK_FLOPS_ENV, "2e12")
    assert XC.peak_flops() == 2e12
    assert XC.peak_flops(device_kind="TPU v5 lite") == 2e12  # env beats table
    monkeypatch.delenv(XC.PEAK_FLOPS_ENV)
    assert XC.peak_flops(device_kind="TPU v5 lite") == 197e12
    # the host CPU is a known device without a peak; anything the table
    # does not list is an error, never another chip's figure
    assert XC.peak_flops(device_kind="cpu") is None
    assert XC.peak_flops() is None
    with pytest.raises(XC.UnknownDeviceError, match="TPU v9"):
        XC.peak_flops(device_kind="TPU v9")

    reg = MetricsRegistry()
    mfu = XC.record_mfu("exe", flops=1e12, seconds=0.01, peak=500e12,
                        registry=reg)
    assert mfu == pytest.approx(0.2)
    series = reg.get("mfu")._series()
    assert series[0][0] == ("exe",)
    assert series[0][1].value == pytest.approx(0.2)
    # degenerate inputs and unknown peak report nothing
    assert XC.record_mfu("e", 0, 1.0, peak=1e12, registry=reg) is None
    assert XC.record_mfu("e", 1e9, 0.0, peak=1e12, registry=reg) is None
    assert XC.record_mfu("e", 1e9, 1.0, peak=None, device_kind="cpu",
                         registry=reg) is None
    with pytest.raises(XC.UnknownDeviceError):
        XC.record_mfu("e", 1e9, 1.0, device_kind="TPU v9", registry=reg)


def test_cost_of_jitted_real_executable():
    import jax

    from paddle_tpu.observability import xla_cost as XC

    f = jax.jit(lambda a, b: a @ b)
    x = np.ones((16, 16), np.float32)
    cost = XC.cost_of_jitted(f, x, x)
    assert cost and cost["flops"] >= 2 * 16 * 16 * 16 * 0.9
    assert XC.cost_of_jitted(object()) is None     # not jitted: telemetry


# ---------------------------------------------------------------------------
# bench guard regression (BENCH_r05: raw traceback, rc 1)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["init", "late"])
def test_bench_backend_failure_emits_skip_convention(mode):
    env = dict(os.environ, BENCH_FORCE_BACKEND_FAIL=mode,
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       capture_output=True, text=True, timeout=300,
                       env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["skipped"] is True
    assert "injected by BENCH_FORCE_BACKEND_FAIL" in out["reason"]
    assert ("init failed" in out["reason"]) == (mode == "init")
