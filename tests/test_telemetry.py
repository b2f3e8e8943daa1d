"""Unified telemetry subsystem (paddle_tpu.observability): registry,
exporters, step-level training telemetry, system gauges, fleet
aggregation, and the fluid.profiler metric aliases."""

import json
import threading
import urllib.request

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import observability as obs
from paddle_tpu.observability.metrics import MetricsRegistry


# ---------------------------------------------------------------------------
# registry + metric primitives
# ---------------------------------------------------------------------------


def test_registry_get_or_create_and_mismatch():
    reg = MetricsRegistry()
    c1 = reg.counter("requests_total", "reqs", labelnames=("path",))
    c2 = reg.counter("requests_total")  # help/labels taken from first
    assert c1 is c2
    with pytest.raises(ValueError, match="exists as Counter"):
        reg.gauge("requests_total")
    with pytest.raises(ValueError, match="exists as Counter"):
        reg.counter("requests_total", labelnames=("other",))


def test_labeled_children_are_independent():
    reg = MetricsRegistry()
    c = reg.counter("hits", labelnames=("k",))
    c.labels("a").inc(2)
    c.labels(k="b").inc(5)
    assert c.labels("a").value == 2
    assert c.labels("b").value == 5
    with pytest.raises(ValueError, match="call .labels"):
        c.inc()
    with pytest.raises(ValueError, match="do not match"):
        c.labels(wrong="x")


def test_counter_monotonic_and_gauge_function():
    reg = MetricsRegistry()
    c = reg.counter("n")
    with pytest.raises(ValueError, match="only go up"):
        c.inc(-1)
    g = reg.gauge("depth")
    g.set(3)
    assert g.value == 3
    g.dec()
    assert g.value == 2
    g.set_function(lambda: 42)
    assert g.value == 42
    assert "depth 42" in reg.prometheus_text()


def test_histogram_aggregates_and_percentiles():
    reg = MetricsRegistry()
    h = reg.histogram("lat", buckets=(1, 10, 100))
    assert h.percentile(50) is None
    for v in range(1, 101):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 100 and s["min"] == 1 and s["max"] == 100
    assert s["sum"] == pytest.approx(5050)
    assert 45 <= s["p50"] <= 55 and s["p99"] >= 95
    # bucket cumulativity: each bound's count includes all below it
    cum = h.cumulative_buckets()
    bounds = [b for b, _ in cum]
    counts = [c for _, c in cum]
    assert bounds == [1.0, 10.0, 100.0, float("inf")]
    assert counts == [1, 10, 100, 100]
    assert counts == sorted(counts)


# ---------------------------------------------------------------------------
# satellite: concurrent-writer thread-safety stress
# ---------------------------------------------------------------------------


def test_concurrent_writer_stress_exact_counts():
    """Serving hits Counter/Histogram from dispatch + completion threads;
    increments and observations must never be lost."""
    reg = MetricsRegistry()
    c = reg.counter("stress_total")
    h = reg.histogram("stress_ms")
    n_threads, n_iter = 8, 2000
    barrier = threading.Barrier(n_threads)

    def writer(tid):
        barrier.wait()
        for i in range(n_iter):
            c.inc()
            h.observe(float(i % 97))

    ts = [threading.Thread(target=writer, args=(t,))
          for t in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == n_threads * n_iter
    assert h.count == n_threads * n_iter
    expected_sum = n_threads * sum(float(i % 97) for i in range(n_iter))
    assert h.summary()["sum"] == pytest.approx(expected_sum)
    # cumulative buckets account for every observation exactly once
    assert h.cumulative_buckets()[-1][1] == n_threads * n_iter


def test_json_snapshot_stable_under_concurrent_mutation():
    reg = MetricsRegistry()
    c = reg.counter("live_total", labelnames=("w",))
    h = reg.histogram("live_ms")
    stop = threading.Event()

    def writer(tid):
        while not stop.is_set():
            c.labels(str(tid)).inc()
            h.observe(tid)

    ts = [threading.Thread(target=writer, args=(t,)) for t in range(4)]
    for t in ts:
        t.start()
    try:
        last = {}
        for _ in range(200):
            snap = reg.snapshot()
            json.dumps(snap)               # always serializable
            for s in snap["live_total"]["series"]:
                w = s["labels"]["w"]
                assert s["value"] >= last.get(w, 0)  # counters never regress
                last[w] = s["value"]
            reg.prometheus_text()          # and text never raises
    finally:
        stop.set()
        for t in ts:
            t.join()


# ---------------------------------------------------------------------------
# satellite: Prometheus exposition golden-format
# ---------------------------------------------------------------------------


def _golden_registry():
    reg = MetricsRegistry()
    c = reg.counter("http_requests_total", "Total requests",
                    labelnames=("path", "code"))
    c.labels('/a"b\\c\nd', "200").inc(3)
    h = reg.histogram("lat_ms", "Latency", buckets=(1, 2.5, 5))
    for v in (0.5, 2, 2, 7):
        h.observe(v)
    g = reg.gauge("temp", "Temp")
    g.set(1.5)
    return reg


def test_prometheus_text_golden():
    golden = "\n".join([
        "# HELP http_requests_total Total requests",
        "# TYPE http_requests_total counter",
        'http_requests_total{path="/a\\"b\\\\c\\nd",code="200"} 3',
        "# HELP lat_ms Latency",
        "# TYPE lat_ms histogram",
        'lat_ms_bucket{le="1"} 1',
        'lat_ms_bucket{le="2.5"} 3',
        'lat_ms_bucket{le="5"} 3',
        'lat_ms_bucket{le="+Inf"} 4',
        "lat_ms_sum 11.5",
        "lat_ms_count 4",
        "# HELP temp Temp",
        "# TYPE temp gauge",
        "temp 1.5",
    ]) + "\n"
    assert _golden_registry().prometheus_text() == golden


def test_prometheus_text_sum_count_consistency():
    """_count equals the +Inf bucket; buckets are monotone; _sum matches
    the observations — parsed back out of the TEXT, not the objects."""
    text = _golden_registry().prometheus_text()
    buckets, total, count = [], None, None
    for line in text.splitlines():
        if line.startswith("lat_ms_bucket"):
            buckets.append(int(line.rsplit(" ", 1)[1]))
        elif line.startswith("lat_ms_sum"):
            total = float(line.rsplit(" ", 1)[1])
        elif line.startswith("lat_ms_count"):
            count = int(line.rsplit(" ", 1)[1])
    assert buckets == sorted(buckets)
    assert buckets[-1] == count == 4
    assert total == pytest.approx(0.5 + 2 + 2 + 7)


def test_prometheus_name_sanitization():
    reg = MetricsRegistry()
    reg.counter("io.step-wait ms").inc(1)
    text = reg.prometheus_text()
    assert "io_step_wait_ms 1" in text


# ---------------------------------------------------------------------------
# exporters: HTTP endpoint
# ---------------------------------------------------------------------------


def test_serve_metrics_http_endpoint():
    reg = MetricsRegistry()
    reg.counter("scraped_total", "scrapes").inc(7)
    httpd = obs.serve_metrics_http(registry=reg, port=0)
    try:
        port = httpd.server_address[1]
        body = urllib.request.urlopen(
            "http://127.0.0.1:%d/metrics" % port, timeout=10).read().decode()
        assert "# TYPE scraped_total counter" in body
        assert "scraped_total 7" in body
        jbody = json.loads(urllib.request.urlopen(
            "http://127.0.0.1:%d/metrics.json" % port, timeout=10).read())
        assert jbody["scraped_total"]["series"][0]["value"] == 7
    finally:
        httpd.shutdown()


def test_inference_server_metrics_endpoint():
    """The serving HTTP front end answers /metrics with the registry
    text exposition, and /stats keeps its PR-2 shape."""
    from paddle_tpu.inference.server import InferenceServer

    class FakePredictor:
        def run(self, feed):
            return [np.asarray(v).sum(axis=tuple(range(1, np.asarray(v).ndim)))
                    if np.asarray(v).ndim > 1 else np.asarray(v)
                    for v in feed.values()]

    reg = MetricsRegistry()
    server = InferenceServer(FakePredictor(), max_batch=4,
                             batch_timeout_ms=1.0, name="t-metrics",
                             metrics_registry=reg).start()
    try:
        server.infer({"x": np.ones((2, 3), np.float32)})
        httpd = server.serve_http(port=0, block=False)
        try:
            port = httpd.server_address[1]
            body = urllib.request.urlopen(
                "http://127.0.0.1:%d/metrics" % port,
                timeout=10).read().decode()
            assert 'serving_requests_total{server="t-metrics"} 1' in body
            assert "serving_latency_ms_bucket" in body
            stats = json.loads(urllib.request.urlopen(
                "http://127.0.0.1:%d/stats" % port, timeout=10).read())
            # PR-2 backward-compatible keys
            for k in ("requests", "batches", "errors", "queue_depth",
                      "batch_size", "latency_ms", "compile_count"):
                assert k in stats
            assert stats["requests"] == 1
        finally:
            httpd.shutdown()
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# fluid.profiler aliases + reset_profiler
# ---------------------------------------------------------------------------


def test_profiler_metric_aliases_are_shared_impl():
    from paddle_tpu.fluid import profiler

    assert profiler.Counter is obs.Counter
    assert profiler.Histogram is obs.Histogram
    # standalone construction (the PR-2 call-site shape) still works
    c = profiler.Counter("x")
    c.inc(2)
    assert c.summary() == {"name": "x", "value": 2}
    h = profiler.Histogram("y", max_samples=8)
    for v in range(100):
        h.observe(v)
    assert h.count == 100 and len(h._samples) == 8


def test_reset_profiler_resets_registry_metrics():
    from paddle_tpu.fluid import profiler

    reg = obs.default_registry()
    c = reg.counter("reset_probe_total")
    h = reg.histogram("reset_probe_ms")
    c.inc(5)
    h.observe(1.0)
    assert c.value == 5 and h.count == 1
    profiler.reset_profiler()
    assert c.value == 0 and h.count == 0
    # families stay registered: the same objects keep working
    c.inc()
    assert reg.counter("reset_probe_total").value == 1


def test_profiler_contextmanager_roundtrip(tmp_path, capsys):
    """start -> RecordEvent -> stop via the contextmanager: the
    aggregated table prints with real rows and the chrome trace lands."""
    from paddle_tpu.fluid import layers, profiler

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[-1, 8], append_batch_size=False)
        y = layers.reduce_sum(layers.fc(x, size=4))
    exe = fluid.Executor()
    out_json = tmp_path / "trace.json"
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        with profiler.profiler(sorted_key="calls",
                               profile_path=str(out_json),
                               log_dir=str(tmp_path / "tr")):
            with profiler.RecordEvent("roundtrip_region"):
                exe.run(main, feed={"x": np.ones((2, 8), np.float32)},
                        fetch_list=[y])
    out = capsys.readouterr().out
    assert "Profiling Report" in out
    rows = [l for l in out.splitlines() if "%" in l]
    assert rows, out
    data = json.loads(out_json.read_text())
    assert any("roundtrip_region" in str(e.get("name"))
               for e in data["traceEvents"])


# ---------------------------------------------------------------------------
# step-level training telemetry
# ---------------------------------------------------------------------------


def _toy_model():
    import paddle_tpu.hapi as hp
    from paddle_tpu.fluid import dygraph, layers

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


def test_fit_emits_step_breakdown_that_sums(tmp_path):
    """Acceptance: data_wait + compile + compute + host_overhead ≈
    step_time for every step of a toy Model.fit, compile is detected on
    the first (cache-miss) step, and the scalar log carries it all."""
    m = _toy_model()
    rng = np.random.RandomState(0)
    x = rng.randn(24, 4).astype("float32")
    y = np.zeros((24, 1), np.int64)
    log = tmp_path / "scalars.jsonl"
    m.fit((x, y), batch_size=8, epochs=2, verbose=0, shuffle=False,
          scalar_log=str(log))
    timer = m.step_timer
    assert timer is not None and len(timer.history) == 6
    for bd in timer.history:
        parts = (bd["data_wait"] + bd["compile"] + bd["compute"]
                 + bd["host_overhead"])
        assert parts == pytest.approx(bd["step_time"], rel=1e-6, abs=1e-3)
        assert bd["step_time"] > 0
    # steady state executes without compiling
    assert timer.history[-1]["compute"] > 0
    # the first step pays the trace+XLA compile; steady state does not
    assert timer.history[0]["compile"] > 0
    assert timer.history[0]["compiles"] >= 1
    assert timer.history[-1]["compiles"] == 0
    assert timer.history[-1]["compile"] <= timer.history[0]["compile"]
    # always-on aggregates landed in the shared registry
    reg = obs.default_registry()
    steps = reg.counter("train_steps_total",
                        labelnames=("loop",)).labels("hapi.fit")
    assert steps.value >= 6
    h = reg.histogram("train_step_ms", labelnames=("loop",))
    assert h.labels("hapi.fit").count >= 6
    # scalar JSONL log: one line per component per step
    rows = obs.ScalarWriter.read(str(log))
    tags = {r["tag"] for r in rows}
    for comp in ("data_wait", "compile", "compute", "host_overhead",
                 "step_time"):
        assert "hapi.fit/%s_ms" % comp in tags
    by_step = [r for r in rows if r["tag"] == "hapi.fit/step_time_ms"]
    assert [r["step"] for r in by_step] == list(range(6))


def test_fit_dygraph_breakdown_attributes_compute(tmp_path):
    """Eager mode has no Executor.run; fit itself must still split the
    step into compile/compute rather than dumping it all into
    host_overhead."""
    import paddle_tpu.hapi as hp
    from paddle_tpu.fluid import dygraph

    with dygraph.guard():
        class Net(dygraph.Layer):
            def __init__(self):
                super().__init__()
                self.fc = dygraph.Linear(4, 3)

            def forward(self, x):
                return self.fc(x)

        m = hp.Model(Net())

        def loss_fn(pred, y):
            from paddle_tpu.fluid import layers

            return layers.reduce_mean(layers.square(
                pred - layers.cast(y, "float32")))

        m.prepare(optimizer=fluid.optimizer.SGDOptimizer(0.01),
                  loss_function=loss_fn)
        x = np.zeros((16, 4), np.float32)
        y = np.zeros((16, 1), np.int64)
        m.fit((x, y), batch_size=8, epochs=1, verbose=0)
    hist = m.step_timer.history
    assert len(hist) == 2
    for bd in hist:
        parts = (bd["data_wait"] + bd["compile"] + bd["compute"]
                 + bd["host_overhead"])
        assert parts == pytest.approx(bd["step_time"], rel=1e-6, abs=1e-3)
    # the eager step's device work lands in compile+compute, not in the
    # host_overhead residual
    assert hist[-1]["compile"] + hist[-1]["compute"] > 0


def test_fit_telemetry_off():
    m = _toy_model()
    x = np.zeros((8, 4), np.float32)
    y = np.zeros((8, 1), np.int64)
    m.fit((x, y), batch_size=8, epochs=1, verbose=0, telemetry=False)
    assert m.step_timer is None


def test_step_timer_nests_and_cancels():
    timer = obs.StepTimer(name="nest-test", registry=MetricsRegistry())
    with timer.step() as rec:
        obs.record_component("compute", 0.01)
        assert rec.components["compute"] == pytest.approx(0.01)
    assert timer.last_breakdown["compute"] == pytest.approx(10.0)
    with timer.step() as rec:
        rec.cancel()
    assert len(timer.history) == 1           # cancelled: not recorded
    # outside a step, recording is a no-op (never raises)
    obs.record_component("compute", 1.0)
    obs.record_compile(1.0)


def test_executor_records_compile_then_cached_runs(tmp_path):
    """Cache-miss runs bill compile; cached runs are compute-only."""
    from paddle_tpu.fluid import layers

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[-1, 4], append_batch_size=False)
        out = layers.reduce_sum(layers.fc(x, size=2))
    exe = fluid.Executor()
    timer = obs.StepTimer(name="exe-test", registry=MetricsRegistry())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        feed = {"x": np.ones((2, 4), np.float32)}
        with timer.step():
            exe.run(main, feed=feed, fetch_list=[out])
        first = timer.last_breakdown
        for _ in range(2):                   # warm the donation variants
            exe.run(main, feed=feed, fetch_list=[out])
        with timer.step():
            exe.run(main, feed=feed, fetch_list=[out])
        cached = timer.last_breakdown
    assert first["compile"] > 0 and first["compiles"] >= 1
    assert cached["compiles"] == 0
    assert cached["compile"] == 0.0
    assert cached["compute"] > 0


# ---------------------------------------------------------------------------
# system gauges + checkpoint wiring
# ---------------------------------------------------------------------------


def test_system_metrics_sampler_cpu_graceful():
    reg = MetricsRegistry()
    s = obs.SystemMetricsSampler(registry=reg, interval_s=0.05)
    out = s.sample_once()
    # CPU jax: no device memory stats — but host metrics still land
    assert "host_rss_bytes" in out
    assert out["host_rss_bytes"] > 0
    assert "jax_live_arrays" in out
    assert reg.counter("system_metrics_samples_total").value == 1
    with s:                                   # background thread runs
        import time

        time.sleep(0.15)
    assert reg.counter("system_metrics_samples_total").value >= 2
    assert "host_rss_bytes" in reg.prometheus_text()


def test_collection_pauses_report_to_every_holder_and_leave_no_hook():
    """One hook however many hold it; each collection is one observation
    in every holder's registry and one ring span on the thread that
    collected, once drained; the hook goes with the last holder."""
    import gc
    import threading

    from paddle_tpu.observability.system import CollectionPauses

    pauses = CollectionPauses("test.gc", "test_gc_pause_ms", cat="test")
    regs = [MetricsRegistry(), MetricsRegistry()]
    hooks = len(gc.callbacks)
    tr = obs.enable_tracing()
    tr.clear()
    gc.disable()
    try:
        for reg in regs + regs[:1]:
            pauses.acquire(reg)
        assert len(gc.callbacks) == hooks + 1
        gc.collect()
        pauses.drain()
        spans = [e for e in tr.events() if e.get("name") == "test.gc"]
        for reg in regs:
            assert reg.get("test_gc_pause_ms").summary()["count"] == 1
        pauses.release(regs[0])
        pauses.release(regs[1])
        assert len(gc.callbacks) == hooks + 1   # regs[0] still holds it
        pauses.release(regs[0])
    finally:
        gc.enable()
        obs.disable_tracing()
        tr.clear()
    assert len(gc.callbacks) == hooks
    assert [(s["args"]["generation"], s["tid"], s["cat"]) for s in spans] \
        == [(2, threading.get_ident(), "test")]


def test_checkpoint_save_durations_wired(tmp_path):
    from paddle_tpu.fluid import layers
    from paddle_tpu.incubate.checkpoint import train_epoch_range

    reg = obs.default_registry()
    saves0 = reg.counter("checkpoint_saves_total").value
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[-1, 4], append_batch_size=False)
        loss = layers.reduce_mean(layers.fc(x, size=2))
        fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        for epoch in train_epoch_range(
                2, checkpoint_dir=str(tmp_path), main_program=main,
                async_save=False):
            exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                    fetch_list=[loss])
    assert reg.counter("checkpoint_saves_total").value >= saves0 + 2
    assert reg.histogram("checkpoint_save_ms").count >= 2
    assert reg.histogram("checkpoint_commit_ms").count >= 2
    assert reg.histogram(
        "train_epoch_ms", labelnames=("loop",)).labels("acp").count >= 2


def test_async_checkpoint_snapshot_metric(tmp_path):
    from paddle_tpu.fluid import layers
    from paddle_tpu.incubate.checkpoint import TrainEpochRange

    reg = obs.default_registry()
    snap_h = reg.histogram("checkpoint_snapshot_ms")
    n0 = snap_h.count
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[-1, 4], append_batch_size=False)
        loss = layers.reduce_mean(layers.fc(x, size=2))
        fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        r = TrainEpochRange(1, checkpoint_dir=str(tmp_path),
                            main_program=main, async_save=True)
        for _ in r:
            exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                    fetch_list=[loss])
        r.wait()
    assert snap_h.count >= n0 + 1
    assert reg.gauge("checkpoint_save_in_flight").value == 0


# ---------------------------------------------------------------------------
# fleet aggregation (distributed/monitor.py)
# ---------------------------------------------------------------------------


def test_metrics_aggregator_fleet_min_max_mean(tmp_path):
    from paddle_tpu.distributed.monitor import MetricsAggregator

    ws = str(tmp_path)
    regs = [MetricsRegistry() for _ in range(3)]
    for i, reg in enumerate(regs):
        reg.counter("steps_total").inc(10 * (i + 1))      # 10, 20, 30
        h = reg.histogram("step_ms")
        for v in (float(i + 1),) * 4:                      # mean = i+1
            h.observe(v)
    aggs = [MetricsAggregator(ws, i, 3, registry=regs[i])
            for i in range(3)]
    for a in aggs:
        a.publish()
    fleet = aggs[0].fleet_snapshot()
    assert fleet["ranks_reporting"] == [0, 1, 2]
    s = fleet["series"]["steps_total"]
    assert s["min"] == 10 and s["max"] == 30 and s["mean"] == 20
    hs = fleet["series"]["step_ms"]
    assert hs["min"] == 1 and hs["max"] == 3 and hs["mean"] == 2
    assert hs["total_count"] == 12 and hs["total_sum"] == pytest.approx(24)
    # a missing rank never blocks the view
    partial = MetricsAggregator(ws + "/other", 0, 2,
                                registry=regs[0])
    partial.publish()
    view = partial.fleet_snapshot()
    assert view["ranks_reporting"] == [0] and view["expected_ranks"] == 2


def test_pipeline_stats_instances_independent_and_scrapeable():
    from paddle_tpu.io import PipelineStats

    reg = MetricsRegistry()
    a = PipelineStats(name="io", registry=reg)
    b = PipelineStats(name="io", registry=reg)
    a.batches.inc(3)
    b.batches.inc(1)
    assert a.batches.value == 3 and b.batches.value == 1
    assert a.summary()["batches"] == 3        # back-compat shape
    assert a.summary()["name"] == "io"
    text = reg.prometheus_text()
    assert 'io_batches_total{pipeline="%s"} 3' % a.instance_label in text
    assert 'io_batches_total{pipeline="%s"} 1' % b.instance_label in text


# ---------------------------------------------------------------------------
# ScalarWriter
# ---------------------------------------------------------------------------


def test_scalar_writer_roundtrip_and_append(tmp_path):
    p = tmp_path / "log" / "scalars.jsonl"
    with obs.ScalarWriter(p) as w:
        for i in range(5):
            w.add_scalar("loss", 1.0 / (i + 1), i)
        w.add_scalars("sys", {"rss": 1.0, "cpu": 2.0}, 0)
    rows = obs.ScalarWriter.read(str(p))
    assert len(rows) == 7
    assert [r["value"] for r in rows if r["tag"] == "loss"] == \
        [pytest.approx(1.0 / (i + 1)) for i in range(5)]
    assert {r["tag"] for r in rows} == {"loss", "sys/rss", "sys/cpu"}
    # append-on-resume: a second writer extends the same file
    with obs.ScalarWriter(p) as w:
        w.add_scalar("loss", 0.1, 5)
    assert len(obs.ScalarWriter.read(str(p))) == 8


# ---------------------------------------------------------------------------
# host-embedding + streaming observability (PR-14): labeled metric
# families and trace spans for the online-learning hot path
# ---------------------------------------------------------------------------


def test_host_embedding_metrics_and_spans():
    """pull/push/exchange ms, exchange bytes, unique-id ratio, cache
    hit rate + staleness, pipeline conflicts: all land as labeled PR-4
    families, and the pull/push spans hit the PR-6 tracer."""
    from paddle_tpu.fluid.host_embedding import HostEmbedding
    from paddle_tpu.observability import trace as trace_mod

    reg = MetricsRegistry()
    t = HostEmbedding("obs_t", 500, 4, optimizer="sgd")
    t.enable_stats(registry=reg)
    t.attach_cache(16)
    tracer = trace_mod.enable_tracing()
    try:
        ids = np.asarray([[1, 2, 2, 7]], np.int64)
        _p, _l, uniq = t.pull(ids)
        t.push(uniq, np.ones((len(uniq), 4), np.float32))
        t.pull(ids)                         # all cached now: hits
    finally:
        trace_mod.disable_tracing()

    snap = reg.snapshot()

    def one(name):
        fam = snap[name]
        series, = fam["series"]
        assert series["labels"] == {"table": t.stats.instance_label}
        return series

    assert one("hostemb_pull_ms")["count"] == 2
    assert one("hostemb_push_ms")["count"] == 1
    assert one("hostemb_exchange_ms")["count"] >= 1
    assert one("hostemb_exchange_bytes_total")["value"] > 0
    # 4 ids, 3 unique, observed once per pull
    ur = one("hostemb_unique_ratio")
    assert ur["count"] == 2 and ur["sum"] == pytest.approx(1.5)
    assert one("hostemb_cache_misses_total")["value"] == 3
    assert one("hostemb_cache_hits_total")["value"] == 3
    assert one("hostemb_cache_hit_rate")["value"] == pytest.approx(0.5)
    assert one("hostemb_cache_staleness_steps")["count"] == 1
    names = [e["name"] for e in tracer.events() if e.get("ph") == "X"]
    assert "hostemb.pull" in names and "hostemb.push" in names
    # label released on close so the next instance gets a fresh child
    t.stats.close()


def test_streaming_delta_lag_and_window_metrics(tmp_path):
    """The streaming loop's delta-checkpoint lag gauge + window
    families land on the registry (the freshness loop's dashboards)."""
    from paddle_tpu import streaming
    from paddle_tpu.fluid.host_embedding import HostEmbedding

    reg = MetricsRegistry()
    table = HostEmbedding("lag_t", 100, 4, optimizer="sgd")
    ck = streaming.DeltaCheckpointer(str(tmp_path / "ck"), [table])

    class _Sess:
        def run(self, feed, fetch_list=None, lr=None):
            table.push(np.unique(feed["ids"]),
                       np.ones((len(np.unique(feed["ids"])), 4),
                               np.float32))
            return [np.float32(0.5)]

    feeds = [{"ids": np.arange(i, i + 4, dtype=np.int64).reshape(1, 4)}
             for i in range(6)]
    tr = streaming.StreamingTrainer(
        _Sess(), feeds, ["loss"], window_events=2,
        checkpoint=ck, metrics_registry=reg)
    report = tr.run()
    tr.close()
    assert len(report.windows) == 3
    snap = reg.snapshot()

    def val(name, key="value"):
        return snap[name]["series"][0][key]

    assert val("streaming_events_total") == 6
    assert val("streaming_steps_total") == 6
    assert val("streaming_windows_total") == 3
    assert val("streaming_window_loss") == pytest.approx(0.5)
    assert val("streaming_events_per_s") > 0
    # the lag gauge ticked after the first commit
    assert val("streaming_delta_lag_s") >= 0
    assert report.checkpoints
