"""paddle_tpu.serving drills: the fleet router under fire.

The acceptance bar (ISSUE 9): a hot swap under sustained load completes
with ZERO failed requests and a verify-gate-rejected version never
receives traffic; a killed replica loses no request and duplicates no
response (request-id accounting); overload yields 503 + Retry-After
with bounded behavior instead of queue collapse.  Every drill here
injects its fault (incubate.fault style) rather than asserting prose.
"""

import json as _json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.observability.metrics import MetricsRegistry
from paddle_tpu.serving import (
    AdmissionController,
    BatchingConfig,
    DeployError,
    Router,
    ShedError,
    TransitionError,
)
from paddle_tpu.serving.canary import canary_fraction
from paddle_tpu.serving.http_front import serve_http


# ---------------------------------------------------------------------------
# fakes + model builders
# ---------------------------------------------------------------------------


class EchoPredictor:
    """Output row j = [sum(x[j]) * scale]: responses are attributable to
    their requests (cross-wiring between coalesced requests would show
    up as a wrong value, not just a missing one)."""

    def __init__(self, scale=1.0, delay=0.0):
        self.scale = scale
        self.delay = delay

    def run(self, feed):
        if self.delay:
            time.sleep(self.delay)
        return [feed["x"].sum(axis=1, keepdims=True) * self.scale]

    def get_input_names(self):
        return ["x"]


def _router(scales=(1.0,), delay=0.0, **kw):
    """Router whose i-th DISTINCT model_dir gets scale scales[i] (every
    replica of a version shares its version's scale)."""
    mapping = {}

    def factory(model_dir):
        if model_dir not in mapping:
            mapping[model_dir] = scales[min(len(mapping),
                                            len(scales) - 1)]
        return EchoPredictor(scale=mapping[model_dir], delay=delay)

    kw.setdefault("max_batch", 4)
    kw.setdefault("batch_timeout_ms", 1)
    kw.setdefault("metrics_registry", MetricsRegistry())
    return Router(predictor_factory=factory, **kw)


def _save_fc_model(tmp_path, name, seed):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[-1, 8], append_batch_size=False)
        pred = layers.fc(layers.fc(x, 16, act="relu"), 2)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    path = str(tmp_path / name)
    fluid.io.save_inference_model(path, ["x"], [pred], exe, main)
    return path


def _corrupt_model(model_dir):
    """Drop the fetch's producing op: structurally broken, exactly what
    the analysis verify gate exists to catch."""
    import os

    path = os.path.join(model_dir, "__model__.json")
    with open(path) as f:
        pj = _json.load(f)
    pj["blocks"][0]["ops"] = pj["blocks"][0]["ops"][:-1]
    with open(path, "w") as f:
        _json.dump(pj, f)


def _fam_total(reg, name):
    fam = reg.get(name)
    if fam is None:
        return 0
    total = 0
    for _labels, child in fam._series():
        v = child.value
        if isinstance(v, (int, float)):
            total += v
    return total


# ---------------------------------------------------------------------------
# tentpole: continuous batching across replicas
# ---------------------------------------------------------------------------


def test_fleet_spreads_batches_across_replicas_and_answers_correctly():
    reg = MetricsRegistry()
    r = _router(scales=(1.0,), delay=0.005, metrics_registry=reg)
    try:
        mv = r.deploy("v1", "m", replicas=3)
        r.promote("v1")
        results = {}
        lock = threading.Lock()

        def call(i):
            x = np.full((1, 3), float(i), np.float32)
            out, = r.infer({"x": x}, request_id="rq-%d" % i, timeout=30)
            with lock:
                results[i] = float(out[0, 0])

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(60)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(results) == 60
        for i, got in results.items():
            assert got == pytest.approx(3.0 * i), (i, got)
        # all three replicas pulled work (continuous batching: whichever
        # replica frees a slot takes the next oldest group)
        fam = reg.get("serving_fleet_batches_total")
        replicas_used = {labels[2] for labels, c in fam._series()
                         if c.value > 0}
        assert len(replicas_used) == 3, replicas_used
        assert len(mv.alive_replicas) == 3
        assert _fam_total(reg, "serving_fleet_errors_total") == 0
    finally:
        r.shutdown(drain_timeout=5)


def test_oldest_first_discipline_holds_across_signatures():
    """A minority signature must not be starved by a steady stream of a
    majority signature (the PR-2 head-of-line guarantee, now at the
    router tier)."""
    r = _router(scales=(1.0,), delay=0.004)
    try:
        r.deploy("v1", "m", replicas=1)
        r.promote("v1")
        stop = threading.Event()
        errors = []

        def flood():
            x = np.zeros((1, 4), np.float32)
            while not stop.is_set():
                try:
                    r.infer({"x": x}, timeout=30)
                except Exception as e:
                    errors.append(e)
                    return

        floods = [threading.Thread(target=flood) for _ in range(3)]
        for t in floods:
            t.start()
        time.sleep(0.05)
        t0 = time.monotonic()
        out, = r.infer({"x": np.ones((1, 6), np.float32)}, timeout=5)
        minority_latency = time.monotonic() - t0
        stop.set()
        for t in floods:
            t.join(10)
        assert not errors, errors[:1]
        assert out[0, 0] == pytest.approx(6.0)
        assert minority_latency < 2.0, minority_latency
    finally:
        r.shutdown(drain_timeout=5)


# ---------------------------------------------------------------------------
# tentpole: zero-downtime hot swap + rollback-on-bad-model
# ---------------------------------------------------------------------------


def test_hot_swap_under_load_zero_failed_requests(tmp_path):
    """Real models, sustained client load, deploy + promote mid-stream:
    every request succeeds, answers come from exactly the two versions,
    the old version drains to `retired` with its replicas closed."""
    m1 = _save_fc_model(tmp_path, "m1", seed=1)
    m2 = _save_fc_model(tmp_path, "m2", seed=2)

    from paddle_tpu.inference import AnalysisConfig, create_predictor

    p1 = create_predictor(AnalysisConfig(m1))
    p2 = create_predictor(AnalysisConfig(m2))
    x_probe = np.ones((1, 8), np.float32)
    want1, = p1.run([x_probe])
    want2, = p2.run([x_probe])
    assert not np.allclose(want1, want2)   # distinguishable versions

    reg = MetricsRegistry()
    r = Router(max_batch=4, batch_timeout_ms=1, metrics_registry=reg)
    try:
        r.deploy("v1", m1, replicas=2,
                 warmup_example={"x": np.zeros((1, 8), np.float32)})
        r.promote("v1")

        failures = []
        versions_seen = set()
        n_ok = [0]
        stop = threading.Event()
        lock = threading.Lock()

        def client(k):
            i = 0
            while not stop.is_set():
                i += 1
                try:
                    outs, info = r.infer_with_details(
                        {"x": x_probe}, request_id="c%d-%d" % (k, i),
                        timeout=30)
                except Exception as e:
                    failures.append(repr(e))
                    return
                got = outs[0]
                ok1 = np.allclose(got, want1, atol=1e-5)
                ok2 = np.allclose(got, want2, atol=1e-5)
                if not (ok1 or ok2):
                    failures.append("wrong value for %s" % info)
                    return
                with lock:
                    versions_seen.add(info["version"])
                    n_ok[0] += 1

        clients = [threading.Thread(target=client, args=(k,))
                   for k in range(4)]
        for t in clients:
            t.start()
        time.sleep(0.15)                       # sustained load running
        mv2 = r.deploy("v2", m2, replicas=2,
                       warmup_example={"x": np.zeros((1, 8), np.float32)})
        assert mv2.state == "ready"
        r.promote("v2", drain_timeout=30)      # default: drain-then-retire
        time.sleep(0.15)                       # traffic now on v2
        stop.set()
        for t in clients:
            t.join(30)

        assert not failures, failures[:3]
        assert n_ok[0] > 20, n_ok
        assert versions_seen == {"v1", "v2"}, versions_seen
        v1 = r.registry.get("v1")
        assert v1.state == "retired"
        assert len(v1.alive_replicas) == 0     # drained THEN closed
        assert r.registry.stable == "v2"
        assert _fam_total(reg, "serving_fleet_errors_total") == 0
        # v2 keeps serving after the cutover
        out, info = r.infer_with_details({"x": x_probe})
        assert info["version"] == "v2"
        np.testing.assert_allclose(out[0], want2, atol=1e-5)
    finally:
        r.shutdown(drain_timeout=5)


def test_verify_gate_rejects_bad_model_and_old_version_keeps_serving(
        tmp_path):
    """The rollback-on-gate-failure guarantee: a structurally broken
    model is rejected at deploy (analysis verify gate), receives zero
    traffic, and the serving version is untouched."""
    m1 = _save_fc_model(tmp_path, "m1", seed=1)
    m_bad = _save_fc_model(tmp_path, "m_bad", seed=3)
    _corrupt_model(m_bad)

    reg = MetricsRegistry()
    r = Router(max_batch=4, batch_timeout_ms=1, metrics_registry=reg)
    try:
        r.deploy("v1", m1, replicas=1)
        r.promote("v1")
        x = np.ones((2, 8), np.float32)
        before, = r.infer({"x": x})

        with pytest.raises(DeployError, match="rejected"):
            r.deploy("v2", m_bad, replicas=1)

        v2 = r.registry.get("v2")
        assert v2.state == "rejected"
        assert v2.error
        assert v2.requests == 0                # never received traffic
        assert not v2.alive_replicas           # replicas closed
        # promotion of a rejected version is refused
        with pytest.raises(TransitionError):
            r.promote("v2")
        # old version still serving, same answers
        assert r.registry.stable == "v1"
        after, info = r.infer_with_details({"x": x})
        assert info["version"] == "v1"
        np.testing.assert_allclose(after[0], before, atol=0)
        fam = reg.get("serving_fleet_requests_total")
        v2_requests = sum(c.value for labels, c in fam._series()
                          if labels[1] == "v2")
        assert v2_requests == 0
    finally:
        r.shutdown(drain_timeout=5)


def test_promote_keep_old_enables_rollback():
    r = _router(scales=(1.0, 2.0))
    try:
        r.deploy("v1", "m1")
        r.promote("v1")
        r.deploy("v2", "m2")
        r.promote("v2", keep_old=True)
        x = np.ones((1, 3), np.float32)
        out, info = r.infer_with_details({"x": x})
        assert info["version"] == "v2" and out[0][0, 0] == 6.0
        v1 = r.registry.get("v1")
        assert v1.state == "ready"             # warm standby, not retired
        assert v1.alive_replicas
        r.rollback()
        out, info = r.infer_with_details({"x": x})
        assert info["version"] == "v1" and out[0][0, 0] == 3.0
        assert r.registry.stable == "v1"
    finally:
        r.shutdown(drain_timeout=5)


def test_refused_transitions():
    r = _router(scales=(1.0, 1.0))
    try:
        r.deploy("v1", "m1")
        with pytest.raises(TransitionError, match="unknown version"):
            r.promote("ghost")
        r.promote("v1")
        # duplicate deploy of a live version
        with pytest.raises(TransitionError, match="already exists"):
            r.deploy("v1", "m1b")
        # canary/shadow to the stable version itself
        with pytest.raises(TransitionError):
            r.set_canary("v1", 10)
        with pytest.raises(TransitionError):
            r.set_shadow("v1")
        # retire the stable version
        with pytest.raises(TransitionError, match="refusing to retire"):
            r.retire("v1")
        # rollback with nothing kept
        with pytest.raises(TransitionError, match="roll back"):
            r.rollback()
        # promote an already-serving version
        with pytest.raises(TransitionError):
            r.promote("v1")
    finally:
        r.shutdown(drain_timeout=5)


# ---------------------------------------------------------------------------
# tentpole: kill-a-replica drill (request-id accounting)
# ---------------------------------------------------------------------------


def _id_accounting_drill(r, mv, n_requests, reg):
    """Run n_requests uniquely-valued requests through the router while
    one replica dies; assert every id answered exactly once with its
    own answer and nothing errored."""
    results = {}
    lock = threading.Lock()

    def call(i):
        rid = "acct-%d" % i
        x = np.full((1, 3), float(i), np.float32)
        try:
            out, = r.infer({"x": x}, request_id=rid, timeout=30)
            with lock:
                results.setdefault(rid, []).append(float(out[0, 0]))
        except Exception as e:
            with lock:
                results.setdefault(rid, []).append("ERR %r" % e)

    threads = [threading.Thread(target=call, args=(i,))
               for i in range(n_requests)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    # exactly-once response accounting, with the RIGHT value per id
    assert len(results) == n_requests
    for i in range(n_requests):
        rid = "acct-%d" % i
        answers = results[rid]
        assert len(answers) == 1, (rid, answers)      # no duplicates
        assert answers[0] == pytest.approx(3.0 * i), (rid, answers)
    assert _fam_total(reg, "serving_fleet_errors_total") == 0
    assert _fam_total(reg, "serving_fleet_replica_deaths_total") == 1
    assert _fam_total(reg, "serving_fleet_requeued_total") >= 1
    assert len(mv.alive_replicas) == len(mv.replicas) - 1


def test_kill_a_replica_in_process_no_request_lost_or_duplicated():
    """In-process flavor: the fault plan's kill_replica event surfaces
    as ReplicaDeadError mid-request; the router detects the death,
    re-queues the in-flight group once, and every request id is
    answered exactly once."""
    from paddle_tpu.incubate.fault import FaultPlan

    reg = MetricsRegistry()
    r = Router(max_batch=2, batch_timeout_ms=1, metrics_registry=reg,
               predictor_factory=lambda d: EchoPredictor(delay=0.004))
    try:
        mv = r.deploy("v1", "m", replicas=2)
        r.promote("v1")
        # arm the drill: replica 0 dies serving its 3rd request
        plan = FaultPlan([{"kind": "kill_replica",
                           "replica": 0, "request": 3}])
        mv.replicas[0]._kill_at = plan.replica_kill_request(0)
        _id_accounting_drill(r, mv, n_requests=24, reg=reg)
    finally:
        r.shutdown(drain_timeout=5)


def test_kill_a_replica_process_level_real_sigkill(tmp_path):
    """Process flavor: a real subprocess worker dies by REAL SIGKILL
    mid-request (incubate.fault plan via env).  The router sees a dead
    pipe with an unanswered frame — the hardest crash shape — and the
    accounting still holds."""
    import os

    from paddle_tpu.incubate.fault import FaultPlan

    model = _save_fc_model(tmp_path, "m1", seed=1)
    reg = MetricsRegistry()
    r = Router(max_batch=2, batch_timeout_ms=1, metrics_registry=reg)
    try:
        plan = FaultPlan([{"kind": "kill_replica",
                           "replica": 0, "request": 1}])
        env = plan.to_env({})
        env["JAX_PLATFORMS"] = "cpu"
        mv = r.deploy("v1", model, replicas=2, kind="process", env=env)
        r.promote("v1")
        assert all(rep.kind == "process" for rep in mv.replicas)

        results = {}
        lock = threading.Lock()

        def call(i):
            rid = "proc-%d" % i
            x = np.full((1, 8), float(i) / 8.0, np.float32)
            try:
                out, = r.infer({"x": x}, request_id=rid, timeout=60)
                with lock:
                    results.setdefault(rid, []).append(out.shape)
            except Exception as e:
                with lock:
                    results.setdefault(rid, []).append("ERR %r" % e)

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(results) == 12
        for rid, answers in results.items():
            assert len(answers) == 1, (rid, answers)
            assert answers[0] == (1, 2), (rid, answers)
        assert _fam_total(reg, "serving_fleet_errors_total") == 0
        assert _fam_total(reg, "serving_fleet_replica_deaths_total") == 1
        assert len(mv.alive_replicas) == 1
        # the dead worker really is a dead PROCESS, killed by SIGKILL
        dead = [rep for rep in mv.replicas if not rep.alive][0]
        assert dead._proc.poll() == -9, dead._proc.poll()
    finally:
        r.shutdown(drain_timeout=5)


def test_request_surviving_two_deaths_fails_loudly():
    """Requeue-once, not requeue-forever: a request whose re-run also
    hits a dying replica errors out instead of looping."""
    reg = MetricsRegistry()
    r = Router(max_batch=1, batch_timeout_ms=1, metrics_registry=reg,
               predictor_factory=lambda d: EchoPredictor())
    try:
        mv = r.deploy("v1", "m", replicas=2)
        r.promote("v1")
        mv.replicas[0]._kill_at = 1            # dies on first request
        mv.replicas[1]._kill_at = 1            # and so does its backup
        with pytest.raises(RuntimeError, match="survived one replica"):
            r.infer({"x": np.ones((1, 3), np.float32)},
                    request_id="doomed", timeout=10)
        assert _fam_total(reg, "serving_fleet_replica_deaths_total") == 2
        assert not r.ready()                   # no alive replicas left
    finally:
        r.shutdown(drain_timeout=5)


# ---------------------------------------------------------------------------
# tentpole: canary + shadow
# ---------------------------------------------------------------------------


def test_canary_split_is_deterministic_and_proportional():
    r = _router(scales=(1.0, 2.0))
    try:
        r.deploy("v1", "m1", replicas=1)
        r.promote("v1")
        r.deploy("v2", "m2", replicas=1)
        r.set_canary("v2", 25.0)
        x = np.ones((1, 3), np.float32)
        routes = {}
        for i in range(200):
            rid = "cn-%d" % i
            out, info = r.infer_with_details({"x": x}, request_id=rid)
            expect = 6.0 if info["route"] == "canary" else 3.0
            assert out[0][0, 0] == pytest.approx(expect)
            assert info["route"] == (
                "canary" if canary_fraction(rid) < 0.25 else "stable")
            routes[rid] = info["route"]
        n_canary = sum(1 for v in routes.values() if v == "canary")
        assert 20 <= n_canary <= 80, n_canary   # ~25% of 200, loose CI
        # identical ids re-route identically (sticky retries)
        for rid in list(routes)[:20]:
            _, info = r.infer_with_details({"x": x}, request_id=rid)
            assert info["route"] == routes[rid]
        # graduation: promote clears the canary pointer
        r.promote("v2", keep_old=True)
        assert r.registry.canary is None
        _, info = r.infer_with_details({"x": x}, request_id="post")
        assert info["version"] == "v2" and info["route"] == "stable"
    finally:
        r.shutdown(drain_timeout=5)


def test_shadow_traffic_is_compared_never_returned():
    reg = MetricsRegistry()
    scales = iter([1.0, 1.5])     # shadow answers differ measurably
    r = Router(max_batch=4, batch_timeout_ms=1, metrics_registry=reg,
               predictor_factory=lambda d: EchoPredictor(
                   scale=next(scales)))
    try:
        r.deploy("v1", "m1")
        r.promote("v1")
        r.deploy("v2", "m2")
        r.set_shadow("v2")
        x = np.ones((1, 4), np.float32)
        for i in range(12):
            out, info = r.infer_with_details(
                {"x": x}, request_id="sh-%d" % i)
            # the client ALWAYS gets the primary's answer
            assert out[0][0, 0] == pytest.approx(4.0)
            assert info["version"] == "v1" and info["route"] == "stable"
        deadline = time.monotonic() + 10
        while (time.monotonic() < deadline and _fam_total(
                reg, "serving_fleet_shadow_compared_total") < 12):
            time.sleep(0.01)
        assert _fam_total(
            reg, "serving_fleet_shadow_compared_total") == 12
        # scale 1.5 vs 1.0 on sum=4 -> diff 2.0: every compare mismatched
        assert _fam_total(
            reg, "serving_fleet_shadow_mismatch_total") == 12
        fam = reg.get("serving_fleet_shadow_absdiff")
        diffs = [c.summary() for labels, c in fam._series()
                 if labels[1] == "v2"]
        assert diffs and diffs[0]["count"] == 12
        assert diffs[0]["max"] == pytest.approx(2.0)
        # shadow requests counted under route="shadow", never as errors
        assert _fam_total(reg, "serving_fleet_errors_total") == 0
    finally:
        r.shutdown(drain_timeout=5)


# ---------------------------------------------------------------------------
# tentpole: SLO-aware load shedding
# ---------------------------------------------------------------------------


def test_admission_controller_policy_math():
    adm = AdmissionController(max_queue_rows=10, slo_ms=100.0,
                              max_version_rows=6)
    # cold fleet admits (no evidence of overload)
    adm.check(4, 0, 0, 0.0)
    # hard queue bound
    with pytest.raises(ShedError) as ei:
        adm.check(4, 8, 2, 1000.0)
    assert ei.value.reason == "queue_full"
    assert ei.value.retry_after_s >= 1
    # per-version cap
    with pytest.raises(ShedError) as ei:
        adm.check(4, 4, 4, 1000.0)
    assert ei.value.reason == "version_cap"
    # SLO: 8 queued rows at 20 rows/s = 400ms est wait > 100ms
    with pytest.raises(ShedError) as ei:
        adm.check(1, 7, 0, 20.0)
    assert ei.value.reason == "slo"
    # same queue at a fast service rate: admitted
    adm.check(1, 7, 0, 1000.0)


def test_overload_sheds_with_retry_after_instead_of_collapsing():
    """Open-loop burst far beyond capacity: admitted requests keep
    bounded latency, the rest get ShedError with Retry-After, nothing
    errors, and the queue never exceeds its bound."""
    reg = MetricsRegistry()
    r = Router(max_batch=4, batch_timeout_ms=1,
               metrics_registry=reg,
               admission=AdmissionController(max_queue_rows=16,
                                             slo_ms=200.0),
               predictor_factory=lambda d: EchoPredictor(delay=0.02))
    try:
        r.deploy("v1", "m", replicas=1)
        r.promote("v1")
        # warm the service-rate estimate
        for _ in range(4):
            r.infer({"x": np.ones((1, 3), np.float32)}, timeout=10)

        ok_lat, shed, errors = [], [], []
        lock = threading.Lock()

        def call(i):
            t0 = time.perf_counter()
            try:
                r.infer({"x": np.ones((1, 3), np.float32)},
                        request_id="ov-%d" % i, timeout=30)
                with lock:
                    ok_lat.append(time.perf_counter() - t0)
            except ShedError as e:
                with lock:
                    shed.append(e)
            except Exception as e:
                with lock:
                    errors.append(e)

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(80)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors[:3]
        assert shed, "overload never shed"
        assert ok_lat, "everything was shed"
        for e in shed:
            assert e.retry_after_s >= 1
            assert e.reason in ("queue_full", "slo")
        # bounded behavior for admitted requests: the queue bound caps
        # the worst case at ~(16 rows / 50 rows-per-s) + service; give
        # a generous CI margin — the point is NOT 30s collapse
        assert max(ok_lat) < 5.0, max(ok_lat)
        assert _fam_total(reg, "serving_fleet_shed_total") == len(shed)
        assert _fam_total(reg, "serving_fleet_errors_total") == 0
    finally:
        r.shutdown(drain_timeout=5)


# ---------------------------------------------------------------------------
# HTTP front + operator CLI
# ---------------------------------------------------------------------------


def _req(base, path, body=None):
    if body is None:
        rq = urllib.request.Request(base + path)
    else:
        rq = urllib.request.Request(
            base + path, data=_json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(rq, timeout=30) as resp:
            return resp.status, _json.loads(resp.read()), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, _json.loads(e.read()), dict(e.headers)


def test_http_front_lifecycle_readyz_and_shedding():
    r = _router(scales=(1.0, 2.0))
    httpd = serve_http(r, port=0, block=False, install_sigterm=False)
    base = "http://127.0.0.1:%d" % httpd.server_address[1]
    try:
        assert _req(base, "/healthz")[0] == 200
        code, out, _ = _req(base, "/readyz")
        assert code == 503 and out["ready"] is False
        # predict before any promote: 503 + Retry-After, not a 500
        code, out, hdr = _req(base, "/predict",
                              {"inputs": {"x": [[1.0] * 3]}})
        assert code == 503 and "Retry-After" in hdr

        code, out, _ = _req(base, "/admin/deploy",
                            {"version": "v1", "model_dir": "m1",
                             "replicas": 2})
        assert code == 200 and out["state"] == "ready"
        assert _req(base, "/admin/promote", {"version": "v1"})[0] == 200
        assert _req(base, "/readyz")[0] == 200

        code, out, _ = _req(base, "/predict",
                            {"inputs": {"x": [[1.0] * 3]},
                             "request_id": "h1"})
        assert code == 200
        assert out["outputs"][0][0] == [pytest.approx(3.0)]
        assert out["version"] == "v1" and out["route"] == "stable"
        assert out["request_id"] == "h1" and out["trace_id"]

        # canary via admin, then graduation
        assert _req(base, "/admin/deploy",
                    {"version": "v2", "model_dir": "m2"})[0] == 200
        code, out, _ = _req(base, "/admin/canary",
                            {"version": "v2", "percent": 50})
        assert code == 200 and out["canary"]["version"] == "v2"
        # refused transitions answer 409 with refused:true
        code, out, _ = _req(base, "/admin/retire", {"version": "v1"})
        assert code == 409 and out["refused"] is True
        code, out, _ = _req(base, "/admin/promote", {"version": "ghost"})
        assert code == 409
        # malformed admin bodies answer 400
        code, out, _ = _req(base, "/admin/promote", {})
        assert code == 400
        # stats + models + metrics all live
        assert _req(base, "/stats")[0] == 200
        code, models, _ = _req(base, "/admin/models")
        assert code == 200 and models["stable"] == "v1"
        with urllib.request.urlopen(base + "/metrics", timeout=10) as resp:
            text = resp.read().decode()
        assert "serving_fleet_requests_total" in text
    finally:
        httpd.shutdown()
        r.shutdown(drain_timeout=5)


def test_serving_ctl_cli_against_live_front(capsys):
    import sys

    sys.path.insert(0, "tools")
    try:
        import serving_ctl
    finally:
        sys.path.pop(0)

    r = _router(scales=(1.0, 2.0, 3.0))
    httpd = serve_http(r, port=0, block=False, install_sigterm=False)
    base = "http://127.0.0.1:%d" % httpd.server_address[1]
    try:
        def ctl(*args):
            return serving_ctl.main(["--endpoint", base] + list(args))

        assert ctl("deploy", "-v", "v1", "--model-dir", "m1",
                   "--replicas", "2") == 0
        assert ctl("promote", "-v", "v1") == 0
        assert ctl("deploy", "-v", "v2", "--model-dir", "m2") == 0
        assert ctl("canary", "-v", "v2", "--percent", "10") == 0
        assert ctl("shadow", "-v", "v2") == 0      # canary+shadow compose
        assert ctl("shadow", "--off") == 0
        assert ctl("list") == 0
        out = capsys.readouterr().out
        assert "stable:   v1" in out
        assert "canary:   v2 @ 10.0%" in out
        # refused transitions exit rc=1 (the CI contract)
        assert ctl("retire", "-v", "v1") == 1
        err = capsys.readouterr().err
        assert "refused" in err
        assert ctl("promote", "-v", "ghost") == 1
        assert ctl("rollback") == 1                # nothing kept yet
        # promote with standby, then rollback succeeds
        assert ctl("promote", "-v", "v2", "--keep-old") == 0
        assert ctl("rollback") == 0
        # drain (alias of retire) the now-standby v2
        assert ctl("drain", "-v", "v2") == 0
        assert ctl("stats") == 0
        capsys.readouterr()
        # --json emits a machine-readable envelope
        assert serving_ctl.main(
            ["--endpoint", base, "--json", "list"]) == 0
        payload = _json.loads(capsys.readouterr().out)
        assert payload["ok"] is True and payload["status"] == 200
        assert payload["response"]["stable"] == "v1"   # rolled back
        # unreachable endpoint exits rc=1
        assert serving_ctl.main(
            ["--endpoint", "http://127.0.0.1:1", "list"]) == 1
    finally:
        httpd.shutdown()
        r.shutdown(drain_timeout=5)


def test_http_graceful_shutdown_drains_and_answers_503():
    """The in-flight request is HELD inside the replica (an event, not a
    sleep) while the shutdown starts, so "draining" is a state the test
    observes, not a window it has to hit."""
    started, release = threading.Event(), threading.Event()

    class GatedPredictor(EchoPredictor):
        def run(self, feed):
            started.set()
            assert release.wait(30), "test never released the request"
            return super().run(feed)

    r = Router(predictor_factory=lambda model_dir: GatedPredictor(),
               max_batch=4, batch_timeout_ms=1,
               metrics_registry=MetricsRegistry())
    httpd = serve_http(r, port=0, block=False, install_sigterm=False)
    base = "http://127.0.0.1:%d" % httpd.server_address[1]
    try:
        r.deploy("v1", "m", replicas=1)
        r.promote("v1")
        inflight = {}

        def slow_call():
            inflight["result"] = _req(
                base, "/predict", {"inputs": {"x": [[1.0] * 3]}})

        t = threading.Thread(target=slow_call)
        t.start()
        assert started.wait(20)            # request is in flight
        shut = threading.Thread(target=r.shutdown, kwargs={
            "drain_timeout": 30})
        shut.start()
        deadline = time.monotonic() + 20   # the drain cannot finish: the
        code = None                        # request is still held
        while code != 503 and time.monotonic() < deadline:
            code, out, _ = _req(base, "/readyz")
        assert code == 503                 # readiness flips on shutdown
        code, out, hdr = _req(base, "/predict",
                              {"inputs": {"x": [[1.0] * 3]}})
        assert code == 503 and "Retry-After" in hdr
        assert out.get("reason") == "draining"
        assert shut.is_alive() and t.is_alive()
        release.set()
        shut.join(20)
        t.join(20)
        assert not shut.is_alive() and not t.is_alive()
        # the in-flight request was drained, not dropped
        code, out, _ = inflight["result"]
        assert code == 200, out
        assert out["outputs"][0][0] == [pytest.approx(3.0)]
    finally:
        release.set()
        httpd.shutdown()


# ---------------------------------------------------------------------------
# plumbing details worth pinning
# ---------------------------------------------------------------------------


def test_batching_config_is_shared_between_server_and_router():
    """The router and InferenceServer must make IDENTICAL shape
    decisions — both delegate to BatchingConfig."""
    from paddle_tpu.inference.server import InferenceServer

    cfg = BatchingConfig(max_batch=8, ragged_dims={"x": {1: [4, 8]}})
    # signature wildcards ragged axes
    a = {"x": np.zeros((1, 3), np.float32)}
    b = {"x": np.zeros((2, 7), np.float32)}
    assert cfg.signature(a) == cfg.signature(b)
    # coalesce pads batch to ladder and ragged dim to bucket
    feed, total, real, padded = cfg.coalesce([a, b])
    assert feed["x"].shape == (4, 8)       # 3 rows -> bucket 4; len -> 8
    assert total == 3
    assert real == 1 * 3 + 2 * 7
    assert padded == 4 * 8
    # ladder_specs is the warmup cross product
    specs = cfg.ladder_specs({"x": np.zeros((1, 4), np.float32)})
    shapes = {s["x"].shape for s in specs}
    assert shapes == {(b, l) for b in (1, 2, 4, 8) for l in (4, 8)}
    # the server delegates to the same class
    srv = InferenceServer(EchoPredictor(), max_batch=8,
                          ragged_dims={"x": {1: [4, 8]}})
    assert srv._cfg.signature(a) == cfg.signature(a)
    # and the ragged-axis validation is shared
    with pytest.raises(ValueError, match="batch dim"):
        BatchingConfig(ragged_dims={"x": {0: [2]}})


def test_router_validates_requests_like_the_server():
    r = _router(scales=(1.0,))
    try:
        r.deploy("v1", "m")
        r.promote("v1")
        with pytest.raises(ValueError, match="feed names"):
            r.infer({"bogus": np.ones((1, 3), np.float32)})
        with pytest.raises(ValueError, match="batch dim"):
            r.infer({"x": np.float32(3.0)})
    finally:
        r.shutdown(drain_timeout=5)


def test_per_request_traces_carry_version_and_replica():
    from paddle_tpu import observability

    r = _router(scales=(1.0,))
    observability.enable_tracing(capacity=4096)
    try:
        r.deploy("v1", "m", replicas=1)
        r.promote("v1")
        _, info = r.infer_with_details(
            {"x": np.ones((1, 3), np.float32)}, request_id="traced")
        tracer = observability.trace.default_tracer()
        evs = [e for e in tracer.events()
               if e.get("id") == info["trace_id"]]
        assert evs, "no events for the request's trace id"
        names = {e["name"] for e in evs}
        assert {"request", "queue", "replica_run"} <= names
        root = [e for e in evs if e["name"] == "request"
                and e["ph"] == "b"][0]
        assert root["args"]["version"] == "v1"
        assert root["args"]["replica"] == "v1/r0"
        assert root["args"]["request_id"] == "traced"
    finally:
        observability.disable_tracing()
        r.shutdown(drain_timeout=5)
