"""Generation through the serving tier: fleet routing, the
replica-death requeue-once drill (`incubate.fault` kill events),
chunked HTTP token streaming, 503 + Retry-After shedding, the
generation_ctl smoke contract, and the bench skip convention.
"""

import json
import http.client
import os
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu
from paddle_tpu import models
from paddle_tpu.fluid import dygraph
from paddle_tpu.incubate.fault import FaultPlan

gen = paddle_tpu.generation
serving = paddle_tpu.serving

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = models.TransformerLMConfig.tiny()


@pytest.fixture(scope="module")
def lm():
    with dygraph.guard():
        np.random.seed(0)
        model = models.TransformerLM(CFG)
    return model


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def make_fleet(lm, replicas=2, fault_plan=None, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("prefill_buckets", [8, 16])
    kw.setdefault("max_queue", 32)
    return serving.GenerationFleet(lm, replicas=replicas,
                                   fault_plan=fault_plan, **kw)


def sample_requests(n, max_new=6):
    rng = np.random.RandomState(4)
    return [gen.GenerationRequest(
        rng.randint(0, CFG.vocab_size, int(rng.randint(2, 12))),
        max_new_tokens=max_new, request_id="s%d" % i)
        for i in range(n)]


# ---------------------------------------------------------------------------
# fleet
# ---------------------------------------------------------------------------


class TestFleet:
    def test_routes_and_matches_oracle(self, lm):
        fleet = make_fleet(lm).start()
        try:
            reqs = sample_requests(6)
            handles = [fleet.submit(r) for r in reqs]
            got = {h.request.request_id: h.result(timeout=60)
                   for h in handles}
        finally:
            fleet.stop()
        oracle = gen.sequential_oracle(
            lambda: gen.GenerationEngine(lm, slots=2, max_len=64,
                                         prefill_buckets=[8, 16]),
            reqs)
        for r, o in zip(reqs, oracle):
            assert got[r.request_id] == o
        # both replicas actually served traffic
        served = [r.engine._decode_steps for r in fleet.replicas]
        assert all(s > 0 for s in served), served

    def test_replica_death_requeues_exactly_once(self, lm):
        """Mid-generation death: replica 0 dies at decode step 3 with
        half-generated slots; every affected request restarts on the
        survivor exactly once and still matches the oracle."""
        plan = FaultPlan([], rank=0)
        plan.add("kill_replica", replica=0, request=3)
        fleet = make_fleet(lm, fault_plan=plan).start()
        try:
            reqs = sample_requests(4, max_new=8)
            handles = [fleet.submit(r) for r in reqs]
            got = {h.request.request_id: h.result(timeout=60)
                   for h in handles}
        finally:
            fleet.stop()
        assert int(fleet._m_deaths.value) == 1
        requeued = [h for h in handles if h.requeued]
        assert requeued, "the dead replica held in-flight requests"
        assert int(fleet._m_requeued.value) == len(requeued)
        oracle = gen.sequential_oracle(
            lambda: gen.GenerationEngine(lm, slots=2, max_len=64,
                                         prefill_buckets=[8, 16]),
            reqs)
        for r, o in zip(reqs, oracle):
            assert got[r.request_id] == o

    def test_death_with_no_survivor_fails_loudly(self, lm):
        """A 1-replica fleet's death leaves nowhere to requeue: every
        affected request fails LOUDLY (no hang, no silent retry)."""
        plan = FaultPlan([], rank=0)
        plan.add("kill_replica", replica=0, request=2)
        fleet = make_fleet(lm, replicas=1, fault_plan=plan).start()
        try:
            handles = [fleet.submit(r)
                       for r in sample_requests(3, max_new=10)]
            outcomes = []
            for h in handles:
                try:
                    h.result(timeout=60)
                    outcomes.append("ok")
                except RuntimeError as e:
                    outcomes.append(str(e))
        finally:
            fleet.stop()
        assert int(fleet._m_deaths.value) == 1
        assert all("no alive replicas" in o for o in outcomes), outcomes

    def test_second_death_budget_exhausted_fails_loudly(self, lm):
        """Requeue-once is a BUDGET: a handle that already survived one
        death is failed loudly by the next, never retried a third
        time (deterministic unit drill of the fleet's death hook)."""
        fleet = make_fleet(lm, replicas=2)
        req = gen.GenerationRequest([1, 2, 3], max_new_tokens=4,
                                    request_id="unlucky")
        handle = gen.RequestHandle(req)
        handle.requeued = True          # survived one death already
        failed0 = int(fleet._m_failed.value)
        fleet._requeue_affected([handle])
        with pytest.raises(RuntimeError, match="second replica"):
            handle.result(timeout=5)
        assert int(fleet._m_failed.value) == failed0 + 1
        fleet.stop()

    def test_slot_occupancy_signal(self, lm):
        fleet = make_fleet(lm, replicas=1)
        assert fleet.slot_occupancy() == 0.0
        st = fleet.stats()
        assert st["ready"] and len(st["replicas"]) == 1
        fleet.stop()


# ---------------------------------------------------------------------------
# HTTP front
# ---------------------------------------------------------------------------


class TestHttpFront:
    @pytest.fixture()
    def front(self, lm):
        fleet = make_fleet(lm, replicas=1, max_queue=2).start()
        port = free_port()
        httpd = serving.serve_generation_http(fleet, port=port,
                                              block=False)
        yield fleet, port
        httpd.shutdown()
        fleet.stop()

    def _post(self, port, body, path="/generate", timeout=60):
        conn = http.client.HTTPConnection("127.0.0.1", port,
                                          timeout=timeout)
        conn.request("POST", path, json.dumps(body),
                     {"Content-Type": "application/json"})
        return conn, conn.getresponse()

    def test_streamed_tokens_are_chunked_ndjson(self, front):
        _, port = front
        conn, resp = self._post(port, {"prompt": [5, 7, 9],
                                       "max_new_tokens": 5,
                                       "stream": True})
        assert resp.status == 200
        assert resp.getheader("Transfer-Encoding") == "chunked"
        assert "ndjson" in resp.getheader("Content-Type")
        records = []
        while True:
            line = resp.readline()
            if not line:
                break
            records.append(json.loads(line))
        conn.close()
        toks = [r for r in records if "token" in r]
        assert [r["index"] for r in toks] == list(range(5))
        done = records[-1]
        assert done["done"] and done["n_tokens"] == 5
        assert done["reason"] == "max_new_tokens"

    def test_stream_equals_sync_response(self, front):
        _, port = front
        conn, resp = self._post(port, {"prompt": [5, 7, 9],
                                       "max_new_tokens": 5,
                                       "stream": True})
        streamed = []
        while True:
            line = resp.readline()
            if not line:
                break
            rec = json.loads(line)
            if "token" in rec:
                streamed.append(rec["token"])
        conn.close()
        conn, resp = self._post(port, {"prompt": [5, 7, 9],
                                       "max_new_tokens": 5,
                                       "stream": False})
        out = json.loads(resp.read())
        conn.close()
        assert out["tokens"] == streamed

    def test_shed_answers_503_with_retry_after(self, lm):
        """Saturation is built, not raced: the scheduler loop is not
        started, so the test itself decides when slots fill and when
        the queue does (a load thread against a running tiny model
        drains faster than it can fill)."""
        fleet = make_fleet(lm, replicas=1, max_queue=2)   # no .start()
        engine = fleet.replicas[0].engine
        port = free_port()
        httpd = serving.serve_generation_http(fleet, port=port,
                                              block=False)
        long_req = {"prompt": [1, 2, 3], "max_new_tokens": 40,
                    "stream": True, "timeout": 30}
        conns = []
        try:
            # 2 slots busy on long generations ...
            conns += [self._post(port, long_req) for _ in range(2)]
            assert engine.step()
            occ = engine.occupancy()
            assert (occ["active"], occ["pending"]) == (2, 0)
            # ... + a full queue of 2
            conns += [self._post(port, long_req) for _ in range(2)]
            assert engine.occupancy()["pending"] == 2
            conn, resp = self._post(
                port, {"prompt": [1, 2], "max_new_tokens": 2,
                       "stream": False})
            retry = resp.getheader("Retry-After")
            body = json.loads(resp.read())
            conn.close()
            assert resp.status == 503
            assert body["shed"] and body["reason"] == "slots_full"
            assert retry is not None and int(retry) >= 1
            # nothing was dropped: the four admitted requests finish
            fleet.start()
            for _, stream in conns:
                done = json.loads(stream.read().splitlines()[-1])
                assert done["done"] and done["n_tokens"] == 40
        finally:
            for c, _ in conns:
                c.close()
            httpd.shutdown()
            fleet.stop()

    def test_bad_request_400(self, front):
        _, port = front
        conn, resp = self._post(port, {"prompt": []})
        assert resp.status == 400
        conn.close()

    def test_health_stats_metrics(self, front):
        _, port = front
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        for path, want in (("/healthz", 200), ("/readyz", 200),
                           ("/stats", 200), ("/metrics", 200)):
            conn.request("GET", path)
            resp = conn.getresponse()
            assert resp.status == want, path
            body = resp.read()
        conn.request("GET", "/stats")
        stats = json.loads(conn.getresponse().read())
        assert "slot_occupancy" in stats
        # PR-17: every replica exposes its paged-KV gauges — pool fill
        # and preemptions — the capacity dashboard's signals
        for rep in stats["replicas"]:
            assert rep["kv_cache"]["paged"] is True
            assert rep["kv_cache"]["blocks_free"] >= 0
            assert "blocks_used" in rep["kv_cache"]
            assert rep["preempted"] >= 0
        # the report's bytes are what the per-layer pool arrays hold
        fleet = front[0]
        for rep, replica in zip(stats["replicas"], fleet.replicas):
            cache = replica.engine.cache
            assert len(cache.arrays()) == 2 * cache.num_layers
            assert rep["kv_cache"]["bytes"] == sum(
                a.nbytes for a in cache.arrays())
            assert rep["kv_cache"]["num_blocks"] == cache.layer_shape[0]
        conn.close()


def test_router_front_mounts_generate(lm):
    """`serving.serve_http(generation_fleet=...)` serves /generate next
    to the router's data plane."""
    from paddle_tpu.serving import Router

    fleet = make_fleet(lm, replicas=1).start()
    router = Router(max_batch=4)
    port = free_port()
    httpd = serving.serve_http(router, port=port, block=False,
                               install_sigterm=False,
                               generation_fleet=fleet)
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        conn.request("POST", "/generate", json.dumps(
            {"prompt": [3, 4], "max_new_tokens": 3, "stream": False}),
            {"Content-Type": "application/json"})
        out = json.loads(conn.getresponse().read())
        assert len(out["tokens"]) == 3
        conn.request("GET", "/stats")
        stats = json.loads(conn.getresponse().read())
        assert "generation" in stats
        conn.close()
    finally:
        httpd.shutdown()
        fleet.stop()
        router.shutdown(drain_timeout=1)


# ---------------------------------------------------------------------------
# generation_ctl smoke contract
# ---------------------------------------------------------------------------


class TestCtl:
    def test_smoke_rc0_on_healthy_engine(self, lm):
        fleet = make_fleet(lm, replicas=1, max_queue=32).start()
        port = free_port()
        httpd = serving.serve_generation_http(fleet, port=port,
                                              block=False)
        try:
            r = subprocess.run(
                [sys.executable,
                 os.path.join(REPO, "tools", "generation_ctl.py"),
                 "--endpoint", "http://127.0.0.1:%d" % port, "--json",
                 "smoke", "--requests", "6", "--max-new", "4",
                 "--prompt-vocab", str(CFG.vocab_size - 1)],
                capture_output=True, text=True, timeout=120)
            assert r.returncode == 0, r.stdout + r.stderr
            out = json.loads(r.stdout)
            assert out["ok"] and out["tokens"] == 6 * 4
        finally:
            httpd.shutdown()
            fleet.stop()

    def test_kv_command_reports_pool_gauges(self, lm):
        fleet = make_fleet(lm, replicas=2).start()
        port = free_port()
        httpd = serving.serve_generation_http(fleet, port=port,
                                              block=False)
        try:
            r = subprocess.run(
                [sys.executable,
                 os.path.join(REPO, "tools", "generation_ctl.py"),
                 "--endpoint", "http://127.0.0.1:%d" % port, "--json",
                 "kv"],
                capture_output=True, text=True, timeout=60)
            assert r.returncode == 0, r.stdout + r.stderr
            out = json.loads(r.stdout)
            assert len(out["replicas"]) == 2
            for rep in out["replicas"]:
                assert rep["paged"] is True
                assert rep["blocks_free"] >= 0
                assert rep["preempted"] == 0
        finally:
            httpd.shutdown()
            fleet.stop()

    def test_check_stream_flags_drop_dup_and_missing_done(self):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import generation_ctl as ctl

        good = [{"index": 0, "token": 7}, {"index": 1, "token": 8},
                {"done": True, "n_tokens": 2}]
        assert ctl.check_stream(good)[0]
        dropped = [{"index": 0, "token": 7}, {"index": 2, "token": 8},
                   {"done": True, "n_tokens": 2}]
        ok, why, _ = ctl.check_stream(dropped)
        assert not ok and "dropped" in why
        dup = [{"index": 0, "token": 7}, {"index": 0, "token": 7},
               {"done": True, "n_tokens": 2}]
        ok, why, _ = ctl.check_stream(dup)
        assert not ok and "duplicated" in why
        ok, why, _ = ctl.check_stream([{"index": 0, "token": 7}])
        assert not ok and "without a done" in why
        restart = [{"index": 0, "token": 7},
                   {"event": "restart"},
                   {"index": 0, "token": 9}, {"index": 1, "token": 2},
                   {"done": True, "n_tokens": 2}]
        assert ctl.check_stream(restart)[0]


# ---------------------------------------------------------------------------
# bench conventions
# ---------------------------------------------------------------------------


def test_generation_bench_skip_convention():
    env = dict(os.environ, BENCH_FORCE_BACKEND_FAIL="init",
               JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--generate"],
        capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["skipped"] is True
    assert "injected by BENCH_FORCE_BACKEND_FAIL" in out["reason"]
