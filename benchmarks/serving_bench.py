"""Serving-path benchmark: shape-bucketed pipelined batching vs. the
unbucketed server on a ragged traffic mix.

Workload: requests with variable batch size AND sequence length (the
traffic shape that makes `jax.jit` over raw shapes compile one XLA
executable per unique total shape — the compile storm the bucket ladder
eliminates).  Two closed-loop runs over the SAME request list:

* baseline = pre-change behavior (`batch_buckets=False`, no ragged
  padding, depth-1 pipeline): every new coalesced shape compiles;
* optimized = bucket ladder + ragged-length ladder + AOT warmup +
  pipelined dispatch.

Plus an open-loop run (Poisson arrivals) against the optimized server
for tail-latency percentiles under un-coordinated load.

Prints ONE JSON line (driver-parseable):
{"metric", "value" (optimized req/s), "unit", "vs_baseline"
 (optimized/baseline throughput), ...detail keys...}.
On any backend-init failure prints {"skipped": true, ...} with rc 0
(bench.py convention).
"""

import json
import os
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _build_model(tmp):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[-1, -1], append_batch_size=False)
        # zero-padding-safe per-row reduction (tanh(0)=0, square(0)=0)
        out = layers.reduce_sum(layers.tanh(layers.square(x)), dim=1)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    path = os.path.join(tmp, "serving.model")
    fluid.io.save_inference_model(path, ["x"], [out], exe, main)
    return path


def _ragged_workload(n_requests, seed=11):
    rng = np.random.RandomState(seed)
    reqs = []
    for _ in range(n_requests):
        n = int(rng.randint(1, 5))          # batch 1..4
        l = int(rng.randint(4, 37))         # length 4..36 (33 values)
        reqs.append(rng.randn(n, l).astype(np.float32))
    return reqs


def _closed_loop(server, requests, n_threads=4):
    """n_threads clients issuing back-to-back; returns (req/s, [latency_s])."""
    idx = {"i": 0}
    lock = threading.Lock()
    latencies = []
    errors = []

    def client():
        while True:
            with lock:
                i = idx["i"]
                if i >= len(requests):
                    return
                idx["i"] = i + 1
            t0 = time.perf_counter()
            try:
                server.infer({"x": requests[i]}, timeout=120)
            except Exception as e:
                errors.append(e)
                return
            with lock:
                latencies.append(time.perf_counter() - t0)

    threads = [threading.Thread(target=client) for _ in range(n_threads)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if errors:
        raise RuntimeError("closed-loop client failed: %s" % errors[0])
    return len(requests) / wall, latencies


def _open_loop(server, requests, rate_rps, seed=13):
    """Poisson arrivals at rate_rps: one thread per in-flight request
    (un-coordinated open-loop load); returns client latencies."""
    rng = np.random.RandomState(seed)
    latencies = []
    lock = threading.Lock()
    errors = []

    def one(arr):
        t0 = time.perf_counter()
        try:
            server.infer({"x": arr}, timeout=120)
        except Exception as e:
            errors.append(e)
            return
        with lock:
            latencies.append(time.perf_counter() - t0)

    threads = []
    for arr in requests:
        time.sleep(float(rng.exponential(1.0 / rate_rps)))
        t = threading.Thread(target=one, args=(arr,))
        t.start()
        threads.append(t)
    for t in threads:
        t.join()
    if errors:
        raise RuntimeError("open-loop client failed: %s" % errors[0])
    return latencies


def _pct(lat_s, p):
    if not lat_s:
        return None
    s = sorted(lat_s)
    k = min(len(s) - 1, max(0, int(round((p / 100.0) * (len(s) - 1)))))
    return round(s[k] * 1e3, 3)


def main():
    # a dead backend (or any backend-init failure) must yield ONE
    # structured skip line and rc 0, never a raw traceback
    try:
        import jax

        jax.devices()
    except Exception as e:
        print(json.dumps({
            "skipped": True,
            "reason": "backend init failed: %s: %s"
                      % (type(e).__name__, str(e)[:300]),
        }))
        return 0

    from paddle_tpu.inference import AnalysisConfig, create_predictor
    from paddle_tpu.inference.server import InferenceServer

    tmp = tempfile.mkdtemp(prefix="serving_bench_")
    try:
        model = _build_model(tmp)
        n_req = int(os.getenv("SERVING_BENCH_REQUESTS", "160"))
        requests = _ragged_workload(n_req)
        max_batch = 8
        seq_buckets = [8, 16, 32, 40]

        # -- baseline: raw shapes, no padding, no pipelining ------------
        base_pred = create_predictor(AnalysisConfig(model))
        base_srv = InferenceServer(
            base_pred, max_batch=max_batch, batch_timeout_ms=2,
            batch_buckets=False, pipeline_depth=1).start()
        base_rps, base_lat = _closed_loop(base_srv, requests)
        base_compiles = base_pred.compile_count
        base_srv.stop()

        # -- optimized: bucket ladder + ragged ladder + warmup + pipe ---
        opt_pred = create_predictor(AnalysisConfig(model))
        opt_srv = InferenceServer(
            opt_pred, max_batch=max_batch, batch_timeout_ms=2,
            ragged_dims={"x": {1: seq_buckets}},
            pipeline_depth=4).start()
        t0 = time.perf_counter()
        opt_srv.warmup({"x": np.zeros((1, 8), np.float32)})
        warmup_s = time.perf_counter() - t0
        opt_rps, opt_lat = _closed_loop(opt_srv, requests)
        stats = opt_srv.summary()

        # -- open loop (Poisson) against the optimized server -----------
        open_rate = max(20.0, min(0.6 * opt_rps, 400.0))
        open_lat = _open_loop(opt_srv, requests[:120], open_rate)
        opt_srv.stop()

        result = {
            "metric": "serving_throughput_ragged",
            "value": round(opt_rps, 2),
            "unit": "req/s",
            "vs_baseline": round(opt_rps / base_rps, 2),
            "baseline_rps": round(base_rps, 2),
            "baseline_compiles": base_compiles,
            "optimized_compiles": opt_pred.compile_count,
            "warmup_s": round(warmup_s, 2),
            "closed_p50_ms": _pct(opt_lat, 50),
            "closed_p95_ms": _pct(opt_lat, 95),
            "closed_p99_ms": _pct(opt_lat, 99),
            "open_loop_rate_rps": round(open_rate, 1),
            "open_p50_ms": _pct(open_lat, 50),
            "open_p95_ms": _pct(open_lat, 95),
            "open_p99_ms": _pct(open_lat, 99),
            "baseline_p99_ms": _pct(base_lat, 99),
            "mean_padding_waste": round(
                stats["padding_waste"].get("mean", 0.0), 4),
            "mean_batch_size": round(
                stats["batch_size"].get("mean", 0.0), 2),
            "requests": n_req,
        }
        print(json.dumps(result))
        return 0
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
