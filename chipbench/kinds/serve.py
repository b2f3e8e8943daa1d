"""kind "serve": generation served over HTTP at a fixed open-loop rate
(cut down from `chip_smoke.py` ``serve_once``, which ran on the chip in
PR 22).

The parent holds the chip: it builds the model, the `GenerationFleet` and
the HTTP front, warms the shapes the traffic can reach, and then lets
`chipbench.loadgen`, a child process without JAX, send the schedule
`chipbench.traffic` drew from the seed.  Arrivals start ``ramp_s`` before
the window, longer than the longest request lives, so that the window
opens on steady occupancy; the requests due inside the window are the
sample.  Once the window has closed and the server is down, a sample of
what it served is held to the plain reference."""

import gc
import http.client
import importlib
import json
import os
import shutil
import subprocess
import sys
import threading
import time

from chipbench import common, stats, traffic
from chipbench.common import held, need, say


def post(port, body, timeout=900.0):
    """One streamed request from the parent (checks and warm-up only)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/generate", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, [json.loads(line) for line in resp
                             if line.strip()]
    finally:
        conn.close()


def served_length(pair):
    """Prompt and served tokens of a ``(prompt, record)`` pair."""
    return len(pair[0]) + len(pair[1]["tokens"])


def check_against_reference(builder, model, config, mix, finished, seed,
                            checks):
    """What the timed path served, against the plain reference: a sample
    of the window's finished greedy requests, drawn from the seed with
    the longest in it, each run once through the reference with its
    served tokens (teacher-forced), and the engine's log-probability of
    every served token compared with the reference's.  ``finished``:
    ``(prompt, load generator's record)`` of the greedy requests that
    came back whole.  Run once the window has closed."""
    import random

    chk = mix["check"]
    if not need(finished, "the window finished no greedy request to "
                          "compare with the reference"):
        return False
    longest = max(finished, key=served_length)
    rest = [pair for pair in finished if pair is not longest]
    picked = [longest] + random.Random(seed).sample(
        rest, min(chk["requests"] - 1, len(rest)))
    got = [rec["logprobs"] for _, rec in picked]
    want = [builder.reference_logprobs(
        model, config, [(prompt, rec["tokens"])],
        -(-served_length((prompt, rec)) // chk["pad_multiple"])
        * chk["pad_multiple"])[0] for prompt, rec in picked]
    worst = max(abs(a - b) for g, w in zip(got, want) for a, b in zip(g, w))
    say("reference", requests=len(picked), of_finished_greedy=len(finished),
        served_tokens=sum(len(w) for w in want),
        longest_tokens=served_length(longest),
        max_logprob_diff=worst, atol=builder.LOGPROB_ATOL,
        engine_logprobs=got[0][:4], reference_logprobs=want[0][:4])
    return held(checks, "served_logprob_diff_max", worst, builder.LOGPROB_ATOL,
                "the engine's log-probabilities of the tokens it served "
                "in the window differ from the plain reference's")


def run(ctx):
    import random

    import jax

    from paddle_tpu import serving

    config, mix, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    builder = importlib.import_module(config["builder"])
    rng = random.Random(seed)
    run_dir = os.path.join(ctx["root"], ".chipbench_run",
                           ctx["cell"]["name"])
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)

    model = builder.build(config, seed)
    ctx["mark"]("model")
    fleet = serving.GenerationFleet(model, name="chipbench",
                                    **config["serving"]).start()
    server = serving.serve_generation_http(fleet, port=0, block=False)
    port = server.server_address[1]
    engine = fleet.replicas[0].engine
    ctx["mark"]("fleet-and-front")
    child = sampler_stop = None
    try:
        ok = builder.holds_stated_precision(config, engine.stats()["cache"])
        # warm the prefill shapes this traffic can reach
        for i, plen in enumerate(mix["warmup_prompt_tokens"]):
            status, records = post(port, {
                "request_id": "warm-%d" % i, "max_new_tokens": 2,
                "prompt": [rng.randrange(config["vocab_size"])
                           for _ in range(plen)]})
            ok &= need(status == 200 and records[-1].get("done"),
                       "warm-up request of %d tokens answered %s"
                       % (plen, status))
        ctx["mark"]("warm-up-requests")
        say("warmup", executables=engine.stats()["executables"],
            cache=engine.stats()["cache"],
            dispatch=common.dispatch_lines())

        plan = traffic.requests(mix, config["vocab_size"], seed,
                                ctx["seconds"])
        start_at = time.monotonic() + mix["generator_start_s"]
        w0 = start_at + mix["ramp_s"]
        w1 = w0 + ctx["seconds"]
        give_up_at = w1 + mix["drain_s"]
        schedule = os.path.join(run_dir, "schedule.json")
        out_path = os.path.join(run_dir, "records.json")
        with open(schedule, "w") as f:
            json.dump({"port": port, "start_at": start_at,
                       "give_up_at": give_up_at, "requests": plan}, f)
        env = dict(os.environ, JAX_PLATFORMS="cpu")     # it imports no JAX
        child = subprocess.Popen(
            [sys.executable, "-m", "chipbench.loadgen", "--schedule",
             schedule, "--out", out_path], cwd=ctx["root"], env=env)

        occupancy, pool_live, sampler_stop = [], [], threading.Event()

        def sample():
            while not sampler_stop.wait(mix["occupancy_every_s"]):
                now = time.monotonic()
                if w0 <= now < w1:
                    occ = engine.occupancy()
                    occupancy.append((occ["active"] + occ["chunking"])
                                     / occ["slots"])
                    kv = engine.cache.describe()
                    if "blocks_used" in kv:     # a paged cache
                        pool_live.append(kv["blocks_used"] / (
                            kv["blocks_used"] + kv["blocks_free"]))

        sampler = threading.Thread(target=sample, daemon=True,
                                   name="chipbench-occupancy")
        sampler.start()

        time.sleep(max(0.0, w0 - time.monotonic()))
        ctx["mark"]("schedule-and-ramp")
        common.clear_histograms()
        before = common.snapshot()
        setup_s = time.perf_counter() - ctx["t0"]
        traced = None
        if ctx["trace"]:
            t_on = w0 + 0.3 * ctx["seconds"]
            time.sleep(max(0.0, t_on - time.monotonic()))
            common.start_trace(ctx["trace_dir"])
            time.sleep(min(mix["traced_s"], max(0.0, w1 - time.monotonic())))
            jax.profiler.stop_trace()
            traced = [t_on - w0, time.monotonic() - w0]
        time.sleep(max(0.0, w1 - time.monotonic()))
        after = common.snapshot()
        sampler_stop.set()
        sampler.join(timeout=5)
        try:
            child.wait(timeout=max(1.0, give_up_at + 15 - time.monotonic()))
        except subprocess.TimeoutExpired:
            ok &= need(False, "the load generator did not finish")
        engine_stats = engine.stats()
        peak = max(common.memory_peak(d.memory_stats() or {})
                   for d in ctx["devices"])
    finally:
        if sampler_stop is not None:
            sampler_stop.set()
        if child is not None and child.poll() is None:
            child.kill()
            child.wait()
        server.shutdown()
        server.server_close()
        fleet.stop()

    with open(out_path) as f:
        result = json.load(f)
    records = result["records"]
    sample_ = [r for r in records if r["id"].startswith("w")]
    by_id = {p["body"]["request_id"]: p["body"] for p in plan}
    ttft, gaps, per_token, failed, reasons, whole = [], [], [], 0, {}, []
    for r in sample_:
        want = by_id[r["id"]]["max_new_tokens"]
        why = None
        if r["status"] == 503:
            why = "shed"
        elif r["status"] != 200:
            why = "status-%s" % r["status"]
        elif r["error"]:
            why = "error"
        elif not r["done"] or len(r["tokens"]) != want:
            why = "short"
        elif not all(0 <= t < config["vocab_size"] for t in r["tokens"]):
            why = "token-out-of-range"
        if why:
            failed += 1
            reasons[why] = reasons.get(why, 0) + 1
            continue
        whole.append(r)
        ttft.append(1e3 * (r["token_times"][0] - r["due"]))
        gaps.extend(1e3 * (b - a) for a, b in
                    zip(r["token_times"], r["token_times"][1:]))
        per_token.append(1e3 * (r["token_times"][-1] - r["due"]) / want)
    in_window = sum(w0 <= t < w1 for r in records
                    for t in r["token_times"])
    lateness = [1e3 * (r["send"] - r["due"]) for r in sample_]
    offered = sum(by_id[r["id"]]["max_new_tokens"] for r in sample_)
    say("window", requests_due=len(sample_), failed=failed, reasons=reasons,
        unfinished_threads=result["unfinished"], setup_s=setup_s,
        ttft_ms=stats.summary(ttft), itl_ms=stats.summary(gaps),
        latency_ms_per_token=stats.summary(per_token),
        lateness_ms=stats.summary(lateness), tokens_in_window=in_window,
        output_tokens_offered=offered, all_requests=len(records),
        occupancy_samples=len(occupancy),
        decode_steps=engine_stats["decode_steps"],
        preempted=engine_stats.get("preempted"),
        executables=engine_stats["executables"])
    red = None
    if traced:
        red = common.reduced_trace(ctx["trace_dir"], "between-decode-steps",
                                   seconds_into_window=traced)
    ok &= need("token-out-of-range" not in reasons,
               "a stream held a token outside the vocabulary")
    # the server is down, its peak is read and its state is let go: now
    # the reference, over what the window served (no part of ``setup_s``)
    del server, fleet, engine
    gc.collect()
    t_ref, checks = time.perf_counter(), {}
    ok &= check_against_reference(
        builder, model, config, mix,
        [(by_id[r["id"]]["prompt"], r) for r in whole
         if "temperature" not in by_id[r["id"]]
         and None not in r["logprobs"]], seed, checks)
    say("after-window", reference_check_s=time.perf_counter() - t_ref)
    return {
        "correct": bool(ok), "checks": checks, "attempted": len(sample_),
        "failed": failed,
        "setup_s": setup_s, "counters_before": before,
        "counters_after": after, "trace": red, "memory_peak_bytes": peak,
        "window_s": ctx["seconds"], "records": sample_,
        "tokens_in_window": in_window,
        "samples": {"occupancy": occupancy, "kv_pool_live": pool_live,
                    "lateness_ms": lateness,
                    "ttft_ms": ttft, "itl_ms": gaps,
                    "latency_ms_per_token": per_token},
        "failed_reasons": reasons, "chips": len(ctx["devices"]),
        "peaks": ctx["peaks"], "config": config, "traffic": mix,
    }
