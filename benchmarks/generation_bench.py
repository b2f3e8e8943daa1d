"""Autoregressive decoding benchmark: tokens/s, TTFT, ITL, the
KV-cache-vs-recompute-prefix A/B, and the PAGED-vs-dense KV A/B.

Workload: a `models.TransformerLM` served by
`generation.GenerationEngine` under a batch of concurrent requests
(continuous batching keeps every slot busy; prompts spread over the
prefill bucket ladder).  Measurements over identical prompts/seeds:

* **throughput** — generated tokens/s across the run, plus per-request
  TTFT (submit -> first token) and ITL (inter-token latency) p50/p99;
* **A/B** — the same requests decoded by recomputing the full prefix
  every step (the legacy `fluid.contrib.decoder` cost model: one
  causal forward over the whole sequence per token, no cache) vs the
  engine's attention-over-cache decode step.  Token streams are
  checked identical before the ratio is reported;
* **paged vs dense** — the measured engine is paged (block pool
  auto-provisioned to the workload's MEAN sequence length unless
  ``--kv-blocks`` pins it); a dense PR-15 engine decodes the same
  requests, streams are checked identical, and the report carries
  ``paged_kv_bytes`` / ``dense_kv_bytes`` / ``kv_bytes_ratio`` plus
  block-pool occupancy (mean and peak blocks used);
* **prefix / speculative** — ``--prefix-cache`` reports hit rate and
  tokens served from cache; ``--draft-len k`` reports the speculative
  acceptance rate.  ``--kv-dtype int8`` opts the pool into quantized
  storage (documented-tolerance: the paged-vs-dense token check is
  skipped, streams may lawfully differ);
* **occupancy** — mean slot occupancy, the admission signal;
* **tensor-parallel A/B** — ``--tp N`` decodes the same requests on a
  `tp_serving.TPGenerationEngine` over N devices: streams must match
  the single-chip engine token-for-token, the sharded decode step must
  compile exactly once, and the per-layer all-reduce bytes priced by
  `analysis.comm` must equal the compiled executable's HLO exactly.

CPU-host caveat: with JAX_PLATFORMS=cpu this is the smoke config (tiny
model, short generations) — the numbers calibrate the harness, not the
hardware; the TPU capture slot is reserved in PERF.md round 15.

Prints ONE JSON line: {"metric": "tokens_per_s", "value": ...,
"ttft_ms_p50": ..., "itl_ms_p50": ..., "cache_vs_recompute": ...,
"paged": {...}, "platform": ..., "smoke_config": ...}.  On any backend
failure prints {"skipped": true, ...} with rc 0 (bench.py convention).
``--autotune`` adds a `tune.search_generation_config` search over
slots x block_size.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _skip(reason):
    print(json.dumps({"skipped": True, "reason": reason}))
    return 0


def _pct(xs, p):
    return float(np.percentile(np.asarray(xs, np.float64), p)) if xs else 0.0


def build_model(smoke):
    from paddle_tpu import models
    from paddle_tpu.fluid import dygraph

    if smoke:
        cfg = models.TransformerLMConfig.tiny()
    else:
        cfg = models.TransformerLMConfig(
            vocab_size=32000, hidden_size=768, num_layers=12,
            num_heads=12, intermediate_size=3072,
            max_position_embeddings=1024, dropout=0.0)
    with dygraph.guard():
        np.random.seed(7)
        model = models.TransformerLM(cfg)
    return cfg, model


def make_requests(cfg, n, max_new, seed=11):
    from paddle_tpu import generation as gen

    rng = np.random.RandomState(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.randint(2, 14))
        prompt = rng.randint(0, cfg.vocab_size, plen)
        sp = (gen.SamplingParams.greedy() if i % 2 == 0 else
              gen.SamplingParams(temperature=0.8, top_k=40, top_p=0.95,
                                 seed=1000 + i))
        reqs.append(gen.GenerationRequest(
            prompt, max_new_tokens=max_new, sampling=sp,
            request_id="bench-%d" % i))
    return reqs


def recompute_prefix_generate(model, cfg, request):
    """The no-cache baseline: one full causal forward over the whole
    sequence per generated token, sampling with the SAME per-request
    key stream as the engine — streams must match token-for-token."""
    import jax.numpy as jnp

    from paddle_tpu.fluid import dygraph, framework
    from paddle_tpu.generation.sampling import make_base_key, sample_tokens

    sp = request.sampling
    key = np.asarray(make_base_key(sp.seed), np.uint32)[None]
    seq = list(request.prompt_ids)
    out = []
    with dygraph.guard():
        framework._dygraph_tracer.train_mode = False
        for vb in model.state_dict().values():
            framework._dygraph_tracer.register_var(vb)
        for g in range(request.max_new_tokens):
            ids = np.asarray(seq, np.int64)[None]
            pos = np.arange(len(seq), dtype=np.int64)[None]
            logits = model(dygraph.to_variable(ids),
                           dygraph.to_variable(pos))
            last = jnp.asarray(logits.data)[:, -1]
            tok = int(sample_tokens(
                last, key, np.asarray([g], np.int32),
                np.asarray([sp.temperature], np.float32),
                np.asarray([sp.top_k], np.int32),
                np.asarray([sp.top_p], np.float32))[0])
            out.append(tok)
            seq.append(tok)
            if tok in request.stop_token_ids:
                break
    return out


def run_engine(model, reqs, slots, max_len, buckets, engine=None,
               engine_kwargs=None):
    from paddle_tpu import generation as gen

    if engine is None:
        engine = gen.GenerationEngine(model, slots=slots,
                                      max_len=max_len,
                                      prefill_buckets=buckets,
                                      max_queue=4096,
                                      **(engine_kwargs or {}))
    t0 = time.perf_counter()
    handles = [engine.submit(r) for r in reqs]
    occ, step_ms, pool_used = [], [], []
    while True:
        before = engine.occupancy()
        steps_before = engine._decode_steps
        ts = time.perf_counter()
        progressed = engine.step()
        # ITL sample = a pure decode iteration; steps that also ran a
        # prefill (a free slot + pending work existed) would bill the
        # bucketed forward to "inter-token latency"
        prefilled = before["free"] > 0 and before["pending"] > 0
        if engine._decode_steps > steps_before and not prefilled:
            step_ms.append((time.perf_counter() - ts) * 1e3)
        occ.append(engine.occupancy()["active"] / max(slots, 1))
        if engine.paged:
            pool_used.append(engine.cache.pool.used_blocks)
        if not progressed:
            break
    wall = time.perf_counter() - t0
    results = [h.result(timeout=1.0) for h in handles]
    n_tokens = sum(len(r) for r in results)
    ttft = [(h.t_first_token - h.t_submit) * 1e3 for h in handles
            if h.t_first_token is not None]
    m = {
        "wall_s": wall,
        "tokens": n_tokens,
        "tokens_per_s": n_tokens / wall if wall > 0 else 0.0,
        "ttft_ms_p50": _pct(ttft, 50), "ttft_ms_p99": _pct(ttft, 99),
        "itl_ms_p50": _pct(step_ms, 50), "itl_ms_p99": _pct(step_ms, 99),
        "occupancy_mean": float(np.mean(occ)) if occ else 0.0,
    }
    if pool_used:
        m["pool_blocks_mean"] = float(np.mean(pool_used))
        m["pool_blocks_peak"] = int(max(pool_used))
    return engine, results, m


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--autotune", action="store_true")
    ap.add_argument("--skip-ab", action="store_true",
                    help="skip the recompute-prefix A/B (slow)")
    ap.add_argument("--dense", action="store_true",
                    help="measure the dense PR-15 engine instead of paged")
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--kv-blocks", type=int, default=None,
                    help="pin the pool size; default provisions for the "
                         "workload MEAN sequence length (the paged win)")
    ap.add_argument("--prefix-cache", action="store_true")
    ap.add_argument("--prefill-chunk", type=int, default=None)
    ap.add_argument("--kv-dtype", choices=["int8"], default=None)
    ap.add_argument("--draft-len", type=int, default=0,
                    help="speculative decoding with a tiny draft LM")
    ap.add_argument("--skip-paged-ab", action="store_true",
                    help="skip the paged-vs-dense A/B")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel A/B: decode the same requests "
                         "on a tp_serving.TPGenerationEngine over N "
                         "devices, assert token exactness, and pin the "
                         "per-layer all-reduce bytes against compiled "
                         "HLO")
    args = ap.parse_args(argv)

    try:
        if os.getenv("BENCH_FORCE_BACKEND_FAIL") == "init":
            raise RuntimeError(
                "Unable to initialize backend 'tpu': UNAVAILABLE: "
                "injected by BENCH_FORCE_BACKEND_FAIL=init")
        import jax

        jax.devices()
    except Exception as e:
        return _skip("backend init failed: %s: %s"
                     % (type(e).__name__, str(e)[:300]))

    smoke = jax.default_backend() != "tpu"
    cfg, model = build_model(smoke)
    buckets = [8, 16]
    reqs = make_requests(cfg, args.requests, args.max_new)

    mean_seq = (float(np.mean([len(r.prompt_ids) for r in reqs]))
                + args.max_new)
    engine_kwargs = {}
    if args.dense:
        engine_kwargs["paged"] = False
    else:
        bs = args.block_size
        kv_blocks = args.kv_blocks
        if kv_blocks is None:
            # provision for the MEAN sequence, not the worst case: the
            # capacity win the dense [slots, max_len] layout cannot
            # express (preemption absorbs the tail)
            kv_blocks = args.slots * (-(-int(mean_seq) // bs) + 1) + 1
        engine_kwargs.update(block_size=bs, kv_blocks=kv_blocks)
        if args.prefix_cache:
            engine_kwargs["prefix_cache"] = True
        if args.prefill_chunk:
            engine_kwargs["prefill_chunk"] = args.prefill_chunk
        if args.kv_dtype:
            engine_kwargs["kv_dtype"] = args.kv_dtype
        if args.draft_len > 0:
            from paddle_tpu import models
            from paddle_tpu.fluid import dygraph

            dcfg = (models.TransformerLMConfig.tiny() if smoke else
                    models.TransformerLMConfig(
                        vocab_size=cfg.vocab_size, hidden_size=256,
                        num_layers=2, num_heads=4,
                        intermediate_size=1024,
                        max_position_embeddings=cfg.max_position_embeddings,
                        dropout=0.0))
            with dygraph.guard():
                np.random.seed(23)
                draft = models.TransformerLM(dcfg)
            engine_kwargs.update(draft_model=draft,
                                 draft_len=args.draft_len)

    from paddle_tpu.observability import install_jax_compile_hooks
    from paddle_tpu.observability.metrics import default_registry

    install_jax_compile_hooks()
    reg = default_registry()

    # warmup run covering EVERY prefill bucket + the decode step (the
    # full executable set), then measure — so the measured run's
    # compile count is the zero the compile-once design promises
    from paddle_tpu import generation as gen

    warm = [gen.GenerationRequest(list(range(1, b + 1)),
                                  max_new_tokens=2)
            for b in buckets]
    engine, _, _ = run_engine(model, warm, args.slots, args.max_len,
                              buckets, engine_kwargs=engine_kwargs)
    c0 = reg.counter("xla_compilations_total",
                     "XLA backend compilations (jax.monitoring)").value
    engine, results, m = run_engine(model, reqs, args.slots,
                                    args.max_len, buckets,
                                    engine=engine)
    compiles_measured = reg.counter(
        "xla_compilations_total",
        "XLA backend compilations (jax.monitoring)").value - c0


    out = {
        "metric": "tokens_per_s",
        "value": round(m["tokens_per_s"], 2),
        "requests": args.requests,
        "max_new_tokens": args.max_new,
        "slots": args.slots,
        "ttft_ms_p50": round(m["ttft_ms_p50"], 3),
        "ttft_ms_p99": round(m["ttft_ms_p99"], 3),
        "itl_ms_p50": round(m["itl_ms_p50"], 3),
        "itl_ms_p99": round(m["itl_ms_p99"], 3),
        "occupancy_mean": round(m["occupancy_mean"], 3),
        "decode_executables": engine._decode_cache_size(),
        "compiles_in_measured_run": compiles_measured,
        "model": {"layers": cfg.num_layers, "hidden": cfg.hidden_size,
                  "vocab": cfg.vocab_size},
        "platform": jax.default_backend(),
        "smoke_config": smoke,
    }

    if engine.paged:
        st = engine.stats()
        realized = [len(r.prompt_ids) + len(res)
                    for r, res in zip(reqs, results)]
        mean_real = float(np.mean(realized)) if realized else 0.0
        bs = engine.block_size
        mean_rows = max(-(-int(round(mean_real)) // bs) * bs, bs)
        paged_info = {
            "block_size": bs,
            "kv_blocks": engine.cache.num_blocks,
            "capacity_tokens": engine.cache.capacity_tokens,
            "kv_bytes": engine.cache.nbytes,
            "kv_dtype": args.kv_dtype or "float32",
            "pool_blocks_mean": round(m.get("pool_blocks_mean", 0.0), 2),
            "pool_blocks_peak": m.get("pool_blocks_peak", 0),
            "mean_seq_len": round(mean_real, 2),
            # sequences-per-HBM-byte vs the dense [slots, max_len]
            # layout: dense reserves max_len rows/seq, paged reserves
            # ceil(mean/bs)*bs — the effective-capacity multiplier
            "effective_capacity_x": round(args.max_len / mean_rows, 2),
            "preempted": st["preempted"],
        }
        if "prefix_cache" in st:
            paged_info["prefix_cache"] = st["prefix_cache"]
        if "speculative" in st:
            paged_info["speculative"] = st["speculative"]
        out["paged"] = paged_info
    else:
        out["paged"] = False

    if not args.skip_ab:
        # recompute-prefix A/B over a subset (it is O(len) per token)
        ab_reqs = reqs[: min(4, len(reqs))]
        # pass 1 traces/compiles one executable per distinct sequence
        # length (the recompute decoder's inherent cost); pass 2 rides
        # those caches — report the WARMED pass so the ratio measures
        # per-token work, not jit tracing
        baseline = [recompute_prefix_generate(model, cfg, r)
                    for r in ab_reqs]
        t0 = time.perf_counter()
        baseline = [recompute_prefix_generate(model, cfg, r)
                    for r in ab_reqs]
        t_recompute = time.perf_counter() - t0
        _, cached, m2 = run_engine(
            model, ab_reqs, args.slots, args.max_len, buckets,
            engine=engine)
        for i, (b, c) in enumerate(zip(baseline, cached)):
            if b != c:
                print(json.dumps({
                    "error": "A/B token mismatch on request %d" % i,
                    "recompute": b, "cached": c}))
                return 1
        ab_tokens = sum(len(r) for r in cached)
        out["ab_tokens"] = ab_tokens
        out["recompute_tokens_per_s"] = round(
            ab_tokens / t_recompute, 2) if t_recompute > 0 else 0.0
        out["cache_tokens_per_s"] = round(m2["tokens_per_s"], 2)
        out["cache_vs_recompute"] = round(
            m2["tokens_per_s"] * t_recompute / ab_tokens, 2) \
            if ab_tokens else 0.0

    if engine.paged and not args.skip_paged_ab:
        # dense PR-15 engine over the SAME prompts/seeds: token streams
        # must match (int8 excepted — documented tolerance), and the
        # HBM-bytes ratio is the headline paged win
        dense_eng, _, _ = run_engine(
            model, [gen.GenerationRequest(list(range(1, b + 1)),
                                          max_new_tokens=2)
                    for b in buckets],
            args.slots, args.max_len, buckets,
            engine_kwargs={"paged": False})
        dense_eng, dense_results, md = run_engine(
            model, make_requests(cfg, args.requests, args.max_new),
            args.slots, args.max_len, buckets, engine=dense_eng)
        if args.kv_dtype is None and args.draft_len == 0:
            for i, (p, d) in enumerate(zip(results, dense_results)):
                if p != d:
                    print(json.dumps({
                        "error": "paged/dense token mismatch on "
                                 "request %d" % i,
                        "paged": p, "dense": d}))
                    return 1
            out["paged"]["token_exact_vs_dense"] = True
        out["paged"]["dense_kv_bytes"] = dense_eng.cache.nbytes
        out["paged"]["kv_bytes_ratio"] = round(
            dense_eng.cache.nbytes / max(engine.cache.nbytes, 1), 2)
        out["paged"]["dense_tokens_per_s"] = round(md["tokens_per_s"], 2)
        out["paged"]["paged_vs_dense_tps"] = round(
            m["tokens_per_s"] / max(md["tokens_per_s"], 1e-9), 2)

    if args.tp > 1:
        # tensor-parallel A/B (paddle_tpu.tp_serving): identical
        # requests through a TP engine — streams must match the
        # single-chip engine token-for-token, the sharded decode must
        # compile exactly once, and the per-layer all-reduce bytes the
        # comm model prices must equal the compiled executable's
        if len(jax.devices()) < args.tp:
            out["tp"] = {"skipped": "tp=%d needs %d devices, have %d"
                         % (args.tp, args.tp, len(jax.devices()))}
        else:
            from paddle_tpu.tp_serving import TPGenerationEngine

            tp_eng = TPGenerationEngine(
                model, tp=args.tp, slots=args.slots,
                max_len=args.max_len, prefill_buckets=buckets,
                max_queue=4096, **engine_kwargs)
            tp_warm = [gen.GenerationRequest(list(range(1, b + 1)),
                                             max_new_tokens=2)
                       for b in buckets]
            run_engine(model, tp_warm, args.slots, args.max_len,
                       buckets, engine=tp_eng)
            c1 = reg.counter("xla_compilations_total",
                             "XLA backend compilations "
                             "(jax.monitoring)").value
            tp_eng, tp_results, mt = run_engine(
                model, make_requests(cfg, args.requests, args.max_new),
                args.slots, args.max_len, buckets, engine=tp_eng)
            tp_compiles = reg.counter(
                "xla_compilations_total",
                "XLA backend compilations (jax.monitoring)").value - c1
            if args.kv_dtype is None and args.draft_len == 0:
                for i, (p, t) in enumerate(zip(results, tp_results)):
                    if p != t:
                        print(json.dumps({
                            "error": "tp/single-chip token mismatch on "
                                     "request %d" % i,
                            "single": p, "tp": t}))
                        return 1
            commchk = tp_eng.decode_hlo_comm_check()
            if not (commchk["count_match"] and commchk["wire_match"]):
                print(json.dumps({
                    "error": "comm estimate does not match compiled "
                             "HLO", "comm": commchk}))
                return 1
            out["tp"] = {
                "degree": args.tp,
                "tokens_per_s": round(mt["tokens_per_s"], 2),
                "tokens_per_s_tp1": out["value"],
                "itl_ms_p50": round(mt["itl_ms_p50"], 3),
                "token_exact_vs_tp1": (args.kv_dtype is None
                                       and args.draft_len == 0),
                "decode_executables": tp_eng._decode_cache_size(),
                "compiles_in_measured_run": tp_compiles,
                "per_layer_allreduce_bytes":
                    commchk["per_layer_wire_bytes"],
                "comm_bytes_per_step": commchk["comm_bytes_per_step"],
                "hlo_all_reduce_count":
                    commchk["hlo_all_reduce_count"],
                "hlo_wire_bytes": commchk["hlo_wire_bytes"],
                "comm_match": True,
            }

    if args.autotune:
        from paddle_tpu import tune

        def build_and_time(params):
            kw = {"paged": False} if args.dense else {
                "block_size": params.get("block_size") or args.block_size}
            if not args.dense:
                cbs = kw["block_size"]
                kw["kv_blocks"] = (params["slots"]
                                   * (-(-int(mean_seq) // cbs) + 1) + 1)
            eng, _, mm = run_engine(
                model, make_requests(cfg, args.requests, args.max_new),
                params["slots"], args.max_len, buckets,
                engine_kwargs=kw)
            return mm["wall_s"] / max(mm["tokens"], 1)

        report = tune.search_generation_config(
            build_and_time, workload="generation_bench:%dx%d"
            % (args.requests, args.max_new),
            slot_counts=(args.slots, 1, 2, 8),
            block_sizes=None if args.dense
            else (args.block_size, 32))
        out["autotune"] = {
            "winner": report.winner.candidate.label
            if report.winner else None,
            "cache_hit": report.cache_hit,
            "candidates": len(report.results),
        }

    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
