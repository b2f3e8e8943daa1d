"""Benchmark: flagship BERT-base pretraining step, tokens/sec/chip.

North star (BASELINE.md): ERNIE/BERT-base pretrain tokens/sec/chip at
>=35% MFU.  The reference publishes no in-repo numbers (BASELINE.json
"published": {}), so vs_baseline reports measured-MFU / 0.35 — the ratio to
the target; 1.0 means the 35% MFU goal is met.

Self-validation (round-2, after VERDICT r1 flagged an impossible 179% MFU):
- timing fetches the loss *value* to host every step, so the wall clock can
  never be shorter than true device compute (defeats any async-dispatch or
  remote-platform distortion in ``block_until_ready``);
- the FLOP model counts only matmul params (embedding gather tables
  excluded; the word-embedding table counts once because it is tied to the
  MLM decoder matmul) plus the attention term 12*L*S*h per token;
- asserts implied MFU <= 100% before printing; per-step latency and the
  full accounting go to stderr.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
"""

import json
import os
import sys
import time

import numpy as np


def _marginal_step_time(step, state, batches, k_short, k_long, reps):
    """Shared timing harness: min-of-segments marginal step time.

    Each segment chains K steps through the donated state and ends with a
    host fetch of the loss VALUE, so a segment cannot finish before the
    device executed every step in it (a timing that ends in an enqueue,
    not in a fetched value, once implied 179% MFU).  The marginal cost
    between long and short segments cancels the fixed per-segment
    dispatch and fetch cost that a production input pipeline would
    overlap.  Returns (dt, dt_worst, state); dt_worst includes all fixed
    overhead.
    """
    def seg(k, i0):
        nonlocal state
        t0 = time.perf_counter()
        loss = None
        for i in range(i0, i0 + k):
            state, loss = step(state, batches[i % len(batches)])
        lv = float(loss)
        if not np.isfinite(lv):
            raise RuntimeError("bench loss went non-finite")
        return time.perf_counter() - t0

    shorts, longs = [], []
    i0 = 0
    for _ in range(reps):
        shorts.append(seg(k_short, i0))
        i0 += k_short
        longs.append(seg(k_long, i0))
        i0 += k_long
    dt = (min(longs) - min(shorts)) / (k_long - k_short)
    dt_worst = max(longs) / k_long
    # plain raise, not assert: the guards must survive python -O
    if dt <= 0:
        raise RuntimeError(
            "non-positive marginal step time (%.1f ms): RTT noise swamped "
            "the measurement; segment times shorts=%s longs=%s"
            % (dt * 1e3, shorts, longs))
    return dt, dt_worst, state


def _flops_per_step(cfg, params, B, S, P):
    """Training FLOPs for one step: 6 per matmul-param-use + exact
    attention term.

    The MLM head (tied word-embedding decoder + the D x D mlm_transform)
    runs only on the P masked positions per sequence (the reference
    BERT/ERNIE static graph gathers mask_pos before the decoder); the
    transformer trunk runs on all S positions.  Embedding gather tables
    (word/position/token-type lookups) cost no matmul FLOPs.
    Attention scores+context: 2*S*h MACs per token per layer forward
    = 12*L*S*h FLOPs per token for fwd+bwd.
    """
    d, v = cfg.hidden_size, cfg.vocab_size
    head = v * d + d * d + d + v  # tied decoder + mlm_transform (+biases)
    gather_only = 0
    trunk = 0
    for name, arr in params.items():
        n = int(np.prod(arr.shape))
        if ("position" in name or "token_type" in name
                or "word" in name or "mlm" in name):
            gather_only += n
        else:
            trunk += n
    attn = 12.0 * cfg.num_hidden_layers * cfg.hidden_size * S
    per_token_trunk = 6.0 * trunk + attn
    per_masked = 6.0 * head
    total = B * S * per_token_trunk + B * P * per_masked
    return total, trunk, head


def _skip(reason):
    """The driver parses stdout: any infrastructure failure must yield
    ONE structured skip line and rc 0, never a raw traceback."""
    print(json.dumps({"skipped": True, "reason": reason}))
    return 0


# substrings that mark a backend failure (vs a bug in the bench
# itself, which must still traceback loudly)
_BACKEND_ERR_MARKERS = (
    "UNAVAILABLE",
    "Unable to initialize backend",
    "backend setup",
    "DEADLINE_EXCEEDED",
    "failed to connect",
    "Connection reset",
    "Socket closed",
)


def _is_backend_failure(e):
    """True when the exception is the platform dying, not the bench
    being wrong.  A backend can die at ANY jax call — default_backend,
    first compile, a mid-segment execute — and every such failure
    surfaces as a JaxRuntimeError/XlaRuntimeError or carries an XLA
    status marker in the message chain."""
    seen = set()
    while e is not None and id(e) not in seen:
        seen.add(id(e))
        if type(e).__name__ in ("JaxRuntimeError", "XlaRuntimeError"):
            return True
        msg = str(e)
        if any(m in msg for m in _BACKEND_ERR_MARKERS):
            return True
        e = e.__cause__ or e.__context__
    return False


def _metrics_snapshot():
    """Compact observability dump for the output line: compile counts
    and device/host memory as the run ends — the before/after numbers a
    perf investigation starts from."""
    try:
        from paddle_tpu import observability as obs

        obs.SystemMetricsSampler().sample_once()
        snap = obs.default_registry().snapshot()
        out = {}
        for name, key in (("xla_compilations_total", "value"),
                          ("xla_compile_ms", "sum"),
                          ("host_rss_bytes", "value"),
                          ("jax_live_arrays", "value")):
            fam = snap.get(name)
            if fam and fam["series"]:
                out[name] = fam["series"][0].get(key)
        mem = snap.get("device_memory_bytes_in_use")
        if mem and mem["series"]:
            out["device_memory_bytes_in_use"] = {
                s["labels"].get("device", "?"): s.get("value")
                for s in mem["series"]
            }
        mfu = snap.get("mfu")
        if mfu and mfu["series"]:
            out["mfu"] = {
                s["labels"].get("executable", "?"): s.get("value")
                for s in mfu["series"]
            }
        return out
    except Exception as e:  # telemetry must never sink the bench
        return {"error": repr(e)[:200]}


def main():
    if "--recsys" in sys.argv:
        return _run_recsys()
    if "--generate" in sys.argv:
        return _run_generate()
    multichip = "--multichip" in sys.argv
    if multichip:
        n = 8
        idx = sys.argv.index("--multichip")
        if idx + 1 < len(sys.argv) and sys.argv[idx + 1].isdigit():
            n = int(sys.argv[idx + 1])
        # held to the CPU (JAX_PLATFORMS=cpu), simulate n chips there;
        # otherwise leave the platform alone and take the devices jax
        # finds.  The flag must be set BEFORE any jax import initializes
        # a platform (same discipline as
        # __graft_entry__.dryrun_multichip)
        if os.environ.get("JAX_PLATFORMS") == "cpu":
            flag = "--xla_force_host_platform_device_count"
            if flag not in os.environ.get("XLA_FLAGS", ""):
                os.environ["XLA_FLAGS"] = (
                    os.environ.get("XLA_FLAGS", "") + " %s=%d" % (flag, n))
    try:
        if os.getenv("BENCH_FORCE_BACKEND_FAIL") == "init":
            raise RuntimeError(
                "Unable to initialize backend 'tpu': UNAVAILABLE: "
                "injected by BENCH_FORCE_BACKEND_FAIL=init")
        import jax

        on_tpu = jax.default_backend() == "tpu"
        jax.devices()
        if on_tpu:      # a cold process reloads the BERT step, not rebuilds it
            from paddle_tpu.fluid.core.compile_cache import (
                enable_compile_cache,
            )

            enable_compile_cache()
    except Exception as e:
        return _skip("backend init failed: %s: %s"
                     % (type(e).__name__, str(e)[:300]))
    try:
        if multichip:
            return _run_multichip(n)
        return _run(on_tpu)
    except Exception as e:
        # init succeeded but the backend died at the first real compile
        # — still an infra skip, not a bench bug
        if _is_backend_failure(e):
            return _skip("backend failed mid-run: %s: %s"
                         % (type(e).__name__, str(e)[:300]))
        raise


def _run(on_tpu):
    import jax

    if os.getenv("BENCH_FORCE_BACKEND_FAIL") == "late":
        raise RuntimeError(
            "TPU backend setup/compile error (Unavailable): injected by "
            "BENCH_FORCE_BACKEND_FAIL=late")

    # arm the compile-event hooks so the output line's metrics_snapshot
    # carries compile count/time for THIS run
    from paddle_tpu.observability import install_jax_compile_hooks

    install_jax_compile_hooks()

    from paddle_tpu import distributed as dist
    from paddle_tpu import models
    from paddle_tpu.fluid import dygraph
    from paddle_tpu.fluid.optimizer import AdamWOptimizer

    if on_tpu:
        cfg = models.BertConfig(  # BERT-base
            vocab_size=30528,  # pad to multiple of 64 for lane alignment
            hidden_size=768, num_hidden_layers=12, num_attention_heads=12,
            intermediate_size=3072, max_position_embeddings=512,
            hidden_dropout_prob=0.1, attention_probs_dropout_prob=0.1,
        )
        # masked-position MLM shrinks the logits buffer ~6x, which is what
        # previously capped the batch at 16; B is env-sweepable
        B, S, P = int(os.getenv("BENCH_B", "60")), 512, 80
        k_short, k_long, reps = 10, 30, 2
        # bf16 peak TFLOP/s for one v5e chip (public spec: 197 bf16)
        peak = 197e12
    else:  # CPU smoke path so the bench never hangs off-TPU
        cfg = models.BertConfig.tiny()
        B, S, P = 4, 32, 8
        k_short, k_long, reps = 1, 3, 1
        peak = 1e12

    with dygraph.guard():
        model = models.BertForPretraining(cfg)
        opt = AdamWOptimizer(learning_rate=1e-4, weight_decay=0.01)
        mesh = dist.auto_mesh(1)

        def loss_fn(m, batch):
            logits, nsp_logits = m(
                batch["input_ids"], batch["token_type_ids"],
                batch["position_ids"],
                masked_positions=batch["masked_positions"],
            )
            return m.loss(
                logits, nsp_logits, batch["mlm_labels"],
                batch["mlm_weights"], batch["nsp_labels"],
            )

        step = dist.ShardedTrainStep(
            model, opt, loss_fn, mesh, zero_stage=0,
            amp="bf16" if on_tpu else None,
        )
        state = step.init()
        n_params = sum(int(np.prod(v.shape)) for v in state["params"].values())
        flops_step, trunk_params, head_params = _flops_per_step(
            cfg, state["params"], B, S, P
        )

        rng = np.random.RandomState(0)

        def make_batch():
            pos = np.stack([
                np.sort(rng.choice(S, size=P, replace=False))
                for _ in range(B)
            ]).astype(np.int32)
            return {
                "input_ids": rng.randint(
                    0, cfg.vocab_size, (B, S)).astype(np.int32),
                "token_type_ids": np.zeros((B, S), np.int32),
                "position_ids": np.tile(
                    np.arange(S, dtype=np.int32), (B, 1)),
                "masked_positions": pos,
                "mlm_labels": rng.randint(
                    0, cfg.vocab_size, (B, P)).astype(np.int32),
                "mlm_weights": np.ones((B, P), np.float32),
                "nsp_labels": rng.randint(0, 2, (B, 1)).astype(np.int32),
            }

        batches = [make_batch() for _ in range(4)]

        # warmup (compile + two real executes, value-fetched)
        for i in range(2):
            state, loss = step(state, batches[i % 4])
        float(loss)

        # measured FLOPs: what the fused HLO actually contains per step
        # (cost_analysis of the compiled executable), vs the hand model
        cost = step.cost_analysis(state, batches[0])

        # pre-place the batches on device (a production input pipeline
        # double-buffers transfers; an in-loop device_put would bill the
        # host-to-device copy to the step time)
        batches = [step.place_batch(b) for b in batches]

        dt, dt_worst, state = _marginal_step_time(
            step, state, batches, k_short, k_long, reps)

    tokens_per_sec = B * S / dt
    mfu = (flops_step / dt) / peak
    mfu_measured = None
    if cost and cost.get("flops"):
        from paddle_tpu.observability.xla_cost import record_mfu

        mfu_measured = record_mfu(
            "bench.bert_step", cost["flops"], dt, peak=peak)
        print(
            "bench: XLA cost_analysis %.1f GFLOP/step (hand model %.1f), "
            "measured MFU %s"
            % (cost["flops"] / 1e9, flops_step / 1e9,
               "%.1f%%" % (mfu_measured * 100)
               if mfu_measured is not None else "n/a"),
            file=sys.stderr,
        )
    print(
        "bench: B=%d S=%d P=%d marginal step %.2f ms over %dx(%d,%d)-step "
        "segments (conservative incl. dispatch RTT: %.2f ms), %.0f "
        "tokens/s, params=%.1fM (trunk %.1fM, head %.1fM on P rows), "
        "%.1f GFLOP/step, implied MFU %.1f%%"
        % (B, S, P, dt * 1e3, reps, k_short, k_long, dt_worst * 1e3,
           tokens_per_sec, n_params / 1e6, trunk_params / 1e6,
           head_params / 1e6, flops_step / 1e9, mfu * 100),
        file=sys.stderr,
    )
    if mfu > 1.0:
        raise RuntimeError(
            "implied MFU %.1f%% exceeds physical peak — measurement or FLOP "
            "accounting is wrong; refusing to report" % (mfu * 100)
        )

    resnet = None
    if on_tpu or os.getenv("BENCH_RESNET"):
        try:
            resnet = _bench_resnet(on_tpu, peak)
        except Exception as e:  # the headline metric must still report
            print("resnet bench failed: %r" % (e,), file=sys.stderr)

    out = {
        "metric": "bert_base_pretrain_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 2),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.35, 4),
        "mfu_model": round(mfu, 4),
        # a CPU capture is the tiny smoke config, not a number of record
        # — consumers must be able to tell without guessing from scale
        "platform": jax.default_backend(),
        "smoke_config": not on_tpu,
    }
    if mfu_measured is not None:
        out["mfu_measured"] = round(mfu_measured, 4)
        out["flops_per_step_xla"] = cost["flops"]
    if resnet is not None:
        out["extra"] = resnet
    out["metrics_snapshot"] = _metrics_snapshot()
    print(json.dumps(out))
    return 0


def _run_recsys():
    """--recsys: the online-learning capture — events/sec +
    minutes-to-freshness, the pipelined-vs-sync embedding A/B and the
    hot-row cache, via benchmarks/streaming_bench (one JSON line with
    the same skip/platform/smoke_config conventions as the headline
    bench; remaining flags pass through, e.g. --autotune)."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmarks"))
    import streaming_bench

    return streaming_bench.main(
        [a for a in sys.argv[1:] if a != "--recsys"])


def _run_generate():
    """--generate: the autoregressive-decoding capture — tokens/s,
    TTFT, ITL, the KV-cache-vs-recompute-prefix A/B, and the
    paged-vs-dense KV A/B (block-pool bytes/occupancy, prefix-cache
    hit rate, speculative acceptance), via benchmarks/generation_bench
    (one JSON line with the same skip/platform/smoke_config
    conventions as the headline bench; remaining flags pass through,
    e.g. --autotune / --slots N / --block-size 16 / --prefix-cache /
    --kv-dtype int8 / --draft-len 3 / --dense)."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmarks"))
    import generation_bench

    return generation_bench.main(
        [a for a in sys.argv[1:] if a != "--generate"])


def _run_multichip(n):
    """--multichip N: time the n-device dryrun train step per ZeRO
    stage and report the per-collective op counts + bytes extracted
    from the COMPILED HLO — so the multichip capture carries real
    collective traffic, not just an rc.  One JSON line, same
    skip/platform/smoke_config conventions as the headline bench."""
    import jax

    from paddle_tpu import distributed as dist
    from paddle_tpu.distributed import _zero_harness as zh

    on_cpu = jax.default_backend() == "cpu"
    devices = jax.devices("cpu") if on_cpu else jax.devices()
    if len(devices) < n:
        return _skip("multichip wants %d %s devices, have %d%s"
                     % (n, jax.default_backend(), len(devices),
                        " (stale XLA_FLAGS in this process)"
                        if on_cpu else ""))
    devices = devices[:n]
    mesh = dist.auto_mesh(n, devices=devices)

    # same workload/contract as the dryrun's ZeRO parity section (one
    # shared harness — the bench measures what the dryrun validates);
    # local batch 4 so accumulate_steps=4 divides
    B, S = 4 * n, 32
    batches = zh.bert_batches(zh.tiny_bert_config(), B, S, 2, seed=0)

    def build_and_time(params, want_stats=False):
        def body(step, state):
            loss = None
            for i in range(2):
                state, loss = step(state, batches[i % 2])
            float(loss)
            placed = [step.place_batch(b) for b in batches]
            dt, _w, state2 = _marginal_step_time(
                step, state, placed, 1, 3, 1)
            stats = (step.collective_stats(state2, batches[0])
                     if want_stats else None)
            est = step.comm_estimate() if want_stats else None
            return dt, stats, est

        return zh.run_deterministic(mesh, body, lr=1e-4, **params)

    stages = {}
    for label, params in (
            ("zero1", {"zero_stage": 1}),
            ("zero2", {"zero_stage": 2}),
            ("zero3", {"zero_stage": 3}),
            ("zero2_acc4", {"zero_stage": 2, "accumulate_steps": 4})):
        dt, stats, est = build_and_time(params, want_stats=True)
        entry = {"step_ms": round(dt * 1e3, 3)}
        if stats:
            entry["collectives"] = {
                k: {kk: (round(vv, 1) if isinstance(vv, float) else vv)
                    for kk, vv in v.items()}
                for k, v in stats.items() if isinstance(v, dict)}
            entry["hlo_wire_bytes"] = round(stats.get(
                "wire_bytes_total", 0.0), 1)
        if est:
            entry["est_wire_bytes"] = round(est["wire_bytes_total"], 1)
        stages[label] = entry

    autotune = None
    if "--autotune" in sys.argv:
        from paddle_tpu import tune

        report = tune.search_train_step(
            lambda p: build_and_time(p)[0], mesh=mesh,
            workload="bench.multichip:n%d.B%d.S%d" % (n, B, S))
        print("multichip autotune:\n%s" % report.format(),
              file=sys.stderr)
        w = report.winner
        autotune = {
            "cache_hit": report.cache_hit,
            "winner": w.to_dict() if w else None,
            "default_s": report.default_s,
            "counts": report.counts(),
        }

    out = {
        "metric": "multichip_dryrun_bert_step_ms",
        "value": stages["zero2"]["step_ms"],
        "unit": "ms",
        "n_devices": n,
        "platform": jax.default_backend(),
        "smoke_config": jax.default_backend() != "tpu",
        "stages": stages,
    }
    if autotune is not None:
        out["autotune"] = autotune
    print(json.dumps(out))
    return 0


def _bench_resnet(on_tpu, peak):
    """Milestone-5 metric (BASELINE.md): ResNet-50 train images/sec on one
    chip.  FLOP model: 4.09 GFLOP forward per 224x224 image (the standard
    published count for ResNet-50 v1.5), x3 for fwd+bwd."""
    import time

    import jax

    from paddle_tpu import distributed as dist
    from paddle_tpu import models
    from paddle_tpu.fluid import dygraph, layers
    from paddle_tpu.fluid.optimizer import MomentumOptimizer

    if on_tpu:
        B, HW, k_short, k_long, reps = (
            int(os.getenv("BENCH_RESNET_B", "128")), 224, 10, 30, 2)
        depth, flops_img = 50, 3 * 4.089e9
    else:
        B, HW, k_short, k_long, reps = 4, 32, 1, 3, 1
        depth, flops_img = 18, 3 * 0.3e9

    with dygraph.guard():
        model = models.ResNet(depth=depth, num_classes=1000)
        opt = MomentumOptimizer(learning_rate=0.1, momentum=0.9)
        mesh = dist.auto_mesh(1)

        def loss_fn(m, batch):
            logits = m(batch["image"])
            return layers.mean(layers.softmax_with_cross_entropy(
                logits, batch["label"]))

        step = dist.ShardedTrainStep(
            model, opt, loss_fn, mesh, zero_stage=0,
            amp="bf16" if on_tpu else None,
        )
        state = step.init()
        rng = np.random.RandomState(0)
        batches = [{
            "image": rng.randn(B, 3, HW, HW).astype(np.float32),
            "label": rng.randint(0, 1000, (B, 1)).astype(np.int32),
        } for _ in range(2)]
        for i in range(2):
            state, loss = step(state, batches[i % 2])
        float(loss)
        cost = step.cost_analysis(state, batches[0])
        batches = [step.place_batch(b) for b in batches]

        dt, _dt_worst, state = _marginal_step_time(
            step, state, batches, k_short, k_long, reps)
    imgs = B / dt
    mfu = imgs * flops_img / peak
    print("resnet%d bench: B=%d step %.2f ms, %.1f images/s, implied "
          "MFU %.1f%%" % (depth, B, dt * 1e3, imgs, mfu * 100),
          file=sys.stderr)
    out = {
        "resnet50_train_images_per_sec_per_chip": round(imgs, 2),
        "resnet50_implied_mfu": round(mfu, 4),
    }
    if cost and cost.get("flops"):
        from paddle_tpu.observability.xla_cost import record_mfu

        m = record_mfu("bench.resnet_step", cost["flops"], dt, peak=peak)
        if m is not None:
            out["resnet50_measured_mfu"] = round(m, 4)
    return out


if __name__ == "__main__":
    sys.exit(main())
