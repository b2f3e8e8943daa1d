"""RL feedback-loop benchmark: events/s, phase breakdown, and
minutes-to-freshness.

Workload: the full `paddle_tpu.rl.FeedbackLoop` over a
`models.TransformerLM` generation fleet scoring against the drill's
verifiable `TokenAffinityReward` — rollout through the engine's
continuous-batching decode, policy-gradient update through
`distributed.ShardedTrainStep`, delta checkpoints, and gated
(verify -> canary -> promote) weight hot-swaps into the same fleet.
Measurements over one run:

* **throughput** — reward events/s end to end, plus the wall-clock
  split between the three phases (rollout / score / train+sync) so
  the report says WHERE the loop spends its time;
* **freshness** — the PR-14 headline: worst-case seconds from a
  reward event being stamped to the policy that trained on it
  answering its promotion probe (`minutes_to_freshness` in the JSON);
* **learning** — mean reward of the first vs last rounds: the bench
  refuses to report throughput for a loop that does not learn.

CPU-host caveat: with JAX_PLATFORMS=cpu this is the smoke config
(tiny model, short generations); the numbers calibrate the harness,
not the hardware.

Prints ONE JSON line: {"metric": "events_per_s", "value": ...,
"rollout_s": ..., "score_s": ..., "train_s": ...,
"minutes_to_freshness": ..., "reward_first": ..., "reward_last": ...,
"platform": ..., "smoke_config": ...}.  On any backend failure prints
{"skipped": true, ...} with rc 0 (bench.py convention).
``--autotune`` adds a `tune.search_rl_config` batch-shape search.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _skip(reason):
    print(json.dumps({"skipped": True, "reason": reason}))
    return 0


PROMPTS = [[1, 2, 3], [4, 5], [6, 7, 8], [9, 10], [2, 4, 6, 8]]


def build_loop(work, *, rollout_batch, accumulate_steps, sync_every,
               max_new, replicas, push_every, kl_coef):
    from paddle_tpu import models, rl, serving
    from paddle_tpu.fluid import dygraph
    from paddle_tpu.fluid.optimizer import AdamOptimizer

    cfg = models.TransformerLMConfig.tiny()
    with dygraph.guard():
        np.random.seed(0)
        model = models.TransformerLM(cfg)
    fleet = serving.GenerationFleet(
        model, replicas=replicas, slots=4, max_len=32,
        prefill_buckets=[8, 16], logprobs=True)
    loop = rl.FeedbackLoop(
        model, AdamOptimizer(learning_rate=0.05), fleet,
        rl.TokenAffinityReward(target_ids=[7]),
        prompts=PROMPTS, rollout_batch=rollout_batch,
        max_new_tokens=max_new, kind="reinforce", kl_coef=kl_coef,
        accumulate_steps=accumulate_steps, sync_every=sync_every,
        checkpoint_root=os.path.join(work, "ckpt"),
        push_every_windows=push_every)
    return loop, fleet


def _instrument(loop):
    """Wrap the loop's three phases with wall-clock accumulators."""
    t = {"rollout": 0.0, "score": 0.0, "train": 0.0}

    def timed(key, fn):
        def inner(*a, **kw):
            t0 = time.perf_counter()
            try:
                return fn(*a, **kw)
            finally:
                t[key] += time.perf_counter() - t0
        return inner

    loop.rollout_engine.rollout = timed(
        "rollout", loop.rollout_engine.rollout)
    loop.reward_source.score = timed("score", loop.reward_source.score)
    loop.session.run = timed("train", loop.session.run)
    return t


def run_loop(work, args):
    loop, fleet = build_loop(
        work, rollout_batch=args.rollout_batch,
        accumulate_steps=args.accumulate_steps,
        sync_every=args.sync_every, max_new=args.max_new,
        replicas=args.replicas, push_every=args.push_every,
        kl_coef=args.kl_coef)
    phases = _instrument(loop)
    try:
        report = loop.run(rounds=args.rounds)
    finally:
        fleet.stop()
    return loop, report, phases


def main(argv=None):
    ap = argparse.ArgumentParser(prog="rl_loop_bench")
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--rollout-batch", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=6)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--accumulate-steps", type=int, default=1)
    ap.add_argument("--sync-every", type=int, default=1)
    ap.add_argument("--push-every", type=int, default=2)
    ap.add_argument("--kl-coef", type=float, default=0.0)
    ap.add_argument("--autotune", action="store_true")
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

    import jax

    work = tempfile.mkdtemp(prefix="rl_loop_bench_")
    try:
        loop, report, phases = run_loop(work, args)

        rewards = [r for _rnd, r in loop.reward_history]
        k = max(1, min(3, len(rewards) // 3))
        reward_first = float(np.mean(rewards[:k]))
        reward_last = float(np.mean(rewards[-k:]))

        out = {
            "metric": "events_per_s",
            "value": round(report.events_per_s, 2),
            "unit": "events/s",
            "events": report.events,
            "rounds": len(report.windows),
            "rollout_s": round(phases["rollout"], 3),
            "score_s": round(phases["score"], 3),
            "train_s": round(phases["train"], 3),
            "freshness_s": (round(report.freshness_s, 3)
                            if report.freshness_s is not None else None),
            "minutes_to_freshness": (
                round(report.freshness_s / 60.0, 4)
                if report.freshness_s is not None else None),
            "pushes": len(report.pushes),
            "checkpoints": len(report.checkpoints),
            "reward_first": round(reward_first, 4),
            "reward_last": round(reward_last, 4),
            "reward_improved": reward_last > reward_first,
            "rollout_ledger": loop.rollout_engine.stats(),
            "config": {"rollout_batch": args.rollout_batch,
                       "max_new_tokens": args.max_new,
                       "replicas": args.replicas,
                       "accumulate_steps": args.accumulate_steps,
                       "sync_every": args.sync_every,
                       "kl_coef": args.kl_coef},
            "platform": jax.default_backend(),
            "smoke_config": jax.default_backend() != "tpu",
        }

        if args.autotune:
            from paddle_tpu import tune

            short = argparse.Namespace(**vars(args))
            short.rounds = max(3, args.rounds // 3)
            short.push_every = 0

            def build_and_time(params):
                short.rollout_batch = params["rollout_batch"]
                short.accumulate_steps = params["accumulate_steps"]
                short.sync_every = params["sync_every"]
                w = tempfile.mkdtemp(prefix="rl_tune_")
                try:
                    _loop, rep, _ph = run_loop(w, short)
                    return 1.0 / max(rep.events_per_s, 1e-9)
                finally:
                    shutil.rmtree(w, ignore_errors=True)

            rep = tune.search_rl_config(
                build_and_time,
                workload="rl_loop_bench.r%d.n%d"
                % (args.rounds, args.max_new),
                rollout_batches=(args.rollout_batch, 4, 16),
                accumulate_steps=(1, 2))
            out["autotune"] = {
                "winner": rep.winner.candidate.label
                if rep.winner else None,
                "cache_hit": rep.cache_hit,
                "candidates": len(rep.results),
            }

        print(json.dumps(out))
        return 0
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
