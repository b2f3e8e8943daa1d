"""kind "train": a training job at a fixed global batch, measured as
tokens per second over a steady window (cut down from `chip_smoke.py`
``run_bert``, which ran on the chip in PR 22).

The loop is the one a user writes: host batches through
`io.DevicePrefetcher`, one `ShardedTrainStep` call a step, the loss
fetched to the host every step.  The fetch is of the step before the one
just dispatched, so the device always has the next program queued."""

import importlib
import itertools
import math
import time

import numpy as np

from chipbench import common, stats
from chipbench.common import held, need, say


def run(ctx):
    import jax

    from paddle_tpu import distributed as dist
    from paddle_tpu import io
    from paddle_tpu.fluid import dygraph
    from paddle_tpu.fluid.optimizer import AdamWOptimizer

    config, job, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    builder = importlib.import_module(config["builder"])
    chips = len(ctx["devices"])
    rng = np.random.RandomState(seed % 2 ** 32)
    pool = [builder.host_batch(config, job, rng)
            for _ in range(job["batch_pool"])]
    ok, checks = True, {}

    with dygraph.guard():
        model = builder.build(config, seed)
        shapes = {k: tuple(v.data.shape)
                  for k, v in model.state_dict().items()}
        ctx["mark"]("batches-and-model")
        got, ref_loss = builder.reference_check(model, config, job, pool[0])
        ok &= held(checks, "forward_loss_diff", abs(got - ref_loss),
                   builder.REFERENCE_LOSS_ATOL,
                   "the program's float32 forward loss %r differs from "
                   "the plain reference's %r" % (got, ref_loss))
        ctx["mark"]("reference-check")
        opt = AdamWOptimizer(learning_rate=job["learning_rate"],
                             weight_decay=job["weight_decay"])
        mesh = dist.auto_mesh(chips, devices=ctx["devices"])
        step = dist.ShardedTrainStep(model, opt, builder.loss_fn, mesh,
                                     zero_stage=job["zero_stage"],
                                     amp=job["amp"])
        state = step.init()
        ctx["mark"]("step-init")

        # warm-up and correctness: the one shape the window uses, on one
        # repeated batch, every loss fetched
        first = step.place_batch(pool[0])
        warm = []
        for _ in range(job["warmup_steps"]):
            state, loss = step(state, first)
            warm.append(float(loss))
        ctx["mark"]("warm-up-steps")
        say("warmup", losses=warm, chance_loss=builder.chance_loss(config),
            reference_loss=ref_loss,
            dispatch=common.dispatch_lines())
        ok &= need(all(math.isfinite(x) for x in warm),
                   "a warm-up loss is not finite: %r" % (warm,))
        ok &= held(checks, "first_loss_diff", abs(warm[0] - ref_loss),
                   builder.TRAIN_FIRST_LOSS_ATOL,
                   "first training loss %r is not near the reference's %r"
                   % (warm[0], ref_loss))
        ok &= need(warm[-1] < warm[0], "the loss did not fall on a repeated "
                   "batch: %r" % (warm,))
        checks["warmup_loss_change"] = {"value": warm[-1] - warm[0],
                                        "limit": 0.0}
        ok &= _state_spread(state, chips, job)

        feed = iter(io.DevicePrefetcher(itertools.cycle(pool),
                                        depth=job["prefetch_depth"],
                                        mesh=mesh))
        # two steps from the prefetcher before the window: its thread is
        # up and the placement it gives has met the compiled step
        for _ in range(2):
            state, loss = step(state, next(feed))
        jax.block_until_ready(loss)
        ctx["mark"]("prefetcher")

        common.clear_histograms()
        before = common.snapshot()
        setup_s = time.perf_counter() - ctx["t0"]
        losses, ends, traced, profiler_s = [], [], None, 0.0
        pending = None
        t_begin = time.perf_counter()
        for i in itertools.count():
            # the traced stretch starts a third into the window
            if ctx["trace"] and traced is None and \
                    time.perf_counter() - t_begin >= 0.3 * ctx["seconds"]:
                jax.block_until_ready(pending)
                profiler_s += _seconds(common.start_trace, ctx["trace_dir"])
                traced = [i, None]
            if traced and traced[1] is None and \
                    i == traced[0] + job["traced_steps"]:
                jax.block_until_ready(pending)
                profiler_s += _seconds(jax.profiler.stop_trace)
                traced[1] = i
            with jax.profiler.TraceAnnotation("bench.feed"):
                batch = next(feed)
            with jax.profiler.TraceAnnotation("bench.step"):
                state, loss = step(state, batch)
            if pending is not None:
                with jax.profiler.TraceAnnotation("bench.loss_fetch"):
                    losses.append(float(pending))
                ends.append(time.perf_counter())
            pending = loss
            if time.perf_counter() - t_begin >= ctx["seconds"]:
                break
        losses.append(float(pending))       # closes the window: the last
        ends.append(time.perf_counter())    # step's result is on the host
        window_s = ends[-1] - t_begin
        if traced and traced[1] is None:
            profiler_s += _seconds(jax.profiler.stop_trace)
            traced[1] = len(losses)
        after = common.snapshot()
        feed.close()            # stops the prefetcher's thread

    steps = len(losses)
    bad = sum(not math.isfinite(x) for x in losses)
    tokens = steps * builder.tokens_per_step(job)
    step_ms = [1e3 * (b - a) for a, b in zip(ends, ends[1:])]
    say("window", steps=steps, window_s=window_s, setup_s=setup_s,
        tokens_per_s=tokens / window_s, non_finite_losses=bad,
        step_ms=stats.summary(step_ms),
        losses_first=losses[:3], losses_last=losses[-3:])
    red = None
    if traced:
        red = common.reduced_trace(ctx["trace_dir"], "between-annotations",
                                   steps=traced)
        inside = step_ms[traced[0]:traced[1] - 1]
        outside = step_ms[:max(0, traced[0] - 1)] + step_ms[traced[1]:]
        say("tracing-overhead",
            step_ms_p50_traced=stats.summary(inside)["p50"],
            step_ms_p50_untraced=stats.summary(outside)["p50"],
            profiler_start_stop_s=profiler_s,
            note="compare tokens_per_s above with a --trace 0 run's")
    checks["non_finite_losses"] = {"value": float(bad), "limit": 0.0}
    return {
        "correct": ok and bad == 0, "checks": checks, "attempted": steps,
        "failed": bad,
        "setup_s": setup_s, "counters_before": before,
        "counters_after": after, "trace": red, "steps": steps,
        "tokens_in_window": tokens, "window_s": window_s,
        # what the profiler's own start and stop took of a traced window
        "profiler_s": profiler_s,
        "samples": {"step_ms": step_ms},
        "flops_per_step": builder.flops_per_step(config, job, shapes),
        "chips": chips, "peaks": ctx["peaks"], "config": config,
        "traffic": job,
    }


def _seconds(fn, *args):
    t0 = time.perf_counter()
    fn(*args)
    return time.perf_counter() - t0


def _state_spread(state, chips, job):
    """Across chips with ZeRO >= 1, the optimizer state is held by all of
    them, about a 1/chips share each."""
    if chips == 1 or job["zero_stage"] < 1:
        return True
    total, per = 0, {}
    for slots in state["opt"].values():
        for arr in slots.values():
            total += arr.nbytes
            for sh in arr.addressable_shards:
                per[sh.device] = per.get(sh.device, 0) + sh.data.nbytes
    worst = max(per.values()) if per else total
    say("opt-state", total_bytes=total, holders=len(per),
        largest_share=worst / max(total, 1))
    return need(len(per) == chips and worst <= 1.2 * total / chips,
                "optimizer state is not spread over %d chips: largest "
                "holder has %d of %d bytes" % (chips, worst, total))
