"""Pipeline parallelism: GPipe microbatch schedule over the `pp` mesh axis.

Capability parity: reference `PipelineOptimizer` (`optimizer.py:3632` splits
the program by device_guard into per-device sections) + `PipelineTrainer` /
`SectionWorker` (`trainer.h:127`, `section_worker.cc:142` — microbatch loop
over sections connected by scope queues, one thread per section).

TPU-first redesign: sections become one SPMD program.  Each `pp` shard
holds ONE stage's parameters; a `lax.scan` over schedule ticks runs every
stage in lockstep while `ppermute` hands activations to the next stage
over ICI.  Because `ppermute` is differentiable (its transpose is the
reverse permutation), `jax.grad` through the scan yields the reverse
pipeline schedule automatically — no hand-written backward scheduler,
no scope queues, no thread pinning.

The schedule is GPipe: T = n_micro + n_stages - 1 ticks, bubble fraction
(n_stages-1)/T; pick n_micro >= 4*n_stages to amortize.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def gpipe(stage_fn, n_stages, n_micro, axis_name="pp",
          first_fn=None, last_fn=None, remat=False):
    """Build a pipelined apply: (stacked_params_local, xs[, first_params,
    last_params]) -> ys.

    stage_fn(params, x) -> y: one stage's compute; the homogeneous middle
    (same activation shape in and out).  Heterogeneous ends (reference
    SectionWorker runs arbitrary per-stage programs, section_worker.cc:142):

      * first_fn(first_params, raw_mb) -> activation — the embedding-style
        entry applied to each raw microbatch before stage 0 (raw shape may
        differ from the inter-stage activation shape);
      * last_fn(last_params, activation) -> output — the head applied after
        the final stage (output shape may differ again).

    Call the result inside shard_map where `axis_name` is a manual axis and
    the stacked params' leading (stage) dim is sharded on it; first/last
    params ride in replicated.

        xs: [n_micro, mb, ...] raw microbatched inputs (used by stage 0)
        returns ys: [n_micro, mb, ...] head outputs, identical on every
        shard (accumulated on the last stage, ONE psum broadcast at the
        end — no per-tick ring traffic).

    remat=True wraps stage_fn in jax.checkpoint: the backward pass then
    stores only each tick's stage INPUT and recomputes the interior,
    bounding activation memory per microbatch to one activation tensor —
    the memory property 1F1B scheduling buys (reference SectionWorker
    holds <= n_stages live microbatches) at the cost of one extra
    forward, without hand-scheduling backward interleaving inside the
    scan.
    """
    if remat:
        stage_fn = jax.checkpoint(stage_fn)

    def pipelined(params_local, xs, first_params=None, last_params=None):
        # drop the sharded stage dim: each shard holds exactly one stage
        params_local = jax.tree.map(lambda a: a[0], params_local)
        s = jax.lax.axis_index(axis_name)
        n_ticks = n_micro + n_stages - 1

        def entry(x):
            return first_fn(first_params, x) if first_fn is not None else x

        def head(a):
            return last_fn(last_params, a) if last_fn is not None else a

        # entry applied ONCE to all microbatches up front (GPipe stores
        # stage-0 inputs anyway); head applied ONCE after the scan — neither
        # runs inside the tick loop, so the embedding gather / vocab matmul
        # cost is per-microbatch, not per-tick-per-shard
        xs_act = jax.vmap(entry)(xs)
        act_shape = xs_act.shape[1:]
        out_s = jax.eval_shape(
            lambda p, x: stage_fn(p, x), params_local,
            jax.ShapeDtypeStruct(act_shape, xs_act.dtype))

        fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(carry, t):
            recv, outs = carry
            # stage 0 ingests microbatch t (zeros on idle ticks)
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            x0 = jnp.where(t < n_micro, xs_act[mb_idx],
                           jnp.zeros(act_shape, xs_act.dtype))
            inp = jnp.where(s == 0, x0, recv)
            out = stage_fn(params_local, inp)
            # hand activations to the next stage over ICI
            recv_next = jax.lax.ppermute(out, axis_name, fwd_perm)
            # last stage accumulates its finished microbatch locally
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            take = (t >= n_stages - 1) & (s == n_stages - 1)
            outs = jnp.where(take, outs.at[out_idx].set(out), outs)
            return (recv_next, outs), None

        outs0 = jax.lax.pcast(
            jnp.zeros((n_micro,) + out_s.shape, out_s.dtype), axis_name,
            to="varying")
        recv0 = jax.lax.pcast(
            jnp.zeros(out_s.shape, out_s.dtype), axis_name, to="varying")
        (_, outs), _ = jax.lax.scan(
            tick, (recv0, outs0), jnp.arange(n_ticks)
        )
        # one collective: broadcast the last stage's activation buffer to
        # every shard, then apply the head replicated (broadcasting hidden
        # states is cheaper than broadcasting vocab-sized logits)
        sel = (s == n_stages - 1).astype(outs.dtype)
        outs = jax.lax.psum(outs * sel, axis_name)
        return jax.vmap(head)(outs)

    return pipelined


class PipelineOptimizer:
    """Static-graph pipeline parallelism (cf. reference optimizer.py:3632).

    Usage matches the reference: annotate the forward with
    ``fluid.device_guard("gpu:<stage>")`` sections, wrap the inner
    optimizer, minimize, then run the program on an Executor whose mesh
    has a ``pp`` axis — the mesh-mode Executor partitions the loss
    ancestors into stages and runs them in a GPipe microbatch schedule
    with `ppermute` boundary handoff (`fluid/pipeline_static.py`; the
    reference's SectionWorker threads + scope queues,
    `section_worker.cc:142`, become one SPMD scan).  Feed the FULL batch
    per run(): each run executes num_microbatches microbatches and does
    ONE optimizer update, exactly the reference PipelineTrainer contract.

    Without a pp mesh the program still runs correctly as a plain
    single-device step (same update given the same full batch) — only
    the stage parallelism is absent.
    """

    def __init__(self, optimizer, num_microbatches=1):
        self._inner = optimizer
        self._num_microbatches = int(num_microbatches)

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        res = self._inner.minimize(
            loss, startup_program, parameter_list, no_grad_set
        )
        prog = loss.block.program
        prog._pipeline = {
            "n_micro": self._num_microbatches,
            "loss": loss.name,
        }
        return res
