"""Static-graph pipeline parallelism: device_guard sections -> GPipe SPMD.

Capability parity: reference `PipelineOptimizer` (`optimizer.py:3632-4482`)
splits a Program into per-device sections by `device_guard` annotations and
`SectionWorker` threads (`framework/section_worker.cc:142`) push microbatch
scopes through them over in-memory queues.

TPU-first redesign — the sections become ONE SPMD program on the `pp` mesh
axis:

  * the forward ops that are ancestors of the loss are partitioned into
    stages by their `op_device` stage index (untagged ops inherit the
    current stage; stage indices must be non-decreasing in program order);
  * a `lax.scan` over GPipe ticks runs every stage in lockstep; each tick
    `ppermute` hands the boundary activations (the union of all vars that
    cross any stage boundary — skip-connections ride through untouched)
    to the next stage over ICI; every shard dynamically indexes its own
    microbatch feeds, so late-stage feeds (labels) need no threading;
  * `jax.grad` through the scan yields the reverse schedule automatically
    (ppermute transposes to the reverse permutation) — the program's
    appended backward ops (op_role=backward) are NOT executed on this
    path; the appended optimizer ops (op_role=optimize) ARE, fed with the
    pipeline-computed grads under the program's own @GRAD names, so the
    user's optimizer/LR-schedule semantics are preserved verbatim.

Persistable vars written by forward stages (batch_norm running stats)
are threaded through the scan as carries — microbatch-SEQUENTIAL, the
reference SectionWorker's order (`framework/section_worker.cc:142`) —
and the owning stage's final value is delta-psum'd to every shard, so
pipelined CNNs with batch norm train with the same running-stat
trajectory as a single device stepping microbatches in order.

Limitations (explicit, erroring): the local batch must divide
num_microbatches.  Full-batch parity holds for mean- AND sum-reduction
losses: the loss reduction is detected from the program
(`_loss_reduction_kind`) and microbatch losses are averaged or summed
accordingly; unrecognized reductions default to mean.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .framework import GRAD_SUFFIX, device_stage_index


def _loss_ancestors(ops, loss_name):
    """Indices of forward ops that are ancestors of loss_name."""
    needed = {loss_name}
    keep = []
    for i in range(len(ops) - 1, -1, -1):
        op = ops[i]
        if any(n in needed for n in op.all_output_names()):
            keep.append(i)
            needed.update(op.all_input_names())
    return set(keep)


def split_forward_stages(ops, loss_name, n_stages):
    """Partition forward ops into pipeline stages.

    Returns (stage_ops, aux_forward_ops, opt_ops, boundary_names) where
    boundary_names are the vars produced in some stage and consumed in a
    LATER stage (the ppermute payload, in deterministic order)."""
    fwd_idx = [i for i, op in enumerate(ops)
               if op.attrs.get("op_role") not in ("backward", "optimize")]
    opt_ops = [op for op in ops if op.attrs.get("op_role") == "optimize"]
    anc = _loss_ancestors([ops[i] for i in fwd_idx], loss_name)
    anc_idx = [fwd_idx[i] for i in range(len(fwd_idx)) if i in anc]
    aux_ops = [ops[i] for i in fwd_idx if i not in set(anc_idx)]

    stage_ops = [[] for _ in range(n_stages)]
    cur = 0
    for i in anc_idx:
        op = ops[i]
        s = device_stage_index(op.attrs.get("op_device"))
        if s is None:
            s = cur
        if s < cur:
            raise ValueError(
                "device_guard stage indices must be non-decreasing in "
                "program order: op %r is tagged stage %d after stage %d"
                % (op.type, s, cur))
        if s >= n_stages:
            raise ValueError(
                "op %r tagged for stage %d but the pp mesh axis has only "
                "%d shards" % (op.type, s, n_stages))
        cur = s
        stage_ops[s].append(op)
    if not stage_ops[0] or sum(1 for so in stage_ops if so) < 2:
        raise ValueError(
            "pipeline program needs >= 2 device_guard stages with ops "
            "(got %d); annotate the forward with fluid.device_guard"
            % sum(1 for so in stage_ops if so))

    produced_at = {}
    for s, sops in enumerate(stage_ops):
        for op in sops:
            for n in op.all_output_names():
                produced_at[n] = s
    boundary = []
    for s, sops in enumerate(stage_ops):
        for op in sops:
            for n in op.all_input_names():
                p = produced_at.get(n)
                if p is not None and p < s and n not in boundary:
                    boundary.append(n)
    return stage_ops, aux_ops, opt_ops, boundary, produced_at


def _loss_reduction_kind(ops, loss_name):
    """'mean' or 'sum': how the program reduces the per-example loss.

    Full-batch parity of the microbatched schedule depends on it: for a
    mean loss, mean-of-microbatch-losses == full-batch loss (equal
    microbatches); for a sum loss the microbatch losses must be SUMMED or
    the loss/grads shrink by 1/num_microbatches.  Walks back from the
    loss var through reduction-neutral ops (scale/cast/assign) to the
    first reducing op; unrecognized producers default to 'mean' (the
    overwhelmingly common convention)."""
    produced_by = {}
    for op in ops:
        for n in op.all_output_names():
            produced_by[n] = op
    name = loss_name
    for _ in range(16):                       # bounded walk-back
        op = produced_by.get(name)
        if op is None:
            break
        if op.type in ("mean", "reduce_mean"):
            return "mean"
        if op.type == "reduce_sum":
            return "sum"
        if op.type in ("scale", "cast", "assign", "share_data"):
            ins = op.all_input_names()
            if not ins:
                break
            name = ins[0]
            continue
        break
    return "mean"


def _stateful_forward_vars(stage_ops, block, scope):
    """Persistable vars WRITTEN by forward stage ops (batch_norm running
    stats).  The reference's SectionWorker carries these sequentially
    across microbatches (`framework/section_worker.cc:142`); here they
    become scan carries — microbatch m+1's stage sees microbatch m's
    update, the SectionWorker order exactly."""
    out = []
    for sops in stage_ops:
        for op in sops:
            for n in op.all_output_names():
                v = block._find_var_recursive(n)
                if ((v is not None and v.persistable) or scope.has(n)) \
                        and n not in out:
                    out.append(n)
    return out


def _varying(x, axis):
    """Type ``x`` as varying over the manual ``axis`` (a value that
    already is stays as it is: the cast accepts only invariant input)."""
    if axis in jax.typeof(x).vma:
        return x
    return jax.lax.pcast(x, axis, to="varying")


def build_pipeline_jit(program, block, ops, feed_names, feed_shapes,
                       fetch_names, state_in, state_out, state_donate,
                       state_ro, scope, mesh, n_micro, loss_name, is_test):
    """Returns a jitted (feed_vals, donate_state, ro_state, rng_key) ->
    (fetches, new_state) with GPipe stage parallelism over the pp axis."""
    from jax.sharding import PartitionSpec as P

    from .core.block_eval import run_ops
    from .core.registry import LowerContext

    n_stages = mesh.axis_size("pp")
    stage_ops, aux_ops, opt_ops, boundary, produced_at = \
        split_forward_stages(ops, loss_name, n_stages)
    stat_names = _stateful_forward_vars(stage_ops, block, scope)
    loss_reduction = _loss_reduction_kind(ops, loss_name)

    # prune aux (non-loss-ancestor) ops nothing consumes, then reject the
    # survivors that read stage activations with a targeted diagnostic
    # (per-microbatch activations are not exposed outside the schedule)
    needed = set(fetch_names)
    for op in opt_ops:
        needed.update(op.all_input_names())
    kept_aux = []
    for op in reversed(aux_ops):
        if any(n in needed for n in op.all_output_names()) \
                or op.attrs.get("op_role") is None and op.type in ("print",):
            kept_aux.append(op)
            needed.update(op.all_input_names())
    aux_ops = list(reversed(kept_aux))
    for op in aux_ops:
        for n in op.all_input_names():
            if n in produced_at:
                raise ValueError(
                    "op %r (not an ancestor of the loss) reads %r, which "
                    "is computed inside pipeline stage %d: per-microbatch "
                    "activations are not exposed outside the pipeline "
                    "schedule.  Fetch the loss / persistable state / vars "
                    "independent of the staged forward, and compute side "
                    "metrics on the host from fetched values or as part "
                    "of the loss program itself" % (op.type, n,
                                                    produced_at[n]))
    # the stage that PRODUCES the loss accumulates it (trailing unannotated
    # stages, if any, just pass the boundary through)
    loss_stage = next(
        s for s, sops in enumerate(stage_ops)
        if any(loss_name in op.all_output_names() for op in sops))

    for n in fetch_names:
        if n != loss_name and n not in state_out and n in boundary:
            raise ValueError(
                "fetch var %r is a pipeline-internal activation; fetchable "
                "on the pipeline path: the loss, persistable state, and "
                "aux (non-loss) vars" % n)

    # grads wanted by the optimizer ops (program's own @GRAD naming)
    grad_params = []
    for op in opt_ops:
        for n in op.all_input_names():
            if n.endswith(GRAD_SUFFIX):
                p = n[: -len(GRAD_SUFFIX)]
                if p not in grad_params:
                    grad_params.append(p)

    # --- shape work (outside jit): boundary structs at microbatch size ---
    def _mb_feed_struct(n):
        shp = tuple(feed_shapes[n])
        if not shp or shp[0] % n_micro != 0:
            raise ValueError(
                "pipeline: feed %r local batch %s must divide "
                "num_microbatches=%d" % (n, shp[:1], n_micro))
        from .framework import np_dtype_of

        v = block._find_var_recursive(n)
        return jax.ShapeDtypeStruct(
            (shp[0] // n_micro,) + shp[1:], np_dtype_of(v))

    mb_structs = {n: _mb_feed_struct(n) for n in feed_names}

    def _state_struct(n):
        v = scope.find_var(n)
        return jax.ShapeDtypeStruct(v.shape, v.dtype)

    state_structs = {n: _state_struct(n) for n in state_in}

    def _fwd_all(env):
        ctx = LowerContext(base_key=jax.random.PRNGKey(0), is_test=True)
        for sops in stage_ops:
            run_ops(sops, env, ctx)
        return {n: env[n] for n in boundary}

    bnd_structs = jax.eval_shape(
        lambda e: _fwd_all(dict(e)), {**mb_structs, **state_structs})

    jmesh = mesh.mesh
    perm = [(i, i + 1) for i in range(n_stages - 1)]

    # SPMD forward: per-shard GPipe schedule over the pp axis.  The loss
    # comes back psum'd (identical on every shard, out_spec P()) so that
    # jax.grad wraps the WHOLE shard_map from outside — shard_map's
    # collective transposes then produce exact gradients (differentiating
    # an in-body psum per shard and psum'ing grads again double-counts
    # by the pp size).
    def pp_forward(train_params, const_params, mb_feeds, rng_key):
        s = jax.lax.axis_index("pp")
        # params enter replicated (invariant over pp) and each stage
        # branch of the switch below reads its own.  Type them varying
        # HERE, so the psum that makes their gradient invariant again
        # sits outside the switch: jax 0.9.0 transposes a switch on a
        # varying index over invariant operands through one branch only
        # (every shard's cotangent came from stage 0's branch)
        params_in = {**const_params, **train_params}
        env_base = {n: _varying(v, "pp") for n, v in params_in.items()}
        # persistable vars written by forward stages (BN running stats)
        # ride the scan carry: microbatch-SEQUENTIAL, like SectionWorker
        stats0 = {n: params_in[n] for n in stat_names}

        def tick(carry, t):
            bnd, acc, stats = carry
            bnd = jax.tree.map(
                lambda a: jax.lax.ppermute(a, "pp", perm), bnd)
            mb = jnp.clip(t - s, 0, n_micro - 1)
            valid = (t - s >= 0) & (t - s < n_micro)
            feeds_t = {
                n: jax.lax.dynamic_index_in_dim(
                    a, mb, axis=0, keepdims=False)
                for n, a in mb_feeds.items()
            }

            def run_stage(si):
                def f(operand):
                    bnd_in, stats_in = operand
                    env = dict(env_base)
                    env.update(feeds_t)
                    env.update(stats_in)     # carried stats win
                    env.update(bnd_in)
                    ctx = LowerContext(
                        base_key=jax.random.fold_in(
                            jax.random.fold_in(rng_key, mb), si),
                        is_test=is_test)
                    run_ops(stage_ops[si], env, ctx)
                    # every switch branch must produce the same
                    # replication type: mark all branch outputs varying
                    # on pp (they are — each shard ran its own stage)
                    out = {n: _varying(env.get(n, bnd_in[n]), "pp")
                           for n in boundary}
                    lv = (env[loss_name].astype(jnp.float32)
                          if si == loss_stage else jnp.float32(0))
                    new_stats = {
                        n: _varying(jax.lax.stop_gradient(
                            env.get(n, stats_in[n])), "pp")
                        for n in stat_names
                    }
                    return (out,
                            _varying(jnp.asarray(lv, jnp.float32).reshape(()),
                                  "pp"),
                            new_stats)
                return f

            new_bnd, lv, new_stats = jax.lax.switch(
                s, [run_stage(i) for i in range(n_stages)], (bnd, stats))
            new_bnd = jax.tree.map(
                lambda new, old: jnp.where(valid, new, old),
                new_bnd, bnd)
            new_stats = jax.tree.map(
                lambda new, old: jnp.where(valid, new, old),
                new_stats, stats)
            acc = acc + jnp.where(valid, lv, 0.0)
            return (new_bnd, acc, new_stats), None

        # every carry leaves a tick varying on pp (each shard ran its
        # own stage), so it has to enter the scan typed that way too
        bnd0 = jax.tree.map(
            lambda sd: _varying(jnp.zeros(sd.shape, sd.dtype), "pp"),
            dict(bnd_structs))
        (_, acc, stats_end), _ = jax.lax.scan(
            tick,
            (bnd0, _varying(jnp.float32(0), "pp"),
             jax.tree.map(lambda a: _varying(a, "pp"), stats0)),
            jnp.arange(n_micro + n_stages - 1))
        # only the last stage accumulated; the psum broadcasts the total.
        # mean losses average over microbatches (== full-batch mean);
        # sum losses just sum (== full-batch sum) — see _loss_reduction_kind
        total = jax.lax.psum(acc, "pp")
        # each stat var was updated only on its owning stage's shard; the
        # delta-psum replicates the owner's final value everywhere
        stats_final = {
            n: stats0[n] + jax.lax.psum(stats_end[n] - stats0[n], "pp")
            for n in stat_names
        }
        loss = total / n_micro if loss_reduction == "mean" else total
        return loss, stats_final

    sharded_loss = jax.shard_map(
        pp_forward,
        mesh=jmesh,
        in_specs=(P(), P(), P(), P()),
        out_specs=(P(), {n: P() for n in stat_names}),
    )

    def step(feed_vals, donate_state, ro_state, rng_key):
        params = {}
        params.update(donate_state)
        params.update(ro_state)
        mb_feeds = {
            n: v.reshape((n_micro, v.shape[0] // n_micro) + v.shape[1:])
            for n, v in feed_vals.items()
        }

        # aux forward ops (LR schedules etc.): replicated, full-batch env
        aux_env = dict(params)
        aux_env.update(feed_vals)
        aux_ctx = LowerContext(base_key=rng_key, is_test=is_test)
        run_ops(aux_ops, aux_env, aux_ctx)

        train_params = {n: params[n] for n in grad_params}
        const_params = {n: v for n, v in params.items()
                        if n not in train_params}
        if grad_params:
            (loss_val, stat_vals), grads = jax.value_and_grad(
                sharded_loss, has_aux=True)(
                train_params, const_params, mb_feeds, rng_key)
        else:  # eval clone: staged forward only, no updates
            loss_val, stat_vals = sharded_loss(
                train_params, const_params, mb_feeds, rng_key)
            grads = {}

        opt_env = dict(params)
        opt_env.update(aux_env)
        opt_env.update(stat_vals)        # carried running stats persist
        for p, g in grads.items():
            opt_env[p + GRAD_SUFFIX] = g.astype(params[p].dtype)
        opt_ctx = LowerContext(base_key=rng_key, is_test=is_test)
        run_ops(opt_ops, opt_env, opt_ctx)

        def fetch_of(n):
            if n == loss_name:
                return loss_val
            if n in opt_env:
                return opt_env[n]
            raise RuntimeError(
                "pipeline fetch %r not available (loss/state/aux only)" % n)

        fetches = [fetch_of(n) for n in fetch_names]
        new_state = {n: opt_env[n] for n in state_out}
        return fetches, new_state

    return jax.jit(step, donate_argnums=(1,))
