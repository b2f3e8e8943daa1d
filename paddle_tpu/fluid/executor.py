"""Executor: lower a Program block to ONE jitted XLA computation and run it.

Capability parity: reference `python/paddle/fluid/executor.py` (Executor:461,
run:890, _run_impl:1081) driving the C++ per-op interpreter
(`framework/executor.cc:184`, hot loop :470-476 with kernel dispatch at
`operator.cc:934`).  TPU-first redesign: there is no interpreter.  The whole
block — forward, backward, optimizer updates — traces into a single jaxpr and
compiles to one XLA executable; persistable state is threaded functionally
with donated buffers so parameter updates are in-place on device.  The
per-op GC, kernel chooser, and data-transfer machinery of the reference
collapse into XLA's memory planner and layout assignment.

Program-level executable cache keyed like the reference's program cache
(`executor.py:382` _get_program_cache_key): (program identity+version, feed
signature, fetch list, state signature).
"""

from __future__ import annotations

import numpy as np

from . import framework
from .core import dtypes as dtypes_mod
from .core.place import Place, default_place
from .core.registry import LowerContext, get_op_def
from .core.scope import Scope, global_scope


class _LoweredBlock:
    """A compiled (feed, state, key) -> (fetch, new_state) executable."""

    def __init__(self, program, block, feed_names, fetch_names, scope,
                 dp_devices=None, mesh=None, feed_shapes=None):
        import jax

        feed_shapes = feed_shapes or {}

        self.feed_names = list(feed_names)
        self.fetch_names = list(fetch_names)
        # single-process data parallel (CompiledProgram.with_data_parallel):
        # a 1-axis GSPMD mesh; feeds shard on dim 0, state replicates
        self.dp_mesh = None
        if dp_devices:
            import numpy as _np
            from jax.sharding import Mesh

            self.dp_mesh = Mesh(_np.array(dp_devices), ("dp",))
        # mesh mode (SPMD over a DeviceMesh, possibly multi-process): the
        # whole block runs under shard_map on the "dp" axis so transpiled
        # c_allreduce_* ops bind the axis and lower to real psum — the
        # execution story behind transpiler/collective.py (reference
        # ParallelExecutor multi-trainer semantics: each rank feeds its
        # LOCAL batch, gradients all-reduce across ranks).
        self.mesh = mesh
        ops = block.ops

        produced = set()
        state_in = []  # persistable inputs read from scope
        for op in ops:
            for name in op.all_input_names():
                if name in produced or name in feed_names or name in state_in:
                    continue
                v = block._find_var_recursive(name)
                if scope.has(name):
                    state_in.append(name)
                elif v is not None and v.persistable:
                    raise RuntimeError(
                        "persistable var '%s' read before initialization — "
                        "run the startup program first (fluid.default_startup_program())"
                        % name
                    )
                else:
                    raise RuntimeError(
                        "op %r reads var '%s' which is neither fed, produced, "
                        "nor found in scope" % (op, name)
                    )
            produced.update(op.all_output_names())

        # fetches must be materialized by the block (clear diagnostic when a
        # var was folded into a recompute_segment interior or never produced)
        produced_all = set(feed_names) | set(state_in)
        for op in ops:
            produced_all.update(op.all_output_names())
        for name in fetch_names:
            if name not in produced_all:
                inside_seg = any(
                    op.type == "recompute_segment"
                    and any(
                        name in od["outputs"].get(slot, [])
                        for od in op.attrs.get("ops", [])
                        for slot in od["outputs"]
                    )
                    for op in ops
                )
                if inside_seg:
                    raise RuntimeError(
                        "fetch var '%s' lives inside a recompute segment; "
                        "its value is rematerialized (not stored). Add it to "
                        "the RecomputeOptimizer checkpoints to fetch it."
                        % name
                    )
                raise RuntimeError(
                    "fetch var '%s' is not produced by this program" % name
                )

        # persistable outputs -> write back to scope after the step
        state_out = []
        for op in ops:
            for name in op.all_output_names():
                v = block._find_var_recursive(name)
                if (v is not None and v.persistable) or scope.has(name):
                    if name not in state_out:
                        state_out.append(name)
        self.state_in = state_in
        self.state_out = state_out
        # print ops emit host callbacks; the executor must flush them so
        # output appears before run() returns (including prints serialized
        # into cond/while/recompute sub-op attrs)
        def _has_print(op_seq):
            for o in op_seq:
                o_type = o["type"] if isinstance(o, dict) else o.type
                o_attrs = o["attrs"] if isinstance(o, dict) else o.attrs
                if o_type == "print":
                    return True
                for key in ("ops", "true_ops", "false_ops", "cond_ops",
                            "body_ops", "step_ops"):
                    sub = o_attrs.get(key)
                    if isinstance(sub, list) and _has_print(sub):
                        return True
            return False

        self.has_print_effects = _has_print(ops)
        # Only state that is rewritten may be donated; read-only persistables
        # (e.g. params during eval) must keep their buffers alive in the scope.
        self.state_donate = [n for n in state_in if n in set(state_out)]
        self.state_ro = [n for n in state_in if n not in set(state_out)]

        is_test = program._is_test

        # static pipeline parallelism: a PipelineOptimizer-marked program
        # on a mesh with a pp axis runs device_guard stages in a GPipe
        # schedule (see fluid/pipeline_static.py)
        pp_meta = getattr(program, "_pipeline", None)
        if (pp_meta and mesh is not None and mesh.has_axis("pp")
                and mesh.axis_size("pp") > 1):
            from jax.sharding import PartitionSpec as _P

            from .pipeline_static import build_pipeline_jit

            self.gspmd = False
            self.is_pipeline = True
            # feeds replicate: every pp shard dynamically indexes its own
            # microbatches out of the full local batch
            self.feed_specs = {n: _P() for n in self.feed_names}
            self._jitted = build_pipeline_jit(
                program, block, ops, self.feed_names, feed_shapes,
                self.fetch_names, state_in, state_out, self.state_donate,
                self.state_ro, scope, mesh, pp_meta["n_micro"],
                pp_meta["loss"], is_test)
            return

        # GSPMD mode (program flagged by distributed.static_sharding):
        # ONE logical program jitted with per-var in/out shardings taken
        # from Variable.dist_attr — XLA partitions the computation and
        # inserts the collectives (grad psum for dp, row-parallel psum for
        # tp, ZeRO gather/scatter).  This is the static-graph answer to
        # ParallelExecutor + distribute_transpiler state sharding under one
        # roof: no program rewrite, no explicit c_* ops.
        self.gspmd = bool(getattr(program, "_gspmd", False)) and mesh is not None

        def run_block(feed_vals, donate_state, ro_state, rng_key):
            from .core.block_eval import run_ops

            env = dict(feed_vals)
            env.update(donate_state)
            env.update(ro_state)
            ctx = LowerContext(base_key=rng_key, is_test=is_test)
            run_ops(ops, env, ctx)
            fetches = [env[n] for n in self.fetch_names]
            new_state = {n: env[n] for n in self.state_out}
            return fetches, new_state

        if self.gspmd:
            from jax.sharding import NamedSharding, PartitionSpec as P

            jmesh = mesh.mesh
            repl = NamedSharding(jmesh, P())
            nproc = jax.process_count()

            def _sharding_for(name):
                v = block._find_var_recursive(name)
                spec = getattr(v, "dist_attr", None) if v is not None else None
                return NamedSharding(jmesh, P(*spec)) if spec else repl

            dp_total = mesh.axis_size("dp")
            self.feed_shardings = {}
            for n in feed_names:
                shp = feed_shapes.get(n, ())
                global0 = shp[0] * nproc if len(shp) >= 1 else 0
                if (mesh.has_axis("dp") and global0 > 0
                        and global0 % dp_total == 0):
                    self.feed_shardings[n] = NamedSharding(jmesh, P("dp"))
                else:
                    if (mesh.has_axis("dp") and dp_total > 1 and nproc > 1
                            and len(shp) >= 1 and global0 > 0):
                        # a replicated feed is stitched by treating each
                        # process's LOCAL value as the full global value —
                        # correct only when every rank feeds identical
                        # data (constant tables etc.); warn about the
                        # contract rather than silently corrupt
                        import warnings

                        warnings.warn(
                            "GSPMD feed %r (local shape %s) cannot be "
                            "sharded over the dp axis (global dim0 %d %% "
                            "dp %d != 0); treating it as REPLICATED from "
                            "this process's local value — every rank must "
                            "feed identical data for this to be consistent"
                            % (n, shp, global0, dp_total), stacklevel=3)
                    self.feed_shardings[n] = repl
            self.state_shardings = {
                n: _sharding_for(n)
                for n in set(state_in) | set(state_out)
            }

            self._jitted = jax.jit(
                run_block,
                in_shardings=(
                    dict(self.feed_shardings),
                    {n: self.state_shardings[n] for n in self.state_donate},
                    {n: self.state_shardings[n] for n in self.state_ro},
                    repl,
                ),
                out_shardings=(
                    [repl] * len(self.fetch_names),
                    {n: self.state_shardings[n] for n in self.state_out},
                ),
                donate_argnums=(1,),
            )
        elif mesh is None:
            # donate_state (arg 1): optimizer updates reuse param buffers.
            self._jitted = jax.jit(run_block, donate_argnums=(1,))
        else:
            import jax.numpy as jnp
            from jax.sharding import PartitionSpec as P

            jmesh = mesh.mesh
            ndev = jmesh.devices.size
            nproc = jax.process_count()
            local_dev = max(1, ndev // nproc)
            # per-feed spec: shard dim 0 over dp when this process's LOCAL
            # feed divides over its addressable devices; otherwise
            # replicate (same fallback as the dp_devices path)
            # a mesh without a "dp" axis (e.g. a pure-pp mesh reused for
            # an unannotated program) replicates feeds and maps fetches
            # over its first axis instead of crashing on the dp name
            rank_axis = "dp" if mesh.has_axis("dp") else mesh.axis_names[0]
            self.feed_specs = {}
            for n in feed_names:
                shp = feed_shapes.get(n, ())
                if (mesh.has_axis("dp") and len(shp) >= 1 and shp[0] > 0
                        and shp[0] % local_dev == 0):
                    self.feed_specs[n] = P("dp")
                else:
                    self.feed_specs[n] = P()
            # Per-rank RNG: a startup program (no feeds, no backward/
            # optimize ops) must init identically on every rank — the XLA
            # analogue of the reference's param broadcast
            # (parallel_executor.cc:740 BCastParamsToDevices).  Training/
            # eval programs fold the rank in so dropout masks decorrelate
            # across ranks (reference: per-device CUDA RNG states).
            fold_rank = bool(feed_names) or any(
                op.attrs.get("op_role") in ("backward", "optimize")
                for op in ops
            )

            def run_block_sharded(feed_vals, donate_state, ro_state, rng_key):
                from .core.block_eval import run_ops

                if fold_rank:
                    rng_key = jax.random.fold_in(
                        rng_key, jax.lax.axis_index(rank_axis)
                    )
                env = dict(feed_vals)
                env.update(donate_state)
                env.update(ro_state)
                ctx = LowerContext(base_key=rng_key, is_test=is_test)
                run_ops(ops, env, ctx)
                # fetches gain a leading per-rank dim (shard_map needs a
                # mapped output dim; per-rank values like the local loss
                # genuinely differ across ranks)
                fetches = [jnp.expand_dims(env[n], 0) for n in self.fetch_names]
                new_state = {n: env[n] for n in self.state_out}
                return fetches, new_state

            sharded = jax.shard_map(
                run_block_sharded,
                mesh=jmesh,
                in_specs=(
                    dict(self.feed_specs),
                    P(),  # state replicated (identical after psum'd grads)
                    P(),
                    P(),
                ),
                out_specs=([P(rank_axis)] * len(fetch_names), P()),
                check_vma=False,
            )
            self._jitted = jax.jit(sharded, donate_argnums=(1,))

    def __call__(self, feed_vals, donate_state, ro_state, rng_key):
        return self._jitted(feed_vals, donate_state, ro_state, rng_key)


class Executor:
    """cf. reference fluid.Executor — run(program, feed, fetch_list)."""

    def __init__(self, place: Place = None, mesh=None):
        """mesh: a distributed.DeviceMesh with a "dp" axis switches the
        executor into SPMD mesh mode — every run executes the block under
        shard_map over dp, feeds are PER-RANK local batches (stitched into
        one global array across processes), and transpiled c_allreduce_*
        ops perform real cross-rank reductions.  This is the execution
        engine the collective transpiler targets (reference
        ParallelExecutor / test_dist_base multi-trainer semantics)."""
        self.place = place if place is not None else default_place()
        self.mesh = mesh
        self._cache = {}
        self._rng_counter = 0
        self._run_hist = None  # cached executor_run_ms child (hot path)
        # program -> versions FLAGS_verify_program already checked — weakly
        # keyed (no id-reuse collisions) and independent of _cache so
        # use_program_cache=False loops still verify each program version
        # exactly once, not every step
        import weakref

        self._verified_programs = weakref.WeakKeyDictionary()

    def close(self):
        self._cache.clear()

    # ------------------------------------------------------------------
    def run(
        self,
        program: framework.Program = None,
        feed: dict = None,
        fetch_list=None,
        scope: Scope = None,
        return_numpy: bool = True,
        use_program_cache: bool = True,
    ):
        """Telemetry wrapper around `_run_impl`: the whole call's wall
        time is split compile-vs-compute via the jax.monitoring compile
        accumulator (`observability.step_timer`) and recorded into the
        always-on registry histograms plus the active StepTimer record,
        if a training loop armed one on this thread."""
        import time

        from ..observability import step_timer as _telemetry

        _telemetry.install_jax_compile_hooks()
        t0 = time.perf_counter()
        comp0 = _telemetry.thread_compile_seconds()
        try:
            return self._run_impl(
                program, feed, fetch_list, scope, return_numpy,
                use_program_cache,
            )
        finally:
            t1 = time.perf_counter()
            wall = t1 - t0
            dcomp = min(_telemetry.thread_compile_seconds() - comp0, wall)
            _telemetry.record_component("compile", dcomp)
            _telemetry.record_component("compute", max(wall - dcomp, 0.0))
            from ..observability import trace as _trace

            tracer = _trace.default_tracer()
            if tracer.enabled:
                tracer.complete(
                    "executor.run", t0, t1, cat="executor",
                    args={"compile_ms": round(dcomp * 1e3, 3),
                          "compute_ms": round((wall - dcomp) * 1e3, 3),
                          "fetches": len(fetch_list or [])})
            if self._run_hist is None:
                self._run_hist = _telemetry.default_registry().histogram(
                    "executor_run_ms",
                    "Executor.run wall time: placement + dispatch + "
                    "device execution + fetch materialization (ms)")
            self._run_hist.observe(wall * 1e3)

    def _run_impl(
        self,
        program: framework.Program = None,
        feed: dict = None,
        fetch_list=None,
        scope: Scope = None,
        return_numpy: bool = True,
        use_program_cache: bool = True,
    ):
        import jax

        program = program or framework.default_main_program()
        # CompiledProgram facade (compiler.py) unwraps to its program + config
        dp_devices = None
        facade = None
        if hasattr(program, "_unwrap_for_executor"):
            facade = program
            if hasattr(program, "_dp_devices"):
                dp_devices = program._dp_devices()
            program = program._unwrap_for_executor()
        feed = dict(feed or {})
        scope = scope or global_scope()
        fetch_names = []
        for f in fetch_list or []:
            fetch_names.append(f.name if isinstance(f, framework.Variable) else str(f))

        block = program.global_block

        # -- convert feeds -------------------------------------------------
        # jax.Arrays (an io.DevicePrefetcher feed) stay device-resident:
        # np.asarray on them would round-trip device->host->device and
        # throw away exactly the overlap the prefetcher bought
        feed_vals = {}
        for name, value in feed.items():
            v = block._find_var_recursive(name)
            if isinstance(value, jax.Array):
                arr = value
                if v is not None and \
                        dtypes_mod.to_jnp(v.dtype) != arr.dtype.type:
                    arr = arr.astype(dtypes_mod.to_jnp(v.dtype))
            else:
                arr = np.asarray(value)
                if v is not None and \
                        dtypes_mod.to_jnp(v.dtype) != arr.dtype.type:
                    arr = arr.astype(dtypes_mod.to_str(v.dtype))
            feed_vals[name] = arr

        # CompiledProgram.with_autotune: first run searches (or loads
        # from the tuning cache) the winning pass pipeline for THIS
        # program version at the live feed shapes; later runs execute
        # the cached tuned clone (same var names, so scope state and
        # feeds carry over unchanged)
        if (facade is not None
                and getattr(facade, "_autotune", None) and fetch_names):
            program = facade._ensure_tuned(
                feed_vals, fetch_names, mesh=self.mesh)
            block = program.global_block

        feed_sig = tuple(
            (n, feed_vals[n].shape, str(feed_vals[n].dtype)) for n in sorted(feed_vals)
        )
        from .flags import get_flags

        key = (
            id(program),
            program._version,
            feed_sig,
            tuple(fetch_names),
            id(scope),
            tuple(id(d) for d in dp_devices) if dp_devices else None,
            id(self.mesh) if self.mesh is not None else None,
            # the NaN guard is baked into the traced program, so the flag
            # must participate in the cache key
            bool(get_flags(["FLAGS_check_nan_inf"])["FLAGS_check_nan_inf"]),
            bool(getattr(program, "_gspmd", False)),
        )
        from .core import monitor
        from ..observability import step_timer as _telemetry

        entry = self._cache.get(key) if use_program_cache else None
        if entry is None:
            # FLAGS_verify_program: opt-in static verification the first
            # time each program version is run — a mutated or hand-built
            # program fails here with a structured diagnostic naming the
            # op/var instead of an XLA trace error below
            seen = self._verified_programs.get(program)
            if (seen is None or program._version not in seen) and \
                    get_flags(["FLAGS_verify_program"])["FLAGS_verify_program"]:
                from ..analysis import assert_program_valid

                assert_program_valid(
                    program, feed_names=list(feed_vals),
                    fetch_names=fetch_names,
                    what="program handed to Executor.run "
                         "(FLAGS_verify_program)")
                self._verified_programs.setdefault(
                    program, set()).add(program._version)
            # cache miss: the lowering/trace below plus the XLA compile
            # inside the first jitted call are "compile" time.  The
            # jax.monitoring hooks catch the XLA side; the lowering wall
            # time is pushed into the same thread accumulator (minus any
            # compile events that already fired inside it) so the run
            # wrapper attributes it to compile, not compute.
            import time as _time

            t_lower = _time.perf_counter()
            c_lower = _telemetry.thread_compile_seconds()
            entry = _LoweredBlock(
                program, block, list(feed_vals), fetch_names, scope,
                dp_devices=dp_devices, mesh=self.mesh,
                feed_shapes={n: a.shape for n, a in feed_vals.items()},
            )
            t_lower1 = _time.perf_counter()
            lower_secs = t_lower1 - t_lower
            lower_evt = _telemetry.thread_compile_seconds() - c_lower
            _telemetry.add_thread_compile_seconds(lower_secs - lower_evt)
            from ..observability import trace as _trace

            _tracer = _trace.default_tracer()
            if _tracer.enabled:
                _tracer.complete(
                    "executor.lower", t_lower, t_lower1, cat="executor",
                    args={"program_version": program._version,
                          "feeds": sorted(feed_vals)})
            monitor.stat_add("STAT_executor_programs_compiled")
            _telemetry.default_registry().histogram(
                "executor_lowering_ms",
                "Program lowering (trace + jit build) wall time (ms)"
            ).observe(lower_secs * 1e3)
            if use_program_cache:
                self._cache[key] = entry
            self._maybe_warn_unused_vars(block, fetch_names)
        monitor.stat_add("STAT_executor_runs")

        donate_state = {n: scope.find_var(n) for n in entry.state_donate}
        ro_state = {n: scope.find_var(n) for n in entry.state_ro}
        if entry.mesh is not None and entry.gspmd:
            # GSPMD: feeds are per-process LOCAL batches stitched into one
            # global batch-sharded array; state is placed per its dist_attr
            # sharding (a resharding device_put is a no-op when the scope
            # value already lands right, e.g. coming out of the last step)
            def _place(n, v):
                tgt = entry.state_shardings[n]
                return v if getattr(v, "sharding", None) == tgt \
                    else jax.device_put(v, tgt)

            def _to_global(a, sharding):
                if getattr(a, "sharding", None) == sharding:
                    return a
                if isinstance(a, jax.Array):
                    # device-resident with a different layout: reshard on
                    # device (np.asarray would fail on a multi-host
                    # global array, and would mislabel global shape as
                    # process-local data)
                    return jax.device_put(a, sharding)
                return jax.make_array_from_process_local_data(
                    sharding, np.asarray(a))

            feed_dev = {
                n: _to_global(a, entry.feed_shardings[n])
                for n, a in feed_vals.items()
            }
            donate_state = {n: _place(n, v) for n, v in donate_state.items()}
            ro_state = {n: _place(n, v) for n, v in ro_state.items()}
        elif entry.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            jmesh = entry.mesh.mesh
            repl = NamedSharding(jmesh, P())

            def _stitch(a, sharding):
                # per-process local data -> one global array (works single-
                # process too, where local IS global); already-placed
                # device arrays pass through or reshard on device
                if getattr(a, "sharding", None) == sharding:
                    return a
                if isinstance(a, jax.Array):
                    return jax.device_put(a, sharding)
                return jax.make_array_from_process_local_data(
                    sharding, np.asarray(a)
                )

            def _ensure_repl(d):
                return {
                    n: v if getattr(v, "sharding", None) == repl
                    else _stitch(v, repl)
                    for n, v in d.items()
                }

            feed_dev = {
                n: _stitch(a, NamedSharding(jmesh, entry.feed_specs[n]))
                for n, a in feed_vals.items()
            }
            donate_state = _ensure_repl(donate_state)
            ro_state = _ensure_repl(ro_state)
        elif entry.dp_mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            mesh = entry.dp_mesh
            ndev = mesh.devices.size
            repl = NamedSharding(mesh, P())

            def _put_feed(a):
                if a.ndim >= 1 and a.shape[0] > 0 and a.shape[0] % ndev == 0:
                    return jax.device_put(a, NamedSharding(mesh, P("dp")))
                return jax.device_put(a, repl)

            feed_dev = {n: _put_feed(a) for n, a in feed_vals.items()}
            donate_state = {
                n: jax.device_put(v, repl) for n, v in donate_state.items()
            }
            ro_state = {n: jax.device_put(v, repl) for n, v in ro_state.items()}
        else:
            device = self.place.get_device()
            feed_dev = {
                n: jax.device_put(a, device) for n, a in feed_vals.items()
            }

        seed = program.random_seed
        if seed is None:
            self._rng_counter += 1
            seed_val = self._rng_counter
        else:
            seed_val = seed + self._rng_counter
            self._rng_counter += 1
        rng_key = jax.random.PRNGKey(seed_val)

        fetches, new_state = entry(feed_dev, donate_state, ro_state, rng_key)
        if entry.has_print_effects:
            jax.effects_barrier()

        for n, val in new_state.items():
            scope.set(n, val)

        if (entry.mesh is not None and not entry.gspmd
                and not getattr(entry, "is_pipeline", False)):
            # fetches carry a leading per-rank dim; a process can only read
            # its addressable shards, so return the LOCAL ranks' values
            # (shape [n_local_ranks, ...]) — reference multi-trainer
            # semantics: each trainer sees its own fetch results.
            out = []
            for f in fetches:
                shards = sorted(
                    f.addressable_shards, key=lambda s: s.index[0].start or 0
                )
                loc = np.concatenate([np.asarray(s.data) for s in shards], 0)
                out.append(loc if return_numpy else jax.numpy.asarray(loc))
            return out

        if return_numpy:
            return [np.asarray(f) for f in fetches]
        return fetches

    @staticmethod
    def _maybe_warn_unused_vars(block, fetch_names):
        """FLAGS_enable_unused_var_check (reference
        `framework/unused_var_check.cc`): warn about op outputs nothing
        consumes — usually a sign of a mis-built program."""
        from .flags import get_flags

        if not get_flags(["FLAGS_enable_unused_var_check"]).get(
            "FLAGS_enable_unused_var_check"
        ):
            return
        consumed = set(fetch_names)
        for op in block.ops:
            consumed.update(op.all_input_names())
        unused = []
        for op in block.ops:
            for n in op.all_output_names():
                v = block._find_var_recursive(n)
                persistable = v is not None and getattr(
                    v, "persistable", False
                )
                if (n not in consumed and not persistable
                        and "@GRAD@JUNK" not in n):
                    # @GRAD@JUNK: deliberate cotangent sinks (backward.py)
                    unused.append("%s (from %s)" % (n, op.type))
        if unused:
            import warnings

            warnings.warn(
                "unused op outputs (FLAGS_enable_unused_var_check): %s"
                % ", ".join(unused[:20])
            )

    # ------------------------------------------------------------------
    # Dataset trainer path (cf. reference Executor.train_from_dataset
    # executor.py:1448 -> _run_from_dataset:1323 -> TrainerDesc +
    # MultiTrainer/HogwildWorker threads, trainer.h:38).  TPU-first
    # redesign: the per-thread interpreter workers collapse into the one
    # jitted block — the native C++ engine parses/shuffles in its own
    # threads while XLA executes the previous batch, and ragged slots are
    # padded to the program's declared static shapes (bucketed otherwise)
    # so recompiles stay bounded.
    # ------------------------------------------------------------------
    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        """One full pass over `dataset` driving `program` batch-by-batch
        with no Python reader.  fetch_list vars are printed every
        `print_period` batches when `debug` (reference PrintFetchVars
        semantics, device_worker.h)."""
        return self._run_from_dataset(
            program, dataset, scope, fetch_list, fetch_info,
            print_period, debug,
        )

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        """Same loop as train_from_dataset but gradient/optimizer ops DO
        NOT run (reference contract, executor.py:1519): the program is
        pruned via clone(for_test=True), cached per program version."""
        program = program or framework.default_main_program()
        key = (id(program), program._version)
        cache = getattr(self, "_infer_clone_cache", None)
        if cache is None:
            cache = self._infer_clone_cache = {}
        clone = cache.get(key)
        if clone is None:
            if len(cache) > 8:
                cache.clear()
            clone = cache[key] = program.clone(for_test=True)
        return self._run_from_dataset(
            clone, dataset, scope, fetch_list, fetch_info,
            print_period, debug,
        )

    def _run_from_dataset(self, program, dataset, scope, fetch_list,
                          fetch_info, print_period, debug):
        if dataset is None:
            raise ValueError("train_from_dataset needs a dataset")
        from .dataset import pad_batch

        program = program or framework.default_main_program()
        block = program.global_block
        fetch_names = [
            f.name if isinstance(f, framework.Variable) else str(f)
            for f in (fetch_list or [])
        ]
        labels = list(fetch_info or fetch_names)
        last_fetch = None
        for step, batch in enumerate(dataset):
            feed = {}
            for name, _is_float in dataset._slots:
                vals, lod = batch[name]
                lod = np.asarray(lod)
                lens = lod[1:] - lod[:-1]
                v = block._find_var_recursive(name)
                vshape = v.shape if v is not None and v.shape else None
                if (np.all(lens == 1) and vshape is not None
                        and len(vshape) >= 2 and vshape[-1] == 1):
                    # one value per sample: dense column (CTR labels)
                    feed[name] = vals.reshape(-1, 1)
                    continue
                # ragged slot -> padded dense [B, T]; T from the program's
                # declared dim, else bucketed to the next power of two so
                # the executor cache sees few distinct shapes
                T = None
                if vshape is not None and len(vshape) >= 2 and vshape[1] > 0:
                    T = int(vshape[1])
                dense, _mask = pad_batch(vals, lod, max_len=T)
                if T is None and dense.shape[1] > 0:
                    L = 1
                    while L < dense.shape[1]:
                        L *= 2
                    if L != dense.shape[1]:
                        pad = np.zeros(
                            (dense.shape[0], L - dense.shape[1]),
                            dense.dtype)
                        dense = np.concatenate([dense, pad], axis=1)
                feed[name] = dense
                lname = name + "_length"
                if block._find_var_recursive(lname) is not None:
                    feed[lname] = lens.astype(np.int64)
            out = self.run(program, feed=feed, fetch_list=fetch_names,
                           scope=scope)
            last_fetch = out
            if debug and fetch_names and step % max(print_period, 1) == 0:
                msg = ", ".join(
                    "%s=%s" % (lbl, np.asarray(val).reshape(-1)[:4])
                    for lbl, val in zip(labels, out)
                )
                print("[train_from_dataset] step %d: %s" % (step, msg))
        return last_fetch

    # convenience used by tests/io
    def run_startup(self, startup_program=None, scope=None):
        startup_program = startup_program or framework.default_startup_program()
        return self.run(startup_program, feed={}, fetch_list=[], scope=scope)


def scope_guard(scope):
    """cf. fluid.scope_guard."""
    import contextlib

    @contextlib.contextmanager
    def _guard():
        from .core import scope as scope_mod

        old = scope_mod._global_scope
        scope_mod._global_scope = scope
        try:
            yield
        finally:
            scope_mod._global_scope = old

    return _guard()
