"""Measured search: prune statically, verify, compile-and-time, cache.

The loop every front end shares (``search`` for Programs,
``search_flash_blocks`` for the pallas attention grid,
``search_bucket_ladder`` for serving ladders, ``search_step`` for
jitted-train-step knobs):

  1. **cache** — build the workload's key (`tune.cache`) and return the
     stored winner when the same program/mesh/chip/jax already searched;
     a cache hit compiles and times NOTHING.
  2. **enumerate** — candidates from `tune.space`.
  3. **prune** — rank candidates with the `analysis.perf` static
     roofline model; anything `prune_ratio` x slower than the best
     estimate is never compiled (TVM/Ansor discipline: the cost model's
     job is to keep the compiler queue short, PERF.md round 8 anchored
     it to XLA within ~1%% on the zoo).
  4. **verify** — every surviving program candidate runs through
     `ir.clone_and_apply(verify=True)`: a broken pass EXCLUDES the
     candidate with the offending pass named (PR 5's safety net); broken
     candidates are recorded, never timed.
  5. **measure** — warmup + median-of-k on synthetic zero inputs,
     outputs blocked via `jax.block_until_ready`.  Compile cost is split
     out of the measurement via the PR-4 jax.monitoring accumulator
     (``xla_compilations_total`` + thread compile seconds), so the
     report attributes search cost honestly; every candidate emits a
     PR-6 tracer span.
  6. **persist** — the winner (with its measured/default times) goes to
     the `TuningCache`; the second run of the workload gets it for free.

The measured default is ALWAYS in the space, so the winner is never
worse than the default under the same harness — the tuner can only
keep or reject, exactly the PERF.md experiment discipline, mechanized.
"""

from __future__ import annotations

import os
import time

import numpy as np

from . import space as space_mod
from .cache import TuningCache, cache_key_parts

__all__ = [
    "CandidateResult",
    "SearchReport",
    "search",
    "search_bucket_ladder",
    "search_flash_blocks",
    "search_gemm_blocks",
    "search_step",
    "search_train_step",
    "tuned_program",
]

# statuses a candidate can end a search with
TIMED = "timed"
PRUNED = "pruned"
EXCLUDED = "excluded"
SKIPPED_BUDGET = "skipped_budget"
CACHED = "cached"


def _median(xs):
    xs = sorted(xs)
    n = len(xs)
    return xs[n // 2] if n % 2 else 0.5 * (xs[n // 2 - 1] + xs[n // 2])


def _default_measured(results, default_cand):
    """The measured time of THE default candidate — None when it did
    not survive to be timed (an excluded/budget-skipped default must
    never be silently impersonated by whichever candidate timed
    first)."""
    for r in results:
        if r.candidate is default_cand and r.status == TIMED:
            return r.measured_s
    return None


def _registry():
    from ..observability import default_registry

    return default_registry()


def _tracer():
    from ..observability import trace

    return trace.default_tracer()


def _note_status(status):
    try:
        _registry().counter(
            "tune_candidates_total",
            "Autotuner candidates by terminal status",
            labelnames=("status",)).labels(status).inc()
    except Exception:
        pass


def _compile_marks():
    """(thread compile seconds, global xla compilation count) — diffed
    around a measurement to attribute search cost to compilation."""
    from ..observability import step_timer

    step_timer.install_jax_compile_hooks()
    n = 0
    try:
        n = _registry().counter(
            "xla_compilations_total",
            "XLA backend compilations (jax.monitoring)").value
    except Exception:
        pass
    return step_timer.thread_compile_seconds(), n


class CandidateResult:
    """One candidate's fate: status + static estimate + measurement."""

    __slots__ = ("candidate", "status", "est_time_s", "measured_s",
                 "times", "compile_s", "compiles", "error", "detail")

    def __init__(self, candidate, status, est_time_s=None, measured_s=None,
                 times=None, compile_s=None, compiles=None, error=None,
                 detail=None):
        self.candidate = candidate
        self.status = status
        self.est_time_s = est_time_s
        self.measured_s = measured_s
        self.times = list(times or ())
        self.compile_s = compile_s
        self.compiles = compiles
        self.error = error
        self.detail = detail or {}

    @property
    def label(self):
        return self.candidate.label

    @property
    def params(self):
        return self.candidate.params

    def to_dict(self):
        d = self.candidate.to_dict()
        d.update({
            "status": self.status, "est_time_s": self.est_time_s,
            "measured_s": self.measured_s, "times": self.times,
            "compile_s": self.compile_s, "compiles": self.compiles,
            "error": self.error,
        })
        if self.detail:
            d["detail"] = self.detail
        return d


class SearchReport:
    """The full verdict of one search, serializable for the CLI/cache."""

    SCHEMA_VERSION = 1

    def __init__(self, kind, workload, key_parts, cache_hit, results,
                 winner, default_s=None, searched_s=None, cache_path=None,
                 cache_stored=False):
        self.kind = kind
        self.workload = workload
        self.key_parts = key_parts
        self.cache_hit = cache_hit
        self.results = list(results)
        self.winner = winner                  # CandidateResult
        self.default_s = default_s
        self.searched_s = searched_s
        self.cache_path = cache_path
        self.cache_stored = cache_stored

    @property
    def speedup(self):
        if (self.winner is None or not self.winner.measured_s
                or not self.default_s):
            return None
        return self.default_s / self.winner.measured_s

    def counts(self):
        out = {}
        for r in self.results:
            out[r.status] = out.get(r.status, 0) + 1
        return out

    def excluded(self):
        return [r for r in self.results if r.status == EXCLUDED]

    def to_dict(self):
        return {
            "schema_version": self.SCHEMA_VERSION,
            "kind": self.kind,
            "workload": self.workload,
            "key_parts": self.key_parts,
            "cache_hit": self.cache_hit,
            "cache_path": self.cache_path,
            "cache_stored": self.cache_stored,
            "counts": self.counts(),
            "candidates": [r.to_dict() for r in self.results],
            "winner": self.winner.to_dict() if self.winner else None,
            "default_s": self.default_s,
            "speedup": self.speedup,
            "searched_s": self.searched_s,
        }

    def format(self):
        lines = ["autotune[%s] %s" % (self.kind, self.workload)]
        lines.append("  cache: %s%s" % (
            "HIT" if self.cache_hit else "miss",
            " (%s)" % self.cache_path if self.cache_path else ""))
        if self.results:
            lines.append("  %-34s %-14s %10s %12s %11s" % (
                "candidate", "status", "est_ms", "measured_ms",
                "compile_ms"))
            for r in self.results:
                lines.append("  %-34s %-14s %10s %12s %11s" % (
                    r.label[:34], r.status,
                    "%.3f" % (r.est_time_s * 1e3)
                    if r.est_time_s is not None else "-",
                    "%.3f" % (r.measured_s * 1e3)
                    if r.measured_s is not None else "-",
                    "%.1f" % (r.compile_s * 1e3)
                    if r.compile_s is not None else "-"))
        for r in self.excluded():
            lines.append("  excluded %s: %s" % (r.label, r.error))
        if self.winner is not None:
            sp = self.speedup
            lines.append(
                "  winner: %s%s%s" % (
                    self.winner.label,
                    " measured %.3f ms" % (self.winner.measured_s * 1e3)
                    if self.winner.measured_s is not None else "",
                    " vs default %.3f ms (%.2fx)"
                    % (self.default_s * 1e3, sp)
                    if self.default_s and sp else ""))
        if self.searched_s is not None:
            lines.append("  search wall time: %.2f s" % self.searched_s)
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# measurement harness
# ---------------------------------------------------------------------------


def measure_callable(fn, make_args, warmup=1, k=5):
    """Warmup + median-of-k wall time of ``fn(*make_args())`` with the
    outputs blocked until ready; compile work (counted by the PR-4
    accumulator) is attributed to the warmup phase and reported
    separately so search cost never masquerades as step time."""
    import warnings

    import jax

    c0, n0 = _compile_marks()
    with warnings.catch_warnings():
        # a candidate whose donation is unusable is a measured outcome
        # the report captures — not a user mistake worth a warning per
        # candidate trace
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        for _ in range(max(warmup, 1)):
            jax.block_until_ready(fn(*make_args()))
    c1, n1 = _compile_marks()
    times = []
    for _ in range(max(k, 1)):
        args = make_args()
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return {"median_s": _median(times), "times": times,
            "compile_s": max(c1 - c0, 0.0), "compiles": int(n1 - n0)}


# ---------------------------------------------------------------------------
# program search
# ---------------------------------------------------------------------------


def _program_workload(program):
    from ..incubate.checkpoint.checkpoint_saver import program_hash

    return program_hash(program)


def _zero_inputs(program, dynamic_dim, feed_specs=None):
    """{name: zero ndarray} for every block-0 input (feeds + params),
    shapes from recorded metadata with -1 -> dynamic_dim; ``feed_specs``
    ({name: (shape, dtype) | ndarray}) overrides individual entries so
    an entry point can tune for the live feed shapes."""
    from ..analysis.perf import _program_input_vars
    from ..fluid.core import dtypes as dtypes_mod

    feed_specs = feed_specs or {}
    block = program.global_block
    vals = {}
    for n in _program_input_vars(program):
        spec = feed_specs.get(n)
        if isinstance(spec, np.ndarray):
            vals[n] = np.zeros(spec.shape, spec.dtype)
            continue
        if spec is not None:
            shape, dtype = spec
            # to_jnp handles every dtype spelling incl. "bfloat16",
            # which plain np.dtype(str) does not understand
            vals[n] = np.zeros(tuple(shape),
                               np.dtype(dtypes_mod.to_jnp(dtype)))
            continue
        v = block._find_var_recursive(n)
        shape = tuple(dynamic_dim if s == -1 else int(s)
                      for s in (v.shape or ()))
        vals[n] = np.zeros(shape, np.dtype(dtypes_mod.to_jnp(v.dtype)))
    return vals


def _apply_sharding(clone, decision):
    """Annotate `decision["vars"]` with a dist_attr sharding the
    decision's dim over its axis and flag the program GSPMD — the
    static_sharding convention the mesh executor honors."""
    block = clone.global_block
    for name in decision["vars"]:
        v = block._find_var_recursive(name)
        if v is None or not v.shape:
            continue
        spec = [None] * len(v.shape)
        spec[decision.get("dim", -1)] = decision["axis"]
        v.dist_attr = tuple(spec)
    clone._gspmd = True
    return clone


def _program_runner(clone, fetch_names, vals, donate, mesh=None,
                    sharding=None):
    """(jitted_fn, make_args) executing block 0 over an input dict.
    Donation passes the whole input dict as the donated argument, so
    the make_args thunk re-places fresh device buffers per call."""
    import jax

    from ..fluid.core.block_eval import run_ops
    from ..fluid.core.registry import LowerContext

    block = clone.global_block
    ops = block.ops

    def f(env_in):
        env = dict(env_in)
        ctx = LowerContext(base_key=jax.random.PRNGKey(0), is_test=True)
        run_ops(ops, env, ctx)
        return [env[n] for n in fetch_names]

    kw = {}
    if donate:
        kw["donate_argnums"] = (0,)
    if sharding is not None and mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        jmesh = mesh.mesh
        repl = NamedSharding(jmesh, P())
        in_sh = {}
        for n in vals:
            v = block._find_var_recursive(n)
            spec = getattr(v, "dist_attr", None) if v is not None else None
            in_sh[n] = NamedSharding(jmesh, P(*spec)) if spec else repl
        kw["in_shardings"] = (in_sh,)
        jf = jax.jit(f, **kw)

        def make_args():
            return ({n: jax.device_put(a, in_sh[n])
                     for n, a in vals.items()},)

        return jf, make_args

    jf = jax.jit(f, **kw)
    if donate:
        def make_args():
            return ({n: jax.device_put(a) for n, a in vals.items()},)
    else:
        placed = {n: jax.device_put(a) for n, a in vals.items()}

        def make_args():
            return (placed,)

    return jf, make_args


def _resolve_cache(use_cache, cache_dir):
    return TuningCache(cache_dir) if use_cache else None


def _winner_from_entry(kind, entry):
    w = entry["winner"]
    cand = space_mod.Candidate(w.get("kind", kind), w.get("params", {}),
                               label=w.get("label"))
    return CandidateResult(
        cand, CACHED, measured_s=w.get("measured_s"),
        compile_s=w.get("compile_s"), detail=w.get("detail"))


def _cache_winner_dict(result):
    return {
        "kind": result.candidate.kind, "params": result.params,
        "label": result.label, "measured_s": result.measured_s,
        "compile_s": result.compile_s,
        "detail": result.detail or None,
    }


def _pipeline_reconstructible(params):
    """True when every pass in the winning pipeline is resolvable from
    the registry by name — only such winners may be cached (an ad-hoc
    Pass INSTANCE cannot be rebuilt in a later process)."""
    from ..fluid import ir

    return all(n in ir._PASS_REGISTRY for n in params.get("pipeline", ()))


def search(program, fetch_list, *, feed_specs=None, mesh=None, space=None,
           chip=None, dynamic_dim=None, warmup=1, k=5, budget_s=None,
           prune_ratio=1.5, use_cache=True, cache_dir=None, platform=None,
           jax_version=None):
    """Measured autotune of a Program: pass pipelines x donation
    (+ GSPMD sharding of large matmuls when ``mesh`` has a >1 axis).

    Returns a `SearchReport`; materialize the winner with
    `tuned_program(program, report)`.  ``budget_s`` bounds the
    compile-and-time phase: the measured baseline always runs, further
    candidates are recorded as ``skipped_budget`` once the budget is
    spent (never silently dropped).  ``platform``/``jax_version``
    override the cache key for tests/cross-tuning."""
    from ..analysis import perf
    from ..fluid import ir

    t_start = time.perf_counter()
    if dynamic_dim is None:
        dynamic_dim = perf.DEFAULT_DYNAMIC_DIM
    chip = chip or perf.ChipSpec.detect()
    fetch_names = [getattr(f, "name", f) for f in fetch_list]
    workload = _program_workload(program)
    # the fetch list is part of the workload identity: pipelines are
    # measured (and DCE "keep"-protected) FOR a fetch set — a winner
    # cached for ['loss'] must not serve a ['loss','acc'] run, whose
    # producers a cached dead-op pipeline would delete.  Different live
    # feed shapes are a different workload too.
    import hashlib

    space = space or space_mod.SearchSpace()
    cands = space.program_candidates(program, mesh=mesh)
    # measured baseline first — every later verdict is relative to it
    cands.sort(key=lambda c: (c.params.get("pipeline") != [],
                              not c.params.get("donate", True),
                              c.params.get("sharding") is not None))
    for c in cands:
        if c.kind == "program":
            # fetches must survive any pipeline (DeadOpElimination's
            # "keep"); recorded in params so a cached winner re-applies
            # with the same protection
            c.params.setdefault("keep", list(fetch_names))
    # a space containing configured Pass INSTANCES is an ad-hoc
    # experiment: its candidates (and thus its verdict) cannot be
    # reconstructed from names in a later process, so such a search
    # neither reads nor writes the cache
    adhoc_space = any(
        not isinstance(p, str)
        for c in cands for p in c.extra.get("passes", ()))

    sig = repr((sorted(fetch_names), sorted(
        (n, (tuple(np.asarray(s).shape), str(np.asarray(s).dtype))
         if isinstance(s, np.ndarray) else (tuple(s[0]), str(s[1])))
        for n, s in (feed_specs or {}).items()),
        # the candidate SPACE is part of the identity: a winner chosen
        # from one space must not answer a search over another (labels
        # encode pipeline + donate + sharding)
        sorted(c.label for c in cands)))
    workload += ":" + hashlib.sha256(sig.encode()).hexdigest()[:8]
    parts = cache_key_parts(workload, mesh=mesh, chip=chip,
                            platform=platform, jax_version=jax_version)
    cache = _resolve_cache(use_cache and not adhoc_space, cache_dir)

    if cache is not None:
        entry = cache.get(parts)
        if entry is not None and _pipeline_reconstructible(
                entry["winner"].get("params", {})):
            winner = _winner_from_entry("program", entry)
            _note_status(CACHED)
            return SearchReport(
                "program", workload, parts, True, [], winner,
                default_s=entry.get("default_s"),
                searched_s=0.0, cache_path=cache.path_for(parts))

    tracer = _tracer()
    span = (tracer.span("tune.search", cat="tune",
                        args={"workload": workload,
                              "candidates": len(cands)})
            if tracer.enabled else None)
    if span is not None:
        span.__enter__()
    try:
        results = _search_program_candidates(
            program, fetch_names, cands, chip, dynamic_dim, feed_specs,
            mesh, warmup, k, budget_s, prune_ratio, t_start, ir, perf)
    finally:
        if span is not None:
            span.__exit__(None, None, None)

    timed = [r for r in results if r.status == TIMED]
    winner = min(timed, key=lambda r: r.measured_s) if timed else None
    default_r = next(
        (r for r in timed
         if r.params.get("pipeline") == [] and r.params.get("donate", True)
         and not r.params.get("sharding")), None)
    default_s = default_r.measured_s if default_r else None

    cache_path = cache_stored = None
    if (cache is not None and winner is not None
            and _pipeline_reconstructible(winner.params)):
        cache_path = cache.put(
            parts, _cache_winner_dict(winner),
            extra={"default_s": default_s,
                   "speedup": (default_s / winner.measured_s
                               if default_s and winner.measured_s
                               else None),
                   "counts": {}})
        cache_stored = True
    return SearchReport(
        "program", workload, parts, False, results, winner,
        default_s=default_s, searched_s=time.perf_counter() - t_start,
        cache_path=cache_path, cache_stored=bool(cache_stored))


def _search_program_candidates(program, fetch_names, cands, chip,
                               dynamic_dim, feed_specs, mesh, warmup, k,
                               budget_s, prune_ratio, t_start, ir, perf):
    """Verify + statically cost each unique pipeline, prune, then
    compile-and-time survivors in order."""
    def _resolve_passes(passes):
        """Names become registry instances with the fetch list protected
        (DeadOpElimination "keep"); Pass instances pass through as-is."""
        out = []
        for p in passes:
            if isinstance(p, str):
                p = ir.get_pass(p).set("keep", list(fetch_names))
            out.append(p)
        return out

    def _pipe_key(c):
        """Dedup key for a candidate's pipeline.  Names dedup by name;
        a configured Pass INSTANCE carries its id, so two differently-
        .set() instances of the same pass never collapse onto one
        clone/measurement."""
        passes = c.extra.get("passes",
                             list(c.params.get("pipeline", ())))
        return tuple(p if isinstance(p, str)
                     else (space_mod._pass_name(p), id(p))
                     for p in passes)

    tracer = _tracer()
    clones, ests, errors = {}, {}, {}
    for c in cands:
        key = _pipe_key(c)
        if key in clones or key in errors:
            continue
        passes = _resolve_passes(
            c.extra.get("passes", list(c.params.get("pipeline", ()))))
        try:
            clone = ir.clone_and_apply(program, passes, verify=True)
        except Exception as e:
            errors[key] = (str(e), getattr(e, "pass_name", None))
            continue
        clones[key] = clone
        ests[key] = perf.program_cost(
            clone, chip=chip, dynamic_dim=dynamic_dim).total_time_s

    best_est = min(ests.values()) if ests else 0.0
    results = []
    default_runner = None
    for c in cands:
        key = _pipe_key(c)
        is_default = (c.params.get("pipeline") == []
                      and c.params.get("donate", True)
                      and not c.params.get("sharding"))
        if key in errors:
            msg, pass_name = errors[key]
            _note_status(EXCLUDED)
            results.append(CandidateResult(
                c, EXCLUDED, error=msg,
                detail={"pass_name": pass_name} if pass_name else None))
            continue
        est = ests[key]
        if (not is_default and prune_ratio is not None and best_est > 0
                and est > prune_ratio * best_est):
            _note_status(PRUNED)
            results.append(CandidateResult(c, PRUNED, est_time_s=est))
            continue
        if (not is_default and budget_s is not None
                and time.perf_counter() - t_start > budget_s):
            _note_status(SKIPPED_BUDGET)
            results.append(CandidateResult(c, SKIPPED_BUDGET,
                                           est_time_s=est))
            continue
        sharding = c.params.get("sharding")
        clone = clones[key]
        if sharding:
            clone = _apply_sharding(
                ir.clone_and_apply(
                    program,
                    _resolve_passes(c.extra.get(
                        "passes", list(c.params.get("pipeline", ())))),
                    verify=False),
                sharding)
        vals = _zero_inputs(clone, dynamic_dim, feed_specs)
        t0 = time.perf_counter()
        try:
            fn, make_args = _program_runner(
                clone, fetch_names, vals, c.params.get("donate", True),
                mesh=mesh, sharding=sharding)
            m = measure_callable(fn, make_args, warmup=warmup, k=k)
        except Exception as e:
            _note_status(EXCLUDED)
            results.append(CandidateResult(
                c, EXCLUDED, est_time_s=est,
                error="%s: %s" % (type(e).__name__, e)))
            continue
        t1 = time.perf_counter()
        if tracer.enabled:
            tracer.complete(
                "tune.candidate", t0, t1, cat="tune",
                args={"label": c.label,
                      "measured_ms": round(m["median_s"] * 1e3, 3),
                      "compile_ms": round(m["compile_s"] * 1e3, 1),
                      "compiles": m["compiles"]})
        _note_status(TIMED)
        r = CandidateResult(
            c, TIMED, est_time_s=est, measured_s=m["median_s"],
            times=m["times"], compile_s=m["compile_s"],
            compiles=m["compiles"])
        results.append(r)
        if is_default:
            default_runner = (r, fn, make_args)
    # the FIRST measurement in a fresh process systematically pays
    # one-time jitter (thread pools, allocator warmup) that would make
    # the baseline look slow and every candidate look like a win; the
    # default runs first, so re-time it after the loop (no recompile —
    # same jitted fn) and keep the better median
    if default_runner is not None:
        r, fn, make_args = default_runner
        try:
            m2 = measure_callable(fn, make_args, warmup=1, k=k)
            if m2["median_s"] < r.measured_s:
                r.measured_s = m2["median_s"]
                r.times = m2["times"]
        except Exception:
            pass   # the first measurement stands
    return results


def tuned_program(program, winner, verify=True, fetch_list=None):
    """Materialize a search winner: apply its pass pipeline to a clone
    (re-verified — the cache could be stale against a changed registry)
    and its sharding annotation.  ``winner`` is a SearchReport, a
    CandidateResult, or a plain params dict.  ``fetch_list`` overrides
    the recorded DCE "keep" protection — pass it whenever the fetches
    at apply time could differ from the fetches the search saw."""
    from ..fluid import ir

    if isinstance(winner, SearchReport):
        winner = winner.winner
    if isinstance(winner, CandidateResult):
        params = winner.params
        # a fresh (uncached) winner may have been measured as configured
        # Pass INSTANCES — re-apply exactly those, not bare-name rebuilds
        # that would drop their .set() attributes
        inst = winner.candidate.extra.get("passes")
    else:
        params, inst = dict(winner), None
    if fetch_list is not None:
        keep = [getattr(f, "name", f) for f in fetch_list]
    else:
        keep = list(params.get("keep", ()))
    if inst is not None:
        passes = [ir.get_pass(p).set("keep", keep)
                  if isinstance(p, str) else p for p in inst]
    else:
        passes = [ir.get_pass(n).set("keep", keep)
                  for n in params.get("pipeline", ())]
    clone = ir.clone_and_apply(program, passes, verify=verify)
    if params.get("sharding"):
        _apply_sharding(clone, params["sharding"])
    return clone


# ---------------------------------------------------------------------------
# flash-attention block search
# ---------------------------------------------------------------------------


def search_flash_blocks(shape, *, kv_len=None, causal=False,
                        layout="BHSD", dtype="float32", grid=None,
                        include_backward=False, interpret=None, warmup=1,
                        k=3, use_cache=True, cache_dir=None, platform=None,
                        jax_version=None):
    """Measured (block_q, block_k) search for one attention shape.

    ``shape`` is the q shape in the given layout.  Returns a
    SearchReport whose winner params are ``{"block_q", "block_k"}`` —
    pass them to ``flash_attention(..., block_q=, block_k=)``."""
    import jax
    import jax.numpy as jnp

    from ..ops.pallas.attention import flash_attention

    t_start = time.perf_counter()
    shape = tuple(int(s) for s in shape)
    if layout == "BHSD":
        b, h, sq, d = shape
    else:
        b, sq, h, d = shape
    sk = int(kv_len) if kv_len else sq
    sq_pad = sq + (-sq) % 128
    sk_pad = sk + (-sk) % 128
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # grid + interpret are part of the workload identity: a winner from
    # the full grid must not answer a caller who constrained the grid
    # (VMEM limits), nor an interpreter timing serve compiled callers
    workload = ("flash:%s:b%d.h%d.sq%d.sk%d.d%d.%s.causal%d.bwd%d."
                "grid%s.interp%d" % (
                    layout, b, h, sq, sk, d, dtype, int(causal),
                    int(include_backward),
                    "x".join(str(int(g)) for g in grid) if grid else "dflt",
                    int(bool(interpret))))
    # the resolved chip spec is part of the key (cache.py's contract):
    # a block choice tuned on one generation must not serve another
    from ..analysis.perf import ChipSpec

    parts = cache_key_parts(workload, chip=ChipSpec.detect(),
                            platform=platform, jax_version=jax_version)
    cache = _resolve_cache(use_cache, cache_dir)
    if cache is not None:
        entry = cache.get(parts)
        if entry is not None:
            _note_status(CACHED)
            return SearchReport(
                "flash_blocks", workload, parts, True, [],
                _winner_from_entry("flash_blocks", entry),
                default_s=entry.get("default_s"), searched_s=0.0,
                cache_path=cache.path_for(parts))

    cands = space_mod.flash_block_candidates(sq_pad, sk_pad, grid=grid)
    rng = np.random.RandomState(0)

    def mk(*s):
        return jnp.asarray(rng.randn(*s).astype(dtype) * 0.1)

    if layout == "BHSD":
        q, kk, v = mk(b, h, sq, d), mk(b, h, sk, d), mk(b, h, sk, d)
    else:
        q, kk, v = mk(b, sq, h, d), mk(b, sk, h, d), mk(b, sk, h, d)

    tracer = _tracer()
    results = []
    for c in cands:
        bq, bk = c.params["block_q"], c.params["block_k"]

        def fwd(q, kk, v, _bq=bq, _bk=bk):
            return flash_attention(q, kk, v, causal=causal,
                                   interpret=interpret, layout=layout,
                                   block_q=_bq, block_k=_bk)

        if include_backward:
            def run(q, kk, v, _f=fwd):
                def loss(q, kk, v):
                    return jnp.sum(_f(q, kk, v) * 0.01)
                return jax.value_and_grad(loss, argnums=(0, 1, 2))(
                    q, kk, v)
        else:
            run = fwd
        fn = jax.jit(run)
        t0 = time.perf_counter()
        try:
            m = measure_callable(fn, lambda: (q, kk, v),
                                 warmup=warmup, k=k)
        except Exception as e:
            _note_status(EXCLUDED)
            results.append(CandidateResult(
                c, EXCLUDED, error="%s: %s" % (type(e).__name__, e)))
            continue
        if tracer.enabled:
            tracer.complete(
                "tune.candidate", t0, time.perf_counter(), cat="tune",
                args={"label": c.label,
                      "measured_ms": round(m["median_s"] * 1e3, 3)})
        _note_status(TIMED)
        results.append(CandidateResult(
            c, TIMED, measured_s=m["median_s"], times=m["times"],
            compile_s=m["compile_s"], compiles=m["compiles"]))

    timed = [r for r in results if r.status == TIMED]
    winner = min(timed, key=lambda r: r.measured_s) if timed else None
    # the baseline is THE heuristic default pair — None when a
    # user-constrained grid excludes it (a report must not cite some
    # other candidate as "default")
    from ..ops.pallas.attention import _pick_block

    default_pair = (_pick_block(sq_pad), _pick_block(sk_pad))
    default_cand = next(
        (c for c in cands
         if (c.params["block_q"], c.params["block_k"]) == default_pair),
        None)
    default_s = (_default_measured(results, default_cand)
                 if default_cand is not None else None)
    cache_path = cache_stored = None
    if cache is not None and winner is not None:
        cache_path = cache.put(parts, _cache_winner_dict(winner),
                               extra={"default_s": default_s})
        cache_stored = True
    return SearchReport(
        "flash_blocks", workload, parts, False, results, winner,
        default_s=default_s, searched_s=time.perf_counter() - t_start,
        cache_path=cache_path, cache_stored=bool(cache_stored))


# ---------------------------------------------------------------------------
# fused-GEMM block search
# ---------------------------------------------------------------------------


def search_gemm_blocks(m, k, n, *, activation="gelu", bias=True,
                       dtype="float32", grid=None, include_backward=False,
                       interpret=None, warmup=1, k_times=3, use_cache=True,
                       cache_dir=None, platform=None, jax_version=None):
    """Measured (block_m, block_n, block_k) search for one fused-GEMM
    shape — `search_flash_blocks` extended to the MXU tile grid of
    `ops.pallas.matmul.matmul_bias_act` ([M, K] x [K, N] with the
    bias+activation epilogue).  Returns a SearchReport whose winner
    params are ``{"block_m", "block_n", "block_k"}`` — pass them to
    ``matmul_bias_act(..., block_m=, block_n=, block_k=)`` (or set
    ``PADDLE_TPU_GEMM_BLOCKS=bm,bn,bk`` for code you don't own)."""
    import jax
    import jax.numpy as jnp

    from ..ops.pallas.matmul import _pick_block, matmul_bias_act

    t_start = time.perf_counter()
    m, k, n = int(m), int(k), int(n)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    workload = ("gemm:m%d.k%d.n%d.%s.%s.bias%d.bwd%d.grid%s.interp%d" % (
        m, k, n, activation, dtype, int(bool(bias)),
        int(include_backward),
        "x".join(str(int(g)) for g in grid) if grid else "dflt",
        int(bool(interpret))))
    from ..analysis.perf import ChipSpec

    parts = cache_key_parts(workload, chip=ChipSpec.detect(),
                            platform=platform, jax_version=jax_version)
    cache = _resolve_cache(use_cache, cache_dir)
    if cache is not None:
        entry = cache.get(parts)
        if entry is not None:
            _note_status(CACHED)
            return SearchReport(
                "gemm_blocks", workload, parts, True, [],
                _winner_from_entry("gemm_blocks", entry),
                default_s=entry.get("default_s"), searched_s=0.0,
                cache_path=cache.path_for(parts))

    cands = space_mod.gemm_block_candidates(m, k, n, grid=grid)
    rng = np.random.RandomState(0)

    def mk(*s):
        return jnp.asarray(rng.randn(*s).astype(dtype) * 0.1)

    x, w = mk(m, k), mk(k, n)
    b = mk(n) if bias else None

    tracer = _tracer()
    results = []
    for c in cands:
        bm, bn, bk = (c.params["block_m"], c.params["block_n"],
                      c.params["block_k"])

        def fwd(x, w, _bm=bm, _bn=bn, _bk=bk):
            return matmul_bias_act(
                x, w, b, activation=activation, interpret=interpret,
                block_m=_bm, block_n=_bn, block_k=_bk)

        if include_backward:
            def run(x, w, _f=fwd):
                def loss(x, w):
                    return jnp.sum(_f(x, w) * 0.01)
                return jax.value_and_grad(loss, argnums=(0, 1))(x, w)
        else:
            run = fwd
        fn = jax.jit(run)
        t0 = time.perf_counter()
        try:
            mres = measure_callable(fn, lambda: (x, w),
                                    warmup=warmup, k=k_times)
        except Exception as e:
            _note_status(EXCLUDED)
            results.append(CandidateResult(
                c, EXCLUDED, error="%s: %s" % (type(e).__name__, e)))
            continue
        if tracer.enabled:
            tracer.complete(
                "tune.candidate", t0, time.perf_counter(), cat="tune",
                args={"label": c.label,
                      "measured_ms": round(mres["median_s"] * 1e3, 3)})
        _note_status(TIMED)
        results.append(CandidateResult(
            c, TIMED, measured_s=mres["median_s"], times=mres["times"],
            compile_s=mres["compile_s"], compiles=mres["compiles"]))

    timed = [r for r in results if r.status == TIMED]
    winner = min(timed, key=lambda r: r.measured_s) if timed else None
    default_triple = (_pick_block(m), _pick_block(n), _pick_block(k))
    default_cand = next(
        (c for c in cands
         if (c.params["block_m"], c.params["block_n"],
             c.params["block_k"]) == default_triple), None)
    default_s = (_default_measured(results, default_cand)
                 if default_cand is not None else None)
    cache_path = cache_stored = None
    if cache is not None and winner is not None:
        cache_path = cache.put(parts, _cache_winner_dict(winner),
                               extra={"default_s": default_s})
        cache_stored = True
    return SearchReport(
        "gemm_blocks", workload, parts, False, results, winner,
        default_s=default_s, searched_s=time.perf_counter() - t_start,
        cache_path=cache_path, cache_stored=bool(cache_stored))


# ---------------------------------------------------------------------------
# serving bucket-ladder search
# ---------------------------------------------------------------------------


def search_bucket_ladder(runner, example_inputs, traffic, *, max_batch=32,
                         ragged_dims=None, mask_feed=None, ladders=None,
                         extra_ladders=None, warmup=1, k=3, workload=None,
                         use_cache=True, cache_dir=None, platform=None,
                         jax_version=None):
    """Measured batch-bucket-ladder search against a traffic sample.

    ``runner``: a Predictor (or anything with ``.run(feed)`` /
    a callable).  ``traffic``: iterable of observed request batch sizes.
    Each candidate ladder's cost is the traffic-weighted expected
    per-request service time: every bucket the traffic would hit is
    compiled (warmup) and timed, then E[t] = sum_n p(n) * t(bucket(n)).
    ``extra_ladders`` appends candidates to the enumerated (or
    ``ladders``-pinned) set — `InferenceServer.autotune` passes its
    incumbent ladder here so tuning can only keep or beat what is
    already serving.  The winner's ``batch_buckets`` slots straight
    into ``BatchingConfig`` / ``InferenceServer``."""
    from ..inference.batching import BatchingConfig, pick_bucket

    t_start = time.perf_counter()
    example = {k_: np.asarray(v) for k_, v in example_inputs.items()}
    # clamp to max_batch: the serving path caps coalescing there, so an
    # oversize log entry must not make the search compile-and-time a
    # bucket no server will ever dispatch
    traffic = [min(int(n), int(max_batch)) for n in traffic if int(n) > 0]
    if not traffic:
        raise ValueError("search_bucket_ladder needs a non-empty traffic "
                         "sample of request batch sizes")
    hist = {}
    for n in traffic:
        hist[n] = hist.get(n, 0) + 1
    total = float(len(traffic))

    cands = space_mod.ladder_candidates(max_batch, traffic=traffic,
                                        ladders=ladders,
                                        extra=extra_ladders)

    if workload is None:
        prog = getattr(runner, "_program", None)
        if prog is not None:
            workload = "ladder:%s" % _program_workload(prog)
    cacheable = workload is not None and use_cache
    if workload is None:
        workload = "ladder:anonymous"
    import hashlib as _hashlib

    # hash the NORMALIZED distribution (3-decimal fractions), not raw
    # counts: a restarted server tuning against a proportionally-equal
    # (e.g. longer) traffic log must hit the cache as the docstring
    # promises; only a real shift in the mix re-opens the search
    dist = sorted((n, round(cnt / total, 3)) for n, cnt in hist.items())
    tsig = _hashlib.sha256(
        repr((dist, max_batch,
              sorted((n, a.shape[1:], str(a.dtype))
                     for n, a in example.items()),
              # the feed contract is part of the identity: a ladder
              # timed with a validity mask / ragged padding must not
              # answer a config without them
              sorted((n, sorted(axes.items()))
                     for n, axes in (ragged_dims or {}).items()),
              mask_feed,
              # ...and so is the candidate set: a winner chosen against
              # one incumbent/pinned ladder list must not answer a
              # search over a different one
              sorted(tuple(c.params["batch_buckets"])
                     for c in cands))).encode()
    ).hexdigest()[:8]
    workload += ":" + tsig
    from ..analysis.perf import ChipSpec

    parts = cache_key_parts(workload, chip=ChipSpec.detect(),
                            platform=platform, jax_version=jax_version)
    cache = _resolve_cache(cacheable, cache_dir)
    if cache is not None:
        entry = cache.get(parts)
        if entry is not None:
            _note_status(CACHED)
            return SearchReport(
                "ladder", workload, parts, True, [],
                _winner_from_entry("ladder", entry),
                default_s=entry.get("default_s"), searched_s=0.0,
                cache_path=cache.path_for(parts))

    run = runner.run if hasattr(runner, "run") else runner

    def feed_at(b, cfg):
        feed = {}
        for name, arr in example.items():
            feed[name] = np.zeros((b,) + arr.shape[1:], arr.dtype)
        if cfg.mask_feed is not None:
            feed[cfg.mask_feed] = cfg.mask_for(feed, rows_valid=b)
        return feed

    bucket_times = {}   # bucket size -> median seconds (shared across
    # ladders: the same padded batch is the same executable)

    def time_bucket(b, cfg):
        if b in bucket_times:
            return bucket_times[b]
        feed = feed_at(b, cfg)
        m = measure_callable(lambda f: run(f), lambda: (feed,),
                             warmup=warmup, k=k)
        bucket_times[b] = m["median_s"]
        return bucket_times[b]

    tracer = _tracer()
    results = []
    for c in cands:
        ladder = c.params["batch_buckets"]
        cfg = BatchingConfig(max_batch=max_batch, batch_buckets=ladder,
                             ragged_dims=ragged_dims, mask_feed=mask_feed)
        t0 = time.perf_counter()
        try:
            expected = 0.0
            per_bucket = {}
            for n, cnt in sorted(hist.items()):
                b = pick_bucket(n, cfg.batch_buckets)
                t = time_bucket(b, cfg)
                per_bucket[str(b)] = t
                expected += (cnt / total) * t
        except Exception as e:
            _note_status(EXCLUDED)
            results.append(CandidateResult(
                c, EXCLUDED, error="%s: %s" % (type(e).__name__, e)))
            continue
        if tracer.enabled:
            tracer.complete(
                "tune.candidate", t0, time.perf_counter(), cat="tune",
                args={"label": c.label,
                      "expected_ms": round(expected * 1e3, 3)})
        _note_status(TIMED)
        results.append(CandidateResult(
            c, TIMED, measured_s=expected,
            detail={"per_bucket_s": per_bucket,
                    "executables": len(per_bucket)}))

    timed = [r for r in results if r.status == TIMED]
    winner = min(timed, key=lambda r: r.measured_s) if timed else None
    default_s = _default_measured(results, cands[0]) if cands else None
    cache_path = cache_stored = None
    if cache is not None and winner is not None:
        cache_path = cache.put(parts, _cache_winner_dict(winner),
                               extra={"default_s": default_s})
        cache_stored = True
    return SearchReport(
        "ladder", workload, parts, False, results, winner,
        default_s=default_s, searched_s=time.perf_counter() - t_start,
        cache_path=cache_path, cache_stored=bool(cache_stored))


# ---------------------------------------------------------------------------
# jitted-step variant search
# ---------------------------------------------------------------------------


def search_train_step(build_and_time, *, workload, mesh=None,
                      zero_stages=(1, 2, 3), accumulate_steps=(1, 4),
                      chunk_bytes=(4 << 20,), use_cache=True,
                      cache_dir=None, platform=None, jax_version=None):
    """Measured search over the distributed-train-step knobs: ZeRO
    stage x accumulate_steps x gather-chunk-bytes
    (`space.train_step_candidates`; the zero/chunk axes collapse on a
    1-chip mesh by construction).

    ``build_and_time(params) -> seconds`` owns constructing a
    ``ShardedTrainStep(**params)`` and timing one step (bench.py's
    marginal harness, or any caller-defined one); the tuner owns
    enumeration, ordering, reporting, and the cache — the winner's
    params slot straight back into ``ShardedTrainStep``.  Same
    default-first contract as `search_step`: the first candidate (the
    first entry of ``zero_stages`` at accumulate_steps[0]) is the
    measured baseline."""
    dp = mesh.axis_size("dp") if mesh is not None else 1
    cands = space_mod.train_step_candidates(
        dp=dp, zero_stages=zero_stages,
        accumulate_steps=accumulate_steps, chunk_bytes=chunk_bytes)
    return search_step(
        build_and_time, cands, workload=workload, mesh=mesh,
        use_cache=use_cache, cache_dir=cache_dir, platform=platform,
        jax_version=jax_version)


def search_hostemb_cache(build_and_time, *, workload, capacities=None,
                         table_rows=None, mesh=None, use_cache=True,
                         cache_dir=None, platform=None,
                         jax_version=None):
    """Measured search over the hot-row device-cache capacity of a
    host-embedding workload (`space.cache_capacity_candidates`; 0 = no
    cache is the measured baseline, first).

    ``build_and_time(params) -> seconds`` owns building the session —
    attach ``HotRowCache(table, params["cache_capacity"])`` when the
    capacity is non-zero — and timing a step (streaming_bench's
    harness, or any caller-defined one); the tuner owns enumeration,
    ordering, reporting, and the cache.  The winner's capacity slots
    straight back into `HostEmbedding.attach_cache`."""
    kw = {}
    if capacities is not None:
        kw["capacities"] = capacities
    cands = space_mod.cache_capacity_candidates(table_rows=table_rows,
                                                **kw)
    return search_step(
        build_and_time, cands, workload=workload, mesh=mesh,
        use_cache=use_cache, cache_dir=cache_dir, platform=platform,
        jax_version=jax_version)


def search_step(build_and_time, variants, *, workload, mesh=None,
                use_cache=True, cache_dir=None, platform=None,
                jax_version=None):
    """Generic variant search for an opaque jitted step: the caller owns
    building and timing (``build_and_time(params) -> seconds``, e.g.
    bench.py rebuilding a ShardedTrainStep per knob set); the tuner owns
    ordering, reporting, and the cache.  The FIRST variant is the
    default."""
    t_start = time.perf_counter()
    cands = [c if isinstance(c, space_mod.Candidate)
             else space_mod.Candidate("step", dict(c[1]), label=c[0])
             for c in variants]
    # the variant set is part of the workload identity: adding a new
    # knob to the list must re-open the search, not hit the old entry
    import hashlib as _hashlib

    workload += ":" + _hashlib.sha256(repr(sorted(
        (c.label, sorted((k_, repr(v)) for k_, v in c.params.items()))
        for c in cands)).encode()).hexdigest()[:8]
    from ..analysis.perf import ChipSpec

    parts = cache_key_parts(workload, mesh=mesh, chip=ChipSpec.detect(),
                            platform=platform, jax_version=jax_version)
    cache = _resolve_cache(use_cache, cache_dir)
    if cache is not None:
        entry = cache.get(parts)
        if entry is not None:
            _note_status(CACHED)
            return SearchReport(
                "step", workload, parts, True, [],
                _winner_from_entry("step", entry),
                default_s=entry.get("default_s"), searched_s=0.0,
                cache_path=cache.path_for(parts))
    results = []
    for c in cands:
        try:
            secs = float(build_and_time(dict(c.params)))
        except Exception as e:
            _note_status(EXCLUDED)
            results.append(CandidateResult(
                c, EXCLUDED, error="%s: %s" % (type(e).__name__, e)))
            continue
        _note_status(TIMED)
        results.append(CandidateResult(c, TIMED, measured_s=secs))
    timed = [r for r in results if r.status == TIMED]
    winner = min(timed, key=lambda r: r.measured_s) if timed else None
    default_s = _default_measured(results, cands[0]) if cands else None
    cache_path = cache_stored = None
    if cache is not None and winner is not None:
        cache_path = cache.put(parts, _cache_winner_dict(winner),
                               extra={"default_s": default_s})
        cache_stored = True
    return SearchReport(
        "step", workload, parts, False, results, winner,
        default_s=default_s, searched_s=time.perf_counter() - t_start,
        cache_path=cache_path, cache_stored=bool(cache_stored))


def search_rl_config(build_and_time, *, workload,
                     rollout_batches=(4, 8, 16),
                     accumulate_steps=(1, 2, 4), sync_every=(1,),
                     mesh=None, use_cache=True, cache_dir=None,
                     platform=None, jax_version=None):
    """Measured search over the RL loop's rollout-vs-train batch
    arbitration (`space.rl_batch_candidates`).

    ``build_and_time(params) -> seconds-per-event`` owns building a
    ``FeedbackLoop(rollout_batch=..., accumulate_steps=...,
    sync_every=...)`` and running a few representative rounds
    (`benchmarks/rl_loop_bench.py`'s harness); the tuner owns
    enumeration, ordering, reporting, and the cache."""
    cands = space_mod.rl_batch_candidates(
        rollout_batches=rollout_batches,
        accumulate_steps=accumulate_steps, sync_every=sync_every)
    if not cands:
        raise ValueError("no feasible rl batch candidates")
    return search_step(
        build_and_time, cands, workload=workload, mesh=mesh,
        use_cache=use_cache, cache_dir=cache_dir, platform=platform,
        jax_version=jax_version)


def search_generation_config(build_and_time, *, workload,
                             slot_counts=(1, 4, 8, 16), max_len=None,
                             hbm_budget_bytes=None,
                             cache_bytes_per_slot=None,
                             block_sizes=None, draft_lens=None,
                             tp_degrees=None, num_heads=None,
                             mesh=None, use_cache=True, cache_dir=None,
                             platform=None, jax_version=None):
    """Measured search over the decode engine's configuration
    (`space.generation_config_candidates`): slot count, and — when
    ``block_sizes`` / ``draft_lens`` / ``tp_degrees`` are given — the
    paged-KV block size, speculative draft length, and tensor-parallel
    degree.

    ``build_and_time(params) -> seconds-per-token`` owns building a
    ``GenerationEngine(slots=params["slots"], ...)`` (forwarding
    ``params.get("block_size")`` / ``params.get("draft_len")`` when
    present, and building a ``tp_serving.TPGenerationEngine(tp=
    params["tp"])`` when ``"tp"`` is present), running a
    representative request mix, and reporting time per generated token
    (`benchmarks/generation_bench.py`'s harness); the tuner owns
    enumeration, ordering, reporting, and the cache.  The first
    candidate is the measured baseline; candidates whose PER-CHIP KV
    cache (divided by tp — the pool shards over heads) would blow the
    HBM budget, or whose tp does not divide ``num_heads``, are dropped
    before anything compiles."""
    cands = space_mod.generation_config_candidates(
        slot_counts=slot_counts, max_len=max_len,
        hbm_budget_bytes=hbm_budget_bytes,
        cache_bytes_per_slot=cache_bytes_per_slot,
        block_sizes=block_sizes, draft_lens=draft_lens,
        tp_degrees=tp_degrees, num_heads=num_heads)
    if not cands:
        raise ValueError("no feasible slot-count candidates")
    return search_step(
        build_and_time, cands, workload=workload, mesh=mesh,
        use_cache=use_cache, cache_dir=cache_dir, platform=platform,
        jax_version=jax_version)
