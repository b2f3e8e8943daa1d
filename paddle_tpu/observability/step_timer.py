"""StepTimer: where did this training step's time go?

The reference answered per-op time with RecordEvent/CUPTI tables
(`platform/profiler.h:39-213`) — post-hoc, trace-based.  Under XLA the
per-op view lives in the jax trace (`fluid.profiler`); what production
training needs ALWAYS ON is the step-level budget:

    step_time = data_wait + compile + compute + host_overhead

* data_wait      blocked on the input pipeline (next(batch); fed by
                 `io.PipelineStats.step_wait_ms` when a DevicePrefetcher
                 is in the loop);
* compile        wall-time inside XLA compilation (trace + lowering +
                 backend compile), detected via `jax.monitoring` event
                 listeners (`/jax/core/compile/...`) with the executor's
                 cache-miss lowering time folded in — a step that
                 recompiles is visible as a spike AND counted;
* compute        dispatch + device execution + fetch materialization of
                 the jitted step (minus any compile time that happened
                 inside the call — first calls compile then run);
* host_overhead  the residual: callbacks, metric updates, python glue.

Components are recorded into a thread-local ACTIVE step record by the
instrumented layers (`fluid.Executor.run`, `io`, checkpointing), so the
attribution works no matter which API drives the step.  Aggregates land
in always-on registry histograms; per-step scalars optionally stream to
a `ScalarWriter` JSONL log (TensorBoard-style `{tag, step, value,
wall_time}` lines).
"""

from __future__ import annotations

import json
import os
import threading
import time

from .metrics import default_registry

__all__ = ["StepTimer", "StepRecord", "ScalarWriter",
           "install_jax_compile_hooks", "record_component",
           "record_compile", "thread_compile_seconds",
           "add_thread_compile_seconds", "add_step_finish_hook",
           "remove_step_finish_hook", "add_step_failure_hook",
           "remove_step_failure_hook"]

_tls = threading.local()

# -- step lifecycle hooks ----------------------------------------------------
#
# The flight recorder (and anything else that wants the per-step budget
# stream without subclassing StepTimer) registers here.  Empty-list
# checks keep the step path at one `if` when nothing is listening.

_finish_hooks = []    # fn(loop_name, breakdown_ms_dict)
_failure_hooks = []   # fn(loop_name, step, exc_type)


def add_step_finish_hook(fn):
    if fn not in _finish_hooks:
        _finish_hooks.append(fn)
    return fn


def remove_step_finish_hook(fn):
    if fn in _finish_hooks:
        _finish_hooks.remove(fn)


def add_step_failure_hook(fn):
    if fn not in _failure_hooks:
        _failure_hooks.append(fn)
    return fn


def remove_step_failure_hook(fn):
    if fn in _failure_hooks:
        _failure_hooks.remove(fn)

# -- jax compile detection ---------------------------------------------------
#
# jax.monitoring fires duration events on the COMPILING thread for
# jaxpr tracing, MLIR lowering, and backend (XLA) compilation.  One
# process-wide listener feeds (a) global registry metrics and (b) a
# thread-local accumulator the executor uses to subtract compile time
# out of a step's compute measurement.

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_COMPILE_EVENT_PREFIX = "/jax/core/compile/"
# persistent compilation cache: a hit loads an executable from disk, a
# miss compiles and writes one (compiles under the cache's size/time
# thresholds are neither)
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "xla_compile_cache_hits_total",
    "/jax/compilation_cache/cache_misses": "xla_compile_cache_misses_total",
}

_hooks_lock = threading.Lock()
_hooks_installed = False


def install_jax_compile_hooks():
    """Register the process-wide jax.monitoring listeners (idempotent).
    Returns True once the hooks are live."""
    global _hooks_installed
    if _hooks_installed:             # hot-path fast exit (benign race:
        return True                  # the flag only ever goes False->True)
    with _hooks_lock:
        if _hooks_installed:
            return True
        import jax.monitoring as jmon

        jmon.register_event_duration_secs_listener(_on_jax_duration_event)
        jmon.register_event_listener(_on_jax_event)
        _hooks_installed = True
        return True


def _on_jax_event(event, **kw):
    name = _CACHE_EVENTS.get(event)
    if name is not None:
        default_registry().counter(
            name, "Persistent compilation cache events "
            "(jax.monitoring %s)" % event).inc()


def _on_jax_duration_event(event, duration, **kw):
    if not event.startswith(_COMPILE_EVENT_PREFIX):
        return
    # every compile phase bills the thread-local accumulator (they are
    # disjoint intervals on the compiling thread)
    _tls.compile_secs = getattr(_tls, "compile_secs", 0.0) + duration
    if event == _BACKEND_COMPILE_EVENT:
        reg = default_registry()
        reg.counter(
            "xla_compilations_total",
            "XLA backend compilations (jax.monitoring)").inc()
        reg.histogram(
            "xla_compile_ms",
            "XLA backend compile wall time (ms)").observe(duration * 1e3)
        rec = current_record()
        if rec is not None:
            rec.compiles += 1


def thread_compile_seconds():
    """Cumulative compile seconds observed on THIS thread.  Instrumented
    regions (Executor.run) diff this across a call to split compile time
    out of their compute measurement."""
    return getattr(_tls, "compile_secs", 0.0)


def add_thread_compile_seconds(seconds):
    """Credit compile work detected outside the jax listener (e.g. the
    executor's program lowering) to this thread's accumulator, so the
    enclosing instrumented region attributes it to compile, not
    compute."""
    _tls.compile_secs = getattr(_tls, "compile_secs", 0.0) \
        + max(float(seconds), 0.0)


# -- active-record plumbing --------------------------------------------------


def current_record():
    """The innermost active StepRecord on this thread (None outside a
    step)."""
    stack = getattr(_tls, "records", None)
    return stack[-1] if stack else None


def record_component(component, seconds):
    """Add `seconds` to `component` of the active step record, if any.
    Called by instrumented layers (executor, io, checkpoint)."""
    rec = current_record()
    if rec is not None:
        rec.add(component, seconds)


def record_compile(seconds, count=1):
    """Credit compile time detected OUTSIDE the jax listener (the
    executor's cache-miss lowering/trace)."""
    rec = current_record()
    if rec is not None:
        rec.add("compile", seconds)
        rec.compiles += count


class StepRecord:
    """One step's component budget (seconds).  host_overhead is the
    residual at close: step_time - (data_wait + compile + compute),
    floored at 0 so the components always sum to ~step_time."""

    __slots__ = ("step", "t0", "components", "compiles", "cancelled",
                 "step_time")

    def __init__(self, step):
        self.step = step
        self.t0 = time.perf_counter()
        self.components = {"data_wait": 0.0, "compile": 0.0,
                           "compute": 0.0, "host_overhead": 0.0}
        self.compiles = 0
        self.cancelled = False
        self.step_time = None

    def add(self, component, seconds):
        self.components[component] = \
            self.components.get(component, 0.0) + max(float(seconds), 0.0)

    def cancel(self):
        """Discard this record (e.g. the data fetch hit StopIteration)."""
        self.cancelled = True

    def close(self):
        self.step_time = time.perf_counter() - self.t0
        known = (self.components["data_wait"] + self.components["compile"]
                 + self.components["compute"])
        self.components["host_overhead"] = max(self.step_time - known, 0.0)
        return self

    def breakdown_ms(self):
        d = {k: v * 1e3 for k, v in self.components.items()}
        d["step_time"] = (self.step_time or 0.0) * 1e3
        d["compiles"] = self.compiles
        return d


class StepTimer:
    """Instrument a training loop with per-step component budgets.

    Usage (what `hapi.Model.fit` does)::

        timer = StepTimer(name="hapi.fit")
        with timer.step() as rec:
            t0 = time.perf_counter()
            batch = next(it)                   # or rec.cancel() on stop
            rec.add("data_wait", time.perf_counter() - t0)
            train_step(batch)   # Executor.run records compile/compute
        timer.last_breakdown   # {"data_wait": ms, ..., "step_time": ms}

    Aggregates are always-on registry histograms
    (`train_step_ms{loop=...}` etc.); per-step scalars stream to
    `scalar_writer` (a ScalarWriter or a path) when given.  The last
    `history` breakdowns are kept (bounded deque) for programmatic
    inspection.
    """

    COMPONENTS = ("data_wait", "compile", "compute", "host_overhead")

    def __init__(self, name="train", registry=None, scalar_writer=None,
                 history=256):
        from collections import deque

        self.name = name
        self.registry = registry or default_registry()
        if isinstance(scalar_writer, (str, os.PathLike)):
            scalar_writer = ScalarWriter(scalar_writer)
        self.scalar_writer = scalar_writer
        self.history = deque(maxlen=max(int(history), 1))
        self.steps = 0
        install_jax_compile_hooks()
        lbl = ("loop",)
        self._h_step = self.registry.histogram(
            "train_step_ms", "Whole train-step wall time (ms)",
            labelnames=lbl).labels(name)
        self._h_comp = {
            c: self.registry.histogram(
                "train_%s_ms" % c,
                "Per-step %s wall time (ms)" % c,
                labelnames=lbl).labels(name)
            for c in self.COMPONENTS
        }
        self._c_steps = self.registry.counter(
            "train_steps_total", "Completed train steps",
            labelnames=lbl).labels(name)

    @property
    def last_breakdown(self):
        return self.history[-1] if self.history else None

    def step(self, step=None):
        """Context manager for ONE step; yields the StepRecord."""
        return _StepCtx(self, self.steps if step is None else step)

    def _finish(self, rec):
        if rec.cancelled:
            return
        rec.close()
        self.steps = rec.step + 1
        self._h_step.observe(rec.step_time * 1e3)
        for c in self.COMPONENTS:
            self._h_comp[c].observe(rec.components[c] * 1e3)
        self._c_steps.inc()
        bd = rec.breakdown_ms()
        bd["step"] = rec.step
        self.history.append(bd)
        if _finish_hooks:
            for h in list(_finish_hooks):
                try:
                    h(self.name, bd)
                except Exception:
                    pass  # a consumer bug must not sink the train loop
        if self.scalar_writer is not None:
            items = [("%s/%s_ms" % (self.name, c), bd[c], rec.step)
                     for c in self.COMPONENTS + ("step_time",)]
            if rec.compiles:
                items.append(("%s/compiles" % self.name,
                              rec.compiles, rec.step))
            self.scalar_writer.add_many(items)

    def close(self):
        if self.scalar_writer is not None:
            self.scalar_writer.close()


class _StepCtx:
    def __init__(self, timer, step):
        self.timer = timer
        self.rec = StepRecord(step)
        self._span = None

    def __enter__(self):
        stack = getattr(_tls, "records", None)
        if stack is None:
            stack = _tls.records = []
        stack.append(self.rec)
        from . import trace as _trace  # deferred: importing
        # observability alone never pulls the tracer; the (stdlib-only)
        # module loads once at the first timed step

        tracer = _trace.default_tracer()
        if tracer.enabled:
            # the per-step timeline span; Executor.run / data_wait spans
            # nest inside it by time containment on the same thread
            self._span = tracer.span(
                "step", cat="train",
                args={"loop": self.timer.name, "step": self.rec.step})
            self._span.__enter__()
        return self.rec

    def __exit__(self, exc_type, exc, tb):
        stack = getattr(_tls, "records", None)
        if stack and stack[-1] is self.rec:
            stack.pop()
        if exc_type is None:
            self.timer._finish(self.rec)
            if self._span is not None:
                if self.rec.cancelled:
                    self._span.abandon()   # no event for a cancelled step
                else:
                    if self.rec.step_time is not None:
                        self._span.add_args(**self.rec.breakdown_ms())
                    self._span.__exit__(None, None, None)
        else:
            # close the span BEFORE the failure hooks: the flight
            # recorder dumps inside them, and the dump that exists to
            # explain this crash must contain the crashing step's own
            # span (error-annotated), not just its lead-up
            if self._span is not None:
                if self.rec.cancelled:
                    self._span.abandon()
                else:
                    self._span.__exit__(exc_type, exc, tb)
            if not self.rec.cancelled and _failure_hooks:
                # the step DIED (XLA error, NaN guard, loader crash):
                # tell the flight recorder while the ring holds the
                # lead-up AND the failed step
                for h in list(_failure_hooks):
                    try:
                        h(self.timer.name, self.rec.step, exc_type)
                    except Exception:
                        pass
        return False


class ScalarWriter:
    """Append-only JSONL scalar log (TensorBoard add_scalar, file-first).

    Each line: {"tag": str, "step": int, "value": float, "wall_time":
    unix seconds}.  Lines are written atomically per-call under a lock
    (safe from multiple threads) and flushed on close().  Reopen-append
    is safe: a resumed run keeps appending; readers should keep the LAST
    line per (tag, step).
    """

    def __init__(self, path, flush_every=64):
        self.path = os.fspath(path)
        self._f = None
        self._lock = threading.Lock()
        self._n = 0
        self._flush_every = max(int(flush_every), 1)

    def add_scalar(self, tag, value, step, wall_time=None):
        self.add_many([(tag, value, step)], wall_time=wall_time)

    def add_scalars(self, main_tag, tag_value_dict, step):
        self.add_many([("%s/%s" % (main_tag, k), v, step)
                       for k, v in tag_value_dict.items()])

    def add_many(self, items, wall_time=None):
        """items: [(tag, value, step)]; one lock + one write for the
        whole batch (the per-step hot path emits 5-6 scalars)."""
        wt = time.time() if wall_time is None else wall_time
        buf = "".join(
            json.dumps({"tag": str(tag), "step": int(step),
                        "value": float(value), "wall_time": wt}) + "\n"
            for tag, value, step in items)
        with self._lock:
            if self._f is None:
                d = os.path.dirname(self.path)
                if d:
                    os.makedirs(d, exist_ok=True)
                self._f = open(self.path, "a")
            self._f.write(buf)
            self._n += len(items)
            if self._n % self._flush_every < len(items):
                self._f.flush()

    def flush(self):
        with self._lock:
            if self._f is not None:
                self._f.flush()

    def close(self):
        with self._lock:
            if self._f is not None:
                self._f.flush()
                self._f.close()
                self._f = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    @staticmethod
    def read(path):
        """Parse a JSONL scalar log -> [{tag, step, value, wall_time}]."""
        out = []
        with open(path) as f:
            for line in f:
                line = line.strip()
                if line:
                    out.append(json.loads(line))
        return out
