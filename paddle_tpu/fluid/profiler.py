"""Profiler: trace collection + chrome-trace export.

Capability parity: reference `python/paddle/fluid/profiler.py` (`profiler`
contextmanager, start_profiler/stop_profiler, reset_profiler) over the C++
RecordEvent/CUPTI DeviceTracer machinery (`platform/profiler.h:39-213`,
`tools/timeline.py` chrome-trace export).

TPU-first: jax.profiler captures host AND device (TPU) activity into a
TensorBoard/Perfetto trace — the XLA-era equivalent of RecordEvent + CUPTI
correlation.  `RecordEvent`/`record_event` map to TraceAnnotation so user
code can mark regions exactly like the reference API.
"""

from __future__ import annotations

import contextlib
import os
import tempfile

_state = {"dir": None, "active": False, "preexisting": frozenset()}


def start_profiler(state="All", tracer_option="Default", log_dir=None):
    """cf. reference start_profiler (state/tracer_option accepted for API
    parity; XLA traces always include host+device)."""
    import jax

    if _state["active"]:
        return
    _state["dir"] = log_dir or tempfile.mkdtemp(prefix="paddle_tpu_prof_")
    # a reused log_dir keeps earlier sessions' trace files around (jax
    # writes each session under a fresh timestamped subdir) — snapshot
    # what exists so stop_profiler aggregates THIS session only
    _state["preexisting"] = frozenset(_trace_files(_state["dir"]))
    jax.profiler.start_trace(_state["dir"])
    _state["active"] = True


def _trace_files(trace_dir):
    import glob

    return sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.trace.json.gz"), recursive=True))


def _collect_events(trace_dir, exclude=frozenset()):
    """Parse the jax trace's .trace.json.gz files -> chrome trace events."""
    import gzip
    import json

    events = []
    for f in _trace_files(trace_dir):
        if f in exclude:
            continue
        try:
            with gzip.open(f) as fh:
                data = json.load(fh)
        except (OSError, ValueError):
            continue
        events.extend(data.get("traceEvents", []))
    return events


def _aggregate(events):
    """Per-op totals from complete ('X') events, split host/device by the
    process name metadata (the chrome-trace layout jax emits)."""
    import re

    pids = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pids[e.get("pid")] = (e.get("args") or {}).get("name", "")
    rows = {}
    for e in events:
        if e.get("ph") != "X" or not e.get("name"):
            continue
        pname = pids.get(e.get("pid"), "")
        dev = "TPU" in pname or "device" in pname.lower() \
            or "GPU" in pname
        base = re.sub(r"\.\d+$", "", e["name"])
        key = (base, dev)
        dur = float(e.get("dur", 0.0))
        r = rows.get(key)
        if r is None:
            rows[key] = [1, dur, dur, dur]       # calls, total, min, max
        else:
            r[0] += 1
            r[1] += dur
            r[2] = min(r[2], dur)
            r[3] = max(r[3], dur)
    return rows


_SORT_KEYS = {"total": 1, "calls": 0, "min": 2, "max": 3,
              "default": 1, None: 1}


def summary_table(trace_dir_or_events, sorted_key="total", max_rows=40):
    """The reference's aggregated per-op profile table
    (`platform/profiler.cc` PrintProfiler) from a captured trace (dir
    path or pre-collected chrome events)."""
    if sorted_key not in _SORT_KEYS and sorted_key != "ave":
        raise ValueError(
            "sorted_key must be one of total/calls/min/max/ave/default, "
            "got %r (reference stop_profiler contract)" % (sorted_key,))
    events = (trace_dir_or_events
              if isinstance(trace_dir_or_events, list)
              else _collect_events(trace_dir_or_events))
    rows = _aggregate(events)
    if not rows:
        return "Profile: no events captured"

    def keyfn(item):
        (name, dev), r = item
        if sorted_key == "ave":
            return r[1] / max(r[0], 1)
        return r[_SORT_KEYS.get(sorted_key, 1)]

    items = sorted(rows.items(), key=keyfn, reverse=True)[:max_rows]
    total_all = sum(r[1] for r in rows.values()) or 1.0
    lines = [
        "------------------------->     Profiling Report     "
        "<-------------------------",
        "%-44s %-6s %8s %12s %10s %10s %10s %8s"
        % ("Event", "Place", "Calls", "Total(us)", "Min(us)", "Max(us)",
           "Ave(us)", "Ratio"),
    ]
    for (name, dev), (calls, tot, mn, mx) in items:
        lines.append(
            "%-44s %-6s %8d %12.1f %10.1f %10.1f %10.1f %7.2f%%"
            % (name[:44], "Device" if dev else "Host", calls, tot, mn, mx,
               tot / max(calls, 1), 100.0 * tot / total_all))
    return "\n".join(lines)


def export_chrome_tracing(trace_dir_or_events, out_path):
    """Write a plain chrome://tracing JSON (the reference
    `tools/timeline.py:115` output format) from the captured trace (dir
    path or pre-collected events)."""
    import json

    events = (trace_dir_or_events
              if isinstance(trace_dir_or_events, list)
              else _collect_events(trace_dir_or_events))
    with open(out_path, "w") as f:
        json.dump({"traceEvents": events}, f)
    return out_path


def stop_profiler(sorted_key=None, profile_path="/tmp/profile"):
    """cf. reference stop_profiler(sorted_key, profile_path): ends the
    trace, PRINTS the aggregated per-op table (sorted_key in
    total/calls/min/max/ave, reference profiler.cc table), and writes a
    chrome://tracing-loadable JSON to `profile_path` (the
    tools/timeline.py output)."""
    import jax

    if not _state["active"]:
        return
    sorted_key = sorted_key or "total"
    if sorted_key not in _SORT_KEYS and sorted_key != "ave":
        raise ValueError(
            "sorted_key must be one of total/calls/min/max/ave/default, "
            "got %r" % (sorted_key,))
    jax.profiler.stop_trace()
    _state["active"] = False
    events = _collect_events(                  # parse the trace ONCE,
        _state["dir"], exclude=_state["preexisting"])  # this session only
    print(summary_table(events, sorted_key))
    try:
        export_chrome_tracing(events, profile_path)
    except OSError:
        pass
    return _state["dir"]


def reset_profiler():
    """cf. reference reset_profiler — but note the trace-vs-metrics split:

    * **traces** (start_profiler/stop_profiler above) are per-session
      under XLA: each start opens a fresh jax trace session and stop
      aggregates only that session's events, so there is no cross-run
      trace state to reset;
    * **metrics** (the always-on Counter/Gauge/Histogram aggregates in
      `paddle_tpu.observability.default_registry()` — serving stats, io
      pipeline stats, step telemetry, compile counts) DO accumulate
      across runs, and this call zeroes them: every registered metric's
      state (counts, sums, reservoirs, bucket rows) resets while the
      families and their label children stay registered.

    The reference's reset cleared the C++ profiler's accumulated event
    table; the registry reset is this framework's equivalent for the
    live-aggregate side.
    """
    from ..observability.metrics import default_registry

    default_registry().reset()


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path="/tmp/profile",
             tracer_option="Default", log_dir=None):
    """cf. reference fluid.profiler.profiler contextmanager."""
    start_profiler(state, tracer_option, log_dir)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


class RecordEvent:
    """Region annotation visible in the trace (cf. platform/profiler.h:126
    RecordEvent RAII; dygraph/profiler record_event)."""

    def __init__(self, name):
        self._name = name
        self._span = None

    def __enter__(self):
        # the program's one span call site: a TraceAnnotation while a
        # profiler session runs, a ring event while tracing is enabled
        from ..observability import trace as _trace

        self._span = _trace.span(self._name, cat="user")
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        return self._span.__exit__(*exc)


record_event = RecordEvent


def cuda_profiler(*a, **kw):
    raise RuntimeError("cuda_profiler is CUDA-only; use fluid.profiler.profiler")


# ---------------------------------------------------------------------------
# Lightweight in-process metrics (serving/io observability)
# ---------------------------------------------------------------------------
#
# The trace machinery above answers "where did one run spend its time";
# production needs cheap always-on aggregates.  Since the unified
# telemetry subsystem landed these are THIN ALIASES of
# `paddle_tpu.observability.metrics` — one implementation (thread-safe,
# labeled, Prometheus-exportable).  Constructed bare (as the PR-2/PR-3
# call sites do) they are standalone; constructed with `registry=...`
# (or via a MetricsRegistry's get-or-create methods) they are scrapeable
# at /metrics.  `Gauge` is re-exported for symmetry.

from ..observability.metrics import (  # noqa: E402,F401
    Counter,
    Gauge,
    Histogram,
)
