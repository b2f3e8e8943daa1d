"""What the runners and the per-layer readers share: the earlier lines,
the program's counters as snapshots, and the device's description."""

import json


def say(phase, **fields):
    """One earlier line: ``[phase] key=value ...`` (values as JSON)."""
    print("[%s] %s" % (phase, " ".join(
        "%s=%s" % (k, json.dumps(v, default=str)) for k, v in fields.items())),
        flush=True)


def need(cond, msg):
    """A failed check of the run's correctness: recorded, not raised, so
    that the run still prints its line with ``correct: false``."""
    if not cond:
        say("check", FAILED=msg)
    return bool(cond)


def held(checks, name, value, limit, msg):
    """A check that compares a number with its limit (``value <= limit``):
    kept in the run's ``checks`` under ``name``, for the result line and
    the last lines of standard error, and recorded like `need` where it
    fails."""
    checks[name] = {"value": float(value), "limit": float(limit)}
    return need(value <= limit, "%s: %s %r against the limit %r"
                % (msg, name, value, limit))


def snapshot():
    """The program's metrics registry as a JSON-able dict."""
    from paddle_tpu.observability import default_registry

    return default_registry().snapshot()


def clear_histograms():
    """Empty every histogram of the program's registry, so that what a
    snapshot after the window holds was observed inside it (a histogram
    keeps a bounded reservoir over its whole life otherwise)."""
    from paddle_tpu.observability import default_registry

    for fam in default_registry().collect():
        if fam.type == "histogram":
            fam.clear()


def _series(snap, name, labels):
    fam = (snap or {}).get(name)
    if fam is None:
        return []
    return [s for s in fam["series"]
            if all(s["labels"].get(k) == v for k, v in labels.items())]


def counter_value(snap, name, **labels):
    """Sum of a counter's series that carry ``labels``; None if the
    family is not in the snapshot."""
    if name not in (snap or {}):
        return None
    return sum(s.get("value") or 0 for s in _series(snap, name, labels))


def counter_delta(obs, name, **labels):
    """Growth of a counter over the window (0 for a family that does not
    exist yet: a counter is created at its first increment)."""
    after = counter_value(obs["counters_after"], name, **labels) or 0
    before = counter_value(obs["counters_before"], name, **labels) or 0
    return after - before


def histogram(obs, name, **labels):
    """The summary (count, sum, p50, ...) of the one histogram series
    with most observations in the window; None if there is none."""
    series = [s for s in _series(obs["counters_after"], name, labels)
              if s.get("count")]
    return max(series, key=lambda s: s["count"]) if series else None


def memory_peak(stats):
    """Peak bytes a chip held: the allocator's peak of live buffers plus
    the most the runtime reserved for compiled programs' temporaries.
    On a TPU ``peak_bytes_in_use`` leaves the second out (BERT-base read
    1.46 GB in use beside 11.59 GB reserved, and the free block was
    what both left of ``bytes_limit``; PR 25)."""
    return int(stats.get("peak_bytes_in_use", 0)
               + stats.get("peak_bytes_reserved", 0))


def device_record(devices, chips):
    """The ``device`` object of the result line, as JAX reports it; the
    memory peak is that of the fullest of the chips used."""
    dev = devices[0]
    peaks = [memory_peak(d.memory_stats() or {}) for d in devices[:chips]]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peaks))}


def idle_share(obs):
    """Percent of the traced stretch in which no operation ran on the
    device (mean over the chips used); None without a trace."""
    red = obs.get("trace")
    if not red or not red["window_s"]:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


def start_trace(trace_dir):
    """Start a profiler session in an emptied ``trace_dir``, without the
    Python call tracer (it slows the host several-fold and the reduction
    reads none of it); device ops and the benchmark's TraceAnnotations
    are kept."""
    import shutil

    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def reduced_trace(trace_dir, idle_label, **where):
    """Reduce the session under ``trace_dir`` and print the earlier lines
    of a traced run: what the trace holds, and the forty groups of device
    operations that took most time.  None where no device op was traced."""
    from chipbench import reduce_xplane

    trace = reduce_xplane.load(trace_dir)
    red = reduce_xplane.reduce(trace, default=idle_label)
    say("trace", layout=trace["layout"], reduced=red and {
        k: red[k] for k in ("devices", "busy_s", "window_s", "per_device")},
        **where)
    if red:
        ops = sorted(red["op_seconds"].items(), key=lambda kv: -kv[1])[:40]
        say("trace-ops", share_of_window_percent=[
            [name, round(100.0 * s / red["window_s"], 3)]
            for name, s in ops])
        say("trace-kernels", seconds=red["kernel_seconds"])
    return red


def dispatch_lines():
    """Which implementation each kernel dispatch took, so far."""
    from paddle_tpu.ops import dispatch

    return ["%s -> %s (%s) x%d" % (key + (n,))
            for key, n in sorted(dispatch.choices().items())]


class Marks:
    """Where set-up time goes: ``mark(phase)`` prints the seconds since
    the last mark."""

    def __init__(self, t0):
        import time

        self.clock, self.last = time.perf_counter, t0

    def __call__(self, phase):
        now = self.clock()
        say("setup", took=phase, seconds=round(now - self.last, 3))
        self.last = now


def kernel_ms_per_step(obs):
    """Device milliseconds a step spends in Pallas custom calls: their
    share of the traced stretch times the median step time.  In the BERT
    step every such call is a flash-attention forward or backward."""
    red = obs.get("trace")
    if not red or not red["kernel_seconds"]:
        return None
    from chipbench import stats

    share = sum(red["kernel_seconds"].values()) / red["window_s"]
    return share * stats.percentile(obs["samples"]["step_ms"], 50)
