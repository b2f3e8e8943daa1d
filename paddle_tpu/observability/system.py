"""System gauges: device memory, live arrays, host RSS — sampled on a
background thread into the shared registry; and the interpreter's
collections, timed by a `gc.callbacks` hook (`CollectionPauses`).

Capability parity: the reference used StatRegistry counters for GPU
memory high-water marks (`platform/monitor.h`, STAT_ADD in the CUDA
allocator) and a separate monitor daemon.  TPU-first: the authoritative
device numbers come from the runtime itself — `jax.Device.memory_stats()`
(bytes_in_use / peak_bytes_in_use / num_allocs on TPU and GPU backends)
— with `jax.live_arrays()` as the framework-level view.  On backends
that expose no memory stats (CPU jax) the sampler degrades to the host
metrics alone: every gauge it CAN read is still correct, and nothing
raises.
"""

from __future__ import annotations

import gc
import os
import threading
import time
from collections import deque

from jax.profiler import TraceAnnotation as _Annotation

from . import locks
from . import trace as _trace
from .metrics import default_registry

__all__ = ["SystemMetricsSampler", "CollectionPauses"]


class SystemMetricsSampler:
    """Background sampler: `start()` spawns a daemon thread calling
    `sample_once()` every `interval_s`; `stop()` joins it.  `sample_once`
    is also usable synchronously (tests, one-shot dumps) and returns the
    dict of values it wrote."""

    def __init__(self, registry=None, interval_s=10.0):
        self.registry = registry or default_registry()
        self.interval_s = float(interval_s)
        self._thread = None
        self._stop = threading.Event()
        r = self.registry
        dl = ("device",)
        self._g_in_use = r.gauge(
            "device_memory_bytes_in_use",
            "Device allocator bytes currently in use "
            "(jax.Device.memory_stats)", labelnames=dl)
        self._g_peak = r.gauge(
            "device_memory_peak_bytes",
            "Device allocator peak bytes in use", labelnames=dl)
        self._g_limit = r.gauge(
            "device_memory_bytes_limit",
            "Device allocator byte limit (0 when the backend reports "
            "none)", labelnames=dl)
        self._g_live = r.gauge(
            "jax_live_arrays", "Live jax.Array count on this host")
        self._g_rss = r.gauge(
            "host_rss_bytes", "Current resident set size of this process")
        self._g_peak_rss = r.gauge(
            "host_peak_rss_bytes",
            "Lifetime peak resident set size (getrusage high-water mark)")
        self._c_samples = r.counter(
            "system_metrics_samples_total", "sample_once() invocations")

    # -- one sample ------------------------------------------------------
    def sample_once(self):
        out = {}
        try:
            import jax

            for d in jax.local_devices():
                label = "%s:%d" % (d.platform, d.id)
                try:
                    stats = d.memory_stats()
                except Exception:
                    stats = None
                if not stats:       # CPU backend: None — graceful no-op
                    continue
                in_use = stats.get("bytes_in_use")
                if in_use is not None:
                    self._g_in_use.labels(label).set(in_use)
                    out["device_memory_bytes_in_use{%s}" % label] = in_use
                peak = stats.get("peak_bytes_in_use")
                if peak is not None:
                    self._g_peak.labels(label).set(peak)
                limit = stats.get("bytes_limit")
                if limit is not None:
                    self._g_limit.labels(label).set(limit)
            try:
                n_live = len(jax.live_arrays())
                self._g_live.set(n_live)
                out["jax_live_arrays"] = n_live
            except Exception:
                pass
        except Exception:
            pass                     # no jax / backend init failed: host-only
        rss = _host_rss_bytes()
        if rss is not None:
            self._g_rss.set(rss)
            out["host_rss_bytes"] = rss
        peak = _host_peak_rss_bytes()
        if peak is not None:
            self._g_peak_rss.set(peak)
            out["host_peak_rss_bytes"] = peak
        self._c_samples.inc()
        return out

    # -- lifecycle -------------------------------------------------------
    def start(self):
        if self._thread is not None:
            return self
        self._stop.clear()

        def loop():
            while not self._stop.is_set():
                self.sample_once()
                self._stop.wait(self.interval_s)

        self._thread = threading.Thread(
            target=loop, daemon=True, name="system-metrics")
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()


class CollectionPauses:
    """The interpreter's collections, each a span ``span_name`` (args
    ``generation`` and ``collected``) and an observation of the
    histogram ``family`` (ms) in the registry of every holder: one
    `gc.callbacks` hook a process, installed while anyone holds it
    (`acquire` and `release` count the holders by registry).

    The hook runs at an allocation on any thread, which may hold any
    lock at that moment (a tracer's, a histogram's), so it takes none:
    it opens only the profiler's own annotation, which puts the
    collection on the device trace's clock as it happens, and keeps the
    finished collection in a bounded queue.  `drain` (a holder's loop
    calls it) turns the queue into ring spans and observations.  A
    collection holds the GIL, so it stalls every thread of the process;
    its span lies on the thread that collected."""

    def __init__(self, span_name, family, help="", cat=""):
        self.span_name = span_name
        self.family = family
        self.help = help
        self.cat = cat
        self._lock = locks.named_lock("observability.gc_pauses")
        self._holders = {}      # id(registry) -> [registry, hist, holds]
        self._hists = ()        # what `drain` observes into: swapped whole
        self._open = None       # the collection under way: one at a time
        self._done = deque(maxlen=1024)

    def _swap_hists(self):
        self._hists = tuple(hist for _, hist, _ in self._holders.values())

    def acquire(self, registry=None):
        registry = registry or default_registry()
        with self._lock:
            if not self._holders:
                self._open = None
                gc.callbacks.append(self._callback)
            held = self._holders.get(id(registry))
            if held is None:
                held = self._holders[id(registry)] = [
                    registry, registry.histogram(self.family, self.help), 0]
                self._swap_hists()
            held[2] += 1

    def release(self, registry=None):
        registry = registry or default_registry()
        self.drain()
        with self._lock:
            held = self._holders[id(registry)]
            held[2] -= 1
            if not held[2]:
                del self._holders[id(registry)]
                self._swap_hists()
                if not self._holders:
                    gc.callbacks.remove(self._callback)

    def _callback(self, phase, info):
        if phase == "start":
            ann = None
            if _Annotation.is_enabled():
                ann = _Annotation(self.span_name,
                                  generation=info["generation"])
                ann.__enter__()
            self._open = (time.perf_counter(), info["generation"], ann)
        elif self._open is not None:
            t0, generation, ann = self._open
            self._open = None
            t1 = time.perf_counter()
            if ann is not None:
                ann.set_metadata(collected=info["collected"])
                ann.__exit__(None, None, None)
            self._done.append((t0, t1, generation, info["collected"],
                               threading.get_ident()))

    def drain(self):
        """Report the collections finished since the last call: a ring
        span each (on the thread that collected) and an observation in
        every holder's histogram."""
        while self._done:
            try:
                t0, t1, generation, collected, tid = self._done.popleft()
            except IndexError:      # another holder took the last one
                return
            for hist in self._hists:
                hist.observe((t1 - t0) * 1e3)
            _trace.default_tracer().complete(
                self.span_name, t0, t1, cat=self.cat,
                args={"generation": generation, "collected": collected},
                tid=tid)


def _host_rss_bytes():
    """CURRENT resident set (linux /proc; ru_maxrss would be the
    lifetime peak — see _host_peak_rss_bytes)."""
    try:
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        return pages * os.sysconf("SC_PAGE_SIZE")
    except Exception:
        return None


def _host_peak_rss_bytes():
    try:
        import resource
        import sys

        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        # linux reports KiB; darwin reports bytes
        return rss if sys.platform == "darwin" else rss * 1024
    except Exception:
        return None
