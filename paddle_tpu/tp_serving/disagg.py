"""Disaggregated prefill/decode serving (the DistServe split).

Prefill workers run ONLY prefill executables and stream each finished
prompt's KV — the paged block pages plus enough metadata to rebuild a
block-table row — to decode workers, which run ONLY the decode step.
The two phases stop competing for the same chip: prefill's long
compute-bound calls no longer stall decode's latency-bound steps.

* `KVHandoff` — the wire unit: finished pool pages in the pool's own
  form, one ``[n_blocks, bs, H*Dh]`` array per layer for K and for V
  (+ int8 scales ``[n_blocks, bs, H]``; the order of
  `PagedKVCache.arrays`), first sampled token, the request's PRNG key,
  and geometry for validation.  ``nbytes`` is what a real
  deployment would move over ICI/DCN; `DisaggPair` and
  `ShardGroupFleet` meter it as ``kv_transfer_bytes``.
* `DisaggPair` — one co-scheduled group: a prefill-role engine and a
  decode-role engine (either may be tensor-parallel
  `TPGenerationEngine`s — TP and disaggregation compose).  ``submit``
  runs prefill_extract -> inject_prefilled; the decode engine's
  scheduler does the rest.
* `ShardGroupFleet` — the group-level router: requests go to the
  group with the most free decode slots (ties to the lowest group
  id), the same least-loaded discipline the PR-9 `Router` uses across
  replicas, lifted one level up to shard GROUPS.  ``stats()`` feeds
  the ``/stats`` shard-group gauges (`tools/generation_ctl.py tp`).

The executable-set pin (`tests/test_perf_gate.py`): a decode worker
never traces a prefill bucket — its ``stats()["executables"]
["prefill"]`` entries stay at jit-cache size 0 for the life of the
process."""

from __future__ import annotations

import threading

from ..generation.engine import GenerationRequest
from ..observability import locks as _locks

__all__ = [
    "DisaggPair",
    "KVHandoff",
    "ShardGroupFleet",
    "extract_prefilled",
    "inject_prefilled",
]


class KVHandoff:
    """One prefilled request's KV, in flight between workers.

    ``trace`` is the request's serialized
    `observability.trace.TraceContext` wire dict (or None): it crosses
    the process boundary with the pages, so the decode worker's spans
    land on the SAME trace_id/anchored timeline as the prefill
    worker's."""

    __slots__ = ("request", "n_prompt", "tok0", "lp0", "key", "pages",
                 "block_size", "kv_dtype", "trace")

    def __init__(self, request, n_prompt, tok0, lp0, key, pages,
                 block_size, kv_dtype, trace=None):
        self.request = request
        self.n_prompt = int(n_prompt)
        self.tok0 = int(tok0)
        self.lp0 = lp0
        self.key = key
        self.pages = tuple(pages)
        self.block_size = int(block_size)
        self.kv_dtype = kv_dtype
        self.trace = trace

    @property
    def nbytes(self):
        """Bytes a deployment would move for this handoff."""
        return int(sum(p.nbytes for p in self.pages))

    def describe(self):
        return {
            "request_id": self.request.request_id,
            "n_prompt": self.n_prompt,
            "blocks": int(self.pages[0].shape[0]),
            "bytes": self.nbytes,
            "kv_dtype": self.kv_dtype or "float32",
        }


def extract_prefilled(engine, request):
    """Functional alias for ``engine.prefill_extract(request)``."""
    return engine.prefill_extract(request)


def inject_prefilled(engine, handoff, _handle=None):
    """Functional alias for ``engine.inject_prefilled(handoff)``."""
    return engine.inject_prefilled(handoff, _handle=_handle)


class DisaggPair:
    """One shard group: prefill-role engine + decode-role engine.

    Handoff/transfer/occupancy telemetry lives in the PR-4 registry as
    labeled families (``disagg_*`` with a unique ``group`` label) so it
    exports via `prometheus_text` / `json_snapshot`; `stats()` reads
    the SAME series back, keeping the ``/stats`` dict byte-compatible
    with the pre-registry shape."""

    def __init__(self, prefill_engine, decode_engine, group_id=0,
                 metrics_registry=None):
        if not prefill_engine.paged or not decode_engine.paged:
            raise ValueError("disaggregation requires paged engines")
        if prefill_engine.block_size != decode_engine.block_size:
            raise ValueError(
                "block_size mismatch: prefill %d, decode %d"
                % (prefill_engine.block_size, decode_engine.block_size))
        self.prefill = prefill_engine
        self.decode = decode_engine
        self.group_id = int(group_id)
        self._lock = _locks.named_lock("tp_serving.disagg.group")
        if metrics_registry is None:
            from ..observability.metrics import default_registry

            metrics_registry = default_registry()
        self.metrics_registry = metrics_registry
        from ..observability.metrics import unique_instance_label

        self._group_label = unique_instance_label(
            "group%d" % self.group_id)
        lbl = ("group",)
        reg = metrics_registry
        self._m_handoffs = reg.counter(
            "disagg_handoffs_total", "KV handoffs prefill -> decode",
            labelnames=lbl).labels(self._group_label)
        self._m_kv_bytes = reg.counter(
            "disagg_kv_transfer_bytes_total",
            "Bytes of KV pages moved prefill -> decode",
            labelnames=lbl).labels(self._group_label)
        reg.gauge(
            "disagg_headroom", "Free decode slots minus queued work",
            labelnames=lbl).labels(self._group_label).set_function(
                self.headroom)
        reg.gauge(
            "disagg_queue_depth", "Queued handoffs on the decode worker",
            labelnames=lbl).labels(self._group_label).set_function(
                lambda: len(self.decode._pending))
        reg.gauge(
            "disagg_free_decode_slots", "Free decode slots",
            labelnames=lbl).labels(self._group_label).set_function(
                self.free_decode_slots)

    @property
    def handoffs(self):
        return int(self._m_handoffs.value)

    @property
    def kv_transfer_bytes(self):
        return int(self._m_kv_bytes.value)

    def free_decode_slots(self):
        return len(self.decode._free)

    def headroom(self):
        """Free decode slots minus queued work — the routing signal
        (queued handoffs haven't taken a slot yet but will)."""
        return len(self.decode._free) - len(self.decode._pending)

    def submit(self, request, _handle=None, trace=None):
        """Prefill on the prefill worker, hand the KV over, decode on
        the decode worker.  Returns the decode-side handle.  ``trace``
        (a `TraceContext` or wire dict) pins the request's timeline id;
        without one the prefill engine mints a fresh context that the
        handoff carries to the decode side."""
        if not isinstance(request, GenerationRequest):
            request = GenerationRequest(request)
        handoff = self.prefill.prefill_extract(request, trace=trace)
        with self._lock:
            self._m_kv_bytes.inc(handoff.nbytes)
            self._m_handoffs.inc()
        return self.decode.inject_prefilled(handoff, _handle=_handle)

    def run_until_idle(self):
        self.decode.run_until_idle()

    def start(self):
        self.decode.start()
        return self

    def stop(self):
        self.decode.stop()

    def stats(self):
        dstats = self.decode.stats()
        out = {
            "group_id": self.group_id,
            "members": [self.prefill._engine, self.decode._engine],
            "roles": {"prefill": self.prefill._engine,
                      "decode": self.decode._engine},
            "handoffs": self.handoffs,
            "kv_transfer_bytes": self.kv_transfer_bytes,
            "free_decode_slots": self.free_decode_slots(),
            "queue_depth": len(self.decode._pending),
            "headroom": self.headroom(),
            "prefill_executables": dstats["executables"]["prefill"],
        }
        if "tp" in dstats:       # TP decode worker: surface the degree
            out["tp"] = dstats["tp"]
        return out


class ShardGroupFleet:
    """Route requests across shard GROUPS (each a `DisaggPair` or any
    object with ``submit``/``headroom``/``stats``): most decode
    headroom (free slots minus queued work) wins, ties to the lowest
    group id."""

    def __init__(self, groups, metrics_registry=None):
        if not groups:
            raise ValueError("need at least one shard group")
        self.groups = list(groups)
        self._lock = _locks.named_lock("tp_serving.disagg.fleet")
        if metrics_registry is None:
            from ..observability.metrics import default_registry

            metrics_registry = default_registry()
        # the serve_generation_http mount point reads this for /metrics
        self.metrics_registry = metrics_registry
        from ..observability.metrics import unique_instance_label

        self._fleet_label = unique_instance_label("shard_fleet")
        lbl = ("fleet",)
        self._m_submitted = metrics_registry.counter(
            "shard_fleet_requests_total",
            "Requests routed across shard groups",
            labelnames=lbl).labels(self._fleet_label)
        metrics_registry.gauge(
            "shard_fleet_kv_transfer_bytes",
            "Total KV bytes moved prefill -> decode, fleet-wide",
            labelnames=lbl).labels(self._fleet_label).set_function(
                lambda: sum(g.kv_transfer_bytes for g in self.groups))

    @property
    def _submitted(self):
        return int(self._m_submitted.value)

    def submit(self, request, trace=None):
        with self._lock:
            group = max(self.groups,
                        key=lambda g: (g.headroom(), -g.group_id))
            self._m_submitted.inc()
        if trace is not None:       # duck-typed groups may not take it
            return group.submit(request, trace=trace)
        return group.submit(request)

    def run_until_idle(self):
        for g in self.groups:
            g.run_until_idle()

    def start(self):
        for g in self.groups:
            g.start()
        return self

    def stop(self):
        for g in self.groups:
            g.stop()

    def ready(self):
        return any(not g.decode.dead for g in self.groups)

    def stats(self):
        return {
            "submitted": self._submitted,
            "shard_groups": [g.stats() for g in self.groups],
            "kv_transfer_bytes": sum(g.kv_transfer_bytes
                                     for g in self.groups),
        }
