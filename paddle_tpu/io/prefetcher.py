"""DevicePrefetcher: double-buffer host batches onto the accelerator.

The reference's C++ double-buffered feed (`py_reader`/`double_buffer`,
`operators/reader/buffered_reader.cc`) kept N batches in flight on a
background thread so the train op never waited on feeding.  The XLA-era
equivalent: a producer thread walks the host loader, issues an async
`jax.device_put` per batch (sharded batch-dim-over-dp when a mesh is
available — each local device receives only its slice), and parks the
device-resident batch in a bounded queue.  XLA's async dispatch overlaps
the H2D copy of batch N+1 with device execution of batch N; the consumer
side of the queue is the only place the trainer can block, and that wait
is measured (`PipelineStats.step_wait_ms`) so an input-bound run is
diagnosable instead of just slow.

Resume alignment: prefetch depth means the producer runs AHEAD of the
trainer.  Checkpointing the source loader's cursor directly would skip
the in-queue batches the trainer never saw, so the producer snapshots
`source.state_dict()` per batch and the prefetcher exposes the snapshot
belonging to the last DELIVERED batch — `DevicePrefetcher.state_dict()`
is always exact no matter how far ahead the queue ran.
"""

from __future__ import annotations

import queue as _queue
import threading
import time

import numpy as np

from ..observability import trace as _trace
from .stats import PipelineStats

__all__ = ["DevicePrefetcher"]

_SENTINEL = object()


class DevicePrefetcher:
    """Iterate `source`, yielding device-resident batches `depth` ahead.

    source   any iterable of batches (DataLoader, ResumableDataLoader,
             PackingStage, generator).  dict / tuple / list batches are
             placed leaf-wise.
    depth    in-flight device batches (2 = classic double buffering).
    mesh     a `distributed.DeviceMesh`; defaults to the ambient
             `distributed.get_mesh()`.  With a mesh, arrays whose leading
             dim divides by the `axis` size are sharded batch-dim-over-
             `axis` (each local device gets its shard of the H2D copy);
             everything else is replicated.  Without one, batches land on
             the default device.
    stats    a `PipelineStats`; one is created if not given.

    `state_dict()/load_state_dict()/set_epoch()` pass through to the
    source (when it supports them), with state aligned to delivered
    batches as described in the module docstring.
    """

    def __init__(self, source, depth=2, mesh=None, axis="dp", stats=None):
        self.source = source
        self.depth = max(1, int(depth))
        self.axis = axis
        self._mesh = mesh
        self.stats = stats or PipelineStats()
        self._last_state = None      # source state as of the last yield
        self._live_iter = 0          # generation tag: one live iterator
        self._prev = None            # (stop event, thread) of prior iter
        self._dirty = False          # a producer ran ahead of delivery
        # let checkpoint adapters handed any stage of the pipeline find
        # the DELIVERED-batch cursor instead of the ran-ahead one (a
        # weakref: the prefetcher must not keep the stages alive); walk
        # nested `.source` chains so DevicePrefetcher(PackingStage(
        # loader)) tags the loader too
        import weakref

        obj, seen = source, set()
        while obj is not None and id(obj) not in seen:
            seen.add(id(obj))
            try:
                obj._device_prefetcher = weakref.ref(self)
            except AttributeError:
                pass                 # e.g. a generator: no attributes
            obj = getattr(obj, "source", None)

    # -- placement --------------------------------------------------------
    def _resolve_mesh(self):
        if self._mesh is not None:
            return self._mesh
        from ..distributed import get_mesh

        return get_mesh()

    def _placer(self):
        """Build the per-leaf placement fn once per iteration (imports
        jax lazily so host-only use of the package never inits a
        backend)."""
        import jax

        mesh = self._resolve_mesh()
        if mesh is None or not mesh.has_axis(self.axis):
            def put(x):
                return jax.device_put(np.asarray(x))

            return put
        from jax.sharding import NamedSharding, PartitionSpec as P

        nshard = mesh.axis_size(self.axis)
        sharded = NamedSharding(mesh.mesh, P(self.axis))
        repl = NamedSharding(mesh.mesh, P())
        multiproc = jax.process_count() > 1

        def put(x):
            a = np.asarray(x)
            # divisibility is judged on the GLOBAL batch: local rows x
            # process count (each process holds only its sampler shard)
            grows = a.shape[0] * (jax.process_count() if multiproc else 1) \
                if a.ndim >= 1 else 0
            if a.ndim >= 1 and a.shape[0] > 0 and grows % nshard == 0:
                if multiproc:
                    # each process holds only ITS sampler shard: stitch
                    # the local rows into the global dp-sharded array
                    # (device_put here would mislabel local data as the
                    # whole global batch — cf. executor._to_global)
                    return jax.make_array_from_process_local_data(
                        sharded, a)
                return jax.device_put(a, sharded)
            # replicated leaves must be process-identical (epoch-seeded
            # metadata usually is); batch-like leaves take the path above
            return jax.device_put(a, repl)

        return put

    def _source_state(self):
        """Probe the source's cursor; None when the source is stateless.
        A source may EXPOSE state_dict yet not support it (a plain
        DataLoader raises TypeError, a passthrough stage over a
        generator raises AttributeError) — both mean 'stateless'."""
        try:
            return self.source.state_dict()
        except (AttributeError, TypeError):
            return None

    # -- iteration --------------------------------------------------------
    def __iter__(self):
        import jax

        if self._prev is not None:
            # a prior iteration was abandoned without closing its
            # generator: stop ITS producer before ours touches the
            # shared source, or both would drain it concurrently
            prev_stop, prev_t = self._prev
            prev_stop.set()
            prev_t.join(timeout=5)
            if prev_t.is_alive():
                raise RuntimeError(
                    "the previous DevicePrefetcher producer is still "
                    "blocked inside the source (a stuck read?); cannot "
                    "start a new iteration over the same source")
            self._prev = None
        self._live_iter += 1
        gen = self._live_iter
        put = self._placer()
        q = _queue.Queue(maxsize=self.depth)
        stop = threading.Event()
        err = []
        completed = False
        cur_state = self._source_state()
        stateful = cur_state is not None
        if stateful:
            if self._dirty and self._last_state is not None and \
                    hasattr(self.source, "load_state_dict"):
                # the abandoned producer had pulled past the last
                # delivered batch — rewind so nothing is skipped
                self.source.load_state_dict(self._last_state)
            else:
                # exact even before the first delivery (the producer
                # starts pulling ahead immediately)
                self._last_state = cur_state
        self._dirty = stateful

        def offer(item):
            """q.put that gives up when the consumer went away."""
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except _queue.Full:
                    continue
            return False

        def producer():
            try:
                for batch in self.source:
                    t0 = time.perf_counter()
                    placed = jax.tree_util.tree_map(put, batch)
                    state = self._source_state() if stateful else None
                    if not offer((placed, state)):
                        return
                    # bill the full copy (not just dispatch) AFTER the
                    # batch is already available to the consumer; the
                    # producer thread would otherwise just idle on queue
                    # space, so the wait is free
                    jax.block_until_ready(placed)
                    self.stats.h2d_copy_ms.observe(
                        (time.perf_counter() - t0) * 1e3)
            except BaseException as e:
                err.append(e)
            finally:
                offer(_SENTINEL)

        t = threading.Thread(target=producer, daemon=True,
                             name="DevicePrefetcher")
        t.start()
        self._prev = (stop, t)
        try:
            while True:
                if self._live_iter != gen:
                    raise RuntimeError(
                        "this DevicePrefetcher iterator was invalidated "
                        "by a newer iteration (one live iterator at a "
                        "time)")
                t0 = time.perf_counter()
                with _trace.span("io.next_batch", cat="io"):
                    item = q.get()
                self.stats.step_wait_ms.observe(
                    (time.perf_counter() - t0) * 1e3)
                if item is _SENTINEL:
                    completed = True
                    if err:
                        # the producer died mid-pull: its last batch was
                        # consumed off the source but never delivered —
                        # rewind so a trainer that catches the error and
                        # re-iterates doesn't skip it
                        if stateful and hasattr(self.source,
                                                "load_state_dict"):
                            self.source.load_state_dict(self._last_state)
                        self._dirty = False
                        raise err[0]
                    self._dirty = False
                    return
                self.stats.queue_depth.observe(q.qsize())
                self.stats.batches.inc()
                placed, state = item
                if state is not None:
                    self._last_state = state
                yield placed
        finally:
            stop.set()
            try:                       # unblock a producer stuck in put()
                while True:
                    q.get_nowait()
            except _queue.Empty:
                pass
            t.join(timeout=5)
            if t.is_alive():
                # producer stuck in a blocking read: the cursor is in
                # motion, so do NOT rewind (and keep _prev so the next
                # iteration re-joins it); _dirty stays set
                pass
            elif self._live_iter == gen:
                self._prev = None
                if not completed and stateful and \
                        hasattr(self.source, "load_state_dict"):
                    # early break: the producer ran up to depth+1 batches
                    # ahead — rewind the source cursor to the last
                    # DELIVERED batch so undelivered prefetches aren't
                    # lost
                    self.source.load_state_dict(self._last_state)
                    self._dirty = False

    def __len__(self):
        return len(self.source)

    # -- resume/epoch passthrough -----------------------------------------
    def state_dict(self):
        """Source state aligned to DELIVERED batches (see module doc)."""
        if self._last_state is not None:
            return self._last_state
        if hasattr(self.source, "state_dict"):
            return self.source.state_dict()
        raise TypeError(
            "DevicePrefetcher source %r has no state_dict()"
            % type(self.source).__name__)

    def load_state_dict(self, state):
        self.source.load_state_dict(state)
        self._last_state = state       # the loaded cursor IS the position

    def set_epoch(self, epoch):
        if hasattr(self.source, "set_epoch"):
            self.source.set_epoch(epoch)
        self._last_state = self._source_state()
