"""Replica pool: N predictor workers behind one interface.

A `Replica` is one loaded copy of one model version that serves one
padded batch at a time.  The router owns a worker thread per replica;
whichever replica frees a slot pulls the next oldest group — that is
the whole "continuous batching across replicas" mechanism, so the
interface stays deliberately tiny:

    run(feed) -> [np.ndarray, ...]        (blocking, one batch)
    warmup(specs), alive, close(), describe()

Two implementations behind it:

* `InProcessReplica` — wraps a `Predictor` in this process (thread
  workers).  Zero IPC cost; replicas share the process's device.
* `ProcessReplica` — a subprocess running `paddle_tpu.serving.worker`,
  speaking length-prefixed pickles over a dedicated pipe pair (fds 3/4
  — stdout stays free for logs).  Process death is detected as EOF on
  the pipe and surfaces as `ReplicaDeadError`, the signal the router's
  requeue-once discipline keys on.

Fault drills: both kinds honor the `incubate.fault` plan's
``kill_replica`` events — the process kind by real SIGKILL mid-request
(in the worker), the in-process kind by raising `ReplicaDeadError` on
the scheduled request, so the same drill runs at both isolation levels.
"""

from __future__ import annotations

import os
import pickle
import struct
import subprocess
import sys
import threading

import numpy as np

from ..fluid.core.place import check_children_can_take_chip
from ..observability import locks as _locks

__all__ = [
    "InProcessReplica",
    "ProcessReplica",
    "Replica",
    "ReplicaDeadError",
    "make_replicas",
]

# env var telling a worker subprocess which replica index it is (the
# address space of the fault plan's kill_replica events)
REPLICA_INDEX_ENV = "PADDLE_TPU_REPLICA_INDEX"
# the worker's end of the pipe pair (fd numbers survive exec via
# pass_fds; stdout/stderr stay ordinary log channels)
WORKER_RFD_ENV = "PADDLE_TPU_WORKER_RFD"
WORKER_WFD_ENV = "PADDLE_TPU_WORKER_WFD"


class ReplicaDeadError(RuntimeError):
    """The replica died (process gone / injected death) — the request
    was NOT served and is safe to re-queue exactly once."""


# -- pipe protocol (shared with serving.worker) ------------------------------

def write_frame(f, obj):
    payload = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    f.write(struct.pack("<I", len(payload)))
    f.write(payload)
    f.flush()


def read_frame(f):
    """One pickled frame, or None on EOF (peer died / closed)."""
    header = f.read(4)
    if len(header) < 4:
        return None
    (n,) = struct.unpack("<I", header)
    payload = b""
    while len(payload) < n:
        chunk = f.read(n - len(payload))
        if not chunk:
            return None
        payload += chunk
    return pickle.loads(payload)


class Replica:
    """Interface + shared bookkeeping (id, served-request count)."""

    def __init__(self, index, version):
        self.index = int(index)
        self.version = str(version)
        self.replica_id = "%s/r%d" % (self.version, self.index)
        self.requests_served = 0

    @property
    def alive(self):
        raise NotImplementedError

    def run(self, feed, trace=None):
        """Serve one padded batch.  ``trace``: optional trace wire dict
        (a `TraceContext.to_wire()` or a batch ``{"trace_ids": [...],
        "anchor_unix_time", "anchor_clock"}``) — process replicas ship
        it over the pipe so the worker's spans land on the requests'
        fleet timeline; in-process replicas share the tracer anyway."""
        raise NotImplementedError

    def warmup(self, specs):
        raise NotImplementedError

    def close(self):
        raise NotImplementedError

    def describe(self):
        return {"replica_id": self.replica_id, "kind": self.kind,
                "alive": self.alive, "requests": self.requests_served}


class InProcessReplica(Replica):
    """A Predictor in this process; `run` is the jitted call itself."""

    kind = "thread"

    def __init__(self, predictor, index=0, version="v", fault_plan=None):
        super().__init__(index, version)
        self._pred = predictor
        self._dead = False
        if fault_plan is None:
            from ..incubate.fault import FaultPlan

            fault_plan = FaultPlan.from_env()
        self._kill_at = fault_plan.replica_kill_request(self.index)

    @property
    def alive(self):
        return not self._dead

    @property
    def feed_names(self):
        if hasattr(self._pred, "get_input_names"):
            return list(self._pred.get_input_names())
        return None

    def run(self, feed, trace=None):
        if self._dead:
            raise ReplicaDeadError("%s is dead" % self.replica_id)
        self.requests_served += 1
        if self._kill_at is not None \
                and self.requests_served >= self._kill_at:
            # the in-process flavor of the kill_replica drill: the
            # request is lost mid-serve, exactly like a SIGKILLed worker
            self._dead = True
            raise ReplicaDeadError(
                "%s: injected death on request %d"
                % (self.replica_id, self.requests_served))
        return [np.asarray(o) for o in self._pred.run(feed)]

    def warmup(self, specs):
        if hasattr(self._pred, "warmup"):
            return self._pred.warmup(specs)
        for feed in specs:
            self._pred.run(feed)
        return getattr(self._pred, "compile_count", None)

    def cost_analysis(self, feed):
        if hasattr(self._pred, "cost_analysis"):
            return self._pred.cost_analysis(feed)
        return None

    def close(self):
        self._dead = True


class ShardGroupReplica(Replica):
    """G model-parallel shard workers behind ONE replica facade — the
    second routing dimension (`paddle_tpu.tp_serving`): the router
    load-balances across GROUPS, and every request fans out to every
    member of its group (all shards of a tensor-parallel executable
    must step together).  The primary (member 0) owns the output; the
    group is alive only while EVERY shard is — one dead shard kills
    the group, exactly like a real TP ensemble losing a chip — and the
    fleet's requeue-after-death drill then replays the request on
    another group."""

    kind = "shard_group"

    def __init__(self, members, group_index=0, version="v"):
        if not members:
            raise ValueError("shard group needs at least one member")
        super().__init__(group_index, version)
        self.members = list(members)
        self.replica_id = "%s/g%d" % (self.version, self.index)

    @property
    def alive(self):
        return all(m.alive for m in self.members)

    @property
    def feed_names(self):
        return getattr(self.members[0], "feed_names", None)

    def run(self, feed, trace=None):
        self.requests_served += 1
        outs = [m.run(feed, trace=trace) for m in self.members]
        return outs[0]

    def warmup(self, specs):
        out = None
        for m in self.members:
            out = m.warmup(specs)
        return out

    def cost_analysis(self, feed):
        m = self.members[0]
        if hasattr(m, "cost_analysis"):
            return m.cost_analysis(feed)
        return None

    def close(self):
        for m in self.members:
            m.close()

    def describe(self):
        return {"replica_id": self.replica_id, "kind": self.kind,
                "alive": self.alive, "requests": self.requests_served,
                "shard_group_size": len(self.members),
                "members": [m.describe() for m in self.members]}


def group_replicas(reps, group_size):
    """Wrap consecutive runs of ``group_size`` replicas in
    `ShardGroupReplica` facades; ``group_size<=1`` is the identity."""
    g = int(group_size)
    if g <= 1:
        return list(reps)
    if len(reps) % g:
        raise ValueError(
            "replicas=%d not divisible by shard_group_size=%d"
            % (len(reps), g))
    return [ShardGroupReplica(reps[i:i + g], group_index=i // g,
                              version=reps[i].version)
            for i in range(0, len(reps), g)]


class ProcessReplica(Replica):
    """A subprocess worker over a private pipe pair.

    The worker loads the model (the load itself runs the verify gate),
    answers ("ready", info) or ("err", message), then serves
    ("run", feed) / ("warmup", specs) / ("close",) frames.  Any pipe
    EOF — a crash, a SIGKILL drill, an OOM kill — is a dead replica."""

    kind = "process"

    def __init__(self, model_dir, index=0, version="v", env=None,
                 load_timeout=120.0):
        super().__init__(index, version)
        # one in-flight frame at a time; allow_blocking: the pipe
        # roundtrip IS the serialized critical section by design
        self._lock = _locks.named_lock(
            "serving.replica.pipe", level="replica",
            allow_blocking=True)
        self._dead = False
        self.feed_names = None

        # parent writes c2w -> worker reads; worker writes w2c ->
        # parent reads.  The worker finds its fd numbers in env.
        c2w_r, c2w_w = os.pipe()
        w2c_r, w2c_w = os.pipe()
        worker_env = dict(os.environ)
        worker_env.update(env or {})
        worker_env[REPLICA_INDEX_ENV] = str(self.index)
        worker_env[WORKER_RFD_ENV] = str(c2w_r)
        worker_env[WORKER_WFD_ENV] = str(w2c_w)
        repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        worker_env.setdefault("PYTHONPATH", repo_root)
        check_children_can_take_chip(
            "process replica %s" % self.replica_id, worker_env)
        self._proc = subprocess.Popen(
            [sys.executable, "-m", "paddle_tpu.serving.worker", model_dir],
            env=worker_env, pass_fds=(c2w_r, w2c_w), close_fds=True)
        os.close(c2w_r)
        os.close(w2c_w)
        self._w = os.fdopen(c2w_w, "wb")
        self._r = os.fdopen(w2c_r, "rb")
        # handshake: the worker's model load (incl. the verify gate)
        # happens before "ready"
        msg = self._read(timeout=load_timeout)
        if msg is None or msg[0] != "ready":
            err = msg[1] if msg else "worker died during model load"
            self.close()
            raise RuntimeError(
                "replica %s failed to load: %s" % (self.replica_id, err))
        self.feed_names = msg[1].get("feed_names")

    def _read(self, timeout=None):
        import select

        if timeout is not None:
            ready, _, _ = select.select([self._r], [], [], timeout)
            if not ready:
                return None
        try:
            return read_frame(self._r)
        except Exception:
            return None

    @property
    def alive(self):
        return not self._dead and self._proc.poll() is None

    def _roundtrip(self, msg):
        with self._lock:
            if self._dead:
                raise ReplicaDeadError("%s is dead" % self.replica_id)
            try:
                # concurrency-ok[blocking-under-lock]: the pipe roundtrip IS the serialized critical section; a dead worker surfaces as EOF, never a hang
                write_frame(self._w, msg)
                # concurrency-ok[blocking-under-lock]: same frame transaction as the write above
                reply = read_frame(self._r)
            except (OSError, ValueError):
                reply = None
            if reply is None:       # EOF: the process died mid-request
                self._dead = True
                raise ReplicaDeadError(
                    "%s: worker process died (rc=%s)"
                    % (self.replica_id, self._proc.poll()))
            return reply

    def run(self, feed, trace=None):
        self.requests_served += 1
        # the 2-element frame stays the wire default — a trace-less
        # parent speaks the exact pre-trace protocol
        msg = ("run", feed) if trace is None else ("run", feed, trace)
        reply = self._roundtrip(msg)
        if reply[0] == "ok":
            return reply[1]
        err_type, err_msg = reply[1], reply[2]
        exc = ValueError if err_type in ("ValueError", "TypeError") \
            else RuntimeError
        raise exc(err_msg)

    def warmup(self, specs):
        reply = self._roundtrip(("warmup", list(specs)))
        if reply[0] == "ok":
            return reply[1]
        raise RuntimeError(reply[2])

    def trace_shard(self):
        """Fetch the worker's tracer shard (a chrome-trace dict with
        anchor metadata) for `merge_fleet_trace` — the parent-side half
        of the cross-process timeline."""
        reply = self._roundtrip(("trace",))
        if reply[0] == "ok":
            return reply[1]
        raise RuntimeError(reply[2])

    def close(self):
        if not self._dead:
            self._dead = True
            try:
                write_frame(self._w, ("close",))
            except Exception:
                pass
        for f in (getattr(self, "_w", None), getattr(self, "_r", None)):
            try:
                if f is not None:
                    f.close()
            except Exception:
                pass
        if self._proc.poll() is None:
            try:
                self._proc.terminate()
                self._proc.wait(timeout=5)
            except Exception:
                try:
                    self._proc.kill()
                    self._proc.wait(timeout=5)
                except Exception:
                    pass


def make_replicas(kind, model_dir, n, version, predictor_factory=None,
                  env=None):
    """Build n replicas of one version.  kind: "thread" (in-process
    Predictors) or "process" (subprocess workers).  predictor_factory
    overrides how thread replicas obtain their predictor (tests inject
    fakes; default loads a fresh `inference.Predictor` per replica)."""
    replicas = []
    try:
        if kind == "thread":
            if predictor_factory is None:
                def predictor_factory(model_dir):
                    from ..inference import AnalysisConfig, create_predictor

                    return create_predictor(AnalysisConfig(model_dir))
            for i in range(n):
                replicas.append(InProcessReplica(
                    predictor_factory(model_dir), index=i, version=version))
        elif kind == "process":
            for i in range(n):
                replicas.append(ProcessReplica(
                    model_dir, index=i, version=version, env=env))
        else:
            raise ValueError("unknown replica kind %r "
                             "(expected 'thread' or 'process')" % kind)
    except Exception:
        for r in replicas:
            r.close()
        raise
    return replicas
