"""The elastic controller: detect, drain, fence, re-form, resume.

Reference capability being subsumed: the reference delegates elasticity
to the cluster manager — LostWorkerMonitor marks dead trainers
(`heart_beat_monitor.h:54`) and the job restarts from checkpoint_N.
Here the supervisor itself is part of the framework: it drives the
`distributed/monitor` heartbeat machinery, drains survivors so their
in-flight async saves force a final commit, bumps a GENERATION fence so
stale ranks from the old group can never commit into the new one,
re-forms the gang at a (possibly different) world size, and the
`incubate.checkpoint` + `distributed.elastic.reshard` restore path does
the rest.

State machine (README "Elastic training")::

    LAUNCHING -> RUNNING --(rank exit / stale heartbeat)--> DRAINING
        ^                                                      |
        |            (bounded retries, exponential backoff)    v
    RELAUNCH <------------- RESHAPING <---- FENCING (generation += 1)

    RUNNING --(all ranks exit 0)--> DONE
    any    --(retry budget exhausted)--> FAILED
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import time

from ..monitor import LOST, UNINITED, HeartBeatMonitor, _atomic_json_dump
from ...fluid.core.place import check_children_can_take_chip
from ...incubate.checkpoint.checkpoint_saver import StaleGenerationError

__all__ = [
    "GenerationFence",
    "StaleGenerationError",
    "PreemptionHandler",
    "ElasticController",
    "GENERATION_ENV",
    "WORKSPACE_ENV",
]

GENERATION_ENV = "PADDLE_ELASTIC_GENERATION"
WORKSPACE_ENV = "PADDLE_ELASTIC_WORKSPACE"

# controller states (surfaced in metrics/trace and the drill report)
LAUNCHING = "LAUNCHING"
RUNNING = "RUNNING"
DRAINING = "DRAINING"
FENCING = "FENCING"
RESHAPING = "RESHAPING"
DONE = "DONE"
FAILED = "FAILED"


class GenerationFence:
    """File-backed elastic generation counter with commit fencing.

    The controller `bump()`s the shared counter before re-forming the
    group; every worker constructs a fence pinned to ITS generation (the
    value of $PADDLE_ELASTIC_GENERATION at spawn) and hands it to its
    CheckpointSaver, whose commit path calls `check()` — a rank that
    outlived its group gets StaleGenerationError instead of publishing a
    checkpoint the new group would then trust."""

    def __init__(self, workspace, generation=None):
        self._path = os.path.join(workspace, "GENERATION")
        if generation is None:
            env = os.getenv(GENERATION_ENV)
            generation = int(env) if env is not None else self.read()
        self.generation = int(generation)

    def read(self):
        """The CURRENT generation in the shared workspace (0 when none
        was ever written)."""
        try:
            with open(self._path) as f:
                return int(f.read().strip() or 0)
        except (OSError, ValueError):
            return 0

    def write(self, generation):
        tmp = "%s.tmp%d" % (self._path, os.getpid())
        d = os.path.dirname(self._path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(tmp, "w") as f:
            f.write(str(int(generation)))
        os.replace(tmp, self._path)
        return int(generation)

    def bump(self):
        """Advance the shared counter (controller side); returns the new
        generation.  Atomic rename: a worker reading concurrently sees
        the old or the new value, never a torn file."""
        new = self.read() + 1
        self.write(new)
        self.generation = new
        return new

    def check(self):
        """Raise StaleGenerationError when the shared counter moved PAST
        this process's generation.

        Read failures are NOT staleness: a transient I/O error on the
        fence file propagates as the OSError it is (retryable by the
        saver's transient policy), and a missing file reads as 0 — the
        bootstrap state, never newer than any live rank.  Only a counter
        genuinely ahead of ours proves we were superseded."""
        try:
            with open(self._path) as f:
                current = int(f.read().strip() or 0)
        except FileNotFoundError:
            current = 0
        except ValueError as e:
            raise OSError(
                "generation fence %r is unreadable: %s" % (self._path, e))
        if current > self.generation:
            raise StaleGenerationError(
                "this rank belongs to elastic generation %d but the "
                "group is at generation %d — a superseded rank must not "
                "commit (its state predates the recovery)"
                % (self.generation, current))


class PreemptionHandler:
    """Worker-side graceful-drain hook.

    `install()` chains a SIGTERM handler that only sets a flag; the
    training loop polls `should_stop` per step and, when set, saves a
    final mid-epoch checkpoint (cursor + params — the exact-resume
    commit) and exits 0.  That is what lets the controller's DRAINING
    state turn "preemption notice" into "no lost work"."""

    def __init__(self):
        self._stop = False
        self._prev = None

    def install(self):
        def handler(signum, frame):
            self._stop = True
            if callable(self._prev):
                self._prev(signum, frame)

        self._prev = signal.getsignal(signal.SIGTERM)
        signal.signal(signal.SIGTERM, handler)
        return self

    @property
    def should_stop(self):
        return self._stop


class ElasticController:
    """Supervise a gang of worker processes across elastic generations.

    `worker_argv(rank, world_size, generation)` builds each rank's
    command line; the controller supplies the launch env contract
    (PADDLE_TRAINER_ID/PADDLE_TRAINERS_NUM plus the elastic generation
    and workspace).  `world_size_policy(generation, prev_world, event)`
    decides the re-formed group's size after a failure — default keeps
    the previous size (replacement hardware); pass a schedule-backed
    policy to drill reshapes or to shrink onto surviving capacity.

    Recovery events land in the PR 4 metrics registry
    (`elastic_recoveries_total`, `elastic_rank_failures_total`,
    `elastic_generation`, `elastic_world_size`) and the PR 6 tracer
    (one `elastic_recovery` span per DRAIN->RELAUNCH cycle with
    rank/cause args, instants for rank loss and fence bumps)."""

    def __init__(self, workspace, worker_argv, world_size,
                 world_size_policy=None, max_restarts=3,
                 backoff_s=1.0, max_backoff_s=30.0,
                 heartbeat_interval_s=0.5, heartbeat_timeout_s=5.0,
                 drain_grace_s=10.0, poll_s=0.2, env=None, log_dir=None,
                 startup_timeout_s=300.0):
        self._ws = workspace
        self._worker_argv = worker_argv
        self._world = int(world_size)
        self._policy = world_size_policy or (
            lambda gen, prev_world, event: prev_world)
        self._max_restarts = int(max_restarts)
        self._backoff_s = float(backoff_s)
        self._max_backoff_s = float(max_backoff_s)
        self._hb_interval = float(heartbeat_interval_s)
        self._hb_timeout = float(heartbeat_timeout_s)
        self._drain_grace = float(drain_grace_s)
        self._poll_s = float(poll_s)
        # a rank that wedges BEFORE its first heartbeat ping stays
        # UNINITED (not LOST) forever — give startup its own deadline so
        # an XLA-init deadlock is still a detectable failure.  Applies
        # only when SOME rank does heartbeat (a gang that never pings is
        # monitored by process exits alone)
        self._startup_timeout = float(startup_timeout_s)
        self._env = env if callable(env) else dict(env or {})
        self._log_dir = log_dir
        self.state = LAUNCHING
        self.history = []          # [{generation, world_size, event, ...}]
        self.fence = GenerationFence(workspace, generation=None)

    # -- observability ----------------------------------------------------
    def _reg(self):
        from ...observability.metrics import default_registry

        return default_registry()

    def _tracer(self):
        from ...observability import trace as _trace

        return _trace.default_tracer()

    def _set_state(self, state, **args):
        self.state = state
        try:
            self._reg().gauge(
                "elastic_generation",
                "Current elastic generation of the controller"
            ).set(self.fence.generation)
            self._reg().gauge(
                "elastic_world_size",
                "World size of the current elastic generation"
            ).set(self._world)
            tr = self._tracer()
            if tr.enabled:
                tr.instant("elastic_state", cat="elastic",
                           args={"state": state, **args})
        except Exception:
            pass   # telemetry must never sink the supervisor

    # -- gang management --------------------------------------------------
    def _spawn(self, generation):
        procs = []
        logs = []
        if self._log_dir:
            os.makedirs(self._log_dir, exist_ok=True)
        for rank in range(self._world):
            env = dict(os.environ)
            # static dict, or a per-(rank, world, generation) factory —
            # launch-style endpoint wiring needs the latter
            env.update(self._env(rank, self._world, generation)
                       if callable(self._env) else self._env)
            env.update({
                "PADDLE_TRAINER_ID": str(rank),
                "PADDLE_TRAINERS_NUM": str(self._world),
                GENERATION_ENV: str(generation),
                WORKSPACE_ENV: self._ws,
            })
            argv = self._worker_argv(rank, self._world, generation)
            check_children_can_take_chip("elastic worker gang", env)
            if self._log_dir:
                f = open(os.path.join(
                    self._log_dir, "worker_g%d_r%d.log"
                    % (generation, rank)), "w")
                logs.append(f)
                procs.append(subprocess.Popen(
                    argv, env=env, stdout=f, stderr=subprocess.STDOUT))
            else:
                procs.append(subprocess.Popen(argv, env=env))
        return procs, logs

    def _terminate(self, procs, sig=signal.SIGTERM):
        for p in procs:
            if p.poll() is None:
                try:
                    p.send_signal(sig)
                except OSError:
                    pass

    def _drain(self, procs):
        """SIGTERM the survivors (their PreemptionHandler saves a final
        cursor-exact checkpoint and exits 0), escalate to SIGKILL after
        the grace window."""
        self._set_state(DRAINING)
        self._terminate(procs, signal.SIGTERM)
        deadline = time.time() + self._drain_grace
        while time.time() < deadline and any(
                p.poll() is None for p in procs):
            time.sleep(self._poll_s)
        stragglers = [i for i, p in enumerate(procs) if p.poll() is None]
        if stragglers:
            self._terminate(procs, signal.SIGKILL)
            for p in procs:
                try:
                    p.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    pass
        return stragglers

    def _clear_heartbeats(self):
        hb_dir = os.path.join(self._ws, "heartbeats")
        if not os.path.isdir(hb_dir):
            return
        for name in os.listdir(hb_dir):
            try:
                os.remove(os.path.join(hb_dir, name))
            except OSError:
                pass

    # -- the supervisor loop ----------------------------------------------
    def run(self):
        """Run generations until the gang completes or the retry budget
        is spent.  Returns a report dict; `state` ends DONE or FAILED."""
        restarts = 0
        backoff = self._backoff_s
        generation = self.fence.generation
        while True:
            self._set_state(LAUNCHING, generation=generation,
                            world_size=self._world)
            self._clear_heartbeats()
            hb = HeartBeatMonitor(
                self._ws, worker_id=-1, worker_num=self._world,
                interval_s=self._hb_interval, timeout_s=self._hb_timeout)
            t_gen = time.time()
            procs, logs = self._spawn(generation)
            self._set_state(RUNNING, generation=generation,
                            world_size=self._world)
            event = None
            try:
                while event is None:
                    time.sleep(self._poll_s)
                    codes = [p.poll() for p in procs]
                    if all(c == 0 for c in codes):
                        event = {"kind": "done"}
                        break
                    bad = [i for i, c in enumerate(codes)
                           if c not in (None, 0)]
                    if bad:
                        event = {"kind": "rank_exit", "ranks": bad,
                                 "codes": [codes[i] for i in bad]}
                        break
                    # a hung-but-alive rank only shows in its heartbeat
                    status = hb.worker_status()
                    lost = [r for r, s in status.items()
                            if s == LOST and codes[r] is None]
                    if lost:
                        event = {"kind": "stale_heartbeat", "ranks": lost}
                        break
                    if time.time() - t_gen > self._startup_timeout:
                        uninit = [r for r, s in status.items()
                                  if s == UNINITED and codes[r] is None]
                        # only meaningful when the gang USES heartbeats:
                        # a worker script that never pings leaves every
                        # rank UNINITED by design — rely on process
                        # exits for those, never kill a healthy gang
                        if uninit and len(uninit) < len(
                                [c for c in codes if c is None]):
                            event = {"kind": "startup_timeout",
                                     "ranks": uninit}
                            break
            finally:
                for f in logs:
                    f.close()
            self.history.append({
                "generation": generation, "world_size": self._world,
                "event": event, "elapsed_s": round(time.time() - t_gen, 3),
            })
            if event["kind"] == "done":
                self._set_state(DONE)
                return self._report(DONE)

            # ---- recovery cycle ----------------------------------------
            try:
                self._reg().counter(
                    "elastic_rank_failures_total",
                    "Worker ranks lost to exits or stale heartbeats",
                    labelnames=("kind",)).labels(event["kind"]).inc(
                        len(event.get("ranks", [])) or 1)
            except Exception:
                pass
            tr = None
            t0 = time.perf_counter()
            try:
                tr = self._tracer()
            except Exception:
                pass
            if restarts >= self._max_restarts:
                self._terminate(procs, signal.SIGKILL)
                self._set_state(FAILED, cause=event["kind"])
                return self._report(FAILED)
            stragglers = self._drain(procs)
            # fence BEFORE the new group exists: from this instant a
            # surviving-but-slow old rank cannot commit a checkpoint
            self._set_state(FENCING)
            generation = self.fence.bump()
            prev_world = self._world
            self._set_state(RESHAPING)
            self._world = int(self._policy(generation, prev_world, event))
            if self._world < 1:
                self._set_state(FAILED, cause="policy returned world<1")
                return self._report(FAILED)
            restarts += 1
            try:
                self._reg().counter(
                    "elastic_recoveries_total",
                    "Completed drain->fence->reshape->relaunch cycles"
                ).inc()
                if tr is not None and tr.enabled:
                    tr.complete(
                        "elastic_recovery", t0, time.perf_counter(),
                        cat="elastic",
                        args={"cause": event["kind"],
                              "ranks": event.get("ranks"),
                              "stragglers": stragglers,
                              "generation": generation,
                              "world_size": {"from": prev_world,
                                             "to": self._world}})
            except Exception:
                pass
            time.sleep(min(backoff, self._max_backoff_s))
            backoff = min(backoff * 2, self._max_backoff_s)

    def _report(self, state):
        report = {
            "state": state,
            "generation": self.fence.generation,
            "world_size": self._world,
            "history": self.history,
        }
        try:
            _atomic_json_dump(
                os.path.join(self._ws, "elastic_report.json"), report)
        except OSError:
            pass
        return report
