"""What the program itself wrote into the profiler session: its spans on
the host planes, its step functions on the device's ``XLA Modules`` line,
and its kernels and named scopes among the device's operations.

The program opens its spans through `paddle_tpu.observability.trace.span`,
which holds a ``TraceAnnotation`` open while a session runs; it names
every jitted step function (``generation_decode``, ``train_step``, ...),
every Pallas kernel (``flash_attention_fwd``, ...) and two scopes inside
the train step (``optimizer_update``, ``loss_and_grad``).  A program that
does none of that (the parent of the PR that brought this file) leaves
nothing to find here, and every reader of this module then returns None.

Everything above `load` works on plain tuples, so the tests drive it with
hand-made intervals and event names; the interval arithmetic is
`reduce_xplane`'s."""

import collections
import functools
import glob
import os
import re

from chipbench import collectives, common, reduce_xplane, stats
from chipbench.reduce_xplane import HLO, attribute, gaps, self_times, union

# the checkout's root, as `chipbench.run.ROOT` has it (that module is the
# command's ``__main__`` and is not imported a second time from here)
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MODULES_LINE = "XLA Modules"    # one event per executed program
# the program's span names, and the benchmark's own annotations
SPAN_PREFIXES = ("http.", "generation.", "train.", "io.", "bench.")
# spans of the threads that hand work to the device.  An HTTP handler never
# does, and its ``http.generate`` lives as long as its request: it covers
# every gap and explains none, so it names no idle time.
DRIVER_PREFIXES = reduce_xplane.HOST_PREFIXES
# idle under this span is the traffic's doing: the scheduler had no work
IDLE_WAIT = "generation.idle_wait"
SCOPES = ("optimizer_update", "loss_and_grad", "flash_attention")
UNATTRIBUTED = "unattributed"

Span = collections.namedtuple("Span", "name start end thread args")

# an ``XLA Modules`` event is named ``jit_<function>(<fingerprint>)``
MODULE = re.compile(r"^(?:jit_|pmap_)?(?P<name>.*?)(?:\(\d+\))?$")
OP_NAME_STAT = "tf_op"           # the profiler's name for HLO's op_name
PROGRAM_STAT = "program_id"      # of an op's event metadata
METADATA_PLANE = "/host:metadata"    # one event metadata per program,
HLO_PROTO_STAT = "Hlo Proto"         # with its serialized HloProto


def module_name(event_name):
    """``jit_generation_decode(7204...)`` -> ``generation_decode``."""
    return MODULE.match(event_name).group("name")


@functools.lru_cache(maxsize=65536)
def kernel_name(event_name):
    """The ``name=`` a Pallas kernel was given, which XLA keeps as the
    name of its custom call (``%flash_attention_fwd.12 = ... custom-call(``
    -> ``flash_attention_fwd``); None for any other operation."""
    if not reduce_xplane.is_kernel(event_name):
        return None
    return re.sub(r"[._]\d+$", "", HLO.match(event_name).group("name"))


@functools.lru_cache(maxsize=65536)
def scope_of(op_name):
    """The innermost of the program's named scopes on an ``op_name`` path
    (``jit(train_step)/loss_and_grad/transpose(loss_and_grad)/
    jvp(flash_attention)/flash_attention_bwd_fused/pallas_call:`` ->
    ``flash_attention``); None outside all of them."""
    best, at = None, -1
    for scope in SCOPES:
        for m in re.finditer(r"(?:^|[/(])%s(?=[)/]|$)" % scope, op_name):
            if m.start() > at:
                best, at = scope, m.start()
    return best


def fusion_scope(own, root=()):
    """The scope an operation counts under: its own ``op_name``'s, and for
    a fusion the one scope that the elements of its root name, where they
    name one.  XLA:TPU gives a matmul's output fusion the matmul's
    ``op_name`` and fuses the AdamW update of a matrix weight into that
    weight's gradient matmul: such a fusion writes the new weight and
    moments, and counts under ``optimizer_update``, matmul and all.  Root
    elements the compiler added (a ``convert`` without ``op_name``) name
    nothing."""
    named = {scope_of(op_name) for op_name in root} - {None}
    return named.pop() if len(named) == 1 else scope_of(own)


def root_op_names(module):
    """``{fusion instruction's name: [op_name of each element of the root
    of its computation]}`` of one ``HloModuleProto``."""
    by_id = {i.id: i for c in module.computations for i in c.instructions}
    roots = {c.id: by_id[c.root_id] for c in module.computations}
    out = {}
    for i in by_id.values():
        if i.opcode == "fusion":
            root = roots[i.called_computation_ids[0]]
            parts = ([by_id[o] for o in root.operand_ids]
                     if root.opcode == "tuple" else [root])
            out[i.name] = [part.metadata.op_name for part in parts]
    return out


def op_names(path):
    """``({op event name: its op_name}, {fusion's event name: [op_name of
    each element of its root]})`` of the device planes of an
    ``.xplane.pb``.  An ``XLA Ops`` event is named by its HLO instruction
    without ``metadata={...}``, and `jax.profiler.ProfileData` shows an
    event's own stats only (``device_offset_ps``, ``device_duration_ps``):
    the instruction's ``op_name`` is the stat ``tf_op`` of the event's
    *metadata* (beside ``program_id``, ``hlo_category``, ``source``; TPU
    v5e, jax 0.9.0), and what a fusion computes is in its program's
    ``Hlo Proto``, a stat of the ``/host:metadata`` plane's event metadata
    whose key is the program's id.  jax ships neither message type for
    Python; tensorflow, which the image holds, ships both."""
    from tensorflow.compiler.xla.service import hlo_pb2
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())

    def stats_of(plane, metadata):
        names = plane.stat_metadata
        return {names[s.metadata_id].name: s for s in metadata.stats}

    roots = {}      # program id -> `root_op_names` of its module
    for plane in space.planes:
        if plane.name == METADATA_PLANE:
            for program, metadata in plane.event_metadata.items():
                proto = stats_of(plane, metadata).get(HLO_PROTO_STAT)
                if proto is not None:
                    roots[program % 2 ** 64] = root_op_names(
                        hlo_pb2.HloProto.FromString(
                            proto.bytes_value).hlo_module)
    paths, root_paths = {}, {}
    for plane in space.planes:
        if not reduce_xplane.DEVICE_PLANE.match(plane.name):
            continue
        for metadata in plane.event_metadata.values():
            found = stats_of(plane, metadata)
            if OP_NAME_STAT not in found:
                continue
            own = found[OP_NAME_STAT]
            paths[metadata.name] = (
                own.str_value or plane.stat_metadata[own.ref_value].name)
            m = HLO.match(metadata.name)
            if m and PROGRAM_STAT in found:
                root = roots.get(found[PROGRAM_STAT].uint64_value, {}).get(
                    m.group("name"))
                if root:
                    root_paths[metadata.name] = root
    return paths, root_paths


def idle_by_span(device_events, spans):
    """Seconds (of the events' own unit) the device ran nothing, between
    its first and last operation, under the innermost of the driving
    threads' spans that covers each instant."""
    if not device_events:
        return {}
    lo = min(s for _, s, _ in device_events)
    hi = max(e for _, _, e in device_events)
    busy = union((s, e) for _, s, e in device_events)
    drivers = [(sp.name, sp.start, sp.end) for sp in spans
               if sp.name.startswith(DRIVER_PREFIXES)
               and sp.end > lo and sp.start < hi]
    return attribute(gaps(busy, lo, hi), drivers, UNATTRIBUTED)


def attributed_share(idle):
    """Percent of the idle time that lies under some span other than
    `IDLE_WAIT`: idle the host's own work explains.  None without idle."""
    whole = sum(idle.values())
    if not whole:
        return None
    named = sum(t for name, t in idle.items()
                if name not in (UNATTRIBUTED, IDLE_WAIT))
    return 100.0 * named / whole


def executions(modules):
    """``{program name: [duration of each execution]}`` from the
    ``(event name, start, end)`` of an ``XLA Modules`` line."""
    out = {}
    for name, s, e in modules:
        out.setdefault(module_name(name), []).append(e - s)
    return out


def grouped(times, key):
    """``{event name: time}`` summed under ``key(event name)`` (None: not
    counted)."""
    out = {}
    for name, t in times.items():
        k = key(name)
        if k is not None:
            out[k] = out.get(k, 0) + t
    return out


def load(path):
    """``{"spans": [Span], "modules": {ordinal: [(name, start_ns,
    end_ns)]}, "ops": {ordinal: [...]}, "paths": ..., "roots": ...}``
    from one ``.xplane.pb``, the last two as `op_names` gives them (empty
    where no device operation was traced)."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    spans, modules, ops = [], {}, {}
    for plane in data.planes:
        m = reduce_xplane.DEVICE_PLANE.match(plane.name)
        for i, line in enumerate(plane.lines):
            if not m:
                thread = "%s/%d" % (plane.name, i)
                spans.extend(
                    Span(e.name, e.start_ns, e.start_ns + e.duration_ns,
                         thread, dict(e.stats))
                    for e in line.events
                    if e.name.startswith(SPAN_PREFIXES))
            elif line.name in (MODULES_LINE, reduce_xplane.OPS_LINE):
                into = modules if line.name == MODULES_LINE else ops
                into.setdefault(int(m.group(2)), []).extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events)
    paths, roots = op_names(path) if ops else ({}, {})
    return {"spans": spans, "modules": modules, "ops": ops,
            "paths": paths, "roots": roots}


def summarize(trace):
    """The numbers the readers use, times in seconds, means over the
    devices traced: the window and the idle time in it by span, each
    span's count and seconds, each program's executions, device self
    time by kernel and by scope, and the collectives' seconds in flight
    and exposed (`collectives.seconds`; empty on one chip)."""
    n = len(trace["ops"])
    idle, kernels, scopes, flying, window = {}, {}, {}, {}, 0.0
    paths, roots = trace["paths"], trace.get("roots", {})

    def scope(event_name):
        return fusion_scope(paths.get(event_name, ""),
                            roots.get(event_name, ()))

    for events in trace["ops"].values():
        window += (max(e for _, _, e in events)
                   - min(s for _, s, _ in events)) / 1e9 / n
        for name, t in idle_by_span(events, trace["spans"]).items():
            idle[name] = idle.get(name, 0) + t / 1e9 / n
        own = self_times(events)    # a loop shares the line with its body
        for name, t in grouped(own, kernel_name).items():
            kernels[name] = kernels.get(name, 0) + t / 1e9 / n
        for name, t in grouped(own, scope).items():
            scopes[name] = scopes.get(name, 0) + t / 1e9 / n
        for name, t in collectives.seconds(events).items():
            flying[name] = flying.get(name, 0) + t / 1e9 / n
    by_module = {}
    for events in trace["modules"].values():
        for name, ns in executions(events).items():
            by_module.setdefault(name, []).extend(t / 1e6 for t in ns)
    by_span = {}
    for sp in trace["spans"]:
        count, seconds = by_span.get(sp.name, (0, 0.0))
        by_span[sp.name] = (count + 1, seconds + (sp.end - sp.start) / 1e9)
    return {"devices": n, "window_s": window, "idle_s": idle,
            "span_seconds": by_span, "module_ms": by_module,
            "kernel_s": kernels, "scope_s": scopes,
            "collective_s": flying}


@functools.lru_cache(maxsize=1)
def _newest_session():
    """The session this process wrote last, summarized once, with the
    earlier line ``[program-spans]``."""
    found = glob.glob(os.path.join(
        ROOT, ".chipbench_trace", "*", "plugins", "profile", "*",
        "*.xplane.pb"))
    if not found:
        return None
    red = summarize(load(max(found, key=os.path.getmtime)))
    whole = sum(red["idle_s"].values())
    common.say(
        "program-spans", window_s=red["window_s"], idle_s=whole,
        idle_s_by_span=dict(sorted(red["idle_s"].items(),
                                   key=lambda kv: -kv[1])),
        unattributed_share_percent=(
            100.0 * red["idle_s"].get(UNATTRIBUTED, 0.0) / whole
            if whole else None),
        spans_count_and_seconds={k: list(v) for k, v in
                                 sorted(red["span_seconds"].items())},
        programs_count_and_median_ms={
            k: [len(v), sorted(v)[len(v) // 2]]
            for k, v in sorted(red["module_ms"].items())},
        kernel_s=red["kernel_s"], scope_s=red["scope_s"],
        collective_s=red["collective_s"])
    return red


def session(obs):
    """The summary of this run's traced stretch; None for an untraced run
    and for one whose trace holds no device operation (off the chip)."""
    if not obs.get("trace"):
        return None
    return _newest_session()


def module_ms_p50(obs, prefix):
    """Median device milliseconds of one execution of the programs whose
    name begins with ``prefix``."""
    red = session(obs)
    if not red:
        return None
    ms = [x for name, values in red["module_ms"].items()
          if name.startswith(prefix) for x in values]
    return stats.percentile(ms, 50) if ms else None


def ms_per_step(obs, group, prefix):
    """Device milliseconds a train step spends in the kernels
    (``group="kernel_s"``), scopes (``"scope_s"``) or collectives
    (``"collective_s"``) whose name begins with ``prefix``: their share
    of the traced stretch times the median step time, as
    `common.kernel_ms_per_step` reckons it."""
    red = session(obs)
    if not red or not red["window_s"]:
        return None
    seconds = [t for name, t in red[group].items()
               if name.startswith(prefix)]
    if not seconds:
        return None
    return (sum(seconds) / red["window_s"]
            * stats.percentile(obs["samples"]["step_ms"], 50))

