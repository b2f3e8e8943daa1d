"""From a profiler trace to device busy time, the operations that took it,
and the idle gaps labelled by what the host was doing.

Everything below `load` works on plain lists of ``(name, start, end)``
in any one unit of time, so the tests drive it with hand-made intervals.
`load` reads the ``.xplane.pb`` a ``jax.profiler`` session wrote, with
nothing but JAX (``jax.profiler.ProfileData``)."""

import functools
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"            # one event per executed HLO op
# host spans that name an idle gap: the benchmark's own TraceAnnotations
# and the program's spans on the threads that hand work to the device
# (an ``http.`` span lives as long as its request, covers every gap and
# explains none)
HOST_PREFIXES = ("bench.", "generation.", "train.", "io.")
# an op's event is named by its whole HLO instruction:
#   %fusion.406 = (bf16[48,512,3072]{2,1,0:T(8,128)(2,1)}, ...) fusion(...
HLO = re.compile(r"^%?(?P<name>\S+) = (?P<type>.*?) (?P<op>[a-z][a-z0-9_-]*)\(")


def load(trace_dir):
    """``{"devices": {ordinal: [(name, start_ns, end_ns)]}, "host":
    [(name, start_ns, end_ns)], "layout": {plane: {line: n_events}}}``
    from the newest session under ``trace_dir``."""
    import jax

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    data = jax.profiler.ProfileData.from_file(paths[-1])
    devices, host, layout = {}, [], {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        lines = layout.setdefault(plane.name, {})
        for line in plane.lines:
            events = list(line.events)
            lines[line.name] = len(events)
            if m and line.name == OPS_LINE:
                devices.setdefault(int(m.group(2)), []).extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in events)
            elif not m:
                host.extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in events if e.name.startswith(HOST_PREFIXES))
    return {"devices": devices, "host": host, "layout": layout}


@functools.lru_cache(maxsize=65536)     # an op runs many times
def describe(event_name):
    """``(group, opcode)`` of a device op's event name.  The group stands
    for every instance of the same operation on the same shapes (the
    twelve layers' copies of one fusion): the instruction's name without
    its number, its opcode and its result type without layouts."""
    m = HLO.match(event_name)
    if not m:
        return event_name[:120], ""
    stem = re.sub(r"[._]\d+$", "", m.group("name"))
    shape = re.sub(r"\{[^}]*\}", "", m.group("type"))
    return ("%s [%s] %s" % (stem, m.group("op"), shape))[:120], m.group("op")


@functools.lru_cache(maxsize=65536)     # an op runs many times
def is_kernel(event_name):
    """A Pallas kernel: a ``custom-call`` whose target, which the event's
    name carries with the instruction's other attributes, is Mosaic's."""
    m = HLO.match(event_name)
    return (bool(m) and m.group("op") == "custom-call"
            and 'custom_call_target="tpu_custom_call"' in event_name)


def union(intervals):
    """Merge ``(start, end)`` pairs into disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy, lo, hi):
    """The complement of disjoint sorted ``busy`` inside ``[lo, hi]``."""
    out, at = [], lo
    for s, e in clip(busy, lo, hi):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def self_times(events):
    """Per-name time on one device line, each instant given to the
    innermost event that covers it (a ``while`` or ``conditional`` shares
    the line with the ops it runs)."""
    out, stack = {}, []         # stack: [name, end, start-of-open-stretch]

    def account(until):
        if stack and until > stack[-1][2]:
            name = stack[-1][0]
            out[name] = out.get(name, 0) + until - stack[-1][2]

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            account(stack[-1][1])
            end = stack.pop()[1]
            if stack:
                stack[-1][2] = end
        account(s)
        stack.append([name, e, s])
    while stack:
        account(stack[-1][1])
        end = stack.pop()[1]
        if stack:
            stack[-1][2] = max(stack[-1][2], end)
    return out


def attribute(gap_list, spans, default="unattributed"):
    """Seconds of ``gap_list`` under each host span's name.  Where spans
    nest, the shortest one covering an instant names it."""
    out = {}
    for gs, ge in gap_list:
        near = [sp for sp in spans if sp[1] < ge and sp[2] > gs]
        cuts = sorted({gs, ge} | {t for _, s, e in near for t in (s, e)
                                  if gs < t < ge})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2.0
            covering = [(e - s, name) for name, s, e in near
                        if s <= mid < e]
            name = min(covering)[1] if covering else default
            out[name] = out.get(name, 0) + b - a
    return out


def reduce(trace, top=10, default="unattributed"):
    """The numbers the result line and the per-layer readers use, from
    what `load` returned.  Times in seconds.  The window of a device is
    from its first op's start to its last op's end, so it is whole only
    where the traced stretch starts and ends in steady work."""
    per_device, ops, idle_by, kernels = [], {}, {}, {}
    for ordinal, events in sorted(trace["devices"].items()):
        if not events:
            continue
        lo = min(s for _, s, _ in events)
        hi = max(e for _, _, e in events)
        busy = union((s, e) for _, s, e in events)
        per_device.append({"device": ordinal, "busy_s": total(busy) / 1e9,
                           "window_s": (hi - lo) / 1e9})
        for name, t in self_times(events).items():
            group = describe(name)[0]
            ops[group] = ops.get(group, 0) + t / 1e9
            if is_kernel(name):
                kernels[group] = kernels.get(group, 0) + t / 1e9
        for name, t in attribute(gaps(busy, lo, hi), trace["host"],
                                 default).items():
            idle_by[name] = idle_by.get(name, 0) + t / 1e9
    n = len(per_device)
    if not n:
        return None
    by_time = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {
        "devices": n,
        "busy_s": sum(d["busy_s"] for d in per_device) / n,
        "window_s": sum(d["window_s"] for d in per_device) / n,
        "per_device": per_device,
        "device_ops": [[k, v / n] for k, v in by_time(ops)],
        "idle_gaps": [[k, v / n] for k, v in by_time(idle_by)],
        "op_seconds": {k: v / n for k, v in ops.items()},
        "kernel_seconds": {k: v / n for k, v in kernels.items()},
    }
