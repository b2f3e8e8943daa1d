"""Device time of one program's executions by the named scopes of a
block-diffusion step (`moe_experts`, `moe_router`, `block_mask_attention`,
`sampling`), for the readers this PR brought.  `program_trace.summarize`
counts the train step's scopes over the whole stretch; here an operation
counts only where it ran inside an execution of the named program (the
prefills hold the same scopes), by the ``XLA Modules`` events that cover
it.  A fusion counts under the scope of its root, as there.  The session
is loaded once more for this (some seconds, after the window)."""

import bisect
import functools
import glob
import os
import re

from chipbench import common, program_trace
from chipbench.reduce_xplane import self_times

SCOPES = ("moe_experts", "moe_router", "block_mask_attention", "sampling")


@functools.lru_cache(maxsize=65536)
def scope_of(op_name):
    """The innermost of `SCOPES` on an ``op_name`` path; None outside."""
    best, at = None, -1
    for scope in SCOPES:
        for m in re.finditer(r"(?:^|[/(])%s(?=[)/]|$)" % scope, op_name):
            if m.start() > at:
                best, at = scope, m.start()
    return best


def fusion_scope(own, root=()):
    named = {scope_of(op_name) for op_name in root} - {None}
    return named.pop() if len(named) == 1 else scope_of(own)


def inside(events, spans):
    """The ``(name, start, end)`` events that begin inside one of the
    disjoint ``(start, end)`` spans."""
    spans = sorted(spans)
    starts = [s for s, _ in spans]
    out = []
    for ev in events:
        i = bisect.bisect_right(starts, ev[1]) - 1
        if i >= 0 and ev[1] < spans[i][1]:
            out.append(ev)
    return out


def by_scope(trace, program):
    """``({scope: self-time ns inside executions of program}, executions,
    [each execution's ns])`` summed over the devices traced."""
    scopes, runs = {}, []
    paths, roots = trace["paths"], trace.get("roots", {})
    for ordinal, events in trace["ops"].items():
        spans = [(s, e) for name, s, e in trace["modules"].get(ordinal, [])
                 if program_trace.module_name(name) == program]
        runs += [e - s for s, e in spans]
        for name, t in self_times(inside(events, spans)).items():
            scope = fusion_scope(paths.get(name, ""), roots.get(name, ()))
            if scope is not None:
                scopes[scope] = scopes.get(scope, 0) + t
    return scopes, len(runs), runs


@functools.lru_cache(maxsize=4)
def _newest(program):
    found = glob.glob(os.path.join(
        program_trace.ROOT, ".chipbench_trace", "*", "plugins", "profile",
        "*", "*.xplane.pb"))
    if not found:
        return None
    scopes, n, runs = by_scope(
        program_trace.load(max(found, key=os.path.getmtime)), program)
    common.say("program-scopes", program=program, executions=n,
               ms_per_execution={k: v / 1e6 / n for k, v in scopes.items()}
               if n else None)
    return scopes, n


def scope_ms_per_execution(obs, scope, program):
    """Device milliseconds one execution of ``program`` spends in the
    operations under ``scope``; None for an untraced run and where the
    trace holds no such program or scope."""
    if not obs.get("trace"):
        return None
    found = _newest(program)
    if not found or not found[1] or not found[0].get(scope):
        return None
    return found[0][scope] / 1e6 / found[1]
