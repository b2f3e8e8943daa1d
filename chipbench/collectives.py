"""Collective operations in one device's ``XLA Ops`` events: how long they
were in flight, and how much of that nothing else on the chip covered.

Plain functions on ``(event name, start, end)`` tuples in any one unit of
time, like `reduce_xplane`'s, so the tests drive them with hand-made
intervals.  An event is named by its whole HLO instruction.  A collective
that XLA made asynchronous is two events: ``<op>-start`` and ``<op>-done``,
the second naming the first among its operands, or, as XLA:TPU writes the
ZeRO-2 step's hidden all-gather (v5e, jax 0.9.0; PERF.md, PR 34), two
``fusion`` instructions *named* ``async-collective-start`` and
``async-collective-done``.  Either is in flight from the start of the one
to the end of the other, and what the chip computes in between hides it.
A synchronous one (that step's six all-reduces and thirteen all-gathers)
is in flight while its event lasts."""

import re

from chipbench.reduce_xplane import HLO, describe, gaps, total, union

COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast)(-start|-done)?$")
# XLA:TPU's asynchronous collective fusion, known by its instruction's name
ASYNC_FUSION = re.compile(r"^%?async-collective(-start|-done)(\.\d+)?$")
# an op that only holds others (its body's ops are events of their own)
CONTAINERS = ("while", "conditional", "call")


def phase(event_name):
    """``(collective opcode, "" | "-start" | "-done")``; None for an event
    that is no collective."""
    m = COLLECTIVE.match(describe(event_name)[1])
    if m:
        return m.group(1), m.group(2) or ""
    m = ASYNC_FUSION.match(event_name.split(" = ", 1)[0])
    return ("async-collective", m.group(1)) if m else None


def in_flight(events):
    """The ``(start, end)`` of every collective of one device line: a
    synchronous one's event, an asynchronous one's start event to the end
    of the done event that names it, or of the next done of its kind
    where none does (to its own end where the traced stretch holds no such
    done)."""
    out, open_ = [], {}     # instruction name of a start -> (kind, event)
    for name, s, e in sorted(events, key=lambda ev: ev[1]):
        ph = phase(name)
        if ph is None:
            continue
        if ph[1] == "-start":
            open_[HLO.match(name).group("name").lstrip("%")] = (ph[0], (s, e))
        elif ph[1] == "-done":
            operands = set(re.findall(r"%([\w.\-]+)", name.split(" = ", 1)[1]))
            mine = ([k for k in open_ if k in operands]
                    or [k for k, (kind, _) in open_.items() if kind == ph[0]])
            # a done whose start lies before the stretch is in flight
            # from its own beginning
            out.append((open_.pop(mine[0])[1][0] if mine else s, e))
        else:
            out.append((s, e))
    out.extend(event for _, event in open_.values())
    return out


def seconds(events):
    """``{"in_flight": t, "exposed": t}`` of one device line, in the
    events' unit: the union of the collectives' intervals, and the part of
    it during which no other operation of the chip ran.  Empty for a line
    without a collective (one chip)."""
    flying = union(in_flight(events))
    if not flying:
        return {}
    others = union((s, e) for name, s, e in events
                   if phase(name) is None
                   and describe(name)[1] not in CONTAINERS)
    exposed = sum(total(gaps(others, lo, hi)) for lo, hi in flying)
    return {"in_flight": total(flying), "exposed": exposed}
