"""Collective-traffic cost model + compiled-HLO collective extraction.

The communication dimension of the analysis substrate: `perf.py` prices
compute against peak FLOP/s and HBM bandwidth; this module prices the
COLLECTIVES a multi-chip program runs against ICI bandwidth, and — the
part that keeps the model honest — extracts the collectives a compiled
executable ACTUALLY contains from its optimized HLO text, so the static
estimate can be validated the same way PERF.md round 8 anchored the
FLOP model to ``cost_analysis()``.

Ring-collective wire model (the standard N-chip ring bounds; GSPMD on a
torus does at least this well, so estimates are a lower bound the same
way the byte model upper-bounds fused HBM traffic):

  * all-reduce       moves ``2*(N-1)/N``  x payload per chip
    (reduce-scatter phase + all-gather phase);
  * reduce-scatter   moves ``(N-1)/N``    x payload per chip;
  * all-gather       moves ``(N-1)/N``    x payload per chip;
  * all-to-all       moves ``(N-1)/N``    x payload per chip;
  * collective-permute / broadcast move the payload once.

``payload`` is always the FULL (unsharded) tensor size; the HLO side
converts each instruction's RESULT buffer to a full payload first
(a reduce-scatter's result is the 1/N shard, an all-gather's result is
already the full tensor).

all-to-all convention: ``payload`` is the PER-CHIP buffer (send and
receive sizes are equal, so "full" here means one chip's local
``[E, cap, d]``-style buffer, of which ``(N-1)/N`` crosses the wire —
the ``1/N`` destined for the chip itself stays home).  This matches
the HLO side bit-for-bit: a (tiled or tuple-form) ``all-to-all``
instruction's result buffers sum to exactly that per-chip buffer, so
`tp_serving.moe.ep_moe_comm_bytes` pins compiled wire bytes exactly
(see ``tests/test_tp_serving.py``).
"""

from __future__ import annotations

import re

__all__ = [
    "COLLECTIVE_KINDS",
    "collective_time_s",
    "collective_wire_bytes",
    "hlo_collectives",
    "hlo_collective_stats",
]

COLLECTIVE_KINDS = (
    "all-reduce",
    "reduce-scatter",
    "all-gather",
    "all-to-all",
    "collective-permute",
    "broadcast",
)

# per-chip wire traffic as a multiple of (N-1)/N x full payload
_RING_FACTORS = {
    "all-reduce": 2.0,
    "reduce-scatter": 1.0,
    "all-gather": 1.0,
    "all-to-all": 1.0,
}


def collective_wire_bytes(kind, nbytes, n, payload="full"):
    """Per-chip wire bytes of one collective over ``n`` participants.

    ``payload="full"``: ``nbytes`` is the full (unsharded) tensor;
    ``payload="shard"``: ``nbytes`` is the 1/n shard (HLO reduce-scatter
    results) and is scaled up first.  n<=1 is free."""
    n = int(n)
    if n <= 1:
        return 0.0
    nbytes = float(nbytes)
    if payload == "shard":
        nbytes *= n
    factor = _RING_FACTORS.get(kind)
    if factor is None:   # permute / broadcast: the payload moves once
        return nbytes
    return factor * (n - 1) / n * nbytes


def collective_time_s(kind, nbytes, n, ici_bw, payload="full"):
    """Ring-bound seconds for one collective at ``ici_bw`` bytes/s."""
    if not ici_bw:
        return 0.0
    return collective_wire_bytes(kind, nbytes, n, payload) / float(ici_bw)


# ---------------------------------------------------------------------------
# compiled-HLO extraction
# ---------------------------------------------------------------------------

_HLO_ITEMSIZE = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

# one typed buffer inside a result type string: "f32[8,128]{1,0}"
_SHAPE_RE = re.compile(r"\b([a-z]+\d*)\[([0-9,]*)\]")

# "%name = <result-type> <opcode>(" — opcode restricted to collectives.
# Async pairs: the "-start" result is a TUPLE carrying operand AND
# result buffers (plus scratch), so counting it would overbill; the
# "-done" result is exactly the collective's result buffer — each async
# pair is therefore counted at its "-done" and the "-start" skipped.
# the result-type class must admit TPU layout/memory-space annotations
# — tiled layouts "{1,0:T(8,128)}" and space markers "S(1)" carry
# UPPERCASE letters the CPU dump never shows, and a tuple of more than
# five buffers carries "/*index=5*/" markers
_COLL_RE = re.compile(
    r"=\s+(\(?[a-zA-Z0-9\[\]{},:\s/()*=]*?\)?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(-start|-done)?\(")


def _shape_bytes(type_str):
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        size = _HLO_ITEMSIZE.get(dtype)
        if size is None:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * size
    return total


def hlo_collectives(hlo_text):
    """Every collective instruction in an optimized-HLO dump.

    Returns [{kind, result_bytes, computation, entry, line}] — one row
    per sync instruction or async start/done PAIR (variadic/tuple
    results summed; async pairs are billed at the "-done", whose result
    type is the collective's actual result buffer — the "-start" tuple
    interleaves operand + result + scratch and would overbill), with
    the enclosing computation name and whether it is the ENTRY
    computation (a collective inside a while-loop body runs once per
    iteration, which is exactly what the accumulate-once tests assert
    never happens to gradient sync)."""
    out = []
    comp, entry = None, False
    for raw in (hlo_text or "").splitlines():
        if raw and not raw[0].isspace() and "{" in raw:
            comp = raw.split("{")[0].strip().rstrip(" ")
            entry = raw.lstrip().startswith("ENTRY")
            continue
        m = _COLL_RE.search(raw)
        if m is None:
            continue
        if m.group(3) == "-start":
            continue
        kind = m.group(2)
        out.append({
            "kind": kind,
            "result_bytes": _shape_bytes(m.group(1)),
            "computation": comp,
            "entry": bool(entry),
            "line": raw.strip(),
        })
    return out


def hlo_collective_stats(hlo_text, n):
    """Aggregate `hlo_collectives` into per-kind counts + bytes.

    Returns ``{kind: {count, result_bytes, wire_bytes, entry_count}}``
    plus ``wire_bytes_total``; ``wire_bytes`` converts each result
    buffer through the ring factors with ``n`` participants (a
    reduce-scatter result is the shard; everything else is the full
    payload)."""
    rows = hlo_collectives(hlo_text)
    stats = {}
    for r in rows:
        kind = r["kind"]
        g = stats.setdefault(kind, {
            "count": 0, "result_bytes": 0.0, "wire_bytes": 0.0,
            "entry_count": 0})
        g["count"] += 1
        g["result_bytes"] += float(r["result_bytes"])
        g["wire_bytes"] += collective_wire_bytes(
            kind, r["result_bytes"], n,
            payload="shard" if kind == "reduce-scatter" else "full")
        if r["entry"]:
            g["entry_count"] += 1
    stats["wire_bytes_total"] = sum(
        g["wire_bytes"] for k, g in stats.items() if isinstance(g, dict))
    return stats
