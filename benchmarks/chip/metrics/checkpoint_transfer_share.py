"""Per cent of the traced window in which the chip sat idle while the host
was in the engine's ``checkpoint.transfer`` span: the ``jax.device_get``
of the whole cache in ``serving/cache.py::offload_slots``.

Counted instant by instant from the trace's events (``ctx["events"]``):
the idle time (no operation on the device, as ``trace.reduce`` has it)
during which the span is the innermost of the program's own spans
(``program_spans.json``, the shortest one covering the instant).  Not by
each gap's middle, as ``breakdown`` attributes whole gaps: a checkpoint
is one gap from the transfer to the end of the pack, and its middle
would give all of it to one half.  Nothing where the trace holds no such
span or no device ran anything."""
import json
import os

from chipbench.trace import CONTAINERS, gaps, op_key, union

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPAN = "checkpoint.transfer"


def _cut(intervals, holes):
    """``intervals`` minus the union of ``holes`` (both sorted lists)."""
    out = []
    for s, e in intervals:
        for hs, he in holes:
            if he <= s or hs >= e:
                continue
            if hs > s:
                out.append((s, hs))
            s = max(s, he)
            if s >= e:
                break
        if s < e:
            out.append((s, e))
    return out


def _overlap(a, b):
    """Total length of the overlap of two sorted disjoint interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_share(ctx, span):
    """Idle seconds whose innermost program span is ``span``, over the
    window, in per cent (busy devices averaged as ``trace.reduce``
    averages them), or None without events, a device that ran anything
    in the window, or a ``span`` in it."""
    events = ctx.get("events")
    if not events:
        return None
    with open(os.path.join(HERE, "program_spans.json")) as f:
        names = set(json.load(f)["spans"])
    host = events["host"]
    lo, hi = next(((s, e) for n, s, e in host if n == "window"), (0, 0))
    spans = [(n, s, e) for n, s, e in host
             if n in names and e > lo and s < hi]
    mine = [(s, e) for n, s, e in spans if n == span]
    if hi <= lo or not mine:
        return None
    # where the span is the innermost: its own time, less that of any
    # shorter program span overlapping it
    owned = []
    for s, e in mine:
        inner = union((max(s2, s), min(e2, e)) for _, s2, e2 in spans
                      if e2 - s2 < e - s and s2 < e and e2 > s)
        owned += _cut([(max(s, lo), min(e, hi))], inner)
    owned = union(owned)
    idle, used = 0.0, 0
    for dev in events["devices"].values():
        busy = [(max(s, lo), min(e, hi)) for name, s, e in dev["ops"]
                if e > lo and s < hi
                and op_key(name).rsplit(" ", 1)[-1] not in CONTAINERS]
        if not busy:
            continue
        used += 1
        idle += _overlap(gaps(union(busy), lo, hi), owned)
    if not used:
        return None
    return 100.0 * idle / used / (hi - lo)


def read(ctx):
    return idle_share(ctx, SPAN)
