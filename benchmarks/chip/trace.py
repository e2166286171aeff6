"""Reduction of a profiler trace (``.xplane.pb``) to device busy time, idle
gaps attributed to host spans, and device time per op and per program.

``load`` turns the trace into plain event lists; ``reduce`` works on those
lists alone, so a small recorded trace, saved as JSON, checks it.

* Device planes are ``/device:TPU:<n>``.  Their ``XLA Ops`` line holds one
  event per operation run on the chip and their ``XLA Modules`` line one
  event per program run.
* The window is the host span named ``window``: everything is clipped to
  it.  Busy time is the union of the operation intervals; idle gaps are
  the rest of the window.  Busy time is averaged over the device planes
  that ran anything.
* Each idle gap is attributed to what the host was doing at its middle:
  the shortest host span, among the ones named, that covers that instant;
  ``host_other`` where none does.
* Each operation and program also gets its number of runs in the window,
  an event cut by the window's edge counting by the share of it inside,
  so that work counted per run stays in step with the time.
* An operation is named ``<program>/<op>``: the program (``XLA Modules``
  event, hash dropped) it ran in, then its HLO instruction name, result
  shape without layouts, and opcode, as ``op_key`` shortens the HLO text
  the trace gives.  Loops and calls (``while``, ``conditional``, ``call``)
  hold other operations and are left out of busy time and op totals.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
CONTAINERS = ("while", "conditional", "call")


def op_key(name: str) -> str:
    """``%fusion.3 = bf16[4,256]{1,0:T(8,128)} fusion(...), kind=...`` ->
    ``%fusion.3 = bf16[4,256] fusion``.  Names that are not HLO text are
    kept, cut to 120 characters."""
    m = re.match(r"^(%\S+) = (.*)$", name, re.S)
    if not m:
        return name[:120]
    inst, rest = m.groups()
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        shape, tail = rest[:i + 1], rest[i + 1:]
    else:
        shape, _, tail = rest.partition(" ")
        tail = " " + tail
    kind = re.match(r"\s*([A-Za-z][\w-]*)", tail)
    shape = re.sub(r"\{[^}]*\}", "", shape)
    return f"{inst} = {shape} {kind.group(1) if kind else ''}".rstrip()


def result_shapes(key: str) -> List[List[int]]:
    """The dimensions of each array an operation returns, from its
    ``op_key``: ``%x = (bf16[4,80], f32[4]) custom-call`` ->
    ``[[4, 80], [4]]``."""
    head = key[max(key.find("%"), 0):].split(" = ", 1)[-1]
    return [[int(d) for d in dims.split(",") if d]
            for dims in re.findall(r"[a-z]\w*\[([\d,]*)\]", head)]


def program_key(name: str) -> str:
    """``jit_decode_n(1739...)`` -> ``jit_decode_n``."""
    return re.sub(r"\(\d+\)$", "", name)


def load(path: str) -> Dict[str, object]:
    """Events of the trace under ``path`` (a file, or a directory holding
    one ``.xplane.pb``): ``{"host": [[name, start_ns, end_ns], ...],
    "devices": {plane: {"ops": [...], "modules": [...]}}}``."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if len(found) != 1:
            raise FileNotFoundError(f"{path}: {len(found)} .xplane.pb files")
        path = found[0]
    pd = ProfileData.from_file(path)
    host: List[list] = []
    devices: Dict[str, Dict[str, list]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host += [[e.name, e.start_ns, e.end_ns] for e in line.events]
        elif DEVICE_PLANE.match(plane.name):
            lines = {line.name: [[e.name, e.start_ns, e.end_ns]
                                 for e in line.events] for line in plane.lines}
            if lines.get("XLA Ops"):
                devices[plane.name] = {"ops": lines["XLA Ops"],
                                       "modules": lines.get("XLA Modules", [])}
    return {"host": host, "devices": devices}


def _clip(events: Iterable[Sequence], lo: float, hi: float):
    """(name, start, end, share of the event inside) of the events that
    overlap [lo, hi], cut to it."""
    for name, s0, e0 in events:
        s, e = max(s0, lo), min(e0, hi)
        if e > s:
            yield name, s, e, (e - s) / (e0 - s0)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


class _Owners:
    """What the host was doing at any instant: the shortest named span
    covering it, from a timeline cut at every span's start and end."""

    def __init__(self, spans: List[Tuple[str, float, float]]):
        cuts = sorted({t for _, s, e in spans for t in (s, e)})
        spans = sorted(spans, key=lambda x: x[1])
        self.starts, self.names = [], []
        active: List[Tuple[str, float, float]] = []
        j = 0
        for a, b in zip(cuts, cuts[1:]):
            while j < len(spans) and spans[j][1] <= a:
                active.append(spans[j])
                j += 1
            active = [x for x in active if x[2] > a]
            inner = min(active, key=lambda x: x[2] - x[1], default=None)
            self.starts.append(a)
            self.names.append(inner[0] if inner and inner[2] >= b
                              else "host_other")
        self.end = cuts[-1] if cuts else 0.0

    def at(self, t: float) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        if i < 0 or t >= self.end:
            return "host_other"
        return self.names[i]


def reduce(events: Dict[str, object], span_names: Sequence[str],
           window: str = "window") -> Dict[str, object]:
    """Busy and window seconds, idle seconds by host span, device seconds
    and runs per operation name and per program name."""
    host = events["host"]
    wins = [(s, e) for name, s, e in host if name == window]
    if not wins:
        raise ValueError(f"no host span named {window!r} in the trace")
    lo, hi = wins[0]
    owners = _Owners([(n, s, e) for n, s, e in host
                      if n in span_names])
    busy_s, ops, modules, ops_n, modules_n = [], {}, {}, {}, {}
    idle_by: Dict[str, float] = {}
    used = 0
    for dev in events["devices"].values():
        progs = sorted((s, e, program_key(n)) for n, s, e in dev["modules"])
        starts = [p[0] for p in progs]
        leaves = []
        for name, s, e, share in _clip(dev["ops"], lo, hi):
            key = op_key(name)
            if key.rsplit(" ", 1)[-1] in CONTAINERS:
                continue
            i = bisect.bisect_right(starts, s) - 1
            prog = progs[i][2] if i >= 0 and s < progs[i][1] else "none"
            k = f"{prog}/{key}"
            ops[k] = ops.get(k, 0.0) + (e - s) / 1e9
            ops_n[k] = ops_n.get(k, 0.0) + share
            leaves.append((s, e))
        if not leaves:
            continue
        used += 1
        for name, s, e, share in _clip(dev["modules"], lo, hi):
            k = program_key(name)
            modules[k] = modules.get(k, 0.0) + (e - s) / 1e9
            modules_n[k] = modules_n.get(k, 0.0) + share
        busy = union(leaves)
        busy_s.append(sum(e - s for s, e in busy) / 1e9)
        for s, e in gaps(busy, lo, hi):
            who = owners.at((s + e) / 2)
            idle_by[who] = idle_by.get(who, 0.0) + (e - s) / 1e9
    n = max(used, 1)
    return {"window_s": (hi - lo) / 1e9,
            "busy_s": sum(busy_s) / n,
            "devices": used,
            "idle_by_span": {k: v / n for k, v in idle_by.items()},
            "ops": ops, "modules": modules,
            "ops_n": ops_n, "modules_n": modules_n}


def device_seconds(summary: Dict[str, object], pattern: str,
                   key: str = "ops") -> float:
    """Device seconds of the operations (or programs) whose name matches
    ``pattern``, per device plane used."""
    rx = re.compile(pattern)
    total = sum(v for k, v in summary[key].items() if rx.search(k))
    return total / max(summary["devices"], 1)


def runs(summary: Dict[str, object], pattern: str,
         key: str = "ops") -> Dict[str, float]:
    """Runs in the window of each operation (or program) whose name
    matches ``pattern``, per device plane used."""
    rx = re.compile(pattern)
    n = max(summary["devices"], 1)
    return {k: v / n for k, v in summary[key + "_n"].items() if rx.search(k)}


def breakdown(summary: Dict[str, object]) -> Dict[str, list]:
    n = max(summary["devices"], 1)
    top = sorted(summary["ops"].items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(summary["idle_by_span"].items(), key=lambda kv: -kv[1])
    return {"device_ops": [[k, v / n] for k, v in top],
            "idle_gaps": [[k, v] for k, v in idle[:10]]}
