"""Reduction of a profiler trace to device busy time, kernel time and the
host's account of the idle gaps.

``load`` reads a ``.xplane.pb`` into a compact dict of events: for each TPU
device plane the operations of its ``XLA Ops`` line, and the harness's own
``bench.*`` host spans.  Everything after that works on the dict.
"""
from __future__ import annotations

from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."


def load(path: Path) -> dict:
    """Events of an xplane file: ``{"devices": {plane: [[name, t0, t1],
    ...]}, "host": [[name, t0, t1], ...]}`` in seconds on the trace's
    clock.  An op's name is its HLO instruction."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices: Dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    t0 = ev.start_ns * 1e-9
                    ops.append([ev.name, t0, t0 + ev.duration_ns * 1e-9])
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        t0 = ev.start_ns * 1e-9
                        host.append([ev.name, t0, t0 + ev.duration_ns * 1e-9])
    return {"devices": devices, "host": host}


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def window(events: dict) -> Optional[Interval]:
    """The traced window: from the first to the last harness span."""
    host = events["host"]
    if not host:
        return None
    return min(h[1] for h in host), max(h[2] for h in host)


def busy_seconds(events: dict, win: Interval) -> float:
    """Seconds in which some operation ran, averaged over the devices."""
    per = [sum(b - a for a, b in clip(union([(o[1], o[2]) for o in ops]), *win))
           for ops in events["devices"].values()]
    return sum(per) / len(per) if per else 0.0


def idle_gaps(events: dict, win: Interval) -> List[Interval]:
    """Intervals of the window in which the first device ran nothing."""
    ops = next(iter(events["devices"].values()), [])
    busy = clip(union([(o[1], o[2]) for o in ops]), *win)
    gaps, at = [], win[0]
    for a, b in busy:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if at < win[1]:
        gaps.append((at, win[1]))
    return gaps


def kernel_seconds(events: dict, pattern: str, win: Interval) -> float:
    """Device seconds of the operations whose name starts with ``pattern``,
    averaged over the devices."""
    per = []
    for ops in events["devices"].values():
        hits = [(o[1], o[2]) for o in ops if o[0].startswith(pattern)]
        per.append(sum(b - a for a, b in clip(hits, *win)))
    return sum(per) / len(per) if per else 0.0


def op_kind(name: str) -> str:
    """``%fusion.191 = s16[2560,8960]{1,0:...} fusion(...)`` -> ``fusion
    s16[2560,8960]``: the instruction without its number, and its result."""
    head, _, rest = name.partition(" = ")
    kind = head.lstrip("%").rsplit(".", 1)[0]
    result = rest.split("{", 1)[0].split(" ", 1)[0] if rest else ""
    return f"{kind} {result}".strip()[:96]


def top_ops(events: dict, win: Interval, n: int = 10) -> List[list]:
    """The device operations that took most time on the first device, by
    kind and result shape.  A loop's op spans the ops of its body, so both
    are listed."""
    ops = next(iter(events["devices"].values()), [])
    total: Dict[str, float] = defaultdict(float)
    for name, a, b in ops:
        for x, y in clip([(a, b)], *win):
            total[op_kind(name)] += y - x
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def _innermost(spans: List[list]) -> List[Tuple[float, float, str]]:
    """The timeline cut at every span edge, each piece named by the
    shortest span that covers it (the innermost, for nested spans)."""
    edges = sorted({t for h in spans for t in (h[1], h[2])})
    starts = sorted(spans, key=lambda h: h[1])
    active: list = []
    out, i = [], 0
    for x, y in zip(edges, edges[1:]):
        while i < len(starts) and starts[i][1] <= x:
            active.append(starts[i])
            i += 1
        active = [h for h in active if h[2] > x]
        if active:
            out.append((x, y, min(active, key=lambda h: h[2] - h[1])[0]))
    return out


def attribute(events: dict, gaps: List[Interval], n: int = 10) -> List[list]:
    """Idle seconds by what the host was doing: each part of a gap goes to
    the innermost harness span around it, or to ``untraced host``."""
    total: Dict[str, float] = defaultdict(float)
    pieces = _innermost(events["host"])
    j = 0
    for a, b in gaps:
        covered = 0.0
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            x, y, name = pieces[k]
            part = min(y, b) - max(x, a)
            if part > 0:
                total[name] += part
                covered += part
            k += 1
        if b - a - covered > 0:
            total["untraced host"] += b - a - covered
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]
