"""The program's own records of the requests served in the untraced head of
a traced run's window.

A traced run profiles a slice in the middle of the window, and the profiler
slows the engine while it runs.  The requests whose first token the harness
stamped before the slice began were served by the system that untraced runs
measure, so the readers that use this take only those, from the engine's
per-request ``RequestStats``.  A program that keeps no such records gives
nothing to read.
"""
from __future__ import annotations

from typing import List


def head_stats(ctx, field: str) -> List[object]:
    """``RequestStats`` carrying ``field`` of the requests whose first token
    was stamped before the profiled slice began.  The harness starts the
    profiler between steps, at the stamp of the step before, so a first
    stamp equal to the slice's start was made before the profiler ran."""
    start = ctx["prof"].get("start")
    if start is None:
        return []
    out = []
    for f in ctx["window"].flights:
        st = getattr(f.req, "stats", None)
        if f.stamps and f.stamps[0] <= start and hasattr(st, field):
            out.append(st)
    return out
