"""Shared arithmetic of the per-layer readers in ``bench/metrics``.

Each reader gets the run's context: the configuration and mix, the device's
peaks, the window the harness recorded (``drive.Window``), the host-clock
span of the profiled slice (``prof``) and the trace's events (``events``,
``traced``).  A reader that finds nothing to read returns None.
"""
from __future__ import annotations

from typing import Optional

import ops
import devtrace

# The fused KMM kernel's operations in the device trace: on a TPU v5e the
# profiler names each op by its HLO instruction, and the dense kernel's
# custom call is the instruction ``%fused_gemm.<n>`` (the grouped kernel's
# is ``%fused_gemm_grouped.<n>``, which this does not match).
FUSED_KERNEL = "%fused_gemm."


def _slice(ctx):
    p = ctx["prof"]
    if p.get("start") is None or p.get("stop") is None:
        return None
    return p["start"], p["stop"]


def idle_pct(ctx) -> Optional[float]:
    ev, win = ctx["events"], ctx["traced"]
    if ev is None or win is None or not ev["devices"] or win[1] <= win[0]:
        return None
    return 100.0 * (1.0 - devtrace.busy_seconds(ev, win) / (win[1] - win[0]))


def decode_step_ms(ctx) -> Optional[float]:
    hist = ctx["prof"].get("hist")
    if not hist or hist[1] <= 0 or _slice(ctx) is None:
        return None
    return 1e3 * hist[0] / hist[1]


def mfu_pct(ctx) -> Optional[float]:
    """Model operations of the tokens served in the profiled slice over the
    slice's length at the int8 peak."""
    sl, cfg, peaks = _slice(ctx), ctx["cfg"], ctx["peaks"]
    if sl is None or peaks is None:
        return None
    total = 0.0
    for f in ctx["window"].flights:
        for j, t in enumerate(f.stamps):
            if sl[0] <= t <= sl[1]:
                total += (ops.prefill_ops(cfg, len(f.prompt)) if j == 0 else
                          ops.decode_ops(cfg, len(f.prompt) + j - 1))
    if total == 0.0:
        return None
    return 100.0 * total / ((sl[1] - sl[0]) * peaks["int8_ops"])


def decode_width(n_live: int, slots: int) -> int:
    w = 1
    while w < n_live:
        w *= 2
    return min(w, slots)


def kernel_roofline_pct(ctx, pattern: str = FUSED_KERNEL) -> Optional[float]:
    """Least time of the fused GEMMs of the profiled steps over the kernel's
    device time in the trace."""
    sl, cfg, peaks = _slice(ctx), ctx["cfg"], ctx["peaks"]
    ev, win = ctx["events"], ctx["traced"]
    if sl is None or peaks is None or ev is None or win is None:
        return None
    bits, slots = cfg["weight_bits"], ctx["mix"]["slots"]
    least = 0.0
    for st in ctx["window"].steps:
        if not (sl[0] <= st.start and st.end <= sl[1]):
            continue
        shapes = [c for p in st.prefill_widths for c in ops.calls(cfg, p, 1)]
        if st.decode_tokens:
            w = decode_width(st.decode_tokens, slots)
            shapes += ops.calls(cfg, w, w)
        least += sum(ops.least_seconds(m, k, n, bits, peaks) for m, k, n in shapes)
    kernel = devtrace.kernel_seconds(ev, pattern, win)
    if least == 0.0 or kernel <= 0.0:
        return None
    return 100.0 * least / kernel
