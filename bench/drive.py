"""The measured window: drives ``Engine.submit`` and ``Engine.step``.

An open loop submits each request when it falls due, whether or not the
engine keeps up; a closed loop keeps ``queue`` requests in the engine,
submitting the next document as one finishes.  After every ``step`` the
host clock is read once and stamped on each token that appeared, so the
time to first token runs from the scheduled arrival to the stamp of the
first token, and the gaps between tokens are gaps between stamps.

Every call into the engine, the wait for the next arrival and the
bookkeeping sit in ``jax.profiler.TraceAnnotation`` spans, so that a traced
run can say what the host was doing while the device idled.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

from jax.profiler import TraceAnnotation


@dataclass
class Flight:
    prompt: List[int]
    max_new: int
    due: float                       # scheduled arrival (host clock)
    req: object = None
    sent: float = 0.0                # when it was handed to the engine
    stamps: List[float] = field(default_factory=list)
    done: bool = False


@dataclass
class Step:
    start: float
    end: float
    decode_tokens: int               # tokens from the decode batch
    prefill_widths: List[int]        # bucket widths of the prefills that ended


@dataclass
class Window:
    start: float
    end: float
    flights: List[Flight]
    steps: List[Step]

    def ttft_ms(self) -> List[float]:
        """Scheduled arrival to first token of every request due in the
        window; one with no first token by the end counts at its age then."""
        return [(min(f.stamps[0] if f.stamps else self.end, self.end) - f.due) * 1e3
                for f in self.flights if f.due < self.end]


def run(engine, request_cls, items, loop: dict, seconds: float,
        bucket: Callable[[int], int],
        hook: Optional[Callable[[float, float], None]] = None) -> Window:
    """Serve ``items`` for ``seconds``.  ``hook(now, start)`` is called
    between steps (the harness starts and stops the profiler there)."""
    open_loop = loop["loop"] == "open"
    start = time.perf_counter()
    end = start + seconds
    # open loop: every request of the run, at its scheduled time; closed
    # loop: the documents sent so far, each due when it was sent
    due = [Flight(list(it.prompt), it.max_new, start + it.due_s)
           for it in items] if open_loop else []
    sent: List[Flight] = []
    live: List[Flight] = []
    steps: List[Step] = []
    nxt = 0

    def send(f: Flight, now: float):
        f.req = request_cls(prompt=f.prompt, max_new_tokens=f.max_new)
        f.sent = now
        with TraceAnnotation("bench.submit"):
            engine.submit(f.req)
        live.append(f)
        sent.append(f)

    now = start
    while now < end:
        if hook is not None:
            hook(now, start)
        with TraceAnnotation("bench.generator"):
            if open_loop:
                while nxt < len(due) and due[nxt].due <= now:
                    send(due[nxt], now)
                    nxt += 1
            else:
                while len(live) < loop["queue"]:
                    it = items[nxt % len(items)]
                    send(Flight(list(it.prompt), it.max_new, now), now)
                    nxt += 1
        if not live:
            wake = min(due[nxt].due if nxt < len(due) else end, end)
            with TraceAnnotation("bench.idle_wait"):
                time.sleep(max(0.0, wake - time.perf_counter()))
            now = time.perf_counter()
            continue
        t0 = time.perf_counter()
        with TraceAnnotation("bench.step"):
            engine.step()
        now = time.perf_counter()
        with TraceAnnotation("bench.record"):
            dec = 0
            widths = []
            for f in live:
                new = len(f.req.generated) - len(f.stamps)
                if new <= 0:
                    continue
                if not f.stamps:
                    widths.append(bucket(len(f.prompt)))
                    new -= 1
                dec += new
                f.stamps.extend([now] * (len(f.req.generated) - len(f.stamps)))
                if len(f.stamps) >= f.max_new:
                    f.done = True
            live[:] = [f for f in live if not f.done]
            steps.append(Step(t0, now, dec, widths))
    # requests due in the window that were never sent count too
    return Window(start, now, sent + due[nxt:], steps)
