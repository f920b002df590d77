"""CPU tests of the readers of the engine's per-request records
(``bench/records.py`` and the ``bench/metrics`` files that use it), on
windows built by hand.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests
"""
from __future__ import annotations

import sys
from dataclasses import dataclass
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

import drive  # noqa: E402
import records  # noqa: E402
import run  # noqa: E402
from repro.serve.scheduler import Request, RequestStats  # noqa: E402

READERS = {name: run.load_reader(name)
           for name in ("prefill_ms.chat", "prefill_tokens_per_s.docs")}


def _flight(rid, plen, arrival, admit, prefill, stamps):
    req = Request(prompt=[1] * plen)
    req.stats = RequestStats(rid=rid, prompt_len=plen, arrival_s=arrival,
                             admit_s=admit, prefill_s=prefill)
    return drive.Flight(prompt=req.prompt, max_new=4, due=arrival, req=req,
                        stamps=list(stamps))


def _ctx(flights, start=10.0, stop=18.0):
    win = drive.Window(start=0.0, end=50.0, flights=flights, steps=[])
    return {"window": win, "prof": {"on": False, "start": start, "stop": stop}}


def _window():
    """Two requests served before the slice (one stamped at its very start),
    one first stamped inside it, one after it, and one never sent."""
    return [_flight(0, 100, 1.0, 1.02, 0.2, [1.3, 1.4]),
            _flight(1, 300, 2.0, 2.10, 0.4, [10.0, 10.1]),
            _flight(2, 200, 9.5, 9.90, 0.3, [11.0]),
            _flight(3, 50, 17.0, 19.0, 0.1, [19.5]),
            drive.Flight(prompt=[1] * 8, max_new=4, due=49.0)]


def test_head_takes_only_requests_first_stamped_before_the_slice():
    ctx = _ctx(_window())
    assert [r.rid for r in records.head_stats(ctx, "prefill_s")] == [0, 1]
    assert records.head_stats(ctx, "no_such_record") == []


def test_readers_read_the_head_of_the_window():
    ctx = _ctx(_window())
    assert READERS["prefill_ms.chat"](ctx) == pytest.approx(
        1e3 * (0.2 + 0.4) / 2)
    assert READERS["prefill_tokens_per_s.docs"](ctx) == pytest.approx(
        400 / 0.6)


@pytest.mark.parametrize("name", sorted(READERS))
def test_no_slice_or_no_qualifying_request_gives_none(name):
    read = READERS[name]
    assert read(_ctx(_window(), start=None, stop=None)) is None
    # every request first stamped inside or after the slice
    assert read(_ctx(_window()[2:])) is None
    assert read(_ctx([])) is None


@dataclass
class _OldStats:
    """Per-request records of a program that keeps no admission or
    prefill time."""
    rid: int
    prompt_len: int
    arrival_s: float
    first_token_s: float = 0.0


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_program_without_the_records_gives_none(name):
    flights = _window()
    for f in flights:
        if f.req is not None:
            st = f.req.stats
            f.req.stats = _OldStats(st.rid, st.prompt_len, st.arrival_s)
    assert READERS[name](_ctx(flights)) is None
