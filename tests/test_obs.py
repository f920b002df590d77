"""Observability-layer tests (repro.obs: metrics / trace).

Pins the two contracts DESIGN.md §14 promises:

  * metrics registry semantics — counter/gauge/histogram math, idempotent
    registration, deterministic snapshots, thread-safety under concurrent
    writers, Prometheus text shape, and the disabled path recording nothing;
  * span tracer — contextvar nesting (depth/parent), Chrome trace-event
    schema of the export, async begin/end pairing, the disabled path
    writing no buffer event and reading no clock of its own, and the serve
    engine's spans on the profiler's timeline;

plus the acceptance bar: serve tokens are bit-identical with observability
fully enabled vs fully disabled, and steady-state decode shows zero
retraces beyond the per-bucket-width compiles.
"""
import json
import threading

import numpy as np
import pytest
import jax

from repro.obs import metrics, trace


@pytest.fixture()
def obs_on():
    """Enable metrics+trace with clean state; restore disabled-and-clean."""
    metrics.reset()
    trace.clear()
    metrics.enable()
    trace.enable()
    try:
        yield
    finally:
        metrics.disable()
        trace.disable()
        metrics.reset()
        trace.clear()


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


def test_disabled_records_nothing():
    metrics.reset()
    assert not metrics.enabled()
    c = metrics.counter("t_disabled_total", labels=("k",))
    g = metrics.gauge("t_disabled_gauge")
    h = metrics.histogram("t_disabled_seconds")
    c.inc("a")
    g.set(5.0)
    h.observe(0.2)
    assert c.value("a") == 0.0 and c.total() == 0.0
    assert g.value() == 0.0
    assert h.count() == 0 and h.sum() == 0.0


def test_counter_semantics(obs_on):
    c = metrics.counter("t_counter_total", "help", labels=("route",))
    c.inc("fast")
    c.inc("fast", by=2)
    c.inc("slow", by=0.5)
    assert c.value("fast") == 3.0
    assert c.value("slow") == 0.5
    assert c.total() == 3.5
    with pytest.raises(ValueError):
        c.inc("fast", by=-1)
    with pytest.raises(ValueError):
        c.inc()                      # label arity mismatch


def test_gauge_set_add(obs_on):
    g = metrics.gauge("t_gauge")
    g.set(4.0)
    g.set(2.0)
    g.add(0.5)
    assert g.value() == 2.5


def test_histogram_buckets_cumulative(obs_on):
    h = metrics.histogram("t_lat_seconds", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.05, 0.5, 5.0):
        h.observe(v)
    assert h.count() == 5
    assert h.sum() == pytest.approx(5.605)
    snap = h._snapshot_values()[""]
    # Prometheus semantics: cumulative counts, +Inf == total count.
    assert snap["buckets"] == {"0.01": 1, "0.1": 3, "1.0": 4, "+Inf": 5}


def test_registration_idempotent_and_conflicting():
    c1 = metrics.counter("t_reg_total", labels=("a",))
    c2 = metrics.counter("t_reg_total", labels=("a",))
    assert c1 is c2
    with pytest.raises(ValueError):
        metrics.counter("t_reg_total", labels=("b",))     # label mismatch
    with pytest.raises(ValueError):
        metrics.gauge("t_reg_total", labels=("a",))       # kind mismatch


def test_snapshot_deterministic_and_reset(obs_on):
    c = metrics.counter("t_snap_total", labels=("x",))
    c.inc("b")
    c.inc("a")
    s1 = json.dumps(metrics.snapshot(), sort_keys=True)
    s2 = json.dumps(metrics.snapshot(), sort_keys=True)
    assert s1 == s2
    doc = metrics.snapshot()["t_snap_total"]
    assert doc["type"] == "counter"
    assert list(doc["values"]) == ["x=a", "x=b"]          # sorted label sets
    metrics.reset()
    assert metrics.snapshot()["t_snap_total"]["values"] == {}
    assert metrics.get("t_snap_total") is c               # registration kept


def test_prometheus_text(obs_on):
    c = metrics.counter("t_prom_total", "prom help", labels=("r",))
    c.inc("x", by=2)
    h = metrics.histogram("t_prom_seconds", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(5.0)
    txt = metrics.prometheus_text()
    assert "# HELP t_prom_total prom help" in txt
    assert "# TYPE t_prom_total counter" in txt
    assert 't_prom_total{r="x"} 2.0' in txt
    assert 't_prom_seconds_bucket{le="0.1"} 1' in txt
    assert 't_prom_seconds_bucket{le="+Inf"} 2' in txt
    assert "t_prom_seconds_count 2" in txt


def test_counter_thread_safety(obs_on):
    c = metrics.counter("t_threads_total", labels=("t",))
    n_threads, n_incs = 8, 500

    def worker(i):
        for _ in range(n_incs):
            c.inc(i % 2)

    ts = [threading.Thread(target=worker, args=(i,))
          for i in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.total() == n_threads * n_incs


# ---------------------------------------------------------------------------
# Span tracer
# ---------------------------------------------------------------------------


def test_disabled_span_is_shared_null(monkeypatch):
    """Disabled, a span is only a profiler annotation: it shares the null
    Chrome path (no buffer write, no read of the tracer's clock)."""
    assert not trace.enabled()

    def no_clock():
        raise AssertionError("the disabled path read the tracer's clock")
    monkeypatch.setattr(trace, "_now_us", no_clock)
    with trace.span("a", k=1) as sp:
        sp.set(x=2)                   # no-op, no error
    with trace.span("b"):
        pass
    trace.instant("nothing")
    trace.begin_async("request", 1)
    trace.end_async("request", 1)
    assert trace.events() == []


def test_span_nesting_and_chrome_schema(obs_on):
    with trace.span("outer", step=1):
        with trace.span("inner", w=4) as sp:
            sp.set(late=True)
    trace.instant("marker", y=2)
    trace.begin_async("request", 7, prompt_len=3)
    trace.end_async("request", 7, reason="length")

    doc = trace.chrome_trace()
    assert doc["displayTimeUnit"] == "ms"
    ev = {e["name"]: e for e in doc["traceEvents"]}
    assert set(ev) == {"outer", "inner", "marker", "request"}

    inner, outer = ev["inner"], ev["outer"]
    for e in (inner, outer):
        assert e["ph"] == "X" and e["cat"] == "repro"
        assert isinstance(e["ts"], float) and e["dur"] >= 0
        assert "pid" in e and "tid" in e
    assert outer["args"]["depth"] == 0
    assert inner["args"]["depth"] == 1
    assert inner["args"]["parent"] == "outer"
    assert inner["args"]["late"] is True
    # inner nests inside outer on the timeline
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]

    assert ev["marker"]["ph"] == "i"
    # async pair shares (name, id); begin carries the open attrs
    reqs = [e for e in doc["traceEvents"] if e["name"] == "request"]
    assert sorted(e["ph"] for e in reqs) == ["b", "e"]
    assert all(e["id"] == "7" for e in reqs)

    json.dumps(doc)                   # schema is JSON-serializable as-is


def test_export_chrome(obs_on, tmp_path):
    with trace.span("one"):
        pass
    out = tmp_path / "trace.json"
    trace.export_chrome(str(out))
    doc = json.loads(out.read_text())
    assert [e["name"] for e in doc["traceEvents"]] == ["one"]


# ---------------------------------------------------------------------------
# Serve: obs on/off token identity + steady-state retraces
# ---------------------------------------------------------------------------


def _tiny_cfg():
    from repro.configs import get_config
    return get_config("llama3.2-1b", smoke=True).scaled_down(
        d_model=64, d_ff=128, vocab_size=256, n_heads=4, n_kv_heads=2,
        head_dim=16)


@pytest.fixture(scope="module")
def tiny():
    from repro.models import lm
    cfg = _tiny_cfg()
    params = lm.init_params(jax.random.PRNGKey(7), cfg)
    return cfg, params


def _requests(cfg, lengths=((3, 6, 0.0), (9, 3, 0.7), (5, 5, 0.0))):
    from repro.serve.engine import Request
    rng = np.random.default_rng(0)
    return [Request(prompt=list(rng.integers(1, cfg.vocab_size, size=n)),
                    max_new_tokens=m, temperature=t)
            for n, m, t in lengths]


def _generate(cfg, params):
    from repro.serve.engine import Engine
    reqs = _requests(cfg)
    eng = Engine(cfg, params, max_seq=32, batch_size=2, rng_seed=3)
    eng.generate(reqs)
    return [r.generated for r in reqs], eng


def test_serve_tokens_identical_with_obs_enabled(tiny):
    cfg, params = tiny
    baseline, _ = _generate(cfg, params)

    metrics.reset()
    trace.clear()
    metrics.enable()
    trace.enable()
    try:
        observed, eng = _generate(cfg, params)
        assert observed == baseline   # enabling obs moves no bits

        snap = metrics.snapshot()
        assert snap["repro_serve_admitted_total"]["values"][""] == 3.0
        fin = snap["repro_serve_finished_total"]["values"]
        assert sum(fin.values()) == 3.0
        ttft = snap["repro_serve_ttft_seconds"]["values"][""]
        assert ttft["count"] == 3

        # Steady-state decode must not retrace: every counted (re)compile
        # is one of the per-bucket-width traces the executor reports.
        retr = metrics.get("repro_serve_retraces_total")
        assert retr.value("decode") == eng.n_traces()["decode"]

        names = {e["name"] for e in trace.events()}
        assert {"serve.step", "serve.admit", "serve.prefill",
                "serve.decode.dispatch", "serve.decode.wait", "serve.finish",
                "request"} <= names
        reqs = [e for e in trace.events() if e["name"] == "request"]
        assert sorted(e["ph"] for e in reqs) == ["b"] * 3 + ["e"] * 3
    finally:
        metrics.disable()
        trace.disable()
        metrics.reset()
        trace.clear()

    # and back off: still identical (no sticky state)
    again, _ = _generate(cfg, params)
    assert again == baseline


def test_serve_spans_on_the_profiler_timeline(tiny, tmp_path):
    """With a profiler session around generate, the engine's spans land on
    a host plane of the trace, the phases nested inside ``serve.step``."""
    from jax.profiler import ProfileData
    from repro.serve.engine import Engine

    cfg, params = tiny
    eng = Engine(cfg, params, max_seq=32, batch_size=2, rng_seed=3)
    eng.generate(_requests(cfg))          # compile outside the session
    with jax.profiler.trace(str(tmp_path)):
        eng.generate(_requests(cfg))
    files = sorted(tmp_path.glob("**/*.xplane.pb"))
    assert files
    spans = {}
    for plane in ProfileData.from_file(str(files[-1])).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    spans.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    steps = spans.get("serve.step", [])
    assert steps
    for name in ("serve.admit", "serve.prefill", "serve.decode.dispatch",
                 "serve.decode.wait"):
        assert spans.get(name), name
        assert all(any(a <= x and y <= b for a, b in steps)
                   for x, y in spans[name]), name


@pytest.mark.parametrize("chunk", [None, 8], ids=["whole", "chunked"])
def test_request_records_are_ordered_and_prefill_is_synced(tiny, chunk):
    from repro.serve.engine import Engine

    cfg, params = tiny
    reqs = _requests(cfg, ((19, 4, 0.0), (9, 3, 0.7), (5, 5, 0.0)))
    eng = Engine(cfg, params, max_seq=32, batch_size=2, rng_seed=3,
                 prefill_chunk=chunk)
    stats = eng.generate(reqs)
    assert len(stats.requests) == 3
    for r in reqs:
        rs = r.stats
        assert rs.arrival_s <= rs.admit_s <= rs.first_token_s
        assert rs.prefill_s > 0
        assert rs.prefill_s <= rs.first_token_s - rs.admit_s
    assert stats.prefill_s == pytest.approx(
        sum(rs.prefill_s for rs in stats.requests), rel=1e-12)


def test_step_phases_count_every_decode_step(tiny, obs_on):
    from repro.serve.engine import Engine

    cfg, params = tiny
    eng = Engine(cfg, params, max_seq=32, batch_size=2, rng_seed=3)
    stats = eng.generate(_requests(cfg))
    phases = metrics.get("repro_serve_step_phase_seconds")
    decode = metrics.get("repro_serve_decode_step_seconds")
    assert decode.count() == stats.decode_steps > 0
    assert phases.count("decode_wait") == decode.count()
    assert phases.count("decode_dispatch") == decode.count()
    assert phases.count("finish") == decode.count()
    assert phases.count("admit") >= decode.count()
    assert phases.count("prefill") > 0


@pytest.mark.parametrize("chunk", [None, 8], ids=["whole", "chunked"])
def test_recurrent_steps_count_padded_prefill_and_decode(obs_on, chunk):
    """``repro_serve_recurrent_steps_total``: a prefill chunk counts its
    padded width times the recurrent layers (the scan runs over pads too),
    a decode step the recurrent layers; ``serve.prefill`` carries the real
    prompt tokens beside the padded width."""
    from repro.configs import get_config
    from repro.models import lm
    from repro.serve.engine import Engine

    cfg = get_config("rwkv6-3b", smoke=True)
    params = lm.init_params(jax.random.PRNGKey(3), cfg)
    eng = Engine(cfg, params, max_seq=32, batch_size=2, rng_seed=3,
                 prefill_chunk=chunk)
    stats = eng.generate(_requests(cfg, ((19, 4, 0.0), (9, 3, 0.0),
                                         (5, 5, 0.0))))
    layers = cfg.n_periods * len(cfg.pattern)
    assert layers == 2
    # (width, real tokens) of each prefill call: whole prompts take the
    # bucket above them; 8-wide chunks split 19 into 8 + 8 + 3 and 9 into
    # 8 + 1, each call 8 wide
    calls = {None: [(32, 19), (16, 9), (8, 5)],
             8: [(8, 8), (8, 8), (8, 3), (8, 8), (8, 1), (8, 5)]}[chunk]
    steps = metrics.get("repro_serve_recurrent_steps_total")
    assert steps.value("prefill") == sum(w for w, _ in calls) * layers
    assert steps.value("decode") == stats.decode_steps * layers > 0
    spans = [e["args"] for e in trace.events() if e["name"] == "serve.prefill"]
    assert sorted((a["width"], a["tokens"]) for a in spans) == sorted(calls)


def test_recurrent_steps_stay_zero_for_attention(tiny, obs_on):
    from repro.serve.engine import Engine

    cfg, params = tiny
    eng = Engine(cfg, params, max_seq=32, batch_size=2, rng_seed=3)
    eng.generate(_requests(cfg))
    steps = metrics.get("repro_serve_recurrent_steps_total")
    assert steps.value("prefill") == steps.value("decode") == 0
    assert all("tokens" in e["args"] for e in trace.events()
               if e["name"] == "serve.prefill")
