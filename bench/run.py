#!/usr/bin/env python3
"""Chip benchmark of the KMM serving engine: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``bench/configs``) and a
traffic mix (``bench/traffic``).  The run makes the weights from the seed on
the device, builds ``repro.serve.engine.Engine`` on the pallas backend,
warms every width the mix reaches, then serves the mix for ``--seconds``
through ``Engine.submit`` / ``Engine.step``.  After the window it frees the
engine and compares a sample of the served tokens with the plain reference
(``bench/reference.py``, ``bench/check.py``).

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` a middle slice of the window runs under the JAX profiler and
the result carries the cell's per-layer metrics, each computed by its reader
``bench/metrics/<metric>.py``.  The last line of standard output is one JSON
object; the numbers compared for ``correct`` are the last lines of standard
error.  Without a TPU, or with fewer chips than the cell asks for, the run
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(BENCH))

import check  # noqa: E402
import traffic  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
# Seconds of the window that run under the profiler in a traced run.
TRACE_SECONDS = 8.0


class NoChip(SystemExit):
    pass


@dataclass
class Cell:
    name: str
    cfg: dict
    mix: dict
    chips: int
    spec: dict                  # the whole BENCHMARK.json
    limits: Dict[str, float]    # of the numbers compared for ``correct``


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])
    cfg = json.loads((root / conf["file"]).read_text())
    mix = traffic.load(root / "bench" / "traffic" / f"{w['traffic']}.json")
    limits = check.load_limits(root / "bench", name)
    return Cell(name, cfg, mix, w["chips"], spec, limits)


def require_chips(n: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"bench: needs a TPU, JAX found {devices[0].platform!r}; "
                     f"there is no fallback")
    if len(devices) < n:
        raise NoChip(f"bench: the cell needs {n} chips, JAX found {len(devices)}")
    return devices[:n]


def peaks_for(kind: str) -> Dict[str, float]:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return table[kind]


def enable_compile_cache():
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class Compiles:
    """Backend compiles seen, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def program_config(cfg: dict):
    """The program's ModelConfig for a configuration file."""
    import dataclasses

    from repro.configs import QUANT_POLICIES, get_config

    base = get_config(cfg["arch"])
    kw = dict(d_model=cfg["hidden_size"], d_ff=cfg["intermediate_size"],
              vocab_size=cfg["vocab_size"], n_periods=cfg["num_hidden_layers"],
              act=cfg["hidden_act"], glu=cfg["gated_mlp"],
              tie_embeddings=cfg["tie_word_embeddings"],
              param_dtype=cfg["param_dtype"], compute_dtype=cfg["compute_dtype"])
    if cfg["block"] == "attn":
        kw.update(n_heads=cfg["num_attention_heads"],
                  n_kv_heads=cfg["num_key_value_heads"],
                  head_dim=cfg["head_dim"], rope_theta=float(cfg["rope_theta"]))
    else:
        kw.update(rwkv_head_dim=cfg["head_size"],
                  n_heads=cfg["hidden_size"] // cfg["head_size"])
    pc = dataclasses.replace(base, **kw).with_quant(QUANT_POLICIES[cfg["quant"]])
    if [b.kind for b in pc.pattern] != [cfg["block"]] or pc.pattern[0].moe:
        raise SystemExit(f"bench: {cfg['arch']} is not one {cfg['block']} block "
                         f"per layer in the program")
    return pc


def make_weights(cfg: dict, pcfg, seed: int):
    import jax
    import numpy as np

    import weights
    from repro.models import lm

    key = jax.random.PRNGKey(int(np.random.SeedSequence(seed % 2 ** 64)
                                 .generate_state(1)[0]))
    params = weights.make(cfg, key)
    want = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0), pcfg))
    got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), params)
    if jax.tree.map(lambda a: (a.shape, str(a.dtype)), want) != got:
        raise SystemExit("bench: the weight tree does not have the layout "
                         "the program takes")
    jax.block_until_ready(params)
    return params


def counter(name: str, *labels) -> float:
    from repro.obs import metrics as obs_metrics

    m = obs_metrics.get(name)
    if m is None:
        return 0.0
    return m.value(*labels) if labels else m.total()


def quantile(values: List[float], q: float) -> float:
    """The ``q`` quantile of all values (Python's exclusive method)."""
    if len(values) == 1:
        return values[0]
    return statistics.quantiles(values, n=100, method="exclusive")[round(q * 100) - 1]


def end_to_end(win, mix: dict, setup_s: float) -> Dict[str, float]:
    """Every end-to-end number the window supports; the cell keeps its own."""
    t0, t1 = win.start, win.end
    out = {"setup_s": setup_s}
    ttft = win.ttft_ms()
    itl = [(b - a) * 1e3 for f in win.flights
           for a, b in zip(f.stamps, f.stamps[1:]) if b <= t1]
    if ttft:
        out["ttft_p90_ms"] = quantile(ttft, 0.90)
    if itl:
        out["itl_p95_ms"] = quantile(itl, 0.95)
    done = 0
    for f in win.flights:
        for j, t in enumerate(f.stamps):
            if t0 <= t <= t1:
                done += 1 + (len(f.prompt) if j == 0 else 0)
    out["tokens_per_s"] = done / (t1 - t0)
    return out


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def served_pairs(win):
    return [(f.prompt, list(f.req.generated)) for f in win.flights
            if f.req is not None and f.stamps and len(f.stamps) >= f.max_new]


def compare(cfg: dict, params, pairs, seed: int) -> Dict[str, float]:
    """The served tokens of a sample against the reference."""
    import reference

    idx = check.sample(pairs, seed)
    if not idx:
        return {"sampled_tokens": 0}
    seqs, pos = zip(*(check.teacher_forced(*pairs[i]) for i in idx))
    refs = reference.logits_at(cfg, params, seqs, pos)
    served = [pairs[i][1] for i in idx]
    return {"sampled_tokens": sum(len(s) for s in served),
            **check.gap_numbers(refs, served)}


def run(args, devices=None, engine_hook=None, root: Path = ROOT,
        quant: Optional[str] = None) -> dict:
    """One run of a cell; returns the result object.  ``devices`` skips
    the look for a chip and ``root`` reads the cell from another tree
    (tests); ``engine_hook(engine)`` may change the engine before the
    window (tests that break the timed path); ``quant`` serves under
    another of the program's quantization policies (the control of
    ``bench/calibrate.py``)."""
    import jax
    import numpy as np

    import drive
    from repro.core.context import ExecContext
    from repro.obs import metrics as obs_metrics
    from repro.serve.engine import Engine, Request
    from repro.serve.scheduler import prompt_buckets_for

    cell = load_cell(args.workload, root)
    if devices is None:
        devices = require_chips(cell.chips)
    enable_compile_cache()
    dev = devices[0]
    peaks = peaks_for(dev.device_kind) if dev.platform == "tpu" else None
    compiles = Compiles()
    cfg, mix = cell.cfg, cell.mix
    if quant is not None:
        cfg = dict(cfg, quant=quant)

    def note(what):
        print(f"[{time.perf_counter() - PROCESS_START:8.2f} s] {what}; compiles "
              f"{compiles.count} ({compiles.seconds:.2f} s), persistent-cache "
              f"hits {compiles.cache_hits}", file=sys.stderr, flush=True)

    note(f"{cell.name} on {dev.device_kind} x {len(devices)}")
    pcfg = program_config(cfg)
    params = make_weights(cfg, pcfg, args.seed)
    note("weights made")
    ladder = prompt_buckets_for(mix["max_seq"])
    buckets = traffic.prompt_buckets(mix, ladder)
    engine = Engine(pcfg, params, max_seq=mix["max_seq"],
                    batch_size=mix["slots"], rng_seed=0,
                    context=ExecContext(backend=cfg["backend"]),
                    prompt_buckets=buckets)
    obs_metrics.enable()
    note("engine built")
    engine.warm()
    # one short request per prompt width: admission, pool zeroing, every
    # prefill width and a decode step, so nothing compiles in the window
    engine.generate([Request(prompt=[1] * min(b, mix["max_seq"] - 2), max_new_tokens=2)
                     for b in buckets])
    note("engine warmed")
    if engine_hook is not None:
        engine_hook(engine)
    items = traffic.generate(mix, args.seed, args.seconds, cfg["vocab_size"])
    routes = ("repro_quant_gemm_routes_total", cfg["backend"])
    before = {"compiles": compiles.count,
              "retraces": counter("repro_serve_retraces_total")}
    decode_hist = obs_metrics.get("repro_serve_decode_step_seconds")
    prof = {"on": False, "start": None, "stop": None, "hist": None}
    t_trace = (args.seconds - TRACE_SECONDS) / 2 if args.trace else None

    def hook(now, start):
        if t_trace is None:
            return
        if not prof["on"] and prof["start"] is None and now - start >= t_trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(str(TRACE_DIR))
            prof.update(on=True, start=now,
                        hist=(decode_hist.sum(), decode_hist.count()))
        elif prof["on"] and now - prof["start"] >= TRACE_SECONDS:
            jax.profiler.stop_trace()
            prof.update(on=False, stop=now,
                        hist=(decode_hist.sum() - prof["hist"][0],
                              decode_hist.count() - prof["hist"][1]))

    setup_s = time.perf_counter() - PROCESS_START
    win = drive.run(engine, Request, items, mix, args.seconds,
                    bucket=lambda n: traffic.bucket(n, ladder), hook=hook)
    if prof["on"]:
        hook(float("inf"), 0.0)
    numbers = {
        "compiles_in_window": compiles.count - before["compiles"],
        "retraces_in_window": counter("repro_serve_retraces_total") - before["retraces"],
        "xla_fallback_routes": counter(*routes, "xla_fallback"),
        "kernel_routes_missing": float(counter(*routes, "pallas") <= 0),
    }
    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))
    e2e = end_to_end(win, mix, setup_s)
    pairs = served_pairs(win)
    attempted = sum(1 for f in win.flights if f.due < win.end)
    finished = len(pairs)
    late = [f.sent - f.due for f in win.flights if f.req is not None]
    note(f"window {win.end - win.start:.2f} s after set-up {setup_s:.2f} s: "
         f"{len(win.steps)} steps, {attempted} requests due, {finished} finished, "
         f"generator late by {max(late, default=0.0) * 1e3:.1f} ms at most, "
         f"memory peak {memory_peak / 2 ** 30:.2f} GiB; {json.dumps(e2e)}")

    # free the program's state before the reference runs
    del engine
    gc.collect()
    numbers.update(compare(cfg, params, pairs, args.seed))
    note("reference compared")

    limits = dict(cell.limits)
    limits.update(compiles_in_window=0, retraces_in_window=0,
                  xla_fallback_routes=0, kernel_routes_missing=0)
    ok, rows = check.verdict(numbers, limits)

    names = [m["name"] for m in cell.spec["end_to_end"]
             if cell.name in m.get("workloads", [cell.name])]
    units = {m["name"]: m["unit"] for m in cell.spec["end_to_end"] + cell.spec["per_layer"]}
    # a request fails when it errs; the engine refuses none, so a run that
    # is not correct counts its sampled requests as failed
    result = {"correct": ok, "attempted": attempted,
              "failed": 0 if ok else len(check.sample(pairs, args.seed)),
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devices), "memory_peak_bytes": memory_peak}}
    if args.trace:
        import devtrace as tr

        ctx = {"cfg": cfg, "mix": mix, "peaks": peaks, "window": win,
               "prof": prof, "events": None, "traced": None}
        files = sorted(TRACE_DIR.glob("**/*.xplane.pb"))
        if files:
            ctx["events"] = tr.load(files[-1])
            ctx["traced"] = tr.window(ctx["events"])
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        metrics = {}
        for m in cell.spec["per_layer"]:
            if cell.name not in m.get("workloads", [cell.name]):
                continue
            value = load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if ctx["traced"] is not None:
            ev, tw = ctx["events"], ctx["traced"]
            result["device"]["busy_s"] = tr.busy_seconds(ev, tw)
            result["device"]["window_s"] = tw[1] - tw[0]
            result["breakdown"] = {"device_ops": tr.top_ops(ev, tw),
                                   "idle_gaps": tr.attribute(ev, tr.idle_gaps(ev, tw))}
    else:
        metrics = {n: {"value": e2e[n], "unit": units[n]} for n in names if n in e2e}
    result["metrics"] = metrics
    result["numbers"] = numbers
    result["checks"] = rows
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args)
    except NoChip as e:
        print(e, file=sys.stderr)
        return 3
    for row in result["checks"]:
        print(f"check {row['name']}: {row['value']} (limit {row['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
