"""Serving launcher: continuous-batching generation demo on a reduced config.

    python -m repro.launch.serve --arch gemma-2b --quant w12 --requests 8

With ``--poisson RATE`` the requests arrive as a Poisson process (RATE
requests/s) instead of all at once, so TTFT includes queueing delay.
"""
from __future__ import annotations

import argparse
import sys

import jax
import numpy as np


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--quant", default="w12",
                    choices=["none", "w8", "w12", "mixed"])
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", "--slots", dest="batch", type=int, default=4,
                    help="decode slots (continuous batching); decode runs "
                         "on the smallest power-of-two bucket covering the "
                         "live slots, so idle slots cost nothing")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill: advance prompts this many tokens "
                         "per engine step, interleaved with decode "
                         "(power of two >= 8; 0: whole-prompt prefill at "
                         "admission)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share repeated prompt prefixes via paged-cache "
                         "snapshots (implies chunked prefill)")
    ap.add_argument("--eos", type=int, default=-1,
                    help="stop token id (-1: none)")
    ap.add_argument("--poisson", type=float, default=0.0,
                    help="arrival rate in req/s (0: all at once)")
    ap.add_argument("--full-size", action="store_true",
                    help="full config (needs real accelerators)")
    ap.add_argument("--tuning-table", default=None,
                    help="repro.tune table JSON (DESIGN.md §10)")
    ap.add_argument("--backend", "--quant-backend", dest="backend",
                    default="xla", choices=["xla", "pallas"],
                    help="quantized-GEMM backend: 'pallas' serves through "
                         "the fused single-pass kernel (DESIGN.md §11); "
                         "with --mesh it runs shard-mapped (DESIGN.md §12)")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="serve sharded on a (data, model) mesh, e.g. 2x4 "
                         "(needs data*model visible devices)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="enable the repro.obs metrics registry and write a "
                         "JSON snapshot here after generation")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="enable the repro.obs span tracer and write a "
                         "Chrome-trace (chrome://tracing / Perfetto) JSON "
                         "file here after generation")
    args = ap.parse_args()

    from repro.configs import get_config
    from repro.core.context import ExecContext
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import lm
    from repro.obs import metrics as obs_metrics
    from repro.obs import trace as obs_trace
    from repro.serve.engine import Engine, Request

    enable_compile_cache()
    # Observability is opt-in: enable before engine construction so plan
    # selection / compile-time counters during warmup are captured too.
    if args.metrics_out:
        obs_metrics.enable()
    if args.trace_out:
        obs_trace.enable()

    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh(tuple(int(x) for x in args.mesh.split("x")))
    ctx = ExecContext(backend=args.backend, mesh=mesh,
                      tuning_table=args.tuning_table)
    cfg = get_config(args.arch, smoke=not args.full_size, quant=args.quant)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    engine = Engine(cfg, params, max_seq=args.max_seq, batch_size=args.batch,
                    context=ctx,
                    prefill_chunk=args.prefill_chunk or None,
                    prefix_cache=args.prefix_cache)
    rng = np.random.default_rng(0)
    stop = (args.eos,) if args.eos >= 0 else ()
    reqs = [Request(prompt=list(rng.integers(1, cfg.vocab_size,
                                             size=rng.integers(4, 17))),
                    max_new_tokens=args.max_new,
                    temperature=0.0 if i % 2 == 0 else 0.8,
                    stop_tokens=stop)
            for i in range(args.requests)]
    arrivals = None
    if args.poisson > 0:
        arrivals = np.cumsum(rng.exponential(1.0 / args.poisson,
                                             size=len(reqs))).tolist()
    stats = engine.generate(reqs, arrival_s=arrivals)
    for i, r in enumerate(reqs):
        rs = r.stats
        print(f"req{i}: prompt[{len(r.prompt)}] -> {r.generated} "
              f"({rs.stop_reason}; ttft {rs.ttft_s*1e3:.0f}ms, "
              f"latency {rs.latency_s*1e3:.0f}ms)")
    print(f"prefill {stats.prefill_s:.2f}s; {stats.generated_tokens} tokens "
          f"in {stats.decode_steps} decode steps / {stats.decode_s:.2f}s "
          f"({stats.tokens_per_s:.1f} tok/s, occupancy "
          f"{stats.occupancy_pct:.0f}%, quant={args.quant}); "
          f"traces={engine.n_traces()}")
    if engine.prefix is not None:
        print(f"prefix cache: {engine.prefix.stats()}")
    if args.metrics_out:
        obs_metrics.write_snapshot(args.metrics_out)
        print(f"metrics snapshot -> {args.metrics_out}")
    if args.trace_out:
        obs_trace.export_chrome(args.trace_out)
        print(f"chrome trace -> {args.trace_out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
