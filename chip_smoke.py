#!/usr/bin/env python3
"""Chip smoke test: the quantized KMM serving path, once, on a TPU.

    python chip_smoke.py               # one chip: kernel phase + serve phase
    python chip_smoke.py --four-chips  # sharded pallas serving on four chips

It runs in one process and refuses to run anywhere but on a TPU.  Phases,
in order, each printing its own lines:

  * kernel: the fused KMM kernel in each of its modes (mm1 w8, kmm2 w12,
    mm2 w16, kmm4 w20) at a llama3.2-1b FFN width, and the ragged grouped
    kernel at granite-moe-3b-a800m expert widths, against the int64 oracle
    of ``kernels/ref.py`` computed on the host;
  * serve: llama3.2-1b at its published width with the w12 policy (KMM2)
    through ``Engine`` on ``ExecContext(backend="pallas")`` and again on
    ``backend="xla"``.  Greedy tokens must agree, no quantized GEMM may
    take the XLA fallback, and the logits of one prompt on either backend
    must stay close to the unquantized model's.
  * four-chips (only with ``--four-chips``): the same requests on a (1, 4)
    ``(data, model)`` mesh with the pallas backend, against a meshless
    pallas run on one of the chips.  Greedy tokens must agree, one
    shard-mapped GEMM must be bit-identical to the unsharded one, the logits
    of one prompt must stay close to the unquantized model's, and no GEMM
    may fall back from shard-mapped execution.

Weights are random, made from ``--seed``.  Compile seconds and tokens/s are
printed as smoke readings, not benchmarks.  Any failure raises and exits
non-zero; the last line of standard output is the JSON result
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "llama3.2-1b"
QUANT = "w12"
MAX_SEQ = 256
SLOTS = 4
MAX_NEW = 16
# (prompt length, temperature) per request: three greedy, one sampled.
REQUESTS = ((9, 0.0), (23, 0.0), (40, 0.8), (17, 0.0))

# One prefill m-tile of llama3.2-1b's FFN up-projection (d_model -> d_ff).
KERNEL_SHAPE = (128, 2048, 8192)
KERNEL_CASES = (("mm1", 8), ("kmm2", 12), ("mm2", 16), ("kmm4", 20))
# granite-moe-3b-a800m experts: 40 experts, d_model 1536, d_ff_expert 512;
# N_SEG sequences of SEG capacity rows each per expert.
MOE_E, MOE_K, MOE_N, MOE_W = 40, 1536, 512, 12
SEG, N_SEG = 16, 4

# Logits of a w12 run may sit this far from the unquantized model's,
# relative to their largest magnitude: 100 quantization steps of a 12-bit
# value.  A wrong GEMM lands near 1.
LOGITS_TOL = 100 * 2.0 ** -11

SMOKE_NOTE = "smoke reading, not a benchmark"


def require_tpu(n_chips: int):
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, but JAX found platform "
                         f"{platform!r}; there is no CPU fallback")
    if len(devices) < n_chips:
        raise SystemExit(f"chip_smoke: needs {n_chips} TPU chips, JAX "
                         f"found {len(devices)}")
    return devices[:n_chips]


class CompileClock:
    """Backend compile seconds (persistent-cache reads included) and cache
    hits, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.secs = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    @contextlib.contextmanager
    def phase(self, name: str):
        secs, hits = self.secs, self.hits
        yield
        print(f"[{name}] compile {self.secs - secs:.2f} s, "
              f"{self.hits - hits} persistent-cache hits ({SMOKE_NOTE})",
              flush=True)


def wrap_int32(x: np.ndarray) -> np.ndarray:
    """Two's-complement reduction of int64 values to the int32 range."""
    return (x + (1 << 31)) % (1 << 32) - (1 << 31)


def host_map(fn, items) -> list:
    """``map`` over the host's cores (the int64 oracle releases the GIL)."""
    with ThreadPoolExecutor(os.cpu_count()) as ex:
        return list(ex.map(fn, items))


def check_exact(label: str, got, want: np.ndarray) -> None:
    """The kernel's int32-combine output is exact integer arithmetic: it
    equals the oracle where that fits int32, and the oracle mod 2^32 where
    it does not."""
    got = np.asarray(got).astype(np.int64)
    fits = bool(np.abs(want).max() < (1 << 31))
    bad = int((got != wrap_int32(want)).sum())
    if bad:
        raise AssertionError(f"[kernel] {label}: {bad} of {want.size} "
                             f"elements differ from the int64 oracle")
    how = "identical to" if fits else "identical mod 2^32 to"
    print(f"[kernel] {label}: {how} the int64 oracle ({want.size} elements, "
          f"max |c| = {int(np.abs(want).max())})", flush=True)


def kernel_phase(seed: int) -> None:
    import jax.numpy as jnp

    from repro.kernels.fused_gemm import fused_gemm, fused_gemm_grouped
    from repro.kernels.ref import ref_int_gemm_i64
    from repro.quant.qmatmul import _fused_plan_for

    rng = np.random.default_rng(seed)
    m, k, n = KERNEL_SHAPE
    for mode, w in KERNEL_CASES:
        lim = 1 << (w - 1)
        a = rng.integers(-lim, lim, (m, k))
        b = rng.integers(-lim, lim, (k, n))
        plan = _fused_plan_for(KERNEL_SHAPE, w, 8, None)
        tiles = (plan.block_m, plan.block_n, plan.block_k)
        out = fused_gemm(jnp.asarray(a, jnp.int32), jnp.asarray(b, jnp.int32),
                         w=w, mode=mode, block_m=tiles[0], block_n=tiles[1],
                         block_k=tiles[2], combine_int32=True)
        want = np.concatenate(host_map(
            lambda j: ref_int_gemm_i64(a, b[:, j:j + 512]),
            range(0, n, 512)), axis=1)
        check_exact(f"{mode} w={w} {KERNEL_SHAPE} tiles={tiles}", out, want)

    c = SEG * N_SEG
    lim = 1 << (MOE_W - 1)
    a = rng.integers(-lim, lim, (MOE_E, c, MOE_K))
    b = rng.integers(-lim, lim, (MOE_E, MOE_K, MOE_N))
    counts = rng.integers(0, SEG + 1, (MOE_E, N_SEG))
    counts[::7] = 0                       # experts that drew no token
    plan = _fused_plan_for((c, MOE_K, MOE_N), MOE_W, 8, None)
    tiles = (plan.block_m, plan.block_n, plan.block_k)
    out = fused_gemm_grouped(
        jnp.asarray(a, jnp.int32), jnp.asarray(b, jnp.int32),
        counts=jnp.asarray(counts, jnp.int32), w=MOE_W, seg=SEG,
        block_m=tiles[0], block_n=tiles[1], block_k=tiles[2],
        combine_int32=True)
    rows = np.arange(c)
    live = (rows % SEG)[None, :] < counts[:, rows // SEG]      # (E, C)
    want = np.stack(host_map(lambda e: ref_int_gemm_i64(a[e], b[e]),
                             range(MOE_E)))
    want = np.where(live[..., None], want, 0)
    check_exact(f"ragged grouped w={MOE_W} E={MOE_E} "
                f"({c}, {MOE_K}, {MOE_N}) seg={SEG} tiles={tiles} "
                f"live rows {int(live.sum())}/{live.size}", out, want)


def make_requests(vocab: int, seed: int):
    from repro.serve.engine import Request

    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(1, vocab, n).tolist(),
                    max_new_tokens=MAX_NEW, temperature=t)
            for n, t in REQUESTS]


def serve(label: str, cfg, params, context, seed: int):
    """Serve the request set twice on one engine: a cold run (compiles) and
    a warm run (the tokens/s reading).  Returns the warm run's tokens."""
    from repro.serve.engine import Engine

    engine = Engine(cfg, params, max_seq=MAX_SEQ, batch_size=SLOTS,
                    context=context)
    t0 = time.perf_counter()
    cold = make_requests(cfg.vocab_size, seed)
    engine.generate(cold)
    cold_s = time.perf_counter() - t0
    warm = make_requests(cfg.vocab_size, seed)
    stats = engine.generate(warm)
    for r_cold, r_warm in zip(cold, warm):
        if r_cold.temperature == 0.0 and r_cold.generated != r_warm.generated:
            raise AssertionError(f"[{label}] greedy tokens changed between "
                                 f"two runs of one engine")
    print(f"[{label}] cold run {cold_s:.2f} s; warm run "
          f"{stats.generated_tokens} tokens, {stats.tokens_per_s:.1f} "
          f"tokens/s ({SMOKE_NOTE})", flush=True)
    for i, r in enumerate(warm):
        print(f"[{label}] req{i} prompt[{len(r.prompt)}] "
              f"T={r.temperature}: {r.generated}", flush=True)
    return [(r.temperature, r.generated) for r in warm]


def compare(label: str, ours, ref, ref_label: str) -> None:
    for i, ((temp, got), (_, want)) in enumerate(zip(ours, ref)):
        if len(got) != MAX_NEW:
            raise AssertionError(f"[{label}] req{i}: {len(got)} tokens, "
                                 f"expected {MAX_NEW}")
        if temp == 0.0 and got != want:
            raise AssertionError(f"[{label}] req{i}: greedy tokens differ "
                                 f"from {ref_label}: {got} != {want}")
    sampled = [got == want for (temp, got), (_, want) in zip(ours, ref)
               if temp != 0.0]
    n_greedy = len(ours) - len(sampled)
    print(f"[{label}] greedy tokens identical to {ref_label} for all "
          f"{n_greedy} greedy requests; sampled requests identical: "
          f"{sum(sampled)}/{len(sampled)}", flush=True)


def logits_check(label: str, cfg, params, seed: int, ours, ref) -> None:
    """Compare the logits of one prompt between two ways of running the
    model, each a ``(backend, mesh)`` pair (``mesh=None``: one device).

    With random weights, greedy decoding echoes one token per request (the
    tied embedding, scaled by sqrt(d_model), dominates the residual
    stream), so token identity alone says little.  Both runs must stay
    within ``LOGITS_TOL`` of the unquantized model's logits (relative to
    their largest magnitude), and pick the same token at every position.
    Their gap to each other is printed: a last-bit difference ahead of a
    12-bit activation quantizer can flip one rounding step, so two correct
    runs may differ by about as much as quantization moves either one."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.dist.sharding import param_sharding
    from repro.models import lm
    from repro.quant.policy import QuantConfig

    prompt = max(make_requests(cfg.vocab_size, seed), key=lambda r:
                 len(r.prompt)).prompt
    tokens = jnp.asarray([prompt], jnp.int32)

    def logits(quant, mesh=None):
        qcfg = cfg.with_quant(quant)
        fn = jax.jit(lambda p, t: lm.forward_train(p, qcfg, t)[0])
        if mesh is None:
            out = fn(params, tokens)
        else:
            with jax.set_mesh(mesh):
                out = fn(jax.device_put(params, param_sharding(params, mesh)),
                         tokens)
        return np.asarray(out, np.float64)[..., :cfg.vocab_size]

    def gap(x, y):
        return np.abs(x - y).max() / np.abs(y).max()

    full = logits(QuantConfig())
    runs = [logits(dataclasses.replace(cfg.quant, backend=backend), mesh)
            for backend, mesh in (ours, ref)]
    to_full = [gap(x, full) for x in runs]
    same = int((runs[0].argmax(-1) == runs[1].argmax(-1)).sum())
    print(f"[{label}] prompt[{len(prompt)}] logits, max relative gap to "
          f"unquantized: {to_full[0]:.3e} and {to_full[1]:.3e} (limit "
          f"{LOGITS_TOL:.3e}); between the runs {gap(*runs):.3e}; same top "
          f"token at {same}/{len(prompt)} positions", flush=True)
    if same != len(prompt) or max(to_full) >= LOGITS_TOL:
        raise AssertionError(f"[{label}] logits disagree")


def shard_gemm_check(mesh, seed: int) -> None:
    """DESIGN.md §12: each shard runs the unmodified kernel on its block
    with K replicated, so a shard-mapped GEMM is bit-identical to the
    same GEMM on one device.  Shape: a 40-token prompt through
    llama3.2-1b's FFN up-projection, at w12."""
    import jax
    import jax.numpy as jnp

    from repro.core.context import ExecContext
    from repro.quant.qmatmul import quantized_matmul

    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((40, 2048)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((2048, 8192)), jnp.float32)
    single = quantized_matmul(x, w, 12, context=ExecContext(backend="pallas"))
    with jax.set_mesh(mesh):
        sharded = quantized_matmul(
            x, w, 12, context=ExecContext(backend="pallas", mesh=mesh))
    bad = int((np.asarray(single) != np.asarray(sharded)).sum())
    print(f"[four-chips gemm] (40, 2048, 8192) w12: {bad} of {single.size} "
          f"elements differ between the mesh and one device", flush=True)
    if bad:
        raise AssertionError("[four-chips gemm] shard-mapped GEMM is not "
                             "bit-identical to the unsharded one")


def counter_value(name: str, *labels) -> float:
    from repro.obs import metrics as obs_metrics

    metric = obs_metrics.get(name)
    if metric is None:
        return 0.0
    return metric.value(*labels) if labels else metric.total()


def full_size_model(seed: int):
    import jax

    from repro.configs import get_config
    from repro.models import lm

    cfg = get_config(ARCH, quant=QUANT)
    params = lm.init_params(jax.random.PRNGKey(seed), cfg)
    jax.block_until_ready(params)
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"[serve] {ARCH} {QUANT}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
          f"{n} parameters", flush=True)
    return cfg, params


def serve_phase(clock: CompileClock, seed: int) -> None:
    from repro.core.context import ExecContext
    from repro.obs import metrics as obs_metrics

    cfg, params = full_size_model(seed)
    obs_metrics.enable()
    obs_metrics.reset()
    with clock.phase("serve pallas"):
        pallas = serve("serve pallas", cfg, params,
                       ExecContext(backend="pallas"), seed)
    with clock.phase("serve xla"):
        xla = serve("serve xla", cfg, params, ExecContext(backend="xla"),
                    seed)
    compare("serve pallas", pallas, xla, "backend=xla")
    with clock.phase("serve logits"):
        logits_check("serve logits pallas vs xla", cfg, params, seed,
                     ours=("pallas", None), ref=("xla", None))
    routes = "repro_quant_gemm_routes_total"
    n_pallas = counter_value(routes, "pallas", "pallas")
    n_fallback = counter_value(routes, "pallas", "xla_fallback")
    n_declined = counter_value("repro_pallas_fallback_total")
    print(f"[serve] GEMM routes: pallas {n_pallas:.0f}, "
          f"xla_fallback {n_fallback:.0f}; pallas declines "
          f"{n_declined:.0f}", flush=True)
    if n_pallas <= 0 or n_fallback or n_declined:
        raise AssertionError("[serve] quantized GEMMs did not all "
                             "take the pallas route")


def four_chip_phase(clock: CompileClock, seed: int) -> None:
    from repro.core.context import ExecContext
    from repro.dist import shard_gemm  # noqa: F401  (registers its counter)
    from repro.launch.mesh import make_mesh
    from repro.obs import metrics as obs_metrics

    cfg, params = full_size_model(seed)
    obs_metrics.enable()
    obs_metrics.reset()
    mesh = make_mesh((1, 4))
    with clock.phase("four-chips mesh"):
        sharded = serve("four-chips mesh (1, 4)", cfg, params,
                        ExecContext(backend="pallas", mesh=mesh), seed)
    with clock.phase("four-chips meshless"):
        single = serve("four-chips meshless", cfg, params,
                       ExecContext(backend="pallas"), seed)
    compare("four-chips", sharded, single, "the meshless run")
    with clock.phase("four-chips gemm"):
        shard_gemm_check(mesh, seed)
    with clock.phase("four-chips logits"):
        logits_check("four-chips logits mesh vs meshless", cfg, params,
                     seed, ours=("pallas", mesh), ref=("pallas", None))
    n_shard_fallback = counter_value("repro_shard_gemm_fallback_total")
    n_pallas = counter_value("repro_quant_gemm_routes_total", "pallas",
                             "pallas")
    print(f"[four-chips] GEMM routes: pallas {n_pallas:.0f}; shard-map "
          f"fallbacks {n_shard_fallback:.0f}", flush=True)
    if n_pallas <= 0 or n_shard_fallback:
        raise AssertionError("[four-chips] GEMMs fell back from "
                             "shard-mapped pallas execution")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded pallas serving phase on a "
                         "(1, 4) mesh, against a meshless run")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, operands and prompts")
    args = ap.parse_args()
    n_chips = 4 if args.four_chips else 1
    devices = require_tpu(n_chips)

    from repro.launch.compile_cache import enable_compile_cache

    print(f"[setup] device {devices[0].device_kind} x {len(devices)}; "
          f"compile cache {enable_compile_cache()}", flush=True)
    clock = CompileClock()
    if args.four_chips:
        four_chip_phase(clock, args.seed)
    else:
        with clock.phase("kernel"):
            kernel_phase(args.seed)
        serve_phase(clock, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": n_chips}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
