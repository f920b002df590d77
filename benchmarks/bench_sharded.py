"""Sharded-GEMM benchmark: shard-mapped fused Pallas kernel vs sharded XLA.

Runs serve-sized quantized GEMMs on a 2x4 (data, model) host-device mesh —
the same ``quantized_matmul`` entry the models call, once with
``ExecContext(backend="pallas", mesh=mesh)`` (the shard_map path of
DESIGN.md §12: each shard runs the fused kernel on its local block) and
once with ``backend="xla"`` under the mesh (GSPMD partitions the
dot_generals).  The two are asserted allclose before timing, so the rows
compare equal-output execution paths.

The measurement needs 8 host devices, which must be configured before jax
initializes — so :func:`run` re-executes this module in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` and parses its JSON.
On this CPU container the Pallas kernel runs in interpret mode, so the
absolute ratio is not a TPU prediction; the rows track that the sharded
path exists, stays correct, and how its overhead trends across PRs (no
CHECK gates pallas beating XLA here).

    PYTHONPATH=src python -m benchmarks.bench_sharded
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Dict, List

# Serve-sized quantized GEMMs (decode batch x d_model x d_ff / vocab slice):
# M divides the data axis (2), N divides the model axis (4).
SHAPES = ((32, 256, 1024), (8, 256, 2048))
W = 12
REPS = 5
MESH_SHAPE = (2, 4)
_WORKER_FLAG = "--worker"
_MARK = "BENCH_SHARDED_JSON:"


def _worker() -> List[Dict]:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.context import ExecContext
    from repro.launch.mesh import make_mesh
    from repro.quant.qmatmul import quantized_matmul

    mesh = make_mesh(MESH_SHAPE)
    rows = []
    rng = np.random.default_rng(0)
    for m, k, n in SHAPES:
        x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
        wm = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
        x = jax.device_put(x, NamedSharding(mesh, P("data", None)))
        wm = jax.device_put(wm, NamedSharding(mesh, P(None, "model")))
        ctx_pallas = ExecContext(backend="pallas", mesh=mesh)
        ctx_xla = ExecContext(backend="xla")
        f_pallas = jax.jit(
            lambda x, wm: quantized_matmul(x, wm, W, context=ctx_pallas))
        f_xla = jax.jit(
            lambda x, wm: quantized_matmul(x, wm, W, context=ctx_xla))
        with jax.set_mesh(mesh):
            out_p = f_pallas(x, wm)
            out_x = f_xla(x, wm)
            assert np.allclose(np.asarray(out_p), np.asarray(out_x),
                               rtol=1e-5, atol=1e-5), \
                f"sharded pallas != sharded xla at {(m, k, n)}"
            best = {"pallas": float("inf"), "xla": float("inf")}
            for _ in range(REPS):
                for name, f in (("pallas", f_pallas), ("xla", f_xla)):
                    t0 = time.perf_counter()
                    f(x, wm).block_until_ready()
                    best[name] = min(best[name],
                                     (time.perf_counter() - t0) * 1e6)
        tag = f"{m}x{k}x{n}"
        rows.append({"bench": "sharded",
                     "name": f"sharded_pallas_w{W}_{tag}",
                     "us_per_call": round(best["pallas"], 1),
                     "mesh": "x".join(map(str, MESH_SHAPE)), "shape": tag})
        rows.append({"bench": "sharded",
                     "name": f"sharded_xla_w{W}_{tag}",
                     "us_per_call": round(best["xla"], 1),
                     "mesh": "x".join(map(str, MESH_SHAPE)), "shape": tag})
        rows.append({"bench": "sharded",
                     "name": f"sharded_pallas_over_xla_time_ratio_{tag}",
                     "us_per_call": round(best["pallas"] / best["xla"], 3),
                     "shape": tag,
                     "note": "interpret-mode pallas on CPU; not a TPU "
                             "prediction"})
    return rows


def run() -> List[Dict]:
    """Spawn the 8-host-device subprocess and collect its rows."""
    env = os.environ.copy()
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_sharded", _WORKER_FLAG],
        env=env, capture_output=True, text=True, timeout=1200)
    if proc.returncode != 0:
        raise RuntimeError(
            f"sharded bench subprocess failed:\n{proc.stdout}\n{proc.stderr}")
    for line in proc.stdout.splitlines():
        if line.startswith(_MARK):
            return json.loads(line[len(_MARK):])
    raise RuntimeError(f"sharded bench emitted no rows:\n{proc.stdout}")


def checks(rows: List[Dict]):
    by_side = {"pallas": 0, "xla": 0}
    for r in rows:
        for side in by_side:
            if r["name"].startswith(f"sharded_{side}_"):
                by_side[side] += 1
    return [("sharded pallas vs sharded XLA measured on "
             f">= {len(SHAPES)} serve-sized shapes (2x4 mesh, equal outputs)",
             all(v >= len(SHAPES) for v in by_side.values()),
             ";".join(f"{r['name']}={r['us_per_call']}us" for r in rows
                      if "ratio" not in r["name"]))]


if __name__ == "__main__":
    if _WORKER_FLAG in sys.argv:
        print(_MARK + json.dumps(_worker()))
    else:
        rows = run()
        for r in rows:
            print(r)
        for claim, ok, detail in checks(rows):
            print(f"CHECK {'PASS' if ok else 'FAIL'}: {claim} [{detail}]")
