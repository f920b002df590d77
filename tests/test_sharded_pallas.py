"""Sharded Pallas kernel tests (PR-6 tentpole acceptance), subprocess-
isolated with 8 forced host devices like tests/test_sharded.py.

Covers:
  * capability negotiation unit behaviour (axes picked / reasons given);
  * kernel-level bit-identity: the fused KMM2 kernel shard-mapped over a
    2x4 mesh == the unsharded fused kernel, bit-for-bit (fp32 w12 class
    AND exact w8 class, whose integer accumulator is the int64 oracle's);
  * engine token-identity: quantized serve with backend="pallas" on the
    2x4 mesh == the same engine unsharded (and the XLA backend for w8);
  * capability-negotiation fallback: a (1, 8) mesh with a d_ff the model
    axis cannot tile downgrades the MLP GEMMs to XLA (logged) while the
    rest stay shard-mapped — tokens still identical to unsharded.
"""
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, sys.argv[1])
import logging
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.context import ExecContext
from repro.dist import shard_gemm as sg
from repro.dist.shard_gemm import GemmShardSpec
from repro.kernels.ref import ref_int_gemm_i64
from repro.launch.mesh import make_mesh
from repro.quant.qmatmul import quantized_matmul, quantized_matmul_batched

mesh = make_mesh((2, 4))
assert len(jax.devices()) == 8

# ---- negotiate: axes and reasons ------------------------------------------
spec, reason = sg.negotiate((32, 256, 1024), mesh)
assert spec == GemmShardSpec(m_axes=("data",), n_axes=("model",)), spec
assert sg.local_shape((32, 256, 1024), spec, mesh) == (16, 256, 256)
spec, reason = sg.negotiate((33, 256, 1025), mesh)   # neither axis divides
assert spec is None and "1025" in reason, (spec, reason)
spec, reason = sg.negotiate((33, 256, 1024), mesh)   # N-only sharding
assert spec == GemmShardSpec(n_axes=("model",)), spec
spec, reason = sg.negotiate((8, 64, 96), mesh, n_experts=8)
assert spec == GemmShardSpec(e_axes=("model",)), spec
spec, reason = sg.negotiate((8, 64, 96), mesh, n_experts=6)
assert spec is None and "expert" in reason, (spec, reason)
assert sg.negotiate((32, 256, 1024), None)[0] is None

# ---- kernel-level bit-identity: fp32 w12 class ----------------------------
rng = np.random.default_rng(0)
M, K, N = 32, 256, 1024
x = jnp.asarray(rng.standard_normal((M, K)), jnp.float32)
wm = jnp.asarray(rng.standard_normal((K, N)), jnp.float32)
unsharded = quantized_matmul(x, wm, 12, context=ExecContext(backend="pallas"))
xs = jax.device_put(x, NamedSharding(mesh, P("data", None)))
ws = jax.device_put(wm, NamedSharding(mesh, P(None, "model")))
with jax.set_mesh(mesh):
    sharded = quantized_matmul(xs, ws, 12,
                               context=ExecContext(backend="pallas",
                                                   mesh=mesh))
assert np.array_equal(np.asarray(sharded), np.asarray(unsharded)), \
    "sharded fused w12 != unsharded (fp32 class must be bit-exact)"

# ---- kernel-level bit-identity: exact w8 class vs int64 oracle ------------
# Quantized operands with unit scales: the dequant epilogue multiplies the
# int32 accumulator by 1.0, so the output is the integer product itself.
a8 = jnp.asarray(rng.integers(-127, 128, (M, K)), jnp.float32)
b8 = jnp.asarray(rng.integers(-127, 128, (K, N)), jnp.float32)
a8 = a8.at[:, 0].set(127.0)                 # per-row abs-max 127 -> scale 1
b8 = b8.at[0, :].set(127.0)                 # per-col abs-max 127 -> scale 1
unsharded8 = quantized_matmul(a8, b8, 8, context=ExecContext(backend="pallas"))
with jax.set_mesh(mesh):
    out8 = quantized_matmul(
        jax.device_put(a8, NamedSharding(mesh, P("data", None))),
        jax.device_put(b8, NamedSharding(mesh, P(None, "model"))), 8,
        context=ExecContext(backend="pallas", mesh=mesh))
oracle = ref_int_gemm_i64(np.asarray(a8), np.asarray(b8))
assert np.array_equal(np.asarray(out8), np.asarray(unsharded8)), \
    "M/N-sharded exact w8 != unsharded"
assert np.array_equal(np.asarray(out8).astype(np.int64), oracle), \
    "M/N-sharded exact w8 != int64 oracle"

# ---- grouped expert GEMM under the mesh -----------------------------------
E, C = 8, 8
xb = jnp.asarray(rng.standard_normal((E, C, 64)), jnp.float32)
wb = jnp.asarray(rng.standard_normal((E, 64, 96)), jnp.float32)
unsharded_b = quantized_matmul_batched(xb, wb, 12,
                                       context=ExecContext(backend="pallas"))
with jax.set_mesh(mesh):
    sharded_b = quantized_matmul_batched(
        xb, wb, 12, context=ExecContext(backend="pallas", mesh=mesh))
assert np.array_equal(np.asarray(sharded_b), np.asarray(unsharded_b)), \
    "expert-sharded grouped kernel != unsharded"

# ---- capability fallback logs a reason, computes via XLA ------------------
records = []
handler = logging.Handler()
handler.emit = lambda rec: records.append(rec.getMessage())
logging.getLogger("repro.dist").addHandler(handler)
logging.getLogger("repro.dist").setLevel(logging.INFO)
x_odd = jnp.asarray(rng.standard_normal((M, K)), jnp.float32)
w_odd = jnp.asarray(rng.standard_normal((K, 1025)), jnp.float32)
with jax.set_mesh(mesh):
    out_odd = quantized_matmul(
        x_odd, w_odd, 12, context=ExecContext(backend="pallas", mesh=mesh))
ref_odd = quantized_matmul(x_odd, w_odd, 12)   # xla, default context
# M=32 divides data(2): negotiation shards M-only and the kernel still runs
np.testing.assert_allclose(np.asarray(out_odd), np.asarray(ref_odd),
                           rtol=1e-5, atol=1e-5)
# force a total fallback with an indivisible M too:
x_np = jnp.asarray(rng.standard_normal((33, K)), jnp.float32)
with jax.set_mesh(mesh):
    out_np = quantized_matmul(
        x_np, w_odd, 12, context=ExecContext(backend="pallas", mesh=mesh))
assert any("falls back to XLA" in m for m in records), records
ref_np = quantized_matmul(x_np, w_odd, 12)
np.testing.assert_allclose(np.asarray(out_np), np.asarray(ref_np),
                           rtol=1e-5, atol=1e-5)

# ---- engine token-identity on the 2x4 mesh --------------------------------
from repro.configs import get_config
from repro.models import lm
from repro.serve.engine import Engine, Request

cfg = get_config("llama3.2-1b", smoke=True, quant="w8").scaled_down(
    d_model=256, d_ff=1024, vocab_size=2048, n_heads=8,
    n_kv_heads=4, head_dim=32)
params = lm.init_params(jax.random.PRNGKey(0), cfg)

def serve(cfg, backend, mesh_arg):
    rng2 = np.random.default_rng(7)
    reqs = [Request(prompt=list(rng2.integers(1, cfg.vocab_size, size=int(n))),
                    max_new_tokens=int(m), temperature=t)
            for n, m, t in zip(rng2.integers(2, 9, size=6),
                               rng2.integers(1, 4, size=6),
                               (0.0, 0.8, 0.0, 0.7, 0.0, 0.9))]
    eng = Engine(cfg, params, max_seq=32, batch_size=8,
                 context=ExecContext(backend=backend, mesh=mesh_arg))
    eng.generate(reqs)
    nt = eng.n_traces()["decode"]
    assert nt == -1 or 1 <= nt <= 4, eng.n_traces()
    return [r.generated for r in reqs]

pallas_sharded = serve(cfg, "pallas", mesh)
pallas_unsharded = serve(cfg, "pallas", None)
assert pallas_sharded == pallas_unsharded, \
    (pallas_sharded, pallas_unsharded)
# w8 is in the exact-int class: XLA tokens must agree too
assert pallas_sharded == serve(cfg, "xla", mesh)

# ---- capability-negotiation fallback at the engine level ------------------
# (1, 8) mesh: no data parallelism, and a d_ff of 1020 is not divisible by
# the model axis -> the MLP wi/wg GEMMs must downgrade to XLA while the
# remaining GEMMs (N = 256 / padded vocab, both % 8 == 0) stay shard-mapped.
mesh18 = make_mesh((1, 8))
cfg_odd = cfg.scaled_down(d_ff=1020)
params = lm.init_params(jax.random.PRNGKey(0), cfg_odd)
records.clear()
mixed = serve(cfg_odd, "pallas", mesh18)
assert any("falls back to XLA" in m and "1020" in m for m in records), \
    records
assert mixed == serve(cfg_odd, "pallas", None), "fallback changed tokens"

print("SHARDED-PALLAS-OK")
"""


@pytest.mark.slow
def test_sharded_pallas_suite(tmp_path):
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = tmp_path / "sharded_pallas_check.py"
    script.write_text(_SCRIPT)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, str(script), src],
                       capture_output=True, text=True, timeout=1800, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    assert "SHARDED-PALLAS-OK" in r.stdout
