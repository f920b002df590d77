"""Multi-device tests (8 forced host devices, subprocess-isolated so the
main pytest process keeps its single-device view).

Covers: sharding rules, distributed collectives (EF-compressed psum, ring
all-gather matmul, split-K decode attention), and a 2x4-mesh train step.
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
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from repro.configs import get_config
from repro.dist.collectives import (
    ef_compressed_psum, ring_ag_matmul, splitk_decode_attention)
from repro.dist.sharding import param_sharding, cache_sharding
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_train_step, input_specs
from repro.models import lm
from repro.train import optim

mesh = make_mesh((2, 4))
assert len(jax.devices()) == 8

# ---- sharding rules -------------------------------------------------------
cfg = get_config("llama3.2-1b", smoke=True).scaled_down(
    d_model=256, d_ff=1024, vocab_size=2048, n_heads=8,
    n_kv_heads=4, head_dim=32)
params = lm.init_params(jax.random.PRNGKey(0), cfg)
sh = param_sharding(params, mesh)
flat = jax.tree_util.tree_flatten_with_path(sh)[0]
specs = {"/".join(str(getattr(k, 'key', k)) for k in p): s.spec
         for p, s in flat}
# big 2D weights must be sharded on at least one axis
wi = [s for n, s in specs.items() if n.endswith("wi")]
assert any(any(ax is not None for ax in s) for s in wi), specs

# ---- EF-compressed psum ---------------------------------------------------
def psum_fn(x, err):
    return ef_compressed_psum(x, err, "data")

xs = jax.random.normal(jax.random.PRNGKey(1), (8, 64))
errs = jnp.zeros((8, 64))
f = shard_map(psum_fn, mesh=mesh, in_specs=(P(("data", "model")), P(("data", "model"))),
              out_specs=(P(("data", "model")), P(("data", "model"))))
total, new_err = f(xs, errs)
# rows are laid out (data, model): psum over 'data' sums rows m and m+4;
# every data shard then holds that sum.
exact = xs[0:4] + xs[4:8]
got = total[0:4]
rel = float(jnp.abs(got - exact).max() / jnp.abs(exact).max())
assert rel < 0.05, rel   # int8 quantized all-reduce
# error feedback: residual bounded by one quantization step
assert float(jnp.abs(new_err).max()) < float(jnp.abs(xs).max()) / 64

# ---- ring all-gather matmul ------------------------------------------------
w = jax.random.normal(jax.random.PRNGKey(2), (16, 8))
x = jax.random.normal(jax.random.PRNGKey(3), (8, 16))   # rows sharded by 4
ring = shard_map(lambda xs, w: ring_ag_matmul(xs, w, "model"),
                 mesh=mesh, in_specs=(P("model", None), P(None, None)),
                 out_specs=P(None, None), check_vma=False)
out = ring(x, w)
np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w), rtol=1e-4)

# ---- split-K decode attention ----------------------------------------------
B, S, H, D = 2, 32, 4, 16
q = jax.random.normal(jax.random.PRNGKey(4), (B, H, D))
k = jax.random.normal(jax.random.PRNGKey(5), (B, S, H, D))
v = jax.random.normal(jax.random.PRNGKey(6), (B, S, H, D))
valid = jnp.ones((B, S), bool)
fk = shard_map(lambda q, k, v, m: splitk_decode_attention(q, k, v, m, "model"),
               mesh=mesh,
               in_specs=(P(), P(None, "model"), P(None, "model"), P(None, "model")),
               out_specs=P(), check_vma=False)
out = fk(q, k, v, valid)
scores = jnp.einsum("bhd,bshd->bhs", q, k) * (D ** -0.5)
ref = jnp.einsum("bhs,bshd->bhd", jax.nn.softmax(scores, -1), v)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)

# ---- 2x4 mesh train step ----------------------------------------------------
from repro.configs import ShapeCell
cell = ShapeCell("t", 64, 8, "train")
ocfg = optim.AdamWConfig()
specs_in = input_specs(cfg, cell, mesh, ocfg)
step_fn = jax.jit(make_train_step(cfg, ocfg), donate_argnums=(0, 1))
with jax.set_mesh(mesh):
    params = jax.jit(lambda k: lm.init_params(k, cfg),
                     out_shardings=jax.tree.map(lambda a: a.sharding,
                                                specs_in["params"]))(
        jax.random.PRNGKey(0))
    opt_state = optim.init(params)
    batch = {
        "tokens": jnp.zeros((8, 64), jnp.int32),
        "labels": jnp.zeros((8, 64), jnp.int32),
        "mask": jnp.ones((8, 64), jnp.float32),
    }
    batch = {k: jax.device_put(v, NamedSharding(mesh, P("data")))
             for k, v in batch.items()}
    p2, s2, metrics = step_fn(params, opt_state, batch)
    assert np.isfinite(float(metrics["loss"]))

# ---- decode on sharded cache -------------------------------------------------
cache = lm.init_cache(cfg, 8, 64)
cs = cache_sharding(jax.eval_shape(lambda: lm.init_cache(cfg, 8, 64)), mesh, batch=8)
with jax.set_mesh(mesh):
    cache = jax.tree.map(lambda c, s: jax.device_put(c, s), cache, cs)
    logits, cache = jax.jit(
        lambda p, c, tok, t: lm.decode_step(p, cfg, tok, c, t))(
        p2, cache, jnp.zeros((8,), jnp.int32), jnp.int32(3))
    assert np.isfinite(np.asarray(logits, np.float32)).all()

# ---- continuous-batching engine on the 2x4 mesh ------------------------------
from repro.serve.engine import Engine, Request

def serve(mesh_arg):
    rng2 = np.random.default_rng(7)
    reqs = [Request(prompt=list(rng2.integers(1, cfg.vocab_size, size=int(n))),
                    max_new_tokens=int(m))
            for n, m in zip(rng2.integers(2, 9, size=10),
                            rng2.integers(1, 5, size=10))]
    eng = Engine(cfg, jax.device_get(p2), max_seq=32, batch_size=8,
                 mesh=mesh_arg)   # p2: post-step params (params was donated)
    stats = eng.generate(reqs)
    nt = eng.n_traces()["decode"]
    assert nt == -1 or 1 <= nt <= 4, eng.n_traces()
    return [r.generated for r in reqs]

sharded_out = serve(mesh)
assert sharded_out == serve(None), (sharded_out, serve(None))

print("SHARDED-OK")
"""


@pytest.mark.slow
def test_sharded_suite(tmp_path):
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = tmp_path / "sharded_check.py"
    script.write_text(_SCRIPT)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, str(script), src],
                       capture_output=True, text=True, timeout=1200, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    assert "SHARDED-OK" in r.stdout
