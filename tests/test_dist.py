"""Fast (1-device) unit tests for repro.dist: compression round-trips,
ring matmul and split-K attention on trivial meshes, and the sharding rule
table against a fake 2x4 mesh (the real multi-device path is covered by
tests/test_sharded.py)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.dist import sharding as shard
from repro.dist.collectives import (
    ef_compress, ring_ag_matmul, splitk_decode_attention)
from repro.launch.mesh import single_device_mesh


# ---------------------------------------------------------------------------
# ef_compress.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bits", [4, 8])
def test_ef_compress_roundtrip_bounds(bits):
    rng = np.random.default_rng(0)
    x = jnp.array(rng.standard_normal((128,)), jnp.float32)
    err = jnp.zeros((128,))
    for _ in range(3):
        q, scale, err = ef_compress(x, err, bits=bits)
        # residual is at most half a quantization step
        assert float(jnp.abs(err).max()) <= float(scale) * 0.5 + 1e-7
        # lossless round-trip: q * scale + err reconstructs the input
        recon = q.astype(jnp.float32) * scale + err
        np.testing.assert_allclose(np.asarray(recon), np.asarray(x),
                                   atol=float(scale) + 1e-6)
    qmax = 2 ** (bits - 1) - 1
    assert int(jnp.abs(q.astype(jnp.int32)).max()) <= qmax


def test_ef_compress_error_decreases_with_bits():
    rng = np.random.default_rng(1)
    x = jnp.array(rng.standard_normal((256,)), jnp.float32)
    errs = []
    for bits in (4, 6, 8):
        _, _, err = ef_compress(x, jnp.zeros_like(x), bits=bits)
        errs.append(float(jnp.abs(err).max()))
    assert errs[0] > errs[1] > errs[2]


def test_ef_compress_zero_input_safe():
    x = jnp.zeros((16,))
    q, scale, err = ef_compress(x, jnp.zeros_like(x))
    assert float(jnp.abs(err).max()) == 0.0
    assert int(jnp.abs(q.astype(jnp.int32)).max()) == 0


# ---------------------------------------------------------------------------
# Collectives on a 1-device mesh (axis size 1: pure local math).
# ---------------------------------------------------------------------------


def test_ring_ag_matmul_matches_dense():
    mesh = single_device_mesh()
    rng = np.random.default_rng(2)
    x = jnp.array(rng.standard_normal((8, 16)), jnp.float32)
    w = jnp.array(rng.standard_normal((16, 4)), jnp.float32)
    f = shard_map(lambda xs, w: ring_ag_matmul(xs, w, "model"),
                  mesh=mesh, in_specs=(P("model", None), P(None, None)),
                  out_specs=P(None, None), check_vma=False)
    np.testing.assert_allclose(np.asarray(f(x, w)), np.asarray(x @ w),
                               rtol=1e-5, atol=1e-5)


def test_ring_ag_matmul_int_path():
    mesh = single_device_mesh()
    rng = np.random.default_rng(3)
    x = jnp.array(rng.standard_normal((4, 32)), jnp.float32)
    w = jnp.array(rng.standard_normal((32, 8)), jnp.float32)
    f = shard_map(lambda xs, w: ring_ag_matmul(xs, w, "model", w_bits=8),
                  mesh=mesh, in_specs=(P("model", None), P(None, None)),
                  out_specs=P(None, None), check_vma=False)
    ref = np.asarray(x @ w)
    got = np.asarray(f(x, w))
    # int8-quantized operands: first-order quantization noise
    assert np.abs(got - ref).max() < 0.05 * np.abs(ref).max() + 0.05


def test_splitk_decode_attention_matches_softmax():
    mesh = single_device_mesh()
    rng = np.random.default_rng(4)
    B, S, H, D = 2, 16, 4, 8
    q = jnp.array(rng.standard_normal((B, H, D)), jnp.float32)
    k = jnp.array(rng.standard_normal((B, S, H, D)), jnp.float32)
    v = jnp.array(rng.standard_normal((B, S, H, D)), jnp.float32)
    valid = jnp.arange(S)[None, :] < jnp.array([[S], [S // 2]])[:, 0, None]
    f = shard_map(lambda q, k, v, m: splitk_decode_attention(q, k, v, m,
                                                             "model"),
                  mesh=mesh,
                  in_specs=(P(), P(None, "model"), P(None, "model"),
                            P(None, "model")),
                  out_specs=P(), check_vma=False)
    out = f(q, k, v, valid)
    scores = jnp.einsum("bhd,bshd->bhs", q, k) * (D ** -0.5)
    scores = jnp.where(valid[:, None, :], scores, -1e30)
    ref = jnp.einsum("bhs,bshd->bhd", jax.nn.softmax(scores, -1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Sharding rules (spec logic is mesh-shape driven; fake a 2x4 mesh).
# ---------------------------------------------------------------------------


class _FakeMesh:
    """Duck-typed stand-in with the attrs leaf_spec/batch_spec consume."""

    def __init__(self, shape):
        self.axis_names = tuple(shape)
        self.shape = dict(shape)


def _spec(tree, mesh):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    (path, leaf), = flat
    return tuple(shard.leaf_spec(path, leaf, mesh))


def test_param_spec_shapes_2x4():
    mesh = _FakeMesh({"data": 2, "model": 4})
    sds = jax.ShapeDtypeStruct
    # stacked FFN up-proj: (periods, d, ff) -> ff on model, d on data
    assert _spec({"blocks": {"pos0": {"mlp": {
        "wi": sds((3, 256, 1024), jnp.float32)}}}}, mesh) == \
        (None, "data", "model")
    # output proj: row TP, column FSDP
    assert _spec({"blocks": {"pos0": {"attn": {
        "wo": sds((3, 256, 256), jnp.float32)}}}}, mesh) == \
        (None, "model", "data")
    # embedding: vocab-parallel
    assert _spec({"embed": sds((2048, 256), jnp.float32)}, mesh) == \
        ("model", "data")
    # MoE experts ride the model axis; d stays FSDP
    assert _spec({"blocks": {"pos0": {"moe": {
        "wi": sds((3, 4, 256, 512), jnp.float32)}}}}, mesh)[1] == "model"
    # norms replicated
    assert _spec({"ln_f": {"scale": sds((256,), jnp.float32)}}, mesh) == ()


def test_param_spec_divisibility_guard():
    mesh = _FakeMesh({"data": 2, "model": 4})
    sds = jax.ShapeDtypeStruct
    # 255 is not divisible by 2, 1022 not by 4: both dims drop their axis
    assert _spec({"mlp": {"wi": sds((255, 1022), jnp.float32)}}, mesh) == \
        (None, None)


def test_batch_spec_axes():
    assert tuple(shard.batch_spec(_FakeMesh({"data": 2, "model": 4}))) == \
        ("data",)
    assert tuple(shard.batch_spec(
        _FakeMesh({"pod": 2, "data": 4, "model": 2}))) == (("pod", "data"),)
    assert len(shard.batch_spec(_FakeMesh({"model": 8}))) == 0


def test_param_sharding_tree_matches_params():
    from repro.configs import get_config
    from repro.models import lm

    cfg = get_config("llama3.2-1b", smoke=True)
    mesh = single_device_mesh()
    shapes = jax.eval_shape(lambda k: lm.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    sh = shard.param_sharding(shapes, mesh)
    assert jax.tree_util.tree_structure(sh) == \
        jax.tree_util.tree_structure(shapes)
    for s, l in zip(jax.tree_util.tree_leaves(sh),
                    jax.tree_util.tree_leaves(shapes)):
        assert len(s.spec) <= len(l.shape)


def test_cache_sharding_tree():
    from repro.configs import get_config
    from repro.models import lm

    cfg = get_config("llama3.2-1b", smoke=True)
    mesh = single_device_mesh()
    shapes = jax.eval_shape(lambda: lm.init_cache(cfg, 4, 32))
    cs = shard.cache_sharding(shapes, mesh, batch=4)
    assert jax.tree_util.tree_structure(cs) == \
        jax.tree_util.tree_structure(shapes)


def test_constrain_batch_dim_noop_outside_mesh():
    x = jnp.ones((4, 8))
    assert shard.constrain_batch_dim(x) is x


# ---------------------------------------------------------------------------
# Serve engine wiring (mesh-aware path on one device).
# ---------------------------------------------------------------------------


def test_engine_mesh_matches_unsharded():
    from repro.configs import get_config
    from repro.models import lm
    from repro.serve.engine import Engine, Request

    cfg = get_config("llama3.2-1b", smoke=True).scaled_down(
        d_model=64, d_ff=128, vocab_size=256, n_heads=4, n_kv_heads=2,
        head_dim=16)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)

    def run(mesh):
        reqs = [Request(prompt=[1, 2, 3], max_new_tokens=4)]
        Engine(cfg, params, max_seq=32, mesh=mesh).generate(reqs)
        return reqs[0].generated

    assert run(None) == run(single_device_mesh())
