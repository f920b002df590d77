"""ExecContext API tests: the unified execution-context bundle and the
deprecation shims that keep the scattered legacy kwargs working.

Covers (PR-6 acceptance): old kwargs == new context bit-for-bit on every
former ``backend=``/``quant_backend=``/``force_mode=`` entry point, exactly
one ``DeprecationWarning`` per legacy call (listing the kwargs), and
``TypeError`` when context and legacy kwargs are mixed.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.context import ExecContext, resolve_context
from repro.kernels import ops
from repro.quant.qmatmul import (
    prequant_matmul, quantized_matmul, quantized_matmul_batched,
)


@pytest.fixture(scope="module")
def operands():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (16, 64), jnp.float32)
    wm = jax.random.normal(jax.random.PRNGKey(1), (64, 32), jnp.float32)
    return x, wm


def _one_deprecation(rec):
    deps = [w for w in rec if issubclass(w.category, DeprecationWarning)]
    assert len(deps) == 1, [str(w.message) for w in rec]
    return str(deps[0].message)


# ---------------------------------------------------------------------------
# ExecContext itself.
# ---------------------------------------------------------------------------


def test_context_validates_fields():
    with pytest.raises(ValueError, match="backend"):
        ExecContext(backend="cuda")
    with pytest.raises(ValueError, match="force_mode"):
        ExecContext(force_mode="kmm3")


def test_context_hashable_and_table_excluded_from_eq():
    a = ExecContext(backend="pallas")
    b = ExecContext(backend="pallas")
    # equal contexts are interchangeable as jit static args / cache keys
    assert a == b and hash(a) == hash(b)
    assert a != ExecContext(backend="xla")
    jax.jit(lambda x: x + 1, static_argnames=())  # smoke: hashability used
    d = {a: 1}
    assert d[b] == 1


def test_context_replace():
    ctx = ExecContext(backend="pallas").replace(force_mode="mm2")
    assert ctx.backend == "pallas" and ctx.force_mode == "mm2"


def test_resolve_context_passthrough_and_defaults():
    ctx = ExecContext(backend="pallas")
    assert resolve_context(ctx, what="t") is ctx
    assert resolve_context(None, what="t") == ExecContext()
    seeded = ExecContext(backend="pallas", force_mode="mm2")
    assert resolve_context(None, what="t", _defaults=seeded) is seeded


def test_resolve_context_legacy_folds_and_warns_once():
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        ctx = resolve_context(None, what="thing", backend="pallas",
                              force_mode="mm2")
    msg = _one_deprecation(rec)
    assert "thing" in msg and "backend" in msg and "force_mode" in msg
    assert ctx.backend == "pallas" and ctx.force_mode == "mm2"


def test_resolve_context_rejects_mixed():
    with pytest.raises(TypeError, match="not both"):
        resolve_context(ExecContext(), what="t", backend="pallas")


# ---------------------------------------------------------------------------
# Shim equivalence: legacy kwargs == context, warning raised.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_quantized_matmul_shim_equivalence(operands, backend):
    x, wm = operands
    new = quantized_matmul(x, wm, 12, context=ExecContext(backend=backend))
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        old = quantized_matmul(x, wm, 12, 8, "auto", backend)
    assert "quantized_matmul" in _one_deprecation(rec)
    assert np.array_equal(np.asarray(new), np.asarray(old))


def test_quantized_matmul_mixed_raises(operands):
    x, wm = operands
    with pytest.raises(TypeError, match="not both"):
        quantized_matmul(x, wm, 8, backend="xla", context=ExecContext())


def test_quantized_matmul_batched_shim_equivalence(operands):
    xb = jax.random.normal(jax.random.PRNGKey(2), (2, 8, 64), jnp.float32)
    wb = jax.random.normal(jax.random.PRNGKey(3), (2, 64, 32), jnp.float32)
    new = quantized_matmul_batched(xb, wb, 8,
                                   context=ExecContext(backend="pallas"))
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        old = quantized_matmul_batched(xb, wb, 8, backend="pallas")
    _one_deprecation(rec)
    assert np.array_equal(np.asarray(new), np.asarray(old))


def test_prequant_matmul_shim_equivalence(operands):
    from repro.quant.policy import POLICY_W8
    from repro.quant.prequant import prequantize

    x, wm = operands
    rec_w = prequantize({"wi": wm}, POLICY_W8)["wi"]
    new = prequant_matmul(x, rec_w, 8, context=ExecContext(backend="pallas"))
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        old = prequant_matmul(x, rec_w, 8, backend="pallas")
    _one_deprecation(rec)
    assert np.array_equal(np.asarray(new), np.asarray(old))


def test_force_mode_via_context(operands):
    x, wm = operands
    new = quantized_matmul(x, wm, 12, context=ExecContext(force_mode="mm2"))
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        old = quantized_matmul(x, wm, 12, 8, "mm2")
    _one_deprecation(rec)
    assert np.array_equal(np.asarray(new), np.asarray(old))


def test_int_gemm_context(operands):
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(-100, 100, (16, 32)), jnp.int32)
    b = jnp.asarray(rng.integers(-100, 100, (32, 16)), jnp.int32)
    via_ctx = ops.int_gemm(a, b, w=8,
                           context=ExecContext(backend="pallas"))
    via_kwarg = ops.int_gemm(a, b, w=8, backend="pallas")
    assert np.array_equal(np.asarray(via_ctx), np.asarray(via_kwarg))


# ---------------------------------------------------------------------------
# Engine shim.
# ---------------------------------------------------------------------------


def test_engine_shim_equivalence():
    from repro.configs import get_config
    from repro.models import lm
    from repro.serve.engine import Engine, Request

    cfg = get_config("llama3.2-1b", smoke=True, quant="w8").scaled_down(
        d_model=64, d_ff=128, vocab_size=256, n_heads=4, n_kv_heads=2,
        head_dim=16)
    params = lm.init_params(jax.random.PRNGKey(7), cfg)

    def run(**kw):
        eng = Engine(cfg, params, max_seq=32, batch_size=2, rng_seed=3, **kw)
        reqs = [Request(prompt=[5, 6, 7], max_new_tokens=3),
                Request(prompt=[9] * 6, max_new_tokens=2, temperature=0.7)]
        eng.generate(reqs)
        return eng, [r.generated for r in reqs]

    eng_new, toks_new = run(context=ExecContext(backend="pallas"))
    assert eng_new.context.backend == "pallas"
    assert eng_new.cfg.quant.backend == "pallas"
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        _, toks_old = run(quant_backend="pallas")
    assert "Engine" in _one_deprecation(rec)
    assert toks_new == toks_old
    with pytest.raises(TypeError, match="not both"):
        run(quant_backend="pallas", context=ExecContext())
