"""Pallas kernel tests: shape/dtype sweeps against the pure-jnp oracle
(interpret=True executes the kernel body on CPU)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from _hypothesis_compat import given, settings, strategies as st

from repro.kernels.fused_gemm import fused_gemm
from repro.kernels.ops import int_gemm
from repro.kernels.ref import (
    ref_digit_planes, ref_fused_gemm, ref_int_gemm_i64,
)
from repro.quant.quantize import quantize_symmetric


def _rand_w(rng, w, shape):
    lim = 2 ** (w - 1)
    return rng.integers(-lim, lim, size=shape).astype(np.int32)


SHAPES = [(64, 64, 64), (128, 256, 128), (130, 70, 50), (1, 64, 1)]


@pytest.mark.parametrize("w", [8, 9, 12, 14, 15, 16])
@pytest.mark.parametrize("mkn", SHAPES)
def test_int_gemm_pallas_vs_oracle(w, mkn):
    m, k, n = mkn
    rng = np.random.default_rng(w * 1000 + m)
    a = _rand_w(rng, w, (m, k))
    b = _rand_w(rng, w, (k, n))
    ref = ref_int_gemm_i64(a, b).astype(np.float64)
    out = np.asarray(int_gemm(jnp.array(a), jnp.array(b), w=w,
                              backend="pallas", block_m=64, block_n=64,
                              block_k=64))
    denom = max(np.abs(ref).max(), 1.0)
    assert np.abs(out - ref).max() / denom < 1e-6, (w, mkn)


@pytest.mark.parametrize("w", [8, 12, 16])
def test_int_gemm_xla_matches_pallas(w):
    rng = np.random.default_rng(w)
    a = _rand_w(rng, w, (96, 192))
    b = _rand_w(rng, w, (192, 64))
    xla = np.asarray(int_gemm(jnp.array(a), jnp.array(b), w=w, backend="xla"))
    pal = np.asarray(int_gemm(jnp.array(a), jnp.array(b), w=w,
                              backend="pallas", block_m=32, block_n=32,
                              block_k=64))
    # normalized error: fp32 combine rounds intermediates ~2^w larger than
    # the output (digit-recombination cancellation), so compare against the
    # output scale, not elementwise.
    denom = max(np.abs(xla).max(), 1.0)
    assert np.abs(xla - pal).max() / denom < 1e-5


def test_exact_int32_path():
    rng = np.random.default_rng(7)
    w, k = 10, 128  # within max_exact_k(10) = 2048
    a = _rand_w(rng, w, (64, k))
    b = _rand_w(rng, w, (k, 64))
    out = np.asarray(int_gemm(jnp.array(a), jnp.array(b), w=w,
                              backend="pallas", exact=True,
                              block_m=64, block_n=64, block_k=64))
    np.testing.assert_array_equal(out.astype(np.int64), ref_int_gemm_i64(a, b))


def test_exact_refuses_overflow():
    a = jnp.zeros((8, 4096), jnp.int32)
    b = jnp.zeros((4096, 8), jnp.int32)
    with pytest.raises(ValueError):
        int_gemm(a, b, w=14, exact=True)


class TestDigitPlanes:
    @pytest.mark.parametrize("w", [9, 12, 14, 16])
    def test_planes_reconstruct(self, w):
        rng = np.random.default_rng(w)
        x = _rand_w(rng, w, (256,))
        hi, lo, h, z = ref_digit_planes(jnp.array(x), w)
        recon = (np.asarray(hi).astype(np.int64) << h) + np.asarray(lo) + z
        np.testing.assert_array_equal(recon, x)
        # all planes must be s8-representable (MXU operands)
        for p in (hi, lo):
            assert np.asarray(p).min() >= -128 and np.asarray(p).max() <= 127

    def test_as_plane_fits_s8_up_to_w14(self):
        """The paper's 2m-2 bound: A1+A0c fits s8 for w<=14, not w=16."""
        for w, fits in [(12, True), (14, True), (16, False)]:
            lim = 2 ** (w - 1)
            x = jnp.arange(-lim, lim, max(1, lim // 1024), dtype=jnp.int32)
            hi, lo, h, z = ref_digit_planes(x, w)
            s = np.asarray(hi).astype(np.int32) + np.asarray(lo)
            ok = s.min() >= -128 and s.max() <= 127
            assert ok == fits, (w, s.min(), s.max())


@settings(max_examples=20, deadline=None)
@given(w=st.integers(9, 14), bm=st.sampled_from([16, 32]),
       seed=st.integers(0, 2**31 - 1))
def test_property_kmm2_kernel_tiling_invariance(w, bm, seed):
    """Kernel output must not depend on block shape (tiling correctness)."""
    rng = np.random.default_rng(seed)
    a = _rand_w(rng, w, (64, 128))
    b = _rand_w(rng, w, (128, 64))
    ref = np.asarray(ref_fused_gemm(jnp.array(a), jnp.array(b), w=w,
                                    mode="kmm2", block_k=32,
                                    combine_int32=False))
    out = np.asarray(fused_gemm(jnp.array(a), jnp.array(b), w=w,
                                mode="kmm2", block_m=bm, block_n=32,
                                block_k=32))
    np.testing.assert_allclose(out, ref, rtol=1e-6)


def test_mm2_kernel_vs_planes_oracle():
    rng = np.random.default_rng(0)
    w = 16
    a = _rand_w(rng, w, (64, 128))
    b = _rand_w(rng, w, (128, 64))
    out = np.asarray(fused_gemm(jnp.array(a), jnp.array(b), w=w, mode="mm2",
                                block_m=32, block_n=32, block_k=64))
    ref = np.asarray(ref_fused_gemm(jnp.array(a), jnp.array(b), w=w,
                                    mode="mm2", block_k=64,
                                    combine_int32=False))
    np.testing.assert_allclose(out, ref, rtol=1e-6)


def test_mm1_kernel_exact():
    rng = np.random.default_rng(1)
    a = rng.integers(-128, 128, size=(128, 256)).astype(np.int8)
    b = rng.integers(-128, 128, size=(256, 128)).astype(np.int8)
    out = np.asarray(fused_gemm(jnp.array(a), jnp.array(b), w=8, mode="mm1",
                                block_m=64, block_n=64, block_k=64))
    np.testing.assert_array_equal(out.astype(np.int64), ref_int_gemm_i64(a, b))


def test_quantize_symmetric_roundtrip():
    rng = np.random.default_rng(5)
    x = jnp.array(rng.standard_normal((64, 64)), jnp.float32)
    q, scale = quantize_symmetric(x, 8)
    err = np.abs(np.asarray(q) * np.asarray(scale) - np.asarray(x)).max()
    assert err <= np.asarray(scale) * 0.5 + 1e-7
