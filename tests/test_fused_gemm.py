"""Fused single-pass kernel (kernels/fused_gemm.py): bit-identity against the
pure-jnp mirror (ref.ref_fused_gemm) and the int64 oracle across hostile
tile/padding combos and every width the kernel serves, the exact-int32
boundary, the grouped expert grid, the dequant epilogue, and the kernel's
own plan (plan_for) at the shapes the benchmark serves.
"""
import functools
import importlib.util
import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core.kmm import max_exact_k
from repro.kernels import ops
from repro.kernels.fused_gemm import (
    DEFAULT_TILES, VMEM_BUDGET, _fused_call, digit_range, dot_path,
    fused_gemm, fused_gemm_grouped, int8_digits, int8_tiles, kernel_mode,
    plan_for, pow2_cover, vmem_footprint, zero_point_sums_fit,
)
from repro.kernels.ref import ref_fused_gemm, ref_int_gemm_i64
from repro.obs import metrics as obs_metrics
from repro.quant.qmatmul import (
    prequant_matmul, quantized_matmul, quantized_matmul_batched,
)

# Non-multiple M/N/K, 1-row/1-col extremes, K-padding that exercises the
# z-correction on padded rows (split(0) = (0, -z) must cancel exactly).
HOSTILE_SHAPES = [(33, 70, 17), (1, 64, 1), (130, 70, 50)]
TILE_COMBOS = [(32, 32, 32), (64, 32, 64), (32, 64, 256)]

# The mirror, jitted like the kernel wrapper.
_mirror = jax.jit(ref_fused_gemm,
                  static_argnames=("w", "mode", "block_k", "combine_int32"))


def _operands(shape, w: int, seed: int = 0):
    """Random signed w-bit operands for an (M, K) x (K, N) problem."""
    m, k, n = shape
    rng = np.random.default_rng(seed)
    lim = 2 ** (w - 1)
    a = rng.integers(-lim, lim, size=(m, k)).astype(np.int32)
    b = rng.integers(-lim, lim, size=(k, n)).astype(np.int32)
    return jnp.asarray(a), jnp.asarray(b)


def _kernel_and_mirror(a, b, *, w, mode, tiles, combine_int32):
    bm, bn, bk = tiles
    out = np.asarray(fused_gemm(a, b, w=w, mode=mode, block_m=bm,
                                block_n=bn, block_k=bk,
                                combine_int32=combine_int32))
    mirror = np.asarray(_mirror(a, b, w=w, mode=mode, block_k=bk,
                                combine_int32=combine_int32))
    return out, mirror


# ---------------------------------------------------------------------------
# Bit-identity vs the jnp mirror + the int64 oracle.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("w", [4, 8, 12, 14])
@pytest.mark.parametrize("mkn", HOSTILE_SHAPES)
def test_fused_bit_identical_to_mirror(w, mkn):
    """Same padding: the fused kernel must reproduce the pure-jnp mirror
    bit-for-bit — fp32 combine included (identical operation sequence, not
    a tolerance) — and the int64 oracle in the exact MM1 window."""
    a, b = _operands(mkn, w, seed=w)
    oracle = ref_int_gemm_i64(np.asarray(a), np.asarray(b))
    mode = "mm1" if w <= 8 else "kmm2"
    for tiles in TILE_COMBOS:
        out, mirror = _kernel_and_mirror(a, b, w=w, mode=mode, tiles=tiles,
                                         combine_int32=w <= 8)
        assert np.array_equal(out, mirror), \
            f"fused != jnp mirror at w={w} tiles={tiles}"
        if w <= 8:
            np.testing.assert_array_equal(out.astype(np.int64), oracle)


def _i64_oracle_atol(w: int, k: int) -> float:
    """fp32-combine noise floor vs the int64 oracle: casting an int32 digit
    accumulator (|value| <= K * 2^(2w-2) per digit pair) to fp32 rounds at
    2^-24 relative; a few combine ops keep the error within a small
    multiple.  Real correction bugs (a dropped z*colsum or z^2*K term) sit
    orders of magnitude above this at the test shapes."""
    return max(1.0, k * 2.0 ** (2 * w) * 2.0 ** -24)


@pytest.mark.parametrize("w", [14, 15, 16])
@pytest.mark.parametrize("mkn", HOSTILE_SHAPES)
def test_fused_mm2_bit_identical_to_mirror(w, mkn):
    """The single-pass MM2 boundary mode (w = 2m-1, 2m): the kernel must
    reproduce the pure-jnp mirror bit-for-bit and sit at the fp32 noise
    floor of the int64 oracle.  w=14 runs the 4-pass mode *inside* the
    KMM2 window — the mode is valid anywhere in (m, 2m], not just on the
    boundary."""
    a, b = _operands(mkn, w, seed=w)
    oracle = ref_int_gemm_i64(np.asarray(a), np.asarray(b))
    for tiles in TILE_COMBOS:
        out, mirror = _kernel_and_mirror(a, b, w=w, mode="mm2", tiles=tiles,
                                         combine_int32=False)
        assert np.array_equal(out, mirror), \
            f"fused mm2 != jnp mirror at w={w} tiles={tiles}"
        np.testing.assert_allclose(
            out.astype(np.float64), oracle, rtol=0,
            atol=_i64_oracle_atol(w, mkn[1]),
            err_msg=f"fused mm2 off the oracle at w={w} tiles={tiles}")


@pytest.mark.parametrize("w", [8, 12, 15, 20])
@pytest.mark.parametrize("mkn", HOSTILE_SHAPES)
def test_fused_depth2_bit_identical_to_mirror(w, mkn):
    """Depth-2 fused recursion (9 MXU passes, nested Fig. 8 pre-adders in
    VMEM): bit-identical to the two-level jnp mirror, fp32-noise-close to
    the int64 oracle.  Depth 2 is forced below its analytic window too
    (w=8/12/15) — the nested split must be valid anywhere
    ``kmm_levels_needed(w, m) <= 2``."""
    a, b = _operands(mkn, w, seed=w)
    oracle = ref_int_gemm_i64(np.asarray(a), np.asarray(b))
    for tiles in TILE_COMBOS:
        out, mirror = _kernel_and_mirror(a, b, w=w, mode="kmm4", tiles=tiles,
                                         combine_int32=False)
        assert np.array_equal(out, mirror), \
            f"fused d2 != jnp mirror at w={w} tiles={tiles}"
        np.testing.assert_allclose(
            out.astype(np.float64), oracle, rtol=0,
            atol=_i64_oracle_atol(w, mkn[1]),
            err_msg=f"fused d2 off the oracle at w={w} tiles={tiles}")


def test_fused_analytic_default_covers_windows():
    """The kernel's plan follows the paper's rule: mm1 (exact int32) for
    the MM1 window, kmm2 for KMM2, mm2 on the (2m-2, 2m] boundary, kmm4
    for 4-digit recursion; depth >= 3 is not the kernel's."""
    shape = (16, 32, 16)
    for w in (4, 8):
        plan = plan_for(shape, w)
        assert plan.mode == "mm1" and plan.combine_int32
    for w, mode in ((9, "kmm2"), (12, "kmm2"), (14, "kmm2"), (15, "mm2"),
                    (16, "mm2"), (17, "kmm4"), (20, "kmm4"), (26, "kmm4")):
        plan = plan_for(shape, w)
        assert plan.mode == mode and not plan.combine_int32, (w, plan)
        exact = plan_for(shape, w, exact=True)
        assert (exact is None) == (max_exact_k(w) < shape[1]), w
        assert exact is None or exact.combine_int32
    assert kernel_mode(28) is None and plan_for(shape, 28) is None


# ---------------------------------------------------------------------------
# Exact-int32 mode at the max_exact_k boundary.
# ---------------------------------------------------------------------------


def test_fused_exact_int32_at_max_exact_k_boundary():
    w = 12
    k = max_exact_k(w)                       # 128: the tight int32 ceiling
    a, b = _operands((16, k, 16), w, seed=3)
    assert plan_for((16, k, 16), w, exact=True) is not None
    out = np.asarray(fused_gemm(a, b, w=w, block_m=32, block_n=32,
                                block_k=32, combine_int32=True))
    assert out.dtype == np.int32
    np.testing.assert_array_equal(
        out.astype(np.int64),
        ref_int_gemm_i64(np.asarray(a), np.asarray(b)))
    # one past the boundary: the plan must decline an exact run, and the
    # int_gemm API must refuse an exact request outright
    assert plan_for((16, k + 1, 16), w, exact=True) is None
    with pytest.raises(ValueError, match="max exact K"):
        ops.int_gemm(jnp.zeros((16, k + 1), jnp.int32),
                     jnp.zeros((k + 1, 16), jnp.int32),
                     w=w, backend="pallas", exact=True)


# ---------------------------------------------------------------------------
# Grouped expert grid vs a per-expert loop.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("w", [8, 12])
def test_fused_grouped_matches_per_expert_loop(w):
    e, c, k, n = 3, 10, 70, 9
    rng = np.random.default_rng(w)
    lim = 2 ** (w - 1)
    a = jnp.asarray(rng.integers(-lim, lim, (e, c, k)), jnp.int32)
    b = jnp.asarray(rng.integers(-lim, lim, (e, k, n)), jnp.int32)
    kw = dict(w=w, block_m=32, block_n=32, block_k=32)
    grouped = np.asarray(fused_gemm_grouped(a, b, **kw))
    for i in range(e):
        single = np.asarray(fused_gemm(a[i], b[i], **kw))
        np.testing.assert_array_equal(grouped[i], single,
                                      err_msg=f"expert {i} diverged")
        if w <= 8:
            np.testing.assert_array_equal(
                grouped[i].astype(np.int64),
                ref_int_gemm_i64(np.asarray(a[i]), np.asarray(b[i])))


def test_fused_grouped_dequant_epilogue():
    e, c, k, n = 2, 6, 33, 5
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(-2048, 2048, (e, c, k)), jnp.int32)
    b = jnp.asarray(rng.integers(-2048, 2048, (e, k, n)), jnp.int32)
    sx = jnp.asarray(rng.random((e, c, 1)), jnp.float32)
    sw = jnp.asarray(rng.random((e, 1, n)), jnp.float32)
    kw = dict(w=12, block_m=32, block_n=32, block_k=32)
    out = np.asarray(fused_gemm_grouped(a, b, sx, sw, **kw))
    acc = np.asarray(fused_gemm_grouped(a, b, **kw))
    np.testing.assert_array_equal(out, acc * np.asarray(sx * sw))


@pytest.mark.parametrize("w", [8, 12])
def test_fused_grouped_ragged_counts_property(w):
    """Ragged contract: with (E, S) live counts and static seg, every live
    row is bit-identical to the dense per-expert result and every dead row
    is an exact zero — including experts with zero live tokens in a
    segment and a fully-dead expert.  Accumulation is untouched (output
    masking only), so liveness never changes a live row's bits."""
    e, seg, n_seg, k, n = 4, 8, 3, 70, 9
    c = seg * n_seg
    rng = np.random.default_rng(w)
    lim = 2 ** (w - 1)
    a = jnp.asarray(rng.integers(-lim, lim, (e, c, k)), jnp.int32)
    b = jnp.asarray(rng.integers(-lim, lim, (e, k, n)), jnp.int32)
    sx = jnp.asarray(rng.random((e, c, 1)), jnp.float32)
    sw = jnp.asarray(rng.random((e, 1, n)), jnp.float32)
    counts = jnp.asarray([[3, 8, 0],     # partial, full, empty segments
                          [0, 0, 0],     # fully-dead expert
                          [8, 8, 8],     # fully-live expert
                          [1, 0, 5]], jnp.int32)
    kw = dict(w=w, seg=seg, block_m=32, block_n=32, block_k=32)
    out = np.asarray(fused_gemm_grouped(a, b, sx, sw, counts=counts, **kw))
    dense = np.asarray(fused_gemm_grouped(
        a, b, sx, sw, w=w, block_m=32, block_n=32, block_k=32))
    live = (np.arange(c)[None, :] % seg
            < np.asarray(counts)[:, np.arange(c) // seg])       # (E, C)
    np.testing.assert_array_equal(
        out[live], dense[live], err_msg="live rows moved bits")
    np.testing.assert_array_equal(
        out[~live], np.zeros_like(out[~live]),
        err_msg="dead rows must be exact zeros")
    # raw-accumulator (no dequant) path honors the same contract
    acc = np.asarray(fused_gemm_grouped(a, b, counts=counts, **kw))
    acc_dense = np.asarray(fused_gemm_grouped(
        a, b, w=w, block_m=32, block_n=32, block_k=32))
    np.testing.assert_array_equal(acc[live], acc_dense[live])
    assert not acc[~live].any()


def test_quantized_batched_ragged_pallas_matches_xla():
    """The serve seam: quantized_matmul_batched with ragged counts must be
    token-identical between the pallas grouped kernel and the XLA
    fallback — dead rows are exact zeros on BOTH backends (the contract is
    backend-independent, so numerics pinning sees one class)."""
    rng = np.random.default_rng(11)
    e, c, k, n, seg = 3, 12, 32, 8, 4
    xb = jnp.asarray(rng.standard_normal((e, c, k)), jnp.float32)
    wb = jnp.asarray(rng.standard_normal((e, k, n)), jnp.float32)
    counts = jnp.asarray([[4, 0, 2], [0, 0, 0], [4, 4, 4]], jnp.int32)
    for w in (8, 12):
        xla = np.asarray(quantized_matmul_batched(
            xb, wb, w, 8, "auto", "xla", counts=counts, seg=seg))
        pal = np.asarray(quantized_matmul_batched(
            xb, wb, w, 8, "auto", "pallas", counts=counts, seg=seg))
        np.testing.assert_array_equal(xla, pal, err_msg=f"w={w}")
        live = (np.arange(c)[None, :] % seg
                < np.asarray(counts)[:, np.arange(c) // seg])
        assert not xla[~live].any() and not pal[~live].any()


# ---------------------------------------------------------------------------
# Dequant epilogue == a separate dequant of the mirror, exact fp32 equality.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("w", [8, 12])
def test_dequant_epilogue_equals_staged_dequant(w):
    """The in-kernel epilogue equals the jnp mirror's accumulator times
    the scales as a separate step, bit for bit."""
    m, k, n = 17, 70, 9
    a, b = _operands((m, k, n), w, seed=w)
    rng = np.random.default_rng(w)
    sx = jnp.asarray(rng.random((m, 1)), jnp.float32)
    sw = jnp.asarray(rng.random((1, n)), jnp.float32)
    fused = np.asarray(fused_gemm(a, b, sx, sw, w=w, block_m=32,
                                  block_n=32, block_k=64))
    acc = np.asarray(_mirror(a, b, w=w, mode="mm1" if w <= 8 else "kmm2",
                             block_k=64, combine_int32=w <= 8))
    staged_dequant = acc.astype(np.float32) * np.asarray(sx * sw)
    np.testing.assert_array_equal(fused, staged_dequant)


@pytest.mark.parametrize("w", [4, 8])
def test_quantized_matmul_pallas_bit_identical_to_xla_exact_class(w):
    """In the exact-int class (w <= m) the fused pallas route computes the
    same integer as the XLA dot, and the in-kernel epilogue multiplies the
    same scales in the same order — outputs are bit-identical."""
    rng = np.random.default_rng(w)
    x = jnp.asarray(rng.standard_normal((4, 16, 32)), jnp.float32)
    wm = jnp.asarray(rng.standard_normal((32, 24)), jnp.float32)
    xla = np.asarray(quantized_matmul(x, wm, w))
    pal = np.asarray(quantized_matmul(x, wm, w, 8, "auto", "pallas"))
    np.testing.assert_array_equal(xla, pal)
    # batched expert path, one grouped kernel launch
    xb = jnp.asarray(rng.standard_normal((3, 8, 32)), jnp.float32)
    wb = jnp.asarray(rng.standard_normal((3, 32, 8)), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(quantized_matmul_batched(xb, wb, w)),
        np.asarray(quantized_matmul_batched(xb, wb, w, 8, "auto", "pallas")))


def test_quantized_matmul_pallas_w12_close_and_bf16():
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((8, 64)), jnp.float32)
    wm = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
    xla = np.asarray(quantized_matmul(x, wm, 12))
    pal = np.asarray(quantized_matmul(x, wm, 12, 8, "auto", "pallas"))
    denom = max(np.abs(xla).max(), 1.0)
    assert np.abs(xla - pal).max() / denom < 1e-6   # same value, fp32 class
    out = quantized_matmul(x.astype(jnp.bfloat16), wm, 12, 8, "auto",
                           "pallas")
    assert out.dtype == jnp.bfloat16                # epilogue casts in-kernel


def test_prequant_matmul_pallas_route():
    from repro.quant.policy import POLICY_W8
    from repro.quant.prequant import prequantize

    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((2, 5, 32)), jnp.float32)
    wm = jnp.asarray(rng.standard_normal((32, 12)), jnp.float32)
    rec = prequantize({"wi": wm}, POLICY_W8)["wi"]
    assert rec["q"].dtype == jnp.int8               # narrow storage carrier
    np.testing.assert_array_equal(
        np.asarray(prequant_matmul(x, rec, 8)),
        np.asarray(prequant_matmul(x, rec, 8, backend="pallas")))


def test_pallas_route_falls_back_outside_fused_windows():
    """w=28 needs depth-3 recursion (no fused kernel): the pallas backend
    must fall back to the XLA path, bit-identically.  w=16 — which used to
    fall back — now rides the fused_mm2 single pass."""
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.standard_normal((4, 32)), jnp.float32)
    wm = jnp.asarray(rng.standard_normal((32, 8)), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(quantized_matmul(x, wm, 28)),
        np.asarray(quantized_matmul(x, wm, 28, 8, "auto", "pallas")))
    assert plan_for((4, 32, 8), 28) is None
    assert plan_for((4, 32, 8), 16).mode == "mm2"


def test_pallas_route_actually_runs_fused_at_serve_shapes():
    """Tiny-M decode/prefill GEMMs must ride the fused kernel (clamped
    tiles), not silently fall back to XLA: in the fp32 class the pallas
    rounding differs from XLA's digit recursion at large K, which is
    observable — so assert the route by checking the pallas result equals
    the fused kernel's output computed directly."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((2, 64)), jnp.float32)   # decode M=2
    wm = jnp.asarray(rng.standard_normal((64, 48)), jnp.float32)
    from repro.quant.qmatmul import _quantize

    qx, sx = _quantize(x, 12, axis=-1)
    qw, sw = _quantize(wm, 12, axis=0)
    plan = plan_for((2, 64, 48), 12)
    assert plan.tiles == (8, 64, 64)
    direct = np.asarray(fused_gemm(
        qx, qw, sx, sw, w=12, block_m=plan.block_m, block_n=plan.block_n,
        block_k=plan.block_k, out_dtype=jnp.float32))
    routed = np.asarray(quantized_matmul(x, wm, 12, 8, "auto", "pallas"))
    np.testing.assert_array_equal(routed, direct)


# ---------------------------------------------------------------------------
# Digit-dot paths: int8 MXU passes on the chip, fp32 in interpret mode.
# ---------------------------------------------------------------------------

# Every (mode, w) whose digit products fit int8 (kmm4 in its depth-2 window;
# test_int8_range_rule_matches_brute_force names the rest).
INT8_CASES = ([("kmm2", w) for w in range(9, 15)]
              + [("mm2", w) for w in (15, 16)]
              + [("kmm4", w) for w in range(17, 23)])


def _extreme_operands(w: int, m: int, k: int, n: int, seed: int):
    """Random w-bit rows/cols, then a block of all -2^(w-1) and a block of
    all 2^(w-1)-1, so one launch covers every pairing of the extremes."""
    rng = np.random.default_rng(seed)
    lo, hi = -(1 << (w - 1)), (1 << (w - 1)) - 1
    a = rng.integers(lo, hi + 1, (m, k))
    b = rng.integers(lo, hi + 1, (k, n))
    a[m // 3:2 * m // 3], a[2 * m // 3:] = lo, hi
    b[:, n // 3:2 * n // 3], b[:, 2 * n // 3:] = lo, hi
    return jnp.asarray(a, jnp.int32), jnp.asarray(b, jnp.int32)


def _launch(a, b, counts=None, *, w, mode, path, combine_int32=False,
            seg=None, tiles=(32, 32, 32)):
    """``_fused_call`` in interpret mode with the digit-dot path pinned."""
    bm, bn, bk = tiles
    fn = functools.partial(
        _fused_call, grouped=counts is not None or a.ndim == 3, w=w, m=8,
        mode=mode, seg=seg, block_m=bm, block_n=bn, block_k=bk,
        combine_int32=combine_int32, out_dtype=None, interpret=True,
        digit_dots=path)
    return np.asarray(jax.jit(fn)(a, b, None, None, counts))


def _int32_ring(x: np.ndarray) -> np.ndarray:
    """An int64 value as the int32 ring holds it (the int32 combine is exact
    mod 2^32, so wrapping is the oracle's own low word)."""
    return ((x + (1 << 31)) % (1 << 32)) - (1 << 31)


@pytest.mark.parametrize("mode,w", INT8_CASES + [("ragged", 12)],
                         ids=lambda v: str(v))
def test_int8_digit_path_bit_identical(mode, w):
    """The int8 digit-dot path equals the fp32 path bit for bit (fp32
    combine) and the int64 oracle's int32 value (int32 combine), random and
    extreme operands alike; the ragged grouped launch keeps its contract."""
    if mode == "ragged":
        e, seg, n_seg, k, n = 3, 8, 3, 70, 9
        counts = jnp.asarray([[3, 8, 0], [0, 0, 0], [8, 1, 5]], jnp.int32)
        pairs = [_extreme_operands(w, seg * n_seg, k, n, seed=i)
                 for i in range(e)]
        a = jnp.stack([p[0] for p in pairs])
        b = jnp.stack([p[1] for p in pairs])
        kw = dict(w=w, mode="kmm2", seg=seg)
        out = _launch(a, b, counts, path="int8", **kw)
        np.testing.assert_array_equal(
            out, _launch(a, b, counts, path="fp32", **kw))
        live = (np.arange(seg * n_seg)[None, :] % seg
                < np.asarray(counts)[:, np.arange(seg * n_seg) // seg])
        assert not out[~live].any()
        return
    assert int8_digits(mode, w)
    a, b = _extreme_operands(w, 48, 70, 33, seed=w)
    oracle = ref_int_gemm_i64(np.asarray(a), np.asarray(b))
    out = _launch(a, b, w=w, mode=mode, path="int8")
    np.testing.assert_array_equal(
        out, _launch(a, b, w=w, mode=mode, path="fp32"),
        err_msg=f"int8 != fp32 digit dots at {mode} w={w}")
    exact = _launch(a, b, w=w, mode=mode, path="int8", combine_int32=True)
    np.testing.assert_array_equal(exact.astype(np.int64), _int32_ring(oracle),
                                  err_msg=f"int8 off the oracle at {mode} w={w}")


def _brute_digit_range(mode: str, w: int):
    """min/max over every operand of every pass, over every w-bit value."""
    h = -(-w // 2)
    z, lo, hi = 1 << (h - 1), None, None
    for start in range(-(1 << (w - 1)), 1 << (w - 1), 1 << 20):
        a = np.arange(start, min(start + (1 << 20), 1 << (w - 1)),
                      dtype=np.int64)
        a1, a0 = a >> h, (a & ((1 << h) - 1)) - z
        ops_ = {"mm2": [a1, a0], "kmm2": [a1, a0, a1 + a0]}.get(mode)
        if ops_ is None:                                  # kmm4
            h2, ops_ = -(-(h + 1) // 2), []
            for v in (a1, a1 + a0, a0):
                v1, v0 = v >> h2, v & ((1 << h2) - 1)
                ops_ += [v1, v0, v1 + v0]
        cl = min(int(o.min()) for o in ops_)
        ch = max(int(o.max()) for o in ops_)
        lo = cl if lo is None else min(lo, cl)
        hi = ch if hi is None else max(hi, ch)
    return lo, hi


def test_int8_range_rule_matches_brute_force():
    """Which (mode, w) take int8 digit dots, each range checked against a
    brute-force min/max over every w-bit value; and the counter sees one
    trace per launch path."""
    windows = {"kmm2": range(9, 15), "mm2": range(9, 17),
               "kmm4": range(4, 27)}
    fits = {mode: [w for w in ws if int8_digits(mode, w)]
            for mode, ws in windows.items()}
    assert fits == {"kmm2": list(range(9, 15)), "mm2": list(range(9, 17)),
                    "kmm4": list(range(4, 23))}
    assert digit_range("kmm2", 12) == (-64, 62)
    assert digit_range("kmm4", 20) == (-16, 77)
    assert digit_range("kmm4", 26) == (-64, 189)
    for mode, ws in windows.items():
        for w in ws:
            assert digit_range(mode, w) == _brute_digit_range(mode, w), \
                (mode, w)
    # On the chip the digit products run int8 wherever they fit; in
    # interpret mode they keep the exact fp32 pass (int32 beyond it).
    assert dot_path("kmm2", 12, 512, interpret=False) == "int8"
    assert dot_path("kmm4", 26, 256, interpret=False) == "fp32"
    assert dot_path("kmm2", 12, 512, interpret=True) == "fp32"
    assert dot_path("kmm2", 14, 2048, interpret=True) == "int32"
    assert dot_path("mm1", 8, 256, interpret=True) == "int8"

    counter = obs_metrics.get("repro_fused_digit_dot_total")
    was_on = obs_metrics.enabled()
    obs_metrics.enable()
    counter.clear()
    try:
        a, b = _extreme_operands(12, 7, 45, 11, seed=0)
        for _ in range(2):                      # one trace, two calls
            fused_gemm(a, b, w=12, block_m=8, block_n=16, block_k=64)
        _launch(a, b, w=12, mode="kmm2", path="int8")
        fused_gemm(a // 16, b // 16, w=8, block_m=8, block_n=16, block_k=64)
        assert counter.value("kmm2", "fp32") == 1
        assert counter.value("kmm2", "int8") == 1
        assert counter.value("mm1", "int8") == 1
        assert counter.total() == 3
    finally:
        counter.clear()
        if not was_on:
            obs_metrics.disable()


# stablelm-12b w12 serve GEMMs (prefill at 4096/2048/512, decode at 16 and
# 8 lanes, the untied head), then ragged, tiny and pow2 shapes.
TILE_SWEEP_SHAPES = [
    (4096, 5120, 13824), (4096, 13824, 5120), (2048, 5120, 13824),
    (2048, 13824, 5120), (512, 5120, 1280), (4096, 5120, 1280),
    (16, 5120, 13824), (8, 5120, 100352), (1024, 5120, 5120),
    (1, 64, 1), (33, 70, 17), (130, 700, 50), (300, 1280, 640),
    (64, 768, 1000), (4, 2048, 8192), (512, 2048, 2048), (100, 300, 5000),
]


def _clamp_tiles(shape):
    m_dim, k_dim, n_dim = shape
    return (min(DEFAULT_TILES[0], pow2_cover(m_dim)),
            min(DEFAULT_TILES[1], pow2_cover(n_dim)),
            min(DEFAULT_TILES[2], pow2_cover(k_dim)))


@pytest.mark.parametrize("w", [9, 12, 14, 16, 20, 24])
def test_int8_tile_rule_keeps_padding_and_vmem(w):
    """The int8 path's tiles (``int8_tiles``) pad K exactly as the 256
    clamp does and fit the VMEM budget; off the int8 path the plan keeps
    the clamp.  Where the plan declines a deep-K shape, the zero-point
    sums are why."""
    mode = kernel_mode(w)
    for shape in TILE_SWEEP_SHAPES:
        clamp = _clamp_tiles(shape)
        tiles = int8_tiles(shape, mode, w)
        assert (tiles is None) == (w == 24), (w, shape)
        want = tiles or clamp
        k = shape[1]
        kp = -(-k // want[2]) * want[2]
        assert kp == -(-k // clamp[2]) * clamp[2], (w, shape)
        assert vmem_footprint(mode, w, *want) <= VMEM_BUDGET, (w, shape)
        plan = plan_for(shape, w)
        if plan is None:
            assert not zero_point_sums_fit(w, k, kp), (w, shape)
        else:
            assert plan.tiles == want, (w, shape)


# ---------------------------------------------------------------------------
# Every width the kernel serves, at the plan plan_for picks.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("w", range(2, 27))
def test_fused_every_width_vs_reference(w):
    """w 2-8 (mm1, exact by construction) equal the int64 oracle; w 9-26
    (the fp32 combine the cells run: kmm2, mm2, kmm4) equal the jnp mirror
    bit for bit and sit at the fp32 noise floor of the oracle.  K = 63 pads
    to 64 and sits on w = 26's zero-point-sum edge."""
    shape = (33, 63, 17)
    plan = plan_for(shape, w)
    assert plan is not None and plan.combine_int32 == (w <= 8)
    a, b = _extreme_operands(w, *shape, seed=w)
    oracle = ref_int_gemm_i64(np.asarray(a), np.asarray(b))
    out, mirror = _kernel_and_mirror(a, b, w=w, mode=plan.mode,
                                     tiles=plan.tiles,
                                     combine_int32=plan.combine_int32)
    if w <= 8:
        np.testing.assert_array_equal(out.astype(np.int64), oracle)
        return
    assert np.array_equal(out, mirror), f"kernel != mirror at w={w} {plan}"
    np.testing.assert_allclose(out.astype(np.float64), oracle, rtol=0,
                               atol=_i64_oracle_atol(w, shape[1]),
                               err_msg=f"kernel off the oracle at w={w}")


def test_plan_declines_zero_point_sum_overflow():
    """The int32 zero-point sums wrap once K * 2^(w-1) + Kp * z passes
    2^31, which the fp32 combine cannot undo: on all-extreme rows the
    kernel then misses the oracle by z * 2^32.  The plan declines there,
    and the quantized pallas route falls back to XLA bit for bit."""
    shape = (33, 70, 17)
    assert plan_for((33, 63, 17), 26) is not None
    assert plan_for((33, 64, 17), 26) is None and plan_for(shape, 26) is None
    assert plan_for((8, 4088, 8), 20) is not None
    assert plan_for((8, 4096, 8), 20) is None
    a, b = _extreme_operands(26, *shape, seed=26)
    wrapped = np.asarray(fused_gemm(a, b, w=26, mode="kmm4", block_m=64,
                                    block_n=32, block_k=128))
    oracle = ref_int_gemm_i64(np.asarray(a), np.asarray(b))
    assert np.abs(wrapped - oracle).max() >= 2.0 ** 44
    rng = np.random.default_rng(26)
    x = jnp.asarray(rng.standard_normal((4, 70)), jnp.float32)
    wm = jnp.asarray(rng.standard_normal((70, 8)), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(quantized_matmul(x, wm, 26)),
        np.asarray(quantized_matmul(x, wm, 26, 8, "auto", "pallas")))


@pytest.mark.parametrize("w", range(9, 15))
def test_fused_exact_class_vs_oracle(w):
    """The int32 combine at the exact window's edge (K = max_exact_k(w),
    at most 200) equals the int64 oracle, extremes included."""
    shape = (33, min(max_exact_k(w), 200), 17)
    plan = plan_for(shape, w, exact=True)
    assert plan is not None and plan.combine_int32
    a, b = _extreme_operands(w, *shape, seed=w)
    bm, bn, bk = plan.tiles
    out = np.asarray(fused_gemm(a, b, w=w, mode=plan.mode, block_m=bm,
                                block_n=bn, block_k=bk, combine_int32=True))
    assert out.dtype == np.int32
    np.testing.assert_array_equal(
        out.astype(np.int64), ref_int_gemm_i64(np.asarray(a), np.asarray(b)))


# ---------------------------------------------------------------------------
# The plan at the shapes the benchmark serves: the tiles and combine the
# fused route chose before it had its own plan function (the same programs).
# ---------------------------------------------------------------------------

# (K, N) of every quantized weight of the two benchmark configurations.
SERVED_KN = [(5120, 5120), (5120, 1280), (5120, 13824), (13824, 5120),
             (5120, 100352),                                   # stablelm-12b
             (2560, 2560), (2560, 8960), (8960, 2560), (2560, 65536)]  # rwkv
# (M, K, N) -> (block_m, block_n, block_k, combine_int32) at w = 12.
SERVED_PLANS = {
    (8, 5120, 5120): (8, 512, 512, False),
    (32, 5120, 5120): (32, 512, 512, False),
    (512, 5120, 5120): (512, 512, 512, False),
    (4096, 5120, 5120): (512, 512, 512, False),
    (8, 5120, 1280): (8, 256, 512, False),
    (32, 5120, 1280): (32, 256, 512, False),
    (512, 5120, 1280): (512, 256, 512, False),
    (4096, 5120, 1280): (512, 256, 512, False),
    (8, 5120, 13824): (8, 512, 512, False),
    (32, 5120, 13824): (32, 512, 512, False),
    (512, 5120, 13824): (512, 512, 512, False),
    (4096, 5120, 13824): (512, 512, 512, False),
    (8, 13824, 5120): (8, 512, 512, False),
    (32, 13824, 5120): (32, 512, 512, False),
    (512, 13824, 5120): (512, 512, 512, False),
    (4096, 13824, 5120): (512, 512, 512, False),
    (8, 5120, 100352): (8, 512, 512, False),
    (32, 5120, 100352): (32, 512, 512, False),
    (512, 5120, 100352): (512, 512, 512, False),
    (4096, 5120, 100352): (512, 512, 512, False),
    (8, 2560, 2560): (8, 512, 512, False),
    (32, 2560, 2560): (32, 512, 512, False),
    (512, 2560, 2560): (512, 512, 512, False),
    (4096, 2560, 2560): (512, 512, 512, False),
    (8, 2560, 8960): (8, 256, 512, False),
    (32, 2560, 8960): (32, 256, 512, False),
    (512, 2560, 8960): (512, 256, 512, False),
    (4096, 2560, 8960): (512, 256, 512, False),
    (8, 8960, 2560): (8, 512, 256, False),
    (32, 8960, 2560): (32, 512, 256, False),
    (512, 8960, 2560): (512, 512, 256, False),
    (4096, 8960, 2560): (512, 512, 256, False),
    (8, 2560, 65536): (8, 512, 512, False),
    (32, 2560, 65536): (32, 512, 512, False),
    (512, 2560, 65536): (512, 512, 512, False),
    (4096, 2560, 65536): (512, 512, 512, False),
}


@pytest.mark.parametrize("shape", list(SERVED_PLANS), ids=str)
def test_plan_at_served_shapes(shape):
    """Decode (M 8, 32) and prefill (M 512, 4096) widths of every served
    GEMM: the plan picks the kmm2 mode with the recorded tiles and the fp32
    combine.  Runs no kernel."""
    plan = plan_for(shape, 12)
    assert plan.mode == "kmm2"
    assert (*plan.tiles, plan.combine_int32) == SERVED_PLANS[shape]


def test_served_shapes_cover_the_benchmark_models():
    """SERVED_KN is every (K, N) that the benchmark's configurations
    quantize, read from the models' own parameter trees."""
    from repro.models import lm
    from repro.quant.prequant import _QUANT_LEAVES

    bench = Path(__file__).resolve().parents[1] / "bench"
    spec = importlib.util.spec_from_file_location("bench_run_for_shapes",
                                                  bench / "run.py")
    run = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = run            # dataclasses look it up
    try:
        spec.loader.exec_module(run)
    finally:
        del sys.modules[spec.name]
    assert {m for (m, _, _) in SERVED_PLANS} == {8, 32, 512, 4096}
    assert {(k, n) for (_, k, n) in SERVED_PLANS} == set(SERVED_KN)
    found = set()
    for cfg_file in sorted((bench / "configs").glob("*.json")):
        pcfg = run.program_config(json.loads(cfg_file.read_text()))
        tree = jax.eval_shape(
            lambda: lm.init_params(jax.random.PRNGKey(0), pcfg))
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
            if str(getattr(path[-1], "key", "")) in _QUANT_LEAVES:
                found.add(tuple(leaf.shape[-2:]))
    assert found == set(SERVED_KN), sorted(found ^ set(SERVED_KN))
