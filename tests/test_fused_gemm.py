"""Fused single-pass kernel (kernels/fused_gemm.py): bit-identity against the
staged Pallas path and the ref.py oracle across hostile tile/padding combos,
the exact-int32 boundary, the grouped expert grid, and the dequant epilogue.
"""
import functools
from dataclasses import replace

import jax
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core.dispatch import ExecPlan, analytic_plan, select_plan
from repro.core.kmm import max_exact_k
from repro.kernels import ops
from repro.kernels.fused_gemm import (
    _fused_call, digit_range, dot_path, fused_gemm, fused_gemm_grouped,
    int8_digits, int8_tiles,
)
from repro.kernels.ref import ref_int_gemm_i64
from repro.obs import metrics as obs_metrics
from repro.quant.qmatmul import (
    prequant_matmul, quantized_matmul, quantized_matmul_batched,
)
from repro.tune import runner, space

# Non-multiple M/N/K, 1-row/1-col extremes, K-padding that exercises the
# z-correction on padded rows (split(0) = (0, -z) must cancel exactly).
HOSTILE_SHAPES = [(33, 70, 17), (1, 64, 1), (130, 70, 50)]
TILE_COMBOS = [(32, 32, 32), (64, 32, 64), (32, 64, 256)]


def _staged_variant(w: int, m: int = 8) -> str:
    return "mm1" if w <= m else "kmm2"


def _plans(w: int, tiles, combine_int32: bool):
    bm, bn, bk = tiles
    depth = 0 if w <= 8 else 1
    fused = ExecPlan("fused", w, backend="pallas", block_m=bm, block_n=bn,
                     block_k=bk, combine_int32=combine_int32, depth=depth)
    staged = ExecPlan(_staged_variant(w), w, backend="pallas", block_m=bm,
                      block_n=bn, block_k=bk, combine_int32=combine_int32,
                      depth=depth)
    return fused, staged


# ---------------------------------------------------------------------------
# Satellite: bit-identity vs the staged path + the ref.py oracle.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("w", [4, 8, 12, 14])
@pytest.mark.parametrize("mkn", HOSTILE_SHAPES)
def test_fused_bit_identical_to_staged_and_mirror(w, mkn):
    """Same tiles, same padding: the fused kernel must reproduce the staged
    Pallas pipeline AND the pure-jnp staged mirror bit-for-bit — fp32
    combine included (identical operation sequence, not a tolerance)."""
    a, b = runner.make_operands(mkn, w, seed=w)
    oracle = ref_int_gemm_i64(np.asarray(a), np.asarray(b))
    for tiles in TILE_COMBOS:
        fused, staged = _plans(w, tiles, combine_int32=w <= 8)
        out = np.asarray(ops.run_plan_jit(a, b, fused))
        np.testing.assert_array_equal(
            out, np.asarray(ops.run_plan_jit(a, b, staged)),
            err_msg=f"fused != staged at w={w} tiles={tiles}")
        np.testing.assert_array_equal(
            out, np.asarray(ops.run_plan_jit(a, b, fused,
                                             use_ref_kernels=True)),
            err_msg=f"fused != jnp mirror at w={w} tiles={tiles}")
        if fused.is_exact_int:
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
def test_fused_mm2_bit_identical_to_staged_and_mirror(w, mkn):
    """The single-pass MM2 boundary mode (w = 2m-1, 2m): fused_mm2 must
    reproduce the staged Pallas MM2 pipeline AND the pure-jnp mirror
    bit-for-bit, and sit at the fp32 noise floor of the int64 oracle.
    w=14 runs the 4-pass mode *inside* the KMM2 window — the mode is valid
    anywhere in (m, 2m], not just on the boundary."""
    a, b = runner.make_operands(mkn, w, seed=w)
    oracle = ref_int_gemm_i64(np.asarray(a), np.asarray(b))
    for tiles in TILE_COMBOS:
        bm, bn, bk = tiles
        fused = ExecPlan("fused_mm2", w, backend="pallas", block_m=bm,
                         block_n=bn, block_k=bk, depth=1)
        staged = ExecPlan("mm2", w, backend="pallas", block_m=bm,
                          block_n=bn, block_k=bk, depth=1)
        out = np.asarray(ops.run_plan_jit(a, b, fused))
        np.testing.assert_array_equal(
            out, np.asarray(ops.run_plan_jit(a, b, staged)),
            err_msg=f"fused_mm2 != staged mm2 at w={w} tiles={tiles}")
        np.testing.assert_array_equal(
            out, np.asarray(ops.run_plan_jit(a, b, fused,
                                             use_ref_kernels=True)),
            err_msg=f"fused_mm2 != jnp mirror at w={w} tiles={tiles}")
        np.testing.assert_allclose(
            out.astype(np.float64), oracle, rtol=0,
            atol=_i64_oracle_atol(w, mkn[1]),
            err_msg=f"fused_mm2 off the oracle at w={w} tiles={tiles}")


@pytest.mark.parametrize("w", [8, 12, 15, 20])
@pytest.mark.parametrize("mkn", HOSTILE_SHAPES)
def test_fused_depth2_bit_identical_to_staged_and_mirror(w, mkn):
    """Depth-2 fused recursion (9 MXU passes, nested Fig. 8 pre-adders in
    VMEM): bit-identical to the staged two-level plane pipeline and the
    jnp mirror, fp32-noise-close to the int64 oracle.  Depth 2 is forced
    below its analytic window too (w=8/12/15) — the nested split must be
    valid anywhere ``kmm_levels_needed(w, m) <= 2``."""
    a, b = runner.make_operands(mkn, w, seed=w)
    oracle = ref_int_gemm_i64(np.asarray(a), np.asarray(b))
    for tiles in TILE_COMBOS:
        bm, bn, bk = tiles
        fused = ExecPlan("fused", w, backend="pallas", block_m=bm,
                         block_n=bn, block_k=bk, depth=2)
        staged = ExecPlan("kmm2", w, backend="pallas", block_m=bm,
                          block_n=bn, block_k=bk, depth=2)
        out = np.asarray(ops.run_plan_jit(a, b, fused))
        np.testing.assert_array_equal(
            out, np.asarray(ops.run_plan_jit(a, b, staged)),
            err_msg=f"fused d2 != staged d2 at w={w} tiles={tiles}")
        np.testing.assert_array_equal(
            out, np.asarray(ops.run_plan_jit(a, b, fused,
                                             use_ref_kernels=True)),
            err_msg=f"fused d2 != jnp mirror at w={w} tiles={tiles}")
        np.testing.assert_allclose(
            out.astype(np.float64), oracle, rtol=0,
            atol=_i64_oracle_atol(w, mkn[1]),
            err_msg=f"fused d2 off the oracle at w={w} tiles={tiles}")


@pytest.mark.parametrize("w", [4, 8, 12, 14, 15, 16, 20])
def test_fused_pruned_space_candidates_pass_the_gate(w):
    """Every fused plan the pruned tune space emits must pass the runner's
    bit-exact correctness gate (the same gate the autotuner applies) —
    including the fused_mm2 boundary mode (w=15, 16) and fused depth-2
    (w=20)."""
    shape = (16, 32, 16)
    cands = [p for p in space.pruned_space(shape, w, backend="pallas",
                                           tile_choices=(32, 64))
             if p.variant in ("fused", "fused_mm2")]
    assert cands, f"no fused candidates at w={w}"
    if w in (15, 16):
        assert any(p.variant == "fused_mm2" for p in cands)
    if w == 20:
        assert any(p.depth == 2 for p in cands)
    a, b = runner.make_operands(shape, w, seed=w)
    for plan in cands:
        ok, err = runner.check_plan(plan, a, b)
        assert ok, (plan, err)


def test_fused_analytic_default_covers_windows():
    """backend='pallas' analytic dispatch: fused for MM1 + KMM2 windows,
    fused_mm2 on the (2m-2, 2m] boundary, fused depth-2 for 4-digit
    recursion; only depth >= 3 stays staged."""
    for w in (4, 8):
        plan = analytic_plan(w, backend="pallas")
        assert plan.variant == "fused" and plan.is_exact_int
    for w in (9, 12, 14):
        plan = analytic_plan(w, backend="pallas")
        assert plan.variant == "fused" and plan.depth == 1
    for w in (15, 16):
        plan = analytic_plan(w, backend="pallas")
        assert plan.variant == "fused_mm2" and plan.depth == 1
    for w in (17, 20, 26):
        plan = analytic_plan(w, backend="pallas")
        assert plan.variant == "fused" and plan.depth == 2
    assert analytic_plan(28, backend="pallas").variant == "kmm2"


# ---------------------------------------------------------------------------
# Satellite: exact-int32 mode at the max_exact_k boundary.
# ---------------------------------------------------------------------------


def test_fused_exact_int32_at_max_exact_k_boundary():
    w = 12
    k = max_exact_k(w)                       # 128: the tight int32 ceiling
    a, b = runner.make_operands((16, k, 16), w, seed=3)
    plan = ExecPlan("fused", w, backend="pallas", block_m=32, block_n=32,
                    block_k=32, combine_int32=True, depth=1)
    assert space.validate(plan, (16, k, 16)) is None
    out = np.asarray(ops.run_plan_jit(a, b, plan))
    assert out.dtype == np.int32
    np.testing.assert_array_equal(
        out.astype(np.int64),
        ref_int_gemm_i64(np.asarray(a), np.asarray(b)))
    # one past the boundary: the pruner must reject the plan, and the
    # int_gemm API must refuse an exact request outright
    assert space.validate(plan, (16, k + 1, 16)) is not None
    with pytest.raises(ValueError, match="max exact K"):
        ops.int_gemm(jnp.zeros((16, k + 1), jnp.int32),
                     jnp.zeros((k + 1, 16), jnp.int32),
                     w=w, backend="pallas", exact=True)


# ---------------------------------------------------------------------------
# Satellite: grouped expert grid vs a per-expert loop.
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
# Satellite: dequant epilogue == staged dequant, exact fp32 equality.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("w", [8, 12])
def test_dequant_epilogue_equals_staged_dequant(w):
    m, k, n = 17, 70, 9
    a, b = runner.make_operands((m, k, n), w, seed=w)
    rng = np.random.default_rng(w)
    sx = jnp.asarray(rng.random((m, 1)), jnp.float32)
    sw = jnp.asarray(rng.random((1, n)), jnp.float32)
    kw = dict(w=w, block_m=32, block_n=32, block_k=64)
    fused = np.asarray(fused_gemm(a, b, sx, sw, **kw))
    acc = np.asarray(fused_gemm(a, b, **kw)).astype(np.float32)
    staged_dequant = acc * np.asarray(sx * sw)
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
    assert select_plan((4, 32, 8), 28, backend="pallas").variant == "kmm2"
    assert select_plan((4, 32, 8), 16, backend="pallas").variant \
        == "fused_mm2"


# ---------------------------------------------------------------------------
# Dispatch/tuning seam: fused plans stay in the staged fingerprint class.
# ---------------------------------------------------------------------------


def test_table_can_swap_fused_and_staged_without_moving_bits():
    """A tuning table recording a staged kmm2 winner is adopted over the
    fused analytic default (same fp32 fingerprint class + same K padding)
    and must not change a single output bit."""
    from repro.tune.table import TuningTable, use_table

    w, shape = 12, (64, 128, 64)
    a, b = runner.make_operands(shape, w, seed=1)
    base = np.asarray(ops.int_gemm(a, b, w=w, backend="pallas"))
    t = TuningTable()
    t.put("pallas", shape, w,
          ExecPlan("kmm2", w, backend="pallas", block_m=32, block_n=32,
                   block_k=256, combine_int32=False, depth=1))
    with use_table(t):
        plan = select_plan(shape, w, backend="pallas")
        assert plan.variant == "kmm2" and plan.source == "table"
        tabled = np.asarray(ops.int_gemm(a, b, w=w, backend="pallas"))
    np.testing.assert_array_equal(base, tabled)


def test_quantized_matmul_pallas_table_never_moves_bits():
    """Numerics pinning holds on the pallas backend too: a table that
    redirects the fused plan to a staged pallas plan (same fingerprint
    class) must leave quantized_matmul(backend='pallas') bit-identical —
    the redirect runs the staged kernel, never the XLA rounding class."""
    from repro.tune.table import TuningTable, use_table

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((8, 256)), jnp.float32)
    wm = jnp.asarray(rng.standard_normal((256, 64)), jnp.float32)
    t = TuningTable()
    t.put("pallas", (8, 256, 64), 12,
          ExecPlan("kmm2", 12, backend="pallas", block_m=32, block_n=64,
                   block_k=256, combine_int32=False, depth=1))
    for w in (8, 12):
        base = np.asarray(quantized_matmul(x, wm, w, 8, "auto", "pallas"))
        with use_table(t):
            tabled = np.asarray(quantized_matmul(x, wm, w, 8, "auto",
                                                 "pallas"))
        np.testing.assert_array_equal(base, tabled, err_msg=f"w={w}")


def test_pallas_route_actually_runs_fused_at_serve_shapes():
    """Tiny-M decode/prefill GEMMs must ride the fused kernel (clamped
    tiles), not silently fall back to XLA: in the fp32 class the pallas
    rounding differs from XLA's digit recursion at large K, which is
    observable — so assert the route by checking the pallas result equals
    the fused kernel's output computed directly."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((2, 64)), jnp.float32)   # decode M=2
    wm = jnp.asarray(rng.standard_normal((64, 48)), jnp.float32)
    from repro.quant.qmatmul import _quantize, _shrink_tiles

    qx, sx = _quantize(x, 12, axis=-1)
    qw, sw = _quantize(wm, 12, axis=0)
    plan = _shrink_tiles(analytic_plan(12, backend="pallas"), (2, 64, 48))
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


@pytest.mark.parametrize("w", [9, 12, 14, 16, 20, 24])
def test_int8_tile_rule_keeps_padding_and_vmem(w):
    """The int8 path's tiles (``int8_tiles``) pad K exactly as the analytic
    256 clamp does, fit the VMEM budget, and leave table plans alone."""
    from repro.core.context import ExecContext
    from repro.quant.qmatmul import _fused_mode, _fused_plan_for, \
        _shrink_tiles
    from repro.tune.table import TuningTable

    for shape in TILE_SWEEP_SHAPES:
        plan = _fused_plan_for(shape, w, 8, None)
        clamp = _shrink_tiles(analytic_plan(w, backend="pallas"), shape)
        tiles = int8_tiles(shape, _fused_mode(plan), w)
        assert (tiles is None) == (w == 24), (w, shape)
        assert plan.tiles == (tiles or clamp.tiles), (w, shape)
        k = shape[1]
        assert -(-k // plan.block_k) * plan.block_k \
            == -(-k // clamp.block_k) * clamp.block_k, (w, shape)
        assert space.vmem_footprint(plan) <= space.VMEM_BUDGET, (w, shape)
    shape = TILE_SWEEP_SHAPES[0]
    base = analytic_plan(w, backend="pallas")
    t = TuningTable()
    t.put("pallas", shape, w,
          replace(base, block_m=64, block_n=128, block_k=256))
    plan = _fused_plan_for(shape, w, 8, ExecContext(backend="pallas",
                                                    tuning_table=t))
    assert plan.source.startswith("table") and plan.tiles == (64, 128, 256)
