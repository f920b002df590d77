"""Pallas TPU kernel: fused single-pass integer GEMM (MM1/KMM2/MM2/depth-2).

The paper's KMM hardware (Figs. 8-9) wins because the digit pre-adders, the
digit-plane multipliers and the post-adder combine live in *one* pipeline
with no intermediate memory round-trips.  The staged Pallas path in
:mod:`repro.kernels.ops` approximates that with ~6 HBM passes: ``_planes``
materializes plane arrays, ``kmm2_gemm_planes`` reads them back, and the
Section IV-D zero-point correction plus dequant each cost another
array-sized pass.  This kernel is the faithful mapping: ONE ``pallas_call``
that

  * reads the **original** integer operands (narrowest carrier: int8 for
    ``w <= m``, int16 up to ``w <= 16``, int32 above) — no pre-split planes
    in HBM;
  * performs the digit split(s) and low-digit centering on the VPU
    in-register, per (bm, bk)/(bk, bn) tile (the Fig. 8 X-adder vector);
  * runs the mode's MXU passes against persistent int32 VMEM accumulators
    across the K grid, as int8 x int8 -> int32 passes on the chip wherever
    every digit of the mode fits int8 (:func:`dot_path`, :func:`digit_range`;
    the tiles of that path come from :func:`int8_tiles`):

      - ``mm1``  (w <= m):        1 pass, no split;
      - ``kmm2`` (m < w <= 2m-2): 3 passes (C1, Cs, C0);
      - ``mm2``  (2m-2 < w <= 2m): 4 passes (C1, C10, C01, C0) — the
        conventional boundary mode, same accumulator scheme;
      - ``kmm4`` (depth-2 KMM, 4 digits): 9 passes — the level-1 centered
        split at ``h`` is re-split (plain, uncentered) at
        ``h2 = ceil((h+1)/2)`` per branch {A1, As, A0}, with the nested
        Fig. 8 pre-adders computed in-register on the VPU;

  * accumulates the zero-point rowsum/colsum terms in (bm, 1)/(1, bn) VMEM
    scratch across the K grid (``rowsum(Abar) = rowsum(A) - Kp*z`` needs the
    *raw* operand tiles, which the kernel already holds);
  * applies the mode's post-adder combine **and** the Section IV-D
    correction in the final K step, optionally followed by a dequant
    epilogue (per-token ``sx`` row scale x per-channel ``sw`` col scale ->
    fp32/bf16), so the quantized model path is 2 operand reads + 1 output
    write.

Numerics are pinned to the staged path bit-for-bit (asserted across the
pruned tune space by ``tests/test_fused_gemm.py`` / ``tests/test_tune.py``):
the digit products and row/col sums are exact int32 regardless of tiling,
and the fp32 combine applies the identical operation sequence as the staged
kernels at every level, so interpret-mode CI can gate the fused kernel
against the pure-jnp staged mirror with ``np.array_equal``.

``fused_gemm_grouped`` adds a leading expert/group grid axis so MoE expert
GEMMs ((E, C, K) x (E, K, N)) run as one kernel launch instead of an XLA
recursion per expert.  With ``counts``/``seg`` it runs *ragged*: row ``r``
of expert ``e`` is live iff ``r % seg < counts[e, r // seg]``; dead rows are
masked to exact zeros at the output (live rows never see the mask, so they
stay bit-identical to the dense grouped launch), and m-blocks with no live
row skip their MXU passes entirely.
"""
from __future__ import annotations

import functools
from dataclasses import replace
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.obs import metrics as obs_metrics

Array = jax.Array

# Which digit-dot path each fused launch took (trace-time: one hit per
# trace of the kernel, like repro_quant_gemm_routes_total).
_DIGIT_DOTS = obs_metrics.counter(
    "repro_fused_digit_dot_total",
    "fused-kernel launches traced, by mode and digit-dot path",
    labels=("mode", "path"))

# Kernel modes (digit layouts).  "auto" resolves to the paper's default for
# the width: mm1 (w <= m) or kmm2 (above).  mm2 and kmm4 are explicit
# because they are *alternatives* inside overlapping width windows (the
# dispatch/tuning layer owns the choice, not the kernel).
MODES = ("mm1", "kmm2", "mm2", "kmm4")


def _pad_tail(x: Array, mults) -> Array:
    """Zero-pad the trailing ``len(mults)`` dims of ``x`` up to multiples."""
    lead = x.ndim - len(mults)
    pads = [(0, 0)] * lead + [(0, (-x.shape[lead + i]) % mult)
                              for i, mult in enumerate(mults)]
    if any(p for _, p in pads):
        x = jnp.pad(x, pads)
    return x


def leaf_mag_bits(mode: str, w: int) -> int:
    """ceil(log2) bound on the largest digit magnitude entering an MXU pass
    (pre-adder outputs included) — the quantity that prices both the exact
    fp32-dot window and the int32 digit-accumulator headroom.

      * kmm2: |A1 + (A0 - z)| <= 2^h          (Fig. 8 pre-adder)
      * mm2:  |A1|, |A0 - z| <= 2^(h-1)       (no pre-adder)
      * kmm4: the level-1 branches fit h+1 signed bits; the plain level-2
        split at h2 = ceil((h+1)/2) gives leaves |hi| <= 2^(h-h2) and
        lo in [0, 2^h2), so the nested pre-adder is < 2^(h-h2) + 2^h2.
    """
    h = -(-w // 2)
    if mode == "kmm2":
        return h
    if mode == "mm2":
        return max(h - 1, 1)
    if mode == "kmm4":
        w1 = h + 1                       # widest branch: As = A1 + A0bar
        h2 = -(-w1 // 2)
        mag = (1 << max(w1 - h2 - 1, 0)) + (1 << h2)
        return max(mag.bit_length(), 1)
    raise ValueError(f"no digit magnitude for mode {mode!r}")


def _fp32_dot_ok(mode: str, w: int, block_k: int) -> bool:
    """Exact-fp32 digit products: every digit entering a dot is an integer
    with magnitude <= 2^leaf_mag_bits, so every K-dot partial sum over a
    block_k-deep tile is an integer of magnitude <= block_k * 2^(2*bits).
    While that stays <= 2^24 every value is exactly representable in fp32:
    the fp32 pass computes the same integers the integer path does, bit for
    bit, and the int32 cast is lossless."""
    bits = leaf_mag_bits(mode, w)
    return block_k <= (1 << max(24 - 2 * bits, 0))


def _span(lo: int, hi: int, s: int) -> Tuple[int, int]:
    """Exact [min, max] of ``(v >> s) + (v & (2^s - 1))`` over every integer
    v in [lo, hi] (the nested pre-adder of a plain split at s).  Within one
    s-aligned row the sum rises with v, so the extremes sit at lo, hi, the
    first value of the row above lo's, and the last value of the row below
    hi's."""
    mask = (1 << s) - 1
    cands = [lo, hi]
    first = ((lo >> s) + 1) << s
    last = ((hi >> s) << s) - 1
    cands += [v for v in (first, last) if lo <= v <= hi]
    vals = [(v >> s) + (v & mask) for v in cands]
    return min(vals), max(vals)


@functools.lru_cache(maxsize=None)
def digit_range(mode: str, w: int) -> Tuple[int, int]:
    """Exact [min, max] over every operand of every MXU pass of a split
    mode, taken over all signed ``w``-bit inputs.

    With ``h = ceil(w/2)`` and ``z = 2^(h-1)``, a w-bit value splits into a
    signed high digit ``a >> h`` in [-2^(w-1-h), 2^(w-1-h) - 1] and a
    centered low digit ``(a & (2^h - 1)) - z`` in [-z, z - 1]; the two are
    independent, so the Fig. 8 pre-adder ``a1 + (a0 - z)`` covers the sum
    of the intervals.  kmm4 re-splits each of those three branches plainly
    at ``h2 = ceil((h+1)/2)`` and pre-adds the halves again (:func:`_span`).
    """
    h = -(-w // 2)
    z = 1 << (h - 1)
    hi_d = (-(1 << max(w - 1 - h, 0)), (1 << max(w - 1 - h, 0)) - 1)
    lo_d = (-z, z - 1)
    pre = (hi_d[0] + lo_d[0], hi_d[1] + lo_d[1])
    if mode == "mm2":
        ranges = [hi_d, lo_d]
    elif mode == "kmm2":
        ranges = [hi_d, lo_d, pre]
    elif mode == "kmm4":
        h2 = -(-(h + 1) // 2)
        ranges = []
        for lo, hi in (hi_d, lo_d, pre):
            mask2 = (1 << h2) - 1
            low = ((lo & mask2, hi & mask2) if lo >> h2 == hi >> h2
                   else (0, mask2))
            ranges += [(lo >> h2, hi >> h2), _span(lo, hi, h2), low]
    else:
        raise ValueError(f"no digit split in mode {mode!r}")
    return min(r[0] for r in ranges), max(r[1] for r in ranges)


def int8_digits(mode: str, w: int) -> bool:
    """Every digit product of the mode fits int8 x int8 -> int32 MXU passes."""
    lo, hi = digit_range(mode, w)
    return -128 <= lo and hi <= 127


def dot_path(mode: str, w: int, block_k: int, interpret: bool) -> str:
    """How the kernel issues its digit products: ``"int8"`` on the chip
    wherever :func:`int8_digits` holds (the v5e MXU's native integer pass;
    an fp32 HIGHEST dot is several bf16 passes), else ``"fp32"`` where the
    fp32 pass is exact (:func:`_fp32_dot_ok`; the CPU interpreter's fast
    sgemm) and ``"int32"`` dots beyond.  mm1's single pass is int8.
    Every path accumulates the same exact int32 products."""
    if mode == "mm1" or (not interpret and int8_digits(mode, w)):
        return "int8"
    return "fp32" if _fp32_dot_ok(mode, w, block_k) else "int32"


# Largest tile edge of the int8 path: (512, 512, 512) is 5.25 MB of
# tune.space.vmem_footprint for kmm2; (512, 1024, 512) overflows v5e's
# 16 MB of scoped VMEM.
_INT8_TILE = 512


def pow2_cover(n: int, lo: int = 8) -> int:
    """Smallest power of two >= n, at least ``lo``."""
    v = lo
    while v < n:
        v *= 2
    return v


def int8_tiles(shape, mode: str, w: int, m: int = 8
               ) -> Optional[Tuple[int, int, int]]:
    """Tiles (block_m, block_n, block_k) for an (M, K, N) GEMM whose digit
    products run on the int8 path, or None where ``(mode, w)`` is not on it
    (mm1 and the widths :func:`int8_digits` refuses keep the analytic
    clamp).  Int8 passes are cheap enough that the per-step cost and the
    in-register split of each tile set the rate, so tiles grow to 512:

      * block_m: pow2 cover of M, at most 512;
      * block_n: of 512/256/128 (at most the pow2 cover of N), the one
        that pads N least, the larger on a tie (13824 = 27 x 512,
        1280 = 5 x 256);
      * block_k: 512 only where it pads K to the same length as the
        analytic 256 clamp (the fp32 combine's correction reads padded K,
        so another padding is another value), else that clamp.

    A choice over ``tune.space.VMEM_BUDGET`` halves block_m until it fits
    (kmm4 above w = 16, whose int32 carriers and 9 accumulators need it).
    """
    from repro.core.dispatch import DEFAULT_TILES, ExecPlan
    from repro.tune.space import VMEM_BUDGET, vmem_footprint

    mode = _resolve_mode(mode, w, m)
    if mode == "mm1" or not int8_digits(mode, w):
        return None
    m_dim, k_dim, n_dim = shape
    bm = min(_INT8_TILE, pow2_cover(m_dim))
    n_cover = pow2_cover(n_dim)
    if n_cover <= 128:
        bn = n_cover
    else:
        bn = min((b for b in (_INT8_TILE, 256, 128) if b <= n_cover),
                 key=lambda b: (-(-n_dim // b) * b, -b))
    bk = min(DEFAULT_TILES[2], pow2_cover(k_dim))
    kp = -(-k_dim // bk) * bk
    if bk == DEFAULT_TILES[2] and kp % _INT8_TILE == 0:
        bk = _INT8_TILE
    plan = ExecPlan("fused_mm2" if mode == "mm2" else "fused", w, m=m,
                    backend="pallas", block_m=bm, block_n=bn, block_k=bk,
                    depth=2 if mode == "kmm4" else 1)
    while vmem_footprint(plan) > VMEM_BUDGET and plan.block_m > 8:
        plan = replace(plan, block_m=plan.block_m // 2)
    return plan.block_m, plan.block_n, plan.block_k


def _fused_kernel(*refs, mode: str, h: int, h2: int, z: int, nk: int,
                  kp: int, seg: int, n_seg: int, digit_dots: str,
                  combine_int32: bool, dequant: bool, grouped: bool,
                  ragged: bool, out_dtype):
    idx = 2
    a_ref, b_ref = refs[:2]
    sx_ref = sw_ref = counts_ref = None
    if dequant:
        sx_ref, sw_ref = refs[idx:idx + 2]
        idx += 2
    if ragged:
        counts_ref = refs[idx]
        idx += 1
    out_ref = refs[idx]
    scratch = refs[idx + 1:]
    k = pl.program_id(3 if grouped else 2)

    def ld(ref):
        return ref[0] if grouped else ref[...]

    @pl.when(k == 0)
    def _init():
        for r in scratch:
            r[...] = jnp.zeros_like(r)

    live = None
    if ragged:
        # Ragged grouped contract: row r is live iff its within-segment
        # rank beats the segment's live count.  The mask depends only on
        # (group, m-block) — dead m-blocks skip their MXU passes, dead
        # rows inside a live block are zeroed at the combine (live rows
        # never see the mask, so they match the dense launch bit-for-bit).
        # ``counts_ref`` is the flattened (E * n_seg,) table in SMEM; an
        # m-block spans at most (bm - 1) // seg + 2 segments, each of whose
        # counts is read as a scalar.
        bm = out_ref.shape[-2]
        g, i = pl.program_id(0), pl.program_id(1)
        rows = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
        seg_ids = rows // seg
        s0 = (i * bm) // seg
        live = jnp.zeros((bm, 1), jnp.bool_)
        for j in range(min(n_seg, (bm - 1) // seg + 2)):
            s = jnp.minimum(s0 + j, n_seg - 1)
            limit = counts_ref[g * n_seg + s]
            live |= (seg_ids == s0 + j) & (rows - seg_ids * seg < limit)
        live &= seg_ids < n_seg

    def _dots(pairs, accs):
        # Same exact int32 products on every path (see dot_path).
        for (x, y), acc in zip(pairs, accs):
            if digit_dots == "int8":
                acc[...] += jnp.dot(x.astype(jnp.int8), y.astype(jnp.int8),
                                    preferred_element_type=jnp.int32)
            elif digit_dots == "fp32":
                acc[...] += jnp.dot(
                    x.astype(jnp.float32), y.astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST).astype(jnp.int32)
            else:
                acc[...] += jnp.dot(x, y, preferred_element_type=jnp.int32)

    def _accumulate():
        a = ld(a_ref)
        b = ld(b_ref)
        if mode == "mm1":
            (acc0_ref,) = scratch
            acc0_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.int32)
            return
        # VPU in-register digit split + centering (ops._planes, minus the
        # HBM plane arrays).  The tile is widened to int32 first: the
        # carrier stays narrow in HBM, but Mosaic has no int16 vector
        # shift.  Digit values are unchanged, so the MXU products are the
        # same exact int32 the staged plane kernels compute.
        a = a.astype(jnp.int32)
        b = b.astype(jnp.int32)
        mask = (1 << h) - 1
        a1 = jnp.right_shift(a, h)
        a0 = jnp.bitwise_and(a, mask) - z
        b1 = jnp.right_shift(b, h)
        b0 = jnp.bitwise_and(b, mask) - z
        if mode == "kmm2":
            # Fig. 8 pre-adders + the three sub-MXU passes.
            pairs = [(a1, b1), (a1 + a0, b1 + b0), (a0, b0)]
        elif mode == "mm2":
            # Conventional 4-product boundary mode (no pre-adder, so the
            # digit planes stay within s8 up to w = 2m).
            pairs = [(a1, b1), (a1, b0), (a0, b1), (a0, b0)]
        else:  # kmm4: nested Fig. 8 — re-split each branch at h2, 9 passes
            mask2 = (1 << h2) - 1
            pairs = []
            for av, bv in ((a1, b1), (a1 + a0, b1 + b0), (a0, b0)):
                av1 = jnp.right_shift(av, h2)
                av0 = jnp.bitwise_and(av, mask2)
                bv1 = jnp.right_shift(bv, h2)
                bv0 = jnp.bitwise_and(bv, mask2)
                pairs += [(av1, bv1), (av1 + av0, bv1 + bv0), (av0, bv0)]
        row_ref, col_ref = scratch[-2], scratch[-1]
        _dots(pairs, scratch[:-2])
        # Zero-point sums accumulated across the K grid: rowsum(Abar) =
        # rowsum(A) - Kp*z, so the raw tiles already in registers suffice.
        row_ref[...] += jnp.sum(a, axis=1, keepdims=True, dtype=jnp.int32)
        col_ref[...] += jnp.sum(b, axis=0, keepdims=True, dtype=jnp.int32)

    if ragged:
        pl.when(jnp.any(live))(_accumulate)
    else:
        _accumulate()

    @pl.when(k == nk - 1)
    def _combine():
        if mode == "mm1":
            val = scratch[0][...]
        else:
            row = scratch[-2][...] - jnp.int32(kp * z)
            col = scratch[-1][...] - jnp.int32(kp * z)
            if mode == "kmm2":
                core = _combine_kmm2(scratch[0][...], scratch[1][...],
                                     scratch[2][...], h, combine_int32)
            elif mode == "mm2":
                core = _combine_mm2(scratch[0][...], scratch[1][...],
                                    scratch[2][...], scratch[3][...],
                                    h, combine_int32)
            else:  # kmm4: level-2 combine per branch, then level-1
                c11 = _combine_kmm2(scratch[0][...], scratch[1][...],
                                    scratch[2][...], h2, combine_int32)
                css = _combine_kmm2(scratch[3][...], scratch[4][...],
                                    scratch[5][...], h2, combine_int32)
                c00 = _combine_kmm2(scratch[6][...], scratch[7][...],
                                    scratch[8][...], h2, combine_int32)
                core = _combine_kmm2_wide(c11, css, c00, h, combine_int32)
            if combine_int32:
                val = core + (z * row + z * col + jnp.int32(z * z * kp))
            else:
                corr = (z * row.astype(jnp.float32)
                        + z * col.astype(jnp.float32)
                        + float(z) * float(z) * float(kp))
                val = core + corr
        if dequant:
            val = val.astype(jnp.float32) * (ld(sx_ref) * ld(sw_ref))
        val = val.astype(out_dtype)
        if ragged:
            val = jnp.where(live, val, jnp.zeros_like(val))
        if grouped:
            out_ref[0] = val
        else:
            out_ref[...] = val


def _combine_kmm2(c1, cs, c0, h: int, combine_int32: bool):
    """KMM post-adder (Fig. 9): C = C1<<2h + (Cs-C1-C0)<<h + C0 — the exact
    operation sequence of kmm2_gemm_planes / ref_kmm2_planes."""
    if combine_int32:
        return (c1 << (2 * h)) + ((cs - c1 - c0) << h) + c0
    c1f = c1.astype(jnp.float32)
    c0f = c0.astype(jnp.float32)
    mid = cs.astype(jnp.float32) - c1f - c0f
    return c1f * (2.0 ** (2 * h)) + mid * (2.0 ** h) + c0f


def _combine_kmm2_wide(c1, cs, c0, h: int, combine_int32: bool):
    """Level-1 KMM combine on already-combined (fp32/int32) branch products
    — same sequence as _combine_kmm2, minus the int32->fp32 casts."""
    if combine_int32:
        return (c1 << (2 * h)) + ((cs - c1 - c0) << h) + c0
    mid = cs - c1 - c0
    return c1 * (2.0 ** (2 * h)) + mid * (2.0 ** h) + c0


def _combine_mm2(c1, c10, c01, c0, h: int, combine_int32: bool):
    """Conventional 4-product combine — the exact operation sequence of
    mm2_gemm_planes / ref_mm2_planes (c10/c01 summed as fp32, not int)."""
    if combine_int32:
        return (c1 << (2 * h)) + ((c10 + c01) << h) + c0
    mid = c10.astype(jnp.float32) + c01.astype(jnp.float32)
    return (c1.astype(jnp.float32) * (2.0 ** (2 * h)) + mid * (2.0 ** h)
            + c0.astype(jnp.float32))


_N_ACC = {"mm1": 1, "kmm2": 3, "mm2": 4, "kmm4": 9}


def _resolve_mode(mode: str, w: int, m: int) -> str:
    if mode == "auto":
        mode = "mm1" if w <= m else "kmm2"
    if mode not in MODES:
        raise ValueError(f"unknown fused mode {mode!r}; choices {MODES}")
    return mode


def _resolve(w: int, m: int, mode: str, dequant: bool, combine_int32: bool,
             out_dtype, interpret: Optional[bool]):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    mode = _resolve_mode(mode, w, m)
    split = mode != "mm1"
    h = -(-w // 2) if split else 0
    h2 = -(-(h + 1) // 2) if mode == "kmm4" else 0
    z = (1 << (h - 1)) if split else 0
    # Narrowest carrier covering the window: int8 for w <= m (one MXU pass,
    # no split), int16 through w = 16 (KMM2/MM2 windows), int32 only for
    # the deep-recursion widths — always at most half the staged wrapper's
    # int32 plane-materialization traffic.
    carrier = (jnp.int8 if not split else
               jnp.int16 if w <= 16 else jnp.int32)
    if out_dtype is None:
        out_dtype = (jnp.float32 if dequant else
                     jnp.int32 if (combine_int32 or not split) else
                     jnp.float32)
    return mode, h, h2, z, carrier, jnp.dtype(out_dtype), interpret


def _scratch_shapes(mode: str, block_m: int, block_n: int):
    accs = [pltpu.VMEM((block_m, block_n), jnp.int32)] * _N_ACC[mode]
    if mode == "mm1":
        return accs
    return accs + [pltpu.VMEM((block_m, 1), jnp.int32),
                   pltpu.VMEM((1, block_n), jnp.int32)]


def _fused_call(a, b, sx, sw, counts, *, grouped: bool, w: int, m: int,
                mode: str, seg: Optional[int], block_m: int, block_n: int,
                block_k: int, combine_int32: bool, out_dtype,
                interpret, digit_dots: Optional[str] = None) -> Array:
    """Shared pallas_call builder; ``grouped`` adds the leading expert grid
    axis (every BlockSpec gains a size-1 leading block on the group index).
    ``digit_dots`` overrides :func:`dot_path` (tests pin the int8 path in
    interpret mode).
    """
    if (sx is None) != (sw is None):
        raise ValueError("pass both sx and sw for the dequant epilogue")
    dequant = sx is not None
    ragged = counts is not None
    if ragged and not grouped:
        raise ValueError("ragged counts require the grouped kernel")
    if ragged and (seg is None or seg <= 0):
        raise ValueError("ragged counts need a positive static seg")
    mode, h, h2, z, carrier, out_dtype, interpret = _resolve(
        w, m, mode, dequant, combine_int32, out_dtype, interpret)
    lead = a.shape[:-2]                  # () dense, (E,) grouped
    m_dim, k_dim = a.shape[-2:]
    n_dim = b.shape[-1]
    a = _pad_tail(a.astype(carrier), (block_m, block_k))
    b = _pad_tail(b.astype(carrier), (block_k, block_n))
    mp, kp = a.shape[-2:]
    np_ = b.shape[-1]
    digit_dots = digit_dots or dot_path(mode, w, block_k, interpret)
    _DIGIT_DOTS.inc(mode, digit_dots)
    body = (mp // block_m, np_ // block_n, kp // block_k)
    grid = lead + body if grouped else body

    def spec(block, index_map):
        if grouped:
            return pl.BlockSpec(
                (1,) + block,
                lambda g, i, j, kk, _f=index_map: (g,) + _f(i, j, kk))
        return pl.BlockSpec(block, index_map)

    kernel = functools.partial(
        _fused_kernel, mode=mode, h=h, h2=h2, z=z, nk=body[2], kp=kp,
        seg=seg or 0, n_seg=counts.shape[-1] if ragged else 0,
        digit_dots=digit_dots,
        combine_int32=combine_int32, dequant=dequant, grouped=grouped,
        ragged=ragged, out_dtype=out_dtype)
    in_specs = [spec((block_m, block_k), lambda i, j, kk: (i, kk)),
                spec((block_k, block_n), lambda i, j, kk: (kk, j))]
    operands = [a, b]
    if dequant:
        operands += [_pad_tail(sx.astype(jnp.float32), (block_m, 1)),
                     _pad_tail(sw.astype(jnp.float32), (1, block_n))]
        in_specs += [spec((block_m, 1), lambda i, j, kk: (i, 0)),
                     spec((1, block_n), lambda i, j, kk: (0, j))]
    if ragged:
        # Whole table in SMEM, flattened so no (8, 128) tiling pads it.
        operands.append(counts.astype(jnp.int32).reshape(-1))
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=spec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct(lead + (mp, np_), out_dtype),
        scratch_shapes=_scratch_shapes(mode, block_m, block_n),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * (len(grid) - 1)
            + ("arbitrary",)),
        interpret=interpret,
    )(*operands)
    return out[..., :m_dim, :n_dim]


@functools.partial(
    jax.jit,
    static_argnames=("w", "m", "mode", "block_m", "block_n", "block_k",
                     "combine_int32", "out_dtype", "interpret"),
)
def fused_gemm(
    a: Array, b: Array, sx: Optional[Array] = None,
    sw: Optional[Array] = None, *,
    w: int,
    m: int = 8,
    mode: str = "auto",
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 256,
    combine_int32: bool = False,
    out_dtype=None,
    interpret: Optional[bool] = None,
) -> Array:
    """Fused integer GEMM on the **original** (M, K) x (K, N) operands.

    ``a``/``b`` hold signed ``w``-bit values in any integer dtype; the
    wrapper zero-pads to tile multiples (padding commutes with the in-kernel
    correction: split(0) = (0, -z) and the K term uses padded K) and slices
    the result back.  ``mode`` picks the digit layout: ``"auto"`` resolves
    the paper's default (``w <= m`` -> single-pass MM1, above -> 3-pass
    KMM2); ``"mm2"`` runs the conventional 4-pass boundary mode (valid
    through ``w <= 2m``); ``"kmm4"`` runs depth-2 KMM (4 digits, 9 passes)
    whose per-leaf int32 accumulators stay exact to far deeper K than the
    single-level split (see ``tune.space.plan_accum_k_bound``).

    With ``sx`` (M, 1) / ``sw`` (1, N) fp32 scales the dequant epilogue
    ``out = acc * (sx * sw)`` runs in the same kernel (fp32, or ``out_dtype``
    e.g. bf16) — bit-identical to the staged ``acc * (sx * sw)``
    post-multiply.  Without scales the output is int32 for exact plans,
    fp32 otherwise.
    """
    return _fused_call(a, b, sx, sw, None, grouped=False, w=w, m=m,
                       mode=mode, seg=None, block_m=block_m, block_n=block_n,
                       block_k=block_k, combine_int32=combine_int32,
                       out_dtype=out_dtype, interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("w", "m", "mode", "seg", "block_m", "block_n",
                     "block_k", "combine_int32", "out_dtype", "interpret"),
)
def fused_gemm_grouped(
    a: Array, b: Array, sx: Optional[Array] = None,
    sw: Optional[Array] = None, counts: Optional[Array] = None, *,
    w: int,
    m: int = 8,
    mode: str = "auto",
    seg: Optional[int] = None,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 256,
    combine_int32: bool = False,
    out_dtype=None,
    interpret: Optional[bool] = None,
) -> Array:
    """Grouped/batched :func:`fused_gemm`: (E, C, K) x (E, K, N) -> (E, C, N).

    The expert axis is a leading parallel grid dimension, so all expert
    GEMMs of an MoE layer run inside one kernel launch (one set of jits, no
    per-expert dispatch).  Scales, when given, are (E, C, 1) and (E, 1, N).
    Per-group results are bit-identical to E independent ``fused_gemm``
    calls with the same tiles.

    ``counts`` (E, S) int32 with static ``seg`` makes the launch *ragged*
    (MegaBlocks-style): the C rows of expert ``e`` are read as S segments of
    ``seg`` rows each, of which only the first ``counts[e, s]`` are live.
    Dead rows come out as exact zeros; live rows are bit-identical to the
    dense launch with the same tiles (the mask touches outputs, never the
    accumulation), and m-blocks with no live row skip their MXU passes —
    the capacity-bucketed MoE dispatch (models/moe.py) passes S = batch,
    seg = capacity.  A zero-count segment (zero-token expert) is all-dead.
    """
    return _fused_call(a, b, sx, sw, counts, grouped=True, w=w, m=m,
                       mode=mode, seg=seg, block_m=block_m, block_n=block_n,
                       block_k=block_k, combine_int32=combine_int32,
                       out_dtype=out_dtype, interpret=interpret)
