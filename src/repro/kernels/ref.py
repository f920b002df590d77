"""Pure-jnp references for the GEMM kernels (tests only).

``ref_int_gemm_i64`` is the numpy int64 oracle: the exact product, which the
exact int32 class (mm1, ``combine_int32``) must equal.  ``ref_fused_gemm``
is the pure-jnp mirror of the fused kernel
(:mod:`repro.kernels.fused_gemm`): the same padded K, centered digit planes,
post-adder combine and zero-point correction with no tiling, which the fp32
combine class must equal bit for bit.  ``ref_int_gemm`` is the jit-able exact
int32 dot both build on.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

Array = jax.Array


def ref_int_gemm(a: Array, b: Array) -> Array:
    """Exact int32 GEMM oracle: (M, K) @ (K, N) with int32 accumulation."""
    return lax.dot_general(
        a.astype(jnp.int32), b.astype(jnp.int32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )


def ref_int_gemm_i64(a, b) -> np.ndarray:
    """numpy int64 oracle — exact for all w <= 16 and any practical K."""
    return np.asarray(a, dtype=np.int64) @ np.asarray(b, dtype=np.int64)


def ref_digit_planes(x: Array, w: int):
    """The centered s8 digit planes of the fused kernel's split.

    Returns (hi, lo_centered, h, z) with x == (hi << h) + lo_centered + z
    elementwise, hi/lo_centered both in s8 range for w <= 16.
    """
    h = -(-w // 2)
    hi, lo = _centered_planes(x.astype(jnp.int32), h)
    return hi.astype(jnp.int8), lo.astype(jnp.int8), h, 1 << (h - 1)


def ref_kmm2_planes(a1: Array, a0: Array, b1: Array, b0: Array, h: int,
                    combine_int32: bool = False) -> Array:
    """jnp mirror of the KMM2 kernel math on digit planes (no tiling)."""
    a1i, a0i = a1.astype(jnp.int32), a0.astype(jnp.int32)
    b1i, b0i = b1.astype(jnp.int32), b0.astype(jnp.int32)
    c1 = ref_int_gemm(a1i, b1i)
    cs = ref_int_gemm(a1i + a0i, b1i + b0i)
    c0 = ref_int_gemm(a0i, b0i)
    if combine_int32:
        return (c1 << (2 * h)) + ((cs - c1 - c0) << h) + c0
    c1f, c0f = c1.astype(jnp.float32), c0.astype(jnp.float32)
    mid = cs.astype(jnp.float32) - c1f - c0f
    return c1f * (2.0 ** (2 * h)) + mid * (2.0 ** h) + c0f


def ref_mm2_planes(a1: Array, a0: Array, b1: Array, b0: Array, h: int,
                   combine_int32: bool = False) -> Array:
    """jnp mirror of the MM2 kernel math on digit planes (no tiling)."""
    a1i, a0i = a1.astype(jnp.int32), a0.astype(jnp.int32)
    b1i, b0i = b1.astype(jnp.int32), b0.astype(jnp.int32)
    c1 = ref_int_gemm(a1i, b1i)
    c10 = ref_int_gemm(a1i, b0i)
    c01 = ref_int_gemm(a0i, b1i)
    c0 = ref_int_gemm(a0i, b0i)
    if combine_int32:
        return (c1 << (2 * h)) + ((c10 + c01) << h) + c0
    mid = c10.astype(jnp.float32) + c01.astype(jnp.float32)
    return (c1.astype(jnp.float32) * (2.0 ** (2 * h)) + mid * (2.0 ** h)
            + c0.astype(jnp.float32))


def _centered_planes(x: Array, h: int):
    z = 1 << (h - 1)
    return jnp.right_shift(x, h), jnp.bitwise_and(x, (1 << h) - 1) - z


def _ref_kmm4_core(a: Array, b: Array, h: int, combine_int32: bool) -> Array:
    """Depth-2 KMM on padded int32 operands without the level-1 correction:
    the centered split at ``h`` gives branches {A1, A1 + A0bar, A0bar}, each
    re-split plainly (uncentered) at ``h2 = ceil((h+1)/2)`` into a KMM2 at
    ``h2``, then the level-1 combine — the fused kmm4 mode's sequence."""
    a1, a0 = _centered_planes(a, h)
    b1, b0 = _centered_planes(b, h)
    h2 = -(-(h + 1) // 2)
    mask2 = (1 << h2) - 1

    def branch(av, bv):
        return ref_kmm2_planes(
            jnp.right_shift(av, h2), jnp.bitwise_and(av, mask2),
            jnp.right_shift(bv, h2), jnp.bitwise_and(bv, mask2), h=h2,
            combine_int32=combine_int32)

    c11 = branch(a1, b1)
    css = branch(a1 + a0, b1 + b0)
    c00 = branch(a0, b0)
    if combine_int32:
        return (c11 << (2 * h)) + ((css - c11 - c00) << h) + c00
    mid = css - c11 - c00
    return c11 * (2.0 ** (2 * h)) + mid * (2.0 ** h) + c00


def ref_fused_gemm(a: Array, b: Array, *, w: int, mode: str, block_k: int,
                   combine_int32: bool) -> Array:
    """jnp mirror of :func:`repro.kernels.fused_gemm.fused_gemm` without
    the dequant epilogue: int32 out for mm1 and ``combine_int32``, fp32
    otherwise.  ``mode`` is one of mm1/kmm2/mm2/kmm4; ``block_k`` sets the
    padded K, which the fp32 correction term reads (M/N tiles never move a
    value)."""
    m_dim, n_dim = a.shape[0], b.shape[1]
    pad = (-a.shape[1]) % block_k
    a = jnp.pad(a.astype(jnp.int32), ((0, 0), (0, pad)))
    b = jnp.pad(b.astype(jnp.int32), ((0, pad), (0, 0)))
    kp = a.shape[1]
    if mode == "mm1":
        return ref_int_gemm(a, b)
    h = -(-w // 2)
    z = 1 << (h - 1)
    if mode == "kmm4":
        core = _ref_kmm4_core(a, b, h, combine_int32)
    else:
        a1, a0 = _centered_planes(a, h)
        b1, b0 = _centered_planes(b, h)
        ref = {"kmm2": ref_kmm2_planes, "mm2": ref_mm2_planes}[mode]
        core = ref(a1, a0, b1, b0, h=h, combine_int32=combine_int32)
    # Zero-point adjuster (paper Section IV-D): abar = a - z elementwise,
    # padded zeros included, so the sums come from the padded operands.
    row = (jnp.sum(a, axis=1, keepdims=True, dtype=jnp.int32)
           - jnp.int32(kp * z))               # (M, 1) rowsum(abar)
    col = (jnp.sum(b, axis=0, keepdims=True, dtype=jnp.int32)
           - jnp.int32(kp * z))               # (1, N) colsum(bbar)
    if combine_int32:
        out = core + (z * row + z * col + jnp.int32(z * z * kp))
    else:
        out = core + (z * row.astype(jnp.float32)
                      + z * col.astype(jnp.float32)
                      + float(z) * float(z) * float(kp))
    return out[:m_dim, :n_dim]
