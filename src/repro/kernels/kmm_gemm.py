"""Pallas TPU kernel: KMM2 integer GEMM (paper Fig. 8 adapted to the MXU).

The fixed-precision KMM architecture keeps three sub-MXUs, one per digit-plane
product (C1, Cs, C0), each with its own accumulator, and combines them once in
the post-adder unit (Fig. 9).  The TPU-native mapping:

  * the three "sub-MXUs" are three int8 MXU passes per (bm, bk)x(bk, bn) tile;
  * the three dedicated accumulators are three int32 VMEM scratch buffers that
    persist across the K grid dimension — each digit product accumulates
    *exactly* in int32 (digit magnitudes are ~2^(w/2), so the int32 headroom
    covers K up to 2^(31 - w - 2));
  * the post-adder combine runs once per output tile on the final K step,
    either in int32 (exact when 2w + log2(K) + 2 <= 31) or in fp32 (the
    paper's wide 2w + w_a accumulators have no int32 TPU analogue — see
    DESIGN.md §2); every input to the combine is an exact int32, so fp32
    introduces a single rounding per output element;
  * Algorithm 5 appears structurally: the MXU dot over block_k is the narrow
    pre-accumulation (p = block_k) and each digit accumulator sees exactly one
    add per K tile — the wide-add count drops by block_k, as in Fig. 6;
  * the A_s/B_s pre-adders (X-adder vector of Fig. 8) are int8 VPU adds on the
    digit planes inside the kernel.

Digit convention (signed, MXU s8-native): the wrapper in ops.py splits w-bit
operands at h = ceil(w/2) into a signed high digit and a *zero-centered* low
digit (low - 2^(h-1)), then folds the centering back with the paper's
zero-point-adjuster correction (Section IV-D).  With centered digits the
A_s = A1 + A0 plane fits s8 for every w <= 2m - 2 = 14 — the same bound that
defines the paper's KMM2 dispatch window.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _kmm2_kernel(a1_ref, a0_ref, b1_ref, b0_ref, out_ref,
                 acc1_ref, accs_ref, acc0_ref, *, h: int, nk: int,
                 combine_int32: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc1_ref[...] = jnp.zeros_like(acc1_ref)
        accs_ref[...] = jnp.zeros_like(accs_ref)
        acc0_ref[...] = jnp.zeros_like(acc0_ref)

    a1 = a1_ref[...]
    a0 = a0_ref[...]
    b1 = b1_ref[...]
    b0 = b0_ref[...]
    # Fig. 8 input pre-adders: A_s = A1 + A0, B_s = B1 + B0 (s8-safe, w<=14).
    a_s = a1 + a0
    b_s = b1 + b0
    # Three sub-MXU passes; int32 MXU accumulation is the Algorithm-5 pre-sum.
    acc1_ref[...] += jnp.dot(a1, b1, preferred_element_type=jnp.int32)
    accs_ref[...] += jnp.dot(a_s, b_s, preferred_element_type=jnp.int32)
    acc0_ref[...] += jnp.dot(a0, b0, preferred_element_type=jnp.int32)

    @pl.when(k == nk - 1)
    def _combine():
        # KMM post-adder unit (Fig. 9): C = C1<<2h + (Cs-C1-C0)<<h + C0.
        c1 = acc1_ref[...]
        cs = accs_ref[...]
        c0 = acc0_ref[...]
        if combine_int32:
            mid = cs - c1 - c0
            out_ref[...] = (c1 << (2 * h)) + (mid << h) + c0
        else:
            c1f = c1.astype(jnp.float32)
            c0f = c0.astype(jnp.float32)
            mid = cs.astype(jnp.float32) - c1f - c0f
            out_ref[...] = c1f * (2.0 ** (2 * h)) + mid * (2.0 ** h) + c0f


@functools.partial(
    jax.jit,
    static_argnames=("h", "block_m", "block_n", "block_k", "combine_int32",
                     "interpret"),
)
def kmm2_gemm_planes(
    a1: Array, a0: Array, b1: Array, b0: Array, *,
    h: int,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 256,
    combine_int32: bool = False,
    interpret: Optional[bool] = None,
) -> Array:
    """KMM2 GEMM on pre-split s8 digit planes.

    a1, a0: (M, K) int8 high/low(-centered) digit planes of A.
    b1, b0: (K, N) int8 digit planes of B.
    Returns (M, N) int32 if ``combine_int32`` else float32.  Shapes must be
    multiples of the block sizes (ops.py pads).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    m, k = a1.shape
    _, n = b1.shape
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        (m, n, k, block_m, block_n, block_k))
    grid = (m // block_m, n // block_n, k // block_k)
    out_dtype = jnp.int32 if combine_int32 else jnp.float32
    kernel = functools.partial(
        _kmm2_kernel, h=h, nk=grid[2], combine_int32=combine_int32)
    a_spec = pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk))
    b_spec = pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[a_spec, a_spec, b_spec, b_spec],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a1, a0, b1, b0)
