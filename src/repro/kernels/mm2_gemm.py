"""Pallas TPU kernel: conventional MM2 integer GEMM (paper Algorithm 3 baseline).

Identical structure to :mod:`repro.kernels.kmm_gemm` but with the conventional
FOUR digit-plane products (C1, C10, C01, C0) and four int32 VMEM accumulators
— the baseline against which KMM2's 3-pass / 3-accumulator advantage is
measured (25% fewer MXU passes, 25% less accumulator VMEM).  Valid for
w <= 2m = 16 with centered digits.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _mm2_kernel(a1_ref, a0_ref, b1_ref, b0_ref, out_ref,
                acc1_ref, acc10_ref, acc01_ref, acc0_ref, *, h: int, nk: int,
                combine_int32: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc1_ref[...] = jnp.zeros_like(acc1_ref)
        acc10_ref[...] = jnp.zeros_like(acc10_ref)
        acc01_ref[...] = jnp.zeros_like(acc01_ref)
        acc0_ref[...] = jnp.zeros_like(acc0_ref)

    a1 = a1_ref[...]
    a0 = a0_ref[...]
    b1 = b1_ref[...]
    b0 = b0_ref[...]
    # Four sub-MXU passes (Fig. 3): the conventional digit cross-products.
    acc1_ref[...] += jnp.dot(a1, b1, preferred_element_type=jnp.int32)
    acc10_ref[...] += jnp.dot(a1, b0, preferred_element_type=jnp.int32)
    acc01_ref[...] += jnp.dot(a0, b1, preferred_element_type=jnp.int32)
    acc0_ref[...] += jnp.dot(a0, b0, preferred_element_type=jnp.int32)

    @pl.when(k == nk - 1)
    def _combine():
        c1 = acc1_ref[...]
        c10 = acc10_ref[...]
        c01 = acc01_ref[...]
        c0 = acc0_ref[...]
        if combine_int32:
            out_ref[...] = (c1 << (2 * h)) + ((c10 + c01) << h) + c0
        else:
            mid = c10.astype(jnp.float32) + c01.astype(jnp.float32)
            out_ref[...] = (c1.astype(jnp.float32) * (2.0 ** (2 * h))
                            + mid * (2.0 ** h) + c0.astype(jnp.float32))


@functools.partial(
    jax.jit,
    static_argnames=("h", "block_m", "block_n", "block_k", "combine_int32",
                     "interpret"),
)
def mm2_gemm_planes(
    a1: Array, a0: Array, b1: Array, b0: Array, *,
    h: int,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 256,
    combine_int32: bool = False,
    interpret: Optional[bool] = None,
) -> Array:
    """MM2 GEMM on pre-split s8 digit planes (see kmm_gemm for conventions)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    m, k = a1.shape
    _, n = b1.shape
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        (m, n, k, block_m, block_n, block_k))
    grid = (m // block_m, n // block_n, k // block_k)
    out_dtype = jnp.int32 if combine_int32 else jnp.float32
    kernel = functools.partial(
        _mm2_kernel, h=h, nk=grid[2], combine_int32=combine_int32)
    a_spec = pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk))
    b_spec = pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[a_spec, a_spec, b_spec, b_spec],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)] * 4,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a1, a0, b1, b0)
