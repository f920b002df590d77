"""Pallas TPU kernel: MM1 int8 GEMM (paper Fig. 7 baseline MXU).

The single-pass baseline for w <= m = 8: one int8 MXU product per tile with
one int32 VMEM accumulator.  The MXU dot over block_k is the Algorithm-5
pre-accumulation (p = block_k); the persistent accumulator sees one add per
K tile (the single wide add of Fig. 6).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _mm1_kernel(a_ref, b_ref, out_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.int32)

    @pl.when(k == nk - 1)
    def _store():
        out_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret"))
def mm1_gemm(
    a: Array, b: Array, *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 256,
    interpret: Optional[bool] = None,
) -> Array:
    """int8 (M, K) @ (K, N) -> int32, exact."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    m, k = a.shape
    _, n = b.shape
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        (m, n, k, block_m, block_n, block_k))
    grid = (m // block_m, n // block_n, k // block_k)
    return pl.pallas_call(
        functools.partial(_mm1_kernel, nk=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
