"""Algorithm 5 — matmul with reduced accumulator complexity.

The paper mitigates KMM's accumulation penalty by pre-accumulating ``p``
products on a narrow ``2w + ceil(log2 p)``-bit adder before one add into the
wide ``2w + ceil(log2 d)``-bit running sum (Eq. 10), cutting wide adds and
accumulator registers by ``p`` (Fig. 6).

Tensor form: the contraction axis K is blocked into groups of ``p``; products
within a group reduce first (the narrow pre-sum), then group sums reduce into
the running accumulator.  The result is bit-identical to a flat accumulation;
what changes is the *hardware* cost, which :mod:`repro.core.complexity` and
:mod:`repro.core.area` account for.  The fused Pallas kernel
(:mod:`repro.kernels.fused_gemm`) keeps its digit accumulators in VMEM
across the K grid.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

Array = jax.Array

DEFAULT_P = 4  # the paper's evaluation setting


def preaccum_matmul(
    a: Array,
    b: Array,
    *,
    p: int = DEFAULT_P,
    accum_dtype=jnp.int32,
) -> Array:
    """Algorithm 5 on (..., M, K) x (K, N): two-level accumulation.

    K must be divisible by ``p`` (pad upstream otherwise).  Exactness bound:
    the pre-sum of ``p`` products of w-bit values needs ``2w + ceil(log2 p)``
    bits — int32 carriers keep this exact for the bitwidths the dispatch rule
    admits (w <= 14 with p <= 16).
    """
    m_axis, k = a.shape[:-1], a.shape[-1]
    if k % p:
        raise ValueError(f"K={k} not divisible by pre-accumulation p={p}")
    n = b.shape[-1]
    groups = k // p
    a_g = a.reshape(*m_axis, groups, p)
    b_g = b.reshape(groups, p, n)
    # Narrow pre-sum: contract only within each group of p.
    partial = lax.dot_general(
        a_g, b_g,
        dimension_numbers=(((a_g.ndim - 1,), (1,)), ((a_g.ndim - 2,), (0,))),
        preferred_element_type=accum_dtype,
    )  # (groups, ..., M, N)
    # Wide accumulation: one add per group into the running sum.
    return jnp.sum(partial, axis=0, dtype=accum_dtype)


def preaccum_mm1(p: int = DEFAULT_P, accum_dtype=jnp.int32):
    """Algorithm-5 base matmul usable as the ``mm1`` hook of Algorithms 3/4.

    Only plain (M, K) x (K, N) dimension numbers are supported — that is the
    shape the MXU tiles see.
    """

    def mm1(a: Array, b: Array, dims: lax.DotDimensionNumbers) -> Array:
        from repro.core.kmm import MATMUL_DIMS

        if dims != MATMUL_DIMS:
            return lax.dot_general(a, b, dims, preferred_element_type=accum_dtype)
        return preaccum_matmul(a, b, p=p, accum_dtype=accum_dtype)

    return mm1


def wide_adds_saved(k: int, p: int = DEFAULT_P) -> float:
    """Fraction of wide (2w + log2 d)-bit adds removed by Algorithm 5."""
    return 1.0 - (k // p) / k
