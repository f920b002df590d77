"""High-level integer GEMM entry point: the paper's dispatch rule on two backends.

``int_gemm(a, b, w)`` is the public API: signed w-bit integer operands
(carried in int32) multiplied through the mode the paper's precision-scalable
rule selects (MM1 / KMM2 / MM2, KMM recursion beyond;
:func:`repro.core.dispatch.select_mode`), on either the fused Pallas kernel
(``backend="pallas"``, with the kernel's own plan
:func:`repro.kernels.fused_gemm.plan_for`) or plain XLA dot_generals
(``backend="xla"``, the default — the digit recursion of
:mod:`repro.core.kmm`, which SPMD partitioning and the dry-run cost analysis
see as ordinary dots).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.dispatch import Mode, select_mode
from repro.core.kmm import kmm_n, max_exact_k, mm_n
from repro.kernels.fused_gemm import fused_gemm, plan_for

Array = jax.Array


def int_gemm(
    a: Array,
    b: Array,
    *,
    w: int,
    m: int = 8,
    backend: str = "xla",
    exact: bool = False,
    block_m: Optional[int] = None,
    block_n: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    context=None,
) -> Array:
    """Integer GEMM with precision-scalable dispatch (paper Fig. 10).

    a: (M, K) signed w-bit values in an integer dtype; b: (K, N) likewise.
    Returns float32 (or int32 when ``exact=True``, which asserts the int32
    exactness bound 2w + log2(K) <= 31 and uses integer combines).

    On ``backend="pallas"`` the tiles are the kernel plan's; explicit
    ``block_*`` arguments override them.  Widths that need KMM recursion
    deeper than two levels, or shapes past the kernel's accumulator bounds,
    raise: run them on ``backend="xla"``.  ``context`` (an
    :class:`repro.core.context.ExecContext`) supplies the backend.
    """
    if context is not None:
        backend = context.backend
    if backend not in ("xla", "pallas"):
        raise ValueError(f"unknown backend {backend!r}")
    m_dim, k_dim = a.shape
    n_dim = b.shape[1]
    if exact and max_exact_k(w) < k_dim:
        raise ValueError(
            f"exact int32 output impossible for w={w}, K={k_dim}; "
            f"max exact K is {max_exact_k(w)}")
    if backend == "xla":
        out = _int_gemm_xla(a, b, w=w, m=m, exact=exact)
    else:
        plan = plan_for((m_dim, k_dim, n_dim), w, m, exact=exact)
        if plan is None:
            raise ValueError(
                f"the fused kernel does not run w={w} at shape "
                f"{(m_dim, k_dim, n_dim)}; use backend='xla'")
        out = fused_gemm(a, b, w=w, m=m, mode=plan.mode,
                         block_m=block_m or plan.block_m,
                         block_n=block_n or plan.block_n,
                         block_k=block_k or plan.block_k,
                         combine_int32=plan.combine_int32,
                         interpret=interpret)
    return out if exact else out.astype(jnp.float32)


def _int_gemm_xla(a: Array, b: Array, *, w: int, m: int,
                  exact: bool) -> Array:
    plan = select_mode(w, m)
    ai, bi = a.astype(jnp.int32), b.astype(jnp.int32)
    if plan.mode is Mode.MM1:
        return jax.lax.dot_general(ai, bi, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.int32)
    fn = kmm_n if plan.mode is Mode.KMM2 else mm_n
    return fn(ai, bi, w=w, n=plan.digits,
              combine_dtype=jnp.int32 if exact else jnp.float32)


@functools.partial(jax.jit, static_argnames=("w", "m", "backend", "exact"))
def int_gemm_jit(a: Array, b: Array, w: int, m: int = 8,
                 backend: str = "xla", exact: bool = False) -> Array:
    return int_gemm(a, b, w=w, m=m, backend=backend, exact=exact)
