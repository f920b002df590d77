"""High-level integer GEMM entry point: dispatch + digit planes + corrections.

``int_gemm(a, b, w)`` is the production API: signed w-bit integer operands
(carried in int32) multiplied exactly through the mode the paper's
precision-scalable rule selects (MM1 / KMM2 / MM2), on either the Pallas MXU
kernels (``backend="pallas"``) or plain XLA dot_generals (``backend="xla"``,
the default — used inside pjit'd model code so SPMD partitioning and the
dry-run cost analysis see ordinary dots).

Execution is plan-driven: :func:`repro.core.dispatch.select_plan` resolves an
:class:`~repro.core.dispatch.ExecPlan` (variant, tiles, combine precision,
recursion depth) — from the paper's analytic rule by default, or from the
active :mod:`repro.tune` table when one is installed — and :func:`run_plan`
executes it.  ``run_plan(..., use_ref_kernels=True)`` swaps the Pallas digit
kernels for their pure-jnp mirrors in :mod:`repro.kernels.ref` while keeping
the padding/correction wrapper identical, which is the bit-exact oracle the
autotuner checks every candidate against.

The Pallas backend's default route is the fused single-pass kernel
(kernels/fused_gemm.py, DESIGN.md §11): digit split, MXU passes, zero-point
correction and optional dequant epilogue inside one pallas_call.  The staged
pipeline below (_int_gemm_pallas: _planes in HBM -> digit kernel ->
correction) remains as the MM2/deep-recursion fallback and as the fused
kernel's bit-exact oracle wrapper (``use_ref_kernels=True``).

Digit handling for the Pallas path (see kmm_gemm.py): split at h = ceil(w/2),
center the low digit by z = 2^(h-1) so all planes are s8, then fold the
centering back with the paper's zero-point-adjuster correction:

    A@B = Abar@Bbar + z*rowsum(Abar) + z*colsum(Bbar) + K*z^2

(rowsum broadcast over columns, colsum over rows).  Zero padding commutes
with the correction because split(0) = (0, -z) and the K term uses padded K.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.dispatch import ExecPlan, Mode, select_plan
from repro.core.strassen import STRASSEN_VARIANTS, strassen_matmul
from repro.core.kmm import kmm_n, mm_n, max_exact_k
from repro.kernels.ffip import ffip_gemm_literal
from repro.kernels.fused_gemm import fused_gemm
from repro.kernels.kmm_gemm import kmm2_gemm_planes
from repro.kernels.mm1_gemm import mm1_gemm
from repro.kernels.mm2_gemm import mm2_gemm_planes
from repro.kernels.ref import ref_int_gemm, ref_kmm2_planes, ref_mm2_planes

Array = jax.Array


def _pad_to(x: Array, mult0: int, mult1: int) -> Array:
    p0 = (-x.shape[0]) % mult0
    p1 = (-x.shape[1]) % mult1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


def _planes(x: Array, h: int):
    z = 1 << (h - 1)
    xi = x.astype(jnp.int32)
    hi = jnp.right_shift(xi, h).astype(jnp.int8)
    lo = (jnp.bitwise_and(xi, (1 << h) - 1) - z).astype(jnp.int8)
    return hi, lo, z


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
    plan: Optional[ExecPlan] = None,
    context=None,
) -> Array:
    """Integer GEMM with precision-scalable dispatch (paper Fig. 10).

    a: (M, K) signed w-bit values in an integer dtype; b: (K, N) likewise.
    Returns float32 (or int32 when ``exact=True``, which asserts the int32
    exactness bound 2w + log2(K) + 2 <= 31 and uses integer combines).

    Tile sizes default to the active tuning table's winner for this
    (backend, M/N/K bucket, w) key — or (128, 128, 256) when no table is
    installed; explicit ``block_*`` arguments always win.  ``plan`` bypasses
    selection entirely and executes the given :class:`ExecPlan` (the
    autotuner's entry point).

    ``context`` (an :class:`repro.core.context.ExecContext`) supplies
    backend / tuning table / mesh in one object; with ``context.mesh`` set
    and the pallas backend, the kernel runs shard-mapped over the mesh
    (:mod:`repro.dist.shard_gemm`) on negotiated M/N axes.  The mesh is
    never inferred from ambient state here — collective helpers that call
    ``int_gemm`` from inside their own ``shard_map`` stay single-shard.
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
    if plan is None:
        plan = select_plan((m_dim, k_dim, n_dim), w, m=m, backend=backend,
                           exact=exact, context=context)
        overrides = {k: v for k, v in (("block_m", block_m),
                                       ("block_n", block_n),
                                       ("block_k", block_k)) if v is not None}
        if overrides:
            plan = dataclasses.replace(plan, **overrides)
    mesh = context.mesh if context is not None else None
    out = run_plan(a, b, plan=plan, interpret=interpret, mesh=mesh)
    if exact:
        return out
    return out if out.dtype == jnp.float32 else out.astype(jnp.float32)


def run_plan(a: Array, b: Array, *, plan: ExecPlan,
             interpret: Optional[bool] = None,
             use_ref_kernels: bool = False,
             mesh=None, context=None) -> Array:
    """Execute one :class:`ExecPlan` on (M, K) x (K, N) integer operands.

    Output dtype follows the plan: int32 for exact-int plans
    (``plan.is_exact_int``), float32 for fp32-combine plans.
    ``use_ref_kernels`` routes the digit-plane products through the pure-jnp
    mirrors in :mod:`repro.kernels.ref` instead of the Pallas kernels —
    identical padding/correction wrapper, bit-identical result — giving the
    tuner its correctness oracle.

    With ``mesh`` (or ``context.mesh``) set and a pallas-backend plan, the
    plan executes shard-mapped (:func:`repro.dist.shard_gemm
    .sharded_run_plan`): each shard runs the identical kernel on its local
    block — covering the fused kernel AND the staged fallback variants —
    with M/N axes from ``plan.shard`` (negotiated when unset).  XLA-backend
    plans ignore the mesh (plain dot_generals partition via GSPMD).
    """
    if mesh is None and context is not None:
        mesh = context.mesh
    if mesh is not None and plan.backend == "pallas" \
            and not getattr(mesh, "empty", False):
        from repro.dist.shard_gemm import sharded_run_plan
        return sharded_run_plan(a, b, plan=plan, mesh=mesh,
                                interpret=interpret,
                                use_ref_kernels=use_ref_kernels)
    if plan.shard is not None:
        plan = dataclasses.replace(plan, shard=None)
    if plan.variant in STRASSEN_VARIANTS:
        # Tile-level Strassen split (core/strassen.py): the 7 sub-GEMMs
        # re-enter this dispatcher with the derived sub-plan, so they ride
        # the full stack — fused Pallas kernels, interpret mode and the
        # ref-kernel oracle mirror included.
        def run_sub(x, y, sub_plan):
            return run_plan(x, y, plan=sub_plan, interpret=interpret,
                            use_ref_kernels=use_ref_kernels)
        return strassen_matmul(a, b, plan=plan, run_sub=run_sub)
    if plan.variant == "xla_ref":
        return ref_int_gemm(a, b)
    if plan.variant == "ffip":
        return ffip_gemm_literal(a, b)
    if plan.variant in ("fused", "fused_mm2"):
        if use_ref_kernels:
            # The staged pure-jnp mirror IS the fused kernel's oracle: the
            # fused plan's mode/depth/tiles drive the identical padding +
            # zero-point-correction wrapper below (incl. the staged depth-2
            # branch and the MM2 plane mirror).
            return _int_gemm_pallas(a, b, plan=plan, interpret=interpret,
                                    use_ref_kernels=True)
        bm, bn, bk = plan.tiles
        mode = ("mm2" if plan.variant == "fused_mm2" else
                "kmm4" if plan.depth == 2 else "auto")
        return fused_gemm(a, b, w=plan.w, m=plan.m, mode=mode, block_m=bm,
                          block_n=bn, block_k=bk,
                          combine_int32=plan.combine_int32,
                          interpret=interpret)
    if plan.backend == "xla":
        return _int_gemm_xla(a, b, plan=plan)
    return _int_gemm_pallas(a, b, plan=plan, interpret=interpret,
                            use_ref_kernels=use_ref_kernels)


@functools.partial(jax.jit,
                   static_argnames=("plan", "interpret", "use_ref_kernels",
                                    "mesh", "context"))
def run_plan_jit(a: Array, b: Array, plan: ExecPlan,
                 interpret: Optional[bool] = None,
                 use_ref_kernels: bool = False,
                 mesh=None, context=None) -> Array:
    """jit'd :func:`run_plan` (ExecPlan is frozen/hashable, so it is a
    static arg — one trace per plan).  ``mesh``/``context`` are static too
    (Mesh and ExecContext both hash; the context's table is excluded from
    its hash and is irrelevant here — the plan is already resolved)."""
    return run_plan(a, b, plan=plan, interpret=interpret,
                    use_ref_kernels=use_ref_kernels, mesh=mesh,
                    context=context)


def _int_gemm_xla(a: Array, b: Array, *, plan: ExecPlan) -> Array:
    combine = jnp.int32 if plan.combine_int32 else jnp.float32
    ai, bi = a.astype(jnp.int32), b.astype(jnp.int32)
    if plan.mode is Mode.MM1:
        return jax.lax.dot_general(ai, bi, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.int32)
    fn = kmm_n if plan.mode is Mode.KMM2 else mm_n
    return fn(ai, bi, w=plan.w, n=plan.digits, combine_dtype=combine)


def _int_gemm_pallas(a: Array, b: Array, *, plan: ExecPlan,
                     interpret: Optional[bool],
                     use_ref_kernels: bool = False) -> Array:
    m_dim, k_dim = a.shape
    n_dim = b.shape[1]
    block_m, block_n, block_k = plan.tiles
    exact = plan.combine_int32
    a = _pad_to(a.astype(jnp.int32), block_m, block_k)
    b = _pad_to(b.astype(jnp.int32), block_k, block_n)
    kp = a.shape[1]
    if plan.mode is Mode.MM1:
        if use_ref_kernels:
            out = ref_int_gemm(a.astype(jnp.int8), b.astype(jnp.int8))
        else:
            out = mm1_gemm(a.astype(jnp.int8), b.astype(jnp.int8),
                           block_m=block_m, block_n=block_n, block_k=block_k,
                           interpret=interpret)
        return out[:m_dim, :n_dim]
    h = -(-plan.w // 2)
    z = 1 << (h - 1)
    if plan.depth == 2 and plan.mode is Mode.KMM2:
        core = _kmm4_core(a, b, h=h, z=z, exact=exact, tiles=plan.tiles,
                          interpret=interpret,
                          use_ref_kernels=use_ref_kernels)
    elif plan.depth > 1:
        raise NotImplementedError(
            "pallas backend implements KMM recursion up to depth 2 "
            "(plus single-level MM2); use backend='xla' for deeper "
            "recursion")
    else:
        a1, a0, _ = _planes(a, h)
        b1, b0, _ = _planes(b, h)
        if use_ref_kernels:
            ref = ref_kmm2_planes if plan.mode is Mode.KMM2 \
                else ref_mm2_planes
            core = ref(a1, a0, b1, b0, h=h, combine_int32=exact)
        else:
            kernel = kmm2_gemm_planes if plan.mode is Mode.KMM2 \
                else mm2_gemm_planes
            core = kernel(a1, a0, b1, b0, h=h, block_m=block_m,
                          block_n=block_n, block_k=block_k,
                          combine_int32=exact, interpret=interpret)
    # Zero-point adjuster (paper Section IV-D / prior work [6]).  The digit
    # identity abar = a - z (elementwise, padded zeros included) gives the
    # correction sums directly from the padded operands — no abar/bbar
    # reconstruction, two fewer full-array passes; values are int32-exact
    # and bit-identical to summing the rebuilt planes.
    row = (jnp.sum(a, axis=1, keepdims=True, dtype=jnp.int32)
           - jnp.int32(kp * z))               # (M, 1) rowsum(abar)
    col = (jnp.sum(b, axis=0, keepdims=True, dtype=jnp.int32)
           - jnp.int32(kp * z))               # (1, N) colsum(bbar)
    if exact:
        corr = z * row + z * col + jnp.int32(z * z * kp)
        out = core + corr
    else:
        corr = (z * row.astype(jnp.float32) + z * col.astype(jnp.float32)
                + float(z) * float(z) * float(kp))
        out = core + corr
    return out[:m_dim, :n_dim]


def _kmm4_core(a: Array, b: Array, *, h: int, z: int, exact: bool, tiles,
               interpret: Optional[bool], use_ref_kernels: bool) -> Array:
    """Staged depth-2 KMM core on padded int32 operands: three branch KMM2
    plane launches at the level-2 split + the level-1 combine in jnp.

    The level-1 centered split at ``h`` yields branches {A1, A1+A0bar,
    A0bar} (each fits h+1 signed bits); each branch is re-split *plain*
    (uncentered — exact in two's complement, so no per-branch zero-point
    correction) at ``h2 = ceil((h+1)/2)`` into int16 planes that the
    single-level KMM2 kernel consumes unchanged.  Operation sequences match
    the fused kmm4 kernel level for level, so fp32 combines are
    bit-identical; the caller applies the one level-1 zero-point
    correction.
    """
    block_m, block_n, block_k = tiles
    mask = (1 << h) - 1
    a1 = jnp.right_shift(a, h)
    a0 = jnp.bitwise_and(a, mask) - z
    b1 = jnp.right_shift(b, h)
    b0 = jnp.bitwise_and(b, mask) - z
    h2 = -(-(h + 1) // 2)
    mask2 = (1 << h2) - 1

    def branch(av, bv):
        av1 = jnp.right_shift(av, h2).astype(jnp.int16)
        av0 = jnp.bitwise_and(av, mask2).astype(jnp.int16)
        bv1 = jnp.right_shift(bv, h2).astype(jnp.int16)
        bv0 = jnp.bitwise_and(bv, mask2).astype(jnp.int16)
        if use_ref_kernels:
            return ref_kmm2_planes(av1, av0, bv1, bv0, h=h2,
                                   combine_int32=exact)
        return kmm2_gemm_planes(av1, av0, bv1, bv0, h=h2, block_m=block_m,
                                block_n=block_n, block_k=block_k,
                                combine_int32=exact, interpret=interpret)

    c11 = branch(a1, b1)
    css = branch(a1 + a0, b1 + b0)
    c00 = branch(a0, b0)
    if exact:
        return (c11 << (2 * h)) + ((css - c11 - c00) << h) + c00
    mid = css - c11 - c00
    return c11 * (2.0 ** (2 * h)) + mid * (2.0 ** h) + c00


@functools.partial(jax.jit, static_argnames=("w", "m", "backend", "exact"))
def int_gemm_jit(a: Array, b: Array, w: int, m: int = 8,
                 backend: str = "xla", exact: bool = False) -> Array:
    return int_gemm(a, b, w=w, m=m, backend=backend, exact=exact)


def quantize_symmetric(x: Array, w: int, axis=None):
    """Symmetric signed w-bit quantization. Returns (q_int32, scale_f32).

    Thin alias for :func:`repro.quant.quantize.quantize_symmetric` — the one
    shared recipe (imported lazily: ``repro.quant``'s package init imports
    qmatmul, which imports the fused kernel from this package)."""
    from repro.quant.quantize import quantize_symmetric as _qs
    return _qs(x, w, axis=axis)
