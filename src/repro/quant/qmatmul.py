"""Quantized matmul with KMM integer GEMM core and straight-through gradients.

Forward: dynamic per-token activation quantization x per-channel weight
quantization to ``w`` bits -> integer GEMM through the precision-scalable
dispatch (MM1 / KMM2 / MM2; Karatsuba digit planes for 9-14 bits) -> dequant.
Backward: straight-through estimator — gradients flow as if the matmul were
full precision (standard integer quantized-training practice; the paper's
architectures are inference-side so STE only affects our training drivers).

Two entry points: ``quantized_matmul`` for (..., K) @ (K, N) dense layers and
``quantized_matmul_batched`` for (E, C, K) @ (E, K, N) expert GEMMs.

Execution is configured by an :class:`repro.core.context.ExecContext`
(``context=`` kwarg): backend, mesh, tuning table and force_mode in one
frozen bundle.  The legacy positional ``force_mode``/``backend`` kwargs keep
working through a shim that emits ``DeprecationWarning`` (DESIGN.md §12
migration table).

Backends.  ``backend="xla"`` (default) lowers to ordinary dot_generals (the
digit recursion of :mod:`repro.core.kmm`) so pjit'd model code stays
GSPMD-partitionable, then dequantizes with a post-multiply.
``backend="pallas"`` routes through the fused single-pass kernel
(:mod:`repro.kernels.fused_gemm`): digit split, MXU passes, zero-point
correction **and** the dequant epilogue (sx row scale x sw col scale) run in
one ``pallas_call`` — the scales are threaded into the kernel instead of a
separate elementwise pass, and expert GEMMs ride the grouped grid axis as a
single launch.  With ``context.mesh`` set, the kernel runs *shard-mapped*
over the mesh (:mod:`repro.dist.shard_gemm`): M over the data axes, N over
``model``, K replicated — bit-identical to the unsharded kernel — with
capability negotiation falling back to XLA (logged, per GEMM) when no mesh
axis tiles the problem or the local-K bounds fail.  Plans resolve through
the table-backed :func:`repro.core.dispatch.select_plan`; when the selected
plan cannot run fused (e.g. w > 2m-2, digit-accumulator headroom, a table
override, or ``force_mode``), the call falls back to the XLA path.
"""
from __future__ import annotations

import functools
import math
from dataclasses import replace
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.context import ExecContext, resolve_context
from repro.core.dispatch import analytic_plan, select_plan
from repro.core.kmm import kmm_n, max_exact_k, mm_n
from repro.kernels import ops
from repro.kernels.fused_gemm import (fused_gemm, fused_gemm_grouped,
                                      int8_tiles, pow2_cover)
from repro.obs import metrics as obs_metrics
from repro.quant.quantize import quantize_symmetric

Array = jax.Array

BACKENDS = ("xla", "pallas")

# Routing traffic of the quantized GEMM dispatch (trace-time, host-side:
# one hit per jit trace, a flag test when metrics are disabled).
_GEMM_ROUTES = obs_metrics.counter(
    "repro_quant_gemm_routes_total",
    "quantized-GEMM dispatch outcomes by backend and route",
    labels=("backend", "route"))
# Where a quantized GEMM's weight operand came from: ``prequant`` (a stored
# {"q", "scale"} record, quant/prequant.py) or ``runtime`` (quantized from
# full precision inside the call).  One hit per jit trace, like the routes.
_WEIGHT_SOURCE = obs_metrics.counter(
    "repro_quant_weight_source_total",
    "quantized-GEMM weight operands by source (prequant or runtime)",
    labels=("source",))
# Reasons the pallas route declined a GEMM (the table-independent XLA
# fallbacks; mesh-negotiation fallbacks count in repro.dist's counter).
_PALLAS_FALLBACKS = obs_metrics.counter(
    "repro_pallas_fallback_total",
    "pallas-route declines by reason (GEMM fell back to XLA)",
    labels=("reason",))


def _quantize(x: Array, w: int, axis) -> Tuple[Array, Array]:
    """Symmetric signed w-bit quantization along ``axis`` (None = per-tensor).

    Delegates to the shared :mod:`repro.quant.quantize` recipe with
    keepdims=True, so fused-epilogue scales and XLA post-multiply scales are
    produced by identical arithmetic.
    """
    return quantize_symmetric(x, w, axis=axis, keepdims=True)


def _quantize_weight(wmat: Array, w: int, axis: int) -> Tuple[Array, Array]:
    """Runtime per-output-channel weight quantization (the training path's
    weights change every step); counted as the ``runtime`` source."""
    _WEIGHT_SOURCE.inc("runtime")
    qw, sw = _quantize(wmat, w, axis=axis)
    # Keep the scale as a stored record holds it: left open, XLA folds its
    # 1/qmax into the activation scale's (one 1/qmax**2 constant), which
    # rounds the dequant differently from the prequantized path.
    return qw, jax.lax.optimization_barrier(sw)


def _dot_shape(qx: Array, qw: Array, dims) -> Tuple[int, int, int]:
    """Flattened (M, K, N) of a dot_general (batch dims folded into M)."""
    (lc, rc), (lb, rb) = dims
    k = 1
    for ax in lc:
        k *= qx.shape[ax]
    mm = 1
    for ax in range(qx.ndim):
        if ax not in lc:
            mm *= qx.shape[ax]
    n = 1
    for ax in range(qw.ndim):
        if ax not in rc and ax not in rb:
            n *= qw.shape[ax]
    return mm, k, n


def _int_dot(qx: Array, qw: Array, w: int, m: int, dims,
             force_mode: str = "auto") -> Array:
    """Integer GEMM on quantized values via the dispatched mode, fp32 out.

    Mode selection goes through the table-backed
    :func:`repro.core.dispatch.select_plan` (numerics-pinned: an installed
    tuning table can never change the computed values here, only — on
    backends where tiles matter — how they are computed), falling back to
    the paper's analytic rule when no table is active.
    """
    eplan = select_plan(_dot_shape(qx, qw, dims), w, m=m, backend="xla")
    if force_mode == "mm2" and w > m:
        return mm_n(qx, qw, w=w, n=max(eplan.digits, 2),
                    dimension_numbers=dims, combine_dtype=jnp.float32)
    if eplan.is_exact_int:
        # Every exact-class plan (mm1/xla_ref/ffip, int32-combine digit
        # variants) computes the same integer; on arbitrary dot_general dims
        # that integer is the fused int32 dot — identical to the analytic
        # w <= m path, so table/prior substitutions cannot move a bit.
        out = jax.lax.dot_general(qx, qw, dims,
                                  preferred_element_type=jnp.int32)
        return out.astype(jnp.float32)
    # fp32 class: pin_numerics guarantees variant/depth match the analytic
    # rule, so this runs exactly the paper's KMM2/MM2 digit recursion.
    fn = kmm_n if eplan.variant == "kmm2" else mm_n
    return fn(qx, qw, w=w, n=max(eplan.digits, 2), dimension_numbers=dims,
              combine_dtype=jnp.float32)


def _shrink_tiles(plan, shape):
    """Clamp the analytic default tiles to the runtime shape (pow2 cover,
    floor 8): serve-sized GEMMs (decode M = batch, prefill M = bucket)
    would otherwise pad every operand up to 128x256 tiles.  M/N clamping
    never affects values (padded rows/cols are sliced away and never enter
    retained outputs); the K clamp fixes the fp32-class padded K as a pure
    function of K, applied identically with or without a tuning table —
    select_plan's padded-K guard only ever adopts table tiles whose padding
    matches the un-clamped default, which the clamp preserves for every
    K >= the default block_k.

    Under a mesh this is called with the per-shard LOCAL shape: K is
    replicated by the negotiated layout, so the K clamp (hence the fp32
    padded K) is identical to the unsharded call — the M/N clamps adapt to
    the local block, which never moves a bit.
    """
    return replace(plan,
                   block_m=min(plan.block_m, pow2_cover(shape[0])),
                   block_n=min(plan.block_n, pow2_cover(shape[2])),
                   block_k=min(plan.block_k, pow2_cover(shape[1])))


def _fused_plan_for(shape, w: int, m: int, context: Optional[ExecContext]):
    """Resolve + tile the pallas plan for a (local) GEMM shape, and check
    the kernel's correctness bounds.  Analytic plans whose digit products
    run on the int8 path take the kernel's own tile rule
    (:func:`repro.kernels.fused_gemm.int8_tiles`), the rest the clamp of
    :func:`_shrink_tiles`; table plans keep their tiles.  Returns None on
    any bound failure (the XLA fallback applies, table-independent)."""
    from repro.tune.space import plan_accum_k_bound    # lazy: tune -> ops

    m_dim, k_dim, n_dim = shape
    table = context.resolve_table() if context is not None else None
    plan = select_plan(shape, w, m=m, backend="pallas", table=table)
    if plan.source == "analytic":
        tiles = int8_tiles(shape, _fused_mode(plan), w, m)
        if tiles is None:
            plan = _shrink_tiles(plan, shape)
        else:
            bm, bn, bk = tiles
            plan = replace(plan, block_m=bm, block_n=bn, block_k=bk)
    # Correctness bounds (identical with or without a table; outside them
    # the XLA fallback applies either way, keeping numerics table-free).
    # The accumulator bound is plan-aware: MM2's pre-adder-free digits and
    # depth-2's quarter-width leaves stretch the exact-K window well past
    # the single-level KMM2 bound, and for the strassen variants it is the
    # composed full-problem bound (tune.space.plan_accum_k_bound).
    if plan.is_exact_int and max_exact_k(w) < k_dim:
        return None
    kp = -(-k_dim // plan.block_k) * plan.block_k
    bound = plan_accum_k_bound(plan)
    if bound is not None and kp > bound:
        return None
    return plan


def _fused_mode(plan) -> str:
    """The fused kernel's mode string for an ExecPlan routed to it."""
    if plan.variant == "fused_mm2":
        return "mm2"
    return "kmm4" if plan.depth == 2 else "auto"


def _ragged_row_mask(counts: Array, seg: int, c_dim: int) -> Array:
    """(E, C, 1) liveness of capacity-bucketed expert rows: row ``r`` is
    live iff ``r % seg < counts[e, r // seg]`` — the same predicate the
    ragged grouped kernel evaluates in-kernel, evaluated in jnp for the
    XLA fallback and staged-redirect paths so the grouped ragged contract
    (dead rows are exact zeros) holds on every backend."""
    rows = jnp.arange(c_dim, dtype=jnp.int32)
    seg_ids = rows // seg
    n_seg = counts.shape[-1]
    limit = jnp.take(counts.astype(jnp.int32),
                     jnp.clip(seg_ids, 0, n_seg - 1), axis=-1)    # (E, C)
    live = (rows - seg_ids * seg < limit) & (seg_ids < n_seg)
    return live[..., None]


def _sharded_pallas(qx: Array, qw: Array, sx: Array, sw: Array, w: int,
                    m: int, dense: bool, shape, out_dtype,
                    context: ExecContext, counts: Optional[Array] = None,
                    seg: Optional[int] = None) -> Optional[Array]:
    """Shard-mapped pallas GEMM under ``context.mesh`` (DESIGN.md §12).

    Each shard runs the unmodified kernel on its local block; the
    zero-point correction and digit accumulators stay per-shard (inside the
    kernel), and with K replicated no collective touches the accumulators —
    sharded output is bit-identical to the unsharded fused output.  Returns
    None — the logged XLA fallback — when no mesh axis tiles the GEMM or
    the plan fails its bounds on the local shape.
    """
    from repro.dist import shard_gemm as sg

    mesh = context.mesh
    n_experts = None if dense else qx.shape[0]
    spec, reason = sg.negotiate(shape, mesh, n_experts=n_experts)
    if spec is None:
        sg.log_fallback(shape, w, reason)
        return None
    lshape = sg.local_shape(shape, spec, mesh)
    plan = _fused_plan_for(lshape, w, m, context)
    if plan is None:
        sg.log_fallback(shape, w, "local-K kernel bounds failed")
        return None
    ok, reason = sg.plan_local_bounds_ok(plan, lshape, w, m)
    if not ok:
        sg.log_fallback(shape, w, reason)
        return None
    m_dim, k_dim, n_dim = shape
    if plan.variant in ("fused", "fused_mm2"):
        plan = replace(plan, epilogue="dequant", shard=spec)
        mode = _fused_mode(plan)

        def local_fused(qxl, qwl, sxl, swl, *cnt):
            fn = fused_gemm if dense else fused_gemm_grouped
            kw = {} if dense else {"counts": cnt[0] if cnt else None,
                                   "seg": seg}
            return fn(qxl, qwl, sxl, swl, w=w, m=m, mode=mode,
                      block_m=plan.block_m, block_n=plan.block_n,
                      block_k=plan.block_k,
                      combine_int32=plan.combine_int32,
                      out_dtype=out_dtype, **kw)

        if dense:
            f = sg.shard_dense_gemm(local_fused, mesh, spec)
            out = f(qx.reshape(m_dim, k_dim), qw,
                    sx.reshape(m_dim, 1), sw.reshape(1, n_dim))
            return out.reshape(qx.shape[:-1] + (n_dim,))
        return sg.shard_grouped_gemm(local_fused, mesh, spec,
                                     counts=counts)(qx, qw, sx, sw)
    # Table/prior redirect inside the pinned fingerprint class: run the
    # staged plan shard-mapped through the production seam, dequant after.
    qw = qw.astype(jnp.int32)           # staged kernels take int32 operands
    plan = replace(plan, shard=spec)
    if dense:
        acc = sg.sharded_run_plan(qx.reshape(m_dim, k_dim), qw, plan=plan,
                                  mesh=mesh)
        out = (acc.astype(jnp.float32)
               * (sx.reshape(m_dim, 1) * sw.reshape(1, n_dim)))
        return out.astype(out_dtype).reshape(qx.shape[:-1] + (n_dim,))
    local_plan = replace(plan, shard=None)

    def local_staged(qxl, qwl, sxl, swl, *cnt):
        accs = [ops.run_plan(qxl[e], qwl[e], plan=local_plan)
                for e in range(qxl.shape[0])]
        acc = jnp.stack(accs).astype(jnp.float32)
        out = (acc * (sxl * swl)).astype(out_dtype)
        if cnt:
            out = jnp.where(_ragged_row_mask(cnt[0], seg, out.shape[1]),
                            out, jnp.zeros_like(out))
        return out

    return sg.shard_grouped_gemm(local_staged, mesh, spec,
                                 counts=counts)(qx, qw, sx, sw)


def _fused_pallas(qx: Array, qw: Array, sx: Array, sw: Array, w: int, m: int,
                  dims, out_dtype, context: Optional[ExecContext] = None,
                  counts: Optional[Array] = None,
                  seg: Optional[int] = None) -> Optional[Array]:
    """Run the GEMM + dequant epilogue on the Pallas backend.

    The selected plan is normally the fused single-pass kernel; a tuning
    table may redirect to a staged Pallas plan *within the same numerics
    fingerprint class* (select_plan pins it) — including the tile-level
    strassen variants in the exact MM1-window class — in which case the
    redirected plan runs through ``ops.run_plan`` with a post-multiply
    dequant — bit-identical to the fused epilogue, so installing a table
    can never move a bit of this backend's output.  Returns None — the XLA fallback — only for reasons that are
    table-independent: unsupported dot_general dims, w outside the fused
    windows (the analytic pallas rule is not "fused"), or the runtime shape
    exceeding the kernel's correctness bounds (digit-accumulator / int32
    headroom).  With ``context.mesh`` set the kernel runs shard-mapped
    (:func:`_sharded_pallas`); capability-negotiation failures there also
    return None, with a logged reason.
    """
    dense = qw.ndim == 2 and dims == (((qx.ndim - 1,), (0,)), ((), ()))
    batched = (qx.ndim == 3 and qw.ndim == 3
               and dims == (((2,), (1,)), ((0,), (0,))))
    if not dense and not batched:
        _PALLAS_FALLBACKS.inc("unsupported_dims")
        return None
    if dense:
        k_dim = qx.shape[-1]
        n_dim = qw.shape[1]
        m_dim = math.prod(qx.shape[:-1])
    else:
        _, m_dim, k_dim = qx.shape
        n_dim = qw.shape[2]
    shape = (m_dim, k_dim, n_dim)
    if analytic_plan(w, m, backend="pallas").variant \
            not in ("fused", "fused_mm2"):
        _PALLAS_FALLBACKS.inc("outside_fused_window")
        return None                     # recursion deeper than 2 levels
    if context is not None and context.mesh is not None \
            and not getattr(context.mesh, "empty", False):
        return _sharded_pallas(qx, qw, sx, sw, w, m, dense, shape,
                               out_dtype, context, counts=counts, seg=seg)
    plan = _fused_plan_for(shape, w, m, context)
    if plan is None:
        _PALLAS_FALLBACKS.inc("kernel_bounds")
        return None
    if plan.variant in ("fused", "fused_mm2"):
        plan = replace(plan, epilogue="dequant")
        mode = _fused_mode(plan)
        if dense:
            out = fused_gemm(
                qx.reshape(m_dim, k_dim), qw,
                sx.reshape(m_dim, 1), sw.reshape(1, n_dim),
                w=w, m=m, mode=mode, block_m=plan.block_m,
                block_n=plan.block_n, block_k=plan.block_k,
                combine_int32=plan.combine_int32, out_dtype=out_dtype)
            return out.reshape(qx.shape[:-1] + (n_dim,))
        return fused_gemm_grouped(
            qx, qw, sx, sw, counts, w=w, m=m, mode=mode, seg=seg,
            block_m=plan.block_m, block_n=plan.block_n,
            block_k=plan.block_k, combine_int32=plan.combine_int32,
            out_dtype=out_dtype)
    # Table/prior redirect inside the pinned fingerprint class: run the
    # selected plan through the production seam and dequant afterwards.
    qw = qw.astype(jnp.int32)           # staged kernels take int32 operands
    if dense:
        acc = ops.run_plan(qx.reshape(m_dim, k_dim), qw, plan=plan)
        out = (acc.astype(jnp.float32)
               * (sx.reshape(m_dim, 1) * sw.reshape(1, n_dim)))
        return out.astype(out_dtype).reshape(qx.shape[:-1] + (n_dim,))
    accs = [ops.run_plan(qx[e], qw[e], plan=plan)
            for e in range(qx.shape[0])]
    acc = jnp.stack(accs).astype(jnp.float32)
    out = (acc * (sx * sw)).astype(out_dtype)
    if counts is not None:
        out = jnp.where(_ragged_row_mask(counts, seg, out.shape[1]),
                        out, jnp.zeros_like(out))
    return out


def _quant_gemm(qx: Array, qw: Array, sx: Array, sw: Array, w: int, m: int,
                dims, context: ExecContext, out_dtype,
                counts: Optional[Array] = None,
                seg: Optional[int] = None) -> Array:
    """Dequantized GEMM: fused Pallas kernel when routed, XLA otherwise.

    ``counts``/``seg`` (batched expert GEMMs only) make the launch ragged:
    on the pallas route the grouped kernel masks in-kernel and skips dead
    m-blocks; every other route applies the identical liveness mask to its
    output, so the contract — live rows unchanged, dead rows exact zeros —
    is backend-independent and the MoE combine sees the same tokens either
    way.  ``qw`` may be a stored record's narrow carrier (int8/int16): the
    fused kernel reads it as it is, the other routes widen it to int32.
    """
    if context.backend not in BACKENDS:
        raise ValueError(f"unknown backend {context.backend!r}; "
                         f"choices {BACKENDS}")
    if context.backend == "pallas" and context.force_mode == "auto":
        out = _fused_pallas(qx, qw, sx, sw, w, m, dims, out_dtype,
                            context=context, counts=counts, seg=seg)
        if out is not None:
            _GEMM_ROUTES.inc(context.backend, "pallas")
            return out
        _GEMM_ROUTES.inc(context.backend, "xla_fallback")
    else:
        _GEMM_ROUTES.inc(context.backend, "xla")
    acc = _int_dot(qx, qw.astype(jnp.int32), w, m, dims, context.force_mode)
    out = (acc * (sx * sw)).astype(out_dtype)
    if counts is not None:
        out = jnp.where(_ragged_row_mask(counts, seg, out.shape[1]),
                        out, jnp.zeros_like(out))
    return out


# ---------------------------------------------------------------------------
# custom_vjp cores (STE backward).  The public entry points below are plain
# shims that resolve an ExecContext and call these; the context is a
# hashable nondiff arg (its tuning table is excluded from eq/hash and is
# installed around the traced call by the shim instead).
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _qmm_core(x: Array, wmat: Array, w_bits: int, m: int,
              context: ExecContext) -> Array:
    return _qmm_fwd_impl(x, wmat, w_bits, m, context)


def _qmm_fwd_impl(x, wmat, w_bits, m, context):
    qx, sx = _quantize(x, w_bits, axis=-1)            # per-token
    qw, sw = _quantize_weight(wmat, w_bits, axis=0)   # per-out-channel
    dims = (((x.ndim - 1,), (0,)), ((), ()))
    return _quant_gemm(qx, qw, sx, sw, w_bits, m, dims, context, x.dtype)


def _qmm_fwd(x, wmat, w_bits, m, context):
    return _qmm_fwd_impl(x, wmat, w_bits, m, context), (x, wmat)


def _qmm_bwd(w_bits, m, context, res, g):
    x, wmat = res
    gf = g.astype(jnp.float32)
    dx = jnp.einsum("...n,kn->...k", gf, wmat.astype(jnp.float32))
    x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    g2 = gf.reshape(-1, gf.shape[-1])
    dw = x2.T @ g2
    return dx.astype(x.dtype), dw.astype(wmat.dtype)


_qmm_core.defvjp(_qmm_fwd, _qmm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _qbmm_core(x: Array, wmat: Array, w_bits: int, m: int,
               context: ExecContext) -> Array:
    return _qbmm_fwd_impl(x, wmat, w_bits, m, context)


def _qbmm_fwd_impl(x, wmat, w_bits, m, context):
    qx, sx = _quantize(x, w_bits, axis=-1)            # per (expert, row)
    qw, sw = _quantize_weight(wmat, w_bits, axis=1)   # per (expert, channel)
    dims = (((2,), (1,)), ((0,), (0,)))
    return _quant_gemm(qx, qw, sx, sw, w_bits, m, dims, context, x.dtype)


def _qbmm_fwd(x, wmat, w_bits, m, context):
    return _qbmm_fwd_impl(x, wmat, w_bits, m, context), (x, wmat)


def _qbmm_bwd(w_bits, m, context, res, g):
    x, wmat = res
    gf = g.astype(jnp.float32)
    dx = jnp.einsum("ecn,ekn->eck", gf, wmat.astype(jnp.float32))
    dw = jnp.einsum("eck,ecn->ekn", x.astype(jnp.float32), gf)
    return dx.astype(x.dtype), dw.astype(wmat.dtype)


_qbmm_core.defvjp(_qbmm_fwd, _qbmm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _qbmm_ragged_core(x: Array, wmat: Array, counts: Array, w_bits: int,
                      m: int, seg: int, context: ExecContext) -> Array:
    """Ragged batched core: ``counts`` is a *traced* integer operand (live
    token counts change per step at serve time without retracing), so it is
    a separate custom_vjp with a ``float0`` cotangent rather than a
    nondiff arg of :func:`_qbmm_core`."""
    return _qbmm_ragged_fwd_impl(x, wmat, counts, w_bits, m, seg, context)


def _qbmm_ragged_fwd_impl(x, wmat, counts, w_bits, m, seg, context):
    qx, sx = _quantize(x, w_bits, axis=-1)            # per (expert, row)
    qw, sw = _quantize_weight(wmat, w_bits, axis=1)   # per (expert, channel)
    dims = (((2,), (1,)), ((0,), (0,)))
    return _quant_gemm(qx, qw, sx, sw, w_bits, m, dims, context, x.dtype,
                       counts=counts, seg=seg)


def _qbmm_ragged_fwd(x, wmat, counts, w_bits, m, seg, context):
    out = _qbmm_ragged_fwd_impl(x, wmat, counts, w_bits, m, seg, context)
    return out, (x, wmat, counts)


def _qbmm_ragged_bwd(w_bits, m, seg, context, res, g):
    # STE through live rows only: dead rows of the forward output are hard
    # zeros, so their cotangents must not leak into dx/dw.
    x, wmat, counts = res
    import numpy as _np
    live = _ragged_row_mask(counts, seg, x.shape[1])
    gf = jnp.where(live, g.astype(jnp.float32), 0.0)
    dx = jnp.einsum("ecn,ekn->eck", gf, wmat.astype(jnp.float32))
    dw = jnp.einsum("eck,ecn->ekn", x.astype(jnp.float32), gf)
    dc = _np.zeros(counts.shape, dtype=jax.dtypes.float0)
    return dx.astype(x.dtype), dw.astype(wmat.dtype), dc


_qbmm_ragged_core.defvjp(_qbmm_ragged_fwd, _qbmm_ragged_bwd)


# ---------------------------------------------------------------------------
# Public entry points (context-first API + deprecation shims).
# ---------------------------------------------------------------------------


def _ctx(context, force_mode, backend, what) -> ExecContext:
    return resolve_context(context, what=what, force_mode=force_mode,
                           backend=backend)


def quantized_matmul(x: Array, wmat: Array, w_bits: int, m: int = 8,
                     force_mode: Optional[str] = None,
                     backend: Optional[str] = None, *,
                     context: Optional[ExecContext] = None) -> Array:
    """(..., K) @ (K, N) quantized to ``w_bits``; returns x.dtype.

    Pass ``context=`` (an :class:`~repro.core.context.ExecContext`) to pick
    backend / mesh / tuning table / force_mode; the positional
    ``force_mode``/``backend`` kwargs are deprecated shims.
    """
    ctx = _ctx(context, force_mode, backend, "quantized_matmul")
    with ctx.activate():
        return _qmm_core(x, wmat, w_bits, m, ctx)


def quantized_matmul_batched(x: Array, wmat: Array, w_bits: int,
                             m: int = 8, force_mode: Optional[str] = None,
                             backend: Optional[str] = None, *,
                             context: Optional[ExecContext] = None,
                             counts: Optional[Array] = None,
                             seg: Optional[int] = None) -> Array:
    """(E, C, K) @ (E, K, N) expert GEMM, quantized to ``w_bits``.

    On the pallas backend all experts run as ONE grouped fused-kernel
    launch (expert axis = leading parallel grid dim) instead of an XLA
    ``kmm_n`` recursion over batched dot_generals; under ``context.mesh``
    the expert axis shards over ``model`` (expert parallelism).

    ``counts`` (E, S) int32 with static ``seg`` makes the launch *ragged*:
    expert ``e``'s C rows are S segments of ``seg`` rows, of which only the
    first ``counts[e, s]`` are live (models/moe.py passes S = batch,
    seg = capacity).  Live rows are bit-identical to the dense call; dead
    rows come out as exact zeros on every backend, and on pallas their
    m-blocks skip the MXU entirely.  ``counts`` is a traced operand (STE
    gradients flow through x/wmat only), so serve-time count changes never
    retrace.
    """
    ctx = _ctx(context, force_mode, backend, "quantized_matmul_batched")
    with ctx.activate():
        if counts is None:
            return _qbmm_core(x, wmat, w_bits, m, ctx)
        if seg is None or seg <= 0:
            raise ValueError("ragged counts need a positive static seg")
        return _qbmm_ragged_core(x, wmat, counts, w_bits, m, seg, ctx)


def prequant_matmul(x: Array, wrec, w_bits: int, m: int = 8,
                    force_mode: Optional[str] = None, batched: bool = False,
                    backend: Optional[str] = None, *,
                    context: Optional[ExecContext] = None,
                    counts: Optional[Array] = None,
                    seg: Optional[int] = None) -> Array:
    """Serving path on pre-quantized weights ({"q", "scale"} records): skips
    the runtime weight quantization (see quant/prequant.py).  Inference-only
    (not differentiable).  On the pallas backend the kernel reads the
    stored int8/int16 carrier as it is, and the stored per-channel scale
    threads straight into its dequant epilogue.
    ``counts``/``seg`` (batched only) run the ragged grouped contract of
    :func:`quantized_matmul_batched`."""
    ctx = _ctx(context, force_mode, backend, "prequant_matmul")
    qx, sx = _quantize(x, w_bits, axis=-1)
    _WEIGHT_SOURCE.inc("prequant")
    dims = (((2,), (1,)), ((0,), (0,))) if batched \
        else (((x.ndim - 1,), (0,)), ((), ()))
    if counts is not None and not batched:
        raise ValueError("ragged counts require batched=True")
    with ctx.activate():
        return _quant_gemm(qx, wrec["q"], sx, wrec["scale"], w_bits, m, dims,
                           ctx, x.dtype, counts=counts, seg=seg)


def _model_context(quant) -> ExecContext:
    """ExecContext for a model-internal GEMM, from the model's QuantConfig.

    The mesh is resolved from the ambient context (the ``with mesh:`` the
    serve engine / train loop trace under) — model code has no mesh kwarg to
    thread.  Only the pallas backend consumes it (shard-mapped kernels);
    XLA GEMMs partition via GSPMD as before.
    """
    backend = getattr(quant, "backend", "xla")
    mesh = None
    if backend == "pallas":
        from repro.dist.sharding import _ambient_mesh
        mesh = _ambient_mesh()
    return ExecContext(backend=backend, mesh=mesh,
                       force_mode=getattr(quant, "force_mode", "auto"))


def maybe_quantized_matmul(x: Array, wmat: Array, quant, name: str) -> Array:
    """Dense matmul that routes through the quantized KMM path when enabled."""
    if isinstance(wmat, dict):
        return prequant_matmul(x, wmat, quant.bits_for(name), quant.m,
                               context=_model_context(quant))
    if quant is not None and quant.enabled:
        return quantized_matmul(x, wmat, quant.bits_for(name), quant.m,
                                context=_model_context(quant))
    return jnp.einsum("...k,kn->...n", x, wmat.astype(x.dtype))


def maybe_quantized_batched(x: Array, wmat: Array, quant, name: str,
                            counts: Optional[Array] = None,
                            seg: Optional[int] = None) -> Array:
    """Expert-batched matmul through the quantized KMM path when enabled.

    ``counts``/``seg`` opt into the ragged grouped contract (dead
    capacity-bucket rows are exact zeros, live rows identical to dense) —
    the unquantized einsum path ignores them because its callers (the MoE
    combine) gather live slots only."""
    if isinstance(wmat, dict):
        return prequant_matmul(x, wmat, quant.bits_for(name), quant.m,
                               batched=True, context=_model_context(quant),
                               counts=counts, seg=seg)
    if quant is not None and quant.enabled:
        return quantized_matmul_batched(x, wmat, quant.bits_for(name),
                                        quant.m,
                                        context=_model_context(quant),
                                        counts=counts, seg=seg)
    return jnp.einsum("eck,ekn->ecn", x, wmat.astype(x.dtype))
