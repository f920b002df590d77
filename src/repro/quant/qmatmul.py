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
(``context=`` kwarg): backend, mesh and force_mode in one frozen bundle.
The legacy positional ``force_mode``/``backend`` kwargs keep working
through a shim that emits ``DeprecationWarning`` (DESIGN.md §12 migration
table).

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
axis tiles the problem or the local-K bounds fail.  The kernel's own plan
(:func:`repro.kernels.fused_gemm.plan_for`) picks its mode, tiles and
combine; where it declines (KMM recursion deeper than two levels, the
int32 headroom of the product, the digit accumulators or the zero-point
sums) or ``force_mode`` is set, the call falls back to the XLA path.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.context import ExecContext, resolve_context
from repro.core.dispatch import Mode, select_mode
from repro.core.kmm import kmm_n, mm_n
from repro.kernels.fused_gemm import (fused_gemm, fused_gemm_grouped,
                                      kernel_mode, plan_for)
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
# Reasons the pallas route declined a GEMM (the XLA fallbacks;
# mesh-negotiation fallbacks count in repro.dist's counter).
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


def _int_dot(qx: Array, qw: Array, w: int, m: int, dims,
             force_mode: str = "auto") -> Array:
    """Integer GEMM on quantized values via the paper's mode, fp32 out:
    one int32 dot for MM1, the KMM/MM digit recursion above it (MM with
    ``force_mode="mm2"``)."""
    plan = select_mode(w, m)
    if plan.mode is Mode.MM1:
        out = jax.lax.dot_general(qx, qw, dims,
                                  preferred_element_type=jnp.int32)
        return out.astype(jnp.float32)
    fn = kmm_n if plan.mode is Mode.KMM2 and force_mode != "mm2" else mm_n
    return fn(qx, qw, w=w, n=plan.digits, dimension_numbers=dims,
              combine_dtype=jnp.float32)


def _ragged_row_mask(counts: Array, seg: int, c_dim: int) -> Array:
    """(E, C, 1) liveness of capacity-bucketed expert rows: row ``r`` is
    live iff ``r % seg < counts[e, r // seg]`` — the same predicate the
    ragged grouped kernel evaluates in-kernel, evaluated in jnp for the
    XLA route so the grouped ragged contract (dead rows are exact zeros)
    holds on every backend."""
    rows = jnp.arange(c_dim, dtype=jnp.int32)
    seg_ids = rows // seg
    n_seg = counts.shape[-1]
    limit = jnp.take(counts.astype(jnp.int32),
                     jnp.clip(seg_ids, 0, n_seg - 1), axis=-1)    # (E, C)
    live = (rows - seg_ids * seg < limit) & (seg_ids < n_seg)
    return live[..., None]


def _run_fused(plan, qx, qw, sx, sw, w: int, m: int, dense: bool,
               out_dtype, counts: Optional[Array] = None,
               seg: Optional[int] = None) -> Array:
    """The kernel at ``plan`` with the dequant epilogue: dense (M, K) x
    (K, N) with (M, 1)/(1, N) scales, or grouped (E, C, K) x (E, K, N)."""
    kw = dict(w=w, m=m, mode=plan.mode, block_m=plan.block_m,
              block_n=plan.block_n, block_k=plan.block_k,
              combine_int32=plan.combine_int32, out_dtype=out_dtype)
    if dense:
        return fused_gemm(qx, qw, sx, sw, **kw)
    return fused_gemm_grouped(qx, qw, sx, sw, counts, seg=seg, **kw)


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
    the kernel's plan declines the local shape.
    """
    from repro.dist import shard_gemm as sg

    mesh = context.mesh
    n_experts = None if dense else qx.shape[0]
    spec, reason = sg.negotiate(shape, mesh, n_experts=n_experts)
    if spec is None:
        sg.log_fallback(shape, w, reason)
        return None
    lshape = sg.local_shape(shape, spec, mesh)
    plan = plan_for(lshape, w, m)
    if plan is None:
        sg.log_fallback(shape, w, f"kernel bounds failed on local shape "
                                  f"{lshape}")
        return None
    m_dim, k_dim, n_dim = shape

    def local_fused(qxl, qwl, sxl, swl, *cnt):
        return _run_fused(plan, qxl, qwl, sxl, swl, w, m, dense, out_dtype,
                          counts=cnt[0] if cnt else None, seg=seg)

    if dense:
        f = sg.shard_dense_gemm(local_fused, mesh, spec)
        out = f(qx.reshape(m_dim, k_dim), qw,
                sx.reshape(m_dim, 1), sw.reshape(1, n_dim))
        return out.reshape(qx.shape[:-1] + (n_dim,))
    return sg.shard_grouped_gemm(local_fused, mesh, spec,
                                 counts=counts)(qx, qw, sx, sw)


def _fused_pallas(qx: Array, qw: Array, sx: Array, sw: Array, w: int, m: int,
                  dims, out_dtype, context: Optional[ExecContext] = None,
                  counts: Optional[Array] = None,
                  seg: Optional[int] = None) -> Optional[Array]:
    """Run the GEMM + dequant epilogue on the fused Pallas kernel.

    Returns None — the XLA fallback — for unsupported dot_general dims, for
    widths the kernel does not run (KMM recursion deeper than two levels),
    or where :func:`repro.kernels.fused_gemm.plan_for` declines the shape
    (digit-accumulator / int32 headroom).  With ``context.mesh`` set the
    kernel runs shard-mapped (:func:`_sharded_pallas`); capability-
    negotiation failures there also return None, with a logged reason.
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
    if kernel_mode(w, m) is None:
        _PALLAS_FALLBACKS.inc("outside_fused_window")
        return None                     # recursion deeper than 2 levels
    if context is not None and context.mesh is not None \
            and not getattr(context.mesh, "empty", False):
        return _sharded_pallas(qx, qw, sx, sw, w, m, dense, shape,
                               out_dtype, context, counts=counts, seg=seg)
    plan = plan_for(shape, w, m)
    if plan is None:
        _PALLAS_FALLBACKS.inc("kernel_bounds")
        return None
    if dense:
        out = _run_fused(plan, qx.reshape(m_dim, k_dim), qw,
                         sx.reshape(m_dim, 1), sw.reshape(1, n_dim), w, m,
                         dense, out_dtype)
        return out.reshape(qx.shape[:-1] + (n_dim,))
    return _run_fused(plan, qx, qw, sx, sw, w, m, dense, out_dtype,
                      counts=counts, seg=seg)


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
# hashable nondiff arg.
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
    backend / mesh / force_mode; the positional
    ``force_mode``/``backend`` kwargs are deprecated shims.
    """
    ctx = _ctx(context, force_mode, backend, "quantized_matmul")
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
