"""Shard-mapped execution of the Pallas integer-GEMM kernels (DESIGN.md §12).

The fused single-pass kernel (kernels/fused_gemm.py) is not
GSPMD-partitionable — XLA cannot slice through a ``pallas_call`` — so under a
mesh every quantized GEMM used to fall back to plain dot_generals.  This
module closes that gap: the GEMM runs under ``jax.shard_map`` with each
shard executing the *unmodified* kernel on its local block.

Layout (capability negotiation, :func:`negotiate`):

  * M (tokens / decode slots) shards over the data axes — the same axes the
    serve cache and batch ride (dist/sharding.py);
  * N (output channels) shards over the ``model`` axis — matching the
    column-TP weight rules (``wi -> ("embed", "mlp")``);
  * K is REPLICATED.  Every output element then sees the identical full-K
    digit arithmetic (same padded K, same zero-point correction, same fp32
    rounding) as the unsharded kernel, so sharded == unsharded **bit-exact**
    — the per-shard digit accumulators live entirely inside each shard's
    kernel launch and the zero-point correction runs per-shard *before* any
    collective, which is what keeps the contract exact.

An explicit K-sharded spec (``GemmShardSpec(k_axes=...)``) is also executed
— each shard's int32 partial product is ``psum``-combined — but only for
exact-int plans, where integer partial sums equal the true product;
:func:`negotiate` never proposes it (fp32-combine partials would change
rounding; see ``numerics_fingerprint``).

Fallback contract: when no mesh axis divides the GEMM (or the *local* K
fails the kernel's ``max_exact_k`` / digit-accumulator / VMEM bounds), the
caller downgrades that GEMM to the XLA backend with a logged reason —
capability negotiation, not a hard error (the old ``serve/engine.py``
mesh-rejection is gone).
"""
from __future__ import annotations

import logging
from dataclasses import replace
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.dispatch import ExecPlan, GemmShardSpec
from repro.dist import sharding as dist_sharding
from repro.obs import metrics as obs_metrics

Array = jax.Array
Shape = Tuple[int, int, int]

log = logging.getLogger("repro.dist")

# One fallback log line per (shape, w, reason): negotiation runs at trace
# time inside jit caches, but also once per eager call — don't spam.
_LOGGED_FALLBACKS = set()

# Every fallback occurrence is COUNTED per (shape, w, reason) even though
# only the first is logged — a 64-slot serve run shows up as one log line
# and an honest count here.
_FALLBACKS = obs_metrics.counter(
    "repro_shard_gemm_fallback_total",
    "shard-mapped pallas GEMMs downgraded to XLA, by shape/w/reason",
    labels=("shape", "w", "reason"))


def _axis_size(mesh: Mesh, axes: Tuple[str, ...]) -> int:
    size = 1
    for a in axes:
        size *= dist_sharding.mesh_axis_size(mesh, a)
    return size


def _axis_entry(axes: Tuple[str, ...]):
    """PartitionSpec entry for a dim sharded over ``axes`` (None if empty)."""
    if not axes:
        return None
    return axes if len(axes) > 1 else axes[0]


def local_shape(shape: Shape, spec: GemmShardSpec, mesh: Mesh) -> Shape:
    """Per-shard (M, K, N) under ``spec`` on ``mesh``."""
    M, K, N = shape
    return (M // _axis_size(mesh, spec.m_axes),
            K // _axis_size(mesh, spec.k_axes),
            N // _axis_size(mesh, spec.n_axes))


def negotiate(shape: Shape, mesh: Optional[Mesh], *,
              n_experts: Optional[int] = None
              ) -> Tuple[Optional[GemmShardSpec], str]:
    """Pick mesh axes for an (M, K, N) GEMM, or explain why none fit.

    Returns ``(spec, reason)``: a usable :class:`GemmShardSpec` with
    ``reason == ""``, or ``(None, reason)`` when the mesh cannot tile this
    GEMM and the caller should fall back to XLA.  K is always replicated
    (bit-identity; see module docstring).  For grouped expert GEMMs
    (``n_experts``) the expert dim takes the model axis (expert parallelism,
    matching dist/sharding.py's MoE rule) and M/N stay local per expert.
    """
    if mesh is None or mesh.empty:
        return None, "no mesh"
    M, K, N = shape
    daxes = dist_sharding.data_axes(mesh)
    dsize = _axis_size(mesh, daxes)
    msize = dist_sharding.mesh_axis_size(mesh, "model")
    if n_experts is not None:
        if msize > 1 and n_experts % msize == 0:
            return GemmShardSpec(e_axes=("model",)), ""
        return None, (f"expert dim {n_experts} not divisible by model "
                      f"axis ({msize})")
    m_axes = daxes if dsize > 1 and M % dsize == 0 else ()
    n_axes = ("model",) if msize > 1 and N % msize == 0 else ()
    if not m_axes and not n_axes:
        return None, (f"no mesh axis tiles ({M}, {K}, {N}): "
                      f"M={M} % data({dsize}) and N={N} % model({msize}) "
                      f"both nonzero")
    return GemmShardSpec(m_axes=m_axes, n_axes=n_axes), ""


def log_fallback(shape: Shape, w: int, reason: str) -> None:
    """Record a capability-negotiation XLA downgrade.

    Deduplication is explicit and applies to the LOG LINE only (once per
    unique (shape, w, reason) key); the metrics counter sees every
    occurrence, so fallback volume stays observable without log flood.
    """
    _FALLBACKS.inc("x".join(str(d) for d in shape), w, reason)
    key = (shape, w, reason)
    if key in _LOGGED_FALLBACKS:
        return
    _LOGGED_FALLBACKS.add(key)
    log.info("pallas GEMM %s (w=%d) under mesh falls back to XLA: %s",
             shape, w, reason)


# ---------------------------------------------------------------------------
# Shard-mapped wrappers.
# ---------------------------------------------------------------------------


def shard_dense_gemm(fn, mesh: Mesh, spec: GemmShardSpec):
    """shard_map a local ``(qx, qw, sx, sw) -> out`` dense GEMM over the mesh.

    ``qx``: (M, K); ``qw``: (K, N); ``sx``: (M, 1); ``sw``: (1, N); the
    returned callable takes the global operands and computes the global
    (M, N) output with each shard running ``fn`` on its local block.  K must
    be replicated in ``spec`` (fp32 bit-identity; use
    :func:`sharded_run_plan` for exact-int split-K).
    """
    if spec.k_axes:
        raise ValueError("dense dequant GEMM requires replicated K "
                         "(fp32 bit-identity); got k_axes=%r" % (spec.k_axes,))
    ms, ns = _axis_entry(spec.m_axes), _axis_entry(spec.n_axes)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(ms, None), P(None, ns), P(ms, None), P(None, ns)),
        out_specs=P(ms, ns), check_vma=False)


def shard_grouped_gemm(fn, mesh: Mesh, spec: GemmShardSpec,
                       counts: Optional[Array] = None):
    """shard_map a local ``(qx, qw, sx, sw[, counts]) -> out`` grouped GEMM.

    Operands are (E, C, K) / (E, K, N) / (E, C, 1) / (E, 1, N); the expert
    dim shards over ``spec.e_axes`` so each shard launches the grouped
    kernel over its local experts.  When ``counts`` (E, S) is given the
    ragged per-expert row counts shard over the same expert axis and are
    appended as a fifth operand — each shard sees exactly its local
    experts' live counts, so sharded ragged masking equals unsharded.
    The returned callable still takes ``(qx, qw, sx, sw)``; ``counts`` is
    closed over here.
    """
    es = _axis_entry(spec.e_axes)
    in_specs = [P(es, None, None)] * 4
    if counts is not None:
        in_specs.append(P(es, None))
    f = jax.shard_map(
        fn, mesh=mesh, in_specs=tuple(in_specs),
        out_specs=P(es, None, None), check_vma=False)
    if counts is None:
        return f
    return lambda qx, qw, sx, sw: f(qx, qw, sx, sw, counts)


def sharded_run_plan(a: Array, b: Array, *, plan: ExecPlan, mesh: Mesh,
                     interpret: Optional[bool] = None,
                     use_ref_kernels: bool = False) -> Array:
    """Shard-mapped :func:`repro.kernels.ops.run_plan` on (M, K) x (K, N).

    Uses ``plan.shard`` when set, else negotiates M/N axes.  Covers both the
    fused kernel and the staged Pallas fallback variants — whatever the plan
    routes to runs per-shard.  K-sharded specs are executed as int32 partial
    products ``psum``-combined over the K axes (exact-int plans only: the
    integer partials sum to the true product, so this composes the paper's
    kernel with the mesh collectives without moving a bit).
    """
    from repro.kernels import ops   # lazy: ops -> dispatch -> (tune) cycle

    spec = plan.shard
    if spec is None:
        spec, reason = negotiate((a.shape[0], a.shape[1], b.shape[1]), mesh)
        if spec is None:
            raise ValueError(f"cannot shard GEMM on mesh {mesh}: {reason}")
    local_plan = replace(plan, shard=None)
    if spec.k_axes and not local_plan.is_exact_int:
        raise ValueError(
            "K-sharded execution is exact-int only (fp32 partial sums "
            f"change rounding); plan {local_plan.variant!r} is fp32-combine")
    ms, ns, ks = (_axis_entry(spec.m_axes), _axis_entry(spec.n_axes),
                  _axis_entry(spec.k_axes))

    def local_fn(al, bl):
        out = ops.run_plan(al, bl, plan=local_plan, interpret=interpret,
                           use_ref_kernels=use_ref_kernels)
        if spec.k_axes:
            out = jax.lax.psum(out, spec.k_axes)
        return out

    f = jax.shard_map(local_fn, mesh=mesh,
                      in_specs=(P(ms, ks), P(ks, ns)),
                      out_specs=P(ms, ns), check_vma=False)
    return f(a, b)


def plan_local_bounds_ok(plan: ExecPlan, lshape: Shape, w: int,
                         m: int) -> Tuple[bool, str]:
    """Check the kernel's correctness bounds on the per-shard LOCAL shape.

    Mirrors the unsharded checks in quant/qmatmul._fused_pallas, evaluated
    on the local K (identical here since negotiation replicates K, but the
    seam is explicit so K-sharded callers and future layouts stay honest) —
    plus the per-shard VMEM accounting from :mod:`repro.tune.space`.
    """
    from repro.core.kmm import max_exact_k
    from repro.core.strassen import STRASSEN_VARIANTS
    from repro.tune import space as tune_space

    _, k_local, _ = lshape
    if plan.variant in STRASSEN_VARIANTS:
        # Strassen's pre-adds and per-product accumulation must stay exact
        # on the shard's LOCAL block: re-run the full composed-bound
        # validation (tile split, (w+1)-bit sub-plan windows, sub tile
        # sanity and VMEM on the local half dims) rather than mirroring
        # its pieces here.
        reason = tune_space.validate(plan, lshape)
        if reason is not None:
            return False, f"strassen bounds on local shape {lshape}: {reason}"
        return True, ""
    if plan.is_exact_int and max_exact_k(w) < k_local:
        return False, (f"local K={k_local} > max_exact_k({w})="
                       f"{max_exact_k(w)}")
    kp = -(-k_local // plan.block_k) * plan.block_k
    bound = tune_space.plan_accum_k_bound(plan)
    if bound is not None and kp > bound:
        return False, (f"local padded K={kp} > accum bound {bound} for "
                       f"{plan.variant!r} depth={plan.depth} (w={w})")
    vmem = tune_space.vmem_footprint(plan)
    if vmem > tune_space.VMEM_BUDGET:
        return False, (f"per-shard VMEM footprint {vmem} > "
                       f"{tune_space.VMEM_BUDGET}")
    return True, ""
