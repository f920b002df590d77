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

K is never sharded: fp32-combine partial sums would round differently.

Fallback contract: when no mesh axis divides the GEMM (or the kernel's plan,
:func:`repro.kernels.fused_gemm.plan_for`, declines the *local* shape), the
caller downgrades that GEMM to the XLA backend with a logged reason —
capability negotiation, not a hard error.
"""
from __future__ import annotations

import logging
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, PartitionSpec as P

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


@dataclass(frozen=True)
class GemmShardSpec:
    """How one GEMM's M and N dims (or a grouped GEMM's expert dim) map
    onto mesh axes.  Each shard runs the kernel on its local block with the
    full K, so values are bit-identical to the unsharded kernel."""

    m_axes: Tuple[str, ...] = ()
    n_axes: Tuple[str, ...] = ()
    e_axes: Tuple[str, ...] = ()   # expert/group dim of grouped GEMMs


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
    """Per-shard (M, K, N) under ``spec`` on ``mesh`` (K stays whole)."""
    M, K, N = shape
    return (M // _axis_size(mesh, spec.m_axes), K,
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
    (M, N) output with each shard running ``fn`` on its local block.
    """
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
