"""ExecContext: one frozen bundle for "how do GEMMs execute here".

Before this module, the knobs that pick an execution path were scattered as
ad-hoc kwargs — ``backend=`` on :func:`repro.kernels.ops.int_gemm`,
``quant_backend=`` on the serve :class:`~repro.serve.engine.Engine`,
``mesh=`` in two, ``force_mode`` threaded positionally through the
``custom_vjp`` entry points.  ``ExecContext`` is the
single replacement: a frozen dataclass carrying

  * ``backend``      — "xla" (plain dot_generals, GSPMD-partitionable) or
                       "pallas" (the fused single-pass KMM kernel);
  * ``mesh``         — a ``jax.sharding.Mesh`` when GEMMs should run
                       shard-mapped (see :mod:`repro.dist.shard_gemm`), or
                       None for single-device / ambient-GSPMD execution;
  * ``force_mode``   — "auto" (the paper's dispatch rule) or "mm2" (the
                       conventional-baseline override used by benchmarks).

It is hashable and is consumed at trace time, never inside traced
computations.

Migration table (DESIGN.md §12):

    old kwarg                              new spelling
    -------------------------------------  --------------------------------
    quantized_matmul(..., backend="p")     quantized_matmul(..., context=ctx)
    quantized_matmul(..., force_mode="m")  ctx = ExecContext(force_mode="m")
    Engine(..., quant_backend="pallas")    Engine(..., context=ctx)
    int_gemm(..., backend="pallas")        int_gemm(..., context=ctx)

The old kwargs keep working through :func:`resolve_context` shims that emit
one ``DeprecationWarning`` naming every legacy kwarg used.
"""
from __future__ import annotations

import dataclasses
import warnings
from dataclasses import dataclass
from typing import Any, Optional

__all__ = ["ExecContext", "resolve_context"]


@dataclass(frozen=True)
class ExecContext:
    """Execution context for the integer-GEMM stack (see module docstring).

    A context is *authoritative* where it is passed: ``Engine(context=ctx)``
    rewrites the model's quant policy to ``ctx.backend``/``ctx.force_mode``,
    and ``quantized_matmul(..., context=ctx)`` executes on ``ctx.backend``
    regardless of defaults.  Pass ``context=None`` (the default everywhere)
    to keep the call site's historical behaviour.
    """

    backend: str = "xla"            # "xla" | "pallas"
    mesh: Optional[Any] = None      # jax.sharding.Mesh | None
    force_mode: str = "auto"        # "auto" | "mm2"

    def __post_init__(self):
        if self.backend not in ("xla", "pallas"):
            raise ValueError(f"unknown backend {self.backend!r}; "
                             f"choices ('xla', 'pallas')")
        if self.force_mode not in ("auto", "mm2"):
            raise ValueError(f"unknown force_mode {self.force_mode!r}; "
                             f"choices ('auto', 'mm2')")

    # -- helpers ------------------------------------------------------------

    def replace(self, **kw) -> "ExecContext":
        return dataclasses.replace(self, **kw)


def resolve_context(context: Optional[ExecContext], *, what: str,
                    backend: Optional[str] = None,
                    force_mode: Optional[str] = None,
                    mesh: Optional[Any] = None,
                    _defaults: Optional[ExecContext] = None) -> ExecContext:
    """Fold legacy kwargs into an :class:`ExecContext` (the deprecation shim).

    ``None`` legacy values mean "not passed".  Passing any legacy kwarg emits
    ONE ``DeprecationWarning`` listing all of them; passing legacy kwargs
    *and* ``context`` together is an error (ambiguous).  ``_defaults`` seeds
    the context the legacy values are folded into (callers with historical
    defaults other than ExecContext()'s pass them here).
    """
    legacy = {k: v for k, v in (("backend", backend),
                                ("force_mode", force_mode),
                                ("mesh", mesh)) if v is not None}
    if context is not None:
        if legacy:
            raise TypeError(
                f"{what}: pass either context= or the deprecated "
                f"{sorted(legacy)} kwargs, not both")
        return context
    base = _defaults if _defaults is not None else ExecContext()
    if not legacy:
        return base
    warnings.warn(
        f"{what}: the {sorted(legacy)} kwarg(s) are deprecated; pass "
        f"context=repro.core.context.ExecContext(...) instead "
        f"(DESIGN.md §12 migration table)",
        DeprecationWarning, stacklevel=3)
    return base.replace(**legacy)
