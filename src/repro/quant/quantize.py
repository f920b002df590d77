"""The one symmetric quantizer every integer path shares.

Three near-identical copies used to live in ``kernels/ops.quantize_symmetric``
(keepdims only when an axis was given), ``quant/qmatmul._quantize``
(keepdims always) and inline in ``quant/prequant.prequantize`` (plus a
storage-dtype cast).  The fused-kernel dequant epilogue multiplies scales
*inside* the GEMM kernel, so activation/weight scales must be produced by
exactly one rounding recipe or the Pallas and XLA backends drift apart.
This module is that recipe:

    qmax  = 2**(bits-1) - 1
    amax  = max(|x|) over ``axis`` (fp32)
    scale = max(amax, 1e-8) / qmax          (fp32)
    q     = clip(round(x / scale), -qmax, qmax)

``keepdims`` defaults to ``axis is not None`` (scales broadcast back against
``x``); pass it explicitly to force either shape.  ``storage_dtype`` selects
the integer carrier (int32 by default; prequantized weights use int8/int16).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


def quantize_symmetric(x: Array, bits: int, axis=None,
                       keepdims: Optional[bool] = None,
                       storage_dtype=jnp.int32) -> Tuple[Array, Array]:
    """Symmetric signed ``bits``-bit quantization. Returns (q, scale_f32)."""
    if keepdims is None:
        keepdims = axis is not None
    xf = x.astype(jnp.float32)
    qmax = float(2 ** (bits - 1) - 1)
    amax = jnp.max(jnp.abs(xf), axis=axis, keepdims=keepdims)
    scale = (jnp.maximum(amax, 1e-8) / qmax).astype(jnp.float32)
    q = jnp.clip(jnp.round(xf / scale), -qmax, qmax).astype(storage_dtype)
    return q, scale


def storage_dtype_for(bits: int):
    """Narrowest integer carrier for ``bits``-bit prequantized storage."""
    return jnp.int8 if bits <= 8 else jnp.int16 if bits <= 16 else jnp.int32
