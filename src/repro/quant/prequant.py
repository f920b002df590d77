"""Pre-quantized weight storage: the serving engine's weight format.

The runtime path re-quantizes every weight from full precision at every
GEMM call (right for STE training, whose weights change every step;
wasteful for serving: each call reads the f32 weight twice, for its
abs-max and its rounding, and writes an integer copy the kernel reads
again).  ``prequantize`` rewrites the param tree once: every quantizable
weight leaf becomes ``{"q": intN, "scale": per-channel f32}``:

  * w <= 8  -> int8 storage (4x fewer weight bytes than f32, 2x vs bf16)
  * w <= 16 -> int16 storage (2x vs f32)
  * w > 16  -> int32 storage (the fused kernel's carrier there)

``maybe_quantized_matmul`` recognizes the dict leaf and skips the runtime
weight quantization entirely; ``repro_quant_weight_source_total{source}``
(quant/qmatmul.py) counts which of the two a GEMM took.  The serving
engine prequantizes the params it is given (serve/engine.py); measured in
PERF.md.
"""
from __future__ import annotations

import functools
from typing import Any

import jax

from repro.quant.policy import QuantConfig
from repro.quant.quantize import quantize_symmetric, storage_dtype_for

Params = Any

# weight-leaf names that feed quantized matmuls; each quantizes along its
# contraction axis K, the second-to-last: (K, N), period-stacked
# (P, K, N) and expert-stacked (P, E, K, N) alike.
_QUANT_LEAVES = {
    "wq", "wk", "wv", "wo", "wi", "wg", "wr", "w1", "w2", "router",
    "in_proj", "out_proj", "x_proj", "dt_proj", "lm_head",
}

# Period-stacked block trees, whose matmul sites models/lm.py names
# ``blk{pos}.<sub>.<leaf>`` (decoder and encoder stacks alike).
_BLOCK_STACKS = ("blocks", "encoder")


def _key(entry) -> str:
    return str(getattr(entry, "key", getattr(entry, "name", entry)))


def site_name(path) -> str:
    """The name the model passes to ``QuantConfig.bits_for`` at the matmul
    site a weight leaf feeds: ``blocks.pos0.attn.wo`` -> ``blk0.attn.wo``,
    ``lm_head`` -> ``lm_head``."""
    keys = [_key(k) for k in path]
    if len(keys) > 2 and keys[0] in _BLOCK_STACKS \
            and keys[1].startswith("pos"):
        keys = ["blk" + keys[1][len("pos"):]] + keys[2:]
    return ".".join(keys)


@functools.partial(jax.jit, static_argnames=("bits",))
def _quantize_leaf(leaf, bits: int):
    q, scale = quantize_symmetric(leaf, bits, axis=leaf.ndim - 2,
                                  keepdims=True,
                                  storage_dtype=storage_dtype_for(bits))
    return {"q": q, "scale": scale}


def prequantize(params: Params, quant: QuantConfig) -> Params:
    """Replace quantizable weight leaves with {"q", "scale"} records.

    Each leaf takes the width ``quant.bits_for`` gives the model's own
    site name (:func:`site_name`) and the shared
    :mod:`repro.quant.quantize` recipe along the same axis as the runtime
    weight quantizer, so a prequantized serve run is bit-identical to the
    on-the-fly path for any backend.  One jitted call per leaf (compiled
    once per leaf shape and width): the transient is one leaf, and the
    input tree is left as it was.  Leaves that are already records pass
    through.
    """

    def rule(path, leaf):
        if _key(path[-1]) not in _QUANT_LEAVES or leaf.ndim < 2:
            return leaf
        return _quantize_leaf(leaf, quant.bits_for(site_name(path)))

    return jax.tree_util.tree_map_with_path(rule, params)
