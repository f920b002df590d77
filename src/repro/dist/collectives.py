"""Communication-efficient collectives (DESIGN.md §4.2).

The paper's thesis — split wide integer arithmetic into narrow digit planes
and recombine cheaply — applies to the network as much as to the MXU.  These
primitives are the mesh-level analogue:

  * ``ef_compressed_psum``: int8-quantized all-reduce with error-feedback
    residual carried across steps (1-bit-Adam / PowerSGD lineage), so the
    wire moves 4x fewer bytes than f32 while the *accumulated* gradient
    stays unbiased.
  * ``ring_ag_matmul``: ring all-gather matmul via ``jax.lax.ppermute`` that
    overlaps each hop's transfer with the local shard GEMM; per-shard chunks
    can route through the paper's integer GEMM (``repro.kernels.ops
    .int_gemm``) when a bitwidth is supplied.
  * ``splitk_decode_attention``: decode attention over a model-axis-sharded
    KV cache, merged with a numerically-stable log-sum-exp across shards.

All functions are written for use inside ``shard_map`` (they speak
``axis_name``), and degrade to plain math on a 1-sized axis.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


# ---------------------------------------------------------------------------
# Error-feedback compressed all-reduce.
# ---------------------------------------------------------------------------


def ef_compress(x: Array, err: Array, *, bits: int = 8
                ) -> Tuple[Array, Array, Array]:
    """Quantize ``x + err`` to signed ``bits`` with a per-tensor scale.

    Returns ``(q, scale, new_err)`` with ``q * scale + new_err == x + err``
    exactly and ``|new_err| <= scale / 2`` (round-to-nearest): the residual
    the wire drops this round is re-injected next round, so compression
    error accumulates to at most one quantization step instead of growing
    with step count.
    """
    y = (x + err).astype(jnp.float32)
    qmax = float(2 ** (bits - 1) - 1)
    scale = jnp.max(jnp.abs(y)) / qmax
    scale = jnp.maximum(scale, jnp.float32(1e-30))
    q = jnp.clip(jnp.round(y / scale), -qmax, qmax)
    q = q.astype(jnp.int8 if bits <= 8 else jnp.int32)
    new_err = y - q.astype(jnp.float32) * scale
    return q, scale, new_err


def ef_compressed_psum(x: Array, err: Array, axis_name: str, *,
                       bits: int = 8) -> Tuple[Array, Array]:
    """All-reduce ``x`` over ``axis_name`` through int8 digit traffic.

    A single shared scale (one scalar ``pmax``) lets every shard quantize
    onto the same grid, so the all-reduce payload really is the integer
    plane — int8 digits accumulated in int32, as on the paper's hardware —
    plus one f32 scalar, not a dequantized f32 tensor.  Returns ``(total,
    new_err)``; callers thread ``new_err`` back in on the next step (error
    feedback), which bounds the accumulated compression error by one
    quantization step.
    """
    y = (x + err).astype(jnp.float32)
    qmax = float(2 ** (bits - 1) - 1)
    scale = jax.lax.pmax(jnp.max(jnp.abs(y)) / qmax, axis_name)
    scale = jnp.maximum(scale, jnp.float32(1e-30))
    q = jnp.clip(jnp.round(y / scale), -qmax, qmax)
    new_err = y - q * scale
    total = jax.lax.psum(q.astype(jnp.int32), axis_name)
    return (total.astype(jnp.float32) * scale).astype(x.dtype), new_err


# ---------------------------------------------------------------------------
# Ring all-gather matmul.
# ---------------------------------------------------------------------------


def _prep_rhs(w: Array, w_bits: Optional[int]):
    """Quantize the loop-invariant RHS once, outside the ring loop."""
    if w_bits is None:
        return w.astype(jnp.float32), None
    from repro.quant.quantize import quantize_symmetric

    return quantize_symmetric(w, w_bits)


def _shard_matmul(a: Array, qb: Array, sb, w_bits: Optional[int],
                  context=None) -> Array:
    """One shard-chunk GEMM; integer path when a bitwidth is supplied.

    ``context`` (an :class:`repro.core.context.ExecContext`) picks the
    backend for the chunk GEMMs.  ``int_gemm`` reads only its backend: the
    ring already runs inside its own ``shard_map``, so each chunk is a
    single-shard GEMM.
    """
    if w_bits is None:
        return jnp.dot(a.astype(jnp.float32), qb)
    from repro.kernels.ops import int_gemm
    from repro.quant.quantize import quantize_symmetric

    qa, sa = quantize_symmetric(a, w_bits)
    return int_gemm(qa, qb, w=w_bits, context=context) * sa * sb


def ring_ag_matmul(x_shard: Array, w: Array, axis_name: str, *,
                   w_bits: Optional[int] = None, context=None) -> Array:
    """Ring all-gather matmul: ``concat_shards(x) @ w`` without ever
    materializing the gathered LHS.

    ``x_shard``: this shard's rows of ``x`` (sharded over ``axis_name``);
    ``w``: replicated RHS.  Each of the N ring steps multiplies the block
    currently held against ``w`` while ``ppermute`` forwards it to the next
    neighbour, so the hop transfer overlaps the local GEMM (the classic
    collective-matmul overlap).  With ``w_bits`` set, each per-shard chunk
    routes through the paper's integer GEMM, on the backend picked by
    ``context`` (chunks always run single-shard — see ``_shard_matmul``).

    Returns the full ``(rows_total, n)`` product, replicated on every shard.
    """
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    rows = x_shard.shape[0]
    out_dtype = jnp.promote_types(x_shard.dtype, w.dtype)
    out = jnp.zeros((n * rows, w.shape[1]), jnp.float32)
    perm = [(j, (j + 1) % n) for j in range(n)]
    qb, sb = _prep_rhs(w, w_bits)
    block = x_shard
    for i in range(n):
        # The block in hand originated on shard (idx - i) mod n: its product
        # lands at that shard's row offset in the gathered output.
        src = jax.lax.rem(idx - i + n, n)
        part = _shard_matmul(block, qb, sb, w_bits, context=context)
        out = jax.lax.dynamic_update_slice(out, part, (src * rows, 0))
        if i + 1 < n:
            block = jax.lax.ppermute(block, axis_name, perm)
    return out.astype(out_dtype)


# ---------------------------------------------------------------------------
# Split-K decode attention.
# ---------------------------------------------------------------------------

_NEG_INF = -1e30


def splitk_decode_attention(q: Array, k: Array, v: Array, valid: Array,
                            axis_name: str) -> Array:
    """One-token decode attention with K/V sharded over ``axis_name``.

    ``q``: (B, H, D) replicated; ``k``/``v``: (B, S_local, KH, D) — the
    local sequence slice of the cache; ``valid``: (B, S_local) bool mask for
    filled cache slots.  Each shard computes its partial softmax in the
    flash-attention (m, l, o) form; shards merge with a log-sum-exp that is
    exact and stable regardless of how the max is distributed:

        m* = pmax(m);  l* = psum(l * e^{m - m*});  o* = psum(o * e^{m - m*})

    Returns (B, H, D), replicated.  GQA is supported via KH <= H with
    H % KH == 0.
    """
    b, h, d = q.shape
    kh = k.shape[2]
    g = h // kh
    qv = q.reshape(b, kh, g, d).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    scores = jnp.einsum("bkgd,bskd->bkgs", qv, kf) * (d ** -0.5)
    scores = jnp.where(valid[:, None, None, :], scores, _NEG_INF)
    m_local = scores.max(axis=-1)                                # (B,KH,G)
    # m_local is floored at _NEG_INF (finite) by the mask above, so the
    # rescale below never sees inf - inf even for fully-invalid shards.
    m_global = jax.lax.pmax(m_local, axis_name)
    p = jnp.exp(scores - m_global[..., None])                    # (B,KH,G,S)
    p = jnp.where(valid[:, None, None, :], p, 0.0)
    l_local = p.sum(axis=-1)
    o_local = jnp.einsum("bkgs,bskd->bkgd", p, vf)
    l_tot = jax.lax.psum(l_local, axis_name)
    o_tot = jax.lax.psum(o_local, axis_name)
    out = o_tot / jnp.maximum(l_tot, 1e-30)[..., None]
    return out.reshape(b, h, d).astype(q.dtype)
