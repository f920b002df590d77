"""Model building blocks: norms, RoPE, attention (train + KV-cache decode),
dense MLPs.  Every weight matmul routes through the quantized KMM path when
the config enables it (`maybe_quantized_matmul`), making the paper's integer
GEMM engine a first-class execution mode for all architectures.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.quant.qmatmul import maybe_quantized_matmul

Array = jax.Array
Params = Dict[str, Array]


# ---------------------------------------------------------------------------
# Norms.
# ---------------------------------------------------------------------------


def norm_init(d: int, kind: str = "rms") -> Params:
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if kind == "ln":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def norm_apply(p: Params, x: Array, kind: str = "rms", eps: float = 1e-6) -> Array:
    xf = x.astype(jnp.float32)
    if kind == "ln":
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:
        ms = (xf * xf).mean(-1, keepdims=True)
        out = xf * jax.lax.rsqrt(ms + eps) * p["scale"]
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings.
# ---------------------------------------------------------------------------


def rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (B, S, H, D) with D even; positions: (S,) or (B, S)."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if positions.ndim == 1:
        ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]   # (S, D/2)
        ang = ang[None, :, None, :]
    else:
        ang = positions.astype(jnp.float32)[..., None] * freqs          # (B,S,D/2)
        ang = ang[:, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Activations / MLP.
# ---------------------------------------------------------------------------


def _act(x: Array, kind: str) -> Array:
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x, approximate=True)
    if kind == "relu2":
        r = jax.nn.relu(x)
        return r * r
    raise ValueError(f"unknown activation {kind!r}")


def mlp_init(key, d: int, ff: int, glu: bool, dtype) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    s_in, s_out = d**-0.5, ff**-0.5
    p = {"wo": (jax.random.normal(k3, (ff, d)) * s_out).astype(dtype)}
    p["wi"] = (jax.random.normal(k1, (d, ff)) * s_in).astype(dtype)
    if glu:
        p["wg"] = (jax.random.normal(k2, (d, ff)) * s_in).astype(dtype)
    return p


def mlp_apply(p: Params, x: Array, act: str, glu: bool, quant, name: str) -> Array:
    up = maybe_quantized_matmul(x, p["wi"], quant, f"{name}.wi")
    if glu:
        gate = maybe_quantized_matmul(x, p["wg"], quant, f"{name}.wg")
        h = _act(gate, act) * up
    else:
        h = _act(up, act)
    return maybe_quantized_matmul(h, p["wo"], quant, f"{name}.wo")


# ---------------------------------------------------------------------------
# Attention (GQA/MQA) — chunked-causal for train/prefill, KV cache for decode.
# ---------------------------------------------------------------------------


def attn_init(key, cfg, dtype) -> Params:
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    s = d**-0.5
    return {
        "wq": (jax.random.normal(kq, (d, qd)) * s).astype(dtype),
        "wk": (jax.random.normal(kk, (d, kvd)) * s).astype(dtype),
        "wv": (jax.random.normal(kv, (d, kvd)) * s).astype(dtype),
        "wo": (jax.random.normal(ko, (qd, d)) * qd**-0.5).astype(dtype),
    }


def _qkv(p: Params, x: Array, cfg, quant, name: str):
    b, s, _ = x.shape
    q = maybe_quantized_matmul(x, p["wq"], quant, f"{name}.wq")
    k = maybe_quantized_matmul(x, p["wk"], quant, f"{name}.wk")
    v = maybe_quantized_matmul(x, p["wv"], quant, f"{name}.wv")
    q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    return q, k, v


def chunked_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                      chunk: int = 256) -> Array:
    """Memory-bounded attention (flash-style query chunking).

    q: (B, S, H, D); k, v: (B, T, K, D) with H = K * G.  Scores for one query
    chunk against all keys are materialized at a time: O(chunk * T) memory.
    """
    b, s, h, d = q.shape
    kh = k.shape[2]
    g = h // kh
    scale = d**-0.5
    chunk = min(chunk, s)
    while s % chunk:
        chunk //= 2
    nc = s // chunk
    qr = q.reshape(b, nc, chunk, kh, g, d)
    kt = k.astype(q.dtype)
    vt = v.astype(q.dtype)
    positions = jnp.arange(k.shape[1], dtype=jnp.int32)

    @jax.checkpoint   # recompute scores/probs in bwd: O(c*T) not O(S*T) live
    def one_chunk(ci):
        qc = qr[:, ci]                                       # (B, c, K, G, D)
        scores = jnp.einsum("bckgd,bskd->bckgs", qc, kt).astype(jnp.float32)
        scores = scores * scale
        if causal:
            row = ci * chunk + jnp.arange(chunk, dtype=jnp.int32)
            mask = positions[None, :] <= row[:, None]        # (c, T)
            scores = jnp.where(mask[None, :, None, None, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("bckgs,bskd->bckgd", probs, vt)

    out = jax.lax.map(one_chunk, jnp.arange(nc))             # (nc, B, c, K, G, D)
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, h, d)
    return out


# Backwards-compatible alias.
def chunked_causal_attention(q, k, v, *, chunk: int = 256):
    return chunked_attention(q, k, v, causal=True, chunk=chunk)


def attn_train(p: Params, x: Array, cfg, quant, name: str,
               positions: Optional[Array] = None,
               chunk: int = 256) -> Array:
    b, s, _ = x.shape
    q, k, v = _qkv(p, x, cfg, quant, name)
    pos = positions if positions is not None else jnp.arange(s, dtype=jnp.int32)
    q = rope(q, pos, cfg.rope_theta)
    k = rope(k, pos, cfg.rope_theta)
    out = chunked_causal_attention(q, k, v, chunk=chunk)
    out = out.reshape(b, s, cfg.q_dim)
    return maybe_quantized_matmul(out, p["wo"], quant, f"{name}.wo")


def attn_cache_init(cfg, batch: int, max_seq: int, dtype) -> Params:
    """K/V caches (B, Smax, K*D): kv-heads and head dim share one minor
    axis.  Kept as (K, D), a head dim off the TPU's 128-lane tile (160 at
    stablelm-12b) pads every cache copy of the serve engine 1.6x and made
    its decode program too large for the device beside the weights."""
    shape = (batch, max_seq, cfg.n_kv_heads * cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _heads(c: Array, cfg) -> Array:
    """(B, S, K*D) cache -> (B, S, K, D)."""
    return c.reshape(c.shape[:2] + (cfg.n_kv_heads, cfg.head_dim))


def cached_attention(q: Array, ck: Array, cv: Array, q_offset: Array, *,
                     kv_valid: Optional[Array] = None,
                     chunk: int = 256) -> Array:
    """Attention of a query chunk against the (partially filled) KV cache.

    q: (B, c, H, D) at global positions q_offset..q_offset+c-1;
    ck/cv: (B, Smax, K, D).  Row r attends kv positions <= q_offset + r.
    ``kv_valid`` (B, Smax) additionally masks out cache slots that hold pad
    tokens (ragged left-padded prompts).
    Peak memory O(sub_chunk * Smax) — the chunked-prefill working set.
    """
    b, c, h, d = q.shape
    kh = ck.shape[2]
    g = h // kh
    scale = d**-0.5
    sub = min(chunk, c)
    while c % sub:
        sub //= 2
    nc = c // sub
    qr = q.reshape(b, nc, sub, kh, g, d)
    kt = ck.astype(q.dtype)
    vt = cv.astype(q.dtype)
    kvpos = jnp.arange(ck.shape[1], dtype=jnp.int32)

    @jax.checkpoint
    def one_chunk(ci):
        qc = qr[:, ci]                                       # (B, sub, K, G, D)
        scores = jnp.einsum("bckgd,bskd->bckgs", qc, kt).astype(jnp.float32)
        scores = scores * scale
        row = q_offset + ci * sub + jnp.arange(sub, dtype=jnp.int32)
        mask = (kvpos[None, :] <= row[:, None])[None]        # (1, sub, Smax)
        if kv_valid is not None:
            mask = jnp.logical_and(mask, kv_valid[:, None, :])
        scores = jnp.where(mask[:, :, None, None, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("bckgs,bskd->bckgd", probs, vt)

    out = jax.lax.map(one_chunk, jnp.arange(nc))
    out = jnp.moveaxis(out, 0, 1).reshape(b, c, h, d)
    return out


def attn_prefill_chunk(p: Params, x: Array, cache: Params, offset: Array,
                       cfg, quant, name: str,
                       positions: Optional[Array] = None,
                       kv_valid: Optional[Array] = None
                       ) -> Tuple[Array, Params]:
    """One chunked-prefill step: project the chunk, extend the KV cache at
    ``offset``, attend against everything cached so far.

    ``positions`` (B, c) overrides the RoPE positions (ragged prompts where
    cache index != logical position); ``kv_valid`` (B, Smax) masks pad slots.
    """
    b, c, _ = x.shape
    q, k, v = _qkv(p, x, cfg, quant, name)
    pos = positions if positions is not None \
        else offset + jnp.arange(c, dtype=jnp.int32)
    q = rope(q, pos, cfg.rope_theta)
    k = rope(k, pos, cfg.rope_theta)
    ck = jax.lax.dynamic_update_slice(
        cache["k"], k.reshape(b, c, -1).astype(cache["k"].dtype),
        (0, offset, 0))
    cv = jax.lax.dynamic_update_slice(
        cache["v"], v.reshape(b, c, -1).astype(cache["v"].dtype),
        (0, offset, 0))
    out = cached_attention(q, _heads(ck, cfg), _heads(cv, cfg), offset,
                           kv_valid=kv_valid)
    out = out.reshape(b, c, cfg.q_dim)
    out = maybe_quantized_matmul(out, p["wo"], quant, f"{name}.wo")
    return out, {"k": ck, "v": cv}


def _as_batch_vec(pos, b: int) -> Array:
    pos = jnp.asarray(pos, jnp.int32)
    return jnp.broadcast_to(pos, (b,)) if pos.ndim == 0 else pos


def attn_decode(p: Params, x: Array, cache: Params, pos: Array, cfg, quant,
                name: str, positions: Optional[Array] = None,
                kv_valid: Optional[Array] = None) -> Tuple[Array, Params]:
    """One-token decode: x (B, 1, d); cache k/v (B, Smax, K*D).

    ``pos`` is the cache write index — a scalar (lock-step batch) or a (B,)
    vector (continuous batching: each slot at its own depth).  ``positions``
    optionally supplies distinct RoPE positions (left-padded caches where
    cache index != logical position); ``kv_valid`` (B, Smax) masks pad slots.
    """
    b = x.shape[0]
    q, k, v = _qkv(p, x, cfg, quant, name)
    pos_b = _as_batch_vec(pos, b)
    rpos = pos_b if positions is None else _as_batch_vec(positions, b)
    q = rope(q, rpos[:, None], cfg.rope_theta)
    k = rope(k, rpos[:, None], cfg.rope_theta)

    def write(c, u, start):
        return jax.lax.dynamic_update_slice(
            c, u.reshape(1, -1).astype(c.dtype), (start, 0))

    ck = jax.vmap(write)(cache["k"], k, pos_b)
    cv = jax.vmap(write)(cache["v"], v, pos_b)
    kh, d = cfg.n_kv_heads, cfg.head_dim
    g = cfg.n_heads // kh
    qv = q.reshape(b, kh, g, d)
    scores = jnp.einsum("bkgd,bskd->bkgs", qv,
                        _heads(ck, cfg).astype(q.dtype)).astype(jnp.float32)
    scores = scores * (d**-0.5)
    valid = (jnp.arange(ck.shape[1], dtype=jnp.int32)[None, :]
             <= pos_b[:, None])                              # (B, Smax)
    if kv_valid is not None:
        valid = jnp.logical_and(valid, kv_valid)
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgs,bskd->bkgd", probs,
                     _heads(cv, cfg).astype(q.dtype))
    out = out.reshape(b, 1, cfg.q_dim)
    out = maybe_quantized_matmul(out, p["wo"], quant, f"{name}.wo")
    return out, {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# Cross-attention (encoder-decoder).
# ---------------------------------------------------------------------------


def xattn_apply(p: Params, x: Array, mem_k: Array, mem_v: Array, cfg, quant,
                name: str) -> Array:
    """x: (B, S, d) queries; mem_k/mem_v: (B, T, K, D) precomputed from the
    encoder output (cached once per request)."""
    b, s, _ = x.shape
    q = maybe_quantized_matmul(x, p["wq"], quant, f"{name}.wq")
    q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
    kh, d = cfg.n_kv_heads, cfg.head_dim
    g = cfg.n_heads // kh
    qv = q.reshape(b, s, kh, g, d)
    scores = jnp.einsum("bskgd,btkd->bskgt", qv,
                        mem_k.astype(q.dtype)).astype(jnp.float32) * (d**-0.5)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bskgt,btkd->bskgd", probs, mem_v.astype(q.dtype))
    out = out.reshape(b, s, cfg.q_dim)
    return maybe_quantized_matmul(out, p["wo"], quant, f"{name}.wo")


def xattn_mem(p: Params, enc_out: Array, cfg, quant, name: str):
    """Project encoder output to cross-attention K/V once."""
    b, t, _ = enc_out.shape
    k = maybe_quantized_matmul(enc_out, p["wk"], quant, f"{name}.wk")
    v = maybe_quantized_matmul(enc_out, p["wv"], quant, f"{name}.wv")
    return (k.reshape(b, t, cfg.n_kv_heads, cfg.head_dim),
            v.reshape(b, t, cfg.n_kv_heads, cfg.head_dim))
