"""RWKV-6 time mix: attention-free linear recurrence with data-dependent
per-channel decay.

Per head (state S in R^{D x D}):  S_t = diag(w_t) S_{t-1} + k_t^T v_t,
y_t = r_t (S_{t-1} + diag(u) k_t^T v_t).  The decay w_t is produced by a
low-rank MLP on the token-shifted input (the v6 data-dependence).  The
recurrence runs in fp32 (not an integer GEMM -> KMM inapplicable, DESIGN.md
§6); the r/k/v/g/o projections ride the quantized KMM path.

This is not the published Finch block (arXiv:2404.05892).  The time mix
departs from it twice: the token shift blends each stream with a static
learned mix (Finch's mix is data-dependent, the ``ddlerp`` LoRA), and the
output is normalised by one LayerNorm over the model width (Finch: a
GroupNorm per head).  The block around it (models/lm.py) departs three more
times: a squared-ReLU channel mix with no receptance gate and no token
shift, RMSNorm pre-norms where Finch has LayerNorm, and no ``ln0`` after the
embedding.

Implementation: time-step `lax.scan` for full sequences (state is
(B, H, D, D), so an associative scan over matrices would materialize
(B, S, H, D, D) — prohibitive); single-step update for decode, which is the
long_500k-relevant path (state size is sequence-length independent).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.quant.qmatmul import maybe_quantized_matmul
from repro.models.layers import norm_apply

Array = jax.Array
Params = Dict[str, Array]

LORA_DIM = 64


def rwkv_init(key, cfg, dtype) -> Params:
    d = cfg.d_model
    keys = jax.random.split(key, 10)
    s = d**-0.5
    hd = cfg.rwkv_head_dim
    nh = d // hd
    return {
        "mix": jnp.full((5, d), 0.5, jnp.float32),     # r,k,v,g,w shift mixes
        "wr": (jax.random.normal(keys[0], (d, d)) * s).astype(dtype),
        "wk": (jax.random.normal(keys[1], (d, d)) * s).astype(dtype),
        "wv": (jax.random.normal(keys[2], (d, d)) * s).astype(dtype),
        "wg": (jax.random.normal(keys[3], (d, d)) * s).astype(dtype),
        "wo": (jax.random.normal(keys[4], (d, d)) * s).astype(dtype),
        "w0": jnp.full((d,), -6.0, jnp.float32),       # base decay (slow)
        "w_lora_a": (jax.random.normal(keys[5], (d, LORA_DIM)) * s
                     ).astype(dtype),
        "w_lora_b": (jax.random.normal(keys[6], (LORA_DIM, d)) * LORA_DIM**-0.5
                     ).astype(dtype),
        "u": (jax.random.normal(keys[7], (nh, hd)) * 0.1).astype(jnp.float32),
        "ln_x": {"scale": jnp.ones((d,), jnp.float32),
                 "bias": jnp.zeros((d,), jnp.float32)},
    }


def _shift_mix(x: Array, prev: Array, mix: Array):
    """Token shift: blend each position with its predecessor.

    x: (B, S, d); prev: (B, 1, d) state carried across calls.
    Returns the 5 mixed streams (r, k, v, g, w) and the new shift state.
    """
    shifted = jnp.concatenate([prev, x[:, :-1, :]], axis=1)
    mixed = [x * m + shifted * (1.0 - m) for m in mix]  # 5 x (B,S,d)
    return mixed, x[:, -1:, :]


def _decay(p: Params, xw: Array) -> Array:
    lora = jnp.tanh(xw @ p["w_lora_a"].astype(xw.dtype))
    lora = lora @ p["w_lora_b"].astype(xw.dtype)
    return jnp.exp(-jnp.exp(p["w0"] + lora.astype(jnp.float32)))  # (B,S,d) in (0,1)


def _project(p: Params, streams, quant, name: str, cfg):
    xr, xk, xv, xg, xw = streams
    r = maybe_quantized_matmul(xr, p["wr"], quant, f"{name}.wr")
    k = maybe_quantized_matmul(xk, p["wk"], quant, f"{name}.wk")
    v = maybe_quantized_matmul(xv, p["wv"], quant, f"{name}.wv")
    g = maybe_quantized_matmul(xg, p["wg"], quant, f"{name}.wg")
    w = _decay(p, xw)
    return r, k, v, g, w


def _heads(x: Array, nh: int, hd: int) -> Array:
    return x.reshape(*x.shape[:-1], nh, hd)


def rwkv_apply_stateful(p: Params, x: Array, cache: Optional[Params], cfg,
                        quant, name: str, mask: Optional[Array] = None,
                        last_idx: Optional[Array] = None
                        ) -> Tuple[Array, Params]:
    """Sequence forward from carried (shift, wkv) state; returns end state.

    Ragged prompts: ``mask`` (B, S) freezes the wkv state on pad positions
    (decay forced to 1, kv contribution zeroed) and zeroes pad inputs so the
    token shift at a left-pad boundary sees the same zeros an unpadded run
    starts from; ``last_idx`` (B,) picks each row's last *real* token for the
    carried shift state (right-padded prompts)."""
    b, s, d = x.shape
    hd = cfg.rwkv_head_dim
    nh = d // hd
    if cache is None:
        cache = rwkv_cache_init(cfg, b, x.dtype)
    if mask is not None:
        x = jnp.where(mask[:, :, None], x, 0)
    prev = cache["shift"].astype(x.dtype)
    streams, new_shift = _shift_mix(x, prev, p["mix"])
    r, k, v, g, w = _project(p, streams, quant, name, cfg)
    r = _heads(r.astype(jnp.float32), nh, hd)
    k = _heads(k.astype(jnp.float32), nh, hd)
    v = _heads(v.astype(jnp.float32), nh, hd)
    w = _heads(w, nh, hd)                                  # (B,S,H,hd)
    if mask is not None:                                   # freeze on pads
        m4 = mask[:, :, None, None]
        k = jnp.where(m4, k, 0.0)
        w = jnp.where(m4, w, 1.0)
    if last_idx is not None:
        new_shift = jnp.take_along_axis(
            x, last_idx.astype(jnp.int32)[:, None, None], axis=1)
    u = p["u"]

    # Time-chunked scan: the matrix state (B, H, D, D) is carried across
    # chunks; inside a chunk the sequential recurrence runs under
    # jax.checkpoint so the backward stores only chunk-boundary states
    # (O(S/csz * state) instead of O(S * state)).
    csz = 64
    while s % csz:
        csz //= 2
    nc = s // csz

    def to_chunks(t):   # (B, S, H, hd) -> (nc, csz, B, H, hd)
        return jnp.moveaxis(t, 1, 0).reshape(nc, csz, b, nh, hd)

    xs = (to_chunks(r), to_chunks(k), to_chunks(v), to_chunks(w))

    def step(state, xs_t):
        rt, kt, vt, wt = xs_t                                 # (B,H,hd)
        kv = kt[..., :, None] * vt[..., None, :]              # (B,H,hd,hd)
        yt = jnp.einsum("bhi,bhij->bhj", rt, state + u[None, :, :, None] * kv)
        new = wt[..., :, None] * state + kv
        return new, yt

    @jax.checkpoint
    def chunk_body(state, xs_chunk):
        return lax.scan(step, state, xs_chunk)

    sT, y = lax.scan(chunk_body, cache["wkv"], xs)            # (nc,csz,B,H,hd)
    y = jnp.moveaxis(y.reshape(s, b, nh, hd), 0, 1).reshape(b, s, d)
    y = norm_apply(p["ln_x"], y, kind="ln")
    y = y * jax.nn.silu(g.astype(jnp.float32))
    out = maybe_quantized_matmul(y.astype(x.dtype), p["wo"], quant,
                                 f"{name}.wo")
    return out, {"shift": new_shift.astype(cache["shift"].dtype), "wkv": sT}


def rwkv_apply(p: Params, x: Array, cfg, quant, name: str) -> Array:
    """Full-sequence forward (train)."""
    out, _ = rwkv_apply_stateful(p, x, None, cfg, quant, name)
    return out


def rwkv_cache_init(cfg, batch: int, dtype) -> Params:
    d = cfg.d_model
    hd = cfg.rwkv_head_dim
    nh = d // hd
    return {
        "shift": jnp.zeros((batch, 1, d), dtype),
        "wkv": jnp.zeros((batch, nh, hd, hd), jnp.float32),
    }


def rwkv_decode(p: Params, x: Array, cache: Params, cfg, quant,
                name: str) -> Tuple[Array, Params]:
    """Single-token step: x (B, 1, d); constant-size state."""
    b, _, d = x.shape
    hd = cfg.rwkv_head_dim
    nh = d // hd
    streams, new_shift = _shift_mix(x, cache["shift"].astype(x.dtype),
                                    p["mix"])
    r, k, v, g, w = _project(p, streams, quant, name, cfg)
    rt = _heads(r.astype(jnp.float32)[:, 0], nh, hd)
    kt = _heads(k.astype(jnp.float32)[:, 0], nh, hd)
    vt = _heads(v.astype(jnp.float32)[:, 0], nh, hd)
    wt = _heads(w[:, 0], nh, hd)
    kv = kt[..., :, None] * vt[..., None, :]
    y = jnp.einsum("bhi,bhij->bhj", rt,
                   cache["wkv"] + p["u"][None, :, :, None] * kv)
    new_state = wt[..., :, None] * cache["wkv"] + kv
    y = y.reshape(b, 1, d)
    y = norm_apply(p["ln_x"], y, kind="ln")
    y = y * jax.nn.silu(g.astype(jnp.float32))
    out = maybe_quantized_matmul(y.astype(x.dtype), p["wo"], quant,
                                 f"{name}.wo")
    return out, {"shift": new_shift.astype(cache["shift"].dtype),
                 "wkv": new_state}
