"""Plain float32 reference of the served model, and its lower-precision control.

Written from the layer equations alone, with no kernel, cache, batching
policy or quantization of the program under test: each block kind is a few
lines of ``jax.numpy`` at ``HIGHEST`` matmul precision.  It runs layer by
layer, one jitted call per layer, so that it fits beside the weights on the
chip once the program's state is freed.

  * ``attn``: pre-RMSNorm block; GQA attention with rotary positions
    (rotate-half, ``theta ** (-2i / head_dim)``), causal softmax at scale
    ``head_dim ** -0.5``; SwiGLU MLP ``(silu(x Wg) * (x Wi)) Wo``.
  * ``rwkv``: pre-RMSNorm block; RWKV6 time mix from a zero state: token
    shift ``x*m + x_prev*(1-m)`` for r, k, v, g, w; decay
    ``exp(-exp(w0 + tanh(x_w A) B))``; per head
    ``y_t = r_t (S + diag(u) k_t^T v_t)``, ``S <- diag(w_t) S + k_t^T v_t``;
    LayerNorm over the model width, times ``silu(g)``, then ``Wo``; the
    channel mix is ``relu(x Wi)^2 Wo``.
  * head: RMSNorm, then the untied head over the real vocabulary.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
# Tokens per reference call: a batch of sequences padded to one length.
TOKENS_PER_CALL = 8192
MIN_LEN = 128


def _mm(x, w):
    return jnp.matmul(x, w.astype(jnp.float32), precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (B, S, H, D), positions 0..S-1."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(cfg, p, h):
    b, s, _ = h.shape
    nh, kh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    q = _rope(_mm(h, p["wq"]).reshape(b, s, nh, hd), cfg["rope_theta"])
    k = _rope(_mm(h, p["wk"]).reshape(b, s, kh, hd), cfg["rope_theta"])
    v = _mm(h, p["wv"]).reshape(b, s, kh, hd)
    q = q.reshape(b, s, kh, nh // kh, hd)
    chunk = min(s, 512)
    pos = jnp.arange(s)

    def rows(c):                       # one block of query rows
        qc = jax.lax.dynamic_slice_in_dim(q, c * chunk, chunk, 1)
        sc = jnp.einsum("bckgd,bskd->bkgcs", qc, k, precision=HI) * hd ** -0.5
        row = c * chunk + jnp.arange(chunk)
        sc = jnp.where(pos[None, :] <= row[:, None], sc, -jnp.inf)
        return jnp.einsum("bkgcs,bskd->bckgd", jax.nn.softmax(sc, -1), v,
                          precision=HI)

    out = jax.lax.map(rows, jnp.arange(s // chunk))      # (n, B, c, K, G, D)
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, nh * hd)
    return _mm(out, p["wo"])


def _rwkv_time_mix(cfg, p, h):
    b, s, d = h.shape
    hd = cfg["head_size"]
    nh = d // hd
    prev = jnp.concatenate([jnp.zeros_like(h[:, :1]), h[:, :-1]], 1)
    xr, xk, xv, xg, xw = (h * m + prev * (1.0 - m) for m in p["mix"])
    r = _mm(xr, p["wr"]).reshape(b, s, nh, hd)
    k = _mm(xk, p["wk"]).reshape(b, s, nh, hd)
    v = _mm(xv, p["wv"]).reshape(b, s, nh, hd)
    g = _mm(xg, p["wg"])
    lora = jnp.matmul(jnp.tanh(jnp.matmul(xw, p["w_lora_a"], precision=HI)),
                      p["w_lora_b"], precision=HI)
    w = jnp.exp(-jnp.exp(p["w0"] + lora)).reshape(b, s, nh, hd)
    u = p["u"]

    def step(state, t):                 # state (B, H, D, D)
        rt, kt, vt, wt = t
        kv = kt[..., :, None] * vt[..., None, :]
        y = jnp.einsum("bhi,bhij->bhj", rt, state + u[None, :, :, None] * kv,
                       precision=HI)
        return wt[..., :, None] * state + kv, y

    seq = tuple(jnp.moveaxis(a, 1, 0) for a in (r, k, v, w))
    _, y = jax.lax.scan(step, jnp.zeros((b, nh, hd, hd), jnp.float32), seq)
    y = jnp.moveaxis(y, 0, 1).reshape(b, s, d)
    mu = y.mean(-1, keepdims=True)
    var = ((y - mu) ** 2).mean(-1, keepdims=True)
    y = (y - mu) * jax.lax.rsqrt(var + cfg["norm_eps"]) * p["ln_x"]["scale"] \
        + p["ln_x"]["bias"]
    return _mm(y * jax.nn.silu(g), p["wo"])


def _mlp(cfg, p, h):
    up = _mm(h, p["wi"])
    if cfg["gated_mlp"]:
        act = {"silu": jax.nn.silu}[cfg["hidden_act"]]
        hid = act(_mm(h, p["wg"])) * up
    else:
        hid = {"relu2": lambda t: jnp.square(jax.nn.relu(t))}[cfg["hidden_act"]](up)
    return _mm(hid, p["wo"])


@functools.partial(jax.jit, static_argnums=(0,))
def _layer(cfg_items, blocks, i, x):
    cfg = dict(cfg_items)
    p = jax.tree.map(lambda a: a[i], blocks)
    eps = cfg["norm_eps"]
    h = _rms(x, p["ln1"]["scale"], eps)
    if cfg["block"] == "attn":
        x = x + _attention(cfg, p["attn"], h)
    else:
        x = x + _rwkv_time_mix(cfg, p["rwkv"], h)
    return x + _mlp(cfg, p["mlp"], _rms(x, p["ln2"]["scale"], eps))


@functools.partial(jax.jit, static_argnums=(0,))
def _embed(cfg_items, embed, tokens):
    d = dict(cfg_items)["hidden_size"]
    return embed[tokens].astype(jnp.float32) * d ** 0.5


@functools.partial(jax.jit, static_argnums=(0,))
def _head(cfg_items, ln_f, lm_head, x, pos):
    cfg = dict(cfg_items)
    xs = jnp.take_along_axis(x, pos[..., None], axis=1)          # (B, P, d)
    xs = _rms(xs, ln_f["scale"], cfg["norm_eps"])
    return _mm(xs, lm_head[:, :cfg["vocab_size"]])


def _items(cfg: dict):
    keep = ("block", "hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rope_theta", "head_size", "gated_mlp", "hidden_act",
            "norm_eps", "vocab_size", "num_hidden_layers")
    return tuple(sorted((k, cfg[k]) for k in keep if k in cfg))


def _length(n: int, least: int = MIN_LEN) -> int:
    s = least
    while s < n:
        s *= 2
    return s


def logits_at(cfg: dict, params: dict, seqs: Sequence[Sequence[int]],
              positions: Sequence[Sequence[int]]) -> List[np.ndarray]:
    """Reference logits ``(len(positions[i]), vocab)`` of each sequence at
    the given positions.  Sequences are right-padded to a power of two and
    batched up to ``TOKENS_PER_CALL`` tokens; the pad follows every real
    token, so causal attention and the recurrence never see it."""
    items = _items(cfg)
    blocks = params["blocks"]["pos0"]
    n_layers = cfg["num_hidden_layers"]
    out: List[Optional[np.ndarray]] = [None] * len(seqs)
    by_len: dict = {}
    for i, s in enumerate(seqs):
        by_len.setdefault(_length(len(s)), []).append(i)
    for length, idxs in sorted(by_len.items()):
        per = max(1, TOKENS_PER_CALL // length)
        for at in range(0, len(idxs), per):
            group = idxs[at:at + per]
            group_b = group + [group[0]] * (per - len(group))   # fixed shapes
            toks = np.zeros((per, length), np.int32)
            npos = _length(max(len(positions[i]) for i in group), 32)
            pos = np.zeros((per, npos), np.int32)
            for row, i in enumerate(group_b):
                toks[row, :len(seqs[i])] = seqs[i]
                pos[row, :len(positions[i])] = positions[i]
            x = _embed(items, params["embed"], jnp.asarray(toks))
            for layer in range(n_layers):
                x = _layer(items, blocks, jnp.int32(layer), x)
            lg = np.asarray(_head(items, params["ln_f"], params["lm_head"], x,
                                  jnp.asarray(pos)))
            for row, i in enumerate(group):
                out[i] = lg[row, :len(positions[i])]
    return out
