"""Random weights from the seed, made on the device in one jitted call.

The tree has the layout the serving program takes (embedding, period-stacked
blocks, final norm, untied head), in the configuration's ``param_dtype``.
The same arrays are handed to the program and, once the program is gone, to
the plain reference, so the two compute over identical weights; neither
depends on how the program would initialise itself.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ops import padded_vocab


def shapes(cfg: dict) -> dict:
    """Leaf name -> (shape, init) with init one of ("normal", std) or
    ("const", value).  Block leaves carry the layer count as leading axis."""
    d, f, n = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_hidden_layers"]
    v = padded_vocab(cfg)
    out = {
        "embed": ((v, d), ("normal", d ** -0.5)),
        "ln_f.scale": ((d,), ("const", 1.0)),
        "lm_head": ((d, v), ("normal", d ** -0.5)),
        "blocks.pos0.ln1.scale": ((n, d), ("const", 1.0)),
        "blocks.pos0.ln2.scale": ((n, d), ("const", 1.0)),
        "blocks.pos0.mlp.wi": ((n, d, f), ("normal", d ** -0.5)),
        "blocks.pos0.mlp.wo": ((n, f, d), ("normal", f ** -0.5)),
    }
    if cfg["gated_mlp"]:
        out["blocks.pos0.mlp.wg"] = ((n, d, f), ("normal", d ** -0.5))
    if cfg["block"] == "attn":
        q = cfg["num_attention_heads"] * cfg["head_dim"]
        kv = cfg["num_key_value_heads"] * cfg["head_dim"]
        out.update({
            "blocks.pos0.attn.wq": ((n, d, q), ("normal", d ** -0.5)),
            "blocks.pos0.attn.wk": ((n, d, kv), ("normal", d ** -0.5)),
            "blocks.pos0.attn.wv": ((n, d, kv), ("normal", d ** -0.5)),
            "blocks.pos0.attn.wo": ((n, q, d), ("normal", q ** -0.5)),
        })
    elif cfg["block"] == "rwkv":
        hd, r = cfg["head_size"], cfg["time_decay_lora_dim"]
        p = "blocks.pos0.rwkv."
        out.update({
            p + "mix": ((n, 5, d), ("const", 0.5)),
            p + "w0": ((n, d), ("const", -6.0)),
            p + "u": ((n, d // hd, hd), ("normal", 0.1)),
            p + "w_lora_a": ((n, d, r), ("normal", d ** -0.5)),
            p + "w_lora_b": ((n, r, d), ("normal", r ** -0.5)),
            p + "ln_x.scale": ((n, d), ("const", 1.0)),
            p + "ln_x.bias": ((n, d), ("const", 0.0)),
        })
        for w in ("wr", "wk", "wv", "wg", "wo"):
            out[p + w] = ((n, d, d), ("normal", d ** -0.5))
    else:
        raise ValueError(f"unknown block kind {cfg['block']!r}")
    return out


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for name, leaf in flat.items():
        node = tree
        *path, last = name.split(".")
        for part in path:
            node = node.setdefault(part, {})
        node[last] = leaf
    return tree


def make(cfg: dict, key: jax.Array) -> dict:
    """The weight tree, drawn from ``key`` in one jitted call."""
    spec = shapes(cfg)
    dtype = jnp.dtype(cfg["param_dtype"])

    def build(key):
        flat = {}
        for i, (name, (shape, (kind, val))) in enumerate(sorted(spec.items())):
            if kind == "normal":
                x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32) * val
            else:
                x = jnp.full(shape, val, jnp.float32)
            # norms, mixes and decays stay float32, as the program keeps them
            big = name.endswith(("embed", "lm_head")) or name.split(".")[-1] in (
                "wq", "wk", "wv", "wo", "wi", "wg", "wr", "w_lora_a", "w_lora_b")
            flat[name] = x.astype(dtype) if big else x
        return _nest(flat)

    return jax.jit(build)(key)
