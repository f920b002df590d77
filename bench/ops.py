"""Operations and bytes from shapes: the yardstick of ``mfu_pct`` and of the
fused GEMM kernel's roofline share.

Both count from the configuration's widths and the token counts the harness
recorded, never from the program's kernels, so a change to how a GEMM is
implemented (mode, digit carrier, tiles) cannot move them.
"""
from __future__ import annotations

from typing import Dict, List, Tuple


def padded_vocab(cfg: dict) -> int:
    """Embedding rows padded to a multiple of 512, as the program stores them."""
    return -(-cfg["vocab_size"] // 512) * 512


def layer_gemms(cfg: dict) -> List[Tuple[int, int]]:
    """(K, N) of every weight GEMM in one layer, as the program runs them."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    if cfg["block"] == "attn":
        q = cfg["num_attention_heads"] * cfg["head_dim"]
        kv = cfg["num_key_value_heads"] * cfg["head_dim"]
        mix = [(d, q), (d, kv), (d, kv), (q, d)]
    else:
        mix = [(d, d)] * 5                          # r, k, v, g, o
    mlp = [(d, f), (d, f), (f, d)] if cfg["gated_mlp"] else [(d, f), (f, d)]
    return mix + mlp


def calls(cfg: dict, m: int, head_m: int) -> List[Tuple[int, int, int]]:
    """(M, K, N) of every fused-kernel GEMM of one model call with ``m``
    rows through the layers and ``head_m`` through the head."""
    per_layer = [(m, k, n) for k, n in layer_gemms(cfg)]
    return per_layer * cfg["num_hidden_layers"] + [
        (head_m, cfg["hidden_size"], padded_vocab(cfg))]


def gemm_ops(m: int, k: int, n: int) -> float:
    return 2.0 * m * k * n


def gemm_bytes(m: int, k: int, n: int, bits: int) -> float:
    """Operands at ``bits`` each, the output in 4-byte words."""
    return (m * k + k * n) * bits / 8.0 + m * n * 4.0


def least_seconds(m: int, k: int, n: int, bits: int, peaks: Dict[str, float]) -> float:
    """The least time the chip could take for one GEMM: the larger of its
    operations at the int8 peak and its bytes at the HBM peak."""
    return max(gemm_ops(m, k, n) / peaks["int8_ops"],
               gemm_bytes(m, k, n, bits) / peaks["hbm_bytes_per_s"])


def _weight_ops_per_token(cfg: dict) -> float:
    per_layer = sum(2.0 * k * n for k, n in layer_gemms(cfg))
    if cfg["block"] == "rwkv":
        d, hd, r = cfg["hidden_size"], cfg["head_size"], cfg["time_decay_lora_dim"]
        # decay LoRA, then the recurrence's read and update of the state
        per_layer += 2.0 * (d * r + r * d) + 4.0 * d * hd
    return per_layer * cfg["num_hidden_layers"]


def _head_ops(cfg: dict) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def _attn_ops(cfg: dict, ctx: int) -> float:
    """Scores and weighted values of one query against ``ctx`` keys, all layers."""
    if cfg["block"] != "attn":
        return 0.0
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    return 4.0 * q * ctx * cfg["num_hidden_layers"]


def prefill_ops(cfg: dict, n: int) -> float:
    """Model operations of one prompt of ``n`` real tokens: every token
    through the layers, causal attention over the prompt, the head once."""
    attn = _attn_ops(cfg, 1) * n * (n + 1) / 2.0
    return _weight_ops_per_token(cfg) * n + attn + _head_ops(cfg)


def decode_ops(cfg: dict, position: int) -> float:
    """Model operations of one generated token at ``position`` (0-based)."""
    return _weight_ops_per_token(cfg) + _attn_ops(cfg, position + 1) + _head_ops(cfg)
