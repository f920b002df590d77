"""The comparison that decides ``correct``, and the numbers it prints.

After the window the harness draws a sample of the requests the program
finished, the longest among them, and feeds each prompt with its served
tokens to the plain reference.  ``served_gap`` is the widest gap, in logits,
by which a served (greedy) token lies below the reference's best token at
its position.  Each number is held to the cell's limit in
``bench/limits/<cell>.json``; how each limit was set is recorded there.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

import numpy as np

# Served tokens the sample gathers before it stops adding requests.
SAMPLE_TOKENS = 1000


def sample(finished: Sequence[Tuple[List[int], List[int]]], seed: int,
           tokens: int = SAMPLE_TOKENS) -> List[int]:
    """Indices of a sample of finished ``(prompt, served)`` pairs: the
    longest, then others in an order drawn from the seed, until ``tokens``
    served tokens are in."""
    if not finished:
        return []
    order = list(np.random.default_rng(seed % 2 ** 64 ^ 0x5EED).permutation(
        len(finished)))
    longest = max(range(len(finished)),
                  key=lambda i: len(finished[i][0]) + len(finished[i][1]))
    order.remove(longest)
    picked, n = [longest], len(finished[longest][1])
    for i in order:
        if n >= tokens:
            break
        picked.append(int(i))
        n += len(finished[i][1])
    return picked


def teacher_forced(prompt: List[int], served: List[int]):
    """The sequence to feed and the positions whose next token was served."""
    seq = list(prompt) + list(served[:-1])
    pos = [len(prompt) - 1 + j for j in range(len(served))]
    return seq, pos


def gaps(ref: np.ndarray, tokens: Sequence[int]) -> np.ndarray:
    """Per position: the reference's best logit minus its logit of ``tokens``."""
    ref = np.asarray(ref, np.float64)
    return ref.max(-1) - ref[np.arange(len(tokens)), np.asarray(tokens)]


def gap_numbers(refs: Sequence[np.ndarray],
                served: Sequence[Sequence[int]]) -> Dict[str, float]:
    """``served_gap``: the widest gap; ``mean_gap``: the mean gap over every
    served token, which counts how often and how far the tokens stray."""
    g = np.concatenate([gaps(r, s) for r, s in zip(refs, served)])
    return {"served_gap": float(g.max()), "mean_gap": float(g.mean())}


def load_limits(bench_dir: Path, cell: str) -> Dict[str, float]:
    data = json.loads((bench_dir / "limits" / f"{cell}.json").read_text())
    return {k: v["limit"] for k, v in data["limits"].items()}


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> Tuple[bool, list]:
    """Each number against its limit (``<=``); a missing number fails."""
    rows = []
    ok = True
    for name, limit in limits.items():
        value = numbers.get(name)
        good = value is not None and np.isfinite(value) and value <= limit
        ok &= bool(good)
        rows.append({"name": name, "value": value, "limit": limit})
    return ok, rows
