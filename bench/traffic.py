"""One general generator for every traffic mix, driven by a data file.

A mix (``bench/traffic/<name>.json``) gives the loop (open or closed), the
slots and ``max_seq`` of the engine, the prompt and output length
distributions and, for an open loop, the arrival process and its rate.

Every seed gets the same set of sizes and gaps: lengths are the
distribution's quantiles at ``(i + 0.5) / n`` and gaps are one fixed draw
rescaled to the mix's mean, so the work in a window does not change with the
seed.  With ``"order": "shuffled"`` (the default) the seed shuffles them;
with ``"order": "fixed"`` every seed replays one schedule, the order drawn
once from a fixed seed, since a tail over tens of requests turns on which
gaps and lengths fall together.  The seed always draws the prompt tokens.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist
from typing import List

import numpy as np

# The fixed draw behind every seed's set of inter-arrival gaps.
GAP_DRAW_SEED = 20250117
# The fixed draw of the order of a mix with ``"order": "fixed"``.
ORDER_DRAW_SEED = 20261017


@dataclass(frozen=True)
class Item:
    prompt: List[int]
    max_new: int
    due_s: float          # scheduled arrival, seconds after the window opens


def load(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles ``(i + 0.5) / n`` of ``dist``, clipped."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"] + 1) - 0.5
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def gaps(arrivals: dict, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps with mean exactly ``1 / rate``: gamma with
    the given shape (1 is Poisson; 0.25 has a coefficient of variation 2)."""
    rng = np.random.default_rng(GAP_DRAW_SEED)
    if arrivals["process"] != "gamma":
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    g = rng.gamma(arrivals["shape"], 1.0, n)
    return g / g.mean() / arrivals["rate"]


def count(mix: dict, seconds: float) -> int:
    """Requests the run draws: the open loop's arrivals due in the window,
    or the closed loop's set of documents that it cycles through."""
    if mix["loop"] == "open":
        return max(1, math.ceil(mix["arrivals"]["rate"] * seconds))
    return mix["set_size"]


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> List[Item]:
    """The run's requests in the order the loop sends them."""
    n = count(mix, seconds)
    rng = np.random.default_rng(seed % 2 ** 64)
    order = mix.get("order", "shuffled")
    if order not in ("shuffled", "fixed"):
        raise ValueError(f"unknown order {order!r}")
    shuffle = rng if order == "shuffled" else np.random.default_rng(ORDER_DRAW_SEED)
    plen = shuffle.permutation(lengths(mix["prompt"], n))
    olen = shuffle.permutation(lengths(mix["output"], n))
    if mix["loop"] == "open":
        due = np.cumsum(shuffle.permutation(gaps(mix["arrivals"], n)))
        due = due - due[0]          # the first request is due as the window opens
    else:
        due = np.zeros(n)
    return [Item(prompt=rng.integers(1, vocab, int(p)).tolist(),
                 max_new=int(o), due_s=float(t))
            for p, o, t in zip(plen, olen, due)]


def bucket(n: int, ladder) -> int:
    return next(b for b in ladder if b >= n)


def prompt_buckets(mix: dict, ladder) -> List[int]:
    """The prefill widths of the engine's ladder that this mix's prompts reach."""
    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    return sorted({bucket(n, ladder) for n in range(lo, hi + 1)})
