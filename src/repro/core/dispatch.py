"""Precision-scalable execution-mode dispatch (paper Section IV-C, Fig. 10).

Given input bitwidth ``w`` and multiplier bitwidth ``m`` the architecture
selects:

  * ``w <= m``          -> MM1  (1 tile pass)
  * ``m < w <= 2m - 2``  -> KMM2 (3 tile passes; the ``2m-2`` bound keeps the
                            ``As = A1 + A0`` digits within ``m`` bits)
  * ``2m - 2 < w <= 2m`` -> MM2  (4 tile passes)

Larger ``w`` recurses (fixed-precision architecture, Fig. 8): each level of
KMM halves the width (+1 carry bit) until digits fit the multiplier.
"""
from __future__ import annotations

import enum
import math
from dataclasses import dataclass
from typing import List


class Mode(enum.Enum):
    MM1 = "mm1"
    KMM2 = "kmm2"
    MM2 = "mm2"


@dataclass(frozen=True)
class Plan:
    mode: Mode
    w: int            # input bitwidth
    m: int            # multiplier bitwidth
    passes: int       # tile-read passes of the precision-scalable MXU
    digits: int       # n: digits per operand at this level
    recursion: int    # r = ceil(log2 n) levels used

    @property
    def mults_per_product(self) -> int:
        """m-bit multiplications per w-bit product (3^r for KMM, 4^r for MM)."""
        if self.mode is Mode.MM1:
            return 1
        base = 3 if self.mode is Mode.KMM2 else 4
        return base ** self.recursion


def select_mode(w: int, m: int = 8) -> Plan:
    """The paper's single-level dispatch rule (Fig. 10 modes).

    The ``w == 2m - 1`` boundary deliberately lands in MM2, not KMM2: at
    ``w = 2m - 1`` the digit split is ``h = ceil(w/2) = m``, so the Karatsuba
    pre-adder outputs ``As = A1 + A0`` need ``m + 1`` bits and no longer fit
    the ``m``-bit multiplier operands — the KMM2 window closes at ``2m - 2``
    (the paper's Fig. 10 rule) and the conventional 4-product MM2 covers
    ``2m - 1`` and ``2m``.  This is correct-by-construction, not a silent
    fallback; tests pin it (``test_w_2m_minus_1_boundary_is_mm2``).
    """
    if m < 2:
        raise ValueError(
            f"multiplier bitwidth m must be >= 2, got m={m}: with m < 2 the "
            f"dispatch windows collapse (KMM2's 'm < w <= 2m - 2' band is "
            f"empty and digit splitting cannot produce m-bit operands)")
    if w < 1:
        raise ValueError(f"bitwidth must be >= 1, got {w}")
    if w <= m:
        return Plan(Mode.MM1, w, m, passes=1, digits=1, recursion=0)
    if w <= 2 * m - 2:
        return Plan(Mode.KMM2, w, m, passes=3, digits=2, recursion=1)
    if w <= 2 * m:
        return Plan(Mode.MM2, w, m, passes=4, digits=2, recursion=1)
    # Fixed-precision recursion (Fig. 8): more than one KMM level.
    r = kmm_levels_needed(w, m)
    if r is None:
        raise ValueError(f"w={w} too wide for m={m} multipliers at any depth")
    return Plan(Mode.KMM2, w, m, passes=3 ** r, digits=2 ** r, recursion=r)


def kmm_levels_needed(w: int, m: int) -> int | None:
    """Minimum KMM recursion depth so every leaf digit fits m bits.

    Each level maps width w -> ceil(w/2) + 1 on the widest (Cs) branch.
    """
    width, r = w, 0
    while width > m:
        width = -(-width // 2) + 1
        r += 1
        if r > 8:
            return None
    return r


def conv_mults_per_product(w: int, m: int) -> int:
    """m-bit mults a *conventional* algorithm (SM/MM) needs per w-bit product:
    4**r with r = ceil(log2(ceil(w/m)))  (paper Eq. 13)."""
    r = conv_recursion(w, m)
    return 4 ** r


def conv_recursion(w: int, m: int) -> int:
    n = -(-w // m)
    return math.ceil(math.log2(n)) if n > 1 else 0


def efficiency_roof(w: int, m: int) -> float:
    """Multiplier-compute-efficiency roof of the precision-scalable KMM
    architecture at width w (paper Eq. 15 + mode rule): conventional mult
    count divided by the mode's mult count."""
    plan = select_mode(w, m)
    return conv_mults_per_product(w, m) / plan.mults_per_product


def schedule(widths: List[int], m: int = 8) -> List[Plan]:
    """Plan a mixed-precision workload (one Plan per layer bitwidth)."""
    return [select_mode(w, m) for w in widths]
