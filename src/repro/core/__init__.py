"""Core KMM library: the paper's algorithms, dispatch rule, and cost models."""
from repro.core.kmm import (
    digit_split,
    kmm_matmul,
    kmm_n,
    ksm_n,
    ksmm,
    matmul_dims_for,
    max_exact_k,
    mm_n,
    sm_n,
    MATMUL_DIMS,
    default_mm1,
)
from repro.core.accum import preaccum_matmul, preaccum_mm1, DEFAULT_P
from repro.core.dispatch import Mode, Plan, select_mode, efficiency_roof

__all__ = [
    "digit_split", "kmm_matmul", "kmm_n", "ksm_n", "ksmm", "matmul_dims_for",
    "max_exact_k", "mm_n", "sm_n", "MATMUL_DIMS", "default_mm1",
    "preaccum_matmul", "preaccum_mm1", "DEFAULT_P",
    "Mode", "Plan", "select_mode", "efficiency_roof",
]
