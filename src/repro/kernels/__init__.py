"""Pallas TPU kernels (validated in interpret mode on CPU; see DESIGN.md §2).

fused_gemm — single-pass MM1/KMM2/MM2/depth-2 KMM GEMM: in-kernel digit
            split, zero-point correction and dequant epilogue in one
            pallas_call, with its own plan (``plan_for``: the paper's mode,
            tiles, combine, bounds); grouped and ragged MoE variants
            (DESIGN.md §11).
wkv_gemm  — RWKV6 recurrence with state resident in VMEM.
ops       — ``int_gemm``: the paper's mode rule on the fused kernel
            (pallas) or the digit recursion of repro.core.kmm (xla).
ref       — tests' references: the int64 oracle and the jnp mirror of
            the fused kernel.
"""
from repro.kernels.ops import int_gemm, int_gemm_jit
