"""Quickstart: the KMM public API in five minutes.

    PYTHONPATH=src python examples/quickstart.py

1. exact integer Karatsuba matrix multiplication (Algorithm 4),
2. the precision-scalable dispatch rule (Fig. 10),
3. the Pallas MXU kernel (interpret mode on CPU),
4. the complexity/area models behind the paper's figures.
"""
import numpy as np
import jax.numpy as jnp

from repro.core import kmm_n, mm_n, select_mode, max_exact_k
from repro.core.complexity import kmm_arith, ksmm_arith, mm_arith
from repro.core.area import au_efficiency_vs_mm1
from repro.kernels.ops import int_gemm
from repro.kernels.ref import ref_int_gemm_i64


def main():
    rng = np.random.default_rng(0)

    # --- 1. KMM is exact integer matmul with 3^r digit products -------------
    w = 12                       # operand bitwidth
    k = min(max_exact_k(w), 64)  # int32-exact contraction bound
    a = rng.integers(-2**11, 2**11, (8, k)).astype(np.int32)
    b = rng.integers(-2**11, 2**11, (k, 8)).astype(np.int32)
    out = np.asarray(kmm_n(jnp.array(a), jnp.array(b), w=w, n=2))
    assert (out.astype(np.int64) == ref_int_gemm_i64(a, b)).all()
    print(f"KMM_2^[{w}]: exact, 3 digit products (MM_2 needs 4)")

    # --- 2. precision-scalable dispatch (paper Fig. 10) ----------------------
    for bits in (8, 12, 14, 15, 16):
        plan = select_mode(bits, m=8)
        print(f"  w={bits:2d} -> {plan.mode.value:5s} "
              f"({plan.passes} tile passes, roof {4/max(plan.passes,1):.2f}x"
              f" conventional)" if bits > 8 else
              f"  w={bits:2d} -> {plan.mode.value:5s} (1 tile pass)")

    # --- 3. Pallas MXU kernel (fixed-precision KMM architecture, Fig. 8) ----
    a = rng.integers(-2**11, 2**11, (128, 256)).astype(np.int32)
    b = rng.integers(-2**11, 2**11, (256, 128)).astype(np.int32)
    out = np.asarray(int_gemm(jnp.array(a), jnp.array(b), w=12,
                              backend="pallas"))
    ref = ref_int_gemm_i64(a, b).astype(np.float64)
    print(f"Pallas fused_gemm (kmm2): max rel err "
          f"{np.abs(out-ref).max()/np.abs(ref).max():.2e}")

    # --- 4. the paper's cost models ------------------------------------------
    d = 64
    print(f"arithmetic ops (d={d}, n=2): MM {mm_arith(2, d):.3g}, "
          f"KSMM {ksmm_arith(2, d):.3g}, KMM {kmm_arith(2, d):.3g}")
    for width in (16, 32, 64):
        eff = au_efficiency_vs_mm1("kmm", width).relative
        print(f"  AU efficiency vs MM1 @ w={width}: {eff:.2f}x")


if __name__ == "__main__":
    main()
