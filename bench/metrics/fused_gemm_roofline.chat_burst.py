"""The fused KMM GEMM kernel's share of its roofline in the profiled window."""
from layer import kernel_roofline_pct


def read(ctx):
    return kernel_roofline_pct(ctx)
