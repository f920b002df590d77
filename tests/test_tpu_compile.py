"""Compile the serving path's Pallas kernels for a TPU v5e that is described,
not attached: what Mosaic refuses fails here, before any chip run.

Interpret-mode tests cannot see these failures (an int16 vector shift, a
VMEM block of the wrong tiling).  The topology is described inside a
module-scoped fixture, never while a module is imported: only the worker
that runs these tests loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fused_gemm import (dot_path, fused_gemm,
                                     fused_gemm_grouped, int8_tiles)
from repro.quant.qmatmul import _fused_mode, _fused_plan_for

# llama3.2-1b serve GEMMs: decode (4 slots) FFN up-projection, and a
# 512-token prefill of the attention output projection.
DECODE = (4, 2048, 8192)
PREFILL = (512, 2048, 2048)
# stablelm-12b w12 serve GEMMs (M, K, N): 4096- and 2048-token prefill of
# the MLP, a 512-token prefill of a KV projection, a 16-lane decode of the
# MLP and an 8-lane decode of the untied head.
STABLELM = [(4096, 5120, 13824), (2048, 13824, 5120), (512, 5120, 1280),
            (16, 5120, 13824), (8, 5120, 100352)]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, args, sharding) -> str:
    specs = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
             for shape, dtype in args]
    return jax.jit(fn).lower(*specs).compile().as_text()


@pytest.mark.parametrize("shape", [DECODE, PREFILL], ids=["decode", "prefill"])
@pytest.mark.parametrize("w", [8, 12, 16, 20])
def test_fused_serve_plan_compiles_for_v5e(one_chip, w, shape):
    """The plan ``_fused_plan_for`` picks, with the dequant epilogue the
    quantized matmul threads in: mm1 (w8), kmm2 (w12), mm2 (w16) and kmm4
    (w20)."""
    plan = _fused_plan_for(shape, w, 8, None)
    assert plan is not None and plan.variant in ("fused", "fused_mm2")
    m, k, n = shape

    def gemm(a, b, sx, sw):
        return fused_gemm(a, b, sx, sw, w=w, mode=_fused_mode(plan),
                          block_m=plan.block_m, block_n=plan.block_n,
                          block_k=plan.block_k,
                          combine_int32=plan.combine_int32,
                          out_dtype=jnp.float32, interpret=False)

    text = _compiled_text(gemm, [((m, k), jnp.int32), ((k, n), jnp.int32),
                                 ((m, 1), jnp.float32), ((1, n), jnp.float32)],
                          one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("shape", STABLELM, ids=lambda s: "x".join(map(str, s)))
def test_int8_tiles_compile_for_v5e(one_chip, shape):
    """The int8 digit-dot path at the tiles its rule picks fits v5e's scoped
    VMEM (up to (512, 512, 512); (512, 1024, 512) overflows it)."""
    plan = _fused_plan_for(shape, 12, 8, None)
    assert plan.tiles == int8_tiles(shape, "kmm2", 12)
    assert dot_path("kmm2", 12, plan.block_k, interpret=False) == "int8"
    m, k, n = shape

    def gemm(a, b, sx, sw):
        return fused_gemm(a, b, sx, sw, w=12, block_m=plan.block_m,
                          block_n=plan.block_n, block_k=plan.block_k,
                          out_dtype=jnp.bfloat16, interpret=False)

    text = _compiled_text(gemm, [((m, k), jnp.int16), ((k, n), jnp.int16),
                                 ((m, 1), jnp.float32), ((1, n), jnp.float32)],
                          one_chip)
    assert "tpu_custom_call" in text


def test_ragged_grouped_compiles_for_v5e(one_chip):
    """granite-moe-3b-a800m expert GEMM (40 experts, d_model 1536,
    d_ff_expert 512) on the ragged grouped kernel at w12: per-expert live
    counts are read as scalars from SMEM."""
    e, c, k, n, seg = 40, 64, 1536, 512, 16
    plan = _fused_plan_for((c, k, n), 12, 8, None)

    def gemm(a, b, sx, sw, counts):
        return fused_gemm_grouped(
            a, b, sx, sw, counts, w=12, seg=seg, block_m=plan.block_m,
            block_n=plan.block_n, block_k=plan.block_k,
            out_dtype=jnp.float32, interpret=False)

    text = _compiled_text(gemm, [((e, c, k), jnp.int32),
                                 ((e, k, n), jnp.int32),
                                 ((e, c, 1), jnp.float32),
                                 ((e, 1, n), jnp.float32),
                                 ((e, c // seg), jnp.int32)], one_chip)
    assert "tpu_custom_call" in text
