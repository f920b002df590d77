"""Pallas TPU kernel: RWKV6 WKV recurrence with the matrix state in VMEM.

The roofline analysis (EXPERIMENTS.md §Roofline) shows rwkv6-3b train/prefill
is memory-dominated: the unfused HLO recurrence reads+writes the (B, H, D, D)
state from HBM every timestep (~9 state-sized tensors per step).  This kernel
keeps the state in a VMEM scratch accumulator across the whole sequence —
HBM traffic collapses to the r/k/v/w streams plus one state write per
(batch, head):

    traffic_unfused ~ S * 9 * D^2 * 4B        (per head)
    traffic_kernel  ~ S * 4 * D * 4B + D^2*4B

Grid: (B*H, S/chunk) with the sequence dim sequential ('arbitrary') so the
state scratch persists across chunks.  Inside a chunk, a fori_loop steps the
recurrence: S_t = diag(w_t) S_{t-1} + k_t^T v_t;  y_t = r_t (S_{t-1} + diag(u)
k_t^T v_t).  Validated against the pure-jnp oracle in interpret mode.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, state_ref, *,
                chunk: int):
    sc = pl.program_id(1)

    @pl.when(sc == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    u = u_ref[0, :]                                      # (D,)

    def step(t, state):
        rt = r_ref[0, t, :]
        kt = k_ref[0, t, :]
        vt = v_ref[0, t, :]
        wt = w_ref[0, t, :]
        kv = kt[:, None] * vt[None, :]                   # (D, D)
        yt = ((state + u[:, None] * kv) * rt[:, None]).sum(axis=0)
        y_ref[0, t, :] = yt
        return wt[:, None] * state + kv

    state_ref[...] = jax.lax.fori_loop(0, chunk, step, state_ref[...])


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv_apply(r: Array, k: Array, v: Array, w: Array, u: Array, *,
              chunk: int = 128, interpret: Optional[bool] = None) -> Array:
    """r/k/v/w: (BH, S, D) fp32 streams (flattened batch*heads);
    u: (BH, D) bonus. Returns y: (BH, S, D) fp32."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bh, s, d = r.shape
    chunk = min(chunk, s)
    while s % chunk:
        chunk //= 2
    grid = (bh, s // chunk)
    spec = pl.BlockSpec((1, chunk, d), lambda i, j: (i, j, 0))
    u_spec = pl.BlockSpec((1, d), lambda i, j: (i, 0))
    return pl.pallas_call(
        functools.partial(_wkv_kernel, chunk=chunk),
        grid=grid,
        in_specs=[spec, spec, spec, spec, u_spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((bh, s, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, w, u)


def wkv_reference(r: Array, k: Array, v: Array, w: Array, u: Array) -> Array:
    """Pure-jnp oracle: sequential scan over timesteps."""
    def step(state, xs):
        rt, kt, vt, wt = xs                              # (BH, D)
        kv = kt[..., :, None] * vt[..., None, :]
        yt = jnp.einsum("bi,bij->bj", rt, state + u[..., None] * kv)
        return wt[..., :, None] * state + kv, yt

    bh, s, d = r.shape
    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (r, k, v, w))
    state0 = jnp.zeros((bh, d, d), jnp.float32)
    _, y = jax.lax.scan(step, state0, xs)
    return jnp.moveaxis(y, 0, 1)


def hbm_traffic_model(bh: int, s: int, d: int):
    """First-order HBM bytes: unfused HLO recurrence vs this kernel."""
    unfused = bh * s * 9 * d * d * 4.0
    kernel = bh * (s * 5 * d * 4.0 + d * d * 4.0)
    return {"unfused_bytes": unfused, "kernel_bytes": kernel,
            "reduction": unfused / kernel}
