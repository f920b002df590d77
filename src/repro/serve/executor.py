"""Jit layer of the serve engine: paged-pool gather/compute/scatter.

The executor owns the compiled entry points the engine steps through:

  * ``decode``  — gather the lane slots' pages/state rows into a dense
    ``(n_periods, W, ...)`` cache, run :func:`repro.models.lm.decode_step`,
    scatter the lanes back.  One trace per decode-bucket width ``W``
    (shape-keyed jit cache); the pool pytree is donated every call so the
    cache state never copies.
  * ``prefill`` — same gather/scatter around a resume-from-offset
    :func:`repro.models.lm.prefill` call (``start=`` is a traced scalar, so
    one trace covers every chunk offset of a given chunk width).
  * ``sample``  — per-(request, step) keyed sampling, vmapped over lanes.

Under a mesh the pool outputs are pinned to
:func:`repro.dist.sharding.page_pool_sharding` so GSPMD never ping-pongs
the pool layout between calls, and every call runs inside the mesh context
(the engine supplies it) so quantized GEMMs negotiate shard-mapping as in
the dense-cache engine.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import lm
from repro.obs import metrics as obs_metrics
from repro.serve.cache import PagedCachePool, is_paged_leaf

Params = Any

# Compile events per jit kind (decode/prefill/sample): tracked as deltas of
# the jit cache size after each call, only when metrics are enabled —
# steady-state decode must show zero growth (the retrace regression the
# serve bench gates on).
_RETRACES = obs_metrics.counter(
    "repro_serve_retraces_total",
    "jit (re)compiles observed by the serve executor, by kind",
    labels=("kind",))
_LANE_WIDTHS = obs_metrics.counter(
    "repro_serve_decode_lane_width_total",
    "decode calls by bucketed lane width",
    labels=("width",))


class Executor:
    """Compiled gather/compute/scatter over a :class:`PagedCachePool`."""

    def __init__(self, cfg, params: Params, pool: PagedCachePool,
                 mesh=None):
        self.cfg = cfg
        self.params = params
        self.pool = pool
        self.mesh = mesh
        pps, page, smax = pool.pages_per_slot, pool.page_size, pool.max_seq

        # named scopes mark the cache layer's copies in the HLO metadata
        # (op names and instruction names are unchanged)
        @jax.named_scope("kv_gather")
        def gather(pools, prows, srows):
            def leaf(path, pool_arr):
                if is_paged_leaf(path):
                    lanes = pool_arr[:, prows]   # (np, W, pps, page, K*D)
                    w = prows.shape[0]
                    return lanes.reshape(
                        (pool_arr.shape[0], w, pps * page)
                        + pool_arr.shape[3:])
                return pool_arr[:, srows]
            return jax.tree_util.tree_map_with_path(leaf, pools)

        @jax.named_scope("kv_scatter")
        def scatter(pools, lanes, prows, srows):
            def leaf(path, pool_arr, lane):
                if is_paged_leaf(path):
                    w = lane.shape[1]
                    lane = lane.reshape(
                        (pool_arr.shape[0], w, pps, page)
                        + pool_arr.shape[3:])
                    return pool_arr.at[:, prows].set(
                        lane.astype(pool_arr.dtype))
                return pool_arr.at[:, srows].set(lane.astype(pool_arr.dtype))
            return jax.tree_util.tree_map_with_path(leaf, pools, lanes)

        def decode_impl(p, pools, prows, srows, toks, pos):
            lanes = gather(pools, prows, srows)
            logits, lanes = lm.decode_step(p, cfg, toks, lanes, pos)
            return logits, scatter(pools, lanes, prows, srows)

        def prefill_impl(p, pools, prows, srows, toks, start, last):
            lanes = gather(pools, prows, srows)
            iota = jnp.arange(toks.shape[1], dtype=jnp.int32)[None, :]
            mask = iota <= last[:, None]
            logits, lanes, _ = lm.prefill(p, cfg, toks, lanes,
                                          pad_mask=mask, last_idx=last,
                                          start=start)
            return logits, scatter(pools, lanes, prows, srows)

        out_sh = None
        if mesh is not None:
            # Pin only the pool outputs: they are the carried state whose
            # layout must not ping-pong call to call.  Logits are fresh
            # per-call outputs — GSPMD picks their layout.
            out_sh = (None, pool.sharding)
        self._decode = jax.jit(decode_impl, donate_argnums=(1,),
                               out_shardings=out_sh)
        self._prefill = jax.jit(prefill_impl, donate_argnums=(1,),
                                out_shardings=out_sh)
        self._sample = jax.jit(self._sample_fn)
        self._seen_traces: Dict[str, int] = {}

    def _note_traces(self, kind: str, fn) -> None:
        """Count jit-cache growth since the last call of ``kind`` (metrics
        enabled only; no-op when the jax version hides cache sizes)."""
        if not obs_metrics.enabled():
            return
        get = getattr(fn, "_cache_size", None)
        if not callable(get):
            return
        n = int(get())
        prev = self._seen_traces.get(kind, 0)
        if n > prev:
            _RETRACES.inc(kind, by=n - prev)
        self._seen_traces[kind] = n

    # -- entry points (mutate pool.pools in place) --------------------------

    def decode(self, lane_slots, toks: np.ndarray, pos: np.ndarray):
        _LANE_WIDTHS.inc(len(lane_slots))
        prows, srows = self.pool.lane_rows(lane_slots)
        logits, self.pool.pools = self._decode(
            self.params, self.pool.pools, jnp.asarray(prows),
            jnp.asarray(srows), jnp.asarray(toks), jnp.asarray(pos))
        self._note_traces("decode", self._decode)
        return logits

    def prefill(self, slot: int, toks: np.ndarray, start: int,
                last: np.ndarray):
        prows, srows = self.pool.lane_rows([slot])
        logits, self.pool.pools = self._prefill(
            self.params, self.pool.pools, jnp.asarray(prows),
            jnp.asarray(srows), jnp.asarray(toks), jnp.int32(start),
            jnp.asarray(last))
        self._note_traces("prefill", self._prefill)
        return logits

    @staticmethod
    def _sample_fn(key, logits, temps, rids, steps):
        def one(lg, tmp, rid, st):
            k = jax.random.fold_in(jax.random.fold_in(key, rid), st)
            scaled = lg.astype(jnp.float32) / jnp.maximum(tmp, 1e-6)
            sampled = jax.random.categorical(k, scaled)
            return jnp.where(tmp > 0, sampled.astype(jnp.int32),
                             jnp.argmax(lg).astype(jnp.int32))

        return jax.vmap(one)(logits, temps, rids, steps)

    def sample(self, key, logits, temps, rids, steps):
        return self._sample(key, logits, jnp.asarray(temps),
                            jnp.asarray(rids), jnp.asarray(steps))

    def n_traces(self) -> Dict[str, int]:
        """Compiled-trace counts (retrace monitoring for the serve bench);
        -1 per entry if the jax version doesn't expose cache sizes.
        ``decode`` counts one trace per decode-bucket width, ``prefill``
        one per chunk/bucket width."""

        def size(fn) -> int:
            get = getattr(fn, "_cache_size", None)
            return int(get()) if callable(get) else -1

        return {
            "decode": size(self._decode),
            "prefill": size(self._prefill),
            "sample": size(self._sample),
        }
