"""Paged KV / recurrent-state pool + prefix sharing for the serve engine.

The engine's decode cache is not a dense ``(n_periods, B, ...)`` pytree any
more: every leaf lives in a *pool* with a row dimension at axis 1, and the
mapping from decode slots to pool rows is data, not layout:

  * attention K/V leaves are split into fixed-size **pages** of
    ``page_size`` tokens: pool layout ``(n_periods, n_pages, page, K*D)``,
    slot -> pages through a ``(n_slots, pages_per_slot)`` int32 page table.
  * recurrent leaves (mamba conv/ssm, rwkv shift/wkv) are a single state
    row per slot: pool layout ``(n_periods, n_state_rows, ...)``, slot ->
    row through a ``(n_slots,)`` int32 state table.

The tables are jit-visible arrays: the executor's gather/scatter jits take
them as device operands, so repointing a slot at different pages never
retraces.  Beyond the per-slot rows the pool keeps

  * a **snapshot region** (``snapshot_slots`` extra slots' worth of pages
    and state rows) backing :class:`PrefixCache` prompt-prefix snapshots,
    allocated/freed through explicit free lists, and
  * one **parking** row set: decode lanes padding a bucketed batch beyond
    the free-slot supply gather from (and scatter garbage into) the parking
    rows, so padded lanes can never corrupt a live slot or a snapshot.

Prefix sharing is copy-on-reference: a snapshot stores a *copy* of the
slot's first ``L / page_size`` pages plus its recurrent state row captured
exactly at position ``L`` (a chunk boundary, so the state is bit-exact),
and a hit copies the snapshot back into the new slot's rows before prefill
resumes at offset ``L``.  Hit == cold holds bitwise because chunked prefill
itself is bit-exact (models/lm.py ``start=`` contract).

Under a mesh the pools are placed with
:func:`repro.dist.sharding.page_pool_sharding` (pages/state rows over the
data axes, kv-heads / inner dims over ``model``) and the copy/zero jits pin
their outputs to the same sharding, so pool state never ping-pongs layouts.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist import sharding as dist_sharding
from repro.models import lm
from repro.obs import metrics as obs_metrics

Params = Any

# Prefix-cache traffic (host-side; PrefixCache also keeps its own
# hits/misses ints for stats() — the counter is the scrapeable form).
_PREFIX_EVENTS = obs_metrics.counter(
    "repro_serve_prefix_cache_total",
    "prompt-prefix cache events (hit/miss/store/evict)",
    labels=("event",))

# Cache leaves that carry a per-token Smax axis and therefore page.
PAGED_LEAVES = ("k", "v")


def is_paged_leaf(path) -> bool:
    """True when a cache/pool pytree path names a paged (attn K/V) leaf."""
    return dist_sharding._path_names(path)[-1] in PAGED_LEAVES


def default_page_size(max_seq: int, preferred: int = 64) -> int:
    """Largest power of two <= ``preferred`` dividing ``max_seq``."""
    p = preferred
    while p > 1 and max_seq % p:
        p //= 2
    return p


class PagedCachePool:
    """Fixed-size page / state-row pools plus slot tables and free lists."""

    def __init__(self, cfg, n_slots: int, max_seq: int, page_size: int, *,
                 snapshot_slots: int = 0, mesh=None):
        if max_seq % page_size:
            raise ValueError(f"page_size={page_size} must divide "
                             f"max_seq={max_seq}")
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.page_size = page_size
        self.pages_per_slot = pps = max_seq // page_size
        self.mesh = mesh

        # +1 slot's worth of parking rows (padded decode lanes land there).
        n_pages = (n_slots + snapshot_slots + 1) * pps
        n_states = n_slots + snapshot_slots + 1
        if mesh is not None:
            d = 1
            for a in dist_sharding.data_axes(mesh):
                d *= dist_sharding.mesh_axis_size(mesh, a)
            n_pages = -(-n_pages // d) * d     # divisible: pages shard evenly
            n_states = -(-n_states // d) * d
        self.n_pages = n_pages
        self.n_states = n_states

        shapes = jax.eval_shape(lambda: lm.init_cache(cfg, 1, max_seq))

        def pool_leaf(path, leaf):
            if is_paged_leaf(path):
                # (n_periods, 1, Smax, K, D) -> (n_periods, P, page, K, D)
                assert leaf.shape[2] == max_seq, leaf.shape
                shape = (leaf.shape[0], n_pages, page_size) + leaf.shape[3:]
            else:
                shape = (leaf.shape[0], n_states) + leaf.shape[2:]
            return jnp.zeros(shape, leaf.dtype)

        self.pools = jax.tree_util.tree_map_with_path(pool_leaf, shapes)
        self.sharding = None
        if mesh is not None:
            self.sharding = dist_sharding.page_pool_sharding(
                jax.eval_shape(lambda: self.pools), mesh)
            self.pools = jax.tree.map(jax.device_put, self.pools,
                                      self.sharding)

        # Slot rows are fixed for the engine's lifetime; the snapshot region
        # cycles through the free lists.
        self.page_table = np.empty((n_slots, pps), np.int32)
        free_pages = list(range(n_pages))
        for i in range(n_slots):
            self.page_table[i] = [free_pages.pop(0) for _ in range(pps)]
        self.parking_pages = np.array(
            [free_pages.pop(0) for _ in range(pps)], np.int32)
        self.state_table = np.arange(n_slots, dtype=np.int32)
        free_states = list(range(n_slots, n_states))
        self.parking_state = free_states.pop(0)
        self._free_pages: List[int] = free_pages
        self._free_states: List[int] = free_states

        out_sh = self.sharding
        self._zero = jax.jit(self._zero_impl, donate_argnums=(0,),
                             out_shardings=out_sh)
        self._copy = jax.jit(self._copy_impl, donate_argnums=(0,),
                             out_shardings=out_sh)

    # -- jitted pool ops ----------------------------------------------------

    @staticmethod
    def _zero_impl(pools, state_row):
        """Zero one state row across all recurrent pools (slot (re)init)."""
        def leaf(path, pool):
            if is_paged_leaf(path):
                return pool    # stale K/V is masked, never zeroed
            return pool.at[:, state_row].set(
                jnp.zeros(pool.shape[2:], pool.dtype))
        return jax.tree_util.tree_map_with_path(leaf, pools)

    @staticmethod
    def _copy_impl(pools, src_pages, dst_pages, src_state, dst_state):
        """Copy page rows + one state row (snapshot take / restore)."""
        def leaf(path, pool):
            if is_paged_leaf(path):
                return pool.at[:, dst_pages].set(pool[:, src_pages])
            return pool.at[:, dst_state].set(pool[:, src_state])
        return jax.tree_util.tree_map_with_path(leaf, pools)

    # -- host-side API ------------------------------------------------------

    def zero_slot_state(self, slot: int):
        self.pools = self._zero(self.pools,
                                jnp.int32(self.state_table[slot]))

    def _copy_rows(self, src_pages, dst_pages, src_state, dst_state):
        self.pools = self._copy(
            self.pools, jnp.asarray(src_pages, jnp.int32),
            jnp.asarray(dst_pages, jnp.int32), jnp.int32(src_state),
            jnp.int32(dst_state))

    def take_snapshot(self, slot: int, n_pages: int
                      ) -> Optional[Tuple[Tuple[int, ...], int]]:
        """Copy the slot's first ``n_pages`` pages + state row into freshly
        allocated snapshot rows; returns ``(page_rows, state_row)`` or None
        when the snapshot region is exhausted (caller evicts and retries)."""
        if len(self._free_pages) < n_pages or not self._free_states:
            return None
        rows = tuple(self._free_pages.pop(0) for _ in range(n_pages))
        srow = self._free_states.pop(0)
        self._copy_rows(self.page_table[slot, :n_pages], rows,
                        self.state_table[slot], srow)
        return rows, srow

    def restore_snapshot(self, slot: int, handle: Tuple[Tuple[int, ...], int]):
        """Copy-on-reference: snapshot rows -> the slot's own rows."""
        rows, srow = handle
        self._copy_rows(rows, self.page_table[slot, :len(rows)], srow,
                        self.state_table[slot])

    def release_snapshot(self, handle: Tuple[Tuple[int, ...], int]):
        rows, srow = handle
        self._free_pages.extend(rows)
        self._free_states.append(srow)

    @property
    def n_free_pages(self) -> int:
        return len(self._free_pages)

    @property
    def n_free_states(self) -> int:
        return len(self._free_states)

    def lane_rows(self, lane_slots: Sequence[Optional[int]]
                  ) -> Tuple[np.ndarray, np.ndarray]:
        """(page_rows (W, pps), state_rows (W,)) for a decode/prefill lane
        list; ``None`` entries map to the parking rows."""
        prows = np.stack([self.page_table[i] if i is not None
                          else self.parking_pages for i in lane_slots])
        srows = np.array([self.state_table[i] if i is not None
                          else self.parking_state for i in lane_slots],
                         np.int32)
        return prows, srows


class PrefixCache:
    """LRU prompt-prefix snapshots over a :class:`PagedCachePool`.

    Keys are ``tuple(prompt[:L])`` with ``L`` a multiple of ``align``
    (lcm of page size and prefill chunk, so snapshots sit on both a page
    and a chunk boundary and the recurrent state is captured bit-exactly).
    """

    def __init__(self, pool: PagedCachePool, align: int,
                 max_entries: int = 16):
        self.pool = pool
        self.align = align
        self.max_entries = max_entries
        self._entries: "OrderedDict[Tuple[int, ...], Tuple" \
            "[Tuple[Tuple[int, ...], int], int]]" = OrderedDict()
        self.hits = 0
        self.misses = 0

    def boundary_for(self, prompt_len: int) -> int:
        """Longest snapshot boundary usable for this prompt (0: none).
        At least one token must remain to prefill (the first sampled token
        comes from the prefill logits), hence ``<= prompt_len - 1``."""
        return ((prompt_len - 1) // self.align) * self.align \
            if prompt_len > self.align else 0

    def lookup(self, prompt: Sequence[int]) -> Tuple[int, bool]:
        """Longest cached prefix of ``prompt``; restores nothing itself.
        Returns ``(L, hit)`` with ``L == 0`` on a miss."""
        L = self.boundary_for(len(prompt))
        while L > 0:
            key = tuple(prompt[:L])
            if key in self._entries:
                self._entries.move_to_end(key)
                self.hits += 1
                _PREFIX_EVENTS.inc("hit")
                return L, True
            L -= self.align
        self.misses += 1
        _PREFIX_EVENTS.inc("miss")
        return 0, False

    def restore(self, slot: int, prompt: Sequence[int], L: int):
        handle, _ = self._entries[tuple(prompt[:L])]
        self.pool.restore_snapshot(slot, handle)

    def store(self, slot: int, prompt: Sequence[int], L: int):
        """Snapshot the slot's first ``L`` positions (L page- and
        chunk-aligned; the slot's prefill must sit exactly at offset L)."""
        key = tuple(prompt[:L])
        if L == 0 or key in self._entries:
            return
        n_pages = L // self.pool.page_size
        handle = self.pool.take_snapshot(slot, n_pages)
        while handle is None and self._entries:
            _, (old, _) = self._entries.popitem(last=False)   # LRU evict
            self.pool.release_snapshot(old)
            _PREFIX_EVENTS.inc("evict")
            handle = self.pool.take_snapshot(slot, n_pages)
        if handle is None:
            return
        self._entries[key] = (handle, L)
        _PREFIX_EVENTS.inc("store")
        while len(self._entries) > self.max_entries:
            _, (old, _) = self._entries.popitem(last=False)
            self.pool.release_snapshot(old)
            _PREFIX_EVENTS.inc("evict")

    def __len__(self) -> int:
        return len(self._entries)

    def stats(self) -> Dict[str, int]:
        return {"entries": len(self._entries), "hits": self.hits,
                "misses": self.misses}
