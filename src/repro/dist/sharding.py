"""Sharding rules: logical-axis partitioning for params, caches and batches.

MaxText-style two-level mapping (DESIGN.md §4.1): each weight leaf gets
*logical* axes from its name (``wi -> ("embed", "mlp")``), and a rules table
maps logical axes onto mesh axes (``"mlp" -> "model"``, ``"embed" ->
"data"`` i.e. FSDP).  A mesh axis is only assigned when the dimension is
divisible by it and the axis is not already used by the same spec, so the
rules degrade gracefully on small smoke configs and 1-device meshes.

Block params carry a leading ``n_periods`` stacking dim (and MoE weights an
expert dim); rules apply to the trailing matmul dims, the expert dim rides
the ``model`` axis (expert parallelism), and stacking dims stay replicated.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
from jax.sharding import AbstractMesh, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

Params = Any

# Mesh axes that carry the (global) batch dimension, in mesh order.
BATCH_AXES = ("pod", "data")

# Logical axis -> mesh axes it may map onto (first fit wins).
LOGICAL_RULES: Tuple[Tuple[str, Tuple[str, ...]], ...] = (
    ("batch", ("pod", "data")),
    ("embed", ("data",)),        # FSDP: hidden dim sharded over data
    ("vocab", ("model",)),       # vocab-parallel embedding / head
    ("heads", ("model",)),       # tensor parallel: attention heads
    ("mlp", ("model",)),         # tensor parallel: FFN hidden
    ("inner", ("model",)),       # tensor parallel: SSM inner dim
    ("expert", ("model",)),      # expert parallelism
    ("stack", ()),               # n_periods scan stacking: replicated
)

# Weight-leaf name -> logical axes of the *trailing* dims.  ``None`` entries
# are replicated.  Names not listed fall back to ("embed", "heads") for
# trailing-2D leaves (row FSDP, column TP) and full replication otherwise.
PARAM_LOGICAL_AXES = {
    "embed": ("vocab", "embed"),
    "lm_head": ("embed", "vocab"),
    "wq": ("embed", "heads"),
    "wk": ("embed", "heads"),
    "wv": ("embed", "heads"),
    "wg": ("embed", "mlp"),
    "wi": ("embed", "mlp"),
    "wr": ("embed", "heads"),
    "wo": ("heads", "embed"),      # output proj: row TP, column FSDP
    "out_proj": ("inner", "embed"),
    "in_proj": ("embed", "inner"),
    "x_proj": ("inner", None),
    "dt_proj": (None, "inner"),
    "w1": ("embed", "mlp"),
    "w2": ("embed", "embed"),
    "router": ("embed", None),
}

# Small / vector leaves that always stay replicated.
NEVER_SHARD = {
    "scale", "bias", "mix", "u", "w0", "a_log", "d_skip", "dt_bias",
    "conv_w", "conv_b", "w_lora_a", "w_lora_b",
}


def _key_name(entry) -> str:
    return str(getattr(entry, "key", getattr(entry, "name", entry)))


def _path_names(path) -> Tuple[str, ...]:
    return tuple(_key_name(k) for k in path)


def mesh_axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape[axis] if axis in mesh.axis_names else 1


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Mesh axes carrying the batch dim, in mesh order."""
    return tuple(a for a in mesh.axis_names if a in BATCH_AXES)


def batch_spec(mesh: Mesh) -> P:
    """PartitionSpec for a batch-leading array: dim 0 over all data axes.

    Returns an empty spec (``len() == 0``) when the mesh has no data axes,
    so callers can fall back to replication.
    """
    axes = data_axes(mesh)
    if not axes:
        return P()
    return P(axes if len(axes) > 1 else axes[0])


def _mesh_axes_for(logical: Optional[str], dim: int, mesh: Mesh,
                   used: set) -> Optional[str]:
    """First mesh axis for ``logical`` that divides ``dim`` and is unused."""
    if logical is None:
        return None
    for name, axes in LOGICAL_RULES:
        if name != logical:
            continue
        for ax in axes:
            size = mesh_axis_size(mesh, ax)
            if size > 1 and dim % size == 0 and ax not in used:
                used.add(ax)
                return ax
        return None
    return None


def leaf_spec(path, leaf, mesh: Mesh) -> P:
    names = _path_names(path)
    name = names[-1]
    # Pre-quantized leaves ({"q": intN, "scale": ...}): the rule lives on
    # the parent weight name; scales are tiny and stay replicated.
    if name == "q" and len(names) >= 2:
        name = names[-2]
    shape = tuple(leaf.shape)
    ndim = len(shape)
    if name in NEVER_SHARD or ndim < 2:
        return P()
    logical = PARAM_LOGICAL_AXES.get(name)
    if logical is None:
        logical = ("embed", "heads")   # generic (K, N): row FSDP, col TP
    spec = [None] * ndim
    used: set = set()
    # An expert dim (MoE: the dim right before the matmul dims, under a
    # "moe" subtree) claims the model axis first — expert parallelism wins
    # over tensor parallelism inside an expert (see models/moe.py).
    if "moe" in names and ndim - len(logical) - 1 >= 0:
        e_idx = ndim - len(logical) - 1
        spec[e_idx] = _mesh_axes_for("expert", shape[e_idx], mesh, used)
    # Trailing dims get the logical rule (matmul layout).
    for off, lax_name in enumerate(reversed(logical)):
        dim_idx = ndim - 1 - off
        if dim_idx < 0:
            break
        spec[dim_idx] = _mesh_axes_for(lax_name, shape[dim_idx], mesh, used)
    return P(*spec)


def param_sharding(params: Params, mesh: Mesh) -> Params:
    """NamedSharding pytree for a param tree (concrete or ShapeDtypeStruct).

    2D weights are sharded on at least one mesh axis whenever divisibility
    permits: column/TP dims over ``model``, row dims over ``data`` (FSDP),
    vocab over ``model``.
    """
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, leaf_spec(path, leaf, mesh)),
        params)


# Cache-leaf name -> axis index (within the (n_periods, slot, ...) layout)
# that may shard over ``model``: attention kv-heads, rwkv heads, mamba inner.
CACHE_MODEL_AXES = {
    "k": 3,       # attn (n_periods, slot, Smax, K*D): kv-heads x head dim
    "v": 3,
    "wkv": 2,     # rwkv (n_periods, slot, H, D, D): heads
    "ssm": 2,     # mamba (n_periods, slot, d_inner, d_state): inner dim
    "conv": 3,    # mamba (n_periods, slot, cw-1, d_inner): inner dim
}


def cache_sharding(cache_shapes: Params, mesh: Mesh, *,
                   batch: int) -> Params:
    """NamedSharding pytree for a decode cache.

    Cache leaves are laid out ``(n_periods, slot, ...)``: axis 1 is the
    serve engine's decode-slot dimension (== the request batch), sharded
    directly over the mesh's data axes.  Per-leaf model
    parallelism: attention K/V shard their kv-heads dim, rwkv its head dim
    and mamba its inner dim over ``model`` (see ``CACHE_MODEL_AXES``), so
    decode stays head-/channel-parallel without resharding the weights.
    ``batch`` is the slot count (sanity-checked against axis 1).
    """
    daxes = data_axes(mesh)
    dsize = 1
    for a in daxes:
        dsize *= mesh_axis_size(mesh, a)
    bentry = (daxes if len(daxes) > 1 else daxes[0]) if daxes else None
    msize = mesh_axis_size(mesh, "model")

    def leaf_sharding(path, leaf):
        shape = tuple(leaf.shape)
        spec = [None] * len(shape)
        # axis 1 is the slot dim in the (n_periods, slot, ...) layout
        if len(shape) >= 2 and shape[1] == batch:
            if bentry is not None and dsize > 1 and shape[1] % dsize == 0:
                spec[1] = bentry
        name = _path_names(path)[-1]
        m_axis = CACHE_MODEL_AXES.get(name)
        if m_axis is not None and m_axis < len(shape) and msize > 1 \
                and shape[m_axis] % msize == 0:
            spec[m_axis] = "model"
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(leaf_sharding, cache_shapes)


def page_pool_sharding(pool_shapes: Params, mesh: Mesh) -> Params:
    """NamedSharding pytree for a paged serve-cache pool (serve/cache.py).

    Pool leaves are laid out ``(n_periods, page_or_state_row, ...)``: axis 1
    is the page (attn K/V pools) or state-row (recurrent pools) dimension,
    sharded over the mesh's data axes when divisible — the pool analogue of
    the slot dim in :func:`cache_sharding`.  The per-leaf model axes are
    unchanged from ``CACHE_MODEL_AXES``: swapping the slot dim for a
    page/state-row dim (and, for K/V, splitting Smax into (page_row, page))
    keeps the kv-head / rwkv-head / mamba-inner payload dims at the same
    indices, so the same table applies.
    """
    daxes = data_axes(mesh)
    dsize = 1
    for a in daxes:
        dsize *= mesh_axis_size(mesh, a)
    bentry = (daxes if len(daxes) > 1 else daxes[0]) if daxes else None
    msize = mesh_axis_size(mesh, "model")

    def leaf_sharding(path, leaf):
        shape = tuple(leaf.shape)
        spec = [None] * len(shape)
        if len(shape) >= 2 and bentry is not None and dsize > 1 \
                and shape[1] % dsize == 0:
            spec[1] = bentry
        name = _path_names(path)[-1]
        m_axis = CACHE_MODEL_AXES.get(name)
        if m_axis is not None and m_axis < len(shape) and msize > 1 \
                and shape[m_axis] % msize == 0:
            spec[m_axis] = "model"
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(leaf_sharding, pool_shapes)


def _ambient_mesh() -> Optional[AbstractMesh]:
    """The mesh set by ``jax.set_mesh`` around the current trace, if any."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def constrain_batch_dim(x: jax.Array) -> jax.Array:
    """Keep an activation's leading (batch) dim sharded over the data axes.

    No-op outside a mesh context (single-device tests, plain eager calls),
    so model code can call it unconditionally.
    """
    if x is None:
        return x
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    axes = data_axes(mesh)
    if not axes or x.ndim == 0:
        return x
    spec = P(*((axes if len(axes) > 1 else axes[0],)
               + (None,) * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
