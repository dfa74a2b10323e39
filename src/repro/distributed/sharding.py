"""Logical-axis sharding rules for every model family (DESIGN.md §7).

Physical mesh axes (launch/mesh.py):
  * ``pod``   — inter-pod axis (multi-pod only). Pure DP: inter-pod links are
                the scarce resource, so only gradient all-reduce crosses pods.
  * ``data``  — intra-pod batch axis; also hosts ZeRO/FSDP weight sharding
                for the MoE giants.
  * ``model`` — tensor/expert/sequence-parallel axis.

Every model declares its params/inputs with *logical* axis names; the rules
below map them to physical mesh axes via PartitionSpec. ``logical_to_spec``
drops axes that aren't present in the mesh (so the same rules serve the
single-pod (data, model) and multi-pod (pod, data, model) meshes, and the
1-device CPU test mesh where everything collapses to replicated).
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Union[None, str, Tuple[str, ...]]

# ---------------------------------------------------------------- rule sets
# logical axis name -> physical mesh axis (or tuple of axes)
#
# LM (dense + MoE):
#   batch      : (pod, data)        activations' batch dim
#   seq        : model              sequence parallelism for long decode KV
#   embed      : None               d_model stays replicated (TP gathers on it)
#   heads      : model              attention-head TP
#   kv_heads   : model              KV heads (GQA; replicated if < axis size)
#   ffn        : model              FFN inner dim TP
#   vocab      : model              embedding/unembedding TP
#   expert     : model              expert parallelism
#   expert_ffn : data               2nd weight-shard axis for MoE giants (FSDP)
#   layers     : None               stacked-scan leading axis
#
# RecSys:
#   rows       : model              embedding-table row sharding
#   batch      : (pod, data)
#   candidates : model              retrieval candidate matrix
#
# GNN:
#   nodes/edges: (pod, data)        edge-cut partitioning
LM_RULES: Dict[str, Axis] = {
    "batch": ("pod", "data"),
    "seq": None,
    "kv_seq": "model",
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "ffn": "model",
    "vocab": "model",
    "expert": "model",
    "expert_ffn": "data",
    "layers": None,
    "pos": None,
}

RECSYS_RULES: Dict[str, Axis] = {
    "batch": ("pod", "data"),
    "rows": "model",
    "embed": None,
    "ffn": "model",
    "seq": None,
    "heads": None,
    "candidates": ("data", "model"),
    "fields": None,
    "interests": None,
}

GNN_RULES: Dict[str, Axis] = {
    "nodes": ("pod", "data"),
    "edges": ("pod", "data"),
    "batch": ("pod", "data"),
    "feat": None,
    "hidden": None,
    "layers": None,
}

RULES_BY_FAMILY = {"lm": LM_RULES, "recsys": RECSYS_RULES, "gnn": GNN_RULES}


def logical_to_spec(logical: Sequence[Optional[str]],
                    rules: Dict[str, Axis],
                    mesh_axes: Sequence[str]) -> P:
    """Map logical axis names to a PartitionSpec valid on ``mesh_axes``.

    Logical axes missing from the rules (or mapping to mesh axes that don't
    exist, e.g. ``pod`` on the single-pod mesh) become None (replicated).
    A mesh axis is consumed at most once per spec (GSPMD requirement).
    """
    used = set()
    out = []
    for name in logical:
        phys = rules.get(name) if name else None
        if phys is None:
            out.append(None)
            continue
        cand = phys if isinstance(phys, tuple) else (phys,)
        keep = tuple(a for a in cand if a in mesh_axes and a not in used)
        used.update(keep)
        if not keep:
            out.append(None)
        elif len(keep) == 1:
            out.append(keep[0])
        else:
            out.append(keep)
    return P(*out)


def tree_spec(logical_tree, family: str, mesh: Mesh):
    """Map a pytree of logical-axis tuples to a pytree of PartitionSpecs."""
    rules = RULES_BY_FAMILY[family]
    axes = mesh.axis_names
    return jax.tree_util.tree_map(
        lambda lg: logical_to_spec(lg, rules, axes),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x),
    )


def tree_sharding(logical_tree, family: str, mesh: Mesh):
    """Same as tree_spec but returns NamedShardings for jit in_shardings."""
    specs = tree_spec(logical_tree, family, mesh)
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def divisible_or_replicate(spec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Drop spec entries whose mesh-axis product doesn't divide the dim.

    GSPMD requires sharded dims to be divisible by the axis size; configs
    with e.g. 56 heads on a 16-way model axis fall back to replicated for
    that dim (and the roofline then shows the cost, which is the point).
    """
    out = []
    for dim, entry in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        out.append(entry if dim % size == 0 else None)
    return P(*out)


def constrain(x, logical: Sequence[Optional[str]], family: str,
              mesh: Optional[Mesh] = None):
    """with_sharding_constraint by logical names; no-op when mesh is None
    (single-device tests run the same code path un-annotated)."""
    if mesh is None:
        return x
    rules = RULES_BY_FAMILY[family]
    spec = logical_to_spec(logical, rules, mesh.axis_names)
    spec = divisible_or_replicate(spec, x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ----------------------------------------------------- cache-tier placement
# The ERCache serving tier shards its cache tables along the BUCKET axis
# (DESIGN.md §11): device s of the 1-D ("shard",) mesh owns the contiguous
# bucket range [s*nb/S, (s+1)*nb/S) of every table. The write/touch rings
# and the admission token bucket stay replicated — they are O(buffer), not
# O(capacity), and every shard needs the full ring to route from.

def validate_cache_sharding(mesh: Mesh, n_buckets_list) -> int:
    """Check a cache-tier mesh: 1-D ``shard`` axis whose size divides every
    tier's bucket count. Returns the shard count."""
    from repro.core import cache as cache_lib
    from repro.distributed import collectives as coll

    if coll.SHARD_AXIS not in mesh.axis_names:
        raise ValueError(
            f"cache-tier mesh needs a '{coll.SHARD_AXIS}' axis, got "
            f"{mesh.axis_names}")
    n_shards = mesh.shape[coll.SHARD_AXIS]
    for nb in n_buckets_list:
        cache_lib.shard_local_buckets(nb, n_shards)  # raises on indivisible
    return n_shards


def _server_state_shardings(state, mesh: Mesh):
    """Cache tables sharded along their bucket axis (the value planes
    along their entry axis), everything else
    (rings, budget) replicated — as a tree of NamedShardings."""
    from repro.distributed import collectives as coll

    validate_cache_sharding(
        mesh, {state.direct.n_buckets, state.failover.n_buckets})

    def spec(tree, pspec):
        sh = NamedSharding(mesh, pspec)
        return jax.tree_util.tree_map(lambda _: sh, tree)

    def tables(tier):
        return jax.tree_util.tree_map(lambda p: NamedSharding(mesh, p),
                                      coll.cache_pspec(tier),
                                      is_leaf=lambda x: isinstance(x, P))

    return state._replace(
        direct=tables(state.direct),
        failover=tables(state.failover),
        writebuf=spec(state.writebuf, P()),
        touchbuf=spec(state.touchbuf, P()),
        budget=spec(state.budget, P()),
    )


def place_server_state(state, mesh: Mesh):
    """Device-put a ServerState/MultiServerState for the bucket-sharded
    tier. Idempotent — placing an already-placed state is a no-op
    resharding."""
    return jax.tree_util.tree_map(jax.device_put, state,
                                  _server_state_shardings(state, mesh))


def init_placed_server_state(build, mesh: Mesh):
    """``build()`` — a ServerState/MultiServerState constructor — run
    straight into the bucket-sharded placement: each device allocates only
    its own shard of the tables, never the whole tier first."""
    shardings = _server_state_shardings(jax.eval_shape(build), mesh)
    return jax.jit(build, out_shardings=shardings)()
