"""Per-(arch × shape) dry-run cells: step fn + ShapeDtypeStruct inputs +
PartitionSpecs for the production mesh.

``build_cell(arch, shape_name, mesh)`` returns everything launch/dryrun.py
needs to ``jit(...).lower(...).compile()`` a cell without allocating a byte
of model state (the shannon/kernels input-spec pattern).

Conventions:
  * Sharded-dim divisibility: GNN node/edge arrays are padded up to the next
    multiple of 512 (padding edges carry sender == -1 and are inert by the
    aggregation contract — semantically identity, see DESIGN.md §4).
  * Optimizer-state shardings are derived from the matching parameter's spec
    by shape (exact → same spec; rank-reduced Adafactor factors → the spec
    with the corresponding axis dropped).
  * MODEL_FLOPS (the "useful compute" numerator of §Roofline) is estimated
    per cell: 6·N_active·tokens for training, 2·N_active·tokens for
    inference, plus attention term; analogous counts for GNN/recsys.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import base as cfg_base
from repro.configs import get_config
from repro.distributed import sharding as shd
from repro.models import gnn as gnn_lib
from repro.models import recsys as rec_lib
from repro.models import transformer as tfm
from repro.training import optimizer as opt_lib

F32 = jnp.float32
I32 = jnp.int32


@dataclasses.dataclass
class Cell:
    arch: str
    shape_name: str
    fn: Callable
    args: Tuple[Any, ...]                 # abstract (ShapeDtypeStruct) trees
    in_specs: Tuple[Any, ...]             # matching PartitionSpec trees
    out_specs: Any = None                 # None = compiler-propagated
    donate_argnums: Tuple[int, ...] = ()
    static_argnums: Tuple[int, ...] = ()
    model_flops: float = 0.0              # useful-FLOPs numerator
    note: str = ""


def _sds(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _pad_to(n: int, mult: int = 512) -> int:
    return ((n + mult - 1) // mult) * mult


def _leaf_spec(logical, shape, family, mesh) -> P:
    spec = shd.logical_to_spec(logical, shd.RULES_BY_FAMILY[family],
                               mesh.axis_names)
    return shd.divisible_or_replicate(spec, shape, mesh)


def _tree_specs(logical_tree, abs_tree, family, mesh):
    """Zip logical axes with abstract shapes → divisibility-checked specs."""
    is_logical = lambda x: isinstance(x, tuple) and all(
        a is None or isinstance(a, str) for a in x)
    flat_l, treedef = jax.tree_util.tree_flatten(logical_tree,
                                                 is_leaf=is_logical)
    flat_a = treedef.flatten_up_to(abs_tree)
    return treedef.unflatten([
        _leaf_spec(lg, a.shape, family, mesh)
        for lg, a in zip(flat_l, flat_a)])


def _opt_state_specs(opt_state_abs, params_abs, param_specs):
    """Shape-match optimizer-state leaves to parameter specs."""
    by_shape: Dict[Tuple[int, ...], P] = {}
    for p, s in zip(jax.tree_util.tree_leaves(params_abs),
                    jax.tree_util.tree_leaves(
                        param_specs, is_leaf=lambda x: isinstance(x, P))):
        by_shape.setdefault(tuple(p.shape), s)

    def spec_of(leaf):
        shp = tuple(leaf.shape)
        if shp in by_shape:
            return by_shape[shp]
        for pshape, spec in by_shape.items():
            entries = tuple(spec) + (None,) * (len(pshape) - len(spec))
            if shp == pshape[:-1]:                    # adafactor row factor
                return P(*entries[:-1])
            if len(pshape) >= 2 and shp == pshape[:-2] + pshape[-1:]:
                return P(*(entries[:-2] + entries[-1:]))  # col factor
        return P()
    return jax.tree_util.tree_map(spec_of, opt_state_abs)


def _batch_spec(mesh) -> Any:
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return axes if len(axes) > 1 else (axes[0] if axes else None)


# ======================================================================== LM
# microbatch counts for train_4k chosen so live rematerialized activations
# (L × tokens/device/micro × D × 2B) stay ≈ 2 GB/device (DESIGN.md §7)
TRAIN_MICRO = {
    "yi-6b": 8, "llama3-8b": 8, "tinyllama-1.1b": 4,
    "arctic-480b": 16, "granite-moe-1b-a400m": 2,
}


def _lm_flops(cfg, tokens: int, train: bool, attn_s: int) -> float:
    n_active = cfg.active_param_count()
    mult = 6.0 if train else 2.0
    param_f = mult * n_active * tokens
    # causal attention matmuls: 2 (qk+pv) × 2 flops/MAC × S/2 avg context
    attn_f = (3.0 if train else 1.0) * cfg.n_layers * tokens \
        * 4.0 * cfg.n_heads * cfg.hd * attn_s
    return param_f + attn_f


def _decode_flops(cfg, batch: int, s: int) -> float:
    n_active = cfg.active_param_count()
    return 2.0 * n_active * batch \
        + cfg.n_layers * batch * 4.0 * cfg.n_heads * cfg.hd * s


def _build_lm_cell(arch: str, shape: cfg_base.LMShape, mesh: Mesh,
                   overrides: Optional[dict] = None) -> Cell:
    cfg = get_config(arch)
    overrides = dict(overrides or {})
    global_batch = overrides.pop("global_batch", shape.global_batch)
    if shape.kind == "train":
        cfg = dataclasses.replace(cfg, microbatches=TRAIN_MICRO[arch])
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = dataclasses.replace(shape, global_batch=global_batch)
    bspec = _batch_spec(mesh)
    params_abs = tfm.abstract_params(cfg)
    param_specs = _tree_specs(tfm.param_logical_axes(cfg), params_abs,
                              "lm", mesh)
    B, S = shape.global_batch, shape.seq_len

    if shape.kind == "train":
        opt = opt_lib.for_config(cfg)
        opt_abs = jax.eval_shape(opt.init, params_abs)
        opt_specs = _opt_state_specs(opt_abs, params_abs, param_specs)
        state_abs = tfm.TrainState(params=params_abs, opt_state=opt_abs,
                                   step=_sds((), I32))
        state_specs = tfm.TrainState(params=param_specs,
                                     opt_state=opt_specs, step=P())
        batch_abs = {"tokens": _sds((B, S), I32),
                     "labels": _sds((B, S), I32)}
        batch_specs = {"tokens": P(bspec, None), "labels": P(bspec, None)}
        step = tfm.make_train_step(cfg, opt, mesh)
        return Cell(
            arch=arch, shape_name=shape.name, fn=step,
            args=(state_abs, batch_abs),
            in_specs=(state_specs, batch_specs),
            out_specs=(state_specs, None),
            donate_argnums=(0,),
            model_flops=_lm_flops(cfg, B * S, True, S // 2),
            note=f"microbatches={cfg.microbatches}")

    if shape.kind == "prefill":
        def fn(params, tokens):
            return tfm.prefill_step(params, tokens, cfg, mesh)
        cache_axes = tfm.kv_cache_logical_axes()
        kv_spec = _leaf_spec(cache_axes.k,
                             (cfg.n_layers, B, S, cfg.n_kv_heads, cfg.hd),
                             "lm", mesh)
        return Cell(
            arch=arch, shape_name=shape.name, fn=fn,
            args=(params_abs, _sds((B, S), I32)),
            in_specs=(param_specs, P(bspec, None)),
            out_specs=(None, tfm.KVCache(k=kv_spec, v=kv_spec,
                                         length=P(bspec))),
            model_flops=_lm_flops(cfg, B * S, False, S // 2))

    # decode: one token against a KV cache of S entries
    seq_axes = ("model",) if B % _bsize(mesh) == 0 else ("data", "model")
    cache_abs = jax.eval_shape(lambda: tfm.init_kv_cache(cfg, B, S))
    bspec_kv = bspec if B % _bsize(mesh) == 0 else None
    kv_spec = P(None, bspec_kv, seq_axes if len(seq_axes) > 1 else "model",
                None, None)
    cache_specs = tfm.KVCache(k=kv_spec, v=kv_spec, length=P(bspec_kv))

    def fn(params, cache, tokens):
        return tfm.decode_step(params, cache, tokens, cfg, mesh,
                               seq_axes=seq_axes)

    return Cell(
        arch=arch, shape_name=shape.name, fn=fn,
        args=(params_abs, cache_abs, _sds((B,), I32)),
        in_specs=(param_specs, cache_specs, P(bspec_kv)),
        out_specs=(None, cache_specs),
        donate_argnums=(1,),
        model_flops=_decode_flops(cfg, B, S),
        note=f"seq_axes={seq_axes}")


def _bsize(mesh) -> int:
    n = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            n *= mesh.shape[a]
    return n


# ======================================================================= GNN
def _gnn_flops(cfg, n_nodes: int, n_edges: int, d_feat: int,
               train: bool) -> float:
    total = 0.0
    d_in = d_feat
    for _ in range(cfg.n_layers):
        total += n_edges * d_in                      # aggregate adds
        total += 2.0 * n_nodes * d_in * cfg.d_hidden
        total += 2.0 * n_nodes * cfg.d_hidden ** 2
        d_in = cfg.d_hidden
    total += 2.0 * n_nodes * cfg.d_hidden * cfg.n_classes
    return (3.0 if train else 1.0) * total


def _build_gnn_cell(arch: str, shape: cfg_base.GNNShape, mesh: Mesh,
                    overrides: Optional[dict] = None) -> Cell:
    cfg = get_config(arch)
    overrides = dict(overrides or {})
    partitioned = overrides.pop("partitioned", False)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    bspec = _batch_spec(mesh)
    opt = opt_lib.for_config(cfg)

    if shape.kind == "sampled":
        from repro.models.sampler import NeighborSampler
        n_nodes = shape.batch_nodes
        max_nodes, max_edges = _sampler_caps(shape)
        d_feat = shape.d_feat
        N, E = max_nodes, max_edges
        kind = "node"
    elif shape.kind == "batched":
        G = shape.graphs_per_batch
        N = _pad_to(G * shape.n_nodes)
        E = _pad_to(G * shape.n_edges)
        d_feat = shape.d_feat or 16
        kind = "graph"
    else:
        N = _pad_to(shape.n_nodes)
        E = _pad_to(shape.n_edges)
        d_feat = shape.d_feat
        kind = "node"

    params_abs = gnn_lib.abstract_params(cfg, d_feat)
    param_specs = jax.tree_util.tree_map(lambda _: P(), params_abs)
    opt_abs = jax.eval_shape(opt.init, params_abs)
    opt_specs = jax.tree_util.tree_map(lambda _: P(), opt_abs)

    batch_abs = {
        "node_feats": _sds((N, d_feat), F32),
        "senders": _sds((E,), I32),
        "receivers": _sds((E,), I32),
        "labels": _sds((shape.graphs_per_batch,) if kind == "graph"
                       else (N,), I32),
    }
    batch_specs = {
        "node_feats": P(bspec, None),
        "senders": P(bspec),
        "receivers": P(bspec),
        "labels": P(bspec) if kind != "graph" else P(),
    }
    if kind == "graph":
        batch_abs["graph_ids"] = _sds((N,), I32)
        batch_specs["graph_ids"] = P(bspec)
        batch_abs["n_graphs"] = shape.graphs_per_batch
    else:
        batch_abs["mask"] = _sds((N,), jnp.bool_)
        batch_specs["mask"] = P(bspec)

    inner = gnn_lib.make_train_step(cfg, opt, kind=kind, mesh=mesh,
                                    partitioned=partitioned)

    if kind == "graph":
        n_graphs = shape.graphs_per_batch

        def fn(params, opt_state, feats, snd, rcv, gids, labels):
            batch = {"node_feats": feats, "senders": snd, "receivers": rcv,
                     "graph_ids": gids, "labels": labels,
                     "n_graphs": n_graphs}
            return inner(params, opt_state, batch)
        args = (params_abs, opt_abs, batch_abs["node_feats"],
                batch_abs["senders"], batch_abs["receivers"],
                batch_abs["graph_ids"], batch_abs["labels"])
        in_specs = (param_specs, opt_specs, batch_specs["node_feats"],
                    batch_specs["senders"], batch_specs["receivers"],
                    batch_specs["graph_ids"], batch_specs["labels"])
    else:
        def fn(params, opt_state, feats, snd, rcv, labels, mask):
            batch = {"node_feats": feats, "senders": snd, "receivers": rcv,
                     "labels": labels, "mask": mask}
            return inner(params, opt_state, batch)
        args = (params_abs, opt_abs, batch_abs["node_feats"],
                batch_abs["senders"], batch_abs["receivers"],
                batch_abs["labels"], batch_abs["mask"])
        in_specs = (param_specs, opt_specs, batch_specs["node_feats"],
                    batch_specs["senders"], batch_specs["receivers"],
                    batch_specs["labels"], batch_specs["mask"])

    return Cell(
        arch=arch, shape_name=shape.name, fn=fn, args=args,
        in_specs=in_specs, donate_argnums=(0, 1),
        model_flops=_gnn_flops(cfg, N, E, d_feat, True),
        note=f"kind={kind} padded N={N} E={E}")


def _sampler_caps(shape: cfg_base.GNNShape) -> Tuple[int, int]:
    nodes = shape.batch_nodes
    edges = 0
    frontier = shape.batch_nodes
    for f in shape.fanout:
        edges += frontier * f
        frontier *= f
        nodes += frontier
    return _pad_to(nodes), _pad_to(edges)


# ==================================================================== recsys
def _recsys_param_specs(cfg, params_abs, mesh):
    """Megatron-style specs for the recsys towers."""
    def spec(path_key: str, leaf):
        shp = leaf.shape
        if path_key in ("tables",):               # (F, V, D) row-sharded
            return shd.divisible_or_replicate(P(None, "model", None),
                                              shp, mesh)
        if path_key in ("wide",):
            return shd.divisible_or_replicate(P(None, "model"), shp, mesh)
        if path_key in ("item_emb",):
            return shd.divisible_or_replicate(P("model", None), shp, mesh)
        return P()

    flat, treedef = jax.tree_util.tree_flatten_with_path(params_abs)
    out = []
    for path, leaf in flat:
        key = ""
        for p in path:
            name = getattr(p, "key", getattr(p, "idx", None))
            if isinstance(name, str):
                key = name
        out.append(spec(key, leaf))
    specs = jax.tree_util.tree_unflatten(treedef, out)
    # Megatron column/row alternation over the deep MLP (replicated in
    # serve_scatter mode: the batch is sharded over every axis instead)
    if getattr(cfg, "serve_scatter", False) and "mlp_w" in params_abs:
        specs["mlp_w"] = [P() for _ in params_abs["mlp_w"]]
        specs["mlp_b"] = [P() for _ in params_abs["mlp_b"]]
    elif "mlp_w" in params_abs:
        ws, bs = [], []
        for i, w in enumerate(params_abs["mlp_w"]):
            col = (i % 2 == 0)
            wspec = P(None, "model") if col else P("model", None)
            bspec_ = P("model") if col else P()
            ws.append(shd.divisible_or_replicate(wspec, w.shape, mesh))
            bs.append(shd.divisible_or_replicate(
                bspec_, params_abs["mlp_b"][i].shape, mesh))
        specs["mlp_w"], specs["mlp_b"] = ws, bs
    return specs


def _recsys_inputs(cfg, B: int) -> Tuple[Dict, Dict]:
    if cfg.arch_id.startswith("wide-deep"):
        abs_ = {"sparse_ids": _sds((B, cfg.n_sparse, cfg.nnz_per_field),
                                   I32)}
    else:
        abs_ = {"seq": _sds((B, cfg.seq_len), I32)}
        if cfg.arch_id.startswith("sasrec"):
            abs_.update(pos=_sds((B,), I32), neg=_sds((B,), I32))
        elif cfg.arch_id.startswith("bst"):
            abs_.update(target=_sds((B,), I32))
        elif cfg.arch_id.startswith("mind"):
            abs_.update(target=_sds((B,), I32), neg=_sds((B, 16), I32))
    return abs_


def _recsys_flops(cfg, B: int, train: bool) -> float:
    total = 0.0
    if cfg.arch_id.startswith("wide-deep"):
        d_in = cfg.n_sparse * cfg.embed_dim
        total += B * cfg.n_sparse * cfg.nnz_per_field * cfg.embed_dim
        for d_out in cfg.mlp:
            total += 2.0 * B * d_in * d_out
            d_in = d_out
        total += 2.0 * B * d_in
    else:
        S, D = max(cfg.seq_len, 1), cfg.embed_dim
        total += B * S * D                                 # gathers
        blocks = max(cfg.n_blocks, 1)
        total += blocks * (8.0 * B * S * D * D + 4.0 * B * S * S * D)
        if cfg.interaction == "multi-interest":
            total += cfg.capsule_iters * 4.0 * B * cfg.n_interests * S * D
        if cfg.mlp:
            d_in = (S + 1) * D
            for d_out in cfg.mlp:
                total += 2.0 * B * d_in * d_out
                d_in = d_out
    return (3.0 if train else 1.0) * total


def _build_recsys_cell(arch: str, shape: cfg_base.RecsysShape,
                       mesh: Mesh, overrides: Optional[dict] = None) -> Cell:
    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    bspec = _batch_spec(mesh)
    params_abs = rec_lib.abstract_params(cfg)
    param_specs = _recsys_param_specs(cfg, params_abs, mesh)
    B = shape.batch

    if shape.kind == "train":
        opt = opt_lib.for_config(cfg)
        opt_abs = jax.eval_shape(opt.init, params_abs)
        opt_specs = _opt_state_specs(opt_abs, params_abs, param_specs)
        batch_abs = _recsys_inputs(cfg, B)
        batch_abs["labels"] = _sds((B,), F32)
        batch_specs = {k: P(bspec, *([None] * (len(v.shape) - 1)))
                       for k, v in batch_abs.items()}
        inner = rec_lib.make_train_step(cfg, opt, mesh)

        def fn(params, opt_state, batch):
            return inner(params, opt_state, batch)
        return Cell(
            arch=arch, shape_name=shape.name, fn=fn,
            args=(params_abs, opt_abs, batch_abs),
            in_specs=(param_specs, opt_specs, batch_specs),
            donate_argnums=(0, 1),
            model_flops=_recsys_flops(cfg, B, True))

    if shape.kind == "serve":
        inputs_abs = _recsys_inputs(cfg, B)
        if cfg.arch_id.startswith(("wide-deep", "bst")):
            fns = rec_lib.get_arch_fns(cfg.arch_id)

            def fn(params, inputs):
                return fns[3](params, inputs, cfg, mesh)
        else:
            def fn(params, inputs):
                return rec_lib.tower_step(params, inputs, cfg, mesh)
        in_specs = {k: P(bspec, *([None] * (len(v.shape) - 1)))
                    for k, v in inputs_abs.items()}
        return Cell(
            arch=arch, shape_name=shape.name, fn=fn,
            args=(params_abs, inputs_abs),
            in_specs=(param_specs, in_specs),
            model_flops=_recsys_flops(cfg, B, False))

    # retrieval: one user query vs n_candidates (padded to a shardable
    # multiple; padding rows are zero vectors whose ids the serving tier
    # drops from the returned top-k)
    N = _pad_to(shape.n_candidates)
    d_cand = (cfg.embed_dim if cfg.interaction == "multi-interest"
              else cfg.user_embed_dim)
    inputs_abs = _recsys_inputs(cfg, B)
    cands_abs = _sds((N, d_cand), F32)
    cand_axes = tuple(a for a in ("data", "model") if a in mesh.axis_names)
    cand_spec = P(cand_axes if len(cand_axes) > 1 else cand_axes[0], None)

    def fn(params, inputs, candidates):
        repr_ = rec_lib.tower_step(params, inputs, cfg, mesh)
        return rec_lib.retrieval_step(repr_, candidates, cfg, mesh)

    in_specs = {k: P(*([None] * len(v.shape)))
                for k, v in inputs_abs.items()}
    return Cell(
        arch=arch, shape_name=shape.name, fn=fn,
        args=(params_abs, inputs_abs, cands_abs),
        in_specs=(param_specs, in_specs, cand_spec),
        model_flops=_recsys_flops(cfg, B, False) + 2.0 * B * N * d_cand)


# ================================================================ cache tier
def cache_tier_specs(state) -> Any:
    """PartitionSpec tree for a ServerState/MultiServerState on the cache
    tier's 1-D ("shard",) mesh (DESIGN.md §11): cache tables bucket-sharded,
    rings and the admission budget replicated. Feed through
    :func:`to_shardings` for jit in_shardings of the serve entry points."""
    from repro.distributed import collectives as coll

    def rep(tree):
        return jax.tree_util.tree_map(lambda _: P(), tree)

    return state._replace(direct=coll.cache_pspec(state.direct),
                          failover=coll.cache_pspec(state.failover),
                          writebuf=rep(state.writebuf),
                          touchbuf=rep(state.touchbuf),
                          budget=rep(state.budget))


# ==================================================================== public
def build_cell(arch: str, shape_name: str, mesh: Mesh,
               overrides: Optional[dict] = None) -> Cell:
    """``overrides``: LMConfig field overrides plus the pseudo-field
    ``global_batch`` — used by the dry-run's roofline accounting variants
    and by §Perf hillclimb configurations."""
    cfg = get_config(arch)
    shapes = cfg_base.LM_SHAPES if cfg.family == "lm" else (
        cfg_base.GNN_SHAPES if cfg.family == "gnn"
        else cfg_base.RECSYS_SHAPES)
    shape = shapes[shape_name]
    if cfg.family == "lm":
        return _build_lm_cell(arch, shape, mesh, overrides)
    if cfg.family == "gnn":
        return _build_gnn_cell(arch, shape, mesh, overrides)
    return _build_recsys_cell(arch, shape, mesh, overrides)


def to_shardings(mesh: Mesh, tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P))
