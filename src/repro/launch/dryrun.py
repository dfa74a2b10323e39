"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Proves the distribution config is coherent without hardware: a sharding
mismatch, compile-time OOM, or unsupported collective fails the cell. The
compiled artifact also yields the §Roofline terms:

    compute    = HLO_FLOPs / (chips × peak)
    memory     = HLO_bytes / (chips × HBM_bw)
    collective = collective_bytes / (chips × link_bw)

cost_analysis() provides FLOPs/bytes; collective bytes are parsed from the
post-SPMD HLO text (operand sizes of all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute).

Usage::

    python -m repro.launch.dryrun --arch yi-6b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--out results.json]
"""
# The dry-run (and ONLY the dry-run) needs 512 placeholder devices — set
# before ANY other import so jax initializes with them.
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

import argparse          # noqa: E402
import json              # noqa: E402
import re                # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from typing import Dict, Optional  # noqa: E402

import jax               # noqa: E402

from repro.configs import all_cells, get_config     # noqa: E402
from repro.launch import specs as specs_lib         # noqa: E402
from repro.launch.mesh import (HBM_BW, ICI_BW, PEAK_FLOPS_BF16,  # noqa: E402
                               make_production_mesh)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"\b(f64|f32|f16|bf16|f8e4m3|f8e5m2|s64|u64|s32|u32|"
                       r"s16|u16|s8|u8|pred)\[([0-9,]*)\]")

# "%name = <type(s)> opcode(" — type may be a tuple "(f32[..], u32[])"
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\([^)]*\)|[\w\[\]{},/]+)\s+"
    r"([\w\-]+)\(")
_OPERAND_NAME_RE = re.compile(r"%([\w.\-]+)")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _type_bytes(type_str: str) -> int:
    return sum(_shape_bytes(m.group(1), m.group(2))
               for m in _SHAPE_RE.finditer(type_str))


def _balanced_args(line: str, start: int) -> str:
    depth = 1
    for i in range(start, len(line)):
        if line[i] == "(":
            depth += 1
        elif line[i] == ")":
            depth -= 1
            if depth == 0:
                return line[start:i]
    return line[start:]


_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9, ]*)\}")


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        ids = m.group(1).strip()
        return len(ids.split(",")) if ids else default
    return default


def _wire_factor(kind: str, n: int) -> float:
    """Per-device WIRE bytes per operand byte (bidirectional-ring model):
    all-gather sends its shard n-1 times; all-reduce = reduce-scatter +
    all-gather ≈ 2(n-1)/n of the full operand; rs/a2a move (n-1)/n;
    collective-permute forwards once."""
    if n <= 1:
        return 0.0
    return {
        "all-gather": float(n - 1),
        "all-reduce": 2.0 * (n - 1) / n,
        "reduce-scatter": (n - 1) / n,
        "all-to-all": (n - 1) / n,
        "collective-permute": 1.0,
    }[kind]


def collective_bytes(hlo_text: str, n_devices: int = 256
                     ) -> Dict[str, float]:
    """Per-device wire bytes of every collective op in post-SPMD HLO text.

    The optimized dump omits operand types, so pass 1 builds a name → result
    -type table from every instruction, pass 2 resolves collective operands
    through it (inline-typed dumps are also handled: inline shapes win) and
    scales operand bytes to wire bytes via the op's replica-group size.
    Async pairs (-start/-done) are counted once via the -start op.
    """
    types: Dict[str, str] = {}
    coll_lines = []
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, type_str, opcode = m.groups()
        types[name] = type_str
        base = opcode[:-6] if opcode.endswith("-start") else opcode
        if base in _COLLECTIVES and not opcode.endswith("-done"):
            args = _balanced_args(line, m.end())
            coll_lines.append((base, args, _group_size(line, n_devices)))

    out = {k: 0.0 for k in _COLLECTIVES}
    count = {k: 0 for k in _COLLECTIVES}
    for base, args, n in coll_lines:
        inline = _type_bytes(args)
        if inline:
            b = inline
        else:
            b = sum(_type_bytes(types.get(nm, ""))
                    for nm in _OPERAND_NAME_RE.findall(args))
        out[base] += b * _wire_factor(base, n)
        count[base] += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    out["counts"] = count
    return out


def _compile_cell(cell, mesh):
    in_shardings = specs_lib.to_shardings(mesh, cell.in_specs)
    out_shardings = (specs_lib.to_shardings(mesh, cell.out_specs)
                     if cell.out_specs is not None else None)
    jitted = jax.jit(cell.fn,
                     in_shardings=in_shardings,
                     out_shardings=out_shardings,
                     donate_argnums=cell.donate_argnums)
    with mesh:
        lowered = jitted.lower(*cell.args)
        compiled = lowered.compile()
    return compiled


def _measure(compiled, n_devices: int = 256) -> Dict[str, float]:
    cost = compiled.cost_analysis() or {}
    coll = collective_bytes(compiled.as_text(), n_devices)
    out = {"flops": float(cost.get("flops", 0.0)),
           "bytes": float(cost.get("bytes accessed", 0.0)),
           "coll": coll["total"]}
    for k in _COLLECTIVES:
        out[f"coll_{k}"] = coll[k]
    return out


def _combine(terms, coeffs) -> Dict[str, float]:
    """Linear combination of measurement dicts; clamps at ≥ 0."""
    keys = terms[0].keys()
    return {k: max(sum(c * t[k] for c, t in zip(coeffs, terms)), 0.0)
            for k in keys}


def lm_accounting(arch: str, shape_name: str, mesh,
                  overrides: Optional[dict] = None) -> Dict[str, float]:
    """Scan-free roofline accounting for LM cells.

    XLA's cost_analysis counts while-loop (scan) bodies ONCE, hiding
    (cost × trip_count). Fully unrolling the real config is intractable to
    compile, so we lower tiny unrolled variants and solve the linear model

        cost(L, M) = opt_base + L·opt_layer + M·(tok_base + L·tok_layer)

    from 4 points (L∈{1,2} × M∈{1,2}) for train, 2 points (L∈{1,2}) for
    prefill/decode, then evaluate at the real (L, M). Exact when cost is
    affine in L and M — which holds per-op since S and per-micro batch stay
    fixed across variants.
    """
    from repro.configs import get_config as _get
    overrides = dict(overrides or {})
    cfg = _get(arch)
    L = cfg.n_layers
    shape = None
    from repro.configs.base import LM_SHAPES
    shape = LM_SHAPES[shape_name]

    def meas(n_layers, micro=None, batch=None):
        ov = dict(overrides)
        ov.update(n_layers=n_layers, unroll_scans=True)
        if micro is not None:
            ov["microbatches"] = micro
        if batch is not None:
            ov["global_batch"] = batch
        cell = specs_lib.build_cell(arch, shape_name, mesh, ov)
        return _measure(_compile_cell(cell, mesh), mesh.size)

    if shape.kind == "train":
        M = overrides.get("microbatches",
                          specs_lib.TRAIN_MICRO[arch])
        B = overrides.get("global_batch", shape.global_batch)
        bm = B // M
        A = meas(1, 1, bm)
        Bv = meas(2, 1, bm)
        C = meas(1, 2, 2 * bm)
        D = meas(2, 2, 2 * bm)
        l_t = _combine([D, C, Bv, A], [1, -1, -1, 1])
        tok = _combine([C, A, l_t], [1, -1, -1])
        l_o = _combine([Bv, A, l_t], [1, -1, -1])
        o1 = _combine([A, l_o, tok, l_t], [1, -1, -1, -1])
        return _combine([o1, l_o, tok, l_t], [1, L, M, M * L])
    # prefill / decode: cost(L) = base + L·layer
    A = meas(1)
    Bv = meas(2)
    layer = _combine([Bv, A], [1, -1])
    return _combine([A, layer], [1, L - 1])


def run_cell(arch: str, shape_name: str, multi_pod: bool = False,
             verbose: bool = True, overrides: Optional[dict] = None,
             accounting: Optional[bool] = None) -> Dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    t0 = time.perf_counter()
    cell = specs_lib.build_cell(arch, shape_name, mesh, overrides)
    compiled = _compile_cell(cell, mesh)
    t_compile = time.perf_counter() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = collective_bytes(hlo, n_chips)

    flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes accessed", 0.0))

    # LM cells hide per-layer/per-microbatch cost inside scans — replace the
    # aggregate counts with the unrolled-variant linear decomposition.
    cfg = get_config(arch)
    if accounting is None:
        accounting = cfg.family == "lm" and not multi_pod
    if accounting:
        acct = lm_accounting(arch, shape_name, mesh, overrides)
        flops = acct["flops"]
        bytes_accessed = acct["bytes"]
        coll = {k: acct[f"coll_{k}"] for k in _COLLECTIVES}
        coll["total"] = acct["coll"]
        coll["counts"] = collective_bytes(hlo, n_chips)["counts"]
    # cost_analysis is per-device (the SPMD module); collective bytes are
    # module-level too (per device's sends).
    compute_s = flops / PEAK_FLOPS_BF16
    memory_s = bytes_accessed / HBM_BW
    collective_s = coll["total"] / ICI_BW
    dominant = max(
        (("compute", compute_s), ("memory", memory_s),
         ("collective", collective_s)), key=lambda kv: kv[1])[0]

    per_dev_model_flops = cell.model_flops / n_chips
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(str(s) for s in mesh.devices.shape)
                + f" ({','.join(mesh.axis_names)})",
        "n_chips": n_chips,
        "compile_s": round(t_compile, 1),
        "hlo_flops_per_dev": flops,
        "hlo_bytes_per_dev": bytes_accessed,
        "collective_bytes_per_dev": coll["total"],
        "collective_breakdown": {k: coll[k] for k in _COLLECTIVES},
        "collective_counts": coll["counts"],
        "compute_s_term": compute_s,
        "memory_s_term": memory_s,
        "collective_s_term": collective_s,
        "dominant": dominant,
        "model_flops_total": cell.model_flops,
        "useful_flops_ratio": (per_dev_model_flops / flops
                               if flops else 0.0),
        "memory_stats": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_estimate_gb": round(
                (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
                / 2**30, 3),
        },
        "note": cell.note,
        "ok": True,
    }
    if verbose:
        print(f"[{arch} × {shape_name} × {result['mesh']}] "
              f"compile {t_compile:.0f}s  "
              f"compute {compute_s*1e3:.2f}ms  memory {memory_s*1e3:.2f}ms  "
              f"collective {collective_s*1e3:.2f}ms  → {dominant}-bound  "
              f"useful {100*result['useful_flops_ratio']:.0f}%  "
              f"mem {result['memory_stats']['peak_estimate_gb']}GB/dev")
    return result


def run_ercache_cell(arch: str = "tinyllama-1.1b", batch: int = 4096,
                     multi_pod: bool = False, verbose: bool = True) -> Dict:
    """BEYOND the 40 assigned cells: the paper's own technique at scale.

    Lowers CachedEmbeddingServer.serve_step — direct-cache probe →
    miss-budget-compacted tower inference (full LM config) → failover →
    async write append — plus the flush program, on the production mesh.
    The cache tables live sharded over (data, model) in HBM.
    """
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core import server as srv_lib
    from repro.core.config import CacheConfig, HOUR_MS, MINUTE_MS
    from repro.core.hashing import Key64
    from repro.models import transformer as tfm

    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch)
    seq = 64                                  # behaviour-history length
    cache_cfg = CacheConfig(
        model_id=1, model_type="ctr",
        cache_ttl_ms=5 * MINUTE_MS, failover_ttl_ms=1 * HOUR_MS,
        n_buckets=1 << 22, ways=8, value_dim=cfg.user_embed_dim)

    def tower_fn(params, tokens):
        return tfm.user_tower_step(params, tokens, cfg, mesh)

    server = srv_lib.CachedEmbeddingServer(
        cfg=cache_cfg, tower_fn=tower_fn, miss_budget=batch // 4)

    params_abs = tfm.abstract_params(cfg)
    param_specs = specs_lib._tree_specs(tfm.param_logical_axes(cfg),
                                        params_abs, "lm", mesh)
    state_abs = jax.eval_shape(
        lambda: srv_lib.init_server_state(cache_cfg, dtype=jnp.float32,
                                          writebuf_capacity=batch))
    bspec = specs_lib._batch_spec(mesh)
    cache_spec = srv_lib.ServerState(
        direct=type(state_abs.direct)(
            key_hi=P(("data", "model")), key_lo=P(("data", "model")),
            write_ts=P(("data", "model")),
            value_plane=P(None, ("data", "model")),
            last_access_ts=P(("data", "model"))),
        failover=type(state_abs.failover)(
            key_hi=P(("data", "model")), key_lo=P(("data", "model")),
            write_ts=P(("data", "model")),
            value_plane=P(None, ("data", "model")),
            last_access_ts=P(("data", "model"))),
        writebuf=jax.tree_util.tree_map(lambda _: P(), state_abs.writebuf),
        touchbuf=jax.tree_util.tree_map(lambda _: P(), state_abs.touchbuf),
        budget=jax.tree_util.tree_map(lambda _: P(), state_abs.budget))
    keys_abs = Key64(hi=jax.ShapeDtypeStruct((batch,), jnp.int32),
                     lo=jax.ShapeDtypeStruct((batch,), jnp.int32))
    toks_abs = jax.ShapeDtypeStruct((batch, seq), jnp.int32)

    def fn(params, state, keys, tokens, now):
        res = server.serve_step(params, state, keys, tokens, now)
        return res.embeddings, res.source, res.stats, res.state

    t0 = time.perf_counter()
    jitted = jax.jit(fn, in_shardings=(
        param_specs and specs_lib.to_shardings(mesh, param_specs),
        specs_lib.to_shardings(mesh, cache_spec),
        specs_lib.to_shardings(mesh, Key64(hi=P(bspec), lo=P(bspec))),
        specs_lib.to_shardings(mesh, P(bspec, None)), None),
        donate_argnums=(1,))
    with mesh:
        compiled = jitted.lower(params_abs, state_abs, keys_abs, toks_abs,
                                jnp.int32(0)).compile()
    t_compile = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    coll = collective_bytes(compiled.as_text(), mesh.size)
    result = {
        "arch": f"ercache-serve[{arch}]", "shape": f"batch{batch}",
        "mesh": "x".join(str(x) for x in mesh.devices.shape),
        "n_chips": mesh.size, "compile_s": round(t_compile, 1),
        "hlo_flops_per_dev": float(cost.get("flops", 0.0)),
        "hlo_bytes_per_dev": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes_per_dev": coll["total"],
        "compute_s_term": float(cost.get("flops", 0.0)) / PEAK_FLOPS_BF16,
        "memory_s_term": float(cost.get("bytes accessed", 0.0)) / HBM_BW,
        "collective_s_term": coll["total"] / ICI_BW,
        "memory_stats": {
            "argument_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_estimate_gb": round(
                (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
                / 2**30, 3)},
        "ok": True,
    }
    if verbose:
        print(f"[ERCACHE serve × {arch} × {result['mesh']}] "
              f"compile {t_compile:.0f}s "
              f"compute {result['compute_s_term']*1e3:.2f}ms "
              f"memory {result['memory_s_term']*1e3:.2f}ms "
              f"collective {result['collective_s_term']*1e3:.2f}ms "
              f"mem {result['memory_stats']['peak_estimate_gb']}GB/dev")
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun_results.json")
    ap.add_argument("--ercache", action="store_true",
                    help="lower the ERCache serve_step cell instead")
    args = ap.parse_args()

    if args.ercache:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        results = {}
        if os.path.exists(args.out):
            with open(args.out) as f:
                results = json.load(f)
        for mp in ([False, True] if args.both_meshes else [args.multi_pod]):
            key = f"ercache|{args.arch or 'tinyllama-1.1b'}|" + \
                ("multipod" if mp else "singlepod")
            results[key] = run_ercache_cell(
                args.arch or "tinyllama-1.1b", multi_pod=mp)
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)
        return

    cells = all_cells() if args.all else [(args.arch, args.shape)]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)

    for arch, shape in cells:
        for mp in meshes:
            key = f"{arch}|{shape}|{'multipod' if mp else 'singlepod'}"
            if results.get(key, {}).get("ok"):
                print(f"[skip] {key} (cached)")
                continue
            try:
                results[key] = run_cell(arch, shape, multi_pod=mp)
            except Exception as e:
                traceback.print_exc()
                results[key] = {"arch": arch, "shape": shape,
                                "multi_pod": mp, "ok": False,
                                "error": f"{type(e).__name__}: {e}"}
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)
    n_ok = sum(1 for r in results.values() if r.get("ok"))
    print(f"\n{n_ok}/{len(results)} cells OK → {args.out}")


if __name__ == "__main__":
    main()
