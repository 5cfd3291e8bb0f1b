import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile one (arch x shape x mesh) cell with
ShapeDtypeStruct stand-ins (no allocation), print/record memory analysis,
cost analysis and the parsed collective schedule.

The two lines above MUST precede any jax import (jax locks the device count
on first init); smoke tests and benches never import this module, so they
see 1 device.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k \
      --mesh single --out experiments/dryrun/
  PYTHONPATH=src python -m repro.launch.dryrun --arch index_service --mesh multi
"""
import argparse
import json
import re
import sys
import time

import jax
import jax.numpy as jnp

import repro  # noqa: F401  (x64 for the index core)
from repro.configs import SHAPES, get_arch, list_archs
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.serve import step as serve_step
from repro.train import optimizer
from repro.train.step import batch_shapes, make_train_step

# v5e hardware constants (DESIGN.md §7)
PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
ICI_BW = 50e9                # bytes/s / link (~per-direction)


def _sharded_sds(tree_shapes, tree_specs, mesh):
    def f(s, spec):
        return jax.ShapeDtypeStruct(
            s.shape, s.dtype,
            sharding=jax.sharding.NamedSharding(mesh, spec))
    return jax.tree.map(f, tree_shapes, tree_specs)


_COLL_RE = re.compile(
    r"(\w+(?:\.\d+)?)\s*=\s*(\w+\[[^\]]*\](?:[^ ]*)?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(", )
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|s64|s32|s8|u64|u32|u8|pred)\[([0-9,]*)\]")
_GROUP_RE = re.compile(r"replica_groups=\{?\{([0-9, ]+)\}")

_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "s32": 4,
          "s8": 1, "u64": 8, "u32": 4, "u8": 1, "pred": 1}


def parse_collectives(hlo_text: str) -> dict:
    """Sum per-chip link traffic by collective kind from post-partition HLO.

    Ring-model per-chip traffic from the op's *result* shape R and group
    size n:  all-gather (n-1)/n * R;  reduce-scatter (n-1) * R (result is
    1/n of the input);  all-reduce 2(n-1)/n * R;  all-to-all (n-1)/n * R;
    collective-permute R.
    """
    totals = {"all-reduce": 0.0, "all-gather": 0.0, "reduce-scatter": 0.0,
              "all-to-all": 0.0, "collective-permute": 0.0}
    counts = dict.fromkeys(totals, 0)
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        kind = m.group(3)
        if m.group(4):  # -start of a start/done pair; done has no shape
            pass
        sm = _SHAPE_RE.search(m.group(2))
        if not sm:
            continue
        dt, dims = sm.group(1), sm.group(2)
        numel = 1
        for d in dims.split(","):
            if d:
                numel *= int(d)
        nbytes = numel * _BYTES[dt]
        gm = _GROUP_RE.search(line)
        n = len(gm.group(1).split(",")) if gm else 2
        if kind == "all-gather":
            traffic = nbytes * (n - 1) / n
        elif kind == "reduce-scatter":
            traffic = nbytes * (n - 1)
        elif kind == "all-reduce":
            traffic = 2 * nbytes * (n - 1) / n
        elif kind == "all-to-all":
            traffic = nbytes * (n - 1) / n
        else:
            traffic = nbytes
        totals[kind] += traffic
        counts[kind] += 1
    return {"bytes_by_kind": totals, "counts": counts,
            "total_bytes": sum(totals.values())}


def lower_cell(arch: str, shape_name: str, mesh, *, compress_pod=False,
               microbatch: int | None = None, psum_bf16: bool = False,
               replicate_weights: bool = False):
    """Returns (lowered, meta) for one cell."""
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    meta = {"arch": arch, "shape": shape_name, "kind": shape.kind,
            "psum_bf16": psum_bf16, "replicate_weights": replicate_weights}

    if shape.kind == "train":
        from repro.train.step import auto_microbatch
        if microbatch is None:
            microbatch = auto_microbatch(cfg, shape, mesh)
        meta["microbatch"] = microbatch
        fn, in_specs = make_train_step(
            cfg, mesh, compress_pod=compress_pod, microbatch=microbatch,
            psum_dtype=jnp.bfloat16 if psum_bf16 else None)
        p = M.param_shapes(cfg)
        o = optimizer.init_shapes(p)
        b = batch_shapes(cfg, shape)
        if compress_pod:
            from repro.train.grad_compress import init_residual
            res = init_residual(p, shapes_only=True)
        else:
            res = jax.ShapeDtypeStruct((), jnp.float32)
        from jax.sharding import PartitionSpec as P
        args = (_sharded_sds(p, in_specs[0], mesh),
                _sharded_sds(o, in_specs[1], mesh),
                _sharded_sds(res, in_specs[2], mesh),
                _sharded_sds(b["inputs"], in_specs[3], mesh),
                _sharded_sds(b["labels"], in_specs[4], mesh),
                _sharded_sds(b["pos"], in_specs[5], mesh))
        lowered = fn.lower(*args)
    elif shape.kind == "prefill":
        fn, in_specs = serve_step.make_prefill(cfg, mesh)
        p = M.param_shapes(cfg)
        sh = serve_step.serve_shapes(cfg, shape, mesh)
        B, S = shape.global_batch, shape.seq_len
        tok = jax.ShapeDtypeStruct(
            (B, S, cfg.d_model) if cfg.embed_input else (B, S),
            jnp.bfloat16 if cfg.embed_input else jnp.int32)
        pos = jax.ShapeDtypeStruct(
            (3, B, S) if cfg.rope == "mrope" else (B, S), jnp.int32)
        args = (_sharded_sds(p, in_specs[0], mesh),
                _sharded_sds(sh["caches"], in_specs[1], mesh),
                _sharded_sds(tok, in_specs[2], mesh),
                _sharded_sds(pos, in_specs[3], mesh))
        lowered = fn.lower(*args)
    else:  # decode
        sh = serve_step.serve_shapes(cfg, shape, mesh)
        fn, in_specs = serve_step.make_decode_step(
            cfg, mesh, batch_sharded=sh["batch_sharded"],
            seq_shard=sh["seq_shard"], replicate_weights=replicate_weights)
        p = M.param_shapes(cfg)
        from jax.sharding import PartitionSpec as P
        args = (_sharded_sds(p, in_specs[0], mesh),
                _sharded_sds(sh["caches"], in_specs[1], mesh),
                _sharded_sds(sh["tokens"], in_specs[2], mesh),
                _sharded_sds(sh["pos"], in_specs[3], mesh),
                _sharded_sds(sh["cache_len"], P(), mesh))
        meta["batch_sharded"] = sh["batch_sharded"]
        meta["seq_shard"] = sh["seq_shard"]
        lowered = fn.lower(*args)
    return lowered, meta


def lower_index_service(mesh, capacity_factor=None):
    """Dry-run cell for the paper's distributed index service itself."""
    import numpy as np
    from repro.core import distributed
    n = 1 << 20
    keys = jnp.asarray(np.linspace(0.0, 1.0, n))
    idx = distributed.build_sharded(keys, mesh, axis="data", n_leaves=256)
    fn = distributed.make_lookup_fn(idx, capacity_factor=capacity_factor)
    q = jax.ShapeDtypeStruct((1 << 16,), jnp.float64)
    return fn.lower(q), {"arch": "index_service", "shape": "lookup_64k",
                         "kind": "index",
                         "capacity_factor": capacity_factor}


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str | None = None, compress_pod: bool = False,
             microbatch: int | None = None, tag: str = "",
             psum_bf16: bool = False, replicate_weights: bool = False) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = 512 if multi_pod else 256
    t0 = time.time()
    if arch == "index_service":
        lowered, meta = lower_index_service(
            mesh, capacity_factor=2.0 if tag == "cap2" else None)
    else:
        lowered, meta = lower_cell(arch, shape_name, mesh,
                                   compress_pod=compress_pod,
                                   microbatch=microbatch,
                                   psum_bf16=psum_bf16,
                                   replicate_weights=replicate_weights)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo_text = compiled.as_text()
    coll = parse_collectives(hlo_text)

    # Trip-count-aware accounting (xla cost_analysis counts loop bodies
    # once — see launch/hlo_cost.py; raw values kept for reference).
    from repro.launch.hlo_cost import HloCost
    acc = HloCost(hlo_text).summary()
    flops = acc["flops"]
    bytes_acc = acc["bytes"]
    coll = {"bytes_by_kind": acc["collective_bytes_by_kind"],
            "counts": acc["collective_counts"],
            "total_bytes": acc["collective_bytes"],
            "once_counted": coll}
    result = dict(
        meta,
        mesh="multi" if multi_pod else "single",
        chips=chips,
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        hlo_flops_per_chip=flops,
        hlo_bytes_per_chip=bytes_acc,
        xla_cost_analysis={"flops": float(cost.get("flops", 0.0)),
                           "bytes": float(cost.get("bytes accessed", 0.0))},
        collective=coll,
        memory={
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_bytes_est": mem.argument_size_in_bytes
            + mem.output_size_in_bytes + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes,
        },
        roofline={
            "compute_s": flops / PEAK_FLOPS,
            "memory_s": bytes_acc / HBM_BW,
            "collective_s": coll["total_bytes"] / ICI_BW,
        },
    )
    r = result["roofline"]
    result["roofline"]["dominant"] = max(
        ("compute_s", "memory_s", "collective_s"), key=lambda k: r[k])
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"__{tag}" if tag else ""
        fname = f"{arch}__{shape_name}__{result['mesh']}{suffix}.json"
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(result, f, indent=1)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True,
                    help=f"one of {list_archs()} or index_service")
    ap.add_argument("--shape", default="train_4k",
                    choices=[*SHAPES, "lookup_64k"])
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--out", default=None)
    ap.add_argument("--compress-pod", action="store_true")
    ap.add_argument("--microbatch", type=int, default=None)
    ap.add_argument("--tag", default="")
    ap.add_argument("--psum-bf16", action="store_true")
    ap.add_argument("--replicate-weights", action="store_true")
    args = ap.parse_args()
    res = run_cell(args.arch, args.shape, args.mesh == "multi", args.out,
                   compress_pod=args.compress_pod,
                   microbatch=args.microbatch, tag=args.tag,
                   psum_bf16=args.psum_bf16,
                   replicate_weights=args.replicate_weights)
    json.dump(res, sys.stdout, indent=1)
    print()


if __name__ == "__main__":
    main()
