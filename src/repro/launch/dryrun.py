"""Multi-pod dry-run: prove every (arch × shape × mesh) lowers + compiles,
and extract roofline inputs from the compiled artifacts.

Run as:  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
             --mesh both --out benchmarks/results/dryrun.json

Two compiles per combination:
  1. the production step (layers under lax.scan, remat on for train) —
     proves lowering/SPMD coherence and yields memory_analysis;
  2. a *cost probe*: the same step at full width but 1 and 2 unrolled
     layers. XLA's HloCostAnalysis counts a while-loop body once, so
     per-layer FLOPs/bytes/collective-bytes are measured as the (L2 − L1)
     difference and extrapolated:  total = c1 + (L − 1)·Δ.
     (Encoder-decoder probes encoder and decoder layers separately.)

Results are cached incrementally per (arch, shape, mesh, strategy).
"""
# The first two statements MUST precede any other import (jax locks the
# device count at first init).
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import dataclasses
import json
import re
import time
import traceback
from collections import defaultdict

import numpy as np


DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def parse_collective_bytes(hlo_text: str):
    """Per-device bytes moved by collectives, summed per op type.

    Convention: result-shape bytes per op; all-reduce counted twice
    (reduce-scatter + all-gather phases of a ring implementation).
    """
    per_type = defaultdict(float)
    counts = defaultdict(int)
    for line in hlo_text.splitlines():
        m = re.search(r"=\s*(\([^)]*\)|\S+)\s+([\w-]+)\(", line)
        if not m:
            continue
        opname = m.group(2)
        base = opname.replace("-start", "")
        if base not in COLLECTIVES or opname.endswith("-done"):
            continue
        b = _shape_bytes(m.group(1))
        factor = 2.0 if base == "all-reduce" else 1.0
        per_type[base] += b * factor
        counts[base] += 1
    return dict(per_type), dict(counts)


def _sharded_bytes(struct, spec_tree, mesh) -> int:
    """Analytic per-device bytes of a sharded pytree."""
    import jax
    from repro.sharding.specs import _axis_size

    def leaf_bytes(leaf, spec):
        n = int(np.prod(leaf.shape)) if leaf.shape else 1
        denom = 1
        for entry in spec:
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            for a in axes:
                denom *= _axis_size(mesh, a)
        return n * leaf.dtype.itemsize // max(denom, 1)

    flat_l = jax.tree_util.tree_leaves(struct)
    flat_s = jax.tree_util.tree_leaves(
        spec_tree, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    return sum(leaf_bytes(l, s) for l, s in zip(flat_l, flat_s))


def _compile_once(cfg, shape_name: str, mesh, strategy: str, unroll: bool,
                  want_memory: bool):
    """Lower + compile one step; return raw per-device cost numbers."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.models import input_specs, shape_for_long_context
    from repro.launch.steps import (make_decode_step, make_prefill_step,
                                    make_train_step)
    from repro.sharding import (STRATEGIES, batch_specs, cache_specs,
                                param_specs, tree_shardings)

    kind, specs = input_specs(cfg, shape_name)
    skw = STRATEGIES[strategy]
    cfg_step = shape_for_long_context(cfg) if kind == "decode" else cfg
    out = {"kind": kind, "optimizer": None}

    if kind == "train":
        model, opt, step = make_train_step(cfg_step, unroll=unroll)
        out["optimizer"] = opt.name
        pstruct = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        ostruct = jax.eval_shape(opt.init, pstruct)
        pspec = param_specs(pstruct, mesh, **skw)
        ospec = param_specs(ostruct, mesh, **skw)
        bspec = batch_specs(specs["batch"], mesh)
        jitted = jax.jit(
            step,
            in_shardings=(tree_shardings(pspec, mesh),
                          tree_shardings(ospec, mesh),
                          tree_shardings(bspec, mesh)),
            out_shardings=(tree_shardings(pspec, mesh),
                           tree_shardings(ospec, mesh),
                           NamedSharding(mesh, P())))
        args = (pstruct, ostruct, specs["batch"])
        out["state_bytes_per_device"] = (
            _sharded_bytes(pstruct, pspec, mesh) +
            _sharded_bytes(ostruct, ospec, mesh))
    elif kind == "prefill":
        model, step = make_prefill_step(cfg_step, shape_name, unroll=unroll)
        pstruct = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        pspec = param_specs(pstruct, mesh, **skw)
        in_sh = [tree_shardings(pspec, mesh)]
        args = [pstruct]
        for key in ("frames", "tokens", "frontend_embeds"):
            if key in specs:
                in_sh.append(tree_shardings(batch_specs(specs[key], mesh), mesh))
                args.append(specs[key])
        jitted = jax.jit(step, in_shardings=tuple(in_sh))
        args = tuple(args)
        out["state_bytes_per_device"] = _sharded_bytes(pstruct, pspec, mesh)
    else:  # decode
        model, step = make_decode_step(cfg, shape_name, unroll=unroll)
        # input_specs was computed for the original cfg — recompute against
        # the (possibly layer-reduced) cfg for probe consistency
        pstruct = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        pspec = param_specs(pstruct, mesh, **skw)
        # production default: decode caches shard seq over the model axis
        # too (GQA einsum + psum-over-seq) — §Perf showed 28x on the
        # dominant term vs batch-only cache sharding
        cspec = cache_specs(specs["cache"], mesh,
                            seq_over_model=skw.get("seq_over_model", True))
        in_sh = [tree_shardings(pspec, mesh),
                 tree_shardings(cspec, mesh),
                 tree_shardings(batch_specs(specs["tokens"], mesh), mesh)]
        args = [pstruct, specs["cache"], specs["tokens"]]
        if "enc_kv" in specs:
            ek_spec = cache_specs(specs["enc_kv"], mesh)  # cross-KV: batch only
            in_sh.append(tree_shardings(ek_spec, mesh))
            args.append(specs["enc_kv"])
        out_sh = (NamedSharding(mesh, P()), tree_shardings(cspec, mesh))
        jitted = jax.jit(step, in_shardings=tuple(in_sh), out_shardings=out_sh)
        args = tuple(args)
        out["state_bytes_per_device"] = (
            _sharded_bytes(pstruct, pspec, mesh) +
            _sharded_bytes(specs["cache"], cspec, mesh))

    t0 = time.time()
    with jax.set_mesh(mesh):
        compiled = jitted.lower(*args).compile()
        out["compile_s"] = round(time.time() - t0, 2)
        if want_memory:
            try:
                ma = compiled.memory_analysis()
                out["memory_analysis"] = {
                    "argument_size": int(ma.argument_size_in_bytes),
                    "output_size": int(ma.output_size_in_bytes),
                    "temp_size": int(ma.temp_size_in_bytes),
                }
            except Exception as e:  # pragma: no cover
                out["memory_analysis"] = {"error": str(e)}
        ca = compiled.cost_analysis() or {}
        out["flops"] = float(ca.get("flops", 0.0))
        out["bytes"] = float(ca.get("bytes accessed", 0.0))
        coll, counts = parse_collective_bytes(compiled.as_text())
        out["collective_bytes"] = coll
        out["collective_counts"] = counts
        out["collective_total"] = float(sum(coll.values()))
    return out


def _probe_cfgs(cfg):
    """(label, probe_cfg, multiplier-extraction) pairs for the cost probe."""
    if cfg.encoder_layers > 0:
        return [
            ("p11", dataclasses.replace(cfg, n_layers=1, encoder_layers=1)),
            ("p21", dataclasses.replace(cfg, n_layers=2, encoder_layers=1)),
            ("p12", dataclasses.replace(cfg, n_layers=1, encoder_layers=2)),
        ]
    return [
        ("p1", dataclasses.replace(cfg, n_layers=1)),
        ("p2", dataclasses.replace(cfg, n_layers=2)),
    ]


def _extrapolate(cfg, probes):
    """total = base + Σ (L_i − 1)·Δ_i per metric."""
    metrics = ("flops", "bytes", "collective_total")
    out = {}
    if cfg.encoder_layers > 0:
        base, p_dec, p_enc = probes["p11"], probes["p21"], probes["p12"]
        for m in metrics:
            d_dec = max(p_dec[m] - base[m], 0.0)
            d_enc = max(p_enc[m] - base[m], 0.0)
            out[m] = base[m] + (cfg.n_layers - 1) * d_dec \
                + (cfg.encoder_layers - 1) * d_enc
    else:
        p1, p2 = probes["p1"], probes["p2"]
        for m in metrics:
            delta = max(p2[m] - p1[m], 0.0)
            out[m] = p1[m] + (cfg.n_layers - 1) * delta
    return out


def dryrun_one(arch: str, shape_name: str, mesh_kind: str,
               strategy: str = "tp_fsdp", verbose: bool = True,
               probe: bool = True) -> dict:
    from repro.configs import get_config
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi_pod"))
    n_chips = int(np.prod(list(dict(mesh.shape).values())))
    cfg = get_config(arch)

    record = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "strategy": strategy, "chips": n_chips,
        "params": cfg.param_count(), "active_params": cfg.active_param_count(),
    }
    # 1. production compile (scan over layers) — the lowering proof
    main = _compile_once(cfg, shape_name, mesh, strategy, unroll=False,
                         want_memory=True)
    record.update({
        "kind": main["kind"], "optimizer": main["optimizer"],
        "compile_s": main["compile_s"],
        "memory_analysis": main.get("memory_analysis"),
        "state_bytes_per_device": main["state_bytes_per_device"],
        "hlo_flops_scan": main["flops"], "hlo_bytes_scan": main["bytes"],
        "collective_bytes_scan": main["collective_total"],
        "collective_counts": main["collective_counts"],
    })
    # 2. cost probe (unrolled 1/2-layer variants, extrapolated)
    if probe:
        probes = {}
        for label, pcfg in _probe_cfgs(cfg):
            probes[label] = _compile_once(pcfg, shape_name, mesh, strategy,
                                          unroll=True, want_memory=False)
        ext = _extrapolate(cfg, probes)
        record["hlo_flops"] = ext["flops"]
        record["hlo_bytes"] = ext["bytes"]
        record["collective_bytes_total"] = ext["collective_total"]
        record["probe_compile_s"] = round(
            sum(p["compile_s"] for p in probes.values()), 2)
    else:
        record["hlo_flops"] = main["flops"]
        record["hlo_bytes"] = main["bytes"]
        record["collective_bytes_total"] = main["collective_total"]
    if verbose:
        print(f"[dryrun] {arch} × {shape_name} × {mesh_kind} ({strategy}): "
              f"compile {record['compile_s']}s, "
              f"flops/dev {record['hlo_flops']:.3e}, "
              f"bytes/dev {record['hlo_bytes']:.3e}, "
              f"coll/dev {record['collective_bytes_total']:.3e}, "
              f"state/dev {record['state_bytes_per_device']/2**30:.2f} GiB",
              flush=True)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single_pod", "multi_pod", "both"])
    ap.add_argument("--strategy", default="tp_fsdp")
    ap.add_argument("--out", default="benchmarks/results/dryrun.json")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--no-probe", action="store_true")
    args = ap.parse_args()

    from repro.configs import all_archs
    from repro.models import SHAPES

    archs = all_archs() if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = (["single_pod", "multi_pod"] if args.mesh == "both"
              else [args.mesh])

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results = []
    if os.path.exists(args.out) and not args.force:
        with open(args.out) as f:
            results = json.load(f)
    done = {(r["arch"], r["shape"], r["mesh"], r["strategy"]) for r in results
            if "error" not in r}

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                key = (arch, shape, mesh_kind, args.strategy)
                if key in done:
                    continue
                try:
                    rec = dryrun_one(arch, shape, mesh_kind, args.strategy,
                                     probe=not args.no_probe)
                except Exception as e:
                    failures += 1
                    rec = {"arch": arch, "shape": shape, "mesh": mesh_kind,
                           "strategy": args.strategy, "error": str(e),
                           "traceback": traceback.format_exc()[-2000:]}
                    print(f"[dryrun] FAIL {key}: {e}", flush=True)
                results = [r for r in results
                           if (r["arch"], r["shape"], r["mesh"],
                               r["strategy"]) != key]
                results.append(rec)
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
    print(f"[dryrun] complete: {len(results)} records, {failures} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
