"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production meshes and extract the roofline terms.

This proves — without hardware — that the distribution config is
coherent: shardings are consistent, the program partitions, nothing OOMs
at compile, and the collective schedule is what DESIGN.md promises.

Usage:
  python -m repro.launch.dryrun --arch llama3.2-3b --shape train_4k
  python -m repro.launch.dryrun --all --mesh both --out dryrun.json

``main`` puts this process on the CPU backend with 512 placeholder
devices before JAX initialises a backend: the dry-run never claims an
attached accelerator, and importing this module changes nothing.
"""
import argparse
import json
import re
import sys
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ASSIGNED, get_config
from ..dlrt import distributed as D
from ..models import model
from ..optim import sgd
from . import hlo_cost
from . import shapes as S
from .mesh import (HBM_BW, ICI_BW, PEAK_FLOPS_BF16, make_production_mesh,
                   mesh_chips)

# ---------------------------------------------------------------------------
# HLO collective parsing (cost_analysis has no collective bytes).
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "c64": 8, "c128": 16,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "f8e4m3fn": 1, "f8e5m2": 1, "s8": 1, "u8": 1, "pred": 1,
}
_SHAPE_RE = re.compile(
    r"(f64|f32|bf16|f16|f8e4m3fn|f8e5m2|s64|s32|s16|s8|u64|u32|u16|u8|"
    r"pred|c64|c128)\[([0-9,]*)\]")
_COLL_RE = re.compile(
    r"=\s*(.*?)\s*(all-gather-start|all-gather|all-reduce-start|"
    r"all-reduce|reduce-scatter|all-to-all|collective-permute-start|"
    r"collective-permute)\(")

# bytes-per-device weight per collective kind (ring model):
#   all-gather: receives (k-1)/k of result  ~ 1x result bytes
#   all-reduce: reduce-scatter + all-gather ~ 2x bytes
#   reduce-scatter / all-to-all / permute   ~ 1x
_WEIGHT = {"all-reduce": 2.0, "all-reduce-start": 2.0}


def _shape_bytes(type_text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo: str) -> Dict[str, Any]:
    """Sum result bytes of every collective op in the (post-SPMD,
    per-device) HLO.  Returns totals per kind + the weighted roofline
    byte count."""
    per_kind: Dict[str, int] = {}
    count: Dict[str, int] = {}
    weighted = 0.0
    for m in _COLL_RE.finditer(hlo):
        result_type, kind = m.group(1), m.group(2)
        b = _shape_bytes(result_type)
        base = kind.replace("-start", "")
        per_kind[base] = per_kind.get(base, 0) + b
        count[base] = count.get(base, 0) + 1
        weighted += _WEIGHT.get(kind, 1.0) * b
    return {"bytes_per_kind": per_kind, "count_per_kind": count,
            "weighted_bytes": int(weighted)}


# ---------------------------------------------------------------------------
# Step assembly per (arch, shape, mesh).
# ---------------------------------------------------------------------------

def _input_shardings(mesh, cfg, n_nodes, specs):
    b_node = specs["tokens"].shape[1]
    base = D.batch_sharding(mesh, cfg, n_nodes, b_node)

    def one(path, leaf):
        if leaf.ndim == 0:
            return D.replicated(mesh)
        return NamedSharding(
            mesh, P(*(tuple(base.spec) + (None,) * (leaf.ndim - 3))))
    return jax.tree_util.tree_map_with_path(one, specs)


def build_lowered(arch: str, shape_name: str, multi_pod: bool):
    """Returns (lowered, info) or (None, skip_record)."""
    cfg0 = get_config(arch)
    spec = S.SHAPES[shape_name]
    skip = S.skip_reason(cfg0, spec)
    if skip:
        return None, {"arch": arch, "shape": shape_name,
                      "multi_pod": multi_pod, "skipped": skip}
    cfg, n_nodes, window, meta = S.shape_config(cfg0, spec,
                                                multi_pod=multi_pod)
    mesh = make_production_mesh(multi_pod=multi_pod)
    specs = S.input_specs(cfg, spec, n_nodes)
    info = {"arch": arch, "shape": shape_name, "n_nodes": n_nodes,
            "multi_pod": multi_pod, "policy": cfg.sharding_policy,
            **meta}

    with jax.set_mesh(mesh):
        if spec.kind == "train":
            opt = sgd(1e-2)        # paper-faithful plain SGD (Alg. 2 l.4)
            mb = S.TRAIN_MICROBATCH.get(arch)
            state_shape = D.abstract_train_state(cfg, opt, n_nodes)
            state_sh = D.train_state_sharding(mesh, cfg, state_shape)
            step = D.make_train_step(cfg, opt, D.MorphHParams(),
                                     microbatch=mb, do_topology=True,
                                     window=window)
            jitted = jax.jit(step,
                             in_shardings=(state_sh,
                                           _input_shardings(mesh, cfg,
                                                            n_nodes, specs)),
                             out_shardings=(state_sh, None))
            lowered = jitted.lower(state_shape, specs)
            info["tokens_per_step"] = (spec.global_batch
                                       * specs["tokens"].shape[-1])
        elif spec.kind == "prefill":
            params_shape = D.abstract_stacked_params(cfg, n_nodes)
            params_sh = D.params_sharding(mesh, cfg, params_shape)

            def prefill(params, batch):
                def one(p, b):
                    return model.forward(p, b, cfg, window=window,
                                         last_only=True)[0]
                return jax.vmap(one)(params, batch)

            jitted = jax.jit(prefill,
                             in_shardings=(params_sh,
                                           _input_shardings(mesh, cfg,
                                                            n_nodes, specs)))
            lowered = jitted.lower(params_shape, specs)
            info["tokens_per_step"] = (spec.global_batch
                                       * specs["tokens"].shape[-1])
        else:  # decode
            b_node = spec.global_batch // n_nodes
            clen = S.cache_len(cfg, spec, window)
            params_shape = D.abstract_stacked_params(cfg, n_nodes)
            params_sh = D.params_sharding(mesh, cfg, params_shape)
            cache_shape = D.abstract_cache(cfg, n_nodes, b_node, clen)
            cache_sh = D.cache_sharding(mesh, cfg, cache_shape)
            serve = D.make_serve_step(
                cfg, window=window,
                kv_spec=D.serve_kv_spec(mesh, cfg, b_node))
            tok_sh = NamedSharding(
                mesh, P(*(tuple(D.batch_sharding(mesh, cfg, n_nodes,
                                                 b_node).spec)[:2]
                          + (None,))))
            jitted = jax.jit(serve,
                             in_shardings=(params_sh, cache_sh, tok_sh,
                                           D.replicated(mesh)))
            lowered = jitted.lower(
                params_shape, cache_shape,
                jax.ShapeDtypeStruct((n_nodes, b_node, 1), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32))
            info["cache_len"] = clen
            info["tokens_per_step"] = spec.global_batch
        info["active_params"] = cfg0.active_param_count()
        info["total_params"] = cfg0.param_count()
        info["chips"] = mesh_chips(mesh)
        info["kind"] = spec.kind
    return lowered, info


# ---------------------------------------------------------------------------
# Roofline extraction.
# ---------------------------------------------------------------------------

def analyse(lowered, info: Dict[str, Any]) -> Dict[str, Any]:
    t0 = time.time()
    compiled = lowered.compile()
    info["compile_s"] = round(time.time() - t0, 1)

    # raw XLA numbers (while bodies counted ONCE — reference only)
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    info["xla_cost_raw"] = {"flops": float(cost.get("flops", 0.0)),
                            "bytes": float(cost.get("bytes accessed", 0.0))}

    try:
        mem = compiled.memory_analysis()
        info["memory"] = {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "peak_bytes": int(mem.argument_size_in_bytes
                              + mem.temp_size_in_bytes),
        }
    except Exception as e:                      # CPU backend variations
        info["memory"] = {"error": str(e)}

    # trip-count-corrected cost model over the post-SPMD HLO
    hlo = hlo_cost.analyse_hlo(compiled.as_text())
    flops = hlo["flops"]
    bytes_accessed = hlo["bytes"]
    info["collectives"] = {
        "bytes_per_kind": hlo["collective_per_kind"],
        "count_per_kind": hlo["collective_counts"],
        "weighted_bytes": hlo["collective_bytes"],
        "unknown_trip_whiles": hlo["unknown_trip_whiles"],
    }

    # Roofline terms (per chip; the HLO is the post-SPMD per-device
    # program, so these are per-chip step times).
    compute_s = flops / PEAK_FLOPS_BF16
    memory_s = bytes_accessed / HBM_BW
    collective_s = hlo["collective_bytes"] / ICI_BW
    dominant = max((("compute", compute_s), ("memory", memory_s),
                    ("collective", collective_s)), key=lambda kv: kv[1])[0]
    mult = 6 if info["kind"] == "train" else 2
    model_flops = (mult * info["active_params"]
                   * info.get("tokens_per_step", 0)) / info["chips"]
    info["roofline"] = {
        "hlo_flops_per_chip": flops,
        "hlo_bytes_per_chip": bytes_accessed,
        "collective_bytes_per_chip": hlo["collective_bytes"],
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
        "dominant": dominant,
        "model_flops_per_chip": model_flops,
        "useful_flop_ratio": (model_flops / flops) if flops else 0.0,
    }
    return info


def run_one(arch: str, shape_name: str, multi_pod: bool) -> Dict[str, Any]:
    lowered, info = build_lowered(arch, shape_name, multi_pod)
    if lowered is None:
        return info
    return analyse(lowered, info)


def main(argv=None):
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 512)
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all",
                    help="architecture id or 'all'")
    ap.add_argument("--shape", default="all",
                    help="input shape name or 'all'")
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--out", default=None, help="write JSON records here")
    args = ap.parse_args(argv)

    archs = list(ASSIGNED) if args.arch == "all" else [args.arch]
    shapes = list(S.SHAPES) if args.shape == "all" else [args.shape]
    pods = {"single": [False], "multi": [True],
            "both": [False, True]}[args.mesh]

    records = []
    failures = 0
    for arch in archs:
        for shape_name in shapes:
            for mp in pods:
                tag = f"{arch} x {shape_name} x " \
                      f"{'multi' if mp else 'single'}-pod"
                try:
                    rec = run_one(arch, shape_name, mp)
                except Exception as e:
                    failures += 1
                    rec = {"arch": arch, "shape": shape_name,
                           "multi_pod": mp, "error": repr(e)[:500]}
                    print(f"[FAIL] {tag}: {rec['error']}", flush=True)
                else:
                    if "skipped" in rec:
                        print(f"[SKIP] {tag}: {rec['skipped']}", flush=True)
                    else:
                        r = rec["roofline"]
                        print(f"[ OK ] {tag}: compile={rec['compile_s']}s "
                              f"compute={r['compute_s']*1e3:.1f}ms "
                              f"memory={r['memory_s']*1e3:.1f}ms "
                              f"collective={r['collective_s']*1e3:.1f}ms "
                              f"dominant={r['dominant']} "
                              f"useful={r['useful_flop_ratio']:.2f}",
                              flush=True)
                records.append(rec)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)
        print(f"wrote {len(records)} records to {args.out}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
