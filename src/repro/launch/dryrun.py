import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: for each cell
the jitted step function is ``.lower().compile()``d against
ShapeDtypeStruct inputs on the production mesh; memory_analysis() proves it
fits, cost_analysis() + HLO collective parsing feed the roofline
(EXPERIMENTS.md §Roofline).

Usage:
  python -m repro.launch.dryrun [--arch qwen3-8b] [--shape train_4k]
      [--multi-pod] [--all] [--out results.json]
"""
import argparse
import json
import re
import sys
import time

import jax

from ..configs import CONFIGS
from ..distributed import sharding as sh
from ..launch import steps as st
from ..launch.mesh import make_production_mesh

# TPU v5e-ish hardware constants (assignment)
PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # B/s per chip
ICI_BW = 50e9                # B/s per link

_COLL_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"[^=]*=\s*(\w+)\[([0-9,{}\[\]]*)\]")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def collective_bytes(hlo_text: str) -> dict:
    """Sum output-shape bytes of every collective op in the (scheduled)
    HLO, grouped by op kind.  Shapes inside while bodies count once per
    textual occurrence; scan-based layer stacks therefore report per-layer
    bytes x trip count via the while loop's repeated execution — we scale
    by trip count when the op sits in a while body (approximated by the
    dominant scan length parsed from the caller)."""
    out = {}
    for m in _COLL_RE.finditer(hlo_text):
        kind, dtype, dims = m.group(1), m.group(2), m.group(3)
        if dtype not in _DTYPE_BYTES:
            continue
        nums = [int(x) for x in re.findall(r"\d+", dims.split("{")[0])]
        n = 1
        for x in nums:
            n *= x
        out[kind] = out.get(kind, 0) + n * _DTYPE_BYTES[dtype]
    return out


def _scan_trip_count(cfg) -> int:
    from ..models.core import n_scan_steps
    return n_scan_steps(cfg)


def run_cell(arch: str, shape: str, multi_pod: bool,
             verbose: bool = True, variant: str = "baseline") -> dict:
    """variant: baseline | tp_serve (decode without FSDP param gathers) |
    dp_only (no tensor parallelism) | microN (train grad-accum N)."""
    cfg = CONFIGS[arch]
    ok, why = st.cell_supported(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape, "status": why}
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    specs = st.input_specs(cfg, shape)
    kind = st.SHAPES[shape]["kind"]
    t0 = time.time()
    from jax.sharding import PartitionSpec as P
    policy = variant if variant in ("dp_only", "tp_only") else "fsdp_tp"
    act_spec = P(sh.dp_axis(mesh), "model", None)         if policy != "dp_only" else None
    n_micro = 8 if st.SHAPES[shape]["batch"] >= 8 * sh.dp_size(mesh) else 1
    if variant.startswith("micro"):
        n_micro = int(variant[5:])
    serve_fsdp = variant != "tp_serve"
    with mesh:
        if kind == "train":
            fn = st.make_train_step(cfg, n_micro=n_micro,
                                    act_spec=act_spec)
            pspec = sh.param_specs(cfg, mesh, policy=policy)
            in_shardings = (
                sh.make_shardings(mesh, pspec),
                sh.make_shardings(
                    mesh, {"m": pspec, "v": pspec,
                           "step": jax.sharding.PartitionSpec()}),
                sh.make_shardings(
                    mesh, sh.batch_specs(cfg, mesh,
                                         "prefix_embeds" in specs["batch"],
                                         policy=policy)),
            )
            args = (specs["params"], specs["opt_state"], specs["batch"])
        elif kind == "prefill":
            fn = st.make_prefill_step(cfg, act_spec=act_spec)
            bspec = {"tokens":
                     jax.sharding.PartitionSpec(sh.dp_axis(mesh), None)}
            if "prefix_embeds" in specs["batch"]:
                bspec["prefix_embeds"] = jax.sharding.PartitionSpec(
                    sh.dp_axis(mesh), None, None)
            in_shardings = (
                sh.make_shardings(mesh,
                                  sh.param_specs(cfg, mesh, policy=policy)),
                sh.make_shardings(mesh, bspec),
            )
            args = (specs["params"], specs["batch"])
        else:
            fn = st.make_decode_step(cfg)
            in_shardings = (
                sh.make_shardings(
                    mesh, sh.param_specs(cfg, mesh, fsdp=serve_fsdp,
                                         policy=policy)),
                sh.make_shardings(
                    mesh, sh.decode_state_specs(cfg, mesh, specs["state"])),
                sh.make_shardings(
                    mesh, jax.sharding.PartitionSpec(
                        sh.dp_for(mesh, st.SHAPES[shape]["batch"]))),
            )
            args = (specs["params"], specs["state"], specs["tokens"])

        jitted = jax.jit(fn, in_shardings=in_shardings)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    # XLA cost_analysis counts while-loop bodies ONCE; the layer stack is a
    # scan (and train adds a microbatch scan), so scale by the static trip
    # counts.  Out-of-loop ops (embeds/logits) are amortised into the
    # multiplier.
    trip_mult = _scan_trip_count(cfg)
    if kind == "train":
        trip_mult *= max(n_micro, 1)
    flops = float(cost.get("flops", 0.0)) * trip_mult
    bytes_accessed = float(cost.get("bytes accessed", 0.0)) * trip_mult
    res = {
        "arch": arch, "shape": shape, "status": "OK", "variant": variant,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": int(n_chips),
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "per_device": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "peak_bytes": (getattr(mem, "argument_size_in_bytes", 0) +
                           getattr(mem, "temp_size_in_bytes", 0)),
        },
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": bytes_accessed,
        "collective_bytes_per_device": coll,
        "scan_trip_count": _scan_trip_count(CONFIGS[arch]),
        "trip_mult": trip_mult,
        "n_micro": n_micro if kind == "train" else 1,
    }
    # roofline terms (per §Roofline: per-chip quantities over per-chip rates)
    coll_total = sum(coll.values()) * trip_mult
    res["roofline"] = {
        "compute_s": flops / PEAK_FLOPS,
        "memory_s": bytes_accessed / HBM_BW,
        "collective_s": coll_total / ICI_BW,
    }
    dom = max(res["roofline"], key=res["roofline"].get)
    res["roofline"]["dominant"] = dom
    if verbose:
        r = res["roofline"]
        print(f"[{res['mesh']}] {arch:26s} {shape:12s} "
              f"compile={t_compile:6.1f}s peak/dev="
              f"{res['per_device']['peak_bytes']/2**30:7.2f}GiB "
              f"comp={r['compute_s']*1e3:8.2f}ms "
              f"mem={r['memory_s']*1e3:8.2f}ms "
              f"coll={r['collective_s']*1e3:8.2f}ms  dom={dom}",
              flush=True)
    return res


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--variant", default="baseline")
    args = ap.parse_args(argv)

    archs = [args.arch] if args.arch else list(CONFIGS)
    shapes = [args.shape] if args.shape else list(st.SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    results = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    results.append(run_cell(arch, shape, mp,
                                            variant=args.variant))
                except Exception as e:  # noqa: BLE001 - report, keep going
                    results.append({"arch": arch, "shape": shape,
                                    "mesh": "2x16x16" if mp else "16x16",
                                    "status": f"FAIL: {type(e).__name__}: "
                                              f"{str(e)[:300]}"})
                    print(results[-1], file=sys.stderr, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    bad = [r for r in results if r["status"].startswith("FAIL")]
    print(f"dry-run: {len(results)} cells, {len(bad)} failures", flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
