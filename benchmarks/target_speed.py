"""Fast-path JaxTarget interpreter throughput (ROADMAP follow-up).

Measures end-to-end instructions/s of the jitted target under the full
FASE runtime on the GAPBS bc workload, across the interpreter's axes:

  * ``jax_fast``          — batched vector issue + fetch-block cache,
  * ``jax_fast_nocache``  — batched vector issue, walk every fetch,
  * ``jax_slow``          — the scalar one-instruction-per-iteration
    reference loop (the pre-fast-path state of the world),
  * ``pysim``             — the pure-Python twin, for context,
  * ``fleet_vmap_x4``     — four boards over ONE stacked vmapped state,
    lockstep global chunks, a single XLA dispatch per chunk
    (``FleetTarget.run_global``): the fleet-aggregate throughput row.

Each backend executes the same boot + measurement window (modelled-tick
slices through ``run_slice``, so the workload is identical down to the
tick); wall time covers only the measurement window, never jit compile.
``--quick`` shrinks the graph and windows and *fails* (exit 1) if the
fast path does not at least match the slow path, or regresses below the
checked-in ``results/target_speed.json`` baseline — the CI smoke gate.

Oracle timing mode keeps the host loop out of the measurement: no
modelled link stalls, so retired instructions dominate the wall clock
and instructions/s compares interpreters, not channel models.

Where the single-board fast path lands (measured on the reference
container, XLA:CPU): the compiled substep retires at most one
instruction per live lane and costs ~7us at 4 lanes regardless of how
many lanes retire, so throughput is (live lanes) x (substep rate).
GAPBS bc sustains only ~1.4 simultaneously-live lanes of 4 even in its
parallel phase (per-core tick split: executing / stalled on staggered
modelled syscall costs / parked on futexes), which caps the fast path
below the event-driven PySim (~2.1us per *retired* instruction, and it
skips idle ticks outright; the break-even is ~2.2 live lanes).
Raising the core count does not help: at 8 cores/8 threads bc's
per-core occupancy halves (futex contention) and aggregate ips
*drops*.

The fleet row is where dispatch amortization pays: N boards advance in
ONE compiled flat machine per global chunk, so fleet-aggregate ips
beats N sequential single-board runs (~1.5x one board at N=4) without
touching per-board modelled timing (the lockstep driver is bit-exact,
``tests/test_cpu_differential.py``).  Two measured walls bound it:
``jax.vmap`` of the chunk loop is ~14x worse than the flat-lane kernel
(a batched ``while_loop`` select-merges the entire carry — memory
images included — every iteration), and the flat kernel's same-tick
conflict matrices are (L, L) in the total lane count, so the per-tick
cost grows superlinearly past ~32 lanes (measured ~25/41/107 us per
tick at 16/32/64 lanes): fleet aggregate peaks around N=8 boards of 4
cores at ~0.6x PySim's sustained rate on bc.  Full (non-quick) runs
therefore measure the *sustained parallel phase* (warm past the serial
graph-load prefix); the whole-run quick gate keeps covering boot.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from .common import load_json, save_json
from repro.compile_cache import enable_compile_cache
from repro.configs.fase_rocket import target_kwargs
from repro.configs.registry import FASE_ROCKET
from repro.core.interface import JaxTarget
from repro.core.runtime import FaseRuntime
from repro.core.target.pysim import PySim
from repro.core.workloads import build, graphgen

THREADS = 4
N_CORES = 4
MEM = 1 << 23
FLEET_DEVICES = 4
#: the registry target config is the baseline; each row overrides one axis
CFG = target_kwargs(FASE_ROCKET)


def _instret(tgt):
    return sum(tgt.get_instret(c) for c in range(tgt.n_cores))


def _measure(name, make_target, g, warm_ticks, meas_ticks):
    tgt = make_target()
    rt = FaseRuntime(tgt, mode="oracle")
    rt.load(build("bc"), ["bc", "g.bin", str(THREADS), "1"],
            files={"g.bin": g})
    paused = rt.run_slice(warm_ticks, max_ticks=1 << 40)   # compile + boot
    t0, i0 = tgt.get_ticks(), _instret(tgt)
    finished = paused is not None
    wall = 0.0
    if not finished:
        w0 = time.time()
        rep = rt.run_slice(t0 + meas_ticks, max_ticks=1 << 40)
        wall = time.time() - w0
        finished = rep is not None
    insts = _instret(tgt) - i0
    ips = insts / wall if wall > 0 else 0.0
    row = dict(name=name, instructions=insts, wall_s=round(wall, 3),
               ips=round(ips, 1), ticks=tgt.get_ticks() - t0,
               finished=finished)
    print(f"target_speed,{name},{ips:.0f},instr={insts} "
          f"wall={wall:.2f}s", flush=True)
    return row


def _measure_fleet(g, warm_ticks, meas_ticks, n_devices=FLEET_DEVICES):
    """Aggregate throughput of ``n_devices`` boards running the bc
    workload concurrently over one stacked vmapped state — every global
    chunk of the measurement loop is a single XLA dispatch."""
    from repro.core.fleet.vmap import FleetTarget

    cfg = {k: v for k, v in CFG.items() if k != "fast_path"}
    ft = FleetTarget(n_devices, N_CORES, MEM, **cfg)
    rts = []
    for d in range(n_devices):
        rt = FaseRuntime(ft.view(d), mode="oracle")
        rt.load(build("bc"), ["bc", "g.bin", str(THREADS), "1"],
                files={"g.bin": g})
        rts.append(rt)
    for rt in rts:                                  # compile + boot (one-hot)
        rt.run_slice(warm_ticks, max_ticks=1 << 40)
    base = [(rt.target.get_ticks(), _instret(rt.target)) for rt in rts]
    d0 = ft.dispatch_count
    live = [True] * n_devices
    budgets = np.zeros(n_devices, np.uint64)
    w0 = time.time()
    while any(live):                    # lockstep: one dispatch per chunk
        budgets[:] = 0
        for d, rt in enumerate(rts):
            if not live[d]:
                continue
            if rt.target.get_ticks() - base[d][0] >= meas_ticks:
                live[d] = False
                continue
            want = rt.chunk_begin()
            if want is None:
                live[d] = False
            elif want:
                budgets[d] = rt.target.chunk_cycles
        if budgets.any():
            ft.run_global(budgets)
            for d, rt in enumerate(rts):
                if budgets[d]:
                    rt.chunk_end()
    wall = time.time() - w0
    insts = sum(_instret(rt.target) - b[1] for rt, b in zip(rts, base))
    ips = insts / wall if wall > 0 else 0.0
    row = dict(name=f"fleet_vmap_x{n_devices}", instructions=insts,
               wall_s=round(wall, 3), ips=round(ips, 1),
               ticks=max(rt.target.get_ticks() - b[0]
                         for rt, b in zip(rts, base)),
               dispatches=ft.dispatch_count - d0,
               n_devices=n_devices, finished=True)
    print(f"target_speed,fleet_vmap_x{n_devices},{ips:.0f},instr={insts} "
          f"wall={wall:.2f}s dispatches={row['dispatches']}", flush=True)
    return row


def run(quick: bool = False):
    try:
        baseline = load_json("target_speed.json")
    except OSError:
        baseline = None
    scale = 5 if quick else 9
    g = graphgen.rmat(scale, 8, weights=True)
    fast_meas = 100_000 if quick else 400_000
    slow_meas = 8_000 if quick else 12_000
    # full mode warms past bc's serial graph-load prefix (~60k modelled
    # ticks at rmat9) so the window is the sustained parallel phase —
    # the interpreter comparison the docstring analysis is about; quick
    # mode keeps the whole-run window as the CI boot-coverage gate
    warm = 3_000 if quick else 60_000
    rows = [
        _measure("jax_fast",
                 lambda: JaxTarget(N_CORES, MEM, **CFG),
                 g, warm, fast_meas),
        _measure("jax_fast_nocache",
                 lambda: JaxTarget(N_CORES, MEM,
                                   **{**CFG, "block_cache": False}),
                 g, warm, fast_meas),
        _measure("jax_slow",
                 lambda: JaxTarget(N_CORES, MEM,
                                   **{**CFG, "fast_path": False}),
                 g, warm, slow_meas),
        _measure("pysim", lambda: PySim(N_CORES, MEM),
                 g, warm, 4_000_000 if quick else 16_000_000),
        _measure_fleet(g, warm, fast_meas),
    ]
    by = {r["name"]: r for r in rows}
    speedup = by["jax_fast"]["ips"] / max(by["jax_slow"]["ips"], 1e-9)
    fleet = by[f"fleet_vmap_x{FLEET_DEVICES}"]
    fleet_vs_seq = fleet["ips"] / max(by["jax_fast"]["ips"], 1e-9)
    out = dict(quick=quick, workload=f"bc rmat{scale} {THREADS}T",
               warm_ticks=warm, n_cores=N_CORES, rows=rows,
               fast_vs_slow_speedup=round(speedup, 2),
               fleet_aggregate_vs_one_board=round(fleet_vs_seq, 2))
    save_json("target_speed.json", out)
    print(f"target_speed,speedup,{speedup:.1f},fast_vs_slow", flush=True)
    print(f"target_speed,fleet_agg,{fleet_vs_seq:.2f},vs_one_board",
          flush=True)
    if quick and speedup < 1.0:
        print("target_speed: FAST PATH SLOWER THAN SLOW PATH", flush=True)
        sys.exit(1)
    # regression gate vs the checked-in baseline: the fast-vs-slow ratio
    # is host-speed-invariant (same process, same windows), but quick
    # mode's smaller graph and window land lower than a full run's, so
    # a full-mode baseline gets extra slack
    if quick and baseline and baseline.get("fast_vs_slow_speedup"):
        ref = baseline["fast_vs_slow_speedup"]
        floor = ref * (0.5 if baseline.get("quick") else 0.25)
        if speedup < floor:
            print(f"target_speed: SPEEDUP {speedup:.1f} REGRESSED BELOW "
                  f"BASELINE FLOOR {floor:.1f} (baseline {ref:.1f})",
                  flush=True)
            sys.exit(1)
    return out


if __name__ == "__main__":
    enable_compile_cache()
    run(quick="--quick" in sys.argv)
