"""Chip smoke test: FASE's main path once on one TPU, checked against PySim.

    python chip_smoke.py

Everything runs in this one process, at ``FASE_ROCKET`` width (4 cores,
64 MiB memory image), through the entry points a user calls:

  device    fail unless JAX's first device is a TPU (there is no CPU
            fallback); print its kind and the device count;
  coremark  one CoreMark iteration, 1 thread, on ``JaxTarget`` over the UART
            (``FASE_ROCKET``), ``FaseRuntime(mode="fase")``;
  bc        GAPBS bc, 4 threads, on ``graphgen.rmat(14, 16)`` (degree
            16 is GAPBS's own generator default) over PCIe
            (``FASE_ROCKET_PCIE``);
  fleet     4 boards of ``FASE_FLEET_VMAP`` on the one chip, stepped by
            ``FleetRuntime.run_synchronous``: boards 0 and 2 run the
            CoreMark job over the UART, boards 1 and 3 the bc job over
            PCIe.  The config puts every board on PCIe; the links are set
            here so each board matches its solo run.

Each phase runs the same jobs on ``PySim`` and requires stdout, ticks,
per-core instret and ``traffic_total`` to be bit-identical; CoreMark's
own CRC must match.  In the fleet every kernel dispatch must advance
every board whose job is still running, so that there is one dispatch
per global chunk.  Any mismatch or exception exits non-zero.

Per phase it prints compile seconds (JAX tracing, lowering and XLA
compilation, from ``jax.monitoring``), run seconds (the rest of the
wall time from ``FaseRuntime.load`` to guest exit, ending in a device
sync), guest instructions and ``peak_bytes_in_use``.  These are a smoke
reading, not a benchmark.  The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import gc
import json
import sys
import time
from pathlib import Path

#: CoreMark-lite's checksum of one iteration (``coremark_crc`` sums it)
COREMARK_CRC = 16356
COREMARK_ITERS = 1
#: GAPBS's Kronecker generator default degree
GAPBS_DEGREE = 16
#: rmat scale of the bc graph: the largest that keeps the whole script
#: within about 10 minutes on one v5e (readings in PERF.md, Findings)
BC_SCALE = 14
BC_THREADS = 4
MAX_TICKS = 1 << 44
COMPARED = ("stdout", "ticks", "instret", "traffic_total")


def device_check() -> dict:
    import jax
    devs = jax.devices()
    dev = dict(platform=devs[0].platform, kind=devs[0].device_kind,
               count=len(devs))
    print(f"device: {dev}", flush=True)
    if dev["platform"] != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev['platform']}")
    return dev


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling.  A nested jit
    is traced inside its caller and never lowered alone, so only the
    trace of a function that is then lowered counts."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        self._traced: dict = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, fun_name="", **_):
        if event == self.TRACE:
            self._traced[fun_name] = secs
        elif event == self.LOWER:
            name = fun_name[4:-1] if fun_name.startswith("jit(") \
                else fun_name
            self.seconds += secs + self._traced.pop(name, 0.0)
        elif event == self.COMPILE:
            self.seconds += secs


def peak_bytes() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


def run_job(target, runtime_kwargs, name, argv, files=None):
    from repro.core.runtime import FaseRuntime
    from repro.core.workloads import build
    rt = FaseRuntime(target, mode="fase", **runtime_kwargs)
    rt.load(build(name), [name] + argv, files=files or {})
    return rt.run(max_ticks=MAX_TICKS)


def check_same(ref, got, what):
    for field in COMPARED:
        a, b = getattr(ref, field), getattr(got, field)
        if a != b:
            raise AssertionError(
                f"{what}: {field} differs from PySim: {a!r} != {b!r}")


def check_coremark(rep):
    kv = dict(line.split() for line in rep.stdout.decode().splitlines())
    crc = int(kv["coremark_crc"])
    if crc != COREMARK_CRC * COREMARK_ITERS:
        raise AssertionError(f"coremark_crc {crc} != "
                             f"{COREMARK_CRC} x {COREMARK_ITERS}")


def report(phase, clock, c0, wall, instr, pysim_s, extra=None):
    compile_s = clock.seconds - c0
    run_s = wall - compile_s
    line = dict(phase=phase, compile_s=round(compile_s, 3),
                run_s=round(run_s, 3), guest_instr=instr,
                instr_per_run_s=round(instr / run_s),
                pysim_s=pysim_s and round(pysim_s, 3),
                peak_bytes_in_use=peak_bytes(),
                identical_to_pysim=list(COMPARED), **(extra or {}))
    print("phase (smoke reading, not a benchmark): " + json.dumps(line),
          flush=True)


def solo_phase(phase, clock, cfg, name, argv, files=None):
    """One job on ``JaxTarget`` and on ``PySim``; returns the PySim
    report after checking the JAX run against it."""
    import jax
    from repro.configs.fase_rocket import runtime_kwargs, target_kwargs
    from repro.core.interface import JaxTarget
    from repro.core.target.pysim import PySim

    rkw = runtime_kwargs(cfg)
    t0 = time.perf_counter()
    ref = run_job(PySim(cfg["n_cores"], cfg["mem_bytes"]), rkw, name,
                  argv, files)
    pysim_s = time.perf_counter() - t0
    tgt = JaxTarget(cfg["n_cores"], cfg["mem_bytes"], **target_kwargs(cfg))
    jax.block_until_ready(tgt.st)
    c0, t0 = clock.seconds, time.perf_counter()
    rep = run_job(tgt, rkw, name, argv, files)
    jax.block_until_ready(tgt.st)
    wall = time.perf_counter() - t0
    check_same(ref, rep, phase)
    report(phase, clock, c0, wall, sum(rep.instret), pysim_s,
           dict(ticks=rep.ticks, instret=rep.instret,
                traffic_total=rep.traffic_total))
    return ref


def fleet_phase(clock, jobs, refs):
    """``jobs[i]`` on board ``i`` of a 4-board single-dispatch fleet;
    every board must reproduce its solo PySim report ``refs[i]``."""
    import jax
    import numpy as np
    from repro.configs.fase_rocket import fleet_kwargs
    from repro.configs.registry import FASE_FLEET_VMAP
    from repro.core.fleet import FleetRuntime
    from repro.core.target import cpu

    links = [j[0] for j in jobs]
    fleet = FleetRuntime(**{**fleet_kwargs(FASE_FLEET_VMAP),
                            "links": links})
    ft = fleet.fleet_target
    assert ft.n_devices == len(jobs)
    jax.block_until_ready(ft.sts)
    advanced = []        # per kernel dispatch: which boards had cycles
    kernel = cpu.run_chunk_fleet

    def counted(sts, n_cores, mem_bytes, budgets, *args):
        advanced.append(np.asarray(budgets) != 0)
        return kernel(sts, n_cores, mem_bytes, budgets, *args)

    cpu.run_chunk_fleet = counted
    try:
        c0, t0 = clock.seconds, time.perf_counter()
        res = fleet.run_synchronous([j[1] for j in jobs],
                                    max_ticks=MAX_TICKS)
        jax.block_until_ready(ft.sts)
        wall = time.perf_counter() - t0
    finally:
        cpu.run_chunk_fleet = kernel
    for i, (r, ref) in enumerate(zip(res, refs)):
        check_same(ref, r.report, f"fleet board {i}")
    # A board's job runs from the first dispatch to the last that gave it
    # cycles.  One dispatch per global chunk means every dispatch advanced
    # exactly the boards still running, so the dispatches number as many
    # as the chunks of the longest job.
    adv = np.array(advanced)
    last = np.array([np.flatnonzero(col).max() for col in adv.T])
    running = np.arange(len(adv))[:, None] <= last[None, :]
    chunks = adv.sum(axis=0)
    if not (adv == running).all():
        k = int(np.flatnonzero((adv != running).any(axis=1))[0])
        raise AssertionError(
            f"fleet: dispatch {k} advanced boards {np.flatnonzero(adv[k])}"
            f" while boards {np.flatnonzero(running[k])} were running")
    if not len(adv) == ft.dispatch_count == chunks.max():
        raise AssertionError(
            f"fleet: {len(adv)} kernel dispatches, dispatch_count "
            f"{ft.dispatch_count}, longest job {chunks.max()} chunks")
    report("fleet", clock, c0, wall,
           sum(sum(r.report.instret) for r in res), None,
           dict(links=links, global_chunks=int(chunks.max()),
                dispatches=len(adv), chunks_per_board=chunks.tolist(),
                ticks=[r.report.ticks for r in res]))


def main() -> None:
    dev = device_check()

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.compile_cache import enable_compile_cache
    from repro.configs.registry import FASE_ROCKET, FASE_ROCKET_PCIE
    from repro.core.fleet import Job
    from repro.core.workloads import graphgen

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    clock = CompileClock()
    cm_argv = [str(COREMARK_ITERS)]
    cm_ref = solo_phase("coremark", clock, FASE_ROCKET, "coremark", cm_argv)
    check_coremark(cm_ref)
    gc.collect()

    print(f"bc graph: rmat scale {BC_SCALE}, degree {GAPBS_DEGREE}",
          flush=True)
    graph = {"g.bin": graphgen.rmat(BC_SCALE, GAPBS_DEGREE, weights=True)}
    bc_argv = ["g.bin", str(BC_THREADS), "1"]
    bc_ref = solo_phase("bc", clock, FASE_ROCKET_PCIE, "bc", bc_argv, graph)
    gc.collect()

    fleet_phase(clock, [job for _ in range(2) for job in (
        ("uart", Job("coremark", cm_argv)),
        ("pcie", Job("bc", bc_argv, files=graph)))], [cm_ref, bc_ref] * 2)
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
