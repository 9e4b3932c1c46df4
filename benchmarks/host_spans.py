"""Host time by layer, from the program's own spans (``repro.core.spans``).

    python -m benchmarks.host_spans [--job bc|coremark] [--scale 9]
        [--mem-mib 64] [--seed 1] [--boards N [--chips K]] [--slice]
        [--save PATH]

Jobs of a cell-sized deployment on ``JaxTarget``: GAPBS bc, 4 threads, 1
trial, on an ``rmat(scale, 16)`` weighted Kronecker graph over the
modelled PCIe link (``FASE_ROCKET_PCIE``), or CoreMark-lite, 1 iteration,
1 thread, over the UART (``FASE_ROCKET``).  With ``--boards N`` a "job"
is one lockstep round of a fleet instead (``FASE_FLEET_VMAP``,
``FleetRuntime.run_synchronous``), a job a board, bc's graphs drawn from
``seed + board``, its stack sharded over ``--chips K`` devices when given
(``FASE_FLEET_SHARDED``).  A warm-up job compiles every
program and a second job runs untraced; a third is recorded whole by the
profiler with the TPU tracer in its light mode (host events only), as
the cell benchmark records its first unit (``bench/fasebench``).  One
JSON line holds, per thousand guest instructions (``_ms_per_kinstr``):

  chunk_wait      host wall inside ``fase:chunk`` (launch to record read)
  between_chunks  the cell benchmark's reading of the same job: wall
                  outside the waits on chunks as its launch events infer
                  them (``bench/metrics/between_chunks_ms_per_kinstr.py``)
  runtime_self    self time of the ``fase:rt:`` spans
  session_self    self time of the ``fase:sess:`` spans
  accessor_host   wall of the ``fase:acc:``/``fase:sync:`` spans outside
                  every ``fase:chunk``

and ``layer_share`` (the three layers over ``between_chunks``),
``host_syncs_per_chunk`` (``fase:sync:`` spans, the chunk record's read
included) beside ``shadow_reads_per_chunk`` (reads ``JaxTarget``
answered from its state shadow, with no device transfer) and
``image_programs_per_chunk`` (``JaxTarget``'s accessor programs that
took the memory image, the chunk aside), the span count by name, both
jobs' walls and
the cost of one span with the profiler off and on; a fleet round adds
``live_boards_per_chunk``, the boards given a budget per global chunk
(``FleetTarget.live_board_chunks`` over its launches).  ``--slice`` runs one
more job with the TPU tracer's full mode over 1 s of it from 1 s in and
adds the device's idle share and its idle gaps, each labelled by the
innermost span around its start and the program launched next, and on a
fleet over chips the chips' wait on the slowest board
(``bench/metrics/lockstep_wait_pct.py``).
``--save`` writes the light recording, gzipped, for
``jax.profiler.ProfileData.from_serialized_xspace``.
"""
from __future__ import annotations

import argparse
import gzip
import json
import sys
import time
from collections import Counter
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

from fasebench import xtrace  # noqa: E402

PREFIX = "fase:"
CHUNK = "fase:chunk"
#: the span around one whole job, target build included
JOB = "host_spans:job"
LAYERS = {"runtime_self": "fase:rt:", "session_self": "fase:sess:"}
ACCESSORS = ("fase:acc:", "fase:sync:")


def program_spans(data, prefix: str = PREFIX) -> list:
    """``(name, start_ns, end_ns, thread)`` of every host event of a
    ``ProfileData`` whose name starts with ``prefix``; ``thread`` is the
    name of the host thread's line."""
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns),
             line.name)
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(prefix)]


def _nesting(spans) -> tuple[list, list]:
    """The program spans by thread and start, and for each the index of
    the innermost one that holds it on its thread (-1 for none)."""
    prog = sorted((sp for sp in spans if sp[0].startswith(PREFIX)),
                  key=lambda sp: (sp[3], sp[1], -sp[2]))
    parent, stack = [], []
    for i, (_, s, e, thread) in enumerate(prog):
        while stack:
            _, ps, pe, pt = prog[stack[-1]]
            if pt == thread and ps <= s and e <= pe:
                break
            stack.pop()
        parent.append(stack[-1] if stack else -1)
        stack.append(i)
    return prog, parent


def _wall(spans) -> int:
    """The union of ``spans`` on each thread, summed over threads."""
    by_thread: dict = {}
    for _, s, e, thread in spans:
        by_thread.setdefault(thread, []).append((s, e))
    return sum(xtrace.covered(iv) for iv in by_thread.values())


def self_ns(spans, prefix: str) -> int:
    """Self time of the program spans whose name starts with ``prefix``:
    each one's duration less the union of the program spans nested in it
    on its thread."""
    prog, parent = _nesting(spans)
    inner: dict = {}
    for i, p in enumerate(parent):
        if p >= 0:
            inner.setdefault(p, []).append(prog[i][1:3])
    return sum(e - s - xtrace.covered(inner.get(i, ()))
               for i, (n, s, e, _) in enumerate(prog) if n.startswith(prefix))


def outside_chunks_ns(spans, prefixes: tuple) -> int:
    """Wall time of the program spans whose name starts with one of
    ``prefixes`` and that no ``fase:chunk`` span holds: the union on each
    thread, summed over threads."""
    prog, parent = _nesting(spans)
    out = []
    for i, sp in enumerate(prog):
        if not sp[0].startswith(prefixes):
            continue
        p = parent[i]
        while p >= 0 and prog[p][0] != CHUNK:
            p = parent[p]
        if p < 0:
            out.append(sp)
    return _wall(out)


def split(spans, kinstr: float) -> dict:
    """The host layers' time per thousand guest instructions and the
    blocking reads per chunk; every value None when no ``fase:chunk``
    span was recorded."""
    names = Counter(sp[0] for sp in spans)
    keys = ("chunk_wait", *LAYERS, "accessor_host")
    if not names[CHUNK] or kinstr <= 0:
        return {**{f"{k}_ms_per_kinstr": None for k in keys},
                "host_syncs_per_chunk": None}
    ns = {"chunk_wait": _wall(sp for sp in spans if sp[0] == CHUNK),
          **{k: self_ns(spans, p) for k, p in LAYERS.items()},
          "accessor_host": outside_chunks_ns(spans, ACCESSORS)}
    syncs = sum(k for n, k in names.items() if n.startswith("fase:sync:"))
    return {**{f"{k}_ms_per_kinstr": ns[k] / 1e6 / kinstr for k in keys},
            "host_syncs_per_chunk": syncs / names[CHUNK]}


def per_chunk(spans, counts: dict) -> dict:
    """``<count>_per_chunk`` for each of the target's counts over the
    job (``shadow_reads``, ``image_programs``): the count per
    ``fase:chunk`` span; empty without a chunk."""
    chunks = sum(1 for sp in spans if sp[0] == CHUNK)
    if not chunks:
        return {}
    return {f"{name}_per_chunk": n / chunks for name, n in counts.items()}


def reduce_job(data, guest_instr: int, counts: dict | None = None
               ) -> dict:
    """The recorded job (inside its ``JOB`` span) of a light recording;
    ``counts``, the target's counts for the job by name, add each per
    chunk (:func:`per_chunk`)."""
    from fasebench.spec import metric_reader
    (_, t0, t1, _), = program_spans(data, JOB)
    programs, _ = xtrace.host_events(data)
    unit = xtrace.HostUnit(
        start_ns=t0, end_ns=t1, guest_instr=guest_instr,
        programs=[p for p in programs if t0 <= p[1] and p[2] <= t1])
    spans = [sp for sp in program_spans(data) if t0 <= sp[1] and sp[2] <= t1]
    out = {"wall_s": unit.wall_ns / 1e9, "kinstr": unit.kinstr,
           "chunk_launches": len(unit.chunks()),
           "other_launches": len(unit.others()),
           **split(spans, unit.kinstr),
           **per_chunk(spans, counts or {}),
           "between_chunks_ms_per_kinstr": metric_reader(
               ROOT, "between_chunks_ms_per_kinstr")(xtrace.Trace(unit))}
    three = [out[f"{k}_ms_per_kinstr"] for k in (*LAYERS, "accessor_host")]
    if None not in three and out["between_chunks_ms_per_kinstr"]:
        out["layer_share"] = sum(three) / out["between_chunks_ms_per_kinstr"]
    out["spans"] = dict(sorted(Counter(sp[0] for sp in spans).items()))
    return out


def reduce_slice(data) -> dict:
    """Idle share and idle gaps of a full-mode recording, each gap
    labelled by the innermost span, the program's included, around its
    start."""
    sl = xtrace.device_slice(data)
    if sl is None:
        return {}
    sl.spans = sl.spans + program_spans(data)
    gaps = sl.idle_gaps(top=1000)
    idle = sum(s for _, s in gaps)
    named = sum(s for label, s in gaps if label.startswith(PREFIX))
    from fasebench.spec import metric_reader
    return {"slice_window_s": sl.window_ns / 1e9,
            "device_idle_pct": 100.0 * (1 - sl.busy_ns() / sl.window_ns),
            "lockstep_wait_pct": metric_reader(
                ROOT, "lockstep_wait_pct")(xtrace.Trace(slice=sl)),
            "idle_s": idle,
            "idle_share_named": named / idle if idle else None,
            "idle_gaps": gaps[:15]}


def span_cost_us(session_options=None, n: int = 100_000) -> float:
    """Mean wall microseconds of one empty span, with a profiler session
    of ``session_options`` running or with none."""
    from jax._src.lib import _profiler

    from repro.core import spans
    session = None if session_options is None \
        else _profiler.ProfilerSession(session_options)
    t = time.perf_counter()
    for _ in range(n):
        with spans.span("rt:probe"):
            pass
    us = (time.perf_counter() - t) / n * 1e6
    if session is not None:
        session.stop()
    return us


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--job", choices=("bc", "coremark"), default="bc")
    ap.add_argument("--scale", type=int, default=9)
    ap.add_argument("--mem-mib", type=int, default=64)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--boards", type=int, default=0)
    ap.add_argument("--chips", type=int)
    ap.add_argument("--slice", action="store_true")
    ap.add_argument("--save")
    args = ap.parse_args(argv)

    import jax
    from jax._src.lib import _profiler
    from jax.profiler import ProfileData, TraceAnnotation

    from fasebench.window import Recording, profile_options
    from repro.compile_cache import enable_compile_cache
    from repro.configs.fase_rocket import (fleet_kwargs, runtime_kwargs,
                                           target_kwargs)
    from repro.configs.registry import (FASE_FLEET_VMAP, FASE_ROCKET,
                                        FASE_ROCKET_PCIE)
    from repro.core.fleet import FleetRuntime, Job
    from repro.core.interface import JaxTarget
    from repro.core.runtime import FaseRuntime
    from repro.core.workloads import build, graphgen
    enable_compile_cache()

    cfg = FASE_ROCKET_PCIE if args.job == "bc" else FASE_ROCKET
    argv_, files = [args.job, "1"], [{}] * max(args.boards, 1)
    if args.job == "bc":
        argv_ = ["bc", "g.bin", "4", "1"]
        files = [{"g.bin": graphgen.rmat(args.scale, 16, args.seed + b,
                                         True)}
                 for b in range(max(args.boards, 1))]
    link = {k: cfg[k] for k in ("link", "qp_depth", "qp_coalesce_ticks")}
    fleet_cfg = {**FASE_FLEET_VMAP, **link, "n_devices": args.boards,
                 "mem_bytes": args.mem_mib << 20}
    if args.chips:
        fleet_cfg["fleet_chips"] = args.chips
    live = []

    def fleet_round():
        """One lockstep round, a job a board."""
        t = time.perf_counter()
        with TraceAnnotation(JOB):
            fleet = FleetRuntime(**fleet_kwargs(fleet_cfg))
            res = fleet.run_synchronous(
                [Job(args.job, argv_[1:], files=f, max_ticks=1 << 44)
                 for f in files], max_ticks=1 << 44)
            ft = fleet.fleet_target
            jax.block_until_ready(ft.sts)
        live[:] = [ft.live_board_chunks / ft.dispatch_count]
        return [r.report for r in res], time.perf_counter() - t, {}

    def job():
        if args.boards:
            return fleet_round()
        t = time.perf_counter()
        with TraceAnnotation(JOB):
            tgt = JaxTarget(cfg["n_cores"], args.mem_mib << 20,
                            **target_kwargs(cfg))
            rt = FaseRuntime(tgt, mode="fase", **runtime_kwargs(cfg))
            rt.load(build(args.job), argv_, files=files[0])
            rep = rt.run(max_ticks=1 << 44)
            jax.block_until_ready(tgt.st)
        return rep, time.perf_counter() - t, {
            "shadow_reads": tgt.shadow_reads,
            "image_programs": tgt.image_programs}

    job()                                    # warm-up: compiles
    rep, untraced_s, _ = job()
    session = _profiler.ProfilerSession(profile_options(full=False))
    rep2, traced_s, counts = job()
    raw = session.stop()
    if rep2 != rep:
        raise RuntimeError("the traced job's report differs from the "
                           "untraced job's")
    if args.save:
        Path(args.save).write_bytes(gzip.compress(raw))
    instr = sum(sum(r.instret) for r in rep2) if args.boards \
        else sum(rep2.instret)
    out = {"device": str(jax.devices()[0].device_kind), "job": args.job,
           "untraced_job_s": untraced_s, "traced_job_s": traced_s,
           "span_us_off": span_cost_us(),
           "span_us_on": span_cost_us(profile_options(full=False)),
           **reduce_job(ProfileData.from_serialized_xspace(raw), instr,
                        counts)}
    if args.boards:
        out["live_boards_per_chunk"] = live[0]
    if args.slice:
        rec = Recording(full=True)
        with rec.over(xtrace.SLICE_START_S, xtrace.SLICE_S):
            job()
        if rec.data is not None:
            out.update(reduce_slice(rec.data))
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
