"""The host spans of ``repro.core.spans``: one span for each event the
program already counts, no span moves a result, they land on the
profiler's host plane beside the program launches they hold, and
``benchmarks.host_spans`` splits host time by layer from them."""
from __future__ import annotations

import contextlib
import gzip
from collections import Counter
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax._src.lib import _profiler
from jax.profiler import ProfileData

from benchmarks.host_spans import (JOB, outside_chunks_ns, program_spans,
                                   reduce_job, self_ns, split, xtrace)
from repro.configs.fase_rocket import runtime_kwargs, target_kwargs
from repro.configs.registry import FASE_ROCKET_PCIE
from repro.core import spans
from repro.core.interface import JaxTarget
from repro.core.runtime import FaseRuntime
from repro.core.workloads import build, graphgen

GRAPH = graphgen.rmat(5, 16, 3, weights=True)
#: this job recorded on a TPU v5 lite with the TPU tracer's light mode:
#: ``python -m benchmarks.host_spans --job bc --scale 5 --mem-mib 8
#: --seed 3 --save <file>``
CHIP_TRACE = Path(__file__).parent / "data" / "bc5_spans_light.xplane.pb.gz"


def bc_runtime() -> FaseRuntime:
    """GAPBS bc, 4 threads, on a kron-5 graph, PCIe, an 8 MiB image."""
    cfg = FASE_ROCKET_PCIE
    tgt = JaxTarget(cfg["n_cores"], 1 << 23, **target_kwargs(cfg))
    rt = FaseRuntime(tgt, mode="fase", **runtime_kwargs(cfg))
    rt.load(build("bc"), ["bc", "g.bin", "4", "1"], files={"g.bin": GRAPH})
    return rt


def counting(obj, name: str, counts: Counter, mp) -> None:
    """Count the calls of ``obj.name`` under ``counts[name]``."""
    fn = getattr(obj, name)

    def call(*args, **kwargs):
        counts[name] += 1
        return fn(*args, **kwargs)
    mp.setattr(obj, name, call)


def profile_options():
    """The lowest host tracer level that records a span at all."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.device_tracer_level = 0
    options.host_tracer_level = 1
    return options


@pytest.fixture(scope="module")
def plain_report():
    return bc_runtime().run(max_ticks=1 << 44)


@pytest.fixture(scope="module")
def recorded():
    """The job with a recorder in the span helper's place: span counts by
    name, the calls of ``JaxTarget.run`` and of the HFutex fast path, the
    runtime and its report."""
    names: Counter = Counter()
    calls: Counter = Counter()

    def recorder(name):
        names[spans.PREFIX + name] += 1
        return contextlib.nullcontext()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(spans, "span", recorder)
        rt = bc_runtime()
        counting(rt.target, "run", calls, mp)
        counting(rt.session, "try_hfutex_fast_path", calls, mp)
        rep = rt.run(max_ticks=1 << 44)
    return names, calls, rt, rep


def test_span_counts_match_counters(recorded, plain_report):
    names, calls, rt, rep = recorded
    assert rep == plain_report
    assert names["fase:rt:exception"] == rep.sched["exceptions"] > 0
    sys_spans = {n[len("fase:rt:sys:"):]: k for n, k in names.items()
                 if n.startswith("fase:rt:sys:")}
    assert sys_spans == rep.syscalls
    assert names["fase:rt:syscall"] == sum(rep.syscalls.values())
    assert names["fase:rt:pagefault"] == \
        rt.stats["page_fault_exceptions"] > 0
    assert names["fase:rt:hfutex"] == calls["try_hfutex_fast_path"] > 0
    assert names["fase:sess:submit"] == rt.session.stats.transactions
    assert names["fase:chunk"] == calls["run"] > 0
    assert names["fase:rt:run"] == names["fase:rt:load"] == \
        names["fase:rt:finish"] == 1
    assert names["fase:rt:poll"] == names["fase:rt:dispatch"] == \
        calls["run"]
    # every chunk's wait ends in one read of its record, and the reads
    # of per-core state between chunks come from the target's shadow
    assert names["fase:sync:chunk_record"] == calls["run"]
    assert names["fase:sync:fetch_batch"] > 0
    assert names["fase:acc:commit_batch"] > 0
    assert all(n.split(":")[1] in ("chunk", "rt", "sess", "sync", "acc")
               for n in names)


def test_shadow_serves_the_per_core_reads(recorded):
    """Of the job's 1 228 blocking reads without a shadow (10.233 a
    chunk), the chunks' records, one fill before the first chunk and the
    reads of memory words reach the device; the rest are served by the
    shadow."""
    names, calls, rt, _ = recorded
    syncs = {n: k for n, k in names.items() if n.startswith("fase:sync:")}
    assert syncs == {"fase:sync:chunk_record": 120,
                     "fase:sync:shadow_fill": 1,
                     "fase:sync:fetch_batch": 55}
    assert rt.target.shadow_reads == 1052
    assert sum(syncs.values()) + rt.target.shadow_reads == 1228
    assert sum(syncs.values()) / names["fase:chunk"] == \
        pytest.approx(1.4666666666666666)


def test_spans_leave_results_alone(plain_report):
    session = _profiler.ProfilerSession(profile_options())
    try:
        rep = bc_runtime().run(max_ticks=1 << 44)
    finally:
        raw = session.stop()
    assert rep == plain_report
    assert b"fase:rt:exception" in raw and b"fase:chunk" in raw


def test_spans_land_beside_the_launches_they_hold():
    """The span and the jitted call inside it share a host thread and a
    clock."""
    f = jax.jit(lambda x: x + 1)
    x = jnp.arange(4)
    f(x).block_until_ready()
    session = _profiler.ProfilerSession(profile_options())
    with spans.span("acc:probe"):
        f(x).block_until_ready()
    data = session.stop_and_get_profile_data()
    (name, start, end, thread), = program_spans(data)
    assert name == "fase:acc:probe" and end > start
    calls = [e for plane in data.planes if plane.name.startswith("/host:")
             for line in plane.lines if line.name == thread
             for e in line.events if e.name.startswith("PjitFunction(")]
    assert calls and all(start <= e.start_ns and
                         e.start_ns + e.duration_ns <= end for e in calls)


def sp(name, start, end, thread="main"):
    return ("fase:" + name, start, end, thread)


def test_self_time_subtracts_nested_spans():
    spans_ = [sp("rt:run", 0, 100), sp("rt:exception", 10, 60),
              sp("rt:syscall", 20, 50), sp("sess:submit", 25, 45),
              sp("sync:fetch_batch", 30, 40), sp("chunk", 70, 90),
              sp("sync:chunk_record", 85, 90)]
    # run 100 - (exception 50 + chunk 20); exception 50 - 30; syscall
    # 30 - 20
    assert self_ns(spans_, "fase:rt:") == 30 + 20 + 10
    assert self_ns(spans_, "fase:sess:") == 20 - 10
    # the record read inside the chunk is the wait on it, not accessor
    # time
    assert outside_chunks_ns(spans_, ("fase:acc:", "fase:sync:")) == 10
    out = split(spans_, kinstr=0.5)
    assert out == pytest.approx({
        "chunk_wait_ms_per_kinstr": 20e-6 / 0.5,
        "runtime_self_ms_per_kinstr": 60e-6 / 0.5,
        "session_self_ms_per_kinstr": 10e-6 / 0.5,
        "accessor_host_ms_per_kinstr": 10e-6 / 0.5,
        "host_syncs_per_chunk": 2.0})
    # the layers and the chunk waits tile the outermost span
    assert sum(v for k, v in out.items() if k.endswith("kinstr")) == \
        pytest.approx(100e-6 / 0.5)


def test_spans_on_two_threads_nest_apart():
    spans_ = [sp("rt:run", 0, 100, "a"), sp("sync:fetch_batch", 10, 20, "b"),
              sp("chunk", 0, 50, "b"), sp("sync:chunk_record", 40, 50, "b"),
              sp("acc:redirect", 30, 40, "a")]
    # a's accessor is not inside b's chunk; b's fetch is
    assert outside_chunks_ns(spans_, ("fase:acc:", "fase:sync:")) == 10
    assert self_ns(spans_, "fase:rt:") == 90
    # overlapping accessors on one thread count once
    assert outside_chunks_ns([sp("sync:fetch_batch", 0, 10),
                              sp("acc:park", 5, 8)],
                             ("fase:sync:", "fase:acc:")) == 10


def test_no_chunk_span_reads_nothing():
    assert set(split([sp("rt:run", 0, 10)], kinstr=1.0).values()) == {None}
    assert set(split([], kinstr=1.0).values()) == {None}


def test_idle_gaps_take_the_innermost_program_span():
    """The device's idle gaps, labelled by the cell benchmark's reduction
    once the program's spans are among those it is given."""
    sl = xtrace.DeviceSlice(
        programs=[("_run_chunk_fast", 0, 10), ("fetch_read_batch", 30, 31),
                  ("_run_chunk_fast", 50, 60)],
        launches=[(5, "_run_chunk_fast"), (29, "fetch_read_batch"),
                  (49, "_run_chunk_fast")],
        spans=[("unit", 0, 100), sp("rt:run", 0, 100), sp("chunk", 4, 12),
               sp("sync:chunk_record", 8, 12), sp("rt:exception", 14, 40),
               sp("sync:fetch_batch", 29, 33)])
    assert dict(sl.idle_gaps()) == pytest.approx({
        "fase:sync:chunk_record before fetch_read_batch": 20e-9,
        "fase:sync:fetch_batch before _run_chunk_fast": 19e-9})


def test_recorded_chip_trace(recorded):
    """On the chip the job records every span the CPU counts, on one host
    thread, and the three host layers tile the wall that the cell
    benchmark reads between chunks from the launch events."""
    names, calls, _, rep = recorded
    data = ProfileData.from_serialized_xspace(
        gzip.decompress(CHIP_TRACE.read_bytes()))
    (_, t0, t1, _), = program_spans(data, JOB)
    chip = [sp for sp in program_spans(data) if t0 <= sp[1] and sp[2] <= t1]
    assert Counter(sp[0] for sp in chip) == names
    assert {sp[3] for sp in chip} == {"python"}
    out = reduce_job(data, sum(rep.instret))
    assert out.pop("spans") == dict(sorted(names.items()))
    assert out["chunk_launches"] == calls["run"] == 120
    assert out["host_syncs_per_chunk"] == pytest.approx(176 / 120)
    assert out == pytest.approx({
        "wall_s": 1.417661344, "kinstr": 38.025, "chunk_launches": 120,
        "other_launches": 456,
        "chunk_wait_ms_per_kinstr": 25.38457412228797,
        "runtime_self_ms_per_kinstr": 0.6405068244575937,
        "session_self_ms_per_kinstr": 1.1056689546351084,
        "accessor_host_ms_per_kinstr": 9.80444996712689,
        "host_syncs_per_chunk": 1.4666666666666666,
        "between_chunks_ms_per_kinstr": 12.745715923734386,
        "layer_share": 0.9062359317698773})
    assert 0.9 <= out["layer_share"] <= 1.1


@pytest.mark.parametrize("chips", [None, 2])
def test_fleet_round_spans(chips, monkeypatch):
    """A fleet round, on one stack or sharded over two chips: one
    ``fase:chunk`` a global chunk, its accessors under the names
    ``JaxTarget`` gives its own, and ``live_board_chunks`` counting the
    boards given a budget."""
    import re

    from repro.configs.fase_rocket import fleet_kwargs
    from repro.configs.registry import FASE_FLEET_VMAP
    from repro.core import interface
    from repro.core.fleet import FleetRuntime, Job

    names: Counter = Counter()

    def recorder(name):
        names[spans.PREFIX + name] += 1
        return contextlib.nullcontext()
    monkeypatch.setattr(spans, "span", recorder)
    cfg = {**FASE_FLEET_VMAP, "n_devices": 2, "mem_bytes": 1 << 23}
    if chips:
        cfg["fleet_chips"] = chips
    fleet = FleetRuntime(**fleet_kwargs(cfg))
    res = fleet.run_synchronous(
        [Job("bc", ["g.bin", "4", "1"], files={"g.bin": GRAPH}),
         Job("bc", ["g.bin", "2", "1"], files={"g.bin": GRAPH})])
    ft = fleet.fleet_target
    assert names["fase:chunk"] == ft.dispatch_count > 0
    assert ft.dispatch_count < ft.live_board_chunks <= 2 * ft.dispatch_count
    assert names["fase:rt:exception"] == \
        sum(r.report.sched["exceptions"] for r in res)
    jax_target = set(re.findall(
        r'spans\.(?:traced|span)\("((?:acc|sync):\w+)"',
        Path(interface.__file__).read_text()))
    fleet_names = {n[len(spans.PREFIX):] for n in names
                   if n.startswith(("fase:acc:", "fase:sync:"))}
    assert {"sync:fetch_batch", "acc:commit_batch", "acc:redirect"} <= \
        fleet_names <= jax_target
