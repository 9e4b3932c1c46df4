"""The cell benchmark under ``bench/``: its files, its trace reduction, its
independent answers and each cell's window, run end to end on the CPU at
a smaller image and graph with its comparison passing."""
from __future__ import annotations

import gzip
import json
import shutil
import time
from pathlib import Path

import pytest
from benchcells import (BENCH, CELLS, ROOT, SMALL_CONFIG,  # noqa: E402
                        SMALL_GRAPH, small_traffic)
from jax.profiler import ProfileData

from fasebench import jobs as jobmod  # noqa: E402
from fasebench.graphs import rmat  # noqa: E402
from fasebench.spec import (answer, load_cell, metric_reader,  # noqa: E402
                            units_class)
from fasebench.window import run_cell  # noqa: E402
from fasebench.xtrace import (DeviceSlice, HostUnit, Trace,  # noqa: E402
                              device_slice, host_unit, union)

DATA = Path(__file__).parent / "data"


def recorded(name: str):
    """A ``hello`` job on ``JaxTarget`` traced on a TPU v5e: ``light`` in
    the TPU tracer's light mode (host events), ``full`` in its full mode
    (host and device events)."""
    if name == "light":
        return ProfileData.from_file(str(DATA / "hello_light.xplane.pb"))
    raw = gzip.decompress((DATA / "hello_full.xplane.pb.gz").read_bytes())
    return ProfileData.from_serialized_xspace(raw)


def cpu_run(cell, root=ROOT, seed=2**31 + 5, trace=False):
    """A run at an 8 MiB image and kron-5 graphs."""
    return run_cell(root, cell, seed, 0.1, trace, time.perf_counter(),
                    require_accelerator=False, grace_s=30.0,
                    config_over=SMALL_CONFIG,
                    traffic_over=small_traffic(cell))


def read_all(root, trace) -> dict:
    return {m["name"]: metric_reader(root, m["name"])(trace)
            for m in BENCH["per_layer"]}


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_resolve(cell):
    c = load_cell(ROOT, cell)
    cfg = jobmod.target_config(c.config, c.traffic)
    assert cfg["n_cores"] == 4
    assert cfg["mem_bytes"] == c.config["deployment"]["mem_bytes"]
    assert cfg["link"] == c.traffic["link"]["link"]
    assert issubclass(units_class(ROOT, c.config), jobmod.Units)
    assert all(callable(answer(ROOT, k))
               for k in c.traffic.get("answers", []))
    assert {m["name"] for m in c.end_to_end} == {"guest_ips", "setup_s"}
    for m in c.per_layer:
        assert callable(metric_reader(ROOT, m["name"]))


def test_job_inputs_follow_the_seed():
    traffic = load_cell(ROOT, "rocket1.bc-pcie").traffic
    traffic = {**traffic, "files": SMALL_GRAPH}
    a = jobmod.job_input(traffic, 2**33 + 1, jobmod.WINDOW, 3, 0)
    b = jobmod.job_input(traffic, 2**33 + 1, jobmod.WINDOW, 3, 0)
    c = jobmod.job_input(traffic, 2**33 + 2, jobmod.WINDOW, 3, 0)
    w = jobmod.job_input(traffic, 2**33 + 1, jobmod.WARMUP, 3, 0)
    assert a == b and a.key() == b.key()
    assert len({a.files, c.files, w.files}) == 3
    # every seed draws the same sizes: the graph header's vertex count
    assert {f[0][1][:8] for f in (a.files, c.files, w.files)} == \
        {(32).to_bytes(8, "little")}


def test_set_seed_fixes_every_units_jobs():
    """With ``set_seed`` every seed gives a unit the same four graphs, one
    a board; units and the warm-up still differ."""
    traffic = load_cell(ROOT, "rocket4-fleet.bc-pcie").traffic
    traffic = {**traffic, "files": SMALL_GRAPH}
    units = [jobmod.unit_inputs(traffic, s, jobmod.WINDOW, 0, 4)
             for s in (2**33 + 1, 2**33 + 2, 2**33 + 3)]
    assert units[0] == units[1] == units[2]
    assert len(set(units[0])) == 4
    later = jobmod.unit_inputs(traffic, 2**33 + 1, jobmod.WINDOW, 1, 4)
    warm = jobmod.unit_inputs(traffic, 2**33 + 1, jobmod.WARMUP, 0, 4)
    assert not set(later) & set(units[0]) and not set(warm) & set(units[0])
    free = {k: v for k, v in traffic.items() if k != "set_seed"}
    assert jobmod.unit_inputs(free, 2**33 + 1, jobmod.WINDOW, 0, 4) != \
        jobmod.unit_inputs(free, 2**33 + 2, jobmod.WINDOW, 0, 4)


@pytest.mark.parametrize("scale,seed,argv,want", [
    (5, 3, ("g.bin", "4", "1"), 115964116989),
    (6, 2**40 + 1, ("g.bin", "2", "3"), 22119081572),
    (9, 7, ("g.bin", "4", "1"), 1765231558405),
])
def test_bc_answer_matches_the_guest(scale, seed, argv, want):
    """``bc_delta0`` worked out from the graph alone, against what the
    guest printed on PySim for these graphs (the last case: three trials,
    so the source is vertex 2)."""
    job = jobmod.JobInput("bc", argv, (("g.bin", rmat(scale, 16, seed,
                                                      True)),), (seed,))
    assert answer(ROOT, "bc_delta0")(job) == want


def test_union_and_reduction():
    assert union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]
    unit = HostUnit(
        start_ns=0, end_ns=100, guest_instr=2000,
        programs=[("_run_chunk_fast", 10, 40),
                  ("fetch_read_batch", 50, 50),
                  ("_run_chunk_fast", 60, 90)],
        spans=[("unit", 0, 100), ("load", 0, 10), ("run", 10, 100)])
    dslice = DeviceSlice(
        programs=[("_run_chunk_fast", 110, 140),
                  ("fetch_read_batch", 150, 152),
                  ("_run_chunk_fast", 160, 190)],
        launches=[(105, "_run_chunk_fast"), (148, "fetch_read_batch"),
                  (155, "_run_chunk_fast")],
        spans=[("unit", 100, 200), ("run", 102, 198)])
    assert dslice.window_ns == 80 and dslice.busy_ns() == 62
    assert dslice.device_ops() == [["_run_chunk_fast", 60e-9],
                                   ["fetch_read_batch", 2e-9]]
    assert dict(dslice.idle_gaps()) == {
        "run before fetch_read_batch": 10e-9,
        "run before _run_chunk_fast": 8e-9}
    assert read_all(ROOT, Trace(unit, dslice)) == pytest.approx({
        "device_idle_pct": 100 * 18 / 80,
        "chunk_device_ms_per_dispatch": 30e-6,
        "chunk_dispatches_per_kinstr": 1.0,
        "between_chunks_ms_per_kinstr": 40e-6 / 2,
        "accessor_programs_per_kinstr": 0.5})
    # a trace with no chunk program, or none at all, reads no chunk
    # metric, never 0
    no_chunk = read_all(ROOT, Trace(HostUnit(0, 100, 2000), DeviceSlice(
        programs=[("fetch_read_batch", 0, 5), ("redirect_op", 10, 20)])))
    assert no_chunk.pop("device_idle_pct") == pytest.approx(25.0)
    assert set(no_chunk.values()) == {None}
    assert set(read_all(ROOT, Trace()).values()) == {None}


def test_reduction_of_recorded_light_trace():
    unit = host_unit(recorded("light"), "unit", 1000)
    assert unit is not None and len(unit.programs) == 147
    names = [p[0] for p in unit.programs]
    assert names.count("_run_chunk_fast") == 6
    assert names.count("fetch_read_batch") == 54
    assert "unnamed" not in names
    assert {s[0] for s in unit.spans} == {"unit", "load", "run"}
    assert all(unit.start_ns <= s <= e <= unit.end_ns
               for _, s, e in unit.programs)
    assert all(e > s for _, s, e in unit.chunks())
    assert all(e == s for _, s, e in unit.others())
    assert device_slice(recorded("light")) is None
    values = read_all(ROOT, Trace(unit=unit))
    assert values["chunk_dispatches_per_kinstr"] == 6
    assert values["accessor_programs_per_kinstr"] == 141
    assert 0 < values["between_chunks_ms_per_kinstr"] < unit.wall_ns / 1e6
    assert values["device_idle_pct"] is None
    assert values["chunk_device_ms_per_dispatch"] is None


def test_reduction_of_recorded_full_trace():
    dslice = device_slice(recorded("full"))
    assert dslice is not None and len(dslice.programs) == 147
    names = [p[0] for p in dslice.programs]
    assert names.count("_run_chunk_fast") == 6
    assert names.count("fetch_read_batch") == 54
    assert all(e > s for _, s, e in dslice.programs)
    ops = dict(dslice.device_ops())
    assert ops["_run_chunk_fast"] == pytest.approx(0.015790351)
    busy, window = dslice.busy_ns(), dslice.window_ns
    assert busy == pytest.approx(37927290) and 0 < busy < window
    gaps = dslice.idle_gaps(top=1000)
    assert sum(s for _, s in gaps) * 1e9 == pytest.approx(window - busy)
    assert {label.split("before ")[0] for label, _ in gaps} <= \
        {"unit ", "load ", "run ", ""}
    assert dict(gaps)["run before fetch_read_batch"] == \
        pytest.approx(0.087133383)
    values = read_all(ROOT, Trace(slice=dslice))
    assert values["device_idle_pct"] == pytest.approx(
        100 * (1 - busy / window))
    assert values["chunk_device_ms_per_dispatch"] == pytest.approx(
        0.015790351e3 / 6)
    assert values["chunk_dispatches_per_kinstr"] is None


@pytest.mark.parametrize("cell", CELLS)
def test_cell_window_on_cpu(cell):
    out = cpu_run(cell)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert set(out["metrics"]) == {"guest_ips", "setup_s"}
    assert out["metrics"]["guest_ips"]["value"] > 0
    assert all(c["value"] == 0 for c in out["checks"].values())
    if "bc" in cell:
        assert "bc_delta0" in out["checks"]
    assert list(out)[-1] == "checks"


def test_traced_cell_window_on_cpu(monkeypatch):
    """With ``--trace 1`` the window runs two units, records them and
    checks them as any run.  On the CPU the host tracer times every
    operation of XLA:CPU (minutes a job) and there is no TPU plane, so the
    recordings here are empty and every per-layer metric is left out,
    never 0."""
    import jax

    from fasebench import window

    def empty(full):
        options = jax.profiler.ProfileOptions()
        options.host_tracer_level = options.device_tracer_level = 0
        options.python_tracer_level = 0
        return options
    monkeypatch.setattr(window, "profile_options", empty)
    out = cpu_run("rocket1.coremark-uart", trace=True)
    assert out["correct"] is True and out["attempted"] >= 2
    assert out["metrics"] == {}
    assert "busy_s" not in out["device"] and "breakdown" not in out


def test_new_cell_and_metric_from_files_alone(tmp_path):
    """A cell, its units, an answer and a per-layer metric that exist only
    as new files and BENCHMARK.json entries run through the unchanged
    harness."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({
        "name": "rocket1-small", "source": "https://example.org",
        "file": "bench/configs/rocket1-small.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "rocket1-small.hello-uart", "config": "rocket1-small",
        "traffic": "hello-uart", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "unit_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "device", "moves": "guest_ips",
        "workloads": ["rocket1-small.hello-uart"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    config = json.loads((ROOT / "bench/configs/rocket1.json").read_text())
    config["deployment"]["mem_bytes"] = 1 << 22
    config["units"] = "solo-counted"
    (tmp_path / "bench/configs/rocket1-small.json").write_text(
        json.dumps(config))
    (tmp_path / "bench/units/solo-counted.py").write_text(
        (ROOT / "bench/units/solo.py").read_text())
    traffic = json.loads(
        (ROOT / "bench/traffic/coremark-uart.json").read_text())
    traffic.update(workload="hello", argv=[], stdout={}, max_jobs=2,
                   answers=["answer"])
    (tmp_path / "bench/traffic/hello-uart.json").write_text(
        json.dumps(traffic))
    (tmp_path / "bench/answers/answer.py").write_text(
        "def expected(job):\n    return 6 * 7\n")
    (tmp_path / "bench/metrics/unit_ms.py").write_text(
        "def read(trace):\n"
        "    u = trace.unit\n"
        "    return u.wall_ns / 1e6 if u else None\n")

    # traced: the first unit is recorded, with its span, even on the CPU
    out = run_cell(tmp_path, "rocket1-small.hello-uart", 7, 0.1, True,
                   time.perf_counter(), require_accelerator=False)
    assert out["correct"] is True and out["attempted"] >= 2
    assert out["checks"]["answer"] == {"value": 0, "limit": 0}
    assert out["metrics"]["unit_ms"]["value"] > 0
    cell = load_cell(tmp_path, "rocket1-small.hello-uart")
    assert [m["name"] for m in cell.per_layer][-1] == "unit_ms"
    unit = host_unit(recorded("light"), "unit", 1000)
    assert metric_reader(tmp_path, "unit_ms")(Trace(unit=unit)) == \
        unit.wall_ns / 1e6
