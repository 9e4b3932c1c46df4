"""The comparison that decides ``correct`` has teeth: a run with the timed
path broken underneath, or with the control in the program's place,
comes out not correct.  CPU runs at a smaller image and graph.

A chunk fault is planted at the cell's own chunk entry, the function of
``repro.core.target.cpu`` that its units name as ``chunk_kernel``
(``run_chunk_fast`` on a solo board, ``run_chunk_fleet`` on a fleet)."""
from __future__ import annotations

import time

import numpy as np
import pytest
from benchcells import CELLS, ROOT, SMALL_CONFIG, small_traffic

from fasebench.control import ControlUnits  # noqa: E402
from fasebench.spec import load_cell, units_class  # noqa: E402
from fasebench.window import run_cell  # noqa: E402
from repro.core.fleet import FleetRuntime  # noqa: E402
from repro.core.runtime import io  # noqa: E402
from repro.core.target import cpu  # noqa: E402


def cpu_run(cell, seed=2**31 + 11, grace_s=6.0, **kw):
    """A run at an 8 MiB image and kron-5 graphs."""
    return run_cell(ROOT, cell, seed, 0.1, False, time.perf_counter(),
                    require_accelerator=False, grace_s=grace_s,
                    config_over=SMALL_CONFIG,
                    traffic_over=small_traffic(cell), **kw)


def units_of(cell):
    return units_class(ROOT, load_cell(ROOT, cell).config)


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))


def unchanged(kernel):
    """A chunk that returns its state unchanged."""
    return lambda st, *args, **kw: st


def altered(kernel):
    """An answer altered where it is produced: in the first chunk, core 0
    retires one instruction more than it ran."""
    calls = []

    def run(st, *args, **kw):
        st = kernel(st, *args, **kw)
        calls.append(1)
        if len(calls) > 1:
            return st
        return st._replace(instret=st.instret.at[0].add(1))
    return run


def half_batch(kernel):
    """Half of the batch of boards left out: every chunk runs only the
    first half of the boards, whatever cycles the others were given."""
    def run(sts, n_cores, mem_bytes, budgets, *args, **kw):
        budgets = np.array(budgets)
        budgets[len(budgets) // 2:] = 0
        return kernel(sts, n_cores, mem_bytes, budgets, *args, **kw)
    return run


def half_results(run_synchronous):
    """Half of the batch of boards left out of the results: the fleet's
    lockstep loop runs every job and returns only the first half of its
    results."""
    def run(self, jobs, *args, **kw):
        return run_synchronous(self, jobs, *args, **kw)[:len(jobs) // 2]
    return run


def misread(read):
    """The host runtime's file read hands the guest a graph whose first
    edge points one vertex further: a fault in code that the timed path
    and PySim share, which only the answer worked out from the inputs
    can see."""
    def run(self, fd, count):
        data = read(self, fd, count)
        if data is None or len(data) < 64:
            return data
        words = np.frombuffer(bytes(data), np.uint64).copy()
        n = int(words[0])
        words[3 + n + 1] = (words[3 + n + 1] + 1) % n
        return words.tobytes() + bytes(data)[8 * len(words):]
    return run


FLEET_ONLY = (half_batch, half_results)
FAULTS = [(cell, fault) for cell in CELLS
          for fault in (unchanged, altered, half_batch, half_results,
                        misread)
          if (fault is not misread or "bc" in cell)
          and (fault not in FLEET_ONLY or units_of(cell).boards > 1)]
EXPECT = {unchanged: {"unfinished"}, altered: {"instret"},
          half_batch: {"unfinished"}, half_results: {"unfinished"},
          misread: {"bc_delta0"}}
#: seconds a unit may run past the window's close: a fault that stalls
#: the guest must run it out, one that lets the job end must never
#: reach it (it bounds the run, which ends with the job)
GRACE = {unchanged: 6.0, half_batch: 6.0, altered: 120.0,
         half_results: 120.0, misread: 120.0}


def patch_point(cell, fault):
    """The object and attribute the fault is planted at."""
    if fault is misread:
        return io.FdTable, "read"
    if fault is half_results:
        return FleetRuntime, "run_synchronous"
    return cpu, units_of(cell).chunk_kernel


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__}" for c, f in FAULTS])
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    where, name = patch_point(cell, fault)
    out = cpu_run(cell, grace_s=GRACE[fault],
                  before_window=lambda: monkeypatch.setattr(
                      where, name, fault(getattr(where, name))))
    assert out["correct"] is False
    assert out["failed"] >= 1 and out["attempted"] >= 1
    bad = {k for k, c in out["checks"].items() if c["value"]}
    assert bad == EXPECT[fault]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    """The reference one precision down (64-bit ALU results kept to 32
    bits) in the program's place fails every board-job."""
    out = cpu_run(cell, units_cls=ControlUnits)
    assert out["correct"] is False
    assert out["attempted"] >= 1 and out["failed"] == out["attempted"]
