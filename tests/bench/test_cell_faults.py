"""The comparison that decides ``correct`` has teeth: a run with the timed
path broken underneath, or with the control in the program's place,
comes out not correct.  CPU runs at a smaller image and graph."""
from __future__ import annotations

import time

import numpy as np
import pytest
from benchcells import CELLS, ROOT, SMALL_CONFIG, small_traffic

from fasebench.control import ControlUnits  # noqa: E402
from fasebench.window import run_cell  # noqa: E402
from repro.core.runtime import io  # noqa: E402
from repro.core.target import cpu  # noqa: E402


def cpu_run(cell, seed=2**31 + 11, **kw):
    """A run at an 8 MiB image and kron-5 graphs."""
    return run_cell(ROOT, cell, seed, 0.1, False, time.perf_counter(),
                    require_accelerator=False, grace_s=6.0,
                    config_over=SMALL_CONFIG,
                    traffic_over=small_traffic(cell), **kw)


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


FAULTS = [(cell, fault) for cell in CELLS
          for fault in (unchanged, altered, misread)
          if fault is not misread or "bc" in cell]
EXPECT = {unchanged: {"unfinished"}, altered: {"instret"},
          misread: {"bc_delta0"}}


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__}" for c, f in FAULTS])
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    where, name = (io.FdTable, "read") if fault is misread \
        else (cpu, "run_chunk_fast")
    out = cpu_run(cell, before_window=lambda: monkeypatch.setattr(
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
