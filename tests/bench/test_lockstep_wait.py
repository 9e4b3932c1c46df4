"""``lockstep_wait_pct`` on hand-made device slices: chunk executions of
one launch on several chips are grouped by overlap, and the wait is what
the faster chips spend idle until the slowest ends."""
from __future__ import annotations

import pytest
from benchcells import ROOT

from fasebench.spec import metric_reader  # noqa: E402
from fasebench.xtrace import DeviceSlice, HostUnit, Trace  # noqa: E402

read = metric_reader(ROOT, "lockstep_wait_pct")
CHUNK = "_run_chunk_fleet_shards"


def sliced(programs) -> Trace:
    return Trace(slice=DeviceSlice(programs=programs))


def test_four_chips_wait_for_the_slowest():
    # two launches on four chips: 10, 10, 10, 40 ns, then 20 ns each;
    # accessor programs between them are not chunks
    t = sliced([(CHUNK, 0, 10), (CHUNK, 1, 11), (CHUNK, 0, 10),
                (CHUNK, 2, 42), ("_fleet_fetch_read_batch", 50, 52),
                (CHUNK, 60, 80), (CHUNK, 60, 80), (CHUNK, 61, 81),
                (CHUNK, 60, 80)])
    busy = 70 + 80
    assert read(t) == pytest.approx(100 * (1 - busy / (4 * 40 + 4 * 20)))


def test_equal_chips_wait_nothing():
    t = sliced([(CHUNK, s, s + 30) for s in (0, 0, 100, 100)])
    assert read(t) == 0.0


def test_one_chip_reads_nothing():
    # executions that never overlap are dispatches of one execution each
    one = sliced([(CHUNK, 0, 10), (CHUNK, 20, 30), ("redirect_op", 12, 14)])
    assert read(one) is None
    assert read(sliced([("redirect_op", 0, 5)])) is None
    assert read(Trace(unit=HostUnit(0, 10, 100))) is None
    assert read(Trace()) is None
