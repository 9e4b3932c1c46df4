"""The fleet sharded over chips (``FleetTarget(fleet_chips=...)``,
``FASE_FLEET_SHARDED``), on four and two host CPU devices at 8 MiB
boards: a lockstep round is bit-identical to PySim alone per board and
to the one-chip stack, each global chunk is one launch, and every
accessor and write touches its own board only and leaves the stack
sharded along the board axis."""
from __future__ import annotations

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs.fase_rocket import fleet_kwargs, runtime_kwargs
from repro.configs.registry import FASE_FLEET_SHARDED, FASE_FLEET_VMAP
from repro.core.fleet import FleetRuntime, Job, vmap
from repro.core.fleet.vmap import BOARD, FleetTarget
from repro.core.runtime import FaseRuntime
from repro.core.target.pysim import PySim
from repro.core.workloads import build, graphgen

MEM = 1 << 23
GRAPHS = [graphgen.rmat(5, 16, seed, weights=True) for seed in (3, 4)]


def jobs() -> list[Job]:
    """Two kron-5 bc jobs and two CoreMark jobs, one a board: boards end
    at different chunks and ride along with budget 0."""
    return [Job("bc", ["g.bin", "4", "1"], files={"g.bin": GRAPHS[0]}),
            Job("coremark", ["1"]),
            Job("bc", ["g.bin", "4", "1"], files={"g.bin": GRAPHS[1]}),
            Job("coremark", ["1"])]


def summary(reports) -> list:
    return [(r.stdout, r.ticks, list(r.instret), r.traffic_total)
            for r in reports]


def fleet_round(chips):
    """One ``run_synchronous`` round; returns the fleet and its reports."""
    cfg = {**FASE_FLEET_SHARDED, "mem_bytes": MEM, "fleet_chips": chips} \
        if chips else {**FASE_FLEET_VMAP, "mem_bytes": MEM}
    fleet = FleetRuntime(**fleet_kwargs(cfg))
    return fleet, [r.report for r in fleet.run_synchronous(jobs())]


@pytest.fixture(scope="module")
def references():
    """Each job alone on PySim, and the round on the one-chip stack."""
    alone = []
    for job in jobs():
        rt = FaseRuntime(PySim(4, MEM), mode="fase",
                         **runtime_kwargs(FASE_FLEET_SHARDED))
        rt.load(build(job.name), [job.name, *job.argv],
                files=job.files or {})
        alone.append(rt.run(max_ticks=job.max_ticks))
    one_chip, reps = fleet_round(None)
    return summary(alone), summary(reps), one_chip.fleet_target


def assert_sharded(ft: FleetTarget) -> None:
    """Every leaf of the stack sharded along the board axis, and every
    chip's part of it on that chip alone."""
    want = NamedSharding(ft.mesh, PartitionSpec(BOARD))
    for x in ft.sts:
        assert x.sharding.is_equivalent_to(want, x.ndim), x.sharding
    for chip, part in zip(ft.mesh.devices.flat, ft._stacks):
        for x in part:
            assert x.devices() == {chip}
            assert x.shape[0] == ft.per_chip


@pytest.mark.parametrize("chips", [4, 2])
def test_sharded_round_matches_pysim_and_one_chip(chips, references,
                                                  monkeypatch):
    alone, one_chip, one_ft = references
    launches = []
    real = vmap._run_chunk_fleet_shards
    monkeypatch.setattr(vmap, "_run_chunk_fleet_shards",
                        lambda *a: (launches.append(1), real(*a))[1])
    fleet, reps = fleet_round(chips)
    ft = fleet.fleet_target
    assert summary(reps) == alone == one_chip
    assert b"coremark_crc 16356" in reps[1].stdout
    # one launch a global chunk, the same chunks as on one chip
    assert len(launches) == ft.dispatch_count == one_ft.dispatch_count
    assert ft.live_board_chunks == one_ft.live_board_chunks
    assert ft.dispatch_count < ft.live_board_chunks <= 4 * ft.dispatch_count
    assert ft.per_chip == 4 // chips
    assert_sharded(ft)


def board_states(ft: FleetTarget) -> list:
    """Each board's state on the host, leaf by leaf."""
    host = [np.asarray(x) for x in ft.sts]
    return [[x[d] for x in host] for d in range(ft.n_devices)]


PAGE = np.arange(512, dtype=np.uint64) * np.uint64(3) + np.uint64(1)

#: each accessor and write of a view, with what it leaves on its board
ACCESSES = {
    "redirect": (lambda v: v.redirect(1, 0x1000, 7),
                 lambda v: v.csr_read(1, "pc") == 0x1000),
    "park": (lambda v: v.park(0), lambda v: v.get_priv(0) == 3),
    "clear_pending": (lambda v: (v.csr_write(0, "pending", 1),
                                 v.clear_pending(0)),
                      lambda v: v.pending_cores() == []),
    "csr_write": (lambda v: v.csr_write(1, "mepc", 0xabc),
                  lambda v: v.csr_read(1, "mepc") == 0xabc),
    "csr_write_ticks": (lambda v: v.csr_write(0, "ticks", 99),
                        lambda v: v.get_ticks() == 99),
    "set_satp": (lambda v: v.set_satp(1, 8 << 60),
                 lambda v: v.csr_read(1, "satp") == 8 << 60),
    "reg_write": (lambda v: v.reg_write(1, 5, 2**64 - 1),
                  lambda v: v.reg_read(1, 5) == 2**64 - 1),
    "commit_batch": (lambda v: v.commit_batch(
                         regs=[(0, 3, 11)], csrs=[(1, "mtval", 12)]),
                     lambda v: v.fetch_batch(
                         regs=[(0, 3)], csrs=[(1, "mtval")])[:2]
                     == ([11], [12])),
    "commit_batch_words": (lambda v: v.commit_batch(
                               regs=[(1, 2, 5)], words=[(9, 13)]),
                           lambda v: v.mem_read_word(72) == 13),
    "mem_write_word": (lambda v: v.mem_write_word(4096, 2**63),
                       lambda v: v.mem_read_word(4096) == 2**63),
    "page_write": (lambda v: v.page_write(5, PAGE),
                   lambda v: (v.page_read(5) == PAGE).all()),
    "page_set": (lambda v: v.page_set(6, 2**64 - 2),
                 lambda v: (v.page_read(6) == 2**64 - 2).all()),
    "page_copy": (lambda v: (v.page_write(5, PAGE), v.page_copy(5, 7)),
                  lambda v: (v.page_read(7) == PAGE).all()),
    "provision": (lambda v: (v.reg_write(0, 1, 4), v.mem_write_word(8, 4),
                             v.ft.provision_view(v.d)),
                  lambda v: v.reg_read(0, 1) == 0 == v.mem_read_word(8)
                  and v.get_priv(0) == 3),
    "run": (lambda v: (v.redirect(0, 0x2000), v.run(50)),
            lambda v: v.get_ticks() > 0 and v.pending_cores() == [0]),
}


@pytest.mark.parametrize("chips", [4, 2])
@pytest.mark.parametrize("access", sorted(ACCESSES))
def test_access_touches_its_board_only(access, chips):
    """A write through view ``d`` leaves every other board bit-identical
    (its neighbour on the same chip among them with two chips) and the
    stack sharded as it was."""
    ft = FleetTarget(4, 2, 1 << 20, fleet_chips=chips)
    for d in range(4):            # every board distinct before the write
        ft.view(d).mem_write_word(8 * d + 8, d + 1)
        ft.view(d).reg_write(0, 9, d + 1)
    d = 3
    do, check = ACCESSES[access]
    before = board_states(ft)
    do(ft.view(d))
    assert_sharded(ft)
    after = board_states(ft)
    for other in range(3):
        for a, b in zip(before[other], after[other]):
            np.testing.assert_array_equal(a, b)
    assert check(ft.view(d))
    assert_sharded(ft)


def test_fleet_needs_its_chips():
    n = len(jax.devices())
    with pytest.raises(ValueError, match=f"{n + 4} chips.* {n} devices"):
        FleetTarget(n + 4, 1, 1 << 20, fleet_chips=n + 4)
    with pytest.raises(ValueError, match="3 boards .* 2 chips"):
        FleetTarget(3, 1, 1 << 20, fleet_chips=2)


def test_fleet_kwargs_carry_the_chips():
    assert fleet_kwargs(FASE_FLEET_SHARDED)["target_cfg"]["fleet_chips"] \
        == FASE_FLEET_SHARDED["fleet_chips"] == 4
    assert "fleet_chips" not in fleet_kwargs(FASE_FLEET_VMAP)["target_cfg"]
    assert FleetTarget(2, 1, 1 << 20).mesh is None
