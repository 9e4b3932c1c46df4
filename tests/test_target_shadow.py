"""``JaxTarget``'s state shadow: every read it serves equals what a device
read would return at that instant, every write keeps it so, and whole
jobs through it are bit-identical to ``PySim``.

``CheckedTarget`` reads the device beside each shadow-served read (the
batched gather the target used before it had a shadow) and compares the
whole shadow with the device's state after each read and each write.
"""
from __future__ import annotations

import contextlib
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.fase_rocket import runtime_kwargs, target_kwargs
from repro.configs.registry import FASE_ROCKET, FASE_ROCKET_PCIE
from repro.core import snapshot as snap
from repro.core import spans
from repro.core.channel import OracleChannel
from repro.core.interface import (JaxTarget, pack_read_batch,
                                  unpack_read_batch)
from repro.core.runtime import FaseRuntime
from repro.core.session import HtpSession
from repro.core.target import cpu
from repro.core.target.pysim import PySim
from repro.core.workloads import build, graphgen

MEM = 1 << 23
BC = (FASE_ROCKET_PCIE, "bc", ["bc", "g.bin", "4", "1"],
      {"g.bin": graphgen.rmat(5, 16, 3, weights=True)})
COREMARK = (FASE_ROCKET, "coremark", ["coremark", "1"], {})
WRITES = ("redirect", "park", "clear_pending", "csr_write", "set_satp",
          "reg_write", "commit_batch")


def device_read(t: JaxTarget, regs=(), csrs=(), words=()):
    """The reads made on the device, with no shadow."""
    regs, csrs, words = list(regs), list(csrs), list(words)
    names, reg_cpu, reg_idx, word_idx, csr_cpus, order = \
        pack_read_batch(regs, csrs, words or [0])
    got = jax.device_get(cpu.fetch_read_batch(
        t.st, names, reg_cpu, reg_idx, word_idx, csr_cpus))
    rv, cv, wv = unpack_read_batch(got, len(regs), len(words), names, order)
    return rv, cv, wv


class CheckedTarget(JaxTarget):
    """A ``JaxTarget`` that checks its shadow against the device at every
    read it serves and after every write; ``checked`` counts the checks
    by accessor."""

    def __init__(self, *args, **kwargs):
        self.checked: Counter = Counter()
        super().__init__(*args, **kwargs)

    def _whole(self, accessor: str) -> None:
        self.checked[accessor] += 1
        if self._shadow is not None:
            np.testing.assert_array_equal(
                self._shadow, np.asarray(cpu.state_record(self.st)))

    def get_ticks(self):
        v = super().get_ticks()
        assert v == device_read(self, csrs=[(0, "ticks")])[1][0]
        self._whole("get_ticks")
        return v

    def pending_cores(self):
        v = super().pending_cores()
        pend = device_read(self, csrs=[(c, "pending")
                                       for c in range(self.nc)])[1]
        assert v == [c for c, p in enumerate(pend) if p]
        self._whole("pending_cores")
        return v

    def get_priv(self, c):
        v = super().get_priv(c)
        assert v == device_read(self, csrs=[(c, "priv")])[1][0]
        self._whole("get_priv")
        return v

    def get_uticks(self, c):
        v = super().get_uticks(c)
        assert v == device_read(self, csrs=[(c, "uticks")])[1][0]
        self._whole("get_uticks")
        return v

    def get_instret(self, c):
        v = super().get_instret(c)
        assert v == device_read(self, csrs=[(c, "instret")])[1][0]
        self._whole("get_instret")
        return v

    def reg_read(self, c, idx):
        v = super().reg_read(c, idx)
        assert v == device_read(self, regs=[(c, idx)])[0][0]
        self._whole("reg_read")
        return v

    def csr_read(self, c, name):
        v = super().csr_read(c, name)
        assert v == device_read(self, csrs=[(c, name)])[1][0]
        self._whole("csr_read")
        return v

    def fetch_batch(self, regs=(), csrs=(), words=()):
        v = super().fetch_batch(regs, csrs, words)
        assert tuple(v) == device_read(self, regs, csrs, words)
        self._whole("fetch_batch")
        return v


for _name in WRITES:
    def _write(self, *args, _name=_name, **kwargs):
        getattr(JaxTarget, _name)(self, *args, **kwargs)
        self._whole(_name)
    setattr(CheckedTarget, _name, _write)


def runtime(target_cls, job, mem=MEM) -> FaseRuntime:
    cfg, name, argv, files = job
    kw = target_kwargs(cfg) if target_cls is CheckedTarget else {}
    rt = FaseRuntime(target_cls(cfg["n_cores"], mem, **kw), mode="fase",
                     **runtime_kwargs(cfg))
    rt.load(build(name), argv, files=files)
    return rt


@pytest.fixture(scope="module")
def bc_pysim():
    return runtime(PySim, BC).run(max_ticks=1 << 44)


@pytest.mark.parametrize("job", [BC, COREMARK], ids=["bc", "coremark"])
def test_jobs_through_the_shadow_match_pysim(job):
    rt = runtime(CheckedTarget, job)
    rep = rt.run(max_ticks=1 << 44)
    assert rep == runtime(PySim, job).run(max_ticks=1 << 44)
    t = rt.target
    assert t.shadow_reads > 0
    served = {"get_ticks", "pending_cores", "get_uticks", "get_instret",
              "fetch_batch", "redirect", "clear_pending", "set_satp",
              "commit_batch"}
    if job is BC:      # futexes, thread switches and exits besides
        served |= {"reg_read", "reg_write", "park"}
    assert served <= set(t.checked)


def test_snapshot_restore_through_the_shadow(bc_pysim):
    """Half-way through a bc job, its state is captured and restored into
    a fresh target whose shadow is full of the initial state; the job
    then finishes there, as on ``PySim`` paused at the same tick."""
    pause = bc_pysim.ticks // 2
    ref = runtime(PySim, BC)
    assert ref.run_slice(pause, max_ticks=1 << 44) is None
    ref_rep = ref.run(max_ticks=1 << 44)
    assert ref_rep == bc_pysim

    rt = runtime(CheckedTarget, BC)
    assert rt.run_slice(pause, max_ticks=1 << 44) is None
    src = rt.target
    s, _ = snap.capture(HtpSession(src, OracleChannel()),
                        at=src.get_ticks(), pages=sorted(rt.alloc.refcnt))
    dst = CheckedTarget(src.nc, MEM, **target_kwargs(FASE_ROCKET_PCIE))
    assert dst.get_ticks() == 0 and dst.get_priv(0) == 3
    snap.restore(HtpSession(dst, OracleChannel()), s, at=s.ticks)
    assert {"commit_batch", "csr_write"} <= set(dst.checked)
    assert [dst.get_instret(c) for c in range(dst.nc)] == \
        [src.get_instret(c) for c in range(src.nc)]
    assert dst.get_ticks() == src.get_ticks() == s.ticks
    assert snap.capture(HtpSession(dst, OracleChannel()), at=s.ticks,
                        pages=sorted(rt.alloc.refcnt))[0].same_state(s)
    rt.target = rt.session.t = dst
    assert rt.run(max_ticks=1 << 44) == ref_rep


def test_reads_between_chunks_reach_no_device():
    """After the read that ends a chunk, reads of per-core state make no
    device transfer; a memory word still does."""
    names: Counter = Counter()

    def recorder(name):
        names[name] += 1
        return contextlib.nullcontext()
    t = JaxTarget(2, 1 << 16)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(spans, "span", recorder)
        t.get_ticks()
        t.run(max_cycles=10)
        t.get_ticks()
        before = t.shadow_reads
        t.pending_cores()
        t.reg_read(1, 5)
        t.fetch_batch(regs=[(0, 1)], csrs=[(1, "mepc"), (0, "ticks")])
        t.get_instret(0)
        assert t.shadow_reads == before + 4
        assert names == {"sync:shadow_fill": 1, "sync:chunk_record": 1}
        assert t.mem_read_word(0) == 0
        assert names["sync:fetch_batch"] == 1
        assert t.shadow_reads == before + 4
        assert t.csr_read(0, "stall_ticks") == 0      # not in the record
        assert names["sync:fetch_batch"] == 2


def test_writes_after_a_chunk_and_replaced_state():
    """A write between a chunk's launch and the first read brings the
    record home first and lands on it; replacing ``st`` drops the shadow
    and the next read refills it."""
    t = CheckedTarget(2, 1 << 16)
    t.reg_write(0, 3, 7)
    t.run(max_cycles=10)
    t.csr_write(1, "mepc", 0x1234)
    t.redirect(0, 0x40, resume_tick=9)
    t.commit_batch(regs=[(1, 2, 5)], csrs=[(0, "priv", (1 << 32) + 1),
                                           (1, "pending", 2)])
    assert t.reg_read(0, 3) == 7 and t.reg_read(1, 2) == 5
    assert t.csr_read(1, "mepc") == 0x1234
    assert t.get_priv(0) == 1 and t.pending_cores() == [1]
    t.st = t.st._replace(ticks=jnp.uint64(99))
    assert t.get_ticks() == 99
    t.trace_arm(4)
    assert t.fetch_batch(regs=[(0, 3)], csrs=[(0, "ticks")])[:2] == \
        ([7], [99])
