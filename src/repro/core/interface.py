"""The FASE CPU interface (paper Table I) and its two implementations.

The paper's target core exposes exactly three signal bundles:

  * ``Priv``   — current privilege level (exception detection),
  * ``Reg``    — handshaked GPR read/write,
  * ``Inject`` — StopFetch + non-branch instruction injection + InjectBusy,

plus an optional ``Interrupt``.  Everything the controller does (Table II) is
a composition of these.  In this reproduction the composition is modelled
*behaviourally*: each HTP execution pattern is applied as a direct state
update, while :mod:`repro.core.session` accounts its cycle/byte cost from
the very same Table II instruction sequences.  This keeps semantics exact and
the timing model faithful without interpreting injected instructions one by
one (the paper itself notes controller-side latency is negligible next to
UART time: 0.01 ms vs 1.144 ms per page, §VI-C).

Two implementations are provided:

  * :class:`JaxTarget` — wraps the jitted XLA target (the "FPGA"),
  * :class:`repro.core.target.pysim.PySim` — the pure-Python twin.
"""
from __future__ import annotations

from typing import Protocol

import numpy as np

from ..kernels.page_walk.ops import check_pallas_backend
from . import spans
from .target import cpu as _cpu

import jax
import jax.numpy as jnp


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def pack_write_batch(nc, mem_words, regs=(), csrs=(), words=()):
    """Pack a staged transaction's writes into pow2-padded scatter arrays
    for :func:`repro.core.target.cpu.apply_write_batch` (and its fleet
    twin).  Pad entries carry out-of-bounds drop sentinels: reg/csr cpu
    = ``nc``, word index = ``mem_words``.  Returns ``(csr_names,
    reg_cpu, reg_idx, reg_val, word_idx, word_val, csr_cpus, csr_vals)``
    or None when there is nothing to commit."""
    regs, csrs, words = list(regs), list(csrs), list(words)
    if not (regs or csrs or words):
        return None
    rp = _pow2(max(len(regs), 1))
    reg_cpu = np.full(rp, nc, np.int32)
    reg_idx = np.zeros(rp, np.int32)
    reg_val = np.zeros(rp, np.uint64)
    for i, (c, idx, v) in enumerate(regs):
        reg_cpu[i], reg_idx[i], reg_val[i] = c, idx, np.uint64(v)
    wp = _pow2(max(len(words), 1))
    word_idx = np.full(wp, mem_words, np.int64)
    word_val = np.zeros(wp, np.uint64)
    for i, (w, v) in enumerate(words):
        word_idx[i], word_val[i] = w, np.uint64(v)
    by_name: dict = {}
    for c, name, v in csrs:
        by_name.setdefault(name, []).append((c, v))
    names = tuple(sorted(by_name))
    csr_cpus, csr_vals = [], []
    for name in names:
        pairs = by_name[name]
        cp = _pow2(len(pairs))
        cc = np.full(cp, nc, np.int32)
        vv = np.zeros(cp, np.uint64)
        for i, (c, v) in enumerate(pairs):
            cc[i], vv[i] = c, np.uint64(int(v))
        csr_cpus.append(cc)
        csr_vals.append(vv)
    return (names, reg_cpu, reg_idx, reg_val, word_idx, word_val,
            tuple(csr_cpus), tuple(csr_vals))


def pack_read_batch(regs=(), csrs=(), words=()):
    """Pack a read mix into pow2-padded gather arrays for
    :func:`repro.core.target.cpu.fetch_read_batch` (and its fleet twin).
    Pad entries index slot 0 (always valid; the host discards the tail).
    Returns ``(csr_names, reg_cpu, reg_idx, word_idx, csr_cpus, order)``
    where ``order`` is the per-input-csr ``(name, slot)`` list used to
    restore input order, or None when there is nothing to read."""
    regs, csrs, words = list(regs), list(csrs), list(words)
    if not (regs or csrs or words):
        return None
    rp = _pow2(max(len(regs), 1))
    reg_cpu = np.zeros(rp, np.int32)
    reg_idx = np.zeros(rp, np.int32)
    for i, (c, ix) in enumerate(regs):
        reg_cpu[i], reg_idx[i] = c, ix
    wp = _pow2(max(len(words), 1))
    word_idx = np.zeros(wp, np.int64)
    for i, pa in enumerate(words):
        word_idx[i] = pa >> 3
    by_name: dict = {}
    order = []                     # (name, slot) per input csr
    for c, name in csrs:
        lst = by_name.setdefault(name, [])
        order.append((name, len(lst)))
        lst.append(c)
    names = tuple(sorted(by_name))
    csr_cpus = []
    for name in names:
        cp = _pow2(max(len(by_name[name]), 1))
        cc = np.zeros(cp, np.int32)
        cc[:len(by_name[name])] = by_name[name]
        csr_cpus.append(cc)
    return names, reg_cpu, reg_idx, word_idx, tuple(csr_cpus), order


def unpack_read_batch(got, n_regs, n_words, names, order):
    """Restore a :func:`pack_read_batch` gather result to the caller's
    three input-ordered int lists."""
    rv, wv, cv = got
    pos = {name: k for k, name in enumerate(names)}
    return ([int(v) for v in rv[:n_regs]],
            [int(cv[pos[name]][slot]) for name, slot in order],
            [int(v) for v in wv[:n_words]])


#: offset of each per-core field in the chunk record, and every name the
#: shadow serves (:func:`repro.core.target.cpu.state_record`)
_FIELD = {name: k for k, name in enumerate(_cpu.SNAPSHOT_CORE_FIELDS)}
_SHADOWED = frozenset(_FIELD) | {"ticks"}
_MASK64 = (1 << 64) - 1
_MASK32 = (1 << 32) - 1


class Target(Protocol):
    """Host-visible surface of a FASE-instrumented target processor."""

    n_cores: int

    # Inst-stream control ------------------------------------------------
    def run(self, max_cycles: int = 1 << 62) -> None: ...
    def redirect(self, c: int, pc: int, resume_tick: int = 0) -> None: ...
    def park(self, c: int) -> None: ...
    def pending_cores(self) -> list[int]: ...
    def clear_pending(self, c: int) -> None: ...
    # Priv / CSR ----------------------------------------------------------
    def csr_read(self, c: int, name: str) -> int: ...
    def csr_write(self, c: int, name: str, v: int) -> None: ...
    def set_satp(self, c: int, v: int) -> None: ...
    def sfence(self, c: int) -> None: ...
    # Reg bundle ----------------------------------------------------------
    def reg_read(self, c: int, idx: int) -> int: ...
    def reg_write(self, c: int, idx: int, v: int) -> None: ...
    # Batched host reads (at most one device sync for any mix) -------------
    def fetch_batch(self, regs=(), csrs=(), words=()) -> tuple: ...
    # Batched host writes (one device update for a staged transaction) -----
    def commit_batch(self, regs=(), csrs=(), words=()) -> None: ...
    # Word / page data access (via injected ld/sd — behavioural) ----------
    def mem_read_word(self, pa: int) -> int: ...
    def mem_write_word(self, pa: int, v: int) -> None: ...
    def page_read(self, ppn: int) -> np.ndarray: ...
    def page_write(self, ppn: int, words) -> None: ...
    def page_set(self, ppn: int, val: int) -> None: ...
    def page_copy(self, src_ppn: int, dst_ppn: int) -> None: ...
    # Perf ------------------------------------------------------------------
    def get_ticks(self) -> int: ...
    def get_uticks(self, c: int) -> int: ...
    def get_instret(self, c: int) -> int: ...
    # Telemetry: commit-trace ring (repro.telemetry) -----------------------
    def trace_arm(self, slots: int) -> None: ...
    def trace_trigger(self, spec: tuple | None) -> None: ...
    def trace_drain(self, c: int | None = None,
                    limit: int | None = None): ...


class JaxTarget:
    """The jitted XLA target ("FPGA") behind the FASE CPU interface.

    State lives in device buffers; ``run`` donates them into the compiled
    while-loop; host-side accesses use tiny donating micro-ops so nothing is
    ever copied wholesale.

    The host keeps a shadow of the per-core state: the clock and every
    core's registers, ``pc``, ``priv``, ``pending``, ``stall_until``,
    ``satp``, trap CSRs, ``res``, ``uticks`` and ``instret``
    (:func:`repro.core.target.cpu.state_record`).  The program that runs
    a chunk also returns that record; the first read after the launch
    brings it home (``fase:sync:chunk_record``, where the host waits on
    the chunk), and until the next chunk those fields are read from it
    with no device call.  Every writer here sends its values to the
    device and into the shadow alike; any other replacement of ``st``
    drops the shadow and the next read refills it
    (``fase:sync:shadow_fill``).  At every host instant a value the
    shadow serves equals what a device read would return.
    ``shadow_reads`` counts the accessor calls it answered with no
    device transfer of their own.

    A program that takes the u64 memory image makes a pass over all of
    it on the TPU, whether it touches a word or not.  So every accessor
    program that touches no word (the micro-ops, the batches without
    words) is given the state without ``mem``, and the image stays
    where it is; ``image_programs`` counts the accessor programs
    launched with it, the chunk aside.

    ``fast_path`` (default on) selects the batched-issue vectorized
    interpreter with the per-core fetch-block cache
    (:func:`repro.core.target.cpu.run_chunk_fast`); ``fast_path=False``
    falls back to the scalar one-instruction-per-iteration reference
    loop, after which the shadow refills on the first read.  Both are
    bit-identical to :class:`~repro.core.target.pysim.\
PySim` — the knobs trade compile time and host speed, never semantics:

      * ``issue_width`` — ticks retired per compiled loop iteration,
      * ``block_words`` — fetch-block size in 32-bit slots (power of 2),
      * ``block_cache=False`` — keep batched issue but re-walk every
        instruction fetch,
      * ``fetch_kernel`` — ``"ref"`` (jnp oracle) or ``"pallas"`` for
        the block-fill translate/fetch chain
        (:mod:`repro.kernels.page_walk`); ``"pallas"`` runs in interpret
        mode on the CPU backend only and is refused here on any other,
      * ``dtlb_ways`` — per-lane data-translation cache ways in the fast
        path (power of 2; 0 disables and re-walks every load/store).
    """

    def __init__(self, n_cores: int, mem_bytes: int,
                 chunk_cycles: int = 1 << 30, fast_path: bool = True,
                 issue_width: int = 8, block_words: int = 16,
                 block_cache: bool = True, fetch_kernel: str = "ref",
                 dtlb_ways: int = 8):
        if fast_path and fetch_kernel == "pallas":
            check_pallas_backend()
        self.nc = n_cores
        self.mem_bytes = mem_bytes
        self.chunk_cycles = chunk_cycles
        self.fast_path = fast_path
        self.issue_width = issue_width
        self.block_words = block_words
        self.block_cache = block_cache
        self.fetch_kernel = fetch_kernel
        self.dtlb_ways = dtlb_ways
        self.trace_slots = 0          # commit-trace ring, off by default
        self._trace_base: list = []
        self._trigger: tuple | None = None   # capture-window predicate
        self.shadow_reads = 0
        self.image_programs = 0
        self.st = _cpu.make_state(n_cores, mem_bytes)

    # -- the state shadow -------------------------------------------------
    @property
    def st(self) -> _cpu.CpuState:
        return self._st

    @st.setter
    def st(self, st: _cpu.CpuState) -> None:
        """Replace the whole state: the shadow is dropped and the next
        read of per-core state refills it from the device."""
        self._st = st
        self._shadow: np.ndarray | None = None
        self._record: jax.Array | None = None   # last chunk's, unread

    def _shadow_now(self, count: bool = True) -> np.ndarray:
        """The shadow, current: the last chunk's record brought home on
        the first access after the chunk, or the state read whole after
        a drop.  ``count`` adds a read served with no transfer to
        ``shadow_reads``."""
        if self._record is not None:
            with spans.span("sync:chunk_record"):
                self._shadow = np.array(self._record)
            self._record = None
        elif self._shadow is None:
            with spans.span("sync:shadow_fill"):
                self._shadow = np.array(_cpu.state_record(self._st))
        elif count:
            self.shadow_reads += 1
        return self._shadow

    def _slot(self, c: int, name: str) -> int:
        """Index of core ``c``'s field ``name`` (or the clock) in the
        record."""
        return 0 if name == "ticks" else 1 + _FIELD[name] * self.nc + c

    def _reg_slot(self, c: int, idx: int) -> int:
        return 1 + len(_FIELD) * self.nc + c * 32 + idx

    def _put(self, slot: int, v: int) -> None:
        """Write ``v`` through to ``slot`` of the shadow, the last
        chunk's record brought home first; nothing while it is
        dropped."""
        if self._record is not None:
            self._shadow_now(count=False)
        if self._shadow is not None:
            self._shadow[slot] = v & _MASK64

    def _write_through(self, c: int, name: str, v: int) -> None:
        """Put the value a device op just wrote into field ``name`` of
        core ``c`` into the shadow, as the device stores it."""
        if name == "pending":
            v = int(v != 0)
        elif name == "priv":
            v &= _MASK32
        if name in _SHADOWED:
            self._put(self._slot(c, name), v)

    def _update(self, op, *args, words: bool = False) -> None:
        """``op(st, *args)``, donated; the state is given without its
        memory image unless ``words``, and the image is put back on the
        result untouched."""
        st = self._st
        self.image_programs += words
        out = op(st if words else st._replace(mem=None), *args)
        self._st = out if words else out._replace(mem=st.mem)

    # -- inst stream ------------------------------------------------------
    @property
    def n_cores(self):
        return self.nc

    def run(self, max_cycles: int = 1 << 62):
        """Launch one chunk; on the fast path the same program returns
        the chunk's state record for the shadow."""
        budget = min(max_cycles, self.chunk_cycles)
        if self.fast_path:
            self._st, self._record = _cpu.run_chunk_fast_record(
                self._st, _cpu.run_chunk_fast, self.nc, self.mem_bytes,
                budget, self.issue_width, self.block_words,
                self.block_cache, self.fetch_kernel, self.trace_slots > 0,
                self._trigger if self.trace_slots > 0 else None,
                self.dtlb_ways)
            self._shadow = None
        else:
            self.st = _cpu.run_chunk(self._st, self.nc, self.mem_bytes,
                                     budget)

    @spans.traced("acc:redirect")
    def redirect(self, c, pc, resume_tick=0):
        # one donated jitted dispatch, not four eager scatters
        pc, resume = int(pc), max(resume_tick, 0)
        self._update(_cpu.redirect_op, np.int32(c), np.uint64(pc),
                     np.uint64(resume))
        for name, v in (("pc", pc), ("priv", 0), ("pending", 0),
                        ("stall_until", resume)):
            self._write_through(c, name, v)

    @spans.traced("acc:park")
    def park(self, c):
        self._update(_cpu.park_op, np.int32(c))
        self._write_through(c, "priv", 3)
        self._write_through(c, "pending", 0)

    def pending_cores(self):
        base = self._slot(0, "pending")
        return np.flatnonzero(
            self._shadow_now()[base:base + self.nc]).tolist()

    @spans.traced("acc:clear_pending")
    def clear_pending(self, c):
        self._update(_cpu.clear_pending_op, np.int32(c))
        self._write_through(c, "pending", 0)

    # -- priv / csr ---------------------------------------------------------
    def csr_read(self, c, name):
        if name in _SHADOWED:
            return int(self._shadow_now()[self._slot(c, name)])
        return self.fetch_batch(csrs=[(c, name)])[1][0]

    def get_priv(self, c):
        return self.csr_read(c, "priv")

    @spans.traced("acc:csr_write")
    def csr_write(self, c, name, v):
        """Host-side CSR/core-state write (CsrW's device half; snapshot
        restore).  Each field keeps its device dtype; ``ticks`` is the
        global clock scalar.  One jitted donated dispatch per write."""
        v = int(v) & _MASK64
        self._update(_cpu.csr_write_op, name, np.int32(c), np.uint64(v))
        self._write_through(c, name, v)

    @spans.traced("acc:set_satp")
    def set_satp(self, c, v):
        self._update(_cpu.csr_write_op, "satp", np.int32(c), np.uint64(v))
        self._write_through(c, "satp", int(v))

    def sfence(self, c):
        # nothing cached across chunks: the slow path walks every access
        # and the fast path's fetch-block cache AND data-translation
        # cache (DTlb) both live only inside one run_chunk_fast call, so
        # any host-driven PTE change is visible by construction — the
        # next chunk starts with empty caches
        pass

    # -- regs -----------------------------------------------------------------
    def reg_read(self, c, idx):
        return int(self._shadow_now()[self._reg_slot(c, idx)])

    def fetch_batch(self, regs=(), csrs=(), words=()):
        """Batched host reads of any mix of GPRs (``(core, idx)``
        pairs), CSR/core-state fields (``(core, name)`` pairs) and
        physical words (byte addresses).  Returns three int lists in
        input order, bit-identical to the per-element accessors.  GPRs
        and the fields the shadow holds come from it; words and the
        other fields (telemetry counters, trace state) from ONE blocking
        device gather (``fase:sync:fetch_batch``,
        :func:`repro.core.target.cpu.fetch_read_batch`), its index
        arrays pow2-padded so that a handful of compiled shapes serve
        every request mix; a gather of no word is given the state
        without its image."""
        regs, csrs, words = list(regs), list(csrs), list(words)
        far = [(c, n) for c, n in csrs if n not in _SHADOWED]
        sh = None
        if regs or len(far) < len(csrs):
            sh = self._shadow_now(count=not (far or words))
        far_vals, wv = [], []
        if far or words:
            with spans.span("sync:fetch_batch"):
                names, reg_cpu, reg_idx, word_idx, csr_cpus, order = \
                    pack_read_batch((), far, words)
                self.image_programs += bool(words)
                got = jax.device_get(_cpu.fetch_read_batch(
                    self._st if words else self._st._replace(mem=None),
                    names, reg_cpu, reg_idx, word_idx, csr_cpus))
            _, far_vals, wv = unpack_read_batch(got, 0, len(words), names,
                                                order)
        far_it = iter(far_vals)
        cv = [int(sh[self._slot(c, n)]) if n in _SHADOWED else next(far_it)
              for c, n in csrs]
        return [int(sh[self._reg_slot(c, i)]) for c, i in regs], cv, wv

    @spans.traced("acc:reg_write")
    def reg_write(self, c, idx, v):
        if idx != 0:
            v = int(v) & _MASK64
            self._update(_cpu.reg_write_op, np.int32(c), np.int32(idx),
                         np.uint64(v))
            self._put(self._reg_slot(c, idx), v)

    @spans.traced("acc:commit_batch")
    def commit_batch(self, regs=(), csrs=(), words=()):
        """Batched host writes: ONE donated device update for any mix of
        GPRs (``(core, idx, val)``), CSR/core-state fields
        (``(core, name, val)``) and physical memory words
        (``(word_index, val)``) — the write-side twin of
        :meth:`fetch_batch` and the device half of the session layer's
        staged write batching (ROADMAP item 1).  Callers guarantee
        unique indices per array (the stage is dict-keyed), values are
        64-bit-masked, and ``x0``/``ticks`` never appear; arrays are
        pow2-padded with out-of-bounds drop sentinels so a handful of
        shapes serve every transaction.  Bit-identical to replaying the
        per-element accessors in order; registers and fields go into the
        shadow too.  A batch of no word is given the state without its
        image."""
        regs, csrs, words = list(regs), list(csrs), list(words)
        packed = pack_write_batch(self.nc, self.mem_bytes >> 3,
                                  regs, csrs, words)
        if packed is None:
            return
        self._update(_cpu.apply_write_batch, *packed, words=bool(words))
        for c, idx, v in regs:
            self._put(self._reg_slot(c, idx), int(v))
        for c, name, v in csrs:
            self._write_through(c, name, int(v))

    # -- memory ---------------------------------------------------------------
    def mem_read_word(self, pa):
        return self.fetch_batch(words=[pa])[2][0]

    @spans.traced("acc:mem_write_word")
    def mem_write_word(self, pa, v):
        self.image_programs += 1
        self._st = self._st._replace(
            mem=_cpu.mem_write_words(self._st.mem,
                                     jnp.asarray([pa >> 3]),
                                     jnp.asarray([v], dtype=jnp.uint64)))

    @spans.traced("sync:page_read")
    def page_read(self, ppn):
        self.image_programs += 1
        return np.asarray(_cpu.page_read_words(self._st.mem,
                                               (ppn << 12) >> 3))

    @spans.traced("acc:page_write")
    def page_write(self, ppn, words):
        w = jnp.asarray(np.ascontiguousarray(words, dtype=np.uint64))
        self.image_programs += 1
        self._st = self._st._replace(
            mem=_cpu.page_write_words(self._st.mem, (ppn << 12) >> 3, w))

    @spans.traced("acc:page_set")
    def page_set(self, ppn, val):
        self.image_programs += 1
        self._st = self._st._replace(
            mem=_cpu.page_set_words(self._st.mem, (ppn << 12) >> 3,
                                    np.uint64(val)))

    @spans.traced("acc:page_copy")
    def page_copy(self, src_ppn, dst_ppn):
        self.image_programs += 2          # a slice, then an update
        self._st = self._st._replace(
            mem=_cpu.page_copy_words(self._st.mem, (src_ppn << 12) >> 3,
                                     (dst_ppn << 12) >> 3))

    # -- perf --------------------------------------------------------------
    def get_ticks(self):
        return self.csr_read(0, "ticks")

    def get_uticks(self, c):
        return self.csr_read(c, "uticks")

    def get_instret(self, c):
        return self.csr_read(c, "instret")

    # -- telemetry: commit-trace ring (repro.telemetry) --------------------
    @spans.traced("acc:trace_arm")
    def trace_arm(self, slots):
        """Arm per-core commit-trace capture: rebuilds the carry with a
        ``(nc, slots, 4)`` ring so the next ``run`` compiles the
        trace-recording variant of the fast path."""
        assert self.fast_path, \
            "commit-trace capture needs the fast path (run_chunk_fast)"
        assert slots > 0
        self.trace_slots = slots
        # a replacement of the state: the shadow refills on the next read
        self.st = self.st._replace(
            tracebuf=jnp.zeros((self.nc, slots, 4), jnp.uint64),
            trace_n=jnp.zeros((self.nc,), jnp.uint64),
            trace_armed=jnp.zeros((self.nc,), jnp.bool_))

        self._trace_base = [0] * self.nc

    @spans.traced("acc:trace_trigger")
    def trace_trigger(self, spec):
        """Install (or clear) the capture-window predicate — a hashable
        trigger spec tuple (see :mod:`repro.telemetry.triggers`) that
        becomes a *static* argument of ``run_chunk_fast``, so the gate
        compiles into the trace path and ``None`` compiles it out
        entirely.  Arm/disarm state rewinds to disarmed."""
        self._trigger = spec
        self._st = self._st._replace(
            trace_armed=jnp.zeros((self.nc,), jnp.bool_))

    @spans.traced("sync:trace_drain")
    def trace_drain(self, c=None, limit=None):
        """Drain commit-trace rings, mirroring
        :meth:`repro.core.target.pysim.PySim.trace_drain` bit-for-bit:
        ``(records, ring_dropped)`` per hart.  ``c=None`` bundles every
        hart's ring + produced-counts into ONE ``jax.device_get`` (the
        ``fetch_batch`` discipline — a drain is a chunk-boundary bulk
        read, not per-record round trips).  ``limit`` caps the records
        taken per hart: the rest stay *in the ring* (streamed-transport
        FIFO stall — a stalled bridge leaves records behind, and later
        overwrites surface as ``ring_dropped`` on a future drain)."""
        if self.trace_slots == 0:     # unarmed: nothing to drain
            return ([], 0) if c is not None else [([], 0)] * self.nc
        if c is None:
            buf, totals = jax.device_get((self.st.tracebuf,
                                          self.st.trace_n))
            return [self._drain_host(buf[i], int(totals[i]), i, limit)
                    for i in range(self.nc)]
        buf, total = jax.device_get((self.st.tracebuf[c],
                                     self.st.trace_n[c]))
        return self._drain_host(buf, int(total), c, limit)

    def _drain_host(self, buf, total, c, limit=None):
        slots = self.trace_slots
        base = self._trace_base[c]
        n_new = total - base
        dropped = max(0, n_new - slots)
        avail_start = base + dropped      # oldest record still in the ring
        take = total - avail_start
        if limit is not None:
            take = min(take, limit)
        recs = [tuple(int(v) for v in buf[i % slots])
                for i in range(avail_start, avail_start + take)]
        self._trace_base[c] = avail_start + take
        return recs, dropped
