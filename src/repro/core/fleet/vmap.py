"""Fleet-wide single-dispatch batched execution (ROADMAP item 1).

An N-board fleet used to run N Python-driven sessions, each dispatching
its own ``run_chunk_fast`` — N XLA dispatches per global chunk and N
copies of the host-side driver loop.  This module is the
FireSim-metasim shape instead: ONE stacked :class:`CpuState` whose
every array carries a leading device axis ``(D, ...)``, executed by
:func:`repro.core.target.cpu.run_chunk_fleet` — the fast-path
interpreter run as one flat machine of ``D * n_cores`` lanes (the
device axis folded into the lane axis; ``jax.vmap`` of the chunk loop
is catastrophically slow on XLA:CPU, see ``run_chunk_fleet``) with
per-device cycle budgets — so a global chunk is exactly one XLA
dispatch (``FleetTarget.dispatch_count`` counts them; the conformance
suite asserts N=4 devices advance in a single dispatch).

The stack may span chips (``fleet_chips``): it is then sharded along the
board axis over a one-axis mesh, each chip holding the boards
``[k * D/chips, (k + 1) * D/chips)`` and never another chip's, as a
FireSim run farm gives each simulated node an FPGA of its own.  A global
chunk is still one launch: ``run_chunk_fleet`` under ``shard_map``, each
chip advancing its own boards with a loop condition local to its shard
(boards share nothing, so no collective).

Two classes:

  * :class:`FleetTarget` — owns the stacked state and the global
    dispatch (`run_global`);
  * :class:`FleetTargetView` — the per-device façade implementing the
    full :class:`~repro.core.interface.Target` protocol against device
    ``d``'s slice of the stack, so a :class:`~repro.core.fleet.device.\
Device`/:class:`~repro.core.cq.AsyncHtpSession`/runtime stack drives it
    exactly like a :class:`~repro.core.interface.JaxTarget`.

Every accessor runs on the chip that holds its board, over that chip's
part of the stack only, and every write is donated and updated in
place.  A program that needs no memory word is not given the memory
image: on the TPU a program that takes a u64 image converts all of it,
so only word and page accesses (and the chunk) pay a pass over the
image.

Semantics are bit-identical to D independent ``JaxTarget``\\ s: devices
are shared-nothing inside the flat kernel (every cross-lane interaction
is masked to same-device pairs), a view's ``run`` issues a one-hot
budget vector, and a device whose budget is 0 never gates a lane in, so
its state rides through *unchanged* — which is what keeps every golden
tick when devices take turns.  Batching budgets via ``run_global`` (all
devices at once) is the single-dispatch fleet chunk.

Commit-trace capture (``trace_arm``) stays a single-device affair — the
fleet kernel does not plumb the trace ring, and a view refuses to arm.
"""
from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .. import spans
from ..interface import pack_read_batch, pack_write_batch, \
    unpack_read_batch
from ..target import cpu as _cpu

U64 = jnp.uint64
U32 = jnp.uint32
#: the mesh axis the boards are sharded along
BOARD = "board"


@partial(jax.jit, static_argnums=(0, 1, 2))
def _blank_stack(n: int, n_cores: int, mem_bytes: int) -> "_cpu.CpuState":
    """``n`` boards at power-on, stacked, built where the call runs."""
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (n,) + x.shape),
        _cpu.make_state(n_cores, mem_bytes))


@partial(jax.jit, static_argnums=(2, 3), donate_argnums=(0,))
def _fleet_reset_op(sts, d, n_cores: int, mem_bytes: int):
    """Board ``d`` of the stack back to power-on, in place."""
    return jax.tree_util.tree_map(lambda s, f: s.at[d].set(f), sts,
                                  _cpu.make_state(n_cores, mem_bytes))


@partial(jax.jit, static_argnums=(2,), donate_argnums=(0,))
def _fleet_write_batch(sts: "_cpu.CpuState", d, csr_names: tuple,
                       reg_cpu, reg_idx, reg_val,
                       word_idx, word_val, csr_cpus, csr_vals):
    """Device-``d`` twin of :func:`repro.core.target.cpu.\
apply_write_batch` over the stacked fleet state: same pow2-padded
    arrays, same out-of-bounds drop sentinels, scattered at ``(d, ...)``
    in one donated update.  ``sts.mem`` is None where the batch writes
    no word."""
    sts = sts._replace(regs=sts.regs.at[d, reg_cpu, reg_idx].set(
        jnp.asarray(reg_val, U64), mode="drop"))
    if sts.mem is not None:
        sts = sts._replace(mem=sts.mem.at[d, word_idx].set(
            jnp.asarray(word_val, U64), mode="drop"))
    for name, cc, vv in zip(csr_names, csr_cpus, csr_vals):
        vv = jnp.asarray(vv, U64)
        if name == "pending":
            field = sts.pending.at[d, cc].set(vv != 0, mode="drop")
        elif name == "priv":
            field = sts.priv.at[d, cc].set(vv.astype(U32), mode="drop")
        else:
            field = getattr(sts, name).at[d, cc].set(vv, mode="drop")
        sts = sts._replace(**{name: field})
    return sts


# Device-indexed twins of the cpu.py host micro-ops (redirect / park /
# clear-pending / csr write): one donated jitted dispatch each, applied
# at (d, ...) of the stacked state, which they are given without its
# memory image.
@partial(jax.jit, donate_argnums=(0,))
def _fleet_redirect_op(sts, d, c, pc, resume):
    return sts._replace(
        pc=sts.pc.at[d, c].set(pc),
        priv=sts.priv.at[d, c].set(U32(0)),
        pending=sts.pending.at[d, c].set(False),
        stall_until=sts.stall_until.at[d, c].set(resume))


@partial(jax.jit, donate_argnums=(0,))
def _fleet_park_op(sts, d, c):
    return sts._replace(priv=sts.priv.at[d, c].set(U32(3)),
                        pending=sts.pending.at[d, c].set(False))


@partial(jax.jit, donate_argnums=(0,))
def _fleet_clear_pending_op(sts, d, c):
    return sts._replace(pending=sts.pending.at[d, c].set(False))


@partial(jax.jit, static_argnums=(2,), donate_argnums=(0,))
def _fleet_csr_write_op(sts, d, name, c, v):
    if name == "ticks":
        return sts._replace(ticks=sts.ticks.at[d].set(jnp.asarray(v, U64)))
    if name == "pending":
        val = jnp.asarray(v, U64) != 0
    elif name == "priv":
        val = jnp.asarray(v, U32)
    else:
        val = jnp.asarray(v, U64)
    return sts._replace(**{name: getattr(sts, name).at[d, c].set(val)})


@partial(jax.jit, donate_argnums=(0,))
def _fleet_reg_write_op(sts, d, c, idx, v):
    return sts._replace(regs=sts.regs.at[d, c, idx].set(v))


# Word- and page-granular twins of the cpu.py HTP data helpers over
# board d of the stacked memory image: one donated dispatch each.
@partial(jax.jit, donate_argnums=(0,))
def _fleet_mem_write_op(mem, d, w, v):
    return mem.at[d, w].set(v)


@partial(jax.jit, donate_argnums=(0,))
def _fleet_page_write_op(mem, d, off, words):
    return lax.dynamic_update_slice(mem, words[None, :], (d, off))


@partial(jax.jit, donate_argnums=(0,))
def _fleet_page_set_op(mem, d, off, val):
    return lax.dynamic_update_slice(mem, jnp.full((1, 512), val, U64),
                                    (d, off))


@partial(jax.jit, donate_argnums=(0,))
def _fleet_page_copy_op(mem, d, src, dst):
    page = lax.dynamic_slice(mem, (d, src), (1, 512))
    return lax.dynamic_update_slice(mem, page, (d, dst))


@jax.jit
def _fleet_page_read(mem, d, off):
    return lax.dynamic_slice(mem, (d, off), (1, 512))[0]


@partial(jax.jit, static_argnums=(2,))
def _fleet_fetch_read_batch(sts, d, csr_names: tuple,
                            reg_cpu, reg_idx, word_idx, csr_cpus):
    """Device-``d`` twin of :func:`repro.core.target.cpu.\
fetch_read_batch` over the stacked fleet state: same pow2-padded gather
    arrays, indexed at ``(d, ...)``, one compiled dispatch.  ``sts.mem``
    is None where the batch reads no word."""
    regs = sts.regs[d, reg_cpu, reg_idx]
    words = sts.mem[d, word_idx] if sts.mem is not None \
        else jnp.zeros(word_idx.shape, U64)
    csr_out = []
    for name, cc in zip(csr_names, csr_cpus):
        if name == "ticks":
            v = jnp.broadcast_to(sts.ticks[d], cc.shape).astype(U64)
        else:
            v = getattr(sts, name)[d, cc].astype(U64)
        csr_out.append(v)
    return regs, words, tuple(csr_out)


@partial(jax.jit, static_argnums=tuple(range(2, 11)), donate_argnums=(0,))
def _run_chunk_fleet_shards(sts, budgets, kernel, mesh, n_cores: int,
                            mem_bytes: int, issue_width: int,
                            block_words: int, block_cache: bool,
                            fetch_kernel: str, dtlb_ways: int):
    """One global chunk of a stack sharded along :data:`BOARD`: ``kernel``
    (``run_chunk_fleet``, static, so whatever the module names at the
    launch is what runs) on each chip's own boards, in one launch.

    The stack comes and goes without its trace ring, which a fleet never
    arms: an empty output would leave the program replicated, not
    sharded."""
    per_chip = sts.pc.shape[0] // mesh.size

    def shard(local, b):
        local = local._replace(
            tracebuf=jnp.zeros((per_chip, n_cores, 0, 4), U64))
        return kernel(local, n_cores, mem_bytes, b, issue_width,
                      block_words, block_cache, fetch_kernel, dtlb_ways,
                      per_chip)._replace(tracebuf=None)

    spec = PartitionSpec(BOARD)
    return jax.shard_map(shard, mesh=mesh, in_specs=(spec, spec),
                         out_specs=spec, check_vma=False)(sts, budgets)


class FleetTarget:
    """The stacked-state owner: D devices' CPU state in one pytree, one
    XLA dispatch per global chunk.

    ``view(d)`` hands out the per-device Target façade; ``run_global``
    advances every device by its budget in a single compiled call.
    ``fast_path`` is implied (the vmapped kernel IS the fast path), and
    ``fetch_kernel`` defaults to the pure-jnp oracle.  ``fleet_chips``
    shards the boards over that many of the process's devices, an equal
    share on each; None keeps the whole stack on the default device."""

    def __init__(self, n_devices: int, n_cores: int, mem_bytes: int,
                 chunk_cycles: int = 1 << 30, issue_width: int = 8,
                 block_words: int = 16, block_cache: bool = True,
                 fetch_kernel: str = "ref", dtlb_ways: int = 8,
                 fleet_chips: int | None = None):
        self.n_devices = n_devices
        self.n_cores = n_cores
        self.mem_bytes = mem_bytes
        self.chunk_cycles = chunk_cycles
        self.issue_width = issue_width
        self.block_words = block_words
        self.block_cache = block_cache
        self.fetch_kernel = fetch_kernel
        self.dtlb_ways = dtlb_ways
        self.mesh = None
        chips = [None]
        if fleet_chips is not None:
            found = jax.devices()
            if len(found) < fleet_chips:
                raise ValueError(f"a fleet on {fleet_chips} chips: this "
                                 f"process has {len(found)} devices")
            if n_devices % fleet_chips:
                raise ValueError(f"{n_devices} boards do not divide over "
                                 f"{fleet_chips} chips")
            self.mesh = Mesh(np.array(found[:fleet_chips]), (BOARD,))
            self._sharding = NamedSharding(self.mesh, PartitionSpec(BOARD))
            chips = list(self.mesh.devices.flat)
        #: boards a chip holds
        self.per_chip = n_devices // len(chips)
        #: each chip's part of the stack, ``(per_chip, ...)`` on that chip
        self._stacks = []
        for chip in chips:
            with jax.default_device(chip):
                part = _blank_stack(self.per_chip, n_cores, mem_bytes)
            # committed to its chip, so that no later program moves it
            self._stacks.append(part if chip is None
                                else jax.device_put(part, chip))
        #: every board's clock as the last chunk left it, until a write of
        #: a clock (``csr_write`` of ``ticks``, a provision) drops it
        self._clock: np.ndarray | None = None
        #: XLA dispatches of the vmapped chunk kernel (the
        #: one-dispatch-per-global-chunk acceptance counter)
        self.dispatch_count = 0
        #: boards given a budget, summed over those dispatches
        self.live_board_chunks = 0
        self._views = [FleetTargetView(self, d) for d in range(n_devices)]

    @property
    def sts(self) -> "_cpu.CpuState":
        """The whole stack: sharded along :data:`BOARD` over the chips'
        own buffers (no copy) when the fleet spans chips."""
        if self.mesh is None:
            return self._stacks[0]
        return _cpu.CpuState(*map(self._whole, _cpu.CpuState._fields))

    def _whole(self, field: str) -> jax.Array:
        """One field of every chip's part, as one array sharded along
        :data:`BOARD`."""
        parts = [getattr(st, field) for st in self._stacks]
        return jax.make_array_from_single_device_arrays(
            (self.n_devices,) + parts[0].shape[1:], self._sharding, parts)

    def view(self, d: int) -> "FleetTargetView":
        return self._views[d]

    def provision_view(self, d: int) -> "FleetTargetView":
        """Reset device ``d``'s lane to power-on state (the fleet-vmap
        analogue of a Device.provision building a fresh target) and
        return its view."""
        v = self._views[d]
        self._stacks[v.chip] = _fleet_reset_op(
            self._stacks[v.chip], np.int32(v.local), self.n_cores,
            self.mem_bytes)
        self._clock = None
        return v

    def run_global(self, budgets) -> None:
        """ONE dispatch for the whole fleet: advance device ``i`` by up
        to ``budgets[i]`` cycles (0 = bit-exactly untouched), then bring
        every board's clock home in one read, which waits on the chunk
        (``FleetRuntime.run_synchronous`` holds both in ``fase:chunk``)."""
        budgets = np.minimum(np.asarray(budgets, np.uint64),
                             np.uint64(self.chunk_cycles))
        kernel = _cpu.run_chunk_fleet       # looked up at each launch
        if self.mesh is None:
            self._stacks[0] = kernel(
                self._stacks[0], self.n_cores, self.mem_bytes, budgets,
                self.issue_width, self.block_words, self.block_cache,
                self.fetch_kernel, self.dtlb_ways, self.n_devices)
        else:
            out = _run_chunk_fleet_shards(
                self.sts._replace(tracebuf=None),
                jax.device_put(budgets, self._sharding), kernel, self.mesh,
                self.n_cores, self.mem_bytes, self.issue_width,
                self.block_words, self.block_cache, self.fetch_kernel,
                self.dtlb_ways)
            shards = {f: {s.device: s.data for s in x.addressable_shards}
                      for f, x in out._asdict().items() if x is not None}
            self._stacks = [
                old._replace(**{f: by_chip[chip]
                                for f, by_chip in shards.items()})
                for chip, old in zip(self.mesh.devices.flat, self._stacks)]
        self._clock = np.asarray(self._stacks[0].ticks if self.mesh is None
                                 else self._whole("ticks"))
        self.dispatch_count += 1
        self.live_board_chunks += int(np.count_nonzero(budgets))


class FleetTargetView:
    """Device ``d``'s full Target-protocol façade over the stack.

    Every accessor indexes the stacked arrays at ``(d, ...)``, on the
    chip that holds board ``d`` (``chip``, at ``local`` in its part of
    the stack); ``run`` issues a one-hot global dispatch.  Drop-in for
    :class:`~repro.core.interface.JaxTarget` behind a queue pair."""

    def __init__(self, ft: FleetTarget, d: int):
        self.ft = ft
        self.d = d
        self.chip, self.local = divmod(d, ft.per_chip)
        self.nc = ft.n_cores
        self.mem_bytes = ft.mem_bytes
        self.chunk_cycles = ft.chunk_cycles
        self.fast_path = True
        self.trace_slots = 0

    @property
    def n_cores(self):
        return self.nc

    @property
    def st(self):
        """This device's :class:`CpuState` slice (conformance-suite
        surface: ``assert_same_state`` reads ``st.mem``)."""
        return jax.tree_util.tree_map(lambda x: x[self.local],
                                      self.ft._stacks[self.chip])

    def _update(self, op, *args, words: bool = False):
        """``op(stack, local, *args)`` on this board's chip, donated; the
        stack is given without its memory image unless ``words``."""
        st = self.ft._stacks[self.chip]
        part = st if words else st._replace(mem=None)
        out = op(part, np.int32(self.local), *args)
        self.ft._stacks[self.chip] = out if words \
            else out._replace(mem=st.mem)

    def _update_mem(self, op, *args):
        """``op(mem, local, *args)`` on this board's chip's image,
        donated."""
        st = self.ft._stacks[self.chip]
        self.ft._stacks[self.chip] = st._replace(
            mem=op(st.mem, np.int32(self.local), *args))

    # -- inst stream ------------------------------------------------------
    def run(self, max_cycles: int = 1 << 62):
        budgets = np.zeros(self.ft.n_devices, np.uint64)
        budgets[self.d] = min(max_cycles, self.chunk_cycles)
        self.ft.run_global(budgets)

    @spans.traced("acc:redirect")
    def redirect(self, c, pc, resume_tick=0):
        self._update(_fleet_redirect_op, np.int32(c), np.uint64(pc),
                     np.uint64(max(resume_tick, 0)))

    @spans.traced("acc:park")
    def park(self, c):
        self._update(_fleet_park_op, np.int32(c))

    def pending_cores(self):
        pending = self.fetch_batch(
            csrs=[(c, "pending") for c in range(self.nc)])[1]
        return [c for c, v in enumerate(pending) if v]

    @spans.traced("acc:clear_pending")
    def clear_pending(self, c):
        self._update(_fleet_clear_pending_op, np.int32(c))

    # -- priv / csr -------------------------------------------------------
    def csr_read(self, c, name):
        return self.fetch_batch(csrs=[(c, name)])[1][0]

    def get_priv(self, c):
        return self.csr_read(c, "priv")

    @spans.traced("acc:csr_write")
    def csr_write(self, c, name, v):
        self._update(_fleet_csr_write_op, name, np.int32(c),
                     np.uint64(v & ((1 << 64) - 1)))
        if name == "ticks":
            self.ft._clock = None

    @spans.traced("acc:set_satp")
    def set_satp(self, c, v):
        self._update(_fleet_csr_write_op, "satp", np.int32(c),
                     np.uint64(v))

    def sfence(self, c):
        # chunk-local caches only (fetch blocks + DTlb inside one
        # run_chunk_fleet call): host-driven PTE changes are visible to
        # the next chunk by construction, same as JaxTarget.sfence
        pass

    # -- regs -------------------------------------------------------------
    def reg_read(self, c, idx):
        return self.fetch_batch(regs=[(c, idx)])[0][0]

    @spans.traced("acc:reg_write")
    def reg_write(self, c, idx, v):
        if idx != 0:
            self._update(_fleet_reg_write_op, np.int32(c), np.int32(idx),
                         np.uint64(v & ((1 << 64) - 1)))

    def fetch_batch(self, regs=(), csrs=(), words=()):
        """One blocking device sync for any read mix on this device —
        see :meth:`repro.core.interface.JaxTarget.fetch_batch`."""
        regs, words = list(regs), list(words)
        packed = pack_read_batch(regs, csrs, words)
        if packed is None:
            return [], [], []
        names, reg_cpu, reg_idx, word_idx, csr_cpus, order = packed
        if words:
            # a read of up to a page of words gathers a whole page: each
            # chip compiles programs of its own, and so the first word
            # read of a board compiles its chip's one word-read shape
            word_idx = np.pad(word_idx, (0, max(512 - word_idx.size, 0)))
        st = self.ft._stacks[self.chip]
        with spans.span("sync:fetch_batch"):
            got = jax.device_get(_fleet_fetch_read_batch(
                st if words else st._replace(mem=None),
                np.int32(self.local), names, reg_cpu, reg_idx, word_idx,
                csr_cpus))
        return unpack_read_batch(got, len(regs), len(words), names,
                                 order)

    @spans.traced("acc:commit_batch")
    def commit_batch(self, regs=(), csrs=(), words=()):
        """One donated device update for any staged write mix on this
        device — see :meth:`repro.core.interface.JaxTarget.\
commit_batch`."""
        words = list(words)
        packed = pack_write_batch(self.nc, self.mem_bytes >> 3,
                                  regs, csrs, words)
        if packed is not None:
            self._update(_fleet_write_batch, *packed, words=bool(words))

    # -- memory -----------------------------------------------------------
    def mem_read_word(self, pa):
        return self.fetch_batch(words=[pa])[2][0]

    @spans.traced("acc:mem_write_word")
    def mem_write_word(self, pa, v):
        self._update_mem(_fleet_mem_write_op, np.int32(pa >> 3),
                         np.uint64(v))

    @spans.traced("sync:page_read")
    def page_read(self, ppn):
        return np.asarray(_fleet_page_read(
            self.ft._stacks[self.chip].mem, np.int32(self.local),
            np.int32((ppn << 12) >> 3)))

    @spans.traced("acc:page_write")
    def page_write(self, ppn, words):
        self._update_mem(_fleet_page_write_op, np.int32((ppn << 12) >> 3),
                         np.ascontiguousarray(words, dtype=np.uint64))

    @spans.traced("acc:page_set")
    def page_set(self, ppn, val):
        self._update_mem(_fleet_page_set_op, np.int32((ppn << 12) >> 3),
                         np.uint64(val))

    @spans.traced("acc:page_copy")
    def page_copy(self, src_ppn, dst_ppn):
        self._update_mem(_fleet_page_copy_op,
                         np.int32((src_ppn << 12) >> 3),
                         np.int32((dst_ppn << 12) >> 3))

    # -- perf -------------------------------------------------------------
    def get_ticks(self):
        if self.ft._clock is not None:
            return int(self.ft._clock[self.d])
        return self.csr_read(0, "ticks")

    def get_uticks(self, c):
        return self.csr_read(c, "uticks")

    def get_instret(self, c):
        return self.csr_read(c, "instret")

    # -- telemetry --------------------------------------------------------
    def trace_arm(self, slots):
        raise NotImplementedError(
            "commit-trace capture is single-device; run this device on a "
            "plain JaxTarget (fleet_vmap=False) to arm the trace ring")

    def trace_trigger(self, spec):
        if spec is not None:
            self.trace_arm(0)

    def trace_drain(self, c=None, limit=None):
        # unarmed ring, mirroring JaxTarget.trace_drain's unarmed path
        return ([], 0) if c is not None else [([], 0)] * self.nc
