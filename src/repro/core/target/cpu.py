"""The jitted XLA target CPU model (the "FPGA" role).

State is a NamedTuple of device arrays stepped by a compiled
``while_loop`` that retires one instruction per non-stalled core per
global tick (cores stepping in core-index order within a tick) until a
core raises an exception, every core is parked, or the cycle budget runs
out.  When every live core is stalled on ``stall_until`` the loop
fast-forwards time to the next wake-up in one step — channel-induced
stalls cost no host work.

Two compiled interpreters share these semantics:

  * :func:`run_chunk` — the reference loop: one scalar
    :func:`_exec_one` per runnable core per tick.  On XLA:CPU its
    per-core gather results feed several carried buffers at once, which
    defeats in-place buffer assignment and costs a full copy of target
    memory per retired instruction — it is kept as the conformance
    baseline the fast path is measured against
    (``benchmarks/target_speed.py``).
  * :func:`run_chunk_fast` — the fast path: all cores execute one tick
    as lane-vectorized math (:func:`_exec_substep`), a chunk-local
    fetch-block cache skips the Sv39 fetch walk and instruction gather
    for straight-line code, and ``issue_width`` ticks are retired per
    loop iteration.  Same-tick memory dependencies between cores are
    detected *before* any write lands and only the conflict-free prefix
    of the core order is applied (the rest of the tick replays from
    post-commit state), so multicore interleaving, LR/SC and
    self-modifying code stay bit-identical to the reference.

Semantics of both are defined to be bit-identical to the pure-Python
twin (:mod:`repro.core.target.pysim`); keep the three in lock-step
(``tests/test_cpu_differential.py`` fuzzes exactly this).  The decode/
ALU/trap math in :func:`_exec_substep` deliberately duplicates
:func:`_exec_one` rather than sharing helpers: the two compiled
interpreters stay independent implementations, so a bug in one is
caught by the differential harness against the other two instead of
propagating to every JAX path at once.  The word- and
page-granular helpers at the bottom are the device-side halves of the
HTP data-access requests (``MemR/MemW/PageS/PageCP/PageR/PageW``).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax

jax.config.update("jax_enable_x64", True)  # the target is a 64-bit CPU

import jax.numpy as jnp              # noqa: E402
from jax import lax                  # noqa: E402

from . import isa                    # noqa: E402
from ...kernels.page_walk import ops as pw_ops   # noqa: E402
from ...kernels.page_walk import ref as pw_ref   # noqa: E402

CLOCK_HZ = 100_000_000

U64 = jnp.uint64
U32 = jnp.uint32
I64 = jnp.int64
_RES_INVALID = (1 << 64) - 1
_INT64_MIN = -(1 << 63)


def _u(x):
    return jnp.uint64(x)


#: Per-core architectural state a target checkpoint captures/restores
#: (:mod:`repro.core.snapshot`), in capture order.  Every name is both a
#: :class:`CpuState` field and a same-named per-core list on the PySim
#: twin, which is what makes a snapshot backend-portable; ``ticks`` (the
#: global clock) is captured separately via the Tick request.
SNAPSHOT_CORE_FIELDS = ("pc", "priv", "pending", "stall_until", "satp",
                        "mcause", "mepc", "mtval", "res", "uticks",
                        "instret")


class CpuState(NamedTuple):
    regs: jax.Array          # (nc, 32) u64
    pc: jax.Array            # (nc,) u64
    priv: jax.Array          # (nc,) u32 — 0 user, 3 parked
    pending: jax.Array       # (nc,) bool
    stall_until: jax.Array   # (nc,) u64
    satp: jax.Array          # (nc,) u64
    mcause: jax.Array        # (nc,) u64
    mepc: jax.Array          # (nc,) u64
    mtval: jax.Array         # (nc,) u64
    res: jax.Array           # (nc,) u64 LR reservation pa, ~0 = invalid
    mem: jax.Array           # (mem_bytes // 8,) u64
    ticks: jax.Array         # () u64
    uticks: jax.Array        # (nc,) u64
    instret: jax.Array       # (nc,) u64
    # -- telemetry counters (repro.telemetry; NOT snapshot state) --------
    stall_ticks: jax.Array   # (nc,) u64 — ticks spent active-but-stalled
    fetch_hits: jax.Array    # (nc,) u64 — fetch-block cache hits (model)
    fetch_walks: jax.Array   # (nc,) u64 — fetch-block fills/walks (model)
    tlb_walks: jax.Array     # (nc,) u64 — data-TLB walks (model counter:
    #                          the fast path counts misses of its chunk-
    #                          local data cache when ``dtlb_ways > 0``;
    #                          the scalar loop walks every access and
    #                          keeps it 0.  PySim counts its own cache's
    #                          misses — the counter-identity contract in
    #                          tests/test_telemetry.py explicitly allows
    #                          the backends to differ here)
    tracebuf: jax.Array      # (nc, slots, 4) u64 — commit-trace ring:
    #                          (tick, pc, inst, priv) per retirement
    trace_n: jax.Array       # (nc,) u64 — records ever produced (the
    #                          host derives ring drops from this)
    trace_armed: jax.Array   # (nc,) bool — sticky capture-window arm
    #                          state for pc/inst triggers (trace_trigger;
    #                          NOT snapshot state)


def make_state(n_cores: int, mem_bytes: int,
               trace_slots: int = 0) -> CpuState:
    assert mem_bytes & (mem_bytes - 1) == 0, "mem_bytes must be pow2"
    nc = n_cores
    z = lambda: jnp.zeros((nc,), U64)       # noqa: E731
    return CpuState(
        regs=jnp.zeros((nc, 32), U64), pc=z(),
        priv=jnp.full((nc,), 3, U32), pending=jnp.zeros((nc,), bool),
        stall_until=z(), satp=z(), mcause=z(), mepc=z(), mtval=z(),
        res=jnp.full((nc,), _RES_INVALID, U64),
        mem=jnp.zeros((mem_bytes // 8,), U64),
        ticks=_u(0), uticks=z(), instret=z(),
        stall_ticks=z(), fetch_hits=z(), fetch_walks=z(), tlb_walks=z(),
        tracebuf=jnp.zeros((nc, trace_slots, 4), U64), trace_n=z(),
        trace_armed=jnp.zeros((nc,), bool),
    )


def _sx(v, bits):
    """Sign-extend the low ``bits`` of u64 ``v`` (wrapping arithmetic)."""
    m = _u(1 << (bits - 1))
    return (v ^ m) - m


def _translate(mem, satp, va, want_write, want_exec, mask):
    """Sv39 walk; returns (pa, fault).  Bare when satp mode != 8."""
    bare = (satp >> _u(60)) != _u(8)
    need = _u(isa.PTE_U) | jnp.where(
        want_exec, _u(isa.PTE_X),
        jnp.where(want_write, _u(isa.PTE_W), _u(isa.PTE_R)))
    a = (satp & _u((1 << 44) - 1)) << _u(12)
    done = jnp.bool_(False)
    fault = jnp.bool_(False)
    pa = _u(0)
    for level in (2, 1, 0):
        idx = (va >> _u(12 + 9 * level)) & _u(0x1FF)
        pte = mem[((a + idx * _u(8)) & mask) >> _u(3)]
        valid = (pte & _u(isa.PTE_V)) != 0
        leaf = valid & ((pte & _u(isa.PTE_R | isa.PTE_X)) != 0)
        perm_ok = (pte & need) == need
        off_mask = _u((1 << (12 + 9 * level)) - 1)
        leaf_pa = (((pte >> _u(10)) << _u(12)) | (va & off_mask)) & mask
        take = ~done
        fault = fault | (take & (~valid | (leaf & ~perm_ok)))
        pa = jnp.where(take & leaf & perm_ok, leaf_pa, pa)
        done = done | (take & (~valid | leaf))
        a = jnp.where(take & valid & ~leaf, (pte >> _u(10)) << _u(12), a)
    fault = (fault | ~done) & ~bare
    pa = jnp.where(bare, va, pa) & mask
    return pa, fault


def _mulhu(a, b):
    m32 = _u(0xFFFFFFFF)
    al, ah = a & m32, a >> _u(32)
    bl, bh = b & m32, b >> _u(32)
    ll = al * bl
    lh = al * bh
    hl = ah * bl
    mid = (ll >> _u(32)) + (lh & m32) + (hl & m32)
    return ah * bh + (lh >> _u(32)) + (hl >> _u(32)) + (mid >> _u(32))


def _sdiv_parts(a, b):
    """Signed div/rem with RISC-V div0/overflow semantics (64-bit)."""
    sa = a.astype(I64)
    sb = b.astype(I64)
    div0 = b == 0
    ovf = (sa == _INT64_MIN) & (sb == -1)
    den = jnp.where(div0 | ovf, jnp.int64(1), sb)
    q = lax.div(sa, den)
    r = lax.rem(sa, den)
    q = jnp.where(div0, jnp.int64(-1), jnp.where(ovf, sa, q))
    r = jnp.where(div0, sa, jnp.where(ovf, jnp.int64(0), r))
    return q.astype(U64), r.astype(U64)


def _udiv_parts(a, b):
    div0 = b == 0
    den = jnp.where(div0, _u(1), b)
    q = jnp.where(div0, _u(_RES_INVALID), a // den)
    r = jnp.where(div0, a, a % den)
    return q, r


def _alu64(f3, is_sub, is_sra, is_m, a, b):
    sa = a.astype(I64)
    sb = b.astype(I64)
    sh = b & _u(63)
    base = jnp.select(
        [f3 == 0, f3 == 1, f3 == 2, f3 == 3, f3 == 4, f3 == 5, f3 == 6],
        [jnp.where(is_sub, a - b, a + b),
         a << sh,
         (sa < sb).astype(U64),
         (a < b).astype(U64),
         a ^ b,
         jnp.where(is_sra, (sa >> sh.astype(I64)).astype(U64), a >> sh),
         a | b],
        a & b)
    q, r = _sdiv_parts(a, b)
    uq, ur = _udiv_parts(a, b)
    mulhu = _mulhu(a, b)
    mulh = mulhu - jnp.where(sa < 0, b, _u(0)) - jnp.where(sb < 0, a, _u(0))
    mulhsu = mulhu - jnp.where(sa < 0, b, _u(0))
    m = jnp.select(
        [f3 == 0, f3 == 1, f3 == 2, f3 == 3, f3 == 4, f3 == 5, f3 == 6],
        [a * b, mulh, mulhsu, mulhu, q, uq, r],
        ur)
    return jnp.where(is_m, m, base)


def _alu32(f3, is_sub, is_sra, is_m, a, b):
    m32 = _u(0xFFFFFFFF)
    a32 = a & m32
    b32 = b & m32
    sa = _sx(a32, 32).astype(I64)
    sb = _sx(b32, 32).astype(I64)
    sh = b & _u(31)
    base = jnp.select(
        [f3 == 0, f3 == 1],
        [jnp.where(is_sub, a - b, a + b),
         a32 << sh],
        jnp.where(is_sra, (sa >> sh.astype(I64)).astype(U64), a32 >> sh))
    div0 = b32 == 0
    ovf = (sa == -(1 << 31)) & (sb == -1)
    den = jnp.where(div0 | ovf, jnp.int64(1), sb)
    q = jnp.where(div0, jnp.int64(-1),
                  jnp.where(ovf, sa, lax.div(sa, den))).astype(U64)
    r = jnp.where(div0, sa,
                  jnp.where(ovf, jnp.int64(0), lax.rem(sa, den))).astype(U64)
    uden = jnp.where(div0, _u(1), b32)
    uq = jnp.where(div0, _u(_RES_INVALID), a32 // uden)
    ur = jnp.where(div0, a32, a32 % uden)
    m = jnp.select([f3 == 0, f3 == 4, f3 == 5, f3 == 6],
                   [a32 * b32, q, uq, r], ur)
    return _sx(jnp.where(is_m, m, base) & m32, 32)


def _exec_one(st: CpuState, c: int, nc: int, mask) -> CpuState:
    mem = st.mem
    pc = st.pc[c]
    satp = st.satp[c]
    f_ = jnp.bool_(False)

    ipa, ifault = _translate(mem, satp, pc, f_, jnp.bool_(True), mask)
    iword = mem[ipa >> _u(3)]
    inst = (iword >> (((ipa >> _u(2)) & _u(1)) * _u(32))) & _u(0xFFFFFFFF)

    op = inst & _u(0x7F)
    rd = (inst >> _u(7)) & _u(0x1F)
    f3 = (inst >> _u(12)) & _u(7)
    rs1 = (inst >> _u(15)) & _u(0x1F)
    rs2 = (inst >> _u(20)) & _u(0x1F)
    f7 = inst >> _u(25)
    imm_i = _sx(inst >> _u(20), 12)
    imm_s = _sx(((inst >> _u(25)) << _u(5)) | rd, 12)
    imm_b = _sx((((inst >> _u(8)) & _u(0xF)) << _u(1)) |
                (((inst >> _u(25)) & _u(0x3F)) << _u(5)) |
                (((inst >> _u(7)) & _u(1)) << _u(11)) |
                ((inst >> _u(31)) << _u(12)), 13)
    imm_u = _sx(inst & _u(0xFFFFF000), 32)
    imm_j = _sx((((inst >> _u(21)) & _u(0x3FF)) << _u(1)) |
                (((inst >> _u(20)) & _u(1)) << _u(11)) |
                (((inst >> _u(12)) & _u(0xFF)) << _u(12)) |
                ((inst >> _u(31)) << _u(20)), 21)

    regs_c = st.regs[c]
    a = regs_c[rs1]
    b = regs_c[rs2]

    is_load = op == _u(isa.OP_LOAD)
    is_fence = op == _u(isa.OP_MISC_MEM)
    is_opimm = op == _u(isa.OP_IMM)
    is_auipc = op == _u(isa.OP_AUIPC)
    is_opimm32 = op == _u(isa.OP_IMM_32)
    is_store = op == _u(isa.OP_STORE)
    is_amo = op == _u(isa.OP_AMO)
    is_op = op == _u(isa.OP_OP)
    is_lui = op == _u(isa.OP_LUI)
    is_op32 = op == _u(isa.OP_OP_32)
    is_branch = op == _u(isa.OP_BRANCH)
    is_jalr = op == _u(isa.OP_JALR)
    is_jal = op == _u(isa.OP_JAL)
    is_system = op == _u(isa.OP_SYSTEM)
    is_ecall = is_system & (inst == _u(isa.INST_ECALL))
    is_ebreak = is_system & (inst == _u(isa.INST_EBREAK))
    illegal = ~(is_load | is_fence | is_opimm | is_auipc | is_opimm32 |
                is_store | is_amo | is_op | is_lui | is_op32 | is_branch |
                is_jalr | is_jal | is_ecall | is_ebreak)

    # ---- ALU ----------------------------------------------------------
    reg_form = is_op | is_op32
    bop = jnp.where(reg_form, b, imm_i)
    is_m = reg_form & (f7 == _u(1))
    is_sub = reg_form & (f7 == _u(0x20)) & (f3 == _u(0))
    is_sra = jnp.where(reg_form, f7 == _u(0x20),
                       (inst >> _u(30)) & _u(1) != 0) & (f3 == _u(5))
    alu_w = _alu64(f3, is_sub, is_sra, is_m, a, bop)
    alu_w32 = _alu32(f3, is_sub, is_sra, is_m, a, bop)

    # ---- data memory access -------------------------------------------
    funct5 = f7 >> _u(2)
    is_lr = is_amo & (funct5 == _u(isa.AMO_LR))
    is_sc = is_amo & (funct5 == _u(isa.AMO_SC))
    dva = jnp.where(is_amo, a,
                    a + jnp.where(is_store, imm_s, imm_i))
    is_memop = is_load | is_store | is_amo
    want_w = is_store | (is_amo & ~is_lr)
    dpa, dfault = _translate(mem, satp, dva, want_w, f_, mask)
    szb = jnp.where(is_amo,
                    jnp.where(f3 == _u(2), _u(4), _u(8)),
                    _u(1) << (f3 & _u(3)))
    misal = is_memop & ((dva & (szb - _u(1))) != 0)

    dword = mem[dpa >> _u(3)]
    dshift = (dpa & _u(7)) << _u(3)
    raw = dword >> dshift
    sizemask = jnp.select([szb == _u(1), szb == _u(2), szb == _u(4)],
                          [_u(0xFF), _u(0xFFFF), _u(0xFFFFFFFF)],
                          _u(_RES_INVALID))
    rawv = raw & sizemask
    uns = (f3 & _u(4)) != 0
    loaded = jnp.select(
        [szb == _u(1), szb == _u(2), szb == _u(4)],
        [jnp.where(uns, rawv, _sx(rawv, 8)),
         jnp.where(uns, rawv, _sx(rawv, 16)),
         jnp.where(uns, rawv, _sx(rawv, 32))],
        rawv)

    # ---- AMO ----------------------------------------------------------
    amo_w = f3 == _u(2)
    amo_old = rawv                       # width-masked old value
    amo_b = b & sizemask
    s_old = jnp.where(amo_w, _sx(amo_old, 32), amo_old).astype(I64)
    s_b = jnp.where(amo_w, _sx(amo_b, 32), amo_b).astype(I64)
    amo_new = jnp.select(
        [funct5 == _u(isa.AMO_SWAP), funct5 == _u(isa.AMO_ADD),
         funct5 == _u(isa.AMO_XOR), funct5 == _u(isa.AMO_AND),
         funct5 == _u(isa.AMO_OR), funct5 == _u(isa.AMO_MIN),
         funct5 == _u(isa.AMO_MAX), funct5 == _u(isa.AMO_MINU)],
        [amo_b, amo_old + amo_b, amo_old ^ amo_b, amo_old & amo_b,
         amo_old | amo_b,
         jnp.where(s_old < s_b, amo_old, amo_b),
         jnp.where(s_old > s_b, amo_old, amo_b),
         jnp.where(amo_old < amo_b, amo_old, amo_b)],
        jnp.where(amo_old > amo_b, amo_old, amo_b))
    sc_ok = is_sc & (st.res[c] == dpa)
    amo_rdval = jnp.where(
        is_sc, jnp.where(sc_ok, _u(0), _u(1)),
        jnp.where(amo_w, _sx(amo_old, 32), amo_old))

    # ---- traps --------------------------------------------------------
    ma_cause = jnp.where(is_load | is_lr, _u(4), _u(6))
    pf_cause = jnp.where(want_w, _u(15), _u(13))
    dtrap = is_memop & (misal | dfault)
    trapped = ifault | illegal | is_ecall | is_ebreak | dtrap
    cause = jnp.where(
        ifault, _u(12),
        jnp.where(illegal, _u(2),
                  jnp.where(is_ecall, _u(8),
                            jnp.where(is_ebreak, _u(3),
                                      jnp.where(misal, ma_cause,
                                                pf_cause)))))
    tval = jnp.where(
        ifault, pc,
        jnp.where(illegal, inst,
                  jnp.where(is_ecall | is_ebreak, _u(0), dva)))

    # ---- memory commit -------------------------------------------------
    commit = ~trapped & (is_store |
                         (is_amo & ~is_lr & (~is_sc | sc_ok)))
    sval = jnp.where(is_store | is_sc, b, amo_new)
    wmask = sizemask << dshift
    new_word = (dword & ~wmask) | ((sval << dshift) & wmask)
    widx = jnp.where(commit, dpa >> _u(3), _u(0))
    wold = mem[widx]
    new_mem = mem.at[widx].set(jnp.where(commit, new_word, wold))

    # ---- reservations ---------------------------------------------------
    line = dpa & ~_u(7)
    others = jnp.arange(nc) != c
    res = jnp.where(others & commit & ((st.res & ~_u(7)) == line),
                    _u(_RES_INVALID), st.res)
    own = jnp.where(
        trapped, st.res[c],
        jnp.where(is_lr, dpa,
                  jnp.where(is_sc, _u(_RES_INVALID), st.res[c])))
    res = res.at[c].set(own)

    # ---- next pc / register writeback ----------------------------------
    sa = a.astype(I64)
    sb64 = b.astype(I64)
    taken = is_branch & jnp.select(
        [f3 == _u(0), f3 == _u(1), f3 == _u(4), f3 == _u(5), f3 == _u(6)],
        [a == b, a != b, sa < sb64, sa >= sb64, a < b],
        a >= b)
    next_pc = pc + _u(4)
    next_pc = jnp.where(taken, pc + imm_b, next_pc)
    next_pc = jnp.where(is_jal, pc + imm_j, next_pc)
    next_pc = jnp.where(is_jalr, (a + imm_i) & ~_u(1), next_pc)

    wval = jnp.where(is_opimm | is_op, alu_w, _u(0))
    wval = jnp.where(is_opimm32 | is_op32, alu_w32, wval)
    wval = jnp.where(is_load, loaded, wval)
    wval = jnp.where(is_lui, imm_u, wval)
    wval = jnp.where(is_auipc, pc + imm_u, wval)
    wval = jnp.where(is_jal | is_jalr, pc + _u(4), wval)
    wval = jnp.where(is_amo, amo_rdval, wval)
    wen = (is_opimm | is_op | is_opimm32 | is_op32 | is_load | is_lui |
           is_auipc | is_jal | is_jalr | is_amo) & (rd != 0) & ~trapped
    new_regs = st.regs.at[c, rd].set(jnp.where(wen, wval, st.regs[c, rd]))

    retired = ~trapped
    return st._replace(
        regs=new_regs,
        pc=st.pc.at[c].set(jnp.where(trapped, pc, next_pc)),
        pending=st.pending.at[c].set(trapped),
        mcause=jnp.where(trapped, st.mcause.at[c].set(cause), st.mcause),
        mepc=jnp.where(trapped, st.mepc.at[c].set(pc), st.mepc),
        mtval=jnp.where(trapped, st.mtval.at[c].set(tval), st.mtval),
        res=res,
        mem=new_mem,
        uticks=st.uticks.at[c].add(retired.astype(U64)),
        instret=st.instret.at[c].add(retired.astype(U64)),
    )


@partial(jax.jit, static_argnums=(1, 2), donate_argnums=(0,))
def run_chunk(st: CpuState, n_cores: int, mem_bytes: int,
              max_cycles) -> CpuState:
    nc = n_cores
    mask = _u(mem_bytes - 1)
    limit = jnp.asarray(max_cycles, U64)

    def cond(carry):
        st, cycles = carry
        return ((cycles < limit) & ~jnp.any(st.pending) &
                jnp.any(st.priv != 3))

    def body(carry):
        st, cycles = carry
        active = st.priv != 3
        can = active & (st.ticks >= st.stall_until)

        def do_exec(st):
            for c in range(nc):
                # not parked (priv != 3) — NOT priv == 0: PySim executes
                # S-mode cores too, and `cond`/`active` already treat
                # every non-parked core as live.  Gating on user mode
                # here silently skipped restored S-mode cores while the
                # tick clock kept advancing (see test_priv_gate_matches_
                # pysim in tests/test_cpu_differential.py).
                runnable = ((st.priv[c] != 3) & ~st.pending[c] &
                            (st.ticks >= st.stall_until[c]))
                st = lax.cond(runnable,
                              lambda s: _exec_one(s, c, nc, mask),
                              lambda s: s, st)
            return st._replace(ticks=st.ticks + _u(1)), _u(1)

        def do_skip(st):
            gaps = jnp.where(active, st.stall_until - st.ticks,
                             _u(_RES_INVALID))
            gap = jnp.minimum(jnp.min(gaps), limit - cycles)
            return st._replace(ticks=st.ticks + gap), gap

        st, dc = lax.cond(jnp.any(can), do_exec, do_skip, st)
        return st, cycles + dc

    st, _ = lax.while_loop(cond, body, (st, _u(0)))
    return st


# ---------------------------------------------------------------------------
# Fast-path interpreter: vectorized tick, fetch-block cache, batched issue
# ---------------------------------------------------------------------------
#: Sentinel word index for "reads nothing here" in the same-tick conflict
#: read sets — outside any reachable physical word index.
_NO_WORD = (1 << 64) - 1


class FetchBlocks(NamedTuple):
    """Per-core fetch-block cache: one translated, pre-gathered run of
    consecutive instruction slots per core.  Strictly chunk-local — it is
    rebuilt empty on every :func:`run_chunk_fast` call, so host-side
    writes between chunks (redirect, sfence, satp/CSR writes, page loads,
    snapshot restore) can never serve stale without any explicit
    invalidation protocol.  Within a chunk, any committed store that
    lands inside a cached range zeroes that block's ``nbytes``.

    A guest store into the *page tables* that translated a block does
    NOT invalidate it — the same delayed-shootdown envelope PySim's own
    host-side TLB has (stale until an sfence, which only the host can
    issue; the guest ISA carries no CSR/sfence instructions and the
    runtime flushes after every PTE change it makes).  All three
    interpreters already sit at different points in that envelope
    (PySim caches across chunks, the scalar loop re-walks always), and
    the bit-identity contract is defined over the flush discipline the
    runtime enforces."""

    vbase: jax.Array    # (nc,) u64 — virtual address of the first slot
    pbase: jax.Array    # (nc,) u64 — its physical address
    nbytes: jax.Array   # (nc,) u64 — valid bytes cached (0 = invalid)
    insts: jax.Array    # (nc, block_words) u32 — raw instruction words


def _empty_blocks(nc: int, block_words: int) -> FetchBlocks:
    z = jnp.zeros((nc,), U64)
    return FetchBlocks(z, z, z, jnp.zeros((nc, block_words), jnp.uint32))


class DTlb(NamedTuple):
    """Chunk-local per-lane data-translation cache — the load/store twin
    of :class:`FetchBlocks`.  Direct-mapped on ``vpn & (ways - 1)``, one
    row per lane, 4 KiB (level-0) leaves only, exactly like PySim's TLB.
    Strictly chunk-local (rebuilt empty every :func:`run_chunk_fast`
    call), so host-driven PTE writes and sfence between chunks can never
    serve stale, and there is no satp tag: the guest ISA carries no CSR
    writes, so a lane's ``satp`` cannot change inside a chunk.  Within a
    chunk a committed store over a cached entry's backing leaf PTE kills
    the entry (``ptw`` match) — the same SMC-exact store-overlap rule the
    fetch blocks apply, sitting inside the delayed-shootdown envelope
    documented on :class:`FetchBlocks`."""

    vpn: jax.Array     # (L, ways) u64 — tag; _NO_WORD = empty way
    ppn: jax.Array     # (L, ways) u64 — post-mask physical page number
    perms: jax.Array   # (L, ways) u64 — leaf PTE permission byte
    ptw: jax.Array     # (L, ways) u64 — word index of the backing PTE


def _empty_dtlb(lanes: int, ways: int) -> DTlb:
    z = jnp.zeros((lanes, ways), U64)
    return DTlb(jnp.full((lanes, ways), _u(_NO_WORD)), z, z, z)


def _exec_substep(st: CpuState, fb: FetchBlocks, dtlb: DTlb, exec_from,
                  gate, budget_left, nc: int, mask, block_words: int,
                  block_cache: bool, walk_fetch, dtlb_ways: int = 0,
                  trace_on: bool = False,
                  trigger: tuple | None = None,
                  n_devices: int = 1, mem_words: int = 0):
    """One fast-path substep: a whole global tick in the common case.

    Mirrors :func:`_exec_one` lane-wise from the pre-substep state, then
    checks whether core-index execution order could have produced a
    different result: an earlier core committing a store into a later
    core's read set (fetch word, PTE walk words, data word), into the
    same word a later core also writes, or onto a line a later core
    holds an LR reservation for.  Only the conflict-free *prefix* of the
    core order is applied; ``exec_from`` (the first lane still owed this
    tick's issue) is returned non-zero and the next substep re-executes
    the deferred lanes from post-commit state — exactly the sequential
    core-order result, with no branch anywhere near the memory buffer.
    The tick counter advances only when a tick completes, and a tick
    whose every live lane is stalled fast-forwards the clock to the next
    wake-up (clamped to ``budget_left``) like the reference loop's skip
    arm.

    ``gate`` is the scalar "a new tick may start" predicate from the
    batched-issue unroll; a partially-executed tick always finishes
    regardless (matching PySim, where a trap raised mid-tick never stops
    the later cores of that same tick).  ``dtlb`` (used when
    ``dtlb_ways > 0``) carries the chunk-local data-translation cache at
    ``L`` lanes.  Returns ``(st, fb, dtlb, exec_from', dcycles)``.

    All lane math runs at ``L = max(lanes, 2)`` lanes with any pad lane
    permanently parked: XLA rewrites single-element gathers/scatters on
    the memory image into dynamic-slice forms that later fuse into
    unrelated consumers, which defeats in-place buffer assignment inside
    the while loop and re-introduces the full-memory copy per tick this
    interpreter exists to avoid.  Two lanes keep them real gather/scatter
    ops, which stay materialized and alias in place.

    ``n_devices > 1`` is the flat-fleet form (``run_chunk_fleet``): the
    state carries ``D * nc`` lanes keyed (device, core), ``st.mem`` is
    every device's image concatenated (``mem_words`` u64 words each,
    lanes offset into their own partition), ``st.ticks`` /
    ``exec_from`` / ``gate`` / ``budget_left`` are per-device ``(D,)``
    vectors, and every cross-lane interaction (conflict ordering, store
    invalidation, cache kills) is masked to same-device pairs — devices
    are shared-nothing by construction, so each advances bit-exactly as
    it would alone while sharing one compiled substep.
    """
    D = n_devices
    # the fleet form is keyed off mem_words, not D: run_chunk_fleet with
    # a single device still carries (1,)-vector clocks/budgets/gates and
    # a (D*W,)-flat memory, so it must take the vectorized paths below
    fleet = mem_words > 0
    total = D * nc
    mem = st.mem
    L = max(total, 2)
    if L == total:
        pc, priv, pend, stall, satp, res = (st.pc, st.priv, st.pending,
                                            st.stall_until, st.satp, st.res)
        regs = st.regs
    else:
        def _pad(v, fill=0):
            tail = jnp.full((L - total,) + v.shape[1:], fill, v.dtype)
            return jnp.concatenate([v, tail])
        pc = _pad(st.pc)
        priv = _pad(st.priv, 3)
        pend = _pad(st.pending, True)
        stall = _pad(st.stall_until)
        satp = _pad(st.satp)
        res = _pad(st.res, _RES_INVALID)
        regs = _pad(st.regs)
        fb = FetchBlocks(_pad(fb.vbase), _pad(fb.pbase), _pad(fb.nbytes),
                         _pad(fb.insts))
    lanes = jnp.arange(L)
    active = priv != 3
    if not fleet:
        dev = None
        base = None
        same_dev = None
        ticks_lane = st.ticks              # scalar, broadcasts per lane
        cont = exec_from > _u(0)
        runnable = active & ~pend & (ticks_lane >= stall)
        cand = (cont | gate) & runnable & (lanes.astype(U64) >= exec_from)
    else:
        # flat fleet: per-device scalars become (D,) vectors, gathered
        # per lane; the pad lane (only when D*nc == 1) maps onto the
        # last device but is permanently parked, so it never acts
        dev = jnp.minimum(lanes // nc, D - 1)
        base = dev.astype(U64) * _u(mem_words)
        same_dev = dev[:, None] == dev[None, :]
        ticks_lane = st.ticks[dev]
        cont = exec_from > _u(0)                         # (D,)
        lane_loc = (lanes - dev * nc).astype(U64)
        runnable = active & ~pend & (ticks_lane >= stall)
        cand = (cont | gate)[dev] & runnable & (lane_loc >= exec_from[dev])

    # ---- fetch: block cache hit / walk+fill on miss --------------------
    if block_cache:
        off = pc - fb.vbase
        hit = cand & (off < fb.nbytes) & ((off & _u(3)) == 0)
    else:
        off = jnp.zeros((L,), U64)
        hit = jnp.zeros((L,), bool)
    miss = cand & ~hit

    def do_walk(_):
        return walk_fetch(mem, satp, pc, base)

    def no_walk(_):
        return (jnp.zeros((L,), U64), jnp.zeros((L,), bool),
                jnp.full((L, 3), _u(_NO_WORD)),
                jnp.zeros((L, block_words), jnp.uint32),
                jnp.zeros((L,), U64))

    wpa, wfault, wwords, winsts, wnb = lax.cond(jnp.any(miss), do_walk,
                                                no_walk, None)
    ipa = jnp.where(hit, fb.pbase + off, wpa)
    ifault = miss & wfault
    slot = ((off >> _u(2)) & _u(block_words - 1)).astype(jnp.int32)
    inst_hit = jnp.take_along_axis(fb.insts, slot[:, None], axis=1)[:, 0]
    inst = jnp.where(hit, inst_hit.astype(U64), winsts[:, 0].astype(U64))

    if block_cache:
        fill = miss & ~wfault
        fb = FetchBlocks(
            vbase=jnp.where(fill, pc, fb.vbase),
            pbase=jnp.where(fill, wpa, fb.pbase),
            nbytes=jnp.where(fill, wnb, fb.nbytes),
            insts=jnp.where(fill[:, None], winsts, fb.insts))

    # ---- decode (identical field math to _exec_one, lane-wise) ---------
    op = inst & _u(0x7F)
    rd = (inst >> _u(7)) & _u(0x1F)
    f3 = (inst >> _u(12)) & _u(7)
    rs1 = (inst >> _u(15)) & _u(0x1F)
    rs2 = (inst >> _u(20)) & _u(0x1F)
    f7 = inst >> _u(25)
    imm_i = _sx(inst >> _u(20), 12)
    imm_s = _sx(((inst >> _u(25)) << _u(5)) | rd, 12)
    imm_b = _sx((((inst >> _u(8)) & _u(0xF)) << _u(1)) |
                (((inst >> _u(25)) & _u(0x3F)) << _u(5)) |
                (((inst >> _u(7)) & _u(1)) << _u(11)) |
                ((inst >> _u(31)) << _u(12)), 13)
    imm_u = _sx(inst & _u(0xFFFFF000), 32)
    imm_j = _sx((((inst >> _u(21)) & _u(0x3FF)) << _u(1)) |
                (((inst >> _u(20)) & _u(1)) << _u(11)) |
                (((inst >> _u(12)) & _u(0xFF)) << _u(12)) |
                ((inst >> _u(31)) << _u(20)), 21)

    a = jnp.take_along_axis(regs, rs1.astype(jnp.int32)[:, None],
                            axis=1)[:, 0]
    b = jnp.take_along_axis(regs, rs2.astype(jnp.int32)[:, None],
                            axis=1)[:, 0]

    is_load = op == _u(isa.OP_LOAD)
    is_fence = op == _u(isa.OP_MISC_MEM)
    is_opimm = op == _u(isa.OP_IMM)
    is_auipc = op == _u(isa.OP_AUIPC)
    is_opimm32 = op == _u(isa.OP_IMM_32)
    is_store = op == _u(isa.OP_STORE)
    is_amo = op == _u(isa.OP_AMO)
    is_op = op == _u(isa.OP_OP)
    is_lui = op == _u(isa.OP_LUI)
    is_op32 = op == _u(isa.OP_OP_32)
    is_branch = op == _u(isa.OP_BRANCH)
    is_jalr = op == _u(isa.OP_JALR)
    is_jal = op == _u(isa.OP_JAL)
    is_system = op == _u(isa.OP_SYSTEM)
    is_ecall = is_system & (inst == _u(isa.INST_ECALL))
    is_ebreak = is_system & (inst == _u(isa.INST_EBREAK))
    illegal = ~(is_load | is_fence | is_opimm | is_auipc | is_opimm32 |
                is_store | is_amo | is_op | is_lui | is_op32 | is_branch |
                is_jalr | is_jal | is_ecall | is_ebreak)

    # ---- ALU ----------------------------------------------------------
    reg_form = is_op | is_op32
    bop = jnp.where(reg_form, b, imm_i)
    is_m = reg_form & (f7 == _u(1))
    is_sub = reg_form & (f7 == _u(0x20)) & (f3 == _u(0))
    is_sra = jnp.where(reg_form, f7 == _u(0x20),
                       (inst >> _u(30)) & _u(1) != 0) & (f3 == _u(5))
    alu_w = _alu64(f3, is_sub, is_sra, is_m, a, bop)
    alu_w32 = _alu32(f3, is_sub, is_sra, is_m, a, bop)

    # ---- data memory access -------------------------------------------
    funct5 = f7 >> _u(2)
    is_lr = is_amo & (funct5 == _u(isa.AMO_LR))
    is_sc = is_amo & (funct5 == _u(isa.AMO_SC))
    dva = jnp.where(is_amo, a,
                    a + jnp.where(is_store, imm_s, imm_i))
    is_memop = is_load | is_store | is_amo
    want_w = is_store | (is_amo & ~is_lr)
    if dtlb_ways:
        # ---- data-TLB lookup: the load/store twin of the fetch-block
        # cache.  A hit replays the cached 4 KiB leaf translation
        # (post-mask ppn) and re-checks the cached permission byte for
        # THIS access (a load-filled entry must still refuse a store on
        # an R-only page — that falls through to a real walk, which
        # faults exactly like the uncached path).  Only true misses
        # walk, and only their PTE words enter the same-tick conflict
        # read set: a hit lane's input is the cached entry, which
        # store-overlap invalidation below keeps coherent.
        bare = (satp >> _u(60)) != _u(8)
        vpn = dva >> _u(12)
        way = (vpn & _u(dtlb_ways - 1)).astype(jnp.int32)[:, None]
        tag = jnp.take_along_axis(dtlb.vpn, way, axis=1)[:, 0]
        tppn = jnp.take_along_axis(dtlb.ppn, way, axis=1)[:, 0]
        tperm = jnp.take_along_axis(dtlb.perms, way, axis=1)[:, 0]
        dneed = _u(isa.PTE_U) | jnp.where(want_w, _u(isa.PTE_W),
                                          _u(isa.PTE_R))
        dhit = cand & is_memop & ~bare & (tag == vpn) & \
            ((tperm & dneed) == dneed)
        dwalk = cand & is_memop & ~bare & ~dhit

        def do_dwalk(_):
            return pw_ref.sv39_walk_leaf(mem, satp, dva, want_w,
                                         jnp.zeros((L,), bool), mask, base)

        def no_dwalk(_):
            z = jnp.zeros((L,), U64)
            return (z, jnp.zeros((L,), bool),
                    jnp.full((L, 3), _u(_NO_WORD)), z,
                    jnp.zeros((L,), bool), jnp.full((L,), _u(_NO_WORD)))

        wdpa, wdfault, dwords, wperms, wleaf0, wptw = lax.cond(
            jnp.any(dwalk), do_dwalk, no_dwalk, None)
        dpa = jnp.where(dhit, ((tppn << _u(12)) | (dva & _u(0xFFF))) & mask,
                        jnp.where(bare, dva & mask, wdpa))
        dfault = dwalk & wdfault
    else:
        dwalk = cand & is_memop
        if not fleet:
            dpa, dfault, dwords = pw_ops.sv39_walk(
                mem, satp, dva, want_w, jnp.zeros((L,), bool), mask)
        else:
            dpa, dfault, dwords = pw_ref.sv39_walk_ref(
                mem, satp, dva, want_w, jnp.zeros((L,), bool), mask, base)
    szb = jnp.where(is_amo,
                    jnp.where(f3 == _u(2), _u(4), _u(8)),
                    _u(1) << (f3 & _u(3)))
    misal = is_memop & ((dva & (szb - _u(1))) != 0)

    dword = mem[(dpa >> _u(3)) if base is None else base + (dpa >> _u(3))]
    dshift = (dpa & _u(7)) << _u(3)
    raw = dword >> dshift
    sizemask = jnp.select([szb == _u(1), szb == _u(2), szb == _u(4)],
                          [_u(0xFF), _u(0xFFFF), _u(0xFFFFFFFF)],
                          _u(_RES_INVALID))
    rawv = raw & sizemask
    uns = (f3 & _u(4)) != 0
    loaded = jnp.select(
        [szb == _u(1), szb == _u(2), szb == _u(4)],
        [jnp.where(uns, rawv, _sx(rawv, 8)),
         jnp.where(uns, rawv, _sx(rawv, 16)),
         jnp.where(uns, rawv, _sx(rawv, 32))],
        rawv)

    # ---- AMO ----------------------------------------------------------
    amo_w = f3 == _u(2)
    amo_old = rawv
    amo_b = b & sizemask
    s_old = jnp.where(amo_w, _sx(amo_old, 32), amo_old).astype(I64)
    s_b = jnp.where(amo_w, _sx(amo_b, 32), amo_b).astype(I64)
    amo_new = jnp.select(
        [funct5 == _u(isa.AMO_SWAP), funct5 == _u(isa.AMO_ADD),
         funct5 == _u(isa.AMO_XOR), funct5 == _u(isa.AMO_AND),
         funct5 == _u(isa.AMO_OR), funct5 == _u(isa.AMO_MIN),
         funct5 == _u(isa.AMO_MAX), funct5 == _u(isa.AMO_MINU)],
        [amo_b, amo_old + amo_b, amo_old ^ amo_b, amo_old & amo_b,
         amo_old | amo_b,
         jnp.where(s_old < s_b, amo_old, amo_b),
         jnp.where(s_old > s_b, amo_old, amo_b),
         jnp.where(amo_old < amo_b, amo_old, amo_b)],
        jnp.where(amo_old > amo_b, amo_old, amo_b))
    sc_ok = is_sc & (res == dpa)
    amo_rdval = jnp.where(
        is_sc, jnp.where(sc_ok, _u(0), _u(1)),
        jnp.where(amo_w, _sx(amo_old, 32), amo_old))

    # ---- traps --------------------------------------------------------
    ma_cause = jnp.where(is_load | is_lr, _u(4), _u(6))
    pf_cause = jnp.where(want_w, _u(15), _u(13))
    dtrap = is_memop & (misal | dfault)
    traps = ifault | illegal | is_ecall | is_ebreak | dtrap
    cause = jnp.where(
        ifault, _u(12),
        jnp.where(illegal, _u(2),
                  jnp.where(is_ecall, _u(8),
                            jnp.where(is_ebreak, _u(3),
                                      jnp.where(misal, ma_cause,
                                                pf_cause)))))
    tval = jnp.where(
        ifault, pc,
        jnp.where(illegal, inst,
                  jnp.where(is_ecall | is_ebreak, _u(0), dva)))

    commit = cand & ~traps & (is_store |
                              (is_amo & ~is_lr & (~is_sc | sc_ok)))
    stw = dpa >> _u(3)

    # ---- same-tick conflict detection ---------------------------------
    # Read set of lane j: the executed instruction word (cache hits read
    # it through fb content, which is kept equal to memory), the PTE
    # words its walks touched, and its data word.  Order matters: only a
    # store by an EARLIER core (i < j) can change what core j would have
    # observed under sequential core-order execution, so the applied set
    # is the prefix of the core order up to the first lane whose inputs
    # an earlier commit may have touched; the rest re-run next substep.
    no_w = _u(_NO_WORD)
    reads = jnp.concatenate([
        jnp.where(cand, ipa >> _u(3), no_w)[:, None],
        jnp.where(cand & is_memop, stw, no_w)[:, None],
        jnp.where(miss[:, None], wwords, no_w),
        jnp.where(dwalk[:, None], dwords, no_w),
    ], axis=1)                                             # (L, 8)
    res_word = jnp.where(cand & (res != _u(_RES_INVALID)),
                         res >> _u(3), no_w)
    earlier = lanes[:, None] < lanes[None, :]              # i executes first
    if D > 1:
        # devices are shared-nothing: only same-device pairs can ever
        # order or conflict (word indices are device-local, so a raw
        # cross-device compare could alias)
        earlier = earlier & same_dev
    wr = commit[:, None] & earlier                         # (i, j)
    read_hit = jnp.any(stw[:, None, None] == reads[None, :, :], axis=-1)
    st_hit = commit[None, :] & (stw[:, None] == stw[None, :])
    res_hit = stw[:, None] == res_word[None, :]
    conf = jnp.any(wr & (read_hit | st_hit | res_hit), axis=0)   # per j
    if not fleet:
        safe = cand & (jnp.cumsum(conf.astype(jnp.int32)) == 0)
    else:
        # conflict prefix is per device: a conflict in one device must
        # never defer another device's lanes
        csum = jnp.cumsum(conf[:total].reshape(D, nc).astype(jnp.int32),
                          axis=1).reshape(total)
        ok_pfx = csum == 0
        if L != total:
            ok_pfx = jnp.concatenate(
                [ok_pfx, jnp.zeros((L - total,), bool)])
        safe = cand & ok_pfx
    deferred = cand & ~safe

    tr = safe & traps
    ret = safe & ~traps
    commit = commit & safe

    # ---- memory commit -------------------------------------------------
    sval = jnp.where(is_store | is_sc, b, amo_new)
    wmask = sizemask << dshift
    new_word = (dword & ~wmask) | ((sval << dshift) & wmask)
    stw_g = stw if base is None else base + stw
    widx = jnp.where(commit, stw_g, _u(mem.shape[0]))      # OOB -> dropped
    new_mem = mem.at[widx].set(new_word, mode="drop")

    # ---- reservations ---------------------------------------------------
    # Own update first (LR acquires, SC always clears), then invalidation
    # by any other core's commit to the same line.  An earlier store onto
    # a line a later core LRs in the same tick is unreachable here — the
    # LR's data read defers that lane to the next substep — so the
    # unordered form below is exact (see also the SC guard via
    # ``res_word`` above).
    own = jnp.where(ret & is_lr, dpa,
                    jnp.where(ret & is_sc, _u(_RES_INVALID), res))
    other = lanes[:, None] != lanes[None, :]
    if D > 1:
        other = other & same_dev
    inv = jnp.any(commit[:, None] & other &
                  (stw[:, None] == (own >> _u(3))[None, :]), axis=0)
    new_res = jnp.where(inv, _u(_RES_INVALID), own)

    # ---- next pc / register writeback ----------------------------------
    sa = a.astype(I64)
    sb64 = b.astype(I64)
    taken = is_branch & jnp.select(
        [f3 == _u(0), f3 == _u(1), f3 == _u(4), f3 == _u(5), f3 == _u(6)],
        [a == b, a != b, sa < sb64, sa >= sb64, a < b],
        a >= b)
    next_pc = pc + _u(4)
    next_pc = jnp.where(taken, pc + imm_b, next_pc)
    next_pc = jnp.where(is_jal, pc + imm_j, next_pc)
    next_pc = jnp.where(is_jalr, (a + imm_i) & ~_u(1), next_pc)

    wval = jnp.where(is_opimm | is_op, alu_w, _u(0))
    wval = jnp.where(is_opimm32 | is_op32, alu_w32, wval)
    wval = jnp.where(is_load, loaded, wval)
    wval = jnp.where(is_lui, imm_u, wval)
    wval = jnp.where(is_auipc, pc + imm_u, wval)
    wval = jnp.where(is_jal | is_jalr, pc + _u(4), wval)
    wval = jnp.where(is_amo, amo_rdval, wval)
    wen = ret & (is_opimm | is_op | is_opimm32 | is_op32 | is_load |
                 is_lui | is_auipc | is_jal | is_jalr | is_amo) & (rd != 0)
    cols = jnp.arange(32, dtype=U64)[None, :] == rd[:, None]
    new_regs = jnp.where(wen[:, None] & cols, wval[:, None], regs)

    if block_cache:
        # content coherence: a committed store into any cached range
        # (including a block filled this very tick) kills that block
        stb = stw << _u(3)
        over = (commit[:, None] & (stb[:, None] + _u(8) > fb.pbase[None, :])
                & (stb[:, None] < (fb.pbase + fb.nbytes)[None, :]))
        if D > 1:
            over = over & same_dev
        fb = fb._replace(nbytes=jnp.where(jnp.any(over, axis=0), _u(0),
                                          fb.nbytes))

    if dtlb_ways:
        # fill: applied (safe) walk lanes that reached a 4 KiB leaf cache
        # it in their own row; deferred lanes re-walk next substep and
        # fill then, so a fill never captures a pre-conflict translation
        dfill = dwalk & safe & ~dfault & wleaf0
        wcols = jnp.arange(dtlb_ways)[None, :] == way      # (L, ways)
        put = dfill[:, None] & wcols
        dtlb = DTlb(
            vpn=jnp.where(put, vpn[:, None], dtlb.vpn),
            ppn=jnp.where(put, (wdpa >> _u(12))[:, None], dtlb.ppn),
            perms=jnp.where(put, wperms[:, None], dtlb.perms),
            ptw=jnp.where(put, wptw[:, None], dtlb.ptw))
        # store-overlap: a committed store onto any entry's backing leaf
        # PTE word (including one filled this very tick) kills the entry
        phit = stw[:, None, None] == dtlb.ptw[None, :, :]
        if D > 1:
            phit = phit & same_dev[:, :, None]
        pinv = jnp.any(commit[:, None, None] & phit, axis=0)
        dtlb = dtlb._replace(vpn=jnp.where(pinv, _u(_NO_WORD), dtlb.vpn))

    # ---- tick bookkeeping ----------------------------------------------
    # The tick completes when no candidate lane was deferred; a fresh
    # tick whose every live lane is stalled fast-forwards the clock to
    # the next wake-up instead (the reference loop's skip arm).
    if not fleet:
        started = jnp.any(cand) | cont
        tick_done = started & ~jnp.any(deferred)
        skip = gate & ~cont & ~jnp.any(runnable) & jnp.any(active)
        gaps = jnp.where(active, stall - st.ticks, _u(_RES_INVALID))
        gap = jnp.minimum(jnp.min(gaps), budget_left)
        dticks = jnp.where(tick_done, _u(1), jnp.where(skip, gap, _u(0)))
        new_from = jnp.where(jnp.any(deferred),
                             jnp.argmax(deferred).astype(U64), _u(0))
        dticks_lane = dticks
    else:
        # every reduction above becomes a segmented per-device one; each
        # device keeps its own clock, skip arm and deferred-lane resume
        def dany(v):
            return jnp.any(v[:total].reshape(D, nc), axis=1)
        started = dany(cand) | cont
        tick_done = started & ~dany(deferred)
        skip = gate & ~cont & ~dany(runnable) & dany(active)
        gaps = jnp.where(active, stall - ticks_lane, _u(_RES_INVALID))
        gap = jnp.minimum(jnp.min(gaps[:total].reshape(D, nc), axis=1),
                          budget_left)
        dticks = jnp.where(tick_done, _u(1), jnp.where(skip, gap, _u(0)))
        new_from = jnp.where(
            dany(deferred),
            jnp.argmax(deferred[:total].reshape(D, nc),
                       axis=1).astype(U64), _u(0))
        dticks_lane = dticks[dev]
    retired = ret.astype(U64)

    def cut(v):
        return v if L == total else v[:total]

    # ---- telemetry counters (repro.telemetry; pure accounting) ---------
    # Stall accrual mirrors the reference loop exactly: on a completed
    # exec tick every active-but-stalled core accrues 1; on a skip tick
    # every active core accrues the fast-forward gap (the gap is the
    # minimum remaining stall, so it never overshoots any lane); a
    # deferred substep (dticks = 0) accrues nothing.
    stalled = cut(active & (stall > ticks_lane))
    tl = ticks_lane if not fleet else cut(ticks_lane)   # scalar vs (total,)
    dtl = dticks_lane if not fleet else cut(dticks_lane)
    dstall = jnp.where(stalled, jnp.minimum(cut(stall) - tl, dtl), _u(0))
    if trace_on:
        assert not fleet, "commit-trace capture is single-device only"
        # Commit-trace ring: one (tick, pc, inst, priv) record per
        # retirement at trace_n % slots; non-retiring lanes scatter to
        # an out-of-range row and drop.  The host derives overflow drops
        # from the monotone trace_n, so ring wrap is loss-*counting*,
        # never loss-hiding.  `trigger` is a STATIC capture-window spec
        # (repro.telemetry.triggers): the gate below compiles into the
        # trace path, and trigger=None compiles to the plain ungated
        # ring — the predicate is free when unused.
        slots = st.tracebuf.shape[1]
        ret_nc = cut(ret)
        new_trace_armed = st.trace_armed
        if trigger is None:
            cap = ret_nc
        elif trigger[0] == "tick":
            cap = ret_nc & (st.ticks >= _u(trigger[1])) & \
                (st.ticks < _u(trigger[2]))
        elif trigger[0] == "instret":
            # pre-retirement count (st.instret increments below)
            cap = ret_nc & (st.instret >= _u(trigger[1]))
        else:                       # "pc" / "inst": sticky arm/disarm
            val = cut(pc) if trigger[0] == "pc" else cut(inst)
            armed_now = st.trace_armed | (ret_nc & (val == _u(trigger[1])))
            cap = ret_nc & armed_now
            if trigger[2] is None:
                new_trace_armed = armed_now
            else:
                new_trace_armed = armed_now & \
                    ~(ret_nc & (val == _u(trigger[2])))
        rows = jnp.where(cap, jnp.arange(nc, dtype=jnp.int32),
                         jnp.int32(nc))
        ring = (st.trace_n % _u(slots)).astype(jnp.int32)
        rec = jnp.stack([jnp.broadcast_to(st.ticks, (nc,)), cut(pc),
                         cut(inst), cut(priv).astype(U64)], axis=1)
        new_tracebuf = st.tracebuf.at[rows, ring].set(rec, mode="drop")
        new_trace_n = st.trace_n + cap.astype(U64)
    else:
        new_trace_armed = st.trace_armed
        new_tracebuf, new_trace_n = st.tracebuf, st.trace_n

    st = st._replace(
        regs=cut(new_regs),
        pc=cut(jnp.where(ret, next_pc, pc)),
        pending=st.pending | cut(tr),
        mcause=jnp.where(cut(tr), cut(cause), st.mcause),
        mepc=jnp.where(cut(tr), cut(pc), st.mepc),
        mtval=jnp.where(cut(tr), cut(tval), st.mtval),
        res=cut(new_res),
        mem=new_mem,
        ticks=st.ticks + dticks,
        uticks=st.uticks + cut(retired),
        instret=st.instret + cut(retired),
        stall_ticks=st.stall_ticks + dstall,
        fetch_hits=st.fetch_hits + cut((hit & safe).astype(U64)),
        fetch_walks=st.fetch_walks + cut((miss & safe).astype(U64)),
        tlb_walks=(st.tlb_walks + cut((dwalk & safe).astype(U64))
                   if dtlb_ways else st.tlb_walks),
        tracebuf=new_tracebuf,
        trace_n=new_trace_n,
        trace_armed=new_trace_armed,
    )
    if L != total:
        fb = FetchBlocks(fb.vbase[:total], fb.pbase[:total],
                         fb.nbytes[:total], fb.insts[:total])
    return st, fb, dtlb, new_from, dticks


def _run_chunk_fast(st: CpuState, n_cores: int, mem_bytes: int, max_cycles,
                    issue_width: int = 8, block_words: int = 16,
                    block_cache: bool = True, fetch_kernel: str = "ref",
                    trace_on: bool = False,
                    trigger: tuple | None = None,
                    dtlb_ways: int = 8) -> CpuState:
    """Fast-path twin of :func:`run_chunk`: identical architectural
    semantics, up to ``issue_width`` vectorized ticks per loop iteration.

    ``block_words`` (a power of two) sizes the per-core fetch block;
    ``block_cache=False`` keeps the batched vector issue but re-walks the
    fetch for every instruction.  ``fetch_kernel`` picks the translate/
    fetch-gather backend for block fills: ``"ref"`` (pure-jnp oracle,
    the default on every backend) or ``"pallas"`` (the Pallas kernel in
    interpret mode, CPU backend only: it does not lower for the TPU).
    ``trigger`` (static, a hashable trigger
    spec from :mod:`repro.telemetry.triggers`) windows commit-trace
    capture; it only affects which records enter the ring — never the
    architectural step — and ``None`` compiles the gate out.
    ``dtlb_ways`` (a power of two; 0 disables) sizes the chunk-local
    per-lane data-translation cache (:class:`DTlb`) so straight-line
    loads/stores skip the Sv39 walk the way cached fetches already do.

    This undecorated body is shared by :func:`run_chunk_fast` (jitted,
    one device) and :func:`run_chunk_fleet` (jitted vmap over stacked
    per-device states) — keep it free of host-side effects.
    """
    assert block_words & (block_words - 1) == 0, "block_words must be pow2"
    assert dtlb_ways & (dtlb_ways - 1) == 0, "dtlb_ways must be pow2 or 0"
    assert not trace_on or st.tracebuf.shape[1] > 0, \
        "trace_on needs an armed ring (make_state trace_slots / trace_arm)"
    nc = n_cores
    mask = _u(mem_bytes - 1)
    limit = jnp.asarray(max_cycles, U64)

    if fetch_kernel == "pallas":
        def walk_fetch(mem, satp, va, base=None):
            assert base is None, "pallas fetch is single-device only"
            return pw_ops.walk_fetch_block(mem, satp, va, mem_bytes - 1,
                                           block_words)
    else:
        def walk_fetch(mem, satp, va, base=None):
            return pw_ref.walk_fetch_block_ref(mem, satp, va, mask,
                                               block_words, base)

    # No lax.cond anywhere near the carry: on XLA:CPU a conditional whose
    # operands include the memory image costs a full copy of it per
    # execution, which is the exact pathology this path removes.  Stall
    # fast-forward and conflict serialization are folded into the substep
    # as masked math instead; `exec_from` in the carry marks a tick whose
    # core-order suffix is still owed (it must finish even once a trap is
    # pending, exactly like the reference tick).
    def cond(carry):
        st, cycles, exec_from, fb, dtlb = carry
        return (((cycles < limit) & ~jnp.any(st.pending) &
                 jnp.any(st.priv != 3)) | (exec_from > _u(0)))

    def body(carry):
        def issue(_, carry):
            st, cycles, exec_from, fb, dtlb = carry
            gate = ~jnp.any(st.pending) & (cycles < limit)
            st, fb, dtlb, exec_from, d = _exec_substep(
                st, fb, dtlb, exec_from, gate, limit - cycles, nc, mask,
                block_words, block_cache, walk_fetch, dtlb_ways,
                trace_on, trigger)
            return st, cycles + d, exec_from, fb, dtlb

        # fori_loop: the substep traces once, runs issue_width times — a
        # python unroll multiplies compile time by issue_width for no
        # measurable run-time win (loop overhead is tens of ns against a
        # multi-microsecond body)
        return lax.fori_loop(0, issue_width, issue, carry)

    carry = (st, _u(0), _u(0), _empty_blocks(nc, block_words),
             _empty_dtlb(max(nc, 2), max(dtlb_ways, 1)))
    st, _, _, _, _ = lax.while_loop(cond, body, carry)
    return st


run_chunk_fast = partial(jax.jit,
                         static_argnums=(1, 2, 4, 5, 6, 7, 8, 9, 10),
                         donate_argnums=(0,))(_run_chunk_fast)


@jax.jit
def state_record(st: CpuState) -> jax.Array:
    """The per-core state the host reads between chunks, as one u64
    vector: ``ticks``, then each :data:`SNAPSHOT_CORE_FIELDS` field over
    the cores (``[1 + k * nc + c]``), then every core's 32 registers
    (``[1 + 11 * nc + c * 32 + idx]``).  Each value is widened to u64
    as :func:`fetch_read_batch` widens it."""
    return jnp.concatenate(
        [st.ticks[None].astype(U64)] +
        [getattr(st, f).astype(U64) for f in SNAPSHOT_CORE_FIELDS] +
        [st.regs.reshape(-1)])


@partial(jax.jit, static_argnums=(1, 2, 3, 5, 6, 7, 8, 9, 10, 11),
         donate_argnums=(0,))
def run_chunk_fast_record(st: CpuState, kernel, n_cores: int,
                          mem_bytes: int, max_cycles, issue_width: int,
                          block_words: int, block_cache: bool,
                          fetch_kernel: str, trace_on: bool,
                          trigger: tuple | None, dtlb_ways: int):
    """One chunk and its :func:`state_record`, in one program: the host
    brings the record home with the chunk's end and answers its reads of
    per-core state from it until the next chunk.

    ``kernel`` is :func:`run_chunk_fast` or a function with its
    signature, given by the caller at each launch (static), so that
    whatever this module's ``run_chunk_fast`` names when a chunk is
    launched, a wrapped kernel included, is what runs."""
    st = kernel(st, n_cores, mem_bytes, max_cycles, issue_width,
                block_words, block_cache, fetch_kernel, trace_on, trigger,
                dtlb_ways)
    return st, state_record(st)


@partial(jax.jit, static_argnums=(1, 2, 4, 5, 6, 7, 8, 9),
         donate_argnums=(0,))
def run_chunk_fleet(sts: CpuState, n_cores: int, mem_bytes: int, budgets,
                    issue_width: int = 8, block_words: int = 16,
                    block_cache: bool = True, fetch_kernel: str = "ref",
                    dtlb_ways: int = 8, n_devices: int = 1) -> CpuState:
    """One XLA dispatch for a whole fleet's global chunk (ROADMAP item 1,
    FireSim-metasim style): ``sts`` is a :class:`CpuState` whose every
    array carries a leading device axis ``(D, ...)``, advanced as ONE
    flat machine of ``D * n_cores`` lanes with a per-device cycle budget
    ``budgets`` ``(D,)``.

    Flat, not vmapped: ``jax.vmap`` over :func:`_run_chunk_fast` is
    catastrophic on XLA:CPU — a batched ``while_loop`` select-merges the
    entire carry (memory images included) every iteration, and batched
    gather/scatter lowers ~9x slower than the flat forms.  Instead the
    device axis folds into the lane axis: memory images concatenate into
    one flat buffer (each lane offset into its own device's partition),
    per-device scalars (clock, budget, deferred-lane resume point)
    become ``(D,)`` vectors with segmented reductions, and every
    cross-lane interaction inside :func:`_exec_substep` is masked to
    same-device pairs — devices stay shared-nothing, so each advances
    bit-exactly as it would alone while sharing one compiled program.

    A device whose budget is 0 is genuinely untouched: its issue gate is
    false every substep, so no lane of it is ever a candidate and its
    clock never moves — which is what lets a single-device ``run`` on a
    fleet view dispatch the whole stacked program with a one-hot budget
    vector and still hold every golden tick.  ``trace_on`` is
    deliberately not plumbed: commit-trace capture stays a
    single-device affair, and only the ``"ref"`` fetch kernel is
    supported (the Pallas path has no per-lane base-offset story).
    """
    assert n_devices == sts.pc.shape[0]
    assert block_words & (block_words - 1) == 0, "block_words must be pow2"
    assert dtlb_ways & (dtlb_ways - 1) == 0, "dtlb_ways must be pow2 or 0"
    assert fetch_kernel == "ref", "fleet chunks use the ref fetch kernel"
    D, nc = n_devices, n_cores
    total = D * nc
    mask = _u(mem_bytes - 1)
    mem_words = mem_bytes // 8
    budgets = jnp.asarray(budgets, U64)

    def flat(x):
        # fold the device axis into the lane axis ((D, nc, ...) ->
        # (D*nc, ...), mem (D, W) -> (D*W,)); per-device scalars that
        # became (D,) vectors (ticks) pass through
        return x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:]) \
            if x.ndim >= 2 else x

    fst = CpuState(*[flat(x) for x in sts])

    def walk_fetch(mem, satp, va, base=None):
        return pw_ref.walk_fetch_block_ref(mem, satp, va, mask,
                                           block_words, base)

    def dany(v):
        return jnp.any(v.reshape(D, nc), axis=1)

    def cond(carry):
        st, cycles, exec_from, fb, dtlb = carry
        return jnp.any(((cycles < budgets) & ~dany(st.pending) &
                        dany(st.priv != 3)) | (exec_from > _u(0)))

    def body(carry):
        def issue(_, carry):
            st, cycles, exec_from, fb, dtlb = carry
            gate = ~dany(st.pending) & (cycles < budgets)
            st, fb, dtlb, exec_from, d = _exec_substep(
                st, fb, dtlb, exec_from, gate, budgets - cycles, nc,
                mask, block_words, block_cache, walk_fetch, dtlb_ways,
                False, None, n_devices=D, mem_words=mem_words)
            return st, cycles + d, exec_from, fb, dtlb

        return lax.fori_loop(0, issue_width, issue, carry)

    carry = (fst, jnp.zeros((D,), U64), jnp.zeros((D,), U64),
             _empty_blocks(total, block_words),
             _empty_dtlb(max(total, 2), max(dtlb_ways, 1)))
    fst, _, _, _, _ = lax.while_loop(cond, body, carry)
    return CpuState(*[y.reshape(jnp.shape(x))
                      for y, x in zip(fst, sts)])


# ---------------------------------------------------------------------------
# Host-side word/page access (the device half of the HTP data requests)
# ---------------------------------------------------------------------------
def mem_write_words(mem, word_idx, vals):
    return mem.at[jnp.asarray(word_idx)].set(
        jnp.asarray(vals, dtype=U64))


def page_read_words(mem, word_off):
    return lax.dynamic_slice(mem, (jnp.asarray(word_off),), (512,))


def page_write_words(mem, word_off, words):
    return lax.dynamic_update_slice(
        mem, jnp.asarray(words, dtype=U64), (jnp.asarray(word_off),))


def page_set_words(mem, word_off, val):
    return lax.dynamic_update_slice(
        mem, jnp.full((512,), val, U64), (jnp.asarray(word_off),))


def page_copy_words(mem, src_off, dst_off):
    page = lax.dynamic_slice(mem, (jnp.asarray(src_off),), (512,))
    return lax.dynamic_update_slice(mem, page, (jnp.asarray(dst_off),))


@partial(jax.jit, static_argnums=(1,), donate_argnums=(0,))
def apply_write_batch(st: CpuState, csr_names: tuple,
                      reg_cpu, reg_idx, reg_val,
                      word_idx, word_val,
                      csr_cpus, csr_vals) -> CpuState:
    """Commit a staged transaction's writes in one donated update — the
    device half of the session's write batching (ROADMAP item 1).

    Index arrays arrive pow2-padded (so a handful of distinct batch
    shapes cover every transaction and the jit cache stays small); pad
    entries carry out-of-bounds indices — ``reg_cpu``/``csr cpu`` = nc,
    ``word_idx`` = mem_words — and ``mode="drop"`` discards them.  The
    stage guarantees unique live indices per array (it is dict-keyed),
    so the scatters have no duplicate-index ordering hazard, and values
    are pre-masked to 64 bits host-side.

    ``csr_names`` is a static sorted tuple of the CSR names present;
    ``csr_cpus``/``csr_vals`` are matching tuples of (cpu-index, value)
    arrays, one pair per name, since each CSR targets a different
    :class:`CpuState` field with its own dtype story.
    """
    regs = st.regs.at[reg_cpu, reg_idx].set(
        jnp.asarray(reg_val, U64), mode="drop")
    mem = st.mem.at[word_idx].set(jnp.asarray(word_val, U64), mode="drop")
    st = st._replace(regs=regs, mem=mem)
    for name, cc, vv in zip(csr_names, csr_cpus, csr_vals):
        vv = jnp.asarray(vv, U64)
        if name == "pending":
            field = st.pending.at[cc].set(vv != 0, mode="drop")
        elif name == "priv":
            field = st.priv.at[cc].set(vv.astype(U32), mode="drop")
        else:
            field = getattr(st, name).at[cc].set(vv, mode="drop")
        st = st._replace(**{name: field})
    return st


# ---------------------------------------------------------------------------
# Jitted host micro-ops: the few per-exception control writes that stay
# eager by design (Redirect / Next's clear-pending / park / the ticks
# clock) are each ONE donated dispatch instead of a handful of
# un-jitted scatter primitives — the same dispatch-count discipline as
# the batched read/write paths, for ops too small to batch.
# ---------------------------------------------------------------------------
@partial(jax.jit, donate_argnums=(0,))
def redirect_op(st: CpuState, c, pc, resume) -> CpuState:
    return st._replace(
        pc=st.pc.at[c].set(pc),
        priv=st.priv.at[c].set(U32(0)),
        pending=st.pending.at[c].set(False),
        stall_until=st.stall_until.at[c].set(resume))


@partial(jax.jit, donate_argnums=(0,))
def park_op(st: CpuState, c) -> CpuState:
    return st._replace(priv=st.priv.at[c].set(U32(3)),
                       pending=st.pending.at[c].set(False))


@partial(jax.jit, donate_argnums=(0,))
def clear_pending_op(st: CpuState, c) -> CpuState:
    return st._replace(pending=st.pending.at[c].set(False))


@partial(jax.jit, static_argnums=(1,), donate_argnums=(0,))
def csr_write_op(st: CpuState, name: str, c, v) -> CpuState:
    if name == "ticks":
        return st._replace(ticks=jnp.asarray(v, U64))
    if name == "pending":
        val = jnp.asarray(v, U64) != 0
    elif name == "priv":
        val = jnp.asarray(v, U32)
    else:
        val = jnp.asarray(v, U64)
    return st._replace(**{name: getattr(st, name).at[c].set(val)})


@partial(jax.jit, donate_argnums=(0,))
def reg_write_op(st: CpuState, c, idx, v) -> CpuState:
    return st._replace(regs=st.regs.at[c, idx].set(v))


@partial(jax.jit, static_argnums=(1,))
def fetch_read_batch(st: CpuState, csr_names: tuple,
                     reg_cpu, reg_idx, word_idx, csr_cpus):
    """One compiled gather for the host's batched reads — the read-side
    twin of :func:`apply_write_batch` and the device half of
    :meth:`~repro.core.interface.JaxTarget.fetch_batch`.

    Index arrays arrive pow2-padded (pad entries index slot 0 — always
    valid; the host discards the padded tail), so a handful of distinct
    batch shapes cover every transaction instead of one eager-gather
    compilation per request mix.  ``csr_names`` is a static sorted tuple
    of the CSR/core-state fields present; ``csr_cpus`` the matching
    tuple of cpu-index arrays.  Every CSR value is widened to u64
    (``pending`` -> 0/1, ``priv`` zero-extended, ``ticks`` broadcast
    from the global scalar), matching the per-element accessors."""
    regs = st.regs[reg_cpu, reg_idx]
    words = st.mem[word_idx]
    csr_out = []
    for name, cc in zip(csr_names, csr_cpus):
        if name == "ticks":
            v = jnp.broadcast_to(st.ticks, cc.shape).astype(U64)
        else:
            v = getattr(st, name)[cc].astype(U64)
        csr_out.append(v)
    return regs, words, tuple(csr_out)
