"""Compile FASE's device programs for a described TPU v5e, with no chip.

The TPU compiler is installed beside the CPU backend, so every program
``chip_smoke.py`` dispatches on the chip can be lowered and compiled
here at ``FASE_ROCKET`` width (4 cores, 64 MiB image; the fleet at 4
boards, and the four-chip farm's sharded chunk at 2 GiB a board): a
program the chip's compiler refuses, or one that does not fit one chip's
16 GB, fails here instead of on the chip.  Nothing runs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.fase_rocket import target_kwargs
from repro.configs.registry import (FASE_FLEET_SHARDED, FASE_FLEET_VMAP,
                                    FASE_ROCKET)
from repro.core.interface import pack_read_batch, pack_write_batch
from repro.core.target import cpu

NC, MEM = FASE_ROCKET["n_cores"], FASE_ROCKET["mem_bytes"]
KW = target_kwargs(FASE_ROCKET)
BOARDS = FASE_FLEET_VMAP["n_devices"]
#: HBM of one v5e chip (Google Cloud documentation, "TPU v5e")
HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2; the persistent compilation cache is off
    meanwhile (an entry compiled for a described chip cannot be read
    back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:     # noqa: BLE001 — any failure means skip
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield topo
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _state(sharding, boards=None):
    shapes = jax.eval_shape(lambda: cpu.make_state(NC, MEM))
    lead = () if boards is None else (boards,)
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(lead + x.shape, x.dtype,
                                       sharding=sharding), shapes)


def _fits_one_chip(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes
            + m.generated_code_size_in_bytes)
    assert 0 < used <= HBM_BYTES, used


def test_run_chunk_fast_compiles_for_v5e(one_chip):
    budget = jax.ShapeDtypeStruct((), jnp.uint64, sharding=one_chip)
    compiled = cpu.run_chunk_fast.lower(
        _state(one_chip), NC, MEM, budget, KW["issue_width"],
        KW["block_words"], KW["block_cache"], KW["fetch_kernel"], False,
        None, KW["dtlb_ways"]).compile()
    _fits_one_chip(compiled)


def test_run_chunk_fast_record_compiles_for_v5e(one_chip):
    """The program ``JaxTarget.run`` launches: a chunk and its record."""
    budget = jax.ShapeDtypeStruct((), jnp.uint64, sharding=one_chip)
    compiled = cpu.run_chunk_fast_record.lower(
        _state(one_chip), cpu.run_chunk_fast, NC, MEM, budget,
        KW["issue_width"], KW["block_words"], KW["block_cache"],
        KW["fetch_kernel"], False, None, KW["dtlb_ways"]).compile()
    _fits_one_chip(compiled)


def test_run_chunk_fleet_compiles_for_v5e(one_chip):
    budgets = jax.ShapeDtypeStruct((BOARDS,), jnp.uint64,
                                   sharding=one_chip)
    compiled = cpu.run_chunk_fleet.lower(
        _state(one_chip, BOARDS), NC, MEM, budgets, KW["issue_width"],
        KW["block_words"], KW["block_cache"], KW["fetch_kernel"],
        KW["dtlb_ways"], BOARDS).compile()
    _fits_one_chip(compiled)


def test_sharded_fleet_chunk_compiles_for_v5e_2x2(topo):
    """The farm's global chunk: four boards at the FASE board's 2 GiB
    image, one a chip of four, in one program that holds each chip to
    its own board and needs no collective."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.core.fleet import vmap
    mem, chips = 1 << 31, FASE_FLEET_SHARDED["fleet_chips"]
    mesh = Mesh(np.array(topo.devices[:chips]), (vmap.BOARD,))
    sharded = NamedSharding(mesh, PartitionSpec(vmap.BOARD))
    shapes = jax.eval_shape(lambda: vmap._blank_stack(chips, NC, mem))
    sts = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharded),
        shapes)._replace(tracebuf=None)
    budgets = jax.ShapeDtypeStruct((chips,), jnp.uint64, sharding=sharded)
    compiled = vmap._run_chunk_fleet_shards.lower(
        sts, budgets, cpu.run_chunk_fleet, mesh, NC, mem,
        KW["issue_width"], KW["block_words"], KW["block_cache"],
        KW["fetch_kernel"], KW["dtlb_ways"]).compile()
    _fits_one_chip(compiled)
    assert mem <= compiled.memory_analysis().argument_size_in_bytes \
        < 2 * mem
    text = compiled.as_text()
    assert not any(op in text for op in ("all-reduce", "all-gather",
                                         "collective-permute",
                                         "all-to-all", "reduce-scatter"))


def _spec_tree(sharding, tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype,
                                       sharding=sharding), tree)


def _write_batch(one_chip):
    # a context switch-in: 31 GPRs, a page-table word and a satp write
    names, *arrays = pack_write_batch(
        NC, MEM >> 3, regs=[(1, i, i) for i in range(1, 32)],
        csrs=[(1, "satp", 8 << 60)], words=[(7, 1)])
    return cpu.apply_write_batch.lower(_state(one_chip), names,
                                       *_spec_tree(one_chip, arrays))


def _read_batch(one_chip):
    # a context save plus the exception CSRs and one memory word
    names, reg_cpu, reg_idx, word_idx, csr_cpus, _ = pack_read_batch(
        regs=[(2, i) for i in range(1, 32)],
        csrs=[(2, "mcause"), (2, "mepc"), (2, "ticks")], words=[64])
    return cpu.fetch_read_batch.lower(
        _state(one_chip), names,
        *_spec_tree(one_chip, (reg_cpu, reg_idx, word_idx, csr_cpus)))


def _redirect(one_chip):
    return cpu.redirect_op.lower(
        _state(one_chip),
        *_spec_tree(one_chip, (np.int32(0), np.uint64(0x1000),
                               np.uint64(0))))


def _state_record(one_chip):
    return cpu.state_record.lower(_state(one_chip))


@pytest.mark.parametrize("lower", [_write_batch, _read_batch, _redirect,
                                   _state_record],
                         ids=["apply_write_batch", "fetch_read_batch",
                              "redirect_op", "state_record"])
def test_host_micro_op_compiles_for_v5e(one_chip, lower):
    _fits_one_chip(lower(one_chip).compile())
