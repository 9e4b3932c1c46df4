"""Pallas kernel for the Sv39 page-walk + fetch-block gather chain.

One grid step per core lane: the three dependent PTE loads lower to
single-word HBM->VMEM DMAs (the pointer chase the XLA gather fusion
cannot pipeline), then one contiguous DMA pulls the whole fetch block
behind the translated pc and the 32-bit instruction slots are carved out
in VMEM.  ``satp``/``va`` ride the scalar-prefetch operand, the same
mechanism the page-ops kernels use for their block-table indirection.

This kernel does not lower for the TPU today: the memory image is u64
words and Mosaic has no 64-bit integer support (a v5e compile is refused
during lowering).  It runs only in interpret mode on the CPU backend
(``tests/test_kernels.py``), and :mod:`repro.kernels.page_walk.ops`
refuses ``fetch_kernel="pallas"`` on any other backend when the target
is built.  The pure-jnp oracle (:mod:`repro.kernels.page_walk.ref`) is
the fill path on every backend, the TPU included.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.target import isa

from .ref import NO_WORD

U64 = jnp.uint64
U32 = jnp.uint32
I32 = jnp.int32


def _u(x):
    return jnp.uint64(x)


def _walk_fetch_kernel(sp_ref, mem_ref, pa_ref, fault_ref, words_ref,
                       insts_ref, nb_ref, pte_buf, blk_buf, sem,
                       *, mask, block_words, n_words):
    i = pl.program_id(0)
    satp = sp_ref[i, 0]
    va = sp_ref[i, 1]

    bare = (satp >> _u(60)) != _u(8)
    need = _u(isa.PTE_U | isa.PTE_X)
    a = (satp & _u((1 << 44) - 1)) << _u(12)
    done = jnp.bool_(False)
    fault = jnp.bool_(False)
    pa = _u(0)
    for slot, level in enumerate((2, 1, 0)):
        idx = (va >> _u(12 + 9 * level)) & _u(0x1FF)
        widx = ((a + idx * _u(8)) & _u(mask)) >> _u(3)
        cp = pltpu.make_async_copy(
            mem_ref.at[pl.ds(widx.astype(I32), 1)], pte_buf, sem)
        cp.start()
        cp.wait()
        pte = pte_buf[0]
        valid = (pte & _u(isa.PTE_V)) != 0
        leaf = valid & ((pte & _u(isa.PTE_R | isa.PTE_X)) != 0)
        perm_ok = (pte & need) == need
        off_mask = _u((1 << (12 + 9 * level)) - 1)
        leaf_pa = (((pte >> _u(10)) << _u(12)) | (va & off_mask)) & _u(mask)
        take = ~done
        words_ref[0, slot] = jnp.where(take & ~bare, widx, _u(NO_WORD))
        fault = fault | (take & (~valid | (leaf & ~perm_ok)))
        pa = jnp.where(take & leaf & perm_ok, leaf_pa, pa)
        done = done | (take & (~valid | leaf))
        a = jnp.where(take & valid & ~leaf, (pte >> _u(10)) << _u(12), a)
    fault = (fault | ~done) & ~bare
    pa = jnp.where(bare, va, pa) & _u(mask)

    # one contiguous DMA covers the whole block: the walk proved the page
    # physically contiguous, so unlike the per-slot gather in the oracle
    # no indirection is left to do
    m = block_words // 2 + 1
    wb = jnp.minimum((pa >> _u(3)).astype(I32), n_words - m)
    cp = pltpu.make_async_copy(mem_ref.at[pl.ds(wb, m)], blk_buf, sem)
    cp.start()
    cp.wait()
    w = blk_buf[:]
    lo = (w & _u(0xFFFFFFFF)).astype(U32)
    hi = (w >> _u(32)).astype(U32)
    inter = jnp.stack([lo, hi], axis=-1).reshape(2 * m)
    first = (pa >> _u(2)).astype(I32) - 2 * wb
    insts_ref[0, :] = lax.dynamic_slice(inter, (first,), (block_words,))

    remain = _u(0x1000) - (va & _u(0xFFF))
    nb_ref[0] = jnp.where(fault, _u(0),
                          jnp.minimum(remain, _u(4 * block_words)))
    pa_ref[0] = pa
    fault_ref[0] = fault.astype(I32)


@functools.partial(jax.jit,
                   static_argnames=("mask", "block_words", "interpret"))
def walk_fetch_block(mem, satp, va, mask, block_words, interpret=False):
    """Pallas twin of :func:`repro.kernels.page_walk.ref.\
walk_fetch_block_ref`; same shapes, ``fault`` returned as bool.
    ``mask`` must be a python int (it parameterizes the kernel)."""
    lanes = satp.shape[0]
    scalars = jnp.stack([satp, va], axis=-1)           # (L, 2) prefetch
    m = block_words // 2 + 1
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(lanes,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.ANY)],
        out_specs=[
            pl.BlockSpec((1,), lambda i, sp: (i,)),
            pl.BlockSpec((1,), lambda i, sp: (i,)),
            pl.BlockSpec((1, 3), lambda i, sp: (i, 0)),
            pl.BlockSpec((1, block_words), lambda i, sp: (i, 0)),
            pl.BlockSpec((1,), lambda i, sp: (i,)),
        ],
        scratch_shapes=[
            pltpu.VMEM((1,), U64),
            pltpu.VMEM((m,), U64),
            pltpu.SemaphoreType.DMA,
        ],
    )
    kernel = functools.partial(
        _walk_fetch_kernel, mask=int(mask), block_words=block_words,
        n_words=mem.shape[0])
    pa, fault, walk_words, insts, nbytes = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((lanes,), U64),
            jax.ShapeDtypeStruct((lanes,), I32),
            jax.ShapeDtypeStruct((lanes, 3), U64),
            jax.ShapeDtypeStruct((lanes, block_words), U32),
            jax.ShapeDtypeStruct((lanes,), U64),
        ],
        interpret=interpret,
    )(scalars, mem)
    return pa, fault != 0, walk_words, insts, nbytes
