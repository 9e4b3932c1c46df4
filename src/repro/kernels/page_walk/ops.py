"""Dispatch for the Sv39 walk and the fetch-block fill.

The data-side walk is always the pure-jnp oracle.  The Pallas fill
kernel runs only in interpret mode on the CPU backend: its memory image
is u64 words, and Mosaic has no 64-bit integer support, so it does not
lower for the TPU.  ``JaxTarget``, the one target that can ask for it
(the fleet kernel is single-device only for this fill), calls
:func:`check_pallas_backend` when it is built, before any lowering.
"""
from __future__ import annotations

import jax

from . import page_walk as K
from . import ref as R


def check_pallas_backend() -> None:
    """Raise unless the Pallas fill kernel can run on this backend."""
    backend = jax.default_backend()
    if backend != "cpu":
        raise NotImplementedError(
            f"fetch_kernel='pallas' does not lower for the {backend} "
            "backend: the memory image is u64 words and Mosaic has no "
            "64-bit integer support; use fetch_kernel='ref'")


def sv39_walk(mem, satp, va, want_write, want_exec, mask):
    """Data-side walk: always the vectorized oracle — it is pure gather
    math the fast-path interpreter fuses into its tick, with no block
    DMA to win back on an accelerator."""
    return R.sv39_walk_ref(mem, satp, va, want_write, want_exec, mask)


def walk_fetch_block(mem, satp, va, mask, block_words):
    """The Pallas fill kernel, in interpret mode (CPU backend only)."""
    return K.walk_fetch_block(mem, satp, va, mask, block_words,
                              interpret=True)
