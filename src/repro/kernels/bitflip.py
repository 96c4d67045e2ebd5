"""Pallas TPU kernel for controlled bit-flip injection (the paper's Fig.2
error-emulation step, adapted to tensors).

Flips up to E bits, each addressed as (flat word index, bit-in-word 0..63),
in one pass over the packed words. E is small and static (the injection
plan is padded with word_idx = -1); the kernel broadcast-compares each
word's global index against the plan, so cost is O(M*W*E/VPU) — negligible
next to a scrub.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode


def _flip_kernel(idx_ref, bit_ref, lo_ref, hi_ref, lo_out, hi_out, *, w):
    m = pl.program_id(0)
    lo = lo_ref[...]
    hi = hi_ref[...]
    bm = lo.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (bm, w), 0) + m * bm
    col = jax.lax.broadcasted_iota(jnp.int32, (bm, w), 1)
    gidx = row * w + col                       # global flat word index
    e = idx_ref.shape[0]
    for k in range(e):
        widx = idx_ref[k]
        b = bit_ref[k]
        active = widx >= 0
        hit = (gidx == widx) & active
        is_lo = b < 32
        mlo = jnp.where(is_lo, jnp.uint32(1) << b.astype(jnp.uint32),
                        jnp.uint32(0))
        mhi = jnp.where(is_lo, jnp.uint32(0),
                        jnp.uint32(1) << (b - 32).astype(jnp.uint32))
        lo = jnp.where(hit, lo ^ mlo, lo)
        hi = jnp.where(hit, hi ^ mhi, hi)
    lo_out[...] = lo
    hi_out[...] = hi


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def bitflip_words(lo, hi, word_idx, bit_idx, *, block_rows: int = 128,
                  interpret=None):
    """lo, hi: (M, W) uint32; word_idx/bit_idx: (E,) int32 -> flipped lo, hi."""
    m, w = lo.shape
    bm = min(block_rows, m)
    assert m % bm == 0
    e = word_idx.shape[0]
    kernel = functools.partial(_flip_kernel, w=w)
    row = pl.BlockSpec((bm, w), lambda i: (i, 0))
    full = pl.BlockSpec((e,), lambda i: (0,))
    outs = (jax.ShapeDtypeStruct((m, w), jnp.uint32),
            jax.ShapeDtypeStruct((m, w), jnp.uint32))
    return pl.pallas_call(
        kernel,
        grid=(m // bm,),
        in_specs=[full, full, row, row],
        out_specs=(row, row),
        out_shape=outs,
        interpret=interpret_mode(interpret),
    )(word_idx, bit_idx, lo, hi)
