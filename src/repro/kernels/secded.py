"""Pallas TPU kernels for Hsiao SEC-DED(72,64) encode and scrub-correct.

Data layout: a tensor is packed (by ``ops.py``) into two uint32 lane arrays
``lo, hi`` of shape (M, W) — each (row, lane) pair is one 64-bit word — plus
an ECC array of the same shape (8 valid bits per word; stored as uint8 in
the sidecar, widened to uint32 for the kernel).

Tiling: grid over rows, BlockSpec (BM, W) in VMEM. W=256 lanes x BM=128
rows x 4 B = 128 KiB per operand block — comfortably inside VMEM with all
operands + temporaries resident; lane width 256 is a multiple of the 128
vector-lane tile so loads stay aligned. The scrub kernel is pure VPU
bit-math (population_count, shifts, compares) at ~17 int-ops/word over
12 B/word — memory-bound by design, which is exactly why the HRM scrub
schedule streams it over HBM in the background of compute steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import hsiao, interpret_mode

_POP = jax.lax.population_count


def _encode_block(lo, hi):
    ecc = jnp.zeros(lo.shape, jnp.uint32)
    for j in range(hsiao.N_CHECK):
        mlo = jnp.uint32(int(hsiao.MASK_LO[j]))
        mhi = jnp.uint32(int(hsiao.MASK_HI[j]))
        bit = (_POP(lo & mlo) + _POP(hi & mhi)) & 1
        ecc = ecc | (bit.astype(jnp.uint32) << j)
    return ecc


def _encode_kernel(lo_ref, hi_ref, ecc_ref):
    ecc_ref[...] = _encode_block(lo_ref[...], hi_ref[...])


def _scrub_kernel(lo_ref, hi_ref, ecc_ref, lo_out, hi_out, ecc_out,
                  corr_ref, unc_ref):
    lo = lo_ref[...]
    hi = hi_ref[...]
    ecc = ecc_ref[...]
    synd = _encode_block(lo, hi) ^ ecc

    flip_lo = jnp.zeros_like(lo)
    flip_hi = jnp.zeros_like(hi)
    matched = synd == 0
    for i in range(hsiao.N_DATA):
        eq = synd == jnp.uint32(int(hsiao.DATA_COLS[i]))
        matched = matched | eq
        if i < 32:
            flip_lo = flip_lo | (eq.astype(jnp.uint32) << i)
        else:
            flip_hi = flip_hi | (eq.astype(jnp.uint32) << (i - 32))
    for j in range(hsiao.N_CHECK):
        matched = matched | (synd == jnp.uint32(1 << j))

    unc = ~matched
    lo2 = lo ^ flip_lo
    hi2 = hi ^ flip_hi
    ecc2 = jnp.where(unc, ecc, _encode_block(lo2, hi2))
    lo_out[...] = lo2
    hi_out[...] = hi2
    ecc_out[...] = ecc2
    corrected = (synd != 0) & matched
    corr_ref[...] = jnp.sum(corrected.astype(jnp.int32), axis=1,
                            keepdims=True)
    unc_ref[...] = jnp.sum(unc.astype(jnp.int32), axis=1, keepdims=True)


def _row_spec(bm: int, w: int):
    return pl.BlockSpec((bm, w), lambda m: (m, 0))


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def secded_encode_words(lo, hi, *, block_rows: int = 128,
                        interpret=None):
    """lo, hi: (M, W) uint32 -> ecc (M, W) uint32. M % block_rows == 0."""
    m, w = lo.shape
    bm = min(block_rows, m)
    assert m % bm == 0, (m, bm)
    return pl.pallas_call(
        _encode_kernel,
        grid=(m // bm,),
        in_specs=[_row_spec(bm, w)] * 2,
        out_specs=_row_spec(bm, w),
        out_shape=jax.ShapeDtypeStruct((m, w), jnp.uint32),
        interpret=interpret_mode(interpret),
    )(lo, hi)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def secded_scrub_words(lo, hi, ecc, *, block_rows: int = 128,
                       interpret=None):
    """Scrub/correct. Returns (lo', hi', ecc', corr (M,1), unc (M,1))."""
    m, w = lo.shape
    bm = min(block_rows, m)
    assert m % bm == 0, (m, bm)
    outs = (
        jax.ShapeDtypeStruct((m, w), jnp.uint32),
        jax.ShapeDtypeStruct((m, w), jnp.uint32),
        jax.ShapeDtypeStruct((m, w), jnp.uint32),
        jax.ShapeDtypeStruct((m, 1), jnp.int32),
        jax.ShapeDtypeStruct((m, 1), jnp.int32),
    )
    return pl.pallas_call(
        _scrub_kernel,
        grid=(m // bm,),
        in_specs=[_row_spec(bm, w)] * 3,
        out_specs=(_row_spec(bm, w),) * 3 + (_row_spec(bm, 1),) * 2,
        out_shape=outs,
        interpret=interpret_mode(interpret),
    )(lo, hi, ecc)
