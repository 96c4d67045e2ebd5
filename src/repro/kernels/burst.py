"""SEC-DAEC-style adjacent-burst Pallas kernels over 64-bit words.

Bit-interleaved construction: two independent copies of the (39,32)
shortened-BCH SEC-DED sub-code from ``kernels/bch.py`` (t=1, GF(2^6),
overall parity), sub-code A over the even data-bit positions
{0, 2, ..., 62} and sub-code B over the odd positions {1, 3, ..., 63}.
14 check bits per 64-bit word, stored as uint16 (bits 0..6 = A, 7..13 = B).

Why interleaving gives DAEC: any adjacent double (i, i+1) splits one bit
into each sub-code, so both halves see a plain single and correct it. An
adjacent burst that straddles a word boundary is a single in each word —
also corrected. Guarantees (proven by ``tests/ecc_conformance.py``):
  * corrects every single-bit error (data or check);
  * corrects every adjacent data-bit double (all 63 in-word pairs);
  * corrects the ~51% of random doubles that split even/odd;
  * detects (never miscorrects) doubles landing in one sub-code — the
    sub-syndrome has even weight and all single columns are odd.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import bch, interpret_mode

SUB_CODE = bch.make_code(k=32, t=1, m=6, parity=True)
N_SUB = SUB_CODE.r                             # 7 check bits per sub-code
N_CHECK = 2 * N_SUB                            # 14
_SUB_MASK = (1 << N_SUB) - 1

_POP = jax.lax.population_count


def _spread_masks(offset: int):
    """Sub-code parity masks spread onto original 64-bit positions.

    Sub-bit i maps to original bit 2*i + offset (offset 0 = A/even,
    1 = B/odd); returns (mask_lo, mask_hi) tuples of length N_SUB.
    """
    mask_lo, mask_hi = [], []
    for j in range(N_SUB):
        sub = SUB_CODE.mask_lo[j]              # k=32: all sub-bits in lo
        m64 = 0
        for i in range(32):
            if (sub >> i) & 1:
                m64 |= 1 << (2 * i + offset)
        mask_lo.append(m64 & 0xFFFFFFFF)
        mask_hi.append(m64 >> 32)
    return tuple(mask_lo), tuple(mask_hi)


_MASKS = (_spread_masks(0), _spread_masks(1))


def encode_block(lo, hi):
    """14 check bits per 64-bit word; uint32 out, same shape as lo/hi."""
    lo = lo.astype(jnp.uint32)
    hi = hi.astype(jnp.uint32)
    ecc = jnp.zeros(lo.shape, jnp.uint32)
    for sub, (mask_lo, mask_hi) in enumerate(_MASKS):
        for j in range(N_SUB):
            bit = (_POP(lo & jnp.uint32(mask_lo[j]))
                   + _POP(hi & jnp.uint32(mask_hi[j]))) & 1
            ecc = ecc | (bit.astype(jnp.uint32) << (sub * N_SUB + j))
    return ecc


def _decode_sub(s, offset: int):
    """t=1 syndrome decode of one sub-code, flips in original bit space.

    Returns (flip_lo, flip_hi, nonzero, unc) — unc is a nonzero syndrome
    that matches no single column (even-weight double within the
    sub-code, or heavier).
    """
    flip_lo = jnp.zeros(s.shape, jnp.uint32)
    flip_hi = jnp.zeros(s.shape, jnp.uint32)
    matched = jnp.zeros(s.shape, jnp.bool_)
    for i, col in enumerate(SUB_CODE.data_cols):
        eq = s == jnp.uint32(col)
        matched = matched | eq
        b = 2 * i + offset                     # original 64-bit position
        if b < 32:
            flip_lo = flip_lo | (eq.astype(jnp.uint32) << b)
        else:
            flip_hi = flip_hi | (eq.astype(jnp.uint32) << (b - 32))
    for j in range(N_SUB):
        matched = matched | (s == jnp.uint32(1 << j))
    nz = s != 0
    return flip_lo, flip_hi, nz, nz & ~matched


def decode_block(lo, hi, ecc):
    """Scrub one block of packed words.

    Returns (lo', hi', ecc', corrected bool, uncorrectable bool) per word.
    A word is left untouched if either sub-code is uncorrectable.
    """
    lo = lo.astype(jnp.uint32)
    hi = hi.astype(jnp.uint32)
    ecc = ecc.astype(jnp.uint32)
    s = encode_block(lo, hi) ^ ecc
    fa_lo, fa_hi, nz_a, unc_a = _decode_sub(s & _SUB_MASK, 0)
    fb_lo, fb_hi, nz_b, unc_b = _decode_sub((s >> N_SUB) & _SUB_MASK, 1)
    unc = unc_a | unc_b
    keep = (~unc).astype(jnp.uint32) * jnp.uint32(0xFFFFFFFF)
    lo2 = lo ^ ((fa_lo | fb_lo) & keep)
    hi2 = hi ^ ((fa_hi | fb_hi) & keep)
    ecc2 = jnp.where(unc, ecc, encode_block(lo2, hi2))
    corrected = (nz_a | nz_b) & ~unc
    return lo2, hi2, ecc2, corrected, unc


def _encode_kernel(lo_ref, hi_ref, ecc_ref):
    ecc_ref[...] = encode_block(lo_ref[...], hi_ref[...])


def _scrub_kernel(lo_ref, hi_ref, ecc_ref, lo_out, hi_out, ecc_out,
                  corr_ref, unc_ref):
    lo2, hi2, ecc2, corrected, unc = decode_block(
        lo_ref[...], hi_ref[...], ecc_ref[...])
    lo_out[...] = lo2
    hi_out[...] = hi2
    ecc_out[...] = ecc2
    corr_ref[...] = jnp.sum(corrected.astype(jnp.int32), axis=1,
                            keepdims=True)
    unc_ref[...] = jnp.sum(unc.astype(jnp.int32), axis=1, keepdims=True)


def _row_spec(bm: int, w: int):
    return pl.BlockSpec((bm, w), lambda m: (m, 0))


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def burst_encode_words(lo, hi, *, block_rows: int = 128,
                       interpret=None):
    """lo, hi: (M, W) uint32 -> ecc (M, W) uint32 (14 valid bits)."""
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
def burst_scrub_words(lo, hi, ecc, *, block_rows: int = 128,
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
