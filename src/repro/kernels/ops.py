"""jit'd public wrappers around the Pallas kernels.

Handles packing arbitrary tensors (f32 / bf16 / f16 / i32 / u32 / i8 / u8)
into the (M, W)-shaped uint32 word-lane layout the kernels consume, and
unpacking corrected data back to the original shape/dtype. On CPU the
kernels run in the Pallas interpreter (``repro.kernels.interpret_mode``) —
TPU is the compile target.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import bitflip as _bitflip
from repro.kernels import burst as _burst
from repro.kernels import dected as _dected
from repro.kernels import parity as _parity
from repro.kernels import secded as _secded

LANES = 256          # words per packed row; multiple of the 128-lane tile
BLOCK_ROWS = 128


class Packed(NamedTuple):
    lo: jax.Array            # (M, LANES) uint32
    hi: jax.Array            # (M, LANES) uint32


def _round_rows(rows: int) -> int:
    """Rows padded so the kernel grid divides evenly: tensors larger than
    one block round up to a multiple of BLOCK_ROWS."""
    rows = max(1, rows)
    if rows > BLOCK_ROWS:
        rows = -(-rows // BLOCK_ROWS) * BLOCK_ROWS
    return rows


_UINT = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}


def _lanes_per_word(dtype) -> Tuple[int, int]:
    """(element bits, elements per 64-bit word) of a supported dtype."""
    size = jnp.dtype(dtype).itemsize
    if size not in _UINT:
        raise TypeError(f"unsupported dtype {dtype}")
    return 8 * size, 8 // size


def pack_words(x: jax.Array) -> Packed:
    """Tensor -> (lo, hi) word lanes, zero-padded to full (M, LANES) rows.

    Word ``w`` holds the tensor's flat bytes ``8w .. 8w+7``, little-endian:
    ``lo`` the first four, ``hi`` the last four. The elements are gathered
    by strided slices of a lane-dense (M, m * LANES) view: on a TPU, any
    intermediate with a narrow minor dimension (an (n, 2) pair array, say)
    is padded to 128 lanes and can outgrow the device memory.

    The integer view comes before any other op: XLA rewrites the NaN
    payloads and subnormals of 16-bit floats in ``pad`` on CPU, and on a
    TPU (measured on a v5e) already in the bitcast itself, so there those
    bit patterns do not survive a pack/unpack."""
    bits, m = _lanes_per_word(x.dtype)
    flat = jax.lax.bitcast_convert_type(x, _UINT[bits // 8]).reshape(-1)
    rows = _round_rows(-(-flat.shape[0] // (m * LANES)))
    pad = rows * LANES * m - flat.shape[0]
    if pad:
        flat = jnp.pad(flat, (0, pad))
    view = flat.reshape(rows, m * LANES)

    def half(first):
        out = view[:, first::m].astype(jnp.uint32)
        for j in range(1, m // 2):
            out = out | (view[:, first + j::m].astype(jnp.uint32)
                         << (bits * j))
        return out

    return Packed(half(0), half(m // 2))


def unpack_words(p: Packed, shape, dtype) -> jax.Array:
    """Inverse of ``pack_words``: the elements are scattered back into a
    lane-dense (M, m * LANES) view of the element width (see
    ``pack_words`` for why)."""
    bits, m = _lanes_per_word(dtype)
    uint = _UINT[bits // 8]
    view = jnp.zeros((p.lo.shape[0], m * LANES), uint)
    for h, word in enumerate((p.lo, p.hi)):
        for j in range(m // 2):
            part = word >> (bits * j) if j else word
            view = view.at[:, h * (m // 2) + j::m].set(part.astype(uint))
    n = int(np.prod(shape)) if shape else 1
    return jax.lax.bitcast_convert_type(view.reshape(-1)[:n].reshape(shape),
                                        dtype)


def words_per_tensor(x) -> int:
    """Number of (M, LANES)-padded 64-bit words used for tensor ``x``."""
    nbytes = int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize if x.shape \
        else jnp.dtype(x.dtype).itemsize
    n64 = -(-nbytes // 8)
    return _round_rows(-(-n64 // LANES)) * LANES


def _bm(m: int) -> int:
    return min(BLOCK_ROWS, m)


# --------------------------------------------------------------- SEC-DED
def secded_encode(x: jax.Array) -> jax.Array:
    """ECC sidecar for tensor ``x``: (M, LANES) uint8 (12.5% capacity)."""
    p = pack_words(x)
    ecc = _secded.secded_encode_words(p.lo, p.hi,
                                      block_rows=_bm(p.lo.shape[0]))
    return ecc.astype(jnp.uint8)


def secded_scrub(x: jax.Array, ecc: jax.Array
                 ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Scrub tensor against its ECC sidecar.

    Returns (corrected tensor, corrected ecc (uint8), n_corrected,
    n_uncorrectable).
    """
    p = pack_words(x)
    lo, hi, ecc2, corr, unc = _secded.secded_scrub_words(
        p.lo, p.hi, ecc.astype(jnp.uint32), block_rows=_bm(p.lo.shape[0]))
    x2 = unpack_words(Packed(lo, hi), x.shape, x.dtype)
    return x2, ecc2.astype(jnp.uint8), jnp.sum(corr), jnp.sum(unc)


# --------------------------------------------------------------- DEC-TED
def dected_encode(x: jax.Array) -> jax.Array:
    """DEC-TED sidecar for tensor ``x``: (M, LANES) uint16 (25% capacity,
    15 valid code bits per 64-bit word)."""
    p = pack_words(x)
    ecc = _dected.dected_encode_words(p.lo, p.hi,
                                      block_rows=_bm(p.lo.shape[0]))
    return ecc.astype(jnp.uint16)


def dected_scrub(x: jax.Array, ecc: jax.Array
                 ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Scrub tensor against its DEC-TED sidecar.

    Returns (corrected tensor, corrected ecc (uint16), n_corrected,
    n_uncorrectable). Corrects all 1/2-bit word errors, detects 3-bit.
    """
    p = pack_words(x)
    lo, hi, ecc2, corr, unc = _dected.dected_scrub_words(
        p.lo, p.hi, ecc.astype(jnp.uint32), block_rows=_bm(p.lo.shape[0]))
    x2 = unpack_words(Packed(lo, hi), x.shape, x.dtype)
    return x2, ecc2.astype(jnp.uint16), jnp.sum(corr), jnp.sum(unc)


# ------------------------------------------------------------ burst/DAEC
def burst_encode(x: jax.Array) -> jax.Array:
    """SEC-DAEC sidecar for tensor ``x``: (M, LANES) uint16 (25% capacity,
    14 valid code bits per 64-bit word)."""
    p = pack_words(x)
    ecc = _burst.burst_encode_words(p.lo, p.hi,
                                    block_rows=_bm(p.lo.shape[0]))
    return ecc.astype(jnp.uint16)


def burst_scrub(x: jax.Array, ecc: jax.Array
                ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Scrub tensor against its SEC-DAEC sidecar.

    Returns (corrected tensor, corrected ecc (uint16), n_corrected,
    n_uncorrectable). Corrects singles and adjacent doubles.
    """
    p = pack_words(x)
    lo, hi, ecc2, corr, unc = _burst.burst_scrub_words(
        p.lo, p.hi, ecc.astype(jnp.uint32), block_rows=_bm(p.lo.shape[0]))
    x2 = unpack_words(Packed(lo, hi), x.shape, x.dtype)
    return x2, ecc2.astype(jnp.uint16), jnp.sum(corr), jnp.sum(unc)


# ---------------------------------------------------------------- parity
def parity_encode(x: jax.Array) -> jax.Array:
    """Packed parity sidecar: (M, LANES//8) uint8 (1.6% capacity)."""
    p = pack_words(x)
    par = _parity.parity_encode_words(p.lo, p.hi,
                                      block_rows=_bm(p.lo.shape[0]))
    return par.astype(jnp.uint8)


def parity_check(x: jax.Array, par: jax.Array) -> jax.Array:
    """Number of 64-bit words whose parity mismatches (detected errors)."""
    p = pack_words(x)
    _, cnt = _parity.parity_check_words(p.lo, p.hi, par.astype(jnp.uint32),
                                        block_rows=_bm(p.lo.shape[0]))
    return jnp.sum(cnt)


def parity_error_words(x: jax.Array, par: jax.Array) -> jax.Array:
    """Per-word boolean error mask, shape (M, LANES)."""
    p = pack_words(x)
    err, _ = _parity.parity_check_words(p.lo, p.hi, par.astype(jnp.uint32),
                                        block_rows=_bm(p.lo.shape[0]))
    bits = (err[..., :, None] >> jnp.arange(8, dtype=jnp.uint32)) & 1
    return bits.reshape(p.lo.shape).astype(jnp.bool_)


def restore_words(x: jax.Array, good: jax.Array, word_mask: jax.Array
                  ) -> jax.Array:
    """Replace the 64-bit words of ``x`` flagged in ``word_mask`` with the
    corresponding words of ``good`` (mirror-repair primitive)."""
    px, pg = pack_words(x), pack_words(good)
    lo = jnp.where(word_mask, pg.lo, px.lo)
    hi = jnp.where(word_mask, pg.hi, px.hi)
    return unpack_words(Packed(lo, hi), x.shape, x.dtype)


# --------------------------------------------------------------- bitflip
@jax.jit
def inject_bitflips(x: jax.Array, word_idx: jax.Array, bit_idx: jax.Array
                    ) -> jax.Array:
    """Flip bits (word_idx[e], bit_idx[e]) of tensor ``x`` (packed space).

    ``word_idx`` entries < 0 are inactive slots. One program, so the packed
    copies of a large leaf are not all live at once.
    """
    p = pack_words(x)
    lo, hi = _bitflip.bitflip_words(p.lo, p.hi,
                                    word_idx.astype(jnp.int32),
                                    bit_idx.astype(jnp.int32),
                                    block_rows=_bm(p.lo.shape[0]))
    return unpack_words(Packed(lo, hi), x.shape, x.dtype)
