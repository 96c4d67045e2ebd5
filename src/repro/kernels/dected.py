"""DEC-TED(79,64) Pallas kernels: double-error-correct, triple-error-detect.

A true DEC-TED code — shortened BCH over GF(2^7) with an overall-parity
factor, built by ``kernels/bch.py`` — replacing the earlier "two SEC-DED
codes over 32-bit half-words" emulation. 15 check bits per 64-bit word
(23.4% code-bit premium; stored as uint16 -> 25% sidecar capacity).

Guarantees (proven exhaustively by ``tests/ecc_conformance.py``):
  * corrects every 1-bit and every 2-bit error pattern over the 79
    codeword bits (data or check);
  * flags every 3-bit pattern detected-uncorrectable — never miscorrects.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import bch

DECTED_CODE = bch.make_code(k=64, t=2, m=7, parity=True)
N_CHECK = DECTED_CODE.r                        # 15


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def dected_encode_words(lo, hi, *, block_rows: int = 128,
                        interpret=None):
    """lo, hi: (M, W) uint32 -> ecc (M, W) uint32 (15 valid bits)."""
    return bch.bch_encode_words(lo, hi, code=DECTED_CODE,
                                block_rows=block_rows, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def dected_scrub_words(lo, hi, ecc, *, block_rows: int = 128,
                       interpret=None):
    """Scrub/correct. Returns (lo', hi', ecc', corr (M,1), unc (M,1))."""
    return bch.bch_scrub_words(lo, hi, ecc, code=DECTED_CODE,
                               block_rows=block_rows, interpret=interpret)
