"""Configurable shortened-BCH codes as Pallas TPU kernels + shared jnp codec.

This module is the single construction behind every stronger-than-SEC-DED
tier in the zoo:

  * ``make_code(k=64, t=2, m=7, parity=True)`` -> the (79,64) DEC-TED code
    used by ``kernels/dected.py`` (double-error-correct, triple-error-detect);
  * ``make_code(k=32, t=1, m=6, parity=True)`` -> the (39,32) SEC-DED-class
    sub-code that ``kernels/burst.py`` interleaves twice for adjacent-burst
    correction;
  * any other (k, t, m, parity) combination for conformance testing.

Construction (all plain ints/numpy at import time, no jax):
  over GF(2^m) with primitive polynomial ``_PRIMITIVE_POLYS[m]``, the
  generator is g(x) = lcm(m_1, m_3, ..., m_{2t-1}) * (x+1 if parity).
  With r = deg g, the code is shortened to n = k + r codeword bits.
  Systematic remainder form: data bit i lives at polynomial degree r+i,
  check bit j at degree j, and the syndrome contribution (column) of a
  data-bit flip is x^{r+i} mod g(x) — so encode is r parity masks over the
  64-bit word, exactly the Hsiao kernel shape.

Decode per 64-bit word (pure VPU bit-math, shared verbatim between the
Pallas kernel body and the eager oracle in ``ref.py``):
  s = recomputed_checks ^ stored_checks           (r-bit syndrome)
  * s == 0: clean.
  * single errors: s equals one of the n columns -> flip that bit. With
    parity, every column has odd weight (e(1) = s(1) since (x+1) | g), so
    even-weight syndromes can never miscorrect onto a single column.
  * t == 2 double errors (even parity, s != 0): power sums S1 = s(alpha),
    S3 = s(alpha^3); the error locator x^2 + S1*x + (S3 + S1^3)/S1 is
    evaluated at every codeword degree by a Chien search in the
    multiplied-through form  S1*alpha^{2p} ^ S1^2*alpha^p ^ (S3 ^ S1^3) == 0
    (no GF division needed). Exactly two roots with S1 != 0 -> flip both.
  * anything else: detected-uncorrectable. Because d_min >= 2t+2 with
    parity, triple errors have odd parity but never match a column, so
    DEC-TED flags every 3-bit pattern instead of miscorrecting.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

_POP = jax.lax.population_count

# x^m + ... primitive over GF(2); value includes the x^m bit.
_PRIMITIVE_POLYS = {
    5: 0b100101,            # x^5 + x^2 + 1
    6: 0b1000011,           # x^6 + x + 1
    7: 0b10001001,          # x^7 + x^3 + 1
    8: 0b100011101,         # x^8 + x^4 + x^3 + x^2 + 1
}


# ------------------------------------------------------------ construction
def _antilog_table(m: int, poly: int) -> Tuple[int, ...]:
    """alpha^i for i in [0, 2^m-1); asserts ``poly`` is primitive."""
    n = (1 << m) - 1
    tab = []
    a = 1
    for _ in range(n):
        tab.append(a)
        a <<= 1
        if a >> m:
            a ^= poly
    assert len(set(tab)) == n, "polynomial is not primitive"
    return tuple(tab)


def _minimal_poly(j: int, m: int, poly: int) -> int:
    """Minimal polynomial of alpha^j over GF(2), as a bit-polynomial int."""
    n = (1 << m) - 1
    antilog = _antilog_table(m, poly)
    log = {v: i for i, v in enumerate(antilog)}

    def mul(a: int, b: int) -> int:
        if a == 0 or b == 0:
            return 0
        return antilog[(log[a] + log[b]) % n]

    coset = []
    c = j % n
    while c not in coset:
        coset.append(c)
        c = (2 * c) % n
    p = [1]                                   # index = degree, GF coeffs
    for c in coset:
        root = antilog[c]
        q = [0] * (len(p) + 1)
        for d, coef in enumerate(p):
            q[d + 1] ^= coef
            q[d] ^= mul(coef, root)
        p = q
    assert all(v in (0, 1) for v in p), "minimal poly not over GF(2)"
    return sum(bit << d for d, bit in enumerate(p))


def _polymul2(a: int, b: int) -> int:
    r, d = 0, 0
    while b >> d:
        if (b >> d) & 1:
            r ^= a << d
        d += 1
    return r


def _polymod2(a: int, g: int) -> int:
    dg = g.bit_length() - 1
    while a and a.bit_length() - 1 >= dg:
        a ^= g << (a.bit_length() - 1 - dg)
    return a


@dataclass(frozen=True)
class BCHCode:
    """Hashable code spec (all-tuple fields -> usable as a jit static arg)."""
    m: int                      # GF(2^m)
    t: int                      # designed correction radius (1 or 2)
    k: int                      # data bits per word (<= 64)
    parity: bool                # overall-parity factor (x+1) in g
    poly: int                   # primitive polynomial of the field
    r: int                      # check bits = deg g
    n: int                      # codeword length = k + r
    gen: int                    # generator polynomial g(x) as bit-int
    data_cols: Tuple[int, ...]  # (k,) syndrome column of data bit i
    check_cols: Tuple[int, ...]  # (r,) unit vectors
    mask_lo: Tuple[int, ...]    # (r,) encode parity masks over data bits
    mask_hi: Tuple[int, ...]
    alpha1: Tuple[int, ...]     # (r,) alpha^j      — S1 = s(alpha)
    alpha3: Tuple[int, ...]     # (r,) alpha^{3j}   — S3 = s(alpha^3)

    @property
    def d_min(self) -> int:
        """Designed minimum distance (BCH bound + parity extension)."""
        return 2 * self.t + 1 + (1 if self.parity else 0)


@functools.lru_cache(maxsize=None)
def make_code(k: int, t: int, m: int, parity: bool = True) -> BCHCode:
    """Build a shortened BCH(n=k+r, k) code over GF(2^m), t in {1, 2}."""
    assert t in (1, 2), "decode paths implemented for t=1 and t=2 only"
    assert 1 <= k <= 64
    poly = _PRIMITIVE_POLYS[m]
    n_field = (1 << m) - 1
    g = 1
    seen = set()
    for j in range(1, 2 * t, 2):              # odd powers 1, 3, ..., 2t-1
        mp = _minimal_poly(j, m, poly)
        if mp not in seen:
            seen.add(mp)
            g = _polymul2(g, mp)
    if parity:
        g = _polymul2(g, 0b11)                # * (x + 1)
    r = g.bit_length() - 1
    n = k + r
    assert n <= n_field, f"(n={n}) exceeds field length {n_field}"

    data_cols = tuple(_polymod2(1 << (r + i), g) for i in range(k))
    check_cols = tuple(1 << j for j in range(r))
    # d_min >= 3 guarantees all n single-error syndromes are distinct.
    assert len(set(data_cols) | set(check_cols)) == n
    if parity:
        # (x+1) | g  =>  every column has odd weight: doubles can't
        # miscorrect onto singles.
        assert all(bin(c).count("1") % 2 == 1 for c in data_cols)

    mask64 = [0] * r
    for i, c in enumerate(data_cols):
        for j in range(r):
            if (c >> j) & 1:
                mask64[j] |= 1 << i
    antilog = _antilog_table(m, poly)
    return BCHCode(
        m=m, t=t, k=k, parity=parity, poly=poly, r=r, n=n, gen=g,
        data_cols=data_cols, check_cols=check_cols,
        mask_lo=tuple(v & 0xFFFFFFFF for v in mask64),
        mask_hi=tuple(v >> 32 for v in mask64),
        alpha1=tuple(antilog[j % n_field] for j in range(r)),
        alpha3=tuple(antilog[(3 * j) % n_field] for j in range(r)),
    )


# ----------------------------------------------------- shared jnp codec
def encode_block(code: BCHCode, lo, hi):
    """r check bits per 64-bit word; uint32 out, same shape as lo/hi."""
    lo = lo.astype(jnp.uint32)
    hi = hi.astype(jnp.uint32)
    ecc = jnp.zeros(lo.shape, jnp.uint32)
    for j in range(code.r):
        bit = (_POP(lo & jnp.uint32(code.mask_lo[j]))
               + _POP(hi & jnp.uint32(code.mask_hi[j]))) & 1
        ecc = ecc | (bit.astype(jnp.uint32) << j)
    return ecc


def _match_single(code: BCHCode, s):
    """Match syndrome against all n single-error columns.

    Returns (matched bool, flip_lo, flip_hi); check-column matches set no
    data flips — re-encoding the (clean) data restores the sidecar.
    """
    flip_lo = jnp.zeros(s.shape, jnp.uint32)
    flip_hi = jnp.zeros(s.shape, jnp.uint32)
    matched = jnp.zeros(s.shape, jnp.bool_)
    for i, col in enumerate(code.data_cols):
        eq = s == jnp.uint32(col)
        matched = matched | eq
        if i < 32:
            flip_lo = flip_lo | (eq.astype(jnp.uint32) << i)
        else:
            flip_hi = flip_hi | (eq.astype(jnp.uint32) << (i - 32))
    for j in range(code.r):
        matched = matched | (s == jnp.uint32(1 << j))
    return matched, flip_lo, flip_hi


def _gf_mulx(code: BCHCode, v):
    """v * alpha in GF(2^m), elementwise over uint32 arrays."""
    red = jnp.uint32(code.poly & ((1 << code.m) - 1))
    top = (v >> (code.m - 1)) & 1
    return ((v << 1) & jnp.uint32((1 << code.m) - 1)) ^ (top * red)


def _gf_mul(code: BCHCode, a, b):
    """a * b in GF(2^m) (Russian-peasant, m unrolled steps)."""
    res = jnp.zeros_like(a)
    for _ in range(code.m):
        res = res ^ jnp.where((b & 1) != 0, a, jnp.uint32(0))
        b = b >> 1
        a = _gf_mulx(code, a)
    return res


def _chien_double(code: BCHCode, s):
    """Locate exactly-two-error patterns from the r-bit syndrome.

    Returns (ok bool, flip_lo, flip_hi, nroots): ok is True where S1 != 0
    and the locator has exactly 2 roots among the n codeword degrees.
    Roots at check degrees (< r) need no data flip — the sidecar is
    rewritten from the corrected data.
    """
    S1 = jnp.zeros(s.shape, jnp.uint32)
    S3 = jnp.zeros(s.shape, jnp.uint32)
    for j in range(code.r):
        sel = ((s >> j) & 1) != 0
        S1 = jnp.where(sel, S1 ^ jnp.uint32(code.alpha1[j]), S1)
        S3 = jnp.where(sel, S3 ^ jnp.uint32(code.alpha3[j]), S3)
    T = S3 ^ _gf_mul(code, _gf_mul(code, S1, S1), S1)     # S3 + S1^3
    w = S1                                                # S1 * alpha^{2p}
    q = _gf_mul(code, S1, S1)                             # S1^2 * alpha^p
    nroots = jnp.zeros(s.shape, jnp.int32)
    flip_lo = jnp.zeros(s.shape, jnp.uint32)
    flip_hi = jnp.zeros(s.shape, jnp.uint32)
    for p in range(code.n):
        root = (w ^ q ^ T) == 0
        nroots = nroots + root.astype(jnp.int32)
        d = p - code.r                                    # data-bit index
        if 0 <= d < 32:
            flip_lo = flip_lo | (root.astype(jnp.uint32) << d)
        elif d >= 32:
            flip_hi = flip_hi | (root.astype(jnp.uint32) << (d - 32))
        w = _gf_mulx(code, _gf_mulx(code, w))
        q = _gf_mulx(code, q)
    ok = (S1 != 0) & (nroots == 2)
    return ok, flip_lo, flip_hi


def decode_block(code: BCHCode, lo, hi, ecc):
    """Scrub one block of packed words.

    Returns (lo', hi', ecc', corrected bool, uncorrectable bool) per word.
    """
    lo = lo.astype(jnp.uint32)
    hi = hi.astype(jnp.uint32)
    ecc = ecc.astype(jnp.uint32)
    s = encode_block(code, lo, hi) ^ ecc
    nz = s != 0
    single, f1_lo, f1_hi = _match_single(code, s)
    if code.t == 1:
        flip_lo, flip_hi = f1_lo, f1_hi
        corrected = single
    else:
        ok2, f2_lo, f2_hi = _chien_double(code, s)
        if code.parity:
            # parity of the syndrome == parity of the error weight, so it
            # routes hard: odd -> single branch, even -> double branch.
            # Triples are odd but never column-match (d_min >= 6), and the
            # Chien never sees them -> detected-uncorrectable, as claimed.
            even = (_POP(s) & 1) == 0
            double = even & nz & ok2
        else:
            # d_min >= 5: a double syndrome never aliases a single column.
            double = ~single & nz & ok2
        dm = double.astype(jnp.uint32)
        flip_lo = f1_lo | (f2_lo & (jnp.uint32(0) - dm))
        flip_hi = f1_hi | (f2_hi & (jnp.uint32(0) - dm))
        corrected = single | double
    unc = nz & ~corrected
    lo2 = lo ^ flip_lo
    hi2 = hi ^ flip_hi
    ecc2 = jnp.where(unc, ecc, encode_block(code, lo2, hi2))
    return lo2, hi2, ecc2, corrected, unc


# ------------------------------------------------------- Pallas kernels
def _encode_kernel(code, lo_ref, hi_ref, ecc_ref):
    ecc_ref[...] = encode_block(code, lo_ref[...], hi_ref[...])


def _scrub_kernel(code, lo_ref, hi_ref, ecc_ref, lo_out, hi_out, ecc_out,
                  corr_ref, unc_ref):
    lo2, hi2, ecc2, corrected, unc = decode_block(
        code, lo_ref[...], hi_ref[...], ecc_ref[...])
    lo_out[...] = lo2
    hi_out[...] = hi2
    ecc_out[...] = ecc2
    corr_ref[...] = jnp.sum(corrected.astype(jnp.int32), axis=1,
                            keepdims=True)
    unc_ref[...] = jnp.sum(unc.astype(jnp.int32), axis=1, keepdims=True)


def _row_spec(bm: int, w: int):
    return pl.BlockSpec((bm, w), lambda m: (m, 0))


@functools.partial(jax.jit, static_argnames=("code", "block_rows",
                                             "interpret"))
def bch_encode_words(lo, hi, *, code: BCHCode, block_rows: int = 128,
                     interpret=None):
    """lo, hi: (M, W) uint32 -> ecc (M, W) uint32 (r valid bits)."""
    m, w = lo.shape
    bm = min(block_rows, m)
    assert m % bm == 0, (m, bm)
    return pl.pallas_call(
        functools.partial(_encode_kernel, code),
        grid=(m // bm,),
        in_specs=[_row_spec(bm, w)] * 2,
        out_specs=_row_spec(bm, w),
        out_shape=jax.ShapeDtypeStruct((m, w), jnp.uint32),
        interpret=interpret_mode(interpret),
    )(lo, hi)


@functools.partial(jax.jit, static_argnames=("code", "block_rows",
                                             "interpret"))
def bch_scrub_words(lo, hi, ecc, *, code: BCHCode, block_rows: int = 128,
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
        functools.partial(_scrub_kernel, code),
        grid=(m // bm,),
        in_specs=[_row_spec(bm, w)] * 3,
        out_specs=(_row_spec(bm, w),) * 3 + (_row_spec(bm, 1),) * 2,
        out_shape=outs,
        interpret=interpret_mode(interpret),
    )(lo, hi, ecc)
