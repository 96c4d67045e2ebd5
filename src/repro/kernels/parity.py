"""Pallas TPU kernels for interleaved word parity (Table 1 "Parity" tier).

One parity bit per 64-bit word, packed 8 words per byte: capacity overhead
1/64 = 1.6%, detection of any odd number of flipped bits per word, no
correction — the software response (Par+R) reloads a clean copy instead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

_POP = jax.lax.population_count


def _parity_bits(lo, hi):
    return (_POP(lo) + _POP(hi)) & 1


def _pack8(bits):
    """(BM, W) 0/1 words -> (BM, W//8) bytes, word ``8c + j`` in bit ``j``
    of byte ``c``. Mosaic has no unsigned reduction and no lane-splitting
    reshape, so the packing is one matmul against the constant (W, W//8)
    matrix holding ``2**(r % 8)`` where ``r // 8 == c``. Every operand is
    0/1 or a power of two <= 128 and every sum is <= 255, all exact in
    bf16 and f32, so the result is exact at any matmul precision."""
    w = bits.shape[1]
    r = jax.lax.broadcasted_iota(jnp.int32, (w, w // 8), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (w, w // 8), 1)
    weights = jnp.where(r // 8 == c, jnp.left_shift(1, r % 8),
                        0).astype(jnp.float32)
    packed = jnp.dot(bits.astype(jnp.int32).astype(jnp.float32), weights,
                     preferred_element_type=jnp.float32)
    return packed.astype(jnp.int32).astype(jnp.uint32)


def _encode_kernel(lo_ref, hi_ref, par_ref):
    par_ref[...] = _pack8(_parity_bits(lo_ref[...], hi_ref[...]))


def _check_kernel(lo_ref, hi_ref, par_ref, err_ref, cnt_ref):
    fresh = _pack8(_parity_bits(lo_ref[...], hi_ref[...]))
    diff = fresh ^ par_ref[...]
    err_ref[...] = diff
    cnt_ref[...] = jnp.sum(_POP(diff).astype(jnp.int32), axis=1,
                           keepdims=True)


def _row_spec(bm, w):
    return pl.BlockSpec((bm, w), lambda m: (m, 0))


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def parity_encode_words(lo, hi, *, block_rows: int = 128,
                        interpret=None):
    """lo, hi: (M, W) uint32 -> packed parity (M, W//8) uint32."""
    m, w = lo.shape
    bm = min(block_rows, m)
    assert m % bm == 0 and w % 8 == 0
    return pl.pallas_call(
        _encode_kernel,
        grid=(m // bm,),
        in_specs=[_row_spec(bm, w)] * 2,
        out_specs=_row_spec(bm, w // 8),
        out_shape=jax.ShapeDtypeStruct((m, w // 8), jnp.uint32),
        interpret=interpret_mode(interpret),
    )(lo, hi)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def parity_check_words(lo, hi, par, *, block_rows: int = 128,
                       interpret=None):
    """Returns (packed error bits (M, W//8), per-row error count (M,1))."""
    m, w = lo.shape
    bm = min(block_rows, m)
    assert m % bm == 0 and w % 8 == 0
    outs = (jax.ShapeDtypeStruct((m, w // 8), jnp.uint32),
            jax.ShapeDtypeStruct((m, 1), jnp.int32))
    return pl.pallas_call(
        _check_kernel,
        grid=(m // bm,),
        in_specs=[_row_spec(bm, w)] * 2 + [_row_spec(bm, w // 8)],
        out_specs=(_row_spec(bm, w // 8), _row_spec(bm, 1)),
        out_shape=outs,
        interpret=interpret_mode(interpret),
    )(lo, hi, par)
