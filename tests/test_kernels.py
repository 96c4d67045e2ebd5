"""Pallas kernel plumbing: pack/inject shape/dtype sweeps and the Hsiao
code-structure invariants.

Per-codec differential and round-trip coverage (encode/scrub vs oracle,
single/double/triple-bit contracts, parity escapes) lives in the
parametrized conformance suite — tests/ecc_conformance.py — which sweeps
ALL codecs (parity, SEC-DED, DEC-TED, BURST, generic BCH) instead of the
SEC-DED-only spot checks that used to sit here."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import hsiao, ops
from repro.kernels.ref import bitflip_ref

DTYPES = [jnp.float32, jnp.bfloat16, jnp.float16, jnp.int32, jnp.int8]
SHAPES = [(8,), (129,), (37, 53), (4, 4, 4), (1, 1), (512, 300)]


def _mk(shape, dtype, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape) * 7
    if jnp.issubdtype(dtype, jnp.integer):
        return (x * 5).astype(dtype)
    return x.astype(dtype)


# ------------------------------------------------------- sweep vs oracle
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", SHAPES)
def test_pack_roundtrip(shape, dtype):
    x = _mk(shape, dtype)
    p = ops.pack_words(x)
    assert p.lo.shape[1] == ops.LANES
    x2 = ops.unpack_words(p, x.shape, x.dtype)
    assert (np.asarray(x2) == np.asarray(x)).all()


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", [(129,), (37, 53), (512, 300)])
def test_pack_layout_is_flat_bytes(shape, dtype):
    """Word w holds the tensor's flat bytes 8w..8w+7, little-endian: lo the
    first four, hi the last four, zero-padded to whole (M, LANES) rows."""
    x = _mk(shape, dtype)
    raw = np.asarray(x).tobytes()
    p = ops.pack_words(x)
    words = np.zeros(p.lo.size * 2, np.uint32)
    words.view(np.uint8)[:len(raw)] = np.frombuffer(raw, np.uint8)
    words = words.reshape(-1, 2)
    assert np.array_equal(np.asarray(p.lo).reshape(-1), words[:, 0])
    assert np.array_equal(np.asarray(p.hi).reshape(-1), words[:, 1])


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float16])
@pytest.mark.parametrize("shape", [(3001,), (3, 5, 7, 64)])
def test_pack_keeps_nan_payloads_and_subnormals(shape, dtype):
    """Any bits pack to the flat-byte layout and unpack unchanged, NaN
    payloads and subnormals included: the integer view comes before any
    other op (on CPU; a TPU's own bitcast rewrites those patterns)."""
    raw = np.random.default_rng(1).integers(0, 1 << 16, shape,
                                            dtype=np.uint16)
    x = jax.device_put(raw.view(jnp.dtype(dtype)))
    p = ops.pack_words(x)
    words = np.zeros(p.lo.size * 2, np.uint32)
    words.view(np.uint16)[:raw.size] = raw.reshape(-1)
    assert np.array_equal(np.asarray(p.lo).reshape(-1), words[0::2])
    assert np.array_equal(np.asarray(p.hi).reshape(-1), words[1::2])
    back = ops.unpack_words(p, shape, dtype)
    assert np.array_equal(np.asarray(back).view(np.uint16), raw)


def test_interpret_mode_follows_backend(monkeypatch):
    from repro.kernels import interpret_mode
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert interpret_mode() is True and interpret_mode(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert interpret_mode() is False
    with pytest.raises(ValueError):
        interpret_mode(True)


# tensor-level wrappers over each codec: one smoke round-trip per tier
# (the words-level kernels themselves are proven in ecc_conformance.py)
@pytest.mark.parametrize("encode,scrub", [
    (ops.secded_encode, ops.secded_scrub),
    (ops.dected_encode, ops.dected_scrub),
    (ops.burst_encode, ops.burst_scrub),
])
def test_tensor_wrappers_roundtrip(encode, scrub):
    x = _mk((64, 64), jnp.float32, seed=1)
    ecc = encode(x)
    widx = jnp.array([0, 7, 100, 333, -1], jnp.int32)
    bidx = jnp.array([0, 17, 63, 31, 0], jnp.int32)
    xf = ops.inject_bitflips(x, widx, bidx)
    x2, ecc2, corr, unc = scrub(xf, ecc)
    assert (np.asarray(x2) == np.asarray(x)).all()
    assert int(corr) == 4 and int(unc) == 0
    assert (np.asarray(ecc2) == np.asarray(ecc)).all()


def test_bitflip_kernel_matches_ref():
    x = _mk((128, 67), jnp.float32, seed=4)
    p = ops.pack_words(x)
    widx = jnp.array([1, 500, 4095, -1, 2], jnp.int32)
    bidx = jnp.array([5, 33, 63, 12, 0], jnp.int32)
    xf = ops.inject_bitflips(x, widx, bidx)
    lo_r, hi_r = bitflip_ref(p.lo.reshape(-1), p.hi.reshape(-1), widx, bidx)
    pf = ops.pack_words(xf)
    assert (np.asarray(pf.lo.reshape(-1)) == np.asarray(lo_r)).all()
    assert (np.asarray(pf.hi.reshape(-1)) == np.asarray(hi_r)).all()


# ------------------------------------------------------ property tests
@settings(max_examples=30, deadline=None)
@given(word=st.integers(0, 127), bit=st.integers(0, 63))
def test_inject_is_involutive(word, bit):
    """Flipping the same bit twice restores the tensor exactly."""
    x = _mk((16, 16), jnp.bfloat16, seed=9)
    w = jnp.array([word], jnp.int32)
    b = jnp.array([bit], jnp.int32)
    x2 = ops.inject_bitflips(ops.inject_bitflips(x, w, b), w, b)
    assert (np.asarray(x2) == np.asarray(x)).all()


def test_hsiao_code_structure():
    """Odd-weight distinct columns; double-error syndromes never alias."""
    cols = hsiao.DATA_COLS.tolist()
    assert len(set(cols)) == 64
    for c in cols:
        assert bin(c).count("1") % 2 == 1
    correctable = set(cols) | set(hsiao.CHECK_COLS.tolist())
    # xor of any two distinct columns (a double error) must not be a
    # correctable syndrome
    allc = cols + hsiao.CHECK_COLS.tolist()
    for i in range(len(allc)):
        for j in range(i + 1, len(allc)):
            assert (allc[i] ^ allc[j]) not in correctable | {0}
