"""ECC conformance suite — every codec in the zoo, proven, not spot-checked.

One parametrized differential + contract suite over ALL codecs (parity,
SEC-DED, DEC-TED, BURST, generic shortened-BCH), replacing the per-codec
tests that used to live in test_kernels.py:

  differential   every Pallas kernel is bit-identical to its pure-jnp
                 eager oracle on random payloads AND corrupted sidecars
  contract       encode -> inject -> scrub round-trips at the codeword
                 level: EXHAUSTIVE single-bit sweeps (every data and
                 check position) always; sampled double/triple sweeps in
                 tier-1; the full C(n,2) double and sampled triple
                 sweeps under ``-m slow``
  system         adjacent-burst storms through a live MemoryDomain
                 across tiers (parity: silent SDC; SEC-DED: detected,
                 stuck; BURST/DEC-TED: fully healed), the §8.3
                 strike-mix regression pin, and the measured-rates
                 calibration cross-check
  property       pack/unpack round-trips over arbitrary dtypes/shapes
                 (ragged tails included), on hypothesis or the conftest
                 fallback

Collected via the ``python_files`` override in pyproject.toml.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Callable, Tuple

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.domain import MemoryDomain
from repro.core.eccmeasure import measure_class_rates
from repro.core.errormodel import (DEFAULT_ADJACENT_FRACTION,
                                   DEFAULT_MULTI_BIT_FRACTION, ErrorModel,
                                   InjectionPlan)
from repro.core.policy import HRMPolicy
from repro.core.tiers import TIER_TABLE, Tier
from repro.kernels import bch, ops, ref
from repro.kernels.burst import (N_CHECK as BURST_CHECK, burst_encode_words,
                                 burst_scrub_words)
from repro.kernels.dected import (DECTED_CODE, N_CHECK as DECTED_CHECK,
                                  dected_encode_words, dected_scrub_words)
from repro.kernels.ops import LANES
from repro.kernels.parity import parity_check_words, parity_encode_words
from repro.kernels.secded import secded_encode_words, secded_scrub_words

# a generic shortened-BCH instance distinct from the DEC-TED production
# code: t=1 over GF(2^7) + parity -> a (72,64) SEC-DED-class code, proving
# the configurable construction (not just the two shipped codes)
BCH72 = bch.make_code(k=64, t=1, m=7, parity=True)


@dataclass(frozen=True)
class Codec:
    """One ECC codec at the packed-words level.

    Codeword positions: 0..63 are data bits (lo then hi), 64..64+n_check-1
    are sidecar check bits.
    """
    name: str
    n_check: int
    corrects: int                 # any pattern of <= this many random bits
    detects: int                  # ... and flags up to this many
    corrects_adjacent: bool       # corrects (b, b+1) data bursts too
    encode_k: Callable            # (lo, hi, **kw) -> ecc
    scrub_k: Callable             # (lo, hi, ecc, **kw) -> 5-tuple
    encode_o: Callable            # oracle twins, same signatures sans kw
    scrub_o: Callable


def _partial_code(fn, code):
    return lambda *a, **kw: fn(*a, code=code, **kw)


CODECS = {
    "secded": Codec("secded", 8, 1, 2, False,
                    secded_encode_words, secded_scrub_words,
                    ref.secded_encode_ref, ref.secded_scrub_ref),
    "dected": Codec("dected", DECTED_CHECK, 2, 3, True,
                    dected_encode_words, dected_scrub_words,
                    ref.dected_encode_ref, ref.dected_scrub_ref),
    "burst": Codec("burst", BURST_CHECK, 1, 2, True,
                   burst_encode_words, burst_scrub_words,
                   ref.burst_encode_ref, ref.burst_scrub_ref),
    "bch72": Codec("bch72", BCH72.r, 1, 2, False,
                   _partial_code(bch.bch_encode_words, BCH72),
                   _partial_code(bch.bch_scrub_words, BCH72),
                   lambda lo, hi: ref.bch_encode_ref(BCH72, lo, hi),
                   lambda lo, hi, e: ref.bch_scrub_ref(BCH72, lo, hi, e)),
}
CODEC_IDS = sorted(CODECS)


def _kw(rows):
    return dict(block_rows=rows)


def _payload(rows, width, seed):
    rng = np.random.default_rng(seed)
    lo = rng.integers(0, 2 ** 32, (rows, width), dtype=np.uint32)
    hi = rng.integers(0, 2 ** 32, (rows, width), dtype=np.uint32)
    return lo, hi


def _apply_patterns(lo, hi, ecc, patterns, width):
    """One codeword-position pattern per row, cycling the struck column."""
    lo, hi, ecc = lo.copy(), hi.copy(), ecc.copy()
    for i, pat in enumerate(patterns):
        c = i % width
        for p in pat:
            if p < 32:
                lo[i, c] ^= np.uint32(1) << np.uint32(p)
            elif p < 64:
                hi[i, c] ^= np.uint32(1) << np.uint32(p - 32)
            else:
                ecc[i, c] ^= np.uint32(1) << np.uint32(p - 64)
    return lo, hi, ecc


def _sweep(codec: Codec, patterns, width=4, seed=0):
    """Encode clean rows, strike one pattern per row, scrub; returns the
    clean/struck arrays plus per-row restored/corr/unc classifications."""
    rows = len(patterns)
    lo, hi = _payload(rows, width, seed)
    ecc = np.asarray(codec.encode_k(jnp.asarray(lo), jnp.asarray(hi),
                                    **_kw(rows)))
    blo, bhi, becc = _apply_patterns(lo, hi, ecc, patterns, width)
    lo2, hi2, ecc2, corr, unc = codec.scrub_k(
        jnp.asarray(blo), jnp.asarray(bhi), jnp.asarray(becc), **_kw(rows))
    lo2, hi2, ecc2 = np.asarray(lo2), np.asarray(hi2), np.asarray(ecc2)
    restored = ((lo2 == lo) & (hi2 == hi)).all(axis=1) & (ecc2 == ecc).all(
        axis=1)
    return dict(lo=lo, hi=hi, ecc=ecc, blo=blo, bhi=bhi, becc=becc,
                lo2=lo2, hi2=hi2, ecc2=ecc2, restored=restored,
                corr=np.asarray(corr)[:, 0], unc=np.asarray(unc)[:, 0])


def _positions(codec: Codec):
    return range(64 + codec.n_check)


def _sample_tuples(codec: Codec, k, count, seed):
    rng = np.random.default_rng(seed)
    n = 64 + codec.n_check
    out = set()
    while len(out) < count:
        out.add(tuple(sorted(rng.choice(n, size=k, replace=False).tolist())))
    return sorted(out)


# ============================================================ differential
@pytest.mark.parametrize("name", CODEC_IDS)
def test_encode_kernel_bit_identical_to_oracle(name):
    codec = CODECS[name]
    lo, hi = _payload(8, LANES, seed=11)
    ecc_k = codec.encode_k(jnp.asarray(lo), jnp.asarray(hi), **_kw(8))
    ecc_o = codec.encode_o(jnp.asarray(lo), jnp.asarray(hi))
    assert (np.asarray(ecc_k) == np.asarray(ecc_o)).all()
    # all check bits fit the declared sidecar width
    assert int(np.asarray(ecc_k).max()) < (1 << codec.n_check)


@pytest.mark.parametrize("name", CODEC_IDS)
def test_scrub_kernel_bit_identical_to_oracle(name):
    """Kernel == oracle on every output, including corrupted-sidecar and
    beyond-capacity strikes (where behavior must still agree exactly)."""
    codec = CODECS[name]
    rng = np.random.default_rng(13)
    rows = 16
    lo, hi = _payload(rows, LANES, seed=13)
    ecc = np.asarray(codec.encode_k(jnp.asarray(lo), jnp.asarray(hi),
                                    **_kw(rows)))
    patterns = [tuple(sorted(
        rng.choice(64 + codec.n_check, size=rng.integers(1, 5),
                   replace=False).tolist())) for _ in range(rows)]
    blo, bhi, becc = _apply_patterns(lo, hi, ecc, patterns, LANES)
    outs_k = codec.scrub_k(jnp.asarray(blo), jnp.asarray(bhi),
                           jnp.asarray(becc), **_kw(rows))
    outs_o = codec.scrub_o(jnp.asarray(blo), jnp.asarray(bhi),
                           jnp.asarray(becc))
    for k, o in zip(outs_k[:3], outs_o[:3]):
        assert (np.asarray(k) == np.asarray(o)).all()
    # corr/unc oracles are per-word bools; kernels emit per-row sums
    assert (np.asarray(outs_k[3])[:, 0]
            == np.asarray(jnp.sum(outs_o[3].astype(jnp.int32),
                                  axis=1))).all()
    assert (np.asarray(outs_k[4])[:, 0]
            == np.asarray(jnp.sum(outs_o[4].astype(jnp.int32),
                                  axis=1))).all()


def test_parity_kernel_bit_identical_to_oracle():
    lo, hi = _payload(8, LANES, seed=17)
    par_k = parity_encode_words(jnp.asarray(lo), jnp.asarray(hi), **_kw(8))
    par_o = ref.parity_encode_ref(jnp.asarray(lo), jnp.asarray(hi))
    assert (np.asarray(par_k) == np.asarray(par_o)).all()
    blo = lo.copy()
    blo[:, 0] ^= 1
    err, cnt = parity_check_words(jnp.asarray(blo), jnp.asarray(hi), par_k,
                                  **_kw(8))
    mask_o = ref.parity_check_ref(jnp.asarray(blo), jnp.asarray(hi), par_o)
    bits = (np.asarray(err)[:, :, None]
            >> np.arange(8, dtype=np.uint32)) & 1
    assert (bits.reshape(lo.shape).astype(bool) == np.asarray(mask_o)).all()
    assert (np.asarray(cnt)[:, 0] == 1).all()


# ================================================================ contract
@pytest.mark.parametrize("name", CODEC_IDS)
def test_single_bit_sweep_exhaustive(name):
    """EVERY single-bit position — data and check — is fully healed:
    payload, sidecar, and flags all return to the clean state."""
    codec = CODECS[name]
    patterns = [(p,) for p in _positions(codec)]
    r = _sweep(codec, patterns)
    assert r["restored"].all()
    assert (r["unc"] == 0).all()
    # data strikes are reported corrected (check-bit-only strikes may
    # legitimately be absorbed silently by re-encode)
    assert (r["corr"][:64] >= 1).all()


@pytest.mark.parametrize("name", CODEC_IDS)
def test_double_bit_sweep_sampled(name):
    _assert_double_contract(CODECS[name],
                            _sample_tuples(CODECS[name], 2, 160, seed=23))


@pytest.mark.slow
@pytest.mark.parametrize("name", CODEC_IDS)
def test_double_bit_sweep_exhaustive(name):
    """All C(n, 2) double-bit patterns over the full codeword."""
    codec = CODECS[name]
    _assert_double_contract(
        codec, list(itertools.combinations(_positions(codec), 2)))


def _assert_double_contract(codec: Codec, patterns):
    r = _sweep(codec, patterns, width=2)
    silent = ~r["restored"] & (r["unc"] == 0)
    assert not silent.any(), "double-bit SDC"
    if codec.corrects >= 2:
        # DEC-TED: every double corrected outright
        assert r["restored"].all()
        assert (r["unc"] == 0).all()
        return
    # t=1 codes: detected-uncorrectable doubles must leave the word as
    # struck (never modify data they cannot fix)
    det = r["unc"] > 0
    assert ((r["lo2"] == r["blo"]) | ~det[:, None]).all()
    assert ((r["hi2"] == r["bhi"]) | ~det[:, None]).all()
    if codec.corrects_adjacent:
        # SEC-DAEC: adjacent *data* pairs are always corrected
        adj = np.array([len(p) == 2 and p[1] == p[0] + 1 and p[1] < 64
                        for p in patterns])
        assert r["restored"][adj].all()
    elif codec.detects >= 2:
        # plain SEC-DED-class: every double detected, none corrected
        assert det.all()


def test_dected_adjacent_data_pairs_all_corrected():
    patterns = [(p, p + 1) for p in range(63)]
    r = _sweep(CODECS["dected"], patterns)
    assert r["restored"].all() and (r["unc"] == 0).all()


def test_burst_adjacent_data_pairs_all_corrected():
    patterns = [(p, p + 1) for p in range(63)]
    r = _sweep(CODECS["burst"], patterns)
    assert r["restored"].all() and (r["unc"] == 0).all()


def test_dected_triple_bit_sampled():
    _assert_dected_triples(_sample_tuples(CODECS["dected"], 3, 256, seed=29))


@pytest.mark.slow
def test_dected_triple_bit_sweep():
    """A large deterministic sample of 3-bit patterns (TED: all flagged,
    none miscorrected — the d_min >= 6 guarantee)."""
    _assert_dected_triples(_sample_tuples(CODECS["dected"], 3, 4096,
                                          seed=31))


def _assert_dected_triples(patterns):
    r = _sweep(CODECS["dected"], patterns, width=2)
    assert (r["unc"] == 1).all()          # every triple flagged
    assert (r["corr"] == 0).all()         # never miscorrected
    # and the flagged word is left exactly as struck
    assert (r["lo2"] == r["blo"]).all() and (r["hi2"] == r["bhi"]).all()
    assert (r["ecc2"] == r["becc"]).all()


def test_parity_single_bit_sweep_exhaustive():
    """Parity detects every single data-bit flip ... """
    rows = 64
    lo, hi = _payload(rows, 8, seed=37)
    par = parity_encode_words(jnp.asarray(lo), jnp.asarray(hi), **_kw(rows))
    blo, bhi, _ = _apply_patterns(lo, hi, np.zeros((rows, 8), np.uint32),
                                  [(p,) for p in range(64)], 8)
    _, cnt = parity_check_words(jnp.asarray(blo), jnp.asarray(bhi), par,
                                **_kw(rows))
    assert (np.asarray(cnt)[:, 0] == 1).all()


def test_parity_double_bit_escape_exhaustive():
    """... and misses every in-word double — the SDC window the
    availability model charges PARITY_R for."""
    patterns = list(itertools.combinations(range(64), 2))
    rows = len(patterns)
    lo, hi = _payload(rows, 8, seed=41)
    par = parity_encode_words(jnp.asarray(lo), jnp.asarray(hi), **_kw(rows))
    blo, bhi, _ = _apply_patterns(lo, hi, np.zeros((rows, 8), np.uint32),
                                  patterns, 8)
    _, cnt = parity_check_words(jnp.asarray(blo), jnp.asarray(bhi), par,
                                **_kw(rows))
    assert (np.asarray(cnt)[:, 0] == 0).all()


# ================================================================== system
_STORM_TIERS = (Tier.PARITY_R, Tier.SECDED, Tier.BURST, Tier.DECTED)


@pytest.fixture(scope="module")
def storm_outcomes():
    """One adjacent-burst storm (6 bursts, distinct words) through a live
    MemoryDomain under each tier."""
    params = {"w": jnp.arange(4096, dtype=jnp.float32)}
    out = {}
    for tier in _STORM_TIERS:
        dom = MemoryDomain.protect(
            params, HRMPolicy(f"storm-{tier.value}", {}, default=tier))
        plan = InjectionPlan.adjacent_burst(
            np.random.default_rng(0), ops.words_per_tensor(params["w"]), 6)
        fixed, rep = dom.apply_plan("w", plan).scrub()
        clean = bool((np.asarray(fixed.payload["w"])
                      == np.asarray(params["w"])).all())
        out[tier] = (rep, clean)
    return out


def test_storm_silent_under_parity(storm_outcomes):
    rep, clean = storm_outcomes[Tier.PARITY_R]
    assert not clean                          # the SDC: data corrupt...
    assert sum(rep.corrected.values()) == 0   # ...and nothing noticed
    assert not rep.needs_recovery()


def test_storm_detected_but_stuck_under_secded(storm_outcomes):
    rep, clean = storm_outcomes[Tier.SECDED]
    assert not clean
    assert sum(rep.detected_uncorrectable.values()) == 6
    assert rep.needs_recovery()


@pytest.mark.parametrize("tier", [Tier.BURST, Tier.DECTED])
def test_storm_healed_under_strong_tiers(storm_outcomes, tier):
    rep, clean = storm_outcomes[tier]
    assert clean
    assert sum(rep.corrected.values()) == 6
    assert sum(rep.detected_uncorrectable.values()) == 0
    assert TIER_TABLE[tier].corrects_adjacent_double


def test_strike_mix_regression():
    """Pin the §8.3 strike mix: the dataclass default and the sampling
    helpers share DEFAULT_MULTI_BIT_FRACTION (the seed shipped 0.02 in
    ``ErrorModel`` but 0.0 in the helpers, so campaigns silently never
    exercised the multi-bit path)."""
    assert ErrorModel().multi_bit_fraction == DEFAULT_MULTI_BIT_FRACTION
    assert ErrorModel().adjacent_fraction == DEFAULT_ADJACENT_FRACTION
    import inspect
    sig = inspect.signature(InjectionPlan.sample)
    assert (sig.parameters["multi_bit_fraction"].default
            == DEFAULT_MULTI_BIT_FRACTION == 0.02)
    assert (sig.parameters["adjacent_fraction"].default
            == DEFAULT_ADJACENT_FRACTION == 0.5)
    # deterministic campaign mix for a pinned seed (vectorized sampler
    # stream): 2000 base strikes grow 35 second flips, 20 of them adjacent
    # to a same-word base flip
    plan = InjectionPlan.sample(np.random.default_rng(0), 10_000, 2000,
                                False)
    n = int((plan.word_idx >= 0).sum())
    w, b = plan.word_idx[:n], plan.bit_idx[:n]
    assert n - 2000 == 35
    adj = sum(
        1 for i in range(2000, n)
        if any(abs(int(m) - int(b[i])) == 1
               for m in b[:2000][w[:2000] == w[i]]))
    assert adj == 20
    # and every extra flip shares a word with (and differs from) a base
    for i in range(2000, n):
        mates = b[:2000][w[:2000] == w[i]]
        assert len(mates) and (mates != b[i]).any()


def test_adjacent_burst_plan_shape():
    plan = InjectionPlan.adjacent_burst(np.random.default_rng(1), 512, 5)
    n = int((plan.word_idx >= 0).sum())
    assert n == 10
    w, b = plan.word_idx[:n], plan.bit_idx[:n]
    for k in range(0, n, 2):
        assert w[k] == w[k + 1] and b[k + 1] == b[k] + 1


@pytest.mark.parametrize("tier,strike,outcome,rate", [
    (Tier.PARITY_R, "single", "detected", 1.0),
    (Tier.PARITY_R, "double_random", "silent", 1.0),
    (Tier.SECDED, "single", "corrected", 1.0),
    (Tier.SECDED, "double_random", "detected", 1.0),
    (Tier.SECDED, "double_adjacent", "detected", 1.0),
    (Tier.BURST, "single", "corrected", 1.0),
    (Tier.BURST, "double_adjacent", "corrected", 1.0),
    (Tier.DECTED, "double_random", "corrected", 1.0),
    (Tier.DECTED, "double_adjacent", "corrected", 1.0),
])
def test_measured_rates_match_code_theory(tier, strike, outcome, rate):
    """The kernel-measured outcome rates (eccmeasure) reproduce what the
    sweeps above prove — the bridge that justifies feeding measured rates
    into the availability model."""
    r = measure_class_rates(tier, strike, n_events=64)
    assert getattr(r, outcome) == rate
    assert r.corrected + r.detected + r.silent == pytest.approx(1.0)


# ================================================================ property
_DTYPES = ["float32", "bfloat16", "float16", "int32", "int8"]


@settings(max_examples=25, deadline=None)
@given(dims=st.lists(st.integers(1, 37), min_size=1, max_size=3),
       dtype=st.sampled_from(_DTYPES), seed=st.integers(0, 2 ** 16))
def test_pack_unpack_roundtrip_property(dims, dtype, seed):
    """pack_words/unpack_words are exact inverses for any shape (ragged
    tails included) and dtype."""
    rng = np.random.default_rng(seed)
    dt = getattr(jnp, dtype)
    if jnp.issubdtype(dt, jnp.integer):
        info = jnp.iinfo(dt)
        x = jnp.asarray(rng.integers(info.min, info.max + 1, size=dims),
                        dtype=dt)
    else:
        x = jnp.asarray(rng.standard_normal(dims) * 7, dtype=dt)
    p = ops.pack_words(x)
    assert p.lo.shape == p.hi.shape and p.lo.shape[1] == LANES
    assert p.lo.dtype == p.hi.dtype == jnp.uint32
    x2 = ops.unpack_words(p, x.shape, x.dtype)
    assert x2.shape == x.shape and x2.dtype == x.dtype
    assert (np.asarray(x2) == np.asarray(x)).all()


@settings(max_examples=25, deadline=None)
@given(n=st.integers(1, 400), seed=st.integers(0, 2 ** 16))
def test_pack_is_stable_under_repacking(n, seed):
    """Packing the unpacked tensor reproduces the packed words exactly —
    padding included (the linear-code contract scrubbing relies on)."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(0, 256, size=n, dtype=np.uint8))
    p = ops.pack_words(x)
    p2 = ops.pack_words(ops.unpack_words(p, x.shape, x.dtype))
    assert (np.asarray(p2.lo) == np.asarray(p.lo)).all()
    assert (np.asarray(p2.hi) == np.asarray(p.hi)).all()
