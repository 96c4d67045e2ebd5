"""Measured per-tier error outcomes — driven through the real Pallas
kernels, not the calibrated constants.

For each tier and each strike class (single bit, random double,
adjacent-double burst) this module injects errors into random payload
words, runs the tier's actual encode/scrub kernels, and classifies every
event as

  corrected   scrub restored the exact clean bits
  detected    scrub flagged the word detected-uncorrectable (software
              recovery / machine-check territory)
  silent      the data stays (or ends up) wrong with no flag — SDC

The per-class rates are *conditional* (measured with one event per packed
row so outcomes attribute exactly); ``measured_outcome_rates`` mixes them
analytically with the incident-error composition (multi-bit fraction,
adjacent fraction), which is how rare multi-bit classes get measured with
full statistical power instead of waiting for a 0.2% event to sample.

``launch/explore.py`` feeds these rates into
``availability.evaluate_availability(..., tier_rates=...)`` for the
strong-tier design points (DEC-TED / BURST), turning their Fig.5 rows
from calibrated into measured. For PARITY_R / SECDED the measured rates
reproduce the calibrated branch exactly (singles corrected/detected,
in-word doubles silent/detected), which ``tests/ecc_conformance.py``
asserts.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, Iterable, Mapping, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.domain import TIER_CODES
from repro.core.tiers import Tier
from repro.kernels.ops import LANES

STRIKE_CLASSES = ("single", "double_random", "double_adjacent")


@dataclass(frozen=True)
class TierOutcomeRates:
    """P(outcome | incident error event) for one tier."""
    corrected: float
    detected: float
    silent: float

    def mix(self, other: "TierOutcomeRates", w_other: float
            ) -> "TierOutcomeRates":
        w = 1.0 - w_other
        return TierOutcomeRates(
            self.corrected * w + other.corrected * w_other,
            self.detected * w + other.detected * w_other,
            self.silent * w + other.silent * w_other)


def _strike(rng: np.random.Generator, rows: int, strike: str
            ) -> Tuple[np.ndarray, np.ndarray]:
    """One event per row: (word-in-row, list-of-bits) per event."""
    words = rng.integers(0, LANES, size=rows)
    if strike == "single":
        bits = rng.integers(0, 64, size=rows)[:, None]
    elif strike == "double_adjacent":
        b = rng.integers(0, 63, size=rows)
        bits = np.stack([b, b + 1], axis=1)
    elif strike == "double_random":
        b1 = rng.integers(0, 64, size=rows)
        b2 = rng.integers(0, 63, size=rows)
        b2 = np.where(b2 >= b1, b2 + 1, b2)
        bits = np.stack([b1, b2], axis=1)
    else:
        raise ValueError(strike)
    return words, bits


def _flip(lo: np.ndarray, hi: np.ndarray, words: np.ndarray,
          bits: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    lo, hi = lo.copy(), hi.copy()
    rows = np.arange(lo.shape[0])
    for k in range(bits.shape[1]):
        b = bits[:, k]
        is_lo = b < 32
        lo[rows, words] ^= np.where(is_lo, np.uint32(1) << b,
                                    0).astype(np.uint32)
        hi[rows, words] ^= np.where(is_lo, 0, np.uint32(1)
                                    << (b - 32)).astype(np.uint32)
    return lo, hi


@functools.lru_cache(maxsize=None)
def measure_class_rates(tier: Tier, strike: str, n_events: int = 128,
                        seed: int = 0) -> TierOutcomeRates:
    """Conditional outcome rates for one tier under one strike class,
    measured through the tier's real kernels (one event per packed row)."""
    rng = np.random.default_rng((seed, STRIKE_CLASSES.index(strike)))
    rows = n_events
    lo = rng.integers(0, 2 ** 32, (rows, LANES), dtype=np.uint32)
    hi = rng.integers(0, 2 ** 32, (rows, LANES), dtype=np.uint32)
    jlo, jhi = jnp.asarray(lo), jnp.asarray(hi)
    words, bits = _strike(rng, rows, strike)
    blo, bhi = _flip(lo, hi, words, bits)
    jblo, jbhi = jnp.asarray(blo), jnp.asarray(bhi)
    kw = dict(block_rows=rows)

    if tier is Tier.NONE:
        return TierOutcomeRates(0.0, 0.0, 1.0)
    code = TIER_CODES[tier]
    stored = code.encode(jlo, jhi, **kw)

    if code.buf == "par" and not code.copy:
        _, cnt = code.scrub(jblo, jbhi, stored, **kw)
        detected = np.asarray(cnt)[:, 0] > 0
        # parity never repairs: undetected events are consumed corrupt
        n_det = int(detected.sum())
        return TierOutcomeRates(0.0, n_det / rows, (rows - n_det) / rows)

    if code.copy:
        err, _ = code.scrub(jblo, jbhi, stored, **kw)
        bitsmask = (np.asarray(err)[..., :, None]
                    >> np.arange(8, dtype=np.uint32)) & 1
        mask = bitsmask.reshape(lo.shape).astype(bool)
        lo2 = np.where(mask, lo, blo)
        hi2 = np.where(mask, hi, bhi)
        good = ((lo2 == lo) & (hi2 == hi)).all(axis=1)
        n_c = int(good.sum())
        return TierOutcomeRates(n_c / rows, 0.0, (rows - n_c) / rows)

    lo2, hi2, _, _, unc = code.scrub(jblo, jbhi, stored, **kw)
    detected = np.asarray(unc)[:, 0] > 0
    clean = ((np.asarray(lo2) == lo) & (np.asarray(hi2) == hi)).all(axis=1)
    corrected = clean & ~detected
    silent = ~clean & ~detected
    return TierOutcomeRates(int(corrected.sum()) / rows,
                            int(detected.sum()) / rows,
                            int(silent.sum()) / rows)


@functools.lru_cache(maxsize=None)
def measured_outcome_rates(tier: Tier, multi_bit_fraction: float,
                           adjacent_fraction: float, n_events: int = 128,
                           seed: int = 0) -> TierOutcomeRates:
    """Outcome rates under the incident-error mix: measured per class,
    mixed analytically (importance stratification over the rare classes)."""
    single = measure_class_rates(tier, "single", n_events, seed)
    rand2 = measure_class_rates(tier, "double_random", n_events, seed)
    adj2 = measure_class_rates(tier, "double_adjacent", n_events, seed)
    multi = rand2.mix(adj2, adjacent_fraction)
    return single.mix(multi, multi_bit_fraction)


def measured_tier_rates(tiers: Iterable[Tier], multi_bit_fraction: float,
                        adjacent_fraction: float, n_events: int = 128,
                        seed: int = 0) -> Dict[Tier, TierOutcomeRates]:
    return {t: measured_outcome_rates(t, multi_bit_fraction,
                                      adjacent_fraction, n_events, seed)
            for t in set(tiers)}
