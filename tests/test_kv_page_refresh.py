"""Write-path encode of the KV pages a serving step wrote: the memory
domain's ``refresh_pages`` against the full encode, bit for bit, under
every tier, and the engine's page-only refresh — the sidecar after every
iteration, the served tokens, and the full-refresh fallbacks (a page that
is not whole sidecar rows, a KV strike)."""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.serve.engine as engine_mod
from repro.configs import get_tiny
from repro.core import MemoryDomain, Tier
from repro.core.trace import BoundStrike
from repro.models import init_params
from repro.runtime.serve_loop import serve_batch
from repro.serve import OnlineEngine, Request
from repro.serve.engine import kv_policy

# 2 KV heads of 64 in bf16: a page of 8 tokens is 2 KiB, one packed row
CFG = get_tiny("llama3-8b").replace(d_head=64)
TIERS = [Tier.PARITY_R, Tier.SECDED, Tier.DECTED, Tier.BURST, Tier.MIRROR]


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _pools(shape, seed):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return {"kv_cache": {
        "k": jax.random.normal(k1, shape, jnp.float32).astype(jnp.bfloat16),
        "v": jax.random.normal(k2, shape, jnp.float32).astype(jnp.bfloat16)}}


def _full_sidecar(dom):
    return dom.refresh().sidecar


def _assert_sidecar_equal(got, want):
    assert got.keys() == want.keys()
    for tier in want:
        assert got[tier].keys() == want[tier].keys()
        for name in want[tier]:
            np.testing.assert_array_equal(np.asarray(got[tier][name]),
                                          np.asarray(want[tier][name]),
                                          err_msg=f"{tier}/{name}")


# ------------------------------------------------- the domain verb
@pytest.mark.parametrize("tier", TIERS, ids=lambda t: t.value)
@pytest.mark.parametrize("page_tokens", [8, 16])
def test_refresh_pages_matches_full_encode(tier, page_tokens):
    """Write three pages of both pools (one listed twice): encoding those
    pages alone gives the sidecar of a full encode. A fourth page changed
    but not listed keeps its old code rows, so only the listed pages were
    encoded. The spent domain's sidecar was updated in place."""
    shape = (2, 9, page_tokens, 2, 64)

    def protect():
        return MemoryDomain.protect(_pools(shape, 0), kv_policy(tier))

    dom = protect()
    assert dom.spec.slices_aligned()
    fresh = _pools((2, 4, page_tokens, 2, 64), 1)["kv_cache"]

    def write(pages):
        return {"kv_cache": {
            n: dom.payload["kv_cache"][n].at[:, pages].set(
                x[:, :len(pages)]) for n, x in fresh.items()}}

    listed = write(np.array([0, 7, 3]))
    got = dom.refresh_pages(np.array([0, 7, 3, 0], np.int32), listed)
    assert got.payload is listed
    _assert_sidecar_equal(got.sidecar, _full_sidecar(got))
    assert all(b.is_deleted() for b in jax.tree.leaves(dom.sidecar))
    unlisted = protect().refresh_pages(np.array([0, 7, 3], np.int32),
                                       write(np.array([0, 7, 3, 5])))
    _assert_sidecar_equal(unlisted.sidecar, got.sidecar)


@pytest.mark.parametrize("tier", TIERS, ids=lambda t: t.value)
def test_refresh_paths_matches_full_encode(tier):
    """Write pool ``k`` and re-encode it alone (``refresh(paths=)``): the
    sidecar is a full encode's, bit for bit. A changed pool ``v`` that
    is not listed keeps its old code rows, so only ``k`` was encoded."""
    shape = (2, 9, 8, 2, 64)
    dom = MemoryDomain.protect(_pools(shape, 0), kv_policy(tier))
    fresh = _pools(shape, 1)["kv_cache"]
    old_v = dom.payload["kv_cache"]["v"]
    got = dom.refresh({"kv_cache": {"k": fresh["k"], "v": old_v}},
                      paths=["kv_cache/k"])
    _assert_sidecar_equal(got.sidecar, _full_sidecar(got))
    unlisted = dom.refresh({"kv_cache": fresh}, paths=["kv_cache/k"])
    _assert_sidecar_equal(unlisted.sidecar, got.sidecar)


def test_refresh_pages_unaligned_is_full_refresh():
    """A page of 512 bytes is a quarter of a packed row: the verb refuses
    it, leaving the sidecar as it was, and the caller's full refresh is
    the full encode of the state."""
    shape = (2, 5, 8, 2, 16)
    dom = MemoryDomain.protect(_pools(shape, 0), kv_policy(Tier.PARITY_R))
    assert not dom.spec.slices_aligned()
    state = _pools(shape, 2)
    with pytest.raises(ValueError, match="whole number of packed rows"):
        dom.refresh_pages(np.array([1], np.int32), state)
    _assert_sidecar_equal(dom.sidecar, _full_sidecar(dom))
    got = dom.refresh(state)
    _assert_sidecar_equal(got.sidecar, _full_sidecar(got))
    with pytest.raises(IndexError):
        MemoryDomain.protect(_pools((2, 5, 8, 2, 64), 0),
                             kv_policy(Tier.PARITY_R)).refresh_pages([5])


# ------------------------------------------------- the engine
def _trace(prompts, max_new):
    return [Request(rid=i, arrival=0.0, prompt=p, max_new=max_new)
            for i, p in enumerate(prompts)]


def _prompts(b, s0, seed=1):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (b, s0),
                                         0, CFG.vocab_size), np.int32)


def _engine(params, kv_tier, page_size, cfg=CFG, **kw):
    return OnlineEngine(cfg, params, slots=3, page_size=page_size,
                        max_prompt_len=20, max_new_cap=14,
                        max_prefills_per_step=2, kv_tier=kv_tier, seed=0,
                        **kw)


@contextlib.contextmanager
def _refresh_spans(monkeypatch):
    """Record the ``serve.kv_refresh`` spans' attributes, in order."""
    seen = []

    @contextlib.contextmanager
    def annotate(name, **attrs):
        if name == "serve.kv_refresh":
            seen.append(attrs)
        yield

    monkeypatch.setattr(engine_mod, "TraceAnnotation", annotate)
    yield seen


@pytest.mark.parametrize("kv_tier", [Tier.PARITY_R, Tier.SECDED],
                         ids=lambda t: t.value)
def test_engine_page_refresh_bit_identical(params, kv_tier, monkeypatch):
    """Pages of 16 tokens (two packed rows): after every iteration the KV
    sidecar is the full encode of the pools, the tokens are the
    contiguous oracle's, and no iteration fell back to the full
    refresh."""
    b, s0, new = 4, 20, 14
    prompts = _prompts(b, s0)
    oracle, _ = serve_batch(CFG, params, jnp.asarray(prompts), new)
    eng = _engine(params, kv_tier, 16)
    assert eng.kv_domain.spec.slices_aligned()
    step = eng._iteration
    checked = []

    def iteration(*args):
        now = step(*args)
        _assert_sidecar_equal(eng.kv_domain.sidecar,
                              _full_sidecar(eng.kv_domain))
        checked.append(args[-1])
        return now

    monkeypatch.setattr(eng, "_iteration", iteration)
    with _refresh_spans(monkeypatch) as spans:
        rep, resp = eng.run(_trace(prompts, new))
    np.testing.assert_array_equal(np.asarray(oracle),
                                  np.stack([resp[i] for i in range(b)]))
    c = rep.counters
    assert len(checked) == len(spans) == c["decode_steps"] > 0
    assert c["kv_full_refreshes"] == 0
    assert all(s["full"] == 0 for s in spans)
    # 2 prompt pages per prefill, and one page for each of the 3 slots
    # per decode
    assert c["kv_pages_encoded"] == sum(s["pages"] for s in spans) == \
        b * 2 + 3 * c["decode_steps"]
    assert c["kv_pages_encoded"] < c["kv_pages_checked"]


def test_engine_unaligned_page_full_refresh(params, monkeypatch):
    """Heads of 16: a page of 16 tokens is 1 KiB, half a packed row, so
    every iteration re-encodes the whole pool."""
    cfg = get_tiny("llama3-8b")
    small = init_params(jax.random.PRNGKey(0), cfg)
    eng = _engine(small, Tier.PARITY_R, 16, cfg=cfg)
    assert not eng.kv_domain.spec.slices_aligned()
    with _refresh_spans(monkeypatch) as spans:
        rep, _ = eng.run(_trace(_prompts(3, 20), 6))
    c = rep.counters
    iters = c["decode_steps"]
    assert c["kv_full_refreshes"] == iters == len(spans) > 0
    assert all(s["full"] == 1 and s["pages"] == eng.cache.n_pages
               for s in spans)
    assert c["kv_pages_encoded"] == iters * eng.cache.n_pages


def test_kv_strike_falls_back_to_full_refresh_once(params, monkeypatch):
    """One single-bit strike into a prompt page under Par+R with no peer
    recovery: the next check counts it, that iteration's refresh is the
    full one (which re-encodes the struck word), so no later check counts
    it again, and the iterations after go back to page-only."""
    strike_at = 3
    eng = _engine(params, Tier.PARITY_R, 16)
    step = eng._iteration
    words_per_page = 16 * 2 * 64 * 2 // 8

    def iteration(router, counters, storm, now, it):
        if it == strike_at:
            page = int(eng.cache.table[0, 0])        # slot 0's prompt page
            storm.append((0.0, BoundStrike(
                t=0.0, domain="kv_cache", path="kv_cache/k",
                word=page * words_per_page + 5, bits=(9,), hard=False,
                dimm=0)))
        return step(router, counters, storm, now, it)

    monkeypatch.setattr(eng, "_iteration", iteration)
    with _refresh_spans(monkeypatch) as spans:
        rep, _ = eng.run(_trace(_prompts(3, 20, seed=4), 14))
    c = rep.counters
    assert c["injected_kv"] == 1
    assert c["kv_detected"] == 1
    assert c["kv_full_refreshes"] == 1
    full = [s["full"] for s in spans]
    assert full == [0] * (strike_at + 1) + [1] + \
        [0] * (len(spans) - strike_at - 2)
    _assert_sidecar_equal(eng.kv_domain.sidecar,
                          _full_sidecar(eng.kv_domain))
