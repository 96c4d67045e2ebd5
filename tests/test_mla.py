"""Latent attention (DeepSeek-V2) on the serving path, at a CPU size,
against the benchmark's plain reference (``perfbench/reference/mla_moe``)
on seeded weights: prefill then paged decode against the reference's full
forward, the absorbed decode against the expanded form, the expert share
against the uncut layer, the latent pools' sidecar and strikes, and the
decode's count of cached tokens read."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_tiny
from repro.configs.base import MoEConfig
from repro.core import Tier
from repro.core.trace import BoundStrike
from repro.models import attention as attn
from repro.models import init_params
from repro.models import mlp as mlp_mod
from repro.models.common import dtype_of
from repro.serve import OnlineEngine, Request
from repro.serve import engine as engine_mod
from repro.serve.paged_kv import pool_shapes

PERFBENCH = Path(__file__).resolve().parents[1] / "perfbench"
PAGE = 16


@pytest.fixture(scope="module")
def bench():
    sys.path.insert(0, str(PERFBENCH))
    import bench as b
    if b.HERE / "tests" / "data" not in b.SEARCH:
        b.SEARCH.append(b.HERE / "tests" / "data")
    return b


@pytest.fixture(scope="module")
def model(bench):
    """The CPU-size configuration (4 of 8 routed experts held, a leading
    dense layer), its reference weights and the program's tree of them."""
    ref = bench.load_module("reference", "mla_moe")
    runner = bench.load_module("runners", "serve_waves_mla")
    cfg = bench.load_json("configs", "dsv2_tiny")
    d = ref.dims(cfg)
    w = ref.make_weights(cfg, jax.random.PRNGKey(3))
    return {"ref": ref, "cfg": cfg, "d": d, "w": w,
            "model": runner.model_config(cfg),
            "params": runner.program_weights(w, d.n_dense)}


def _ref_logits(m, tokens, out_pos):
    return np.asarray(m["ref"].forward_logits(
        m["w"], jnp.asarray(tokens), jnp.asarray(out_pos), m["d"]))


def _paged_logits(model, cfg, toks, T, n):
    """Prefill each row's first T tokens, then decode n tokens
    teacher-forced, two slots whose pages interleave; the decode's
    logits (2, n, V) and the prefills' first tokens."""
    params, cdt = model["params"], dtype_of(cfg.compute_dtype)
    pools = {k: jnp.zeros(s, cdt)
             for k, s in pool_shapes(cfg, 12, PAGE).items()}
    table = np.array([[1, 3, 5], [2, 4, 6]], np.int32)
    prefill = jax.jit(engine_mod._make_prefill_write(cfg, PAGE))
    firsts = []
    sb = -(-T // PAGE) * PAGE
    for s in range(2):
        tk = np.zeros((1, sb), np.int32)
        tk[0, :T] = toks[s, :T]
        a, b, first, ok = prefill(params, pools["c_kv"], pools["k_pe"],
                                  jnp.asarray(tk), jnp.int32(T),
                                  jnp.asarray(table[s, :sb // PAGE]))
        pools = {"c_kv": a, "k_pe": b}
        assert bool(ok)
        firsts.append(int(first))
    step = jax.jit(engine_mod._paged_logits_mla(cfg, PAGE))
    got = []
    for j in range(n):
        pools, logits = step(params, pools, jnp.asarray(table),
                             jnp.asarray(toks[:, T + j]),
                             jnp.full(2, T + j, jnp.int32))
        got.append(np.asarray(logits, np.float32))
    return np.stack(got, 1), firsts


def test_prefill_then_paged_decode_match_reference(model):
    """A 40-token prompt over pages of 16 (the third page part full),
    prefilled, then 8 tokens decoded through the paged latent pools
    (teacher-forced). Computed in float32, every logit lies within 2e-3
    of the float32 reference's full forward (the absorbed and expanded
    orders of summation; logits of unit spread), where the reference with
    its latent cache in float8 misses by more than 0.01 on average. In
    bfloat16, as served, the mean error is under 0.1: a few bfloat16
    steps of the residual stream, and the rare token whose top-k routing
    flips on rounding (an expert's whole part, up to ~1.5 at this size).
    The prefill's first token is the reference's best."""
    T, n = 40, 8
    toks = np.asarray(jax.random.randint(
        jax.random.PRNGKey(7), (2, T + n), 0, model["model"].vocab_size),
        np.int32)
    want = [_ref_logits(model, toks[s], T + np.arange(n)) for s in range(2)]
    for cdt, bound in (("float32", None), ("bfloat16", 0.1)):
        got, firsts = _paged_logits(
            model, model["model"].replace(compute_dtype=cdt), toks, T, n)
        for s in range(2):
            err = np.abs(got[s] - want[s])
            if bound is None:
                assert err.max() < 2e-3, err.max()
                low = np.asarray(model["ref"].forward_logits(
                    model["w"], jnp.asarray(toks[s]),
                    jnp.asarray(T + np.arange(n)), model["d"], True))
                assert np.abs(low - want[s]).mean() > 0.01
                assert firsts[s] == int(np.argmax(_ref_logits(
                    model, toks[s, :T], np.array([T - 1]))[0]))
            else:
                assert err.mean() < bound, err.mean()


def test_absorbed_decode_equals_expanded_form(model):
    """One query against a cached prefix: the absorbed form (query through
    the key half of wkv_b, weighted latents through its value half) is the
    expanded form's mathematics in another order: within 2e-5 in float32
    (rounding of sums of ~100 terms); in bfloat16 within 0.05 and 2% of
    outputs up to ~6, since each form rounds different intermediates to
    bfloat16 (2^-8 each: the absorbed query and weighted latents against
    the expanded keys and values)."""
    for cdt, tol in (("float32", (2e-5, 0)), ("bfloat16", (5e-2, 2e-2))):
        cfg = model["model"].replace(compute_dtype=cdt)
        lp = jax.tree.map(lambda a: a[0], model["params"]["blocks"]["attn"])
        S = 37
        x = jax.random.normal(jax.random.PRNGKey(1), (2, S, cfg.d_model),
                              jnp.float32).astype(dtype_of(cdt))
        full, (c_kv, k_pe) = jax.jit(
            lambda lp, x, cfg=cfg: attn.mla_apply(lp, x, cfg))(lp, x)
        c_cache = jnp.zeros((2, 48, cfg.mla.kv_lora_rank),
                            c_kv.dtype).at[:, :S - 1].set(c_kv[:, :S - 1])
        pe_cache = jnp.zeros((2, 48, cfg.mla.qk_rope_head_dim),
                             k_pe.dtype).at[:, :S - 1].set(k_pe[:, :S - 1])
        y, c2, _ = jax.jit(lambda *a, cfg=cfg: attn.mla_decode(*a, cfg))(
            lp, x[:, S - 1:], c_cache, pe_cache, jnp.int32(S - 1))
        np.testing.assert_allclose(np.asarray(y[:, 0], np.float32),
                                   np.asarray(full[:, -1], np.float32),
                                   atol=tol[0], rtol=tol[1])
        np.testing.assert_array_equal(np.asarray(c2[:, S - 1]),
                                      np.asarray(c_kv[:, S - 1]))


def _moe_cfg(cfg, **kw):
    return cfg.replace(compute_dtype="float32", moe=MoEConfig(**{
        **{f: getattr(cfg.moe, f) for f in MoEConfig.__dataclass_fields__},
        **kw}))


def test_expert_shares_sum_to_uncut_layer(model, bench):
    """The layer cut over 4 chips of 2 experts each: the shares' outputs,
    the shared experts counted once, add up to the uncut reference layer
    (every expert held), in float32 to rounding."""
    ref = model["ref"]
    cfg = bench.load_json("configs", "dsv2_tiny")
    full = dict(cfg, n_routed_experts=8, first_held_expert=0)
    d = ref.dims(full)
    w = ref.make_weights(full, jax.random.PRNGKey(11))
    mw = jax.tree.map(lambda a: a[0].astype(jnp.float32), w["moe"])
    lw = jax.tree.map(lambda a: a[d.n_dense], w["layers"])
    x = jax.random.normal(jax.random.PRNGKey(2), (24, d.D), jnp.float32)
    want = ref._moe(x, lw, mw, d) - x
    h = ref._rms(x, lw["mlp_norm"].astype(jnp.float32), d.eps)
    prog = {"router": mw["router"],
            "shared": {"wi": mw["shared_wi"], "wg": mw["shared_wg"],
                       "wo": mw["shared_wo"]}}
    base = _moe_cfg(model["model"], capacity_factor=8 / 3)

    @jax.jit
    def shares(mw, h):
        total = 0.0
        for first in range(0, 8, 2):
            c = _moe_cfg(base, first_held=first, n_held=2)
            p = dict(prog, **{k: mw[k][first:first + 2]
                              for k in ("wi", "wg", "wo")})
            total = total + mlp_mod.moe_apply(p, h[None], c)[0][0]
        return total - 3 * mlp_mod.mlp_apply(prog["shared"], h, base)

    got = shares(mw, h)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


def _dispatch_before_share(p, xt, cfg):
    """The grouped dispatch as it was before the expert share (every
    expert held, top-k renormalized), kept as the oracle."""
    moe = cfg.moe
    T, D = xt.shape
    E, K = moe.n_experts, moe.top_k
    cdt = dtype_of(cfg.compute_dtype)
    xt = xt.astype(cdt)
    gates = jax.nn.softmax(xt.astype(jnp.float32) @ p["router"], axis=-1)
    topw, tope = jax.lax.top_k(gates, K)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    C = mlp_mod._capacity(T, moe)
    fe, fw = tope.reshape(-1), topw.reshape(-1)
    ftok = jnp.arange(T * K) // K
    order = jnp.argsort(fe, stable=True)
    fe_s, fw_s, ftok_s = fe[order], fw[order], ftok[order]
    starts = jnp.searchsorted(fe_s, jnp.arange(E))
    slot = jnp.arange(T * K) - starts[fe_s]
    keep = slot < C
    row = jnp.where(keep, fe_s, E)
    col = jnp.where(keep, slot, 0)
    buf = jnp.zeros((E + 1, C, D), cdt).at[row, col].add(xt[ftok_s])[:E]
    h = jnp.einsum("ecd,edf->ecf", buf, p["wi"].astype(cdt))
    h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", buf, p["wg"].astype(cdt))
    out = jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(cdt))
    gathered = out[row, col] * jnp.where(keep, fw_s, 0.0)[:, None].astype(cdt)
    return jnp.zeros((T, D), cdt).at[ftok_s].add(gathered)


def test_all_experts_held_is_granites_layer_bit_for_bit():
    """Granite's layer (every expert held, the default) gives the outputs
    of the dispatch before the share, bit for bit, in bfloat16, dropping
    tokens or not."""
    for cf in (5.0, 1.0):
        cfg = get_tiny("granite-moe-3b-a800m")
        cfg = cfg.replace(param_dtype="bfloat16", moe=MoEConfig(
            n_experts=8, top_k=2, d_expert=64, capacity_factor=cf))
        p = init_params(jax.random.PRNGKey(4), cfg)["blocks"]["moe"]
        p = jax.tree.map(lambda a: a[0], p)
        x = jax.random.normal(jax.random.PRNGKey(5), (3, 40, cfg.d_model),
                              jnp.float32).astype(jnp.bfloat16)
        y, _ = jax.jit(lambda p, x: mlp_mod.moe_apply(p, x, cfg))(p, x)
        held = cfg.replace(moe=MoEConfig(n_experts=8, top_k=2, d_expert=64,
                                         capacity_factor=cf, n_held=8))
        y2, _ = jax.jit(lambda p, x: mlp_mod.moe_apply(p, x, held))(p, x)
        want = jax.jit(lambda p, x: _dispatch_before_share(
            p, x.reshape(-1, cfg.d_model), cfg))(p, x)
        np.testing.assert_array_equal(np.asarray(y).reshape(want.shape),
                                      np.asarray(want))
        np.testing.assert_array_equal(np.asarray(y2), np.asarray(y))


def _trace(cfg, lens, new, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, arrival=0.0,
                    prompt=rng.integers(0, cfg.vocab_size, n,
                                        dtype=np.int32), max_new=new)
            for i, n in enumerate(lens)]


def _engine(model, **kw):
    return OnlineEngine(model["model"], model["params"], slots=3,
                        page_size=PAGE, max_prompt_len=48, max_new_cap=8,
                        kv_tier=Tier.PARITY_R, seed=0, **kw)


def _sidecar_equal(dom):
    want = dom.refresh().sidecar
    for tier in want:
        for name in want[tier]:
            np.testing.assert_array_equal(np.asarray(dom.sidecar[tier][name]),
                                          np.asarray(want[tier][name]))


def test_latent_pages_refresh_strike_and_recovery(model, monkeypatch):
    """The latent pools are their own region, under the KV tier. After
    every iteration the page-only refresh leaves the sidecar a full
    re-encode would, bit for bit; a parity strike on a latent page is
    detected, recovered from the peer replica's copy, and the served
    tokens are those of a clean run; the decode's ``ctx_tokens`` sum to
    the slots' cached lengths."""
    lens, new = (40, 23, 33), 7
    clean, clean_resp = _engine(model).run(_trace(model["model"], lens,
                                                  new))
    eng = _engine(model, peer_recovery=True)
    dom = eng.kv_domain
    assert {s.path: s.region for s in dom.spec.leaves} == {
        "kv_cache/c_kv": "kv_cache/latent",
        "kv_cache/k_pe": "kv_cache/latent"}
    assert dom.spec.slices_aligned()
    assert "kv_cache/c_kv" in eng.describe()
    step = eng._iteration
    words_per_page = PAGE * model["model"].mla.kv_lora_rank * 2 // 8

    def iteration(router, counters, storm, now, it):
        if it == 3:
            page = int(eng.cache.table[0, 0])        # slot 0's prompt page
            storm.append((0.0, BoundStrike(
                t=0.0, domain="kv_cache", path="kv_cache/c_kv",
                word=page * words_per_page + 5, bits=(3,), hard=False,
                dimm=0)))
        now = step(router, counters, storm, now, it)
        if it != 3:                      # the strike lands after refresh
            _sidecar_equal(eng.kv_domain)
        return now

    monkeypatch.setattr(eng, "_iteration", iteration)
    rep, resp = eng.run(_trace(model["model"], lens, new))
    c = rep.counters
    assert c["injected_kv"] == c["kv_detected"] == 1
    assert c["peer_recovery_events"] == 1
    assert resp == clean_resp
    # each request decodes new - 1 tokens, at positions prompt .. prompt
    # + new - 2, reading that position plus one cached tokens
    want = sum(sum(p + 1 + j for j in range(new - 1)) for p in lens)
    assert c["decode_ctx_tokens"] == clean.counters["decode_ctx_tokens"] \
        == want
    assert c["kv_full_refreshes"] == 1
