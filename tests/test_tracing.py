"""Tracing of the serving engine and the scrubbed PageRank: device
programs lowered under names that say what they do, host spans per
iteration and phase in a profiler capture, the KV page counters, and the
served wall clock that includes the protection passes."""
import glob
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_tiny
from repro.core import MemoryDomain, Tier, detect_recover_l
from repro.core.domain import _compiled_encode, _compiled_scrub
from repro.graph import graph_state, pagerank_scrubbed, powerlaw_graph
from repro.graph.pagerank import _region_paths
from repro.models import init_params
from repro.serve import OnlineEngine, Request

# 2 KV heads of 64 in bf16: a page of 8 tokens is 2 KiB, one packed row,
# so the engine re-encodes only the pages it wrote
CFG = get_tiny("llama3-8b").replace(d_head=64)
PAGE = 8
SLOTS = 2
SERVE_SPANS = ("serve.kv_check", "serve.params_scrub", "serve.prefill",
               "serve.decode", "serve.kv_refresh", "serve.inject")
GRAPH_SPANS = ("graph.step", "graph.rank_encode", "graph.scrub_slice")


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _wave(n, prompt_lens=(8, 13), max_new=(4, 6), seed=1):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, arrival=0.0,
                    prompt=rng.integers(0, CFG.vocab_size,
                                        prompt_lens[i % len(prompt_lens)],
                                        dtype=np.int32),
                    max_new=max_new[i % len(max_new)]) for i in range(n)]


def _engine(params, kv_tier=Tier.PARITY_R, **kw):
    kw.setdefault("policy", detect_recover_l())
    kw.setdefault("scrub_every", 2)
    return OnlineEngine(CFG, params, slots=SLOTS, page_size=PAGE,
                        max_prompt_len=16, max_new_cap=8, kv_tier=kv_tier,
                        seed=0, **kw)


def _serve(eng, trace, storm_errors=0):
    eng.sched.completed.clear()
    return eng.run(trace, storm_errors=storm_errors)


@pytest.fixture(scope="module")
def graph():
    g = powerlaw_graph(300, avg_degree=5, seed=4)
    return g, graph_state(g, node_block=128, edge_tile=128)


# ------------------------------------------------ (a) program names
def _module_name(lowered) -> str:
    head = lowered.as_text().split("\n", 1)[0]
    return head.split("@", 1)[1].split()[0]


def _lower_serve(params, which):
    eng = _engine(params)
    if which == "serve_decode":
        tokens, pos = np.zeros(2, np.int32), np.zeros(2, np.int32)
        return eng._decode.lower(eng._params(), eng.cache.pools["k"],
                                 eng.cache.pools["v"],
                                 eng.cache.device_table(),
                                 jnp.asarray(tokens), jnp.asarray(pos))
    if which == "serve_prefill":
        return eng._prefill.lower(eng._params(), eng.cache.pools["k"],
                                  eng.cache.pools["v"],
                                  jnp.zeros((1, PAGE), jnp.int32),
                                  jnp.int32(5), jnp.zeros(1, jnp.int32))
    dom = eng.param_domain if which == "params_scrub" else eng.kv_domain
    leaves = tuple(dom._leaves())
    if which == "cache_encode":
        return _compiled_encode(dom.spec, dom.spec.row_set(None)).lower(
            leaves)
    if which == "cache_encode_pages":
        assert dom.spec.slices_aligned()
        return _compiled_encode(dom.spec, dom.spec.row_set(
            None, pages=True)).lower(leaves, dom.sidecar,
                                     jnp.zeros(SLOTS, jnp.int32))
    return _compiled_scrub(dom.spec, dom.spec.row_set(None)).lower(
        leaves, dom.sidecar)


def _lower_graph(graph, which):
    g, state = graph
    dom = MemoryDomain.protect({"graph": state}, detect_recover_l())
    leaves = tuple(dom._leaves())
    if which == "graph_encode_rows":
        rows = dom.spec.row_set(dom.spec.paths_key(["graph/rank/rank"]))
        return _compiled_encode(dom.spec, rows).lower(leaves, dom.sidecar)
    rows = dom.spec.row_set(dom.spec.paths_key(_region_paths(
        dom, ("graph/topology", "graph/rank"))), 0, 3)
    return _compiled_scrub(dom.spec, rows).lower(leaves, dom.sidecar)


# the page-only KV refresh is the write-path encode, narrowed: it lowers
# under the full encode's name, so the device trace counts it as the KV
# refresh
MODULE = {"cache_encode_pages": "cache_encode"}


@pytest.mark.parametrize("which", ["serve_decode", "serve_prefill",
                                   "params_scrub", "cache_scrub",
                                   "cache_encode", "graph_scrub_slice",
                                   "graph_encode_rows", "cache_encode_pages"])
def test_program_module_names(params, graph, which):
    lowered = (_lower_graph(graph, which) if which.startswith("graph")
               else _lower_serve(params, which))
    assert _module_name(lowered) == f"jit_{MODULE.get(which, which)}"


def test_domain_kind_from_roots(params):
    pol = detect_recover_l()
    assert MemoryDomain.protect(params, pol).spec.kind == "params"
    assert MemoryDomain.protect({"params": params}, pol).spec.kind == \
        "params"
    mixed = {"params": params, "opt": jax.tree_util.tree_map(
        jnp.zeros_like, params)}
    assert MemoryDomain.protect(mixed, pol).spec.kind == "domain"


# ------------------------------------------------ (b) profiler spans
def _capture(log_dir, fn):
    jax.profiler.start_trace(str(log_dir))
    try:
        out = fn()
        jax.block_until_ready(out)
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(str(log_dir), "**",
                                         "*.xplane.pb"), recursive=True),
                  key=os.path.getmtime)[-1]
    events = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("serve.", "graph.")):
                    events.append((float(e.start_ns), float(e.end_ns),
                                   e.name, {k: v for k, v in e.stats}))
    return out, events


@pytest.fixture(scope="module")
def captures(params, graph, tmp_path_factory):
    """One capture at a time: a tiny engine run under a storm of two
    strikes, then five scrubbed PageRank iterations."""
    eng = _engine(params)
    trace = _wave(3)
    _serve(eng, trace)                              # compile outside
    (rep, _), serve_ev = _capture(tmp_path_factory.mktemp("serve"),
                                  lambda: _serve(eng, trace, 2))
    g, state = graph
    dom = MemoryDomain.protect({"graph": state}, detect_recover_l())
    pagerank_scrubbed(dom, g.n, iters=5, scrub_slices=3)
    _, graph_ev = _capture(
        tmp_path_factory.mktemp("graph"),
        lambda: pagerank_scrubbed(dom, g.n, iters=5, scrub_slices=3)[1])
    return {"serve": (rep, eng, serve_ev), "graph": graph_ev}


def _nested(events, parent: str, children):
    parents = [e for e in events if e[2] == parent]
    for a, b, name, _ in events:
        if name in children:
            assert any(pa <= a and b <= pb for pa, pb, _, _ in parents), name
    return parents


def _count(events, name):
    return sum(1 for e in events if e[2] == name)


def test_serve_spans_per_iteration(captures):
    rep, eng, ev = captures["serve"]
    iters = _nested(ev, "serve.iteration", SERVE_SPANS)
    n = len(iters)
    assert n == rep.counters["decode_steps"] > 0
    assert [s["step_num"] for *_, s in sorted(iters)] == list(range(n))
    assert _count(ev, "serve.kv_refresh") == n
    assert _count(ev, "serve.kv_check") == n
    assert _count(ev, "serve.decode") == rep.counters["decode_steps"]
    assert _count(ev, "serve.prefill") == rep.counters["prefills"] == 3
    assert _count(ev, "serve.params_scrub") == (n - 1) // 2
    # the strikes are all due at once, in the first iteration
    assert _count(ev, "serve.inject") == 1
    assert rep.counters["injected_params"] + rep.counters["injected_kv"] \
        == 2 and rep.counters["crash_events"] == 0
    # each refresh encodes its iteration's prompt pages and one page per
    # slot of its decode, or the whole pool in the one iteration after a
    # KV strike
    full = 0
    for a, b, *_ in iters:
        inside = [(name, s) for a2, b2, name, s in ev if a <= a2 and b2 <= b]
        (refresh,) = [s for name, s in inside if name == "serve.kv_refresh"]
        full += refresh["full"]
        assert refresh["pages"] == (eng.cache.n_pages if refresh["full"]
                                    else sum(s["pages"] for name, s in inside
                                             if name == "serve.prefill")
                                    + SLOTS)
    assert full == rep.counters["kv_full_refreshes"] == \
        min(rep.counters["injected_kv"], 1)
    assert sum(s["pages"] for *_, name, s in ev
               if name == "serve.kv_refresh") == \
        rep.counters["kv_pages_encoded"]
    written = sum(s["pages"] for *_, name, s in ev
                  if name == "serve.prefill") + \
        sum(s["active"] for *_, name, s in ev if name == "serve.decode")
    assert written == rep.counters["kv_pages_written"]


def test_graph_spans_per_iteration(captures):
    ev = captures["graph"]
    assert len(_nested(ev, "graph.iteration", GRAPH_SPANS)) == 5
    for name in GRAPH_SPANS:
        assert _count(ev, name) == 5


# ------------------------------------------------ (c) page counters
@pytest.mark.parametrize("kv_tier", [Tier.PARITY_R, Tier.NONE])
def test_kv_page_counters_exact(params, kv_tier):
    eng = _engine(params, kv_tier=kv_tier)
    trace = _wave(5)
    rep, resp = _serve(eng, trace)
    c = rep.counters
    assert all(len(resp[r.rid]) == r.max_new for r in trace)
    iters = c["decode_steps"]             # every request is due at t=0
    pages = eng.cache.n_pages
    on = kv_tier is not Tier.NONE
    prompt_pages = sum(-(-r.prompt_len // PAGE) for r in trace)
    # the refresh encodes the prompt pages and one page per slot of each
    # decode, never the whole pool
    assert c["kv_pages_encoded"] == (prompt_pages + iters * SLOTS
                                     if on else 0)
    assert c["kv_full_refreshes"] == 0
    assert c["kv_pages_checked"] == ((iters + 1) * pages if on else 0)
    decoded = sum(r.max_new - 1 for r in trace)
    assert c["kv_pages_written"] == prompt_pages + decoded
    if on:
        assert c["kv_pages_encoded"] <= 2 * c["kv_pages_written"]


def test_serve_online_json_has_page_counters(tmp_path, capsys):
    from repro.launch.serve_online import main
    out = tmp_path / "slo.json"
    assert main(["--arch", "llama3-8b", "--requests", "3", "--slots", "2",
                 "--kv-tier", "parity_r", "--clock", "model",
                 "--json", str(out)]) == 0
    counters = json.loads(out.read_text())["counters"]
    assert counters["kv_pages_encoded"] > 0
    assert counters["kv_pages_checked"] > counters["kv_pages_encoded"]
    assert counters["kv_pages_written"] > 0


# ------------------------------------------------ served wall clock
def test_wall_clock_includes_kv_check(params, monkeypatch):
    eng = _engine(params, clock="wall", policy=None)
    trace = _wave(6)
    _serve(eng, trace)                              # compile outside
    base, base_resp = _serve(eng, trace)
    check = eng._scrub_kv

    def slow_check(counters):
        time.sleep(0.02)
        check(counters)

    monkeypatch.setattr(eng, "_scrub_kv", slow_check)
    slow, slow_resp = _serve(eng, trace)
    assert slow_resp == base_resp
    assert slow.ttft_p50_s >= base.ttft_p50_s + 0.02
