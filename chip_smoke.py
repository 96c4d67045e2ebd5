#!/usr/bin/env python3
"""Chip smoke: drive the HRM system's main paths once on a TPU, at
granite-moe-3b-a800m's published widths, and check what comes out.

    python3 chip_smoke.py              # one chip: phases (a)-(d)
    python3 chip_smoke.py --chips 4    # four chips: the sharded path only

Phases on one chip:
  (a) device  the platform must be a TPU; there is no CPU fallback.
  (b) domain  granite params under ``detect_recover_l`` (SEC-DED on
              embed/attn/norm, Par+R on the experts): a clean scrub reports
              0/0, SEC-DED strikes are corrected and Par+R strikes detected
              and reloaded, and the params end bit-equal to the originals.
  (c) serve   ``OnlineEngine`` on the wall clock, KV pages under Par+R: a
              golden pass and a pass under an error storm answer every
              request in full; the first token's logits agree with a
              float32 forward of the same params, one prompt per length.
  (d) graph   PageRank under a ``MemoryDomain`` on a 2^20-node power-law
              graph in the node-blocked layout, against ``segment_sum``.

With ``--chips 4`` only the sharded path runs: a ``ShardedMemoryDomain``
over a 2x2 (data, model) mesh, one cell per chip, against the
single-device scrub of the same state, plus one PEER_COPY recovery.

Any failure raises, so the exit code is nonzero and the last line is not
printed. The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Everything runs in this one process: a chip belongs to one process.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import (InjectionPlan, MemoryDomain, Response,  # noqa: E402
                        ShardedMemoryDomain, Tier, detect_recover_l)
from repro.kernels import ops  # noqa: E402
from repro.launch.workdir import enable_compile_cache  # noqa: E402
from repro.models import forward, init_params  # noqa: E402

ARCH = "granite-moe-3b-a800m"
# Depth cut (widths untouched). Compiled for one v5e (15.75 GiB of HBM),
# the tier-batched params encode alone needs 16.12 GiB at the full 32
# layers. The params scrub peaks at 11.69 GiB at 20 layers, and a storm
# can leave three struck expert leaves (a copy each, 3.8 GiB) waiting for
# it: 15.7 GiB. At 16 layers: 9.46 + 3.0 GiB.
LAYERS = 16
SHARDED_LAYERS = 8          # 2x2 mesh: device 0 also holds the reference
GRAPH_NODES = 1 << 20
GRAPH_NODE_BLOCK = 8192     # ~20k edge tiles: the dispatch tables fit SMEM
# First-token logits, bf16 serving path vs float32 reference, as
# ||sys - ref|| / ||ref||, median over the trace's prompt lengths. One
# prompt cannot be held tighter: with random weights the top-8-of-40
# router is near-tied, and a flipped choice or a capacity drop moves a
# prompt's logits by up to ~0.25. Measured on a v5e at 16 layers, prompts
# of 128/256/512 tokens: 0.068/0.251/0.031; with float8 weights, which
# the tolerance must reject, 0.335/0.418/0.317.
LOGIT_REL_TOL = 0.15
# The engine's token must be a top logit of the system forward up to a
# couple of bf16 ulps (0.016 at |x| ~ 3): two programs may round apart.
TOKEN_LOGIT_SLACK = 0.05
# PageRank, blocked Pallas push vs segment_sum: f32 sums in another order.
RANK_RTOL = 1e-4

_UINT = {1: np.uint8, 2: np.uint16, 4: np.uint32}


def log(msg: str) -> None:
    print(msg, flush=True)


def _compile_seconds():
    """Running total of JAX's lowering + XLA compile time (tracing is left
    out: nested jits would count twice)."""
    total = [0.0]
    events = {"/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration"}

    def listen(event, duration, **_):
        if event in events:
            total[0] += duration

    jax.monitoring.register_event_duration_secs_listener(listen)
    return lambda: total[0]


def _bits(x) -> np.ndarray:
    """Host copy of an array's raw bits."""
    a = np.asarray(x)
    return a.view(_UINT[a.dtype.itemsize])


def _same_bits(a, b) -> bool:
    return np.array_equal(_bits(a), _bits(b))


def _peak_gib() -> float:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) / 2 ** 30


# ------------------------------------------------------------ (a) device
def phase_device(chips: int) -> dict:
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise RuntimeError(f"no TPU: JAX found {d.platform} devices")
    if len(devs) < chips:
        raise RuntimeError(f"--chips {chips} but JAX found {len(devs)}")
    log(f"[a] device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


# ------------------------------------------------------------ (b) domain
def check_pack_layout() -> None:
    """On the device, word ``w`` must hold the tensor's flat bytes
    ``8w .. 8w+7`` little-endian, the layout every stored sidecar and
    strike address assumes. The bytes are random, except that 16-bit
    floats are kept finite and normal: a TPU's bitcast rewrites their NaN
    payloads and subnormals (measured on a v5e), which this smoke does not
    claim to keep."""
    rng = np.random.default_rng(0)
    for dt, shape in ((jnp.bfloat16, (3001,)), (jnp.bfloat16, (3, 5, 7, 64)),
                      (jnp.float16, (3001,)), (jnp.float32, (1500,)),
                      (jnp.int8, (6001,))):
        dt = jnp.dtype(dt)
        n = int(np.prod(shape))
        raw = rng.integers(0, 256, n * dt.itemsize, np.uint8)
        vals = raw.view(dt)
        if dt.itemsize == 2:
            with np.errstate(invalid="ignore"):
                normal = np.isfinite(vals) & (
                    (np.abs(vals) >= jnp.finfo(dt).tiny) | (vals == 0))
            vals[~normal] = 1                 # writes through to ``raw``
        # a host copy keeps the bits
        x = jax.device_put(vals.reshape(shape))
        p = ops.pack_words(x)
        words = np.zeros(p.lo.size * 2, np.uint32)
        words.view(np.uint8)[:raw.size] = raw
        words = words.reshape(-1, 2)
        if not (np.array_equal(_bits(p.lo).reshape(-1), words[:, 0])
                and np.array_equal(_bits(p.hi).reshape(-1), words[:, 1])):
            raise AssertionError(f"pack_words layout differs for {dt} "
                                 f"{shape}")
        if not _same_bits(ops.unpack_words(p, x.shape, x.dtype), x):
            raise AssertionError(f"unpack_words does not invert for {dt} "
                                 f"{shape}")


def _strike(rng, dom, paths):
    """One single-bit strike on a data word of a leaf drawn
    byte-weighted from ``paths`` (never a pad word, so the outcome is
    certain)."""
    sizes = np.array([dom.leaf(p).size * dom.leaf(p).dtype.itemsize
                      for p in paths], np.float64)
    path = paths[rng.choice(len(paths), p=sizes / sizes.sum())]
    n64 = max(1, int(sizes[paths.index(path)]) // 8)
    plan = InjectionPlan(np.array([rng.integers(0, n64)], np.int32),
                         np.array([rng.integers(0, 64)], np.int32),
                         hard=False)
    return dom.apply_plan(path, plan), path


def phase_domain(params, *, strikes: int = 4, seed: int = 0) -> None:
    check_pack_layout()
    log("[b] pack layout: device words match the host byte layout")
    t0 = time.perf_counter()
    dom = MemoryDomain.protect(params, detect_recover_l())
    jax.block_until_ready(dom.sidecar)
    st = dom.stats()
    log(f"[b] protect: {time.perf_counter() - t0:.3f} s, {st.summary()}")
    t0 = time.perf_counter()
    dom, rep = dom.scrub()
    if rep.totals() != (0, 0):
        raise AssertionError(f"clean scrub reported {rep.totals()}")
    log(f"[b] clean scrub: 0/0 in {time.perf_counter() - t0:.3f} s "
        f"(includes compilation)")
    by_tier = {t: [p for p in dom.paths(protected_only=True)
                   if dom.tier_of(p) is t]
               for t in (Tier.SECDED, Tier.PARITY_R)}
    if not all(by_tier.values()):
        raise AssertionError("detect_recover_l left a tier empty")
    orig = {p: dom.leaf(p) for p in dom.paths()}
    rng = np.random.default_rng(seed)
    for tier in [Tier.SECDED] * strikes + [Tier.PARITY_R] * strikes:
        # one struck leaf at a time: each strike copies its whole leaf
        dom, path = _strike(rng, dom, by_tier[tier])
        dom, rep = dom.scrub()
        if tier is Tier.SECDED:
            if rep.totals() != (1, 0):
                raise AssertionError(f"SEC-DED strike on {path}: "
                                     f"{rep.totals()}, want (1, 0)")
        else:
            if rep.totals() != (0, 1):
                raise AssertionError(f"Par+R strike on {path}: "
                                     f"{rep.totals()}, want (0, 1)")
            dom, events = dom.recover(rep, clean_copy=orig.__getitem__)
            if [e["path"] for e in events] != [path]:
                raise AssertionError(f"recovery events {events}")
    flat0 = jax.tree_util.tree_leaves(params)
    flat1 = jax.tree_util.tree_leaves(dom.payload)
    if not all(_same_bits(a, b) for a, b in zip(flat0, flat1)):
        raise AssertionError("params differ from the originals")
    dom, rep = dom.scrub()
    if rep.totals() != (0, 0):
        raise AssertionError(f"final scrub reported {rep.totals()}")
    log(f"[b] {strikes} SEC-DED strikes corrected, {strikes} Par+R strikes "
        f"detected and reloaded; params bit-equal to the originals; "
        f"peak {_peak_gib():.2f} GiB")


# ------------------------------------------------------------- (c) serve
def _first_token_logits(cfg, params, prompt, page_size: int):
    """First-token logits at the last prompt position, over the
    page-padded tokens the engine's prefill sees (MoE capacity depends on
    the padded length): (system, float32 reference, system with the
    weights rounded through float8_e4m3fn). The last is a lower precision
    than the configuration's bf16, which the tolerance must reject."""
    n = len(prompt)
    padded = -(-n // page_size) * page_size
    toks = np.zeros((1, padded), np.int32)
    toks[0, :n] = prompt
    toks = jnp.asarray(toks)
    sys_fn = jax.jit(lambda p, t: forward(p, {"tokens": t}, cfg)[0][0, n - 1])
    system = np.asarray(sys_fn(params, toks).astype(jnp.float32))
    low = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
    lower = np.asarray(sys_fn(low, toks).astype(jnp.float32))
    del low
    cfg32 = cfg.replace(compute_dtype="float32")
    with jax.default_matmul_precision("highest"):
        ref_fn = jax.jit(
            lambda p, t: forward(p, {"tokens": t}, cfg32)[0][0, n - 1])
        ref = np.asarray(ref_fn(params, toks))
    return system, ref, lower


def _rel(a, ref) -> float:
    return float(np.linalg.norm(a - ref) / np.linalg.norm(ref))


def phase_serve(cfg, params, compile_s, *, n_requests: int = 8,
                prompt_lens=(128, 256, 384, 512), max_new: int = 32,
                storm_errors: int = 8, slots: int = 8, page_size: int = 16,
                seed: int = 0) -> None:
    from repro.serve import (OnlineEngine, TrafficConfig, generate_trace,
                             incorrect_rate)
    tc = TrafficConfig(n_requests=n_requests, rate=8.0,
                       prompt_len_choices=tuple(prompt_lens),
                       max_new_choices=(max_new,), seed=seed)
    trace = generate_trace(tc, cfg.vocab_size)

    def run(storm: int):
        eng = OnlineEngine(cfg, params, slots=slots, page_size=page_size,
                           max_prompt_len=tc.max_prompt_len,
                           max_new_cap=tc.max_new_cap,
                           policy=detect_recover_l(), kv_tier=Tier.PARITY_R,
                           clock="wall", scrub_every=8, seed=seed)
        t0 = time.perf_counter()
        report, resp = eng.run(trace, storm_errors=storm)
        wall = time.perf_counter() - t0
        for req in trace:
            got = len(resp.get(req.rid, ()))
            if got != req.max_new:
                raise AssertionError(f"request {req.rid}: {got} tokens, "
                                     f"want {req.max_new}")
        return report, resp, wall

    c0 = compile_s()
    golden_rep, golden, cold = run(0)
    log(f"[c] set-up: golden pass {cold:.3f} s on the wall, of which "
        f"compilation {compile_s() - c0:.3f} s")
    log(f"[c] golden: {golden_rep.summary()}")
    c0 = compile_s()
    rep, resp, warm = run(storm_errors)
    rep.incorrect_rate = incorrect_rate(golden, resp)
    log(f"[c] storm ({storm_errors} errors): {warm:.3f} s, compilation "
        f"{compile_s() - c0:.3f} s; {rep.summary()}")
    log(f"[c] wall TTFT p50 {rep.ttft_p50_s:.6f} s p99 {rep.ttft_p99_s:.6f} "
        f"s; TPOT p50 {rep.tpot_p50_s:.6f} s p99 {rep.tpot_p99_s:.6f} s; "
        f"incorrect {rep.incorrect_rate:.3f}; layers {cfg.n_layers}; "
        f"peak {_peak_gib():.2f} GiB")
    rels, rels_low = [], []
    for req in {len(r.prompt): r for r in reversed(trace)}.values():
        system, ref, lower = _first_token_logits(cfg, params, req.prompt,
                                                 page_size)
        if not (np.isfinite(system).all() and np.isfinite(ref).all()):
            raise AssertionError("non-finite first-token logits")
        rels.append(_rel(system, ref))
        rels_low.append(_rel(lower, ref))
        tok = golden[req.rid][0]
        log(f"[c] prompt of {len(req.prompt)}: first-token logits vs float32 "
            f"reference: max abs diff {float(np.abs(system - ref).max()):.6f}"
            f", relative L2 {rels[-1]:.6f}, with float8 weights "
            f"{rels_low[-1]:.6f}; engine token {tok}, system argmax "
            f"{int(system.argmax())}, reference argmax {int(ref.argmax())}")
        if system[tok] < system.max() - TOKEN_LOGIT_SLACK:
            raise AssertionError(f"engine token {tok} is not a top logit")
    rel, rel_low = float(np.median(rels)), float(np.median(rels_low))
    log(f"[c] median relative L2 {rel:.6f} (tolerance {LOGIT_REL_TOL}); "
        f"with float8 weights {rel_low:.6f} (must exceed it)")
    if rel > LOGIT_REL_TOL:
        raise AssertionError(f"relative logit error {rel} > "
                             f"{LOGIT_REL_TOL}")
    if rel_low <= LOGIT_REL_TOL:
        raise AssertionError(f"float8 weights pass the tolerance too "
                             f"({rel_low}): it cannot tell precisions apart")


# ------------------------------------------------------------- (d) graph
def phase_graph(*, n: int = GRAPH_NODES, node_block: int = GRAPH_NODE_BLOCK,
                iters: int = 4, seed: int = 0) -> None:
    from repro.graph import (graph_state, pagerank, pagerank_scrubbed,
                             powerlaw_graph)
    t0 = time.perf_counter()
    g = powerlaw_graph(n, seed=seed)
    state = graph_state(g, node_block=node_block)
    n_tiles = state["topology"]["blocks"]["src_block"].shape[0]
    log(f"[d] graph: {g.n} nodes, {g.n_edges} edges, {n_tiles} edge tiles "
        f"of node block {node_block}; built in "
        f"{time.perf_counter() - t0:.3f} s")
    dom = MemoryDomain.protect({"graph": state}, detect_recover_l())
    t0 = time.perf_counter()
    dom, rank, _, rep = pagerank_scrubbed(dom, g.n, iters=iters,
                                          scrub_slices=iters)
    rank = np.asarray(rank)
    log(f"[d] pagerank_scrubbed: {iters} iterations in "
        f"{time.perf_counter() - t0:.3f} s (includes compilation)")
    if rep.totals() != (0, 0):
        raise AssertionError(f"clean graph scrub reported {rep.totals()}")
    _, ref, _ = pagerank(state, g.n, iters=iters, backend="segment_sum")
    ref = np.asarray(ref)
    err = np.abs(rank - ref)
    bound = RANK_RTOL * np.abs(ref) + RANK_RTOL / g.n
    log(f"[d] rank vs segment_sum: max abs diff {err.max():.3e}, max "
        f"relative {float((err / np.maximum(ref, 1e-30)).max()):.3e} "
        f"(tolerance rtol {RANK_RTOL}, atol {RANK_RTOL}/n); "
        f"rank sum {rank.sum():.6f}")
    if not np.isfinite(rank).all() or (err > bound).any():
        raise AssertionError("blocked PageRank differs from segment_sum")


# ----------------------------------------------------------- --chips 4
def phase_sharded(params, *, seed: int = 0) -> None:
    from repro.launch.mesh import make_domain_mesh
    policy = detect_recover_l()
    mesh = make_domain_mesh(2, 2)
    single = MemoryDomain.protect(params, policy)
    orig = {p: single.leaf(p) for p in single.paths()}
    sh = ShardedMemoryDomain.protect(params, policy, mesh=mesh)
    seen = set()
    for r in range(sh.n_replicas):
        for s in range(sh.n_shards):
            dev = sh.devices[r][s]
            cell = sh.shards[r][s]
            for a in jax.tree_util.tree_leaves((cell.payload,
                                                cell.sidecar)):
                if a.devices() != {dev}:
                    raise AssertionError(f"cell ({r},{s}) has an array on "
                                         f"{a.devices()}, not {dev}")
            seen.add(dev.id)
    if len(seen) != 4:
        raise AssertionError(f"cells share devices: {sorted(seen)}")
    log(f"[s] {sh}: cell (r,s) -> device "
        + ", ".join(f"({r},{s})->{sh.devices[r][s].id}"
                    for r in range(2) for s in range(2)))
    rng = np.random.default_rng(seed)
    plans = []
    for tier in (Tier.SECDED, Tier.SECDED, Tier.PARITY_R, Tier.PARITY_R):
        paths = [p for p in single.paths(protected_only=True)
                 if single.tier_of(p) is tier]
        path = paths[rng.integers(0, len(paths))]
        leaf = single.leaf(path)
        n64 = max(1, leaf.size * leaf.dtype.itemsize // 8)
        plans.append((path, InjectionPlan(
            np.array([rng.integers(0, n64)], np.int32),
            np.array([rng.integers(0, 64)], np.int32), hard=False)))
    for path, plan in plans:
        single = single.apply_plan(path, plan)
        sh = sh.apply_plan(path, plan, replica=0)
    single, s_rep = single.scrub()
    sh, rep = sh.scrub()
    agg = rep.domain_report()
    if agg.totals() != s_rep.totals():
        raise AssertionError(f"sharded {agg.totals()} vs single "
                             f"{s_rep.totals()}")
    for p in single.paths(protected_only=True):
        for name in ("corrected", "detected_uncorrectable"):
            a = int(np.asarray(getattr(agg, name).get(p, 0)))
            b = int(np.asarray(getattr(s_rep, name).get(p, 0)))
            if a != b:
                raise AssertionError(f"{name}[{p}]: sharded {a} vs "
                                     f"single {b}")
        if not np.array_equal(_bits(sh.leaf(p, 0)), _bits(single.leaf(p))):
            raise AssertionError(f"scrubbed {p} differs from the single "
                                 f"device's")
    log(f"[s] sharded scrub == single-device scrub: totals "
        f"{agg.totals()}, every per-path count and scrubbed leaf equal")
    needs = rep.needs_recovery()
    if set(needs) != {0}:
        raise AssertionError(f"flagged replicas {sorted(needs)}, want [0]")
    sh, events = sh.recover(rep, response=Response.PEER_COPY)
    for e in events:
        if e["action"] != "peer_copy" or e["donor"] != 1:
            raise AssertionError(f"recovery event {e}")
        p = e["path"]
        leaf = sh.leaf(p, 0)
        if leaf.devices() != {sh.devices[0][sh.shard_of[p]]}:
            raise AssertionError(f"recovered {p} left its device")
        if not np.array_equal(_bits(leaf), _bits(orig[p])):
            raise AssertionError(f"PEER_COPY of {p} is not the original")
    sh, rep = sh.scrub()
    if rep.totals() != (0, 0):
        raise AssertionError(f"post-recovery scrub {rep.totals()}")
    log(f"[s] PEER_COPY: {len(events)} leaves gathered from replica 1 "
        f"return the original bytes; rescrub 0/0")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()
    compile_s = _compile_seconds()
    device = phase_device(args.chips)
    cfg = get_config(ARCH)
    key = jax.random.PRNGKey(args.seed)
    if args.chips == 4:
        cfg = cfg.replace(n_layers=SHARDED_LAYERS)
        log(f"[s] {cfg.name}: {SHARDED_LAYERS} of 32 layers, published "
            f"widths")
        phase_sharded(init_params(key, cfg), seed=args.seed)
    else:
        cfg = cfg.replace(n_layers=LAYERS)
        log(f"[b] {cfg.name}: {LAYERS} of 32 layers (depth cut to fit one "
            f"chip), d_model={cfg.d_model}, experts={cfg.moe.n_experts} "
            f"top-{cfg.moe.top_k}, vocab={cfg.vocab_size}")
        params = init_params(key, cfg)
        phase_domain(params, seed=args.seed)
        phase_serve(cfg, params, compile_s, seed=args.seed)
        del params
        phase_graph(seed=args.seed)
    log(f"total compilation {compile_s():.3f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
