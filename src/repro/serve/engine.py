"""The online serving engine: continuous batching over a paged,
HRM-protected KV cache, driven by a timestamped request trace while an
error storm fires live.

Two memory domains, mirroring the paper's region split:

  params    the model weights — long-lived, crash-vulnerable, protected
            by any of the five design-point policies (patrol-scrubbed on
            the policy cadence; Par+R detections reload from a clean copy
            and charge ``RECOVERY_SECONDS`` of measured downtime).
  kv_cache  the paged KV pools — the Fig. 4 largest, most error-tolerant
            region, under a configurable cheap tier; a latent-attention
            model's latent pools are a region of their own
            (``kv_cache/latent``) under the same tier. Unlike params, the
            pools are written every step, so ECC is emulated the way the
            hardware does it: the sidecar is re-encoded after each step's
            legitimate writes (write-path ECC) and *checked at the start
            of the next step* (access-path ECC) — injected strikes always
            land between a refresh and the next check, so they are
            detected (parity) or corrected (SEC-DED), never laundered.
            The refresh encodes only the pages the step wrote (its
            prefills' prompt pages and one page per slot of the decode),
            where a page is a whole number of packed sidecar rows, and
            the whole pool after a strike, a check that found errors, a
            recovery or a crash.

The decode step is one jit program over every scheduler slot: write the
new token's K/V into its page, gather each slot's pages into a contiguous
view (the contiguous oracle's cache after its update), and attend under
the per-slot validity mask. The gathered view reproduces the contiguous
cache bit-for-bit wherever the mask admits it, so paged decode is
bit-identical to ``runtime.serve_loop.serve_batch``
(``tests/test_serve_plane.py`` pins this). Under latent attention the
step writes the token's latent and rotary key into their pages, then
attends the gathered latents in absorbed form (the query through the
key half of ``wkv_b``, the weighted latents through its value half),
where the prefill runs the expanded form; the two agree to rounding.

Time: the engine advances a virtual clock by a calibrated service model
(``--clock model``, deterministic — the CI/test path) or reads the wall
time since ``run`` began at the prefill's and the decode's host syncs
(``--clock wall``), so the KV check, the KV refresh and the params scrub
count in every token's stamp. An error storm compresses one
server-month's error budget (default 540 incident errors) into the run;
availability is computed from *measured* recovery/crash events against
that month (docs/DESIGN.md §9).

Tracing: each iteration is a ``serve.iteration`` step span holding one
span per phase (``serve.kv_check``, ``serve.params_scrub``,
``serve.prefill``, ``serve.decode``, ``serve.kv_refresh``,
``serve.inject``), and the device programs lower as ``jit_serve_decode``
and ``jit_serve_prefill``; both cost nothing without a profiler. The
decode span carries ``active`` (occupied slots) and ``ctx_tokens`` (the
cached tokens the step reads: each active slot's position plus one).
"""
from __future__ import annotations

import functools
import math
import time
from collections import deque
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.configs.base import ModelConfig
from repro.core import HRMPolicy, MemoryDomain, Response, Tier
from repro.core.availability import MINUTES_PER_MONTH
from repro.core.trace import BoundStrike, ErrorTrace, bind_trace
from repro.models import forward
from repro.models import attention as attn
from repro.models import mlp as mlp_mod
from repro.models.common import dtype_of, rmsnorm
from repro.models.transformer import _head, _layer_stacks, cache_names
from repro.serve.metrics import SLOCounters, SLOReport, build_report
from repro.serve.paged_kv import (PagedKVCache, pack_rope,
                                  rope_tokens_per_row)
from repro.serve.router import RequestRouter
from repro.serve.scheduler import ContinuousBatchingScheduler
from repro.serve.traffic import Request


# =====================================================================
# service-time model (virtual clock)
# =====================================================================
@dataclass(frozen=True)
class ServiceModel:
    """Per-step virtual costs, roughly a small-LLM accelerator: a decode
    step near 10 ms and prefill growing with prompt length."""
    prefill_base: float = 4e-3
    prefill_per_token: float = 5e-5
    decode_base: float = 9e-3
    decode_per_slot: float = 4e-4

    def prefill_cost(self, n_tokens: int) -> float:
        return self.prefill_base + n_tokens * self.prefill_per_token

    def decode_cost(self, n_active: int) -> float:
        return self.decode_base + n_active * self.decode_per_slot


def kv_policy(tier: Tier) -> HRMPolicy:
    """Policy for the KV domain: one (cheap) tier over the KV pools and
    the latent pools alike."""
    tiers = ({} if tier is Tier.NONE
             else {"kv_cache": tier, "kv_cache/latent": tier})
    return HRMPolicy(f"kv_{tier.value}", tiers, default=Tier.NONE,
                     scrub_interval=1)


# =====================================================================
# jitted programs (shared across engine instances via lru_cache)
# =====================================================================
def _ffn(layer, x, cfg: ModelConfig):
    h = rmsnorm(x, layer["norm2"], cfg.norm_eps)
    if "moe" in layer:
        return mlp_mod.moe_apply(layer["moe"], h, cfg)[0]
    return mlp_mod.mlp_apply(layer["mlp"], h, cfg)


def _make_paged_decode(cfg: ModelConfig, page_size: int):
    """One fused decode step over every slot against the paged pools.

    (params, pool_a, pool_b, table, tokens, pos)
      -> (pool_a', pool_b', next_tokens, ok)

    The two pools are the cache's, in ``cache_names`` order: keys and
    values, or latents and rotary keys.

    The attention math mirrors ``models.attention.attn_decode`` line for
    line on the gathered contiguous view, so results are bit-identical to
    the contiguous-cache path.
    """
    if cfg.family not in ("dense", "moe", "vlm"):
        raise ValueError(f"paged decode supports dense/moe/vlm, "
                         f"not {cfg.family!r}")
    if cfg.mla:
        return _make_paged_decode_mla(cfg, page_size)
    dh, H, K = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    cdt = dtype_of(cfg.compute_dtype)

    def serve_decode(params, pool_k, pool_v, table, tokens, pos):
        S, P = table.shape
        smax = P * page_size
        x = params["embed"][tokens][:, None, :].astype(cdt)    # (S,1,D)
        positions = pos[:, None]                               # (S,1)
        pid = jnp.take_along_axis(
            table, (pos // page_size)[:, None], axis=1)[:, 0]  # (S,)
        off = pos % page_size

        def body(x, xs):
            layer, pk, pv = xs
            h = rmsnorm(x, layer["norm1"], cfg.norm_eps)
            q, k_new, v_new = attn._project_qkv(
                layer["attn"], h, cfg, positions)
            # write the new token's K/V into its page first (inactive
            # slots land in the null page, read only where masked), then
            # gather each slot's pages into the contiguous (S, smax, K,
            # dh) view: the oracle's cache with the token inserted
            pk = pk.at[pid, off].set(k_new[:, 0].reshape(S, -1)
                                     .astype(pk.dtype))
            pv = pv.at[pid, off].set(v_new[:, 0].reshape(S, -1)
                                     .astype(pv.dtype))
            vk = pk[table].reshape(S, smax, K, dh)
            vv = pv[table].reshape(S, smax, K, dh)
            scores = jnp.einsum("bqkgd,bskd->bkgqs", q,
                                vk.astype(q.dtype)).astype(jnp.float32)
            scores = scores / math.sqrt(dh)
            valid = (jnp.arange(smax)[None, :]
                     <= pos[:, None])[:, None, None, None, :]
            scores = jnp.where(valid, scores, -jnp.inf)
            w = jax.nn.softmax(scores, axis=-1).astype(vv.dtype)
            o = jnp.einsum("bkgqs,bskd->bqkgd", w, vv).reshape(S, 1,
                                                               H * dh)
            y = o.astype(x.dtype) @ layer["attn"]["wo"].astype(x.dtype)
            x = x + y
            x = x + _ffn(layer, x, cfg)
            return x, (pk, pv)

        x, (pk, pv) = jax.lax.scan(
            body, x, (params["blocks"], pool_k, pool_v))
        logits = _head(params, x, cfg)[:, 0]                   # (S,V)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        ok = jnp.isfinite(logits).all()
        return pk, pv, nxt, ok

    return serve_decode


def _make_paged_decode_mla(cfg: ModelConfig, page_size: int):
    """The decode step under latent attention (``_paged_logits_mla``),
    with the greedy next tokens."""
    step = _paged_logits_mla(cfg, page_size)

    def serve_decode(params, c_kv, k_pe, table, tokens, pos):
        pools, logits = step(params, {"c_kv": c_kv, "k_pe": k_pe}, table,
                             tokens, pos)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (pools["c_kv"], pools["k_pe"], nxt,
                jnp.isfinite(logits).all())

    return serve_decode


def _paged_logits_mla(cfg: ModelConfig, page_size: int):
    """(params, pools, table, tokens, pos) -> (pools', logits (S, V)).

    Per layer, write each slot's latent and rotary key into its page
    (inactive slots into the null page), gather each slot's pages, and
    attend in absorbed form. The pools ride in the layer loop's carry and
    are indexed by layer, so the leading dense layers and the expert
    blocks update one pool in place. The rotary keys stay packed ``g`` to
    a row: the query's rotary part is laid out block-diagonally against a
    row's ``g`` tokens, so the scores come out per token without
    unpacking the view."""
    m, H = cfg.mla, cfg.n_heads
    r, dr = m.kv_lora_rank, m.qk_rope_head_dim
    g = rope_tokens_per_row(cfg)
    cdt = dtype_of(cfg.compute_dtype)

    def layer(x, l, lp, pools, at):
        table, pid, off, positions, valid = at
        S, P = table.shape
        c_pool, pe_pool = pools["c_kv"], pools["k_pe"]
        q_nope, q_pe, c_new, pe_new = attn.mla_project(
            lp["attn"], rmsnorm(x, lp["norm1"], cfg.norm_eps), cfg,
            positions)
        c_pool = c_pool.at[l, pid, off].set(c_new[:, 0].astype(c_pool.dtype))
        lanes = (off % g)[:, None] * dr + jnp.arange(dr)
        pe_pool = pe_pool.at[l, pid[:, None], (off // g)[:, None], lanes].set(
            pe_new[:, 0].astype(pe_pool.dtype))
        c_view = c_pool[l, table].reshape(S, P * page_size, r)
        q_rows = jnp.einsum("jk,shd->shjkd", jnp.eye(g, dtype=q_pe.dtype),
                            q_pe[:, 0]).reshape(S, H, g, g * dr)
        s_pe = jnp.einsum("shjl,sprl->shprj", q_rows,
                          pe_pool[l, table].astype(q_pe.dtype),
                          preferred_element_type=jnp.float32)
        y = attn.mla_attend_absorbed(
            lp["attn"], attn.mla_absorb_query(lp["attn"], q_nope[:, 0], cfg),
            s_pe.reshape(S, H, P * page_size), c_view, valid, cfg)
        x = x + y.astype(x.dtype)
        x = x + _ffn(lp, x, cfg)
        return x, {"c_kv": c_pool, "k_pe": pe_pool}

    def step(params, pools, table, tokens, pos):
        S, P = table.shape
        x = params["embed"][tokens][:, None, :].astype(cdt)    # (S,1,D)
        pid = jnp.take_along_axis(
            table, (pos // page_size)[:, None], axis=1)[:, 0]  # (S,)
        valid = jnp.arange(P * page_size)[None, :] <= pos[:, None]
        at = (table, pid, pos % page_size, pos[:, None], valid)
        lo = 0
        for stack in _layer_stacks(params, cfg):
            n = jax.tree.leaves(stack)[0].shape[0]

            def body(carry, xs):
                x, pools = carry
                lp, l = xs
                return layer(x, l, lp, pools, at), None

            (x, pools), _ = jax.lax.scan(
                body, (x, pools), (stack, lo + jnp.arange(n)))
            lo += n
        return pools, _head(params, x, cfg)[:, 0]              # (S,V)

    return step


def _make_prefill_write(cfg: ModelConfig, page_size: int):
    """Prefill one request (padded to a whole number of pages) and write
    its prompt's cache into the allocated pages.

    (params, pool_a, pool_b, tokens(1,Sb), true_len, pages(n_pp,))
      -> (pool_a', pool_b', first_token, ok)

    Under latent attention only the last prompt position goes through
    the head: a long prompt's logits over a large vocabulary would
    outweigh its cache.
    """

    names = cache_names(cfg)

    def serve_prefill(params, pool_a, pool_b, tokens, true_len, pages):
        last_at = (true_len - 1)[None] if cfg.mla else None
        logits, _, cache = forward(params, {"tokens": tokens}, cfg,
                                   return_cache=True, logits_at=last_at)
        last = jax.lax.dynamic_index_in_dim(
            logits[0], 0 if cfg.mla else true_len - 1, axis=0,
            keepdims=False)
        first = jnp.argmax(last, axis=-1).astype(jnp.int32)
        # zero the padded tail so page contents match the contiguous
        # oracle's zero-initialized cache bit-for-bit
        keep = jnp.arange(tokens.shape[1]) < true_len
        n_pp = pages.shape[0]
        out = []
        for name, pool in zip(names, (pool_a, pool_b)):
            c = cache[name]
            c = jnp.where(keep.reshape((1, 1, -1) + (1,) * (c.ndim - 3)),
                          c, 0).astype(pool.dtype)[:, 0]
            c = c.reshape(c.shape[0], n_pp, page_size, -1)
            if name == "k_pe":
                c = pack_rope(c, rope_tokens_per_row(cfg))
            out.append(pool.at[:, pages].set(c))
        return out[0], out[1], first, jnp.isfinite(last).all()

    return serve_prefill


def _donates_pools(cfg: ModelConfig) -> bool:
    """Whether the serving programs take the pools over (latent
    attention): the decode's layer loop then updates them in place, and
    a prefill writes only its pages, where an undonated pool is copied
    whole on every call. Whoever still holds the old pools (the KV
    domain's payload until the refresh adopts the new ones) must not
    read them."""
    return cfg.mla is not None


@functools.lru_cache(maxsize=None)
def _decode_program(cfg: ModelConfig, page_size: int):
    return jax.jit(_make_paged_decode(cfg, page_size),
                   donate_argnums=(1, 2) if _donates_pools(cfg) else ())


@functools.lru_cache(maxsize=None)
def _prefill_program(cfg: ModelConfig, page_size: int):
    return jax.jit(_make_prefill_write(cfg, page_size),
                   donate_argnums=(1, 2) if _donates_pools(cfg) else ())


# =====================================================================
# the engine
# =====================================================================
class OnlineEngine:
    def __init__(self, cfg: ModelConfig, params, *,
                 slots: int = 4,
                 page_size: int = 8,
                 max_prompt_len: int = 16,
                 max_new_cap: int = 8,
                 n_pages: Optional[int] = None,
                 policy: Optional[HRMPolicy] = None,
                 kv_tier: Tier = Tier.NONE,
                 scrub_every: Optional[int] = None,
                 clock: str = "model",
                 service: Optional[ServiceModel] = None,
                 max_prefills_per_step: int = 2,
                 max_queue: Optional[int] = None,
                 peer_recovery: bool = False,
                 debug_invariants: bool = False,
                 seed: int = 0):
        self.cfg = cfg
        self.params_policy = policy
        self.kv_tier = kv_tier
        # replicated-engine mode: this engine is one data-parallel replica
        # of a fleet, so detected-uncorrectable errors recover by an
        # in-memory gather from a live replica (Response.PEER_COPY, billed
        # PEER_COPY_SECONDS) instead of the disk reload. The peer's params
        # image is the replica-identical clean copy; the KV pools keep a
        # post-refresh peer snapshot (the replica that didn't take the
        # strike) so flagged pool leaves recover in memory too.
        self.peer_recovery = peer_recovery
        self._kv_peer: Optional[Dict[str, jax.Array]] = None
        self.clock_mode = clock
        self._origin = 0.0               # wall clock: when run() began
        self.service = service or ServiceModel()
        self.max_prefills_per_step = max_prefills_per_step
        self.max_queue = max_queue
        self.debug_invariants = debug_invariants
        self.rng = np.random.default_rng(seed)

        max_pages = -(-(max_prompt_len + max_new_cap) // page_size)
        if n_pages is None:
            n_pages = slots * max_pages + 1          # +1: the null page
        self.cache = PagedKVCache(cfg, n_pages=n_pages,
                                  page_size=page_size, slots=slots,
                                  max_pages_per_slot=max_pages)
        self.sched = ContinuousBatchingScheduler(
            self.cache, max_prefills_per_step=max_prefills_per_step)

        # params domain: full protection under the given policy, or a
        # sidecar-free leaf table (injection targeting only) when None
        self.param_domain = MemoryDomain.protect(
            params, policy if policy is not None
            else HRMPolicy("unprotected", {}))
        leaves = jax.tree_util.tree_leaves(params)
        self._clean = {s.path: np.asarray(leaves[s.pos])
                       for s in self.param_domain.spec.leaves}
        self.scrub_every = (scrub_every if scrub_every is not None
                            else (policy.scrub_interval if policy else 0))

        # KV domain: its own root over the page pools
        self.kv_domain = MemoryDomain.protect(self._kv_state(),
                                              kv_policy(kv_tier))
        # page-only refresh where a page is whole sidecar rows; a strike,
        # a check that found errors, a recovery or a crash leaves the
        # sidecar stale beyond the written pages until a full refresh
        self._kv_paged = self.kv_domain.spec.slices_aligned()
        self._kv_stale = False

        self._decode = _decode_program(cfg, page_size)
        self._prefill = _prefill_program(cfg, page_size)
        self._page_size = page_size

    # ----------------------------------------------------------- helpers
    def _params(self):
        return self.param_domain.payload

    def _kv_state(self) -> dict:
        return {"kv_cache": dict(self.cache.pools)}

    def _adopt_kv_payload(self) -> None:
        self.cache.adopt_pools(self.kv_domain.payload["kv_cache"])

    def _pools(self) -> tuple:
        """The two pools in the programs' argument order."""
        return tuple(self.cache.pools[n] for n in cache_names(self.cfg))

    def _adopt(self, a, b) -> None:
        self.cache.adopt_pools(dict(zip(cache_names(self.cfg), (a, b))))

    def _advance(self, now: float, model_cost: float) -> float:
        """The served clock at a host sync: the wall time since ``run``
        began, protection passes included (idle jumps move the origin
        back), or the virtual clock advanced by the service model."""
        if self.clock_mode == "wall":
            return time.perf_counter() - self._origin
        return now + model_cost

    def describe(self) -> str:
        ps = self.param_domain.stats()
        ks = self.kv_domain.stats()
        pol = self.params_policy.name if self.params_policy else "none"
        leaves = []
        for s in self.kv_domain.spec.leaves:
            side = 0
            if s.tier is not Tier.NONE:
                # the leaf's share of its tier's sidecar, by packed rows
                buf = self.kv_domain.sidecar[s.tier.value]
                side = s.rows * sum(v.nbytes // v.shape[0]
                                    for v in buf.values())
            leaves.append(f"  {s.path} {s.shape} {s.dtype} [{s.region}, "
                          f"{s.tier.value}]: payload={s.nbytes}B "
                          f"sidecar={side}B")
        return (f"params[{pol}]: {ps.summary()}\n"
                f"kv_cache[{self.kv_tier.value}]: {ks.summary()}\n"
                + "\n".join(leaves) + "\n"
                f"pages={self.cache.n_pages} x {self._page_size} tokens, "
                f"slots={self.cache.slots}, "
                f"max_pages/slot={self.cache.max_pages_per_slot}")

    # ------------------------------------------------------------ prefill
    def _run_prefill(self, req: Request, pages: np.ndarray
                     ) -> Tuple[int, bool]:
        # only prompt pages are written at prefill; decode fills the rest
        n_pp = -(-req.prompt_len // self._page_size)
        sb = n_pp * self._page_size
        tokens = np.zeros((1, sb), np.int32)
        tokens[0, :req.prompt_len] = req.prompt
        with TraceAnnotation("serve.prefill", rid=req.rid,
                             prompt_len=req.prompt_len, pages=n_pp):
            a, b, first, ok = self._prefill(
                self._params(), *self._pools(), jnp.asarray(tokens),
                jnp.int32(req.prompt_len), jnp.asarray(pages[:n_pp]))
            first = int(first)
            ok = bool(ok)
        self._adopt(a, b)
        return first, ok

    # -------------------------------------------------------- fault plane
    def _inject_one(self, counters: SLOCounters) -> None:
        pb = self.param_domain.stats().payload_bytes
        kb = self.kv_domain.stats().payload_bytes
        if self.rng.random() < pb / max(pb + kb, 1):
            self.param_domain, _ = self.param_domain.inject(self.rng, 1)
            counters.injected_params += 1
        else:
            self.kv_domain, _ = self.kv_domain.inject(self.rng, 1)
            self._adopt_kv_payload()
            counters.injected_kv += 1
            self._kv_stale = True

    def _inject_bound(self, strike: BoundStrike, counters: SLOCounters
                      ) -> None:
        """Fire one trace-bound strike into its resolved domain/leaf/word
        (the replay twin of ``_inject_one``)."""
        if strike.domain == "params":
            self.param_domain = self.param_domain.apply_plan(
                strike.path, strike.plan(), record_hard=strike.hard)
            counters.injected_params += 1
        else:
            self.kv_domain = self.kv_domain.apply_plan(
                strike.path, strike.plan(), record_hard=strike.hard)
            self._adopt_kv_payload()
            counters.injected_kv += 1
            self._kv_stale = True

    def _scrub_params(self, counters: SLOCounters) -> None:
        self.param_domain, rep = self.param_domain.scrub()
        c, u = rep.totals()
        counters.params_corrected += c
        counters.params_detected += u
        needs = rep.needs_recovery()
        if needs:
            # peer mode: params are data-parallel-replicated, so the
            # in-memory clean copy *is* the peer replica's image — same
            # bits as the disk reload, but billed at the peer-copy MTTR
            resp = (Response.PEER_COPY if self.peer_recovery
                    else Response.RELOAD_CLEAN_COPY)
            self.param_domain, events = self.param_domain.recover(
                rep, clean_copy=lambda p: self._clean[p], response=resp,
                needs=needs)
            n_peer = sum(1 for e in events
                         if e["action"].startswith("peer_copy"))
            counters.charge_peer_recoveries(n_peer)
            counters.charge_recoveries(len(events) - n_peer)

    def _scrub_kv(self, counters: SLOCounters) -> None:
        self.kv_domain, rep = self.kv_domain.scrub()
        counters.kv_pages_checked += self.cache.n_pages
        c, u = rep.totals()
        counters.kv_corrected += c
        counters.kv_detected += u
        changed = bool(c)                # SEC-DED repaired pool words
        # errors found: the full refresh re-encodes the pool, so a Par+R
        # detection with no recovery is counted once, not every check
        self._kv_stale |= bool(c or u)
        needs = rep.needs_recovery()
        if self.peer_recovery and needs and self._kv_peer is not None:
            # the peer snapshot is the post-refresh pool image — the
            # state a replica that didn't take this storm's strikes
            # holds — so the gather restores flagged pool leaves
            # bit-identically without a disk round-trip
            peer = self._kv_peer
            self.kv_domain, events = self.kv_domain.recover(
                rep, clean_copy=lambda p: peer[p],
                response=Response.PEER_COPY, needs=needs)
            counters.charge_peer_recoveries(len(events))
            changed = True
        if changed:
            self._adopt_kv_payload()

    def _crash_reset(self, router: RequestRouter, counters: SLOCounters
                     ) -> None:
        """Non-finite logits: the server 'crashed'. Charge the MTTR,
        reload params from the clean copy, wipe the KV pools, and requeue
        every in-flight request from scratch."""
        counters.charge_crash()
        clean = {s.path for s in self.param_domain.spec.leaves}
        leaves = [jnp.asarray(self._clean[s.path])
                  for s in self.param_domain.spec.leaves]
        payload = jax.tree_util.tree_unflatten(
            self.param_domain.spec.treedef, leaves)
        pol = (self.params_policy if self.params_policy is not None
               else HRMPolicy("unprotected", {}))
        self.param_domain = MemoryDomain.protect(payload, pol)
        assert clean == {s.path for s in self.param_domain.spec.leaves}
        for req in reversed(self.sched.evict_all()):
            router.requeue(req)
        self.cache.adopt_pools({n: jnp.zeros_like(p)
                                for n, p in self.cache.pools.items()})
        self.kv_domain = MemoryDomain.protect(self._kv_state(),
                                              kv_policy(self.kv_tier))
        if self.kv_tier is not Tier.NONE:
            counters.kv_pages_encoded += self.cache.n_pages
        self._kv_stale = True
        self._kv_peer = None             # stale after the restart

    # ---------------------------------------------------------- iteration
    def _iteration(self, router: RequestRouter, counters: SLOCounters,
                   storm: deque, now: float, it: int) -> float:
        """One engine iteration; returns the served clock after it."""
        n_pages = self.cache.n_pages
        written: List[np.ndarray] = []   # pages this iteration writes
        # 1. access-path KV check: catches strikes injected after the
        #    previous refresh, before any re-encode can launder them
        if self.kv_tier is not Tier.NONE:
            with TraceAnnotation("serve.kv_check", pages=n_pages):
                self._scrub_kv(counters)
        # 2. params patrol scrub on the policy cadence
        if (self.params_policy is not None and self.scrub_every > 0
                and it > 0 and it % self.scrub_every == 0):
            with TraceAnnotation("serve.params_scrub"):
                self._scrub_params(counters)
        # 3. route arrivals, admit prefills into free slots
        router.poll(now)
        admitted = 0
        while admitted < self.max_prefills_per_step:
            req = router.peek()
            if req is None:
                break
            if self.cache.pages_needed(req.footprint_tokens()) > \
                    self.cache.max_pages_per_slot:
                router.take()            # can never fit: shed it
                router.shed.append(req)
                continue
            if not self.sched.can_admit(req):
                break
            router.take()
            slot = self.sched.free_slot()
            pages = self.cache.alloc(slot, req.footprint_tokens())
            first, ok = self._run_prefill(req, pages)
            counters.prefills += 1
            n_pp = self.cache.pages_needed(req.prompt_len)
            counters.kv_pages_written += n_pp
            written.append(pages[:n_pp])
            now = self._advance(now, self.service.prefill_cost(
                req.prompt_len))
            if not ok:
                self.cache.release(slot)
                router.requeue(req)
                self._crash_reset(router, counters)
                break
            self.sched.admit(req, first, now)
            admitted += 1
        # 4. one continuous-batching decode step over every slot
        if self.sched.n_active:
            tokens, pos = self.sched.batch_inputs()
            active = self.sched.n_active
            # cached tokens the step reads: positions 0..pos of each
            # active slot, its new token included
            ctx = sum(s.pos + 1 for s in self.sched.slots if s is not None)
            # the page each slot's new K/V lands in (the null page for
            # an inactive slot): every slot writes one
            written.append(self.cache.table[
                np.arange(len(pos)), pos // self._page_size])
            with TraceAnnotation("serve.decode", active=active,
                                 ctx_tokens=ctx):
                a, b, nxt, ok = self._decode(
                    self._params(), *self._pools(),
                    self.cache.device_table(), jnp.asarray(tokens),
                    jnp.asarray(pos))
                nxt = np.asarray(nxt)
                ok = bool(ok)
            self._adopt(a, b)
            counters.decode_steps += 1
            counters.decode_ctx_tokens += ctx
            counters.kv_pages_written += active    # one page per slot
            now = self._advance(now, self.service.decode_cost(active))
            if ok:
                self.sched.record_step(nxt, now)
            else:
                self._crash_reset(router, counters)
        elif not router.queue:
            nxt_t = router.next_arrival()
            if nxt_t is not None and nxt_t > now:
                self._origin -= nxt_t - now  # idle: jump to next arrival
                now = nxt_t
        # 5. write-path ECC: re-encode the KV sidecar over this
        #    step's legitimate writes, the pages written or the pool
        if self.kv_tier is not Tier.NONE:
            full = self._kv_stale or not self._kv_paged
            n_enc = n_pages if full else sum(len(w) for w in written)
            with TraceAnnotation("serve.kv_refresh", pages=n_enc,
                                 full=int(full)):
                if full:
                    self.kv_domain = self.kv_domain.refresh(
                        self._kv_state())
                else:
                    dom = self.kv_domain.adopt(self._kv_state())
                    for w in written:
                        dom = dom.refresh_pages(w)
                    self.kv_domain = dom
            counters.kv_pages_encoded += n_enc
            counters.kv_full_refreshes += int(full)
            self._kv_stale = False
        else:
            self.kv_domain = self.kv_domain.adopt(self._kv_state())
        if self.peer_recovery:
            # peer image: a replica that doesn't take this storm's
            # strikes holds exactly this post-write pool state
            # (a copy where the next step takes the pools over)
            keep = jnp.copy if _donates_pools(self.cfg) else (lambda a: a)
            self._kv_peer = {f"kv_cache/{n}": keep(p)
                             for n, p in self.cache.pools.items()}
        # 6. the storm: fire every error due by the current clock
        if storm and storm[0][0] <= now:
            with TraceAnnotation("serve.inject"):
                while storm and storm[0][0] <= now:
                    _, strike = storm.popleft()
                    if strike is None:
                        self._inject_one(counters)
                    else:
                        self._inject_bound(strike, counters)
        if self.debug_invariants:
            self.cache.check_invariants()
        return now

    # ---------------------------------------------------------------- run
    def run(self, trace: List[Request], *, storm_errors: int = 0,
            error_trace: Optional[ErrorTrace] = None,
            month_minutes: float = MINUTES_PER_MONTH,
            max_iters: int = 200_000) -> Tuple[SLOReport, Dict[int,
                                                               List[int]]]:
        """Serve the trace to completion. Returns the SLO report and a
        ``{rid: generated tokens}`` map (for golden comparison).

        ``error_trace`` replaces the Poisson storm with a recorded error
        stream: its events are bound onto the params + KV domains (one
        shared physical address space), compressed onto the arrival
        window, and fired deterministically — two runs with the same
        trace produce identical availability/incorrect numbers."""
        router = RequestRouter(trace, max_queue=self.max_queue)
        counters = SLOCounters()
        last_arrival = max((r.arrival for r in trace), default=0.0)
        span = max(last_arrival, 1e-6)
        if error_trace is not None:
            bound = bind_trace(error_trace,
                               {"params": self.param_domain,
                                "kv_cache": self.kv_domain}, span=span)
            storm = deque((s.t, s) for s in bound)
        else:
            storm = deque((t, None) for t in np.sort(
                self.rng.uniform(0.0, span, storm_errors)))
        now = 0.0
        self._origin = time.perf_counter()
        it = 0
        while not (router.drained and self.sched.n_active == 0):
            if it >= max_iters:
                raise RuntimeError(f"engine wedged after {max_iters} "
                                   f"iterations")
            with StepTraceAnnotation("serve.iteration", step_num=it):
                now = self._iteration(router, counters, storm, now, it)
            it += 1
        # drain the storm tail + one final scrub so every injected error
        # is detected/recovered and accounted before availability is read
        while storm:
            _, strike = storm.popleft()
            if strike is None:
                self._inject_one(counters)
            else:
                self._inject_bound(strike, counters)
        if self.kv_tier is not Tier.NONE:
            self._scrub_kv(counters)
        if self.params_policy is not None:
            self._scrub_params(counters)
        report = build_report(
            self.sched.completed, n_requests=len(trace),
            shed=len(router.shed), elapsed=now, counters=counters,
            peak_active=self.sched.peak_active,
            peak_queue=router.peak_queue, month_minutes=month_minutes)
        responses = {c.req.rid: list(c.tokens)
                     for c in self.sched.completed}
        return report, responses
