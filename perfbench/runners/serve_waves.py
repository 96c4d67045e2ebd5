"""Serving in closed waves: every request of a wave is due at t=0, and
``OnlineEngine.run`` serves the wave to completion on one engine that the
whole run reuses. Every wave of a run is the same trace, drawn from the
seed, and the window holds whole waves only: none starts that the
longest wave so far says cannot end inside it.

A wave has the same sizes for every seed: the bucket counts follow the
mix's weights (largest remainder), the seed pairs prompt lengths with
output lengths and draws the tokens, and the requests go in longest
output first. The KV pool holds the whole wave at once.

The weights are made from the seed in the checkpoint's form and, in the
same jitted call, have the configuration's scalar multipliers folded in
(``fold_multipliers``): the program's block has none, so it serves the
configuration's function through its own equations.

Correctness: once the window has closed and the program's state is
freed, a sample of the last wave's requests drawn from the seed, the
longest among them, runs through the plain reference. The gap by which
each served token's logit lies below the reference's best is read; their
mean is held to its limit.
"""
from __future__ import annotations

import gc
import json
import math
import time
from typing import Dict, List, Tuple

import numpy as np

import bench

WARMUP_PROMPT_SALT = 11
PAIRING_SALT = 12
TOKENS_SALT = 13
SAMPLE_SALT = 14
WEIGHTS_SALT = 1


def bucket_counts(weights, n: int) -> np.ndarray:
    """Counts per bucket summing to ``n`` (largest remainder)."""
    w = np.asarray(weights, np.float64)
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts), kind="stable")[:n - counts.sum()]:
        counts[i] += 1
    return counts


def wave_sizes(traffic: dict, seed: int) -> List[Tuple[int, int]]:
    """(prompt length, output length) of each request, in serving order."""
    n = traffic["wave_requests"]
    prompts = np.repeat(traffic["prompt_buckets"],
                        bucket_counts(traffic["prompt_weights"], n))
    outs = np.repeat(traffic["output_buckets"],
                     bucket_counts(traffic["output_weights"], n))
    prompts = bench.np_rng(seed, PAIRING_SALT).permutation(prompts)
    order = np.argsort(-outs, kind="stable")
    return [(int(prompts[i]), int(outs[i])) for i in order]


def make_wave(traffic: dict, seed: int, vocab: int):
    from repro.serve.traffic import Request
    rng = bench.np_rng(seed, TOKENS_SALT)
    return [Request(rid=i, arrival=0.0,
                    prompt=rng.integers(0, vocab, p, dtype=np.int32),
                    max_new=o)
            for i, (p, o) in enumerate(wave_sizes(traffic, seed))]


def make_warmup(traffic: dict, policy: dict, seed: int, vocab: int):
    """Two requests per prompt bucket, long enough to reach the params
    scrub's cadence, so that every program of the window compiles here
    (the first prefill sees the freshly made pools, the second the pools
    a program wrote, and the two compile apart)."""
    from repro.serve.traffic import Request
    rng = bench.np_rng(seed, WARMUP_PROMPT_SALT)
    new = min(max(10, (policy.get("params_scrub_every") or 0) + 2),
              max(traffic["output_buckets"]))
    return [Request(rid=i, arrival=0.0,
                    prompt=rng.integers(0, vocab, p, dtype=np.int32),
                    max_new=new)
            for i, p in enumerate(list(traffic["prompt_buckets"]) * 2)]


def n_pages(page_size: int, sizes) -> int:
    """Pages that hold every request of the wave at once, and the null
    page."""
    return sum(-(-(p + o) // page_size) for p, o in sizes) + 1


def fold_multipliers(w: dict, d) -> dict:
    """Checkpoint weights in the program's form. Its block computes
    x = embed[t]; x += o Wo with scores q.k / sqrt(dh); x += MoE(h);
    logits = rms(x) nf (embed^T or head). Granite's multipliers are
    linear in one weight each, so scaling that weight gives Granite's
    function: embed by m_emb, Wq by m_att sqrt(dh), both output weights
    by m_res, and the final norm by 1 / s_logit (and 1 / m_emb where the
    head is the scaled embedding)."""
    import jax.numpy as jnp

    def scale(a, c):
        return (a.astype(jnp.float32) * c).astype(a.dtype)

    b = w["blocks"]
    out = {
        "embed": scale(w["embed"], d.m_emb),
        "blocks": {
            "norm1": b["norm1"], "norm2": b["norm2"],
            "attn": dict(b["attn"],
                         wq=scale(b["attn"]["wq"], d.m_att * math.sqrt(d.dh)),
                         wo=scale(b["attn"]["wo"], d.m_res)),
            "moe": dict(b["moe"], wo=scale(b["moe"]["wo"], d.m_res)),
        },
        "final_norm": scale(w["final_norm"],
                            1.0 / (d.s_logit * (d.m_emb if d.tied else 1.0))),
    }
    if not d.tied:
        out["head"] = w["head"]
    return out


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for the configuration file."""
    from repro.configs.base import ModelConfig, MoEConfig
    prog = cfg.get("program", {})
    return ModelConfig(
        name=cfg.get("name", "bench"), family="moe",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        act="swiglu", rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        moe=MoEConfig(n_experts=cfg["num_local_experts"],
                      top_k=cfg["num_experts_per_tok"],
                      d_expert=cfg["intermediate_size"],
                      capacity_factor=float(prog.get("moe_capacity_factor",
                                                     1.25))),
        param_dtype=prog.get("param_dtype", "bfloat16"),
        compute_dtype=prog.get("compute_dtype", "bfloat16"))


def program_policy(policy: dict):
    from repro.core import Tier
    from repro.core import policy as pol
    name = policy.get("params_policy")
    return (getattr(pol, name)() if name else None), Tier(policy["kv_tier"])


class Cell:
    """Set-up, window and check of one serving cell."""

    def __init__(self, cfg: dict, traffic: dict, policy: dict, seed: int,
                 peaks: dict):
        self.cfg, self.traffic, self.policy, self.seed = (cfg, traffic,
                                                          policy, seed)
        self.peaks = peaks
        self.ref = bench.load_module("reference", cfg["reference"])
        self.work = bench.load_module("work", cfg["kind"])
        self.engine = None
        self.last: Dict[int, List[int]] = {}

    # ----------------------------------------------------------- set-up
    def build_engine(self, weights):
        from repro.serve import OnlineEngine
        t = self.traffic
        params_policy, kv_tier = program_policy(self.policy)
        self.wave = make_wave(t, self.seed, self.cfg["vocab_size"])
        self.sizes = [(r.prompt_len, r.max_new) for r in self.wave]
        self.pages = n_pages(t["page_size"], self.sizes)
        return OnlineEngine(
            model_config(self.cfg), weights, slots=t["slots"],
            page_size=t["page_size"],
            max_prompt_len=max(t["prompt_buckets"]),
            max_new_cap=max(t["output_buckets"]), n_pages=self.pages,
            policy=params_policy, kv_tier=kv_tier,
            scrub_every=self.policy.get("params_scrub_every") or 0,
            clock="wall",
            max_prefills_per_step=t["max_prefills_per_step"],
            seed=self.seed)

    def setup(self) -> None:
        import jax
        d = self.ref.dims(self.cfg)
        make = jax.jit(lambda key: fold_multipliers(self.ref.weights(key, d),
                                                    d))
        weights = make(bench.jax_key(self.seed, WEIGHTS_SALT))
        jax.block_until_ready(weights)
        self.engine = self.build_engine(weights)
        del weights
        warm = make_warmup(self.traffic, self.policy, self.seed,
                           self.cfg["vocab_size"])
        with jax.profiler.TraceAnnotation("warmup"):
            if self._serve(warm)[2]:
                raise RuntimeError("the warm-up wave was not served in full")
        bench.log(f"[serve] engine: {self.engine.describe()}")

    def _serve(self, trace):
        eng = self.engine
        eng.sched.completed.clear()
        report, resp = eng.run(trace)
        missing = sum(1 for r in trace
                      if len(resp.get(r.rid, ())) != r.max_new)
        return report, resp, missing

    # ----------------------------------------------------------- window
    def window(self, seconds: float, tracer=None) -> bench.WindowResult:
        import jax
        waves, tokens, failed, longest = 0, 0, 0, 0.0
        steps = prefills = 0
        traced = {}
        t0 = time.perf_counter()
        while True:
            elapsed = time.perf_counter() - t0
            if waves and elapsed + longest > seconds:
                break
            trace_this = tracer is not None and waves == 0
            if trace_this:
                tracer.start()
            w0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("wave"):
                report, resp, missing = self._serve(self.wave)
            wave_s = time.perf_counter() - w0
            if trace_this:
                tracer.stop()
                traced = self._traced_work(report, wave_s)
            longest = max(longest, wave_s)
            waves += 1
            failed += missing
            tokens += sum(len(t) for t in resp.values())
            steps += report.counters["decode_steps"]
            prefills += report.counters["prefills"]
            self.last = resp
        window_s = time.perf_counter() - t0
        served = sum(o for _, o in self.sizes)
        bench.log(f"[serve] window: {waves} waves of {len(self.wave)} "
                  f"requests in {window_s:.6f} s (longest wave "
                  f"{longest:.6f} s); {steps} decode steps, {prefills} "
                  f"prefills; {tokens} tokens")
        return bench.WindowResult(
            seconds=window_s,
            end_to_end={"serve_tokens_per_s": tokens / window_s},
            attempted=waves * len(self.wave), failed=failed,
            counters={"waves": waves, "decode_steps": steps,
                      "prefills": prefills, "tokens": tokens,
                      "wave_tokens": served,
                      "mean_active_slots":
                          (tokens - waves * len(self.wave)) / max(steps, 1)},
            traced=traced)

    def _traced_work(self, report, wave_s: float) -> dict:
        """Work of the traced wave, for the per-layer readers."""
        steps = report.counters["decode_steps"]
        need = self.work.wave_need_seconds(
            self.cfg, self.policy, self.sizes, steps, self.peaks)
        kv_on = self.policy["kv_tier"] != "none"
        every = self.policy.get("params_scrub_every") or 0
        iters = steps                           # every iteration decodes
        scrubs = ((iters - 1) // every + 1) if every else 0
        pool = self.pages * self.traffic["page_size"] * \
            self.work.kv_bytes_per_token(self.cfg)
        ecc = self.work.ecc_kernel_need_seconds(
            self.cfg, self.policy, pool,
            kv_checks=(iters + 1) if kv_on else 0,
            kv_encodes=iters if kv_on else 0, params_scrubs=scrubs,
            peaks=self.peaks)
        return {"iterations": iters, "wave_s": wave_s,
                "need_s": need["total_s"], "ecc_need_s": ecc}

    # ------------------------------------------------------------ check
    def free(self) -> None:
        self.engine = None
        gc.collect()

    def check(self) -> List[bench.Check]:
        readings = self.check_readings(control=False)
        # the widest gap is a tail that the control does not separate
        # from sound runs: it is printed, and the mean gap is compared
        bench.log(f"[serve] reference readings: {json.dumps(readings)}")
        return bench.checks(readings, self.traffic["check"]["limits"])

    def sample(self) -> list:
        """Served requests to compare: the longest of the last wave and a
        draw from the seed."""
        n = self.traffic["check"]["sample_requests"]
        rids = sorted(self.last)
        by_len = max(rids, key=lambda r: (self.sizes[r][0] + self.sizes[r][1],
                                          -r))
        rest = [r for r in rids if r != by_len]
        pick = bench.np_rng(self.seed, SAMPLE_SALT).choice(
            len(rest), size=min(n - 1, len(rest)), replace=False)
        chosen = [by_len] + [rest[i] for i in sorted(pick)]
        return [self.ref.Served(self.wave[r].prompt,
                                np.asarray(self.last[r], np.int32))
                for r in chosen]

    def check_readings(self, control: bool) -> dict:
        served = self.sample()
        weights = self.ref.make_weights(
            self.cfg, bench.jax_key(self.seed, WEIGHTS_SALT))
        res = self.ref.logit_gaps(
            self.cfg, weights, served,
            t_pad=self.ref.sequence_pad(self.traffic),
            p_pad=max(self.traffic["output_buckets"]), control=control)
        del weights
        return res


def make(cfg: dict, traffic: dict, policy: dict, seed: int, peaks: dict
         ) -> Cell:
    return Cell(cfg, traffic, policy, seed, peaks)
