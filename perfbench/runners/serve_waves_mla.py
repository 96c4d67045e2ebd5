"""Serving in closed waves, as ``serve_waves`` (loaded from it: the
waves, warm-up, window, traced work and check are its own), for a
DeepSeek-V2 latent-attention configuration: the program's
``ModelConfig`` comes from the configuration's latent-attention and
expert-share keys, and the served weights are the reference's
checkpoint-form weights laid out as the program's tree, with no
multipliers to fold.

The configuration holds this chip's share of each layer's routed
experts (``n_routed_experts`` of them from ``first_held_expert`` on, of
``router_experts`` that the router scores); the program and the
reference both leave out the other chips' part.
"""
from __future__ import annotations

import bench

sw = bench.load_module("runners", "serve_waves")
PAGE_BLOCK = 128                 # the pool's pages, a multiple of this


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for the configuration file."""
    from repro.configs.base import MLAConfig, ModelConfig, MoEConfig
    prog = cfg.get("program", {})
    rs = cfg["rope_scaling"]
    if cfg["routed_scaling_factor"] != 1 or cfg["q_lora_rank"] is not None \
            or cfg["moe_layer_freq"] != 1:
        raise ValueError("the program serves routed_scaling_factor 1, no "
                         "query compression and an expert layer after "
                         "each leading dense one")
    return ModelConfig(
        name=cfg.get("name", "bench"), family="moe",
        n_layers=cfg["num_hidden_layers"],
        n_dense_layers=cfg["first_k_dense_replace"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        act="swiglu", rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        moe=MoEConfig(n_experts=cfg["router_experts"],
                      top_k=cfg["num_experts_per_tok"],
                      d_expert=cfg["moe_intermediate_size"],
                      n_shared=cfg["n_shared_experts"],
                      capacity_factor=float(prog["moe_capacity_factor"]),
                      norm_topk=bool(cfg["norm_topk_prob"]),
                      first_held=cfg["first_held_expert"],
                      n_held=cfg["n_routed_experts"]),
        mla=MLAConfig(kv_lora_rank=cfg["kv_lora_rank"],
                      qk_nope_head_dim=cfg["qk_nope_head_dim"],
                      qk_rope_head_dim=cfg["qk_rope_head_dim"],
                      v_head_dim=cfg["v_head_dim"],
                      rope_factor=float(rs["factor"]),
                      rope_original_max=int(
                          rs["original_max_position_embeddings"]),
                      beta_fast=float(rs["beta_fast"]),
                      beta_slow=float(rs["beta_slow"]),
                      mscale=float(rs["mscale"]),
                      mscale_all_dim=float(rs["mscale_all_dim"]),
                      q_block=int(prog.get("prefill_query_block", 1024))),
        param_dtype=prog.get("param_dtype", "bfloat16"),
        compute_dtype=prog.get("compute_dtype", "bfloat16"))


def program_weights(w: dict, n_dense: int) -> dict:
    """Checkpoint-form weights (``reference/mla_moe.py``) as the
    program's tree: the leading dense layers, then the expert blocks."""
    lay = w["layers"]

    def layers(sl, ffn):
        return {"norm1": lay["attn_norm"][sl],
                "attn": {k: lay[k][sl] for k in ("wq", "wkv_a", "kv_norm",
                                                 "wkv_b", "wo")},
                "norm2": lay["mlp_norm"][sl], **ffn}

    m = w["moe"]
    out = {"embed": w["embed"], "final_norm": w["final_norm"],
           "head": w["head"],
           "blocks": layers(slice(n_dense, None), {"moe": {
               "router": m["router"], "wi": m["wi"], "wg": m["wg"],
               "wo": m["wo"],
               "shared": {"wi": m["shared_wi"], "wg": m["shared_wg"],
                          "wo": m["shared_wo"]}}})}
    if n_dense:
        out["dense"] = layers(slice(0, n_dense), {"mlp": w["dense"]})
    return out


class Cell(sw.Cell):
    def build_engine(self, weights):
        from repro.serve import OnlineEngine
        t = self.traffic
        params_policy, kv_tier = sw.program_policy(self.policy)
        self.wave = sw.make_wave(t, self.seed, self.cfg["vocab_size"])
        self.sizes = [(r.prompt_len, r.max_new) for r in self.wave]
        # whole blocks of the ECC kernels' rows in every pool leaf: a
        # latent page is 8 sidecar rows and a rotary-key page 1, and a
        # leaf that is not whole blocks of 128 rows is padded in the KV
        # check's packing, which then doubles its temporaries (compiles
        # for a described v5e)
        self.pages = -(-sw.n_pages(t["page_size"], self.sizes)
                       // PAGE_BLOCK) * PAGE_BLOCK
        return OnlineEngine(
            self.model, weights, slots=t["slots"],
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
        self.model = model_config(self.cfg)
        d = self.ref.dims(self.cfg)
        make = jax.jit(lambda key: program_weights(self.ref.weights(key, d),
                                                   d.n_dense))
        weights = make(bench.jax_key(self.seed, sw.WEIGHTS_SALT))
        jax.block_until_ready(weights)
        self.engine = self.build_engine(weights)
        del weights
        warm = sw.make_warmup(self.traffic, self.policy, self.seed,
                              self.cfg["vocab_size"])
        with jax.profiler.TraceAnnotation("warmup"):
            if self._serve(warm)[2]:
                raise RuntimeError("the warm-up wave was not served in full")
        bench.log(f"[serve] engine: {self.engine.describe()}")


def make(cfg: dict, traffic: dict, policy: dict, seed: int, peaks: dict
         ) -> Cell:
    return Cell(cfg, traffic, policy, seed, peaks)
