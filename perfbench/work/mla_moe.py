"""Operations and bytes that serving DeepSeek-V2's latent-attention
mixture-of-experts transformer needs, counted from the configuration's
shapes and the cell's policy, so that they stay the same whatever
implements them.

Parameters are those this chip holds: the held routed experts only
(``n_routed_experts`` of them), the router at its full width, the
leading dense layers, the shared experts and the untied head. A token
costs 2 FLOPs per active parameter (the embedding gather is free; the
held experts count at the share of a token's top-k that lands on them,
k . held / router experts), plus the attention's per-position FLOPs: the
expanded form in prefill, 2 H ((dn + dr) + dv) per position and layer,
and the absorbed form in decode, 2 H ((r + dr) + r) over the cached
latents. The cache holds L (r + dr) bfloat16 values per token. Sidecar
bytes per payload byte are those of the tiers: SEC-DED 8 check bits per
64-bit word, parity one bit per word.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

SIDECAR_PER_BYTE = {"none": 0.0, "parity_r": 1.0 / 64, "secded": 8.0 / 64}
BF16 = 2
F32 = 4


def _d(cfg: dict):
    L, nd = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    return (L, nd, L - nd, cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"])


def param_bytes(cfg: dict) -> Dict[str, int]:
    """Bytes of each region of the served weights (bfloat16, router f32).
    ``mlp`` is the dense layers' MLP and the shared experts."""
    L, nd, Lm, D, H, r, dn, dr, dv = _d(cfg)
    V, F = cfg["vocab_size"], cfg["moe_intermediate_size"]
    E, Eh = cfg["router_experts"], cfg["n_routed_experts"]
    attn = D * H * (dn + dr) + D * (r + dr) + r * H * (dn + dv) + H * dv * D
    return {
        "embed": 2 * V * D * BF16,
        "attn": L * (attn + r) * BF16,
        "norm": (2 * L + 1) * D * BF16,
        "mlp": (nd * 3 * D * cfg["intermediate_size"]
                + Lm * 3 * D * cfg["n_shared_experts"] * F) * BF16,
        "experts": Lm * (3 * Eh * D * F * BF16 + D * E * F32),
    }


def active_params(cfg: dict) -> float:
    """Parameters one token multiplies by: all this chip holds but the
    embedding gather, the held experts at the share of its top-k that
    lands on them."""
    L, nd, Lm, D, *_ = _d(cfg)
    F, V = cfg["moe_intermediate_size"], cfg["vocab_size"]
    E, Eh, k = (cfg["router_experts"], cfg["n_routed_experts"],
                cfg["num_experts_per_tok"])
    b = param_bytes(cfg)
    held = Lm * 3 * Eh * D * F
    router = Lm * D * E
    n = (sum(b.values()) - router * F32) // BF16 + router - V * D - held
    return float(n + held * k / E)


def kv_bytes_per_token(cfg: dict) -> int:
    L, _, _, _, _, r, _, dr, _ = _d(cfg)
    return L * (r + dr) * BF16


def prefill_attn_flops_per_position(cfg: dict) -> float:
    L, _, _, _, H, _, dn, dr, dv = _d(cfg)
    return 2.0 * L * H * (dn + dr + dv)


def decode_attn_flops_per_position(cfg: dict) -> float:
    L, _, _, _, H, r, _, dr, _ = _d(cfg)
    return 2.0 * L * H * (r + dr + r)


def _tier(tiers: Dict[str, str], region: str) -> str:
    # the dense MLP and shared experts are params/mlp, which
    # detect_recover_l puts under the experts' parity + reload
    if region == "mlp" and region not in tiers:
        return tiers.get("experts", "none")
    return tiers.get(region, "none")


def params_sidecar_bytes(cfg: dict, tiers: Dict[str, str]) -> float:
    return sum(b * SIDECAR_PER_BYTE[_tier(tiers, r)]
               for r, b in param_bytes(cfg).items())


def decode_weight_bytes(cfg: dict) -> int:
    """Weights a decode step reads: all but the embedding table, of which
    it gathers one row per slot."""
    return sum(param_bytes(cfg).values()) \
        - cfg["vocab_size"] * cfg["hidden_size"] * BF16


def decode_hbm_bytes(cfg: dict, decode_steps: int, ctx_tokens: int) -> float:
    """Least HBM bytes of ``decode_steps`` decode steps that read
    ``ctx_tokens`` cached tokens in all: the weights once per step and
    each cached token's latent and rotary key once."""
    return (decode_steps * decode_weight_bytes(cfg)
            + ctx_tokens * kv_bytes_per_token(cfg))


def _bound(flops: float, nbytes: float, peaks: dict) -> float:
    return max(flops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])


def wave_need_seconds(cfg: dict, policy: dict,
                      requests: Sequence[Tuple[int, int]],
                      decode_steps: int, peaks: dict) -> Dict[str, float]:
    """Least device time a wave needs, by the larger of its FLOPs over the
    peak and its bytes over the bandwidth, decode and prefill apart.

    ``requests`` are (prompt length, tokens served). A decode step reads
    the weights once (``decode_weight_bytes``) and the cache of every
    live position once; a prefill reads the weights once and writes the
    prompt's cache. Protection adds the sidecars that the policy's
    cadence reads or writes: the live cache's each step, the new tokens',
    and the weights' every ``params_scrub_every`` steps.
    """
    P = sum(param_bytes(cfg).values())
    Pd = decode_weight_bytes(cfg)
    kv = kv_bytes_per_token(cfg)
    kv_side = SIDECAR_PER_BYTE[policy.get("kv_tier", "none")]
    every = policy.get("params_scrub_every") or 0
    p_side = params_sidecar_bytes(cfg, policy.get("params_tiers", {}))
    n_act = active_params(cfg)
    a_pre = prefill_attn_flops_per_position(cfg)
    a_dec = decode_attn_flops_per_position(cfg)
    live = 0.0
    dec_tokens = 0
    pre_s = 0.0
    for prompt, served in requests:
        steps = served - 1
        live += steps * (prompt + 1) + steps * (steps - 1) / 2
        dec_tokens += steps
        pre_flops = 2 * n_act * prompt + a_pre * prompt * (prompt + 1) / 2
        pre_bytes = P + prompt * kv * (1 + kv_side)
        pre_s += _bound(pre_flops, pre_bytes, peaks)
    dec_flops = 2 * n_act * dec_tokens + a_dec * live
    dec_bytes = (decode_steps * Pd + live * kv * (1 + kv_side)
                 + dec_tokens * kv * kv_side
                 + (decode_steps / every * p_side if every else 0.0))
    dec_s = _bound(dec_flops, dec_bytes, peaks)
    return {"decode_s": dec_s, "prefill_s": pre_s, "total_s": dec_s + pre_s,
            "decode_bytes": dec_bytes, "decode_flops": dec_flops}


def ecc_kernel_need_seconds(cfg: dict, policy: dict, pool_bytes: int,
                            kv_checks: int, kv_encodes: int,
                            params_scrubs: int, peaks: dict) -> float:
    """Least time of the ECC kernels as the program calls them: a check or
    scrub reads its payload and sidecar once, an encode reads the payload
    once and writes the sidecar once."""
    kv_side = SIDECAR_PER_BYTE[policy.get("kv_tier", "none")]
    kv_bytes = pool_bytes * (1 + kv_side) if kv_side else 0.0
    tiers = policy.get("params_tiers", {})
    scrub = sum(b * (1 + SIDECAR_PER_BYTE[_tier(tiers, r)])
                for r, b in param_bytes(cfg).items()
                if _tier(tiers, r) != "none")
    nbytes = (kv_checks + kv_encodes) * kv_bytes + params_scrubs * scrub
    return nbytes / peaks["hbm_bytes_per_s"]
