"""Operations and bytes that serving a mixture-of-experts transformer
needs, counted from the configuration's shapes and the cell's policy, so
that they stay the same whatever implements them.

The arithmetic of active parameters and attention follows the usual
model-FLOPs yardstick: a token costs 2 FLOPs per active parameter (the
embedding gather is free, the output head counts, experts count at
top-k/experts) plus 4 * layers * heads * head_dim FLOPs per position it
attends to. Sidecar bytes per payload byte are those of the tiers:
SEC-DED 8 check bits per 64-bit word, parity one bit per word.
"""
from __future__ import annotations

from typing import Dict, Iterable, Sequence, Tuple

SIDECAR_PER_BYTE = {"none": 0.0, "parity_r": 1.0 / 64, "secded": 8.0 / 64}
BF16 = 2
F32 = 4


def param_bytes(cfg: dict) -> Dict[str, int]:
    """Bytes of each region of the served weights (bfloat16, router f32)."""
    D, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    H, K = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or D // H
    E, F, V = (cfg["num_local_experts"], cfg["intermediate_size"],
               cfg["vocab_size"])
    head = 0 if cfg.get("tie_word_embeddings") else V * D
    return {
        "embed": (V * D + head) * BF16,
        "attn": L * (D * H * dh + 2 * D * K * dh + H * dh * D) * BF16,
        "norm": (2 * L + 1) * D * BF16,
        "experts": L * (3 * E * D * F * BF16 + D * E * F32),
    }


def param_count(cfg: dict) -> int:
    b = param_bytes(cfg)
    L, D, E = (cfg["num_hidden_layers"], cfg["hidden_size"],
               cfg["num_local_experts"])
    router = L * D * E
    return (sum(b.values()) - router * F32) // BF16 + router


def active_params(cfg: dict) -> float:
    """Parameters one token multiplies by: all but the embedding gather
    (a tied table still counts once, as the head), experts at top-k of
    all."""
    D, L, V = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    E, k, F = (cfg["num_local_experts"], cfg["num_experts_per_tok"],
               cfg["intermediate_size"])
    n = param_count(cfg) - (0 if cfg.get("tie_word_embeddings") else V * D)
    all_exp = L * 3 * E * D * F
    return float(n - all_exp + all_exp * k / E)


def kv_bytes_per_token(cfg: dict) -> int:
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dh = cfg.get("head_dim") or D // H
    return cfg["num_hidden_layers"] * 2 * cfg["num_key_value_heads"] * dh \
        * BF16


def attn_flops_per_position(cfg: dict) -> float:
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dh = cfg.get("head_dim") or D // H
    return 4.0 * cfg["num_hidden_layers"] * H * dh


def params_sidecar_bytes(cfg: dict, tiers: Dict[str, str]) -> float:
    return sum(b * SIDECAR_PER_BYTE[tiers.get(r, "none")]
               for r, b in param_bytes(cfg).items())


def _bound(flops: float, nbytes: float, peaks: dict) -> float:
    return max(flops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])


def wave_need_seconds(cfg: dict, policy: dict, requests: Sequence[Tuple[int, int]],
                      decode_steps: int, peaks: dict) -> Dict[str, float]:
    """Least device time a wave needs, by the larger of its FLOPs over the
    peak and its bytes over the bandwidth, decode and prefill apart.

    ``requests`` are (prompt length, tokens served). A decode step reads
    every weight once (at these batch sizes every expert is routed to)
    and the KV of every live position once; a prefill reads the weights
    once and writes the prompt's KV. Protection adds the sidecars that
    the policy's cadence reads or writes: the live KV's parity each step,
    the new tokens' parity, and the weights' sidecar every
    ``params_scrub_every`` steps.
    """
    P = sum(param_bytes(cfg).values())
    kv = kv_bytes_per_token(cfg)
    kv_side = SIDECAR_PER_BYTE[policy.get("kv_tier", "none")]
    every = policy.get("params_scrub_every") or 0
    p_side = params_sidecar_bytes(cfg, policy.get("params_tiers", {}))
    n_act = active_params(cfg)
    a_pos = attn_flops_per_position(cfg)
    live = 0.0          # KV positions read over the wave's decode steps
    dec_tokens = 0
    pre_s = 0.0
    for prompt, served in requests:
        steps = served - 1
        live += steps * (prompt + 1) + steps * (steps - 1) / 2
        dec_tokens += steps
        pre_flops = 2 * n_act * prompt + a_pos * prompt * (prompt + 1) / 2
        pre_bytes = P + prompt * kv * (1 + kv_side)
        pre_s += _bound(pre_flops, pre_bytes, peaks)
    dec_flops = 2 * n_act * dec_tokens + a_pos * live
    dec_bytes = (decode_steps * P + live * kv * (1 + kv_side)
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
    scrub = sum(b * (1 + SIDECAR_PER_BYTE[tiers.get(r, "none")])
                for r, b in param_bytes(cfg).items()
                if tiers.get(r, "none") != "none")
    nbytes = (kv_checks + kv_encodes) * kv_bytes + params_scrubs * scrub
    return nbytes / peaks["hbm_bytes_per_s"]
