"""Plain reference of DeepSeek-V2's transformer (multi-head latent
attention, a leading dense layer, then shared and routed experts) that
the latent-attention serving cells run, and the generator of its
weights.

The weights are the benchmark's input: one jitted call makes them on the
device from ``--seed`` in the checkpoint's form (bfloat16, the router in
float32). The reference makes them again from the seed; it takes nothing
from the program.

The forward pass follows DeepSeek-V2's published modeling code
(``DeepseekV2Attention``, ``DeepseekV2YarnRotaryEmbedding``,
``MoEGate``), in float32 at the highest matmul precision, one sequence at
a time, with no cache:

    x = embed[tokens]
    per layer:  h = rms(x) n1
                q = h Wq -> per head (q_nope 128, q_pe 64)
                [c, k_pe] = h Wkv_a;  c = rms(c) n_kv
                [k_nope, v] = c Wkv_b per head
                q_pe, k_pe rotated (YaRN); k_pe shared by the heads
                x += Wo softmax(s (q_nope.k_nope + q_pe.k_pe)) v, causal
                h = rms(x) n2
                x += MLP(h)                       (the leading dense layers)
                x += sum_{e in top-k, held} p_e E_e(h) + shared(h)
                     p = softmax(h R) over every routed expert, not
                     renormalized (norm_topk_prob false)
    logits = rms(x) nf . head

YaRN: inverse frequencies blend the plain ones and those over
``factor`` by a linear ramp between the correction dims of ``beta_fast``
and ``beta_slow``; cos and sin carry mscale / mscale_all_dim (1 here);
the softmax scale is (dn + dr)^-1/2 . (0.1 mscale_all_dim ln factor + 1)^2.
Queries run in blocks of ``Q_BLOCK`` so that one block's scores fit.

Departures, each shared by the program: the rotary dimensions are split
in halves, where the published checkpoint stores them interleaved (with
seeded weights a permutation of ``Wq``'s and ``Wkv_a``'s rotary columns);
the layer holds the configuration's share of the routed experts
(``first_held_expert`` on, ``n_routed_experts`` of them), and the other
chips' experts add nothing here, as in the program.

The generator's scales keep the random model from collapsing and from
chaos: logits of unit spread, and every block adding to the residual
stream several times the embedding's size, so that every layer's
attention and experts decide the next token. Attention scores have a
spread of 3, so that attention stays selective over prompts of
thousands of positions: at spread 1 it averages over so many that the
cache hardly moves the result. The router's logits have unit spread and
the routed experts' output weights are scaled up by 1 / ``HELD_GATE``,
so that the held experts' part is about a fifth of the shared experts'.
Both were chosen on the CPU at 7 of the 9 layers and 4,096 positions
(the program in bfloat16 against this reference, and the float8-latent
control): at a router spread of 1.5 and ten times the routed output,
bfloat16 rounding flips enough top-6 choices, each carrying a whole
expert's part, that the program's gap grew 2.6-fold a layer.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0                      # largest finite float8_e4m3fn
Q_BLOCK = 1024


class Dims(NamedTuple):
    L: int                           # layers on this chip
    n_dense: int                     # leading dense layers among them
    D: int
    H: int
    r: int                           # kv_lora_rank
    dn: int                          # qk_nope_head_dim
    dr: int                          # qk_rope_head_dim
    dv: int                          # v_head_dim
    E: int                           # routed experts the router scores
    first: int                       # first held expert
    E_held: int
    k: int
    F: int                           # routed and shared expert width
    n_shared: int
    F_dense: int
    V: int
    theta: float
    eps: float
    norm_topk: bool
    routed_scale: float
    factor: float
    original_max: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float


def dims(cfg: dict) -> Dims:
    rs = cfg.get("rope_scaling") or {}
    return Dims(
        L=cfg["num_hidden_layers"], n_dense=cfg["first_k_dense_replace"],
        D=cfg["hidden_size"], H=cfg["num_attention_heads"],
        r=cfg["kv_lora_rank"], dn=cfg["qk_nope_head_dim"],
        dr=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"],
        E=cfg["router_experts"], first=cfg["first_held_expert"],
        E_held=cfg["n_routed_experts"], k=cfg["num_experts_per_tok"],
        F=cfg["moe_intermediate_size"], n_shared=cfg["n_shared_experts"],
        F_dense=cfg["intermediate_size"], V=cfg["vocab_size"],
        theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
        norm_topk=bool(cfg["norm_topk_prob"]),
        routed_scale=float(cfg["routed_scaling_factor"]),
        factor=float(rs.get("factor", 1.0)),
        original_max=int(rs.get("original_max_position_embeddings", 4096)),
        beta_fast=float(rs.get("beta_fast", 32)),
        beta_slow=float(rs.get("beta_slow", 1)),
        mscale=float(rs.get("mscale", 1.0)),
        mscale_all_dim=float(rs.get("mscale_all_dim", 0.0)))


# --------------------------------------------------------------- weights
# spread of a block's output per unit of its output weight's scale, at
# unit-spread inputs (as in reference/moe_transformer.py)
ATTN_OUT_SPREAD = 0.3
MLP_OUT_SPREAD = 0.2
SCORE_SPREAD = 3.0
ROUTER_SPREAD = 1.0                  # spread of the router's logits
HELD_GATE = 0.3                      # routed output weights scaled by 1/this


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def softmax_scale(d: Dims) -> float:
    s = (d.dn + d.dr) ** -0.5
    if d.mscale_all_dim:
        s *= _mscale(d.factor, d.mscale_all_dim) ** 2
    return s


def weights(key, d: Dims) -> dict:
    """Checkpoint-form weights from ``key`` (see the module's scales)."""
    bf = jnp.bfloat16
    Lm = d.L - d.n_dense
    # each of the 2L blocks adds sqrt(D / 2L) times the embedding's size
    block = math.sqrt(d.D / (2 * d.L))
    s_q = math.sqrt(SCORE_SPREAD) / (softmax_scale(d) * math.sqrt(d.dn + d.dr)
                                     * math.sqrt(d.D))
    shapes = {
        "embed": ((d.V, d.D), 1.0, bf),
        "attn_norm": ((d.L, d.D), None, bf),
        "wq": ((d.L, d.D, d.H * (d.dn + d.dr)), s_q, bf),
        "wkv_a": ((d.L, d.D, d.r + d.dr), 1 / math.sqrt(d.D), bf),
        "kv_norm": ((d.L, d.r), None, bf),
        "wkv_b": ((d.L, d.r, d.H * (d.dn + d.dv)), 1 / math.sqrt(d.r), bf),
        "wo": ((d.L, d.H * d.dv, d.D),
               block / (ATTN_OUT_SPREAD * math.sqrt(d.H * d.dv)), bf),
        "mlp_norm": ((d.L, d.D), None, bf),
        "dense_wi": ((d.n_dense, d.D, d.F_dense), 1 / math.sqrt(d.D), bf),
        "dense_wg": ((d.n_dense, d.D, d.F_dense), 1 / math.sqrt(d.D), bf),
        "dense_wo": ((d.n_dense, d.F_dense, d.D),
                     block / (MLP_OUT_SPREAD * math.sqrt(d.F_dense)), bf),
        "router": ((Lm, d.D, d.E), ROUTER_SPREAD / math.sqrt(d.D),
                   jnp.float32),
        "wi": ((Lm, d.E_held, d.D, d.F), 1 / math.sqrt(d.D), bf),
        "wg": ((Lm, d.E_held, d.D, d.F), 1 / math.sqrt(d.D), bf),
        "wo_e": ((Lm, d.E_held, d.F, d.D),
                 block / (MLP_OUT_SPREAD * math.sqrt(d.F) * HELD_GATE), bf),
        "shared_wi": ((Lm, d.D, d.n_shared * d.F), 1 / math.sqrt(d.D), bf),
        "shared_wg": ((Lm, d.D, d.n_shared * d.F), 1 / math.sqrt(d.D), bf),
        "shared_wo": ((Lm, d.n_shared * d.F, d.D),
                      block / (MLP_OUT_SPREAD * math.sqrt(d.n_shared * d.F)),
                      bf),
        "final_norm": ((d.D,), None, bf),
        "head": ((d.D, d.V), 1 / math.sqrt(d.D), bf),
    }
    keys = jax.random.split(key, len(shapes))
    out = {}
    for kk, (name, (shape, scale, dt)) in zip(keys, shapes.items()):
        z = jax.random.normal(kk, shape, jnp.float32)
        # norm gains near 1, so that the check covers them too
        out[name] = (1.0 + 0.1 * z if scale is None else z * scale).astype(dt)
    return {
        "embed": out["embed"], "head": out["head"],
        "final_norm": out["final_norm"],
        "layers": {n: out[n] for n in ("attn_norm", "wq", "wkv_a",
                                       "kv_norm", "wkv_b", "wo",
                                       "mlp_norm")},
        "dense": {"wi": out["dense_wi"], "wg": out["dense_wg"],
                  "wo": out["dense_wo"]},
        "moe": {"router": out["router"], "wi": out["wi"], "wg": out["wg"],
                "wo": out["wo_e"], "shared_wi": out["shared_wi"],
                "shared_wg": out["shared_wg"],
                "shared_wo": out["shared_wo"]},
    }


_make = jax.jit(weights, static_argnums=(1,))


def make_weights(cfg: dict, key) -> dict:
    """All weights in one jitted call on the default device."""
    return _make(key, dims(cfg))


# ------------------------------------------------------------- precision
def f32(a):
    return a.astype(jnp.float32)


def fp8_rows(a):
    """The control's cache precision: float8_e4m3fn with one scale per
    token (the max over the last axis), the step below bfloat16."""
    s = jnp.max(jnp.abs(a), axis=-1, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


# ---------------------------------------------------------------- forward
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def yarn_freqs(d: Dims) -> np.ndarray:
    dim = d.dr
    extra = 1.0 / d.theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if d.factor <= 1:
        return extra

    def corr(rot):
        return (dim * math.log(d.original_max / (rot * 2 * math.pi))
                / (2 * math.log(d.theta)))

    low = max(math.floor(corr(d.beta_fast)), 0)
    high = min(math.ceil(corr(d.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp                       # 1: extrapolate, 0: interpolate
    return extra / d.factor * (1 - mask) + extra * mask


def _rope(x, pos, d: Dims):
    """Half-split rotation of x (T, ..., dr) at positions pos (T,)."""
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(yarn_freqs(d),
                                                         jnp.float32)
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    m = _mscale(d.factor, d.mscale) / _mscale(d.factor, d.mscale_all_dim)
    c, s = jnp.cos(ang) * m, jnp.sin(ang) * m
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def _mm(a, b):
    return jnp.matmul(a, f32(b), precision=HIGHEST)


def _attention(x, lw, pos, d: Dims, fp8_cache: bool):
    T = x.shape[0]
    h = _rms(x, f32(lw["attn_norm"]), d.eps)
    q = _mm(h, lw["wq"]).reshape(T, d.H, d.dn + d.dr)
    q_nope, q_pe = q[..., :d.dn], _rope(q[..., d.dn:], pos, d)
    kv = _mm(h, lw["wkv_a"])
    c = _rms(kv[:, :d.r], f32(lw["kv_norm"]), d.eps)
    k_pe = _rope(kv[:, d.r:], pos, d)
    if fp8_cache:                            # what a float8 cache would hold
        c, k_pe = fp8_rows(c), fp8_rows(k_pe)
    kvb = _mm(c, lw["wkv_b"]).reshape(T, d.H, d.dn + d.dv)
    k_nope, v = kvb[..., :d.dn], kvb[..., d.dn:]
    scale = softmax_scale(d)
    outs = []
    for lo in range(0, T, Q_BLOCK):
        hi = min(T, lo + Q_BLOCK)
        s = (jnp.einsum("qhd,khd->hqk", q_nope[lo:hi], k_nope,
                        precision=HIGHEST)
             + jnp.einsum("qhd,kd->hqk", q_pe[lo:hi], k_pe,
                          precision=HIGHEST)) * scale
        causal = pos[lo:hi, None] >= pos[None, :]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST))
    o = jnp.concatenate(outs, axis=0).reshape(T, d.H * d.dv)
    return x + _mm(o, lw["wo"])


def _mlp(h, wi, wg, wo):
    return _mm(jax.nn.silu(_mm(h, wi)) * _mm(h, wg), wo)


def _moe(x, lw, mw, d: Dims):
    T = x.shape[0]
    h = _rms(x, f32(lw["mlp_norm"]), d.eps)
    gates = jax.nn.softmax(
        jnp.matmul(h, mw["router"], precision=HIGHEST), axis=-1)
    topw, tope = jax.lax.top_k(gates, d.k)
    if d.norm_topk:
        topw = topw / topw.sum(-1, keepdims=True)
    topw = topw * d.routed_scale
    wfull = jnp.zeros((T, d.E), jnp.float32).at[
        jnp.arange(T)[:, None], tope].set(topw)
    w_held = wfull[:, d.first:d.first + d.E_held]
    hi = jnp.einsum("td,edf->etf", h, f32(mw["wi"]), precision=HIGHEST)
    hg = jnp.einsum("td,edf->etf", h, f32(mw["wg"]), precision=HIGHEST)
    ye = jnp.einsum("etf,efd->etd", jax.nn.silu(hi) * hg, f32(mw["wo"]),
                    precision=HIGHEST)
    y = jnp.einsum("etd,te->td", ye, w_held, precision=HIGHEST)
    return x + y + _mlp(h, mw["shared_wi"], mw["shared_wg"],
                        mw["shared_wo"])


@functools.partial(jax.jit, static_argnums=(3, 4))
def forward_logits(w, tokens, out_pos, d: Dims, fp8_cache: bool = False):
    """Logits (P, V) in float32 at positions ``out_pos`` of one sequence
    ``tokens`` (T,); positions past the sequence's end do not reach
    earlier ones (causal). ``fp8_cache`` rounds what the cache would hold
    (the normed latent and the rotated key) to float8."""
    T = tokens.shape[0]
    pos = jnp.arange(T)
    x = f32(w["embed"])[tokens]
    lay = w["layers"]
    head = jax.tree.map(lambda a: a[:d.n_dense], lay)
    rest = jax.tree.map(lambda a: a[d.n_dense:], lay)

    def dense(x, xs):
        lw, mw = xs
        x = _attention(x, lw, pos, d, fp8_cache)
        h = _rms(x, f32(lw["mlp_norm"]), d.eps)
        return x + _mlp(h, mw["wi"], mw["wg"], mw["wo"]), None

    def sparse(x, xs):
        lw, mw = xs
        x = _attention(x, lw, pos, d, fp8_cache)
        return _moe(x, lw, mw, d), None

    if d.n_dense:
        x, _ = jax.lax.scan(dense, x, (head, w["dense"]))
    x, _ = jax.lax.scan(sparse, x, (rest, w["moe"]))
    xo = _rms(x[out_pos], f32(w["final_norm"]), d.eps)
    return jnp.matmul(xo, f32(w["head"]), precision=HIGHEST)


class Served(NamedTuple):
    prompt: np.ndarray                 # (prompt_len,) int32
    tokens: np.ndarray                 # (n,) int32 served, greedy


def _inputs(s: Served, t_pad: int, p_pad: int):
    n = len(s.tokens)
    seq = np.concatenate([s.prompt, s.tokens[:-1]]).astype(np.int32)
    if len(seq) > t_pad or n > p_pad:
        raise ValueError(f"request of {len(s.prompt)}+{n} tokens exceeds "
                         f"the reference's {t_pad}/{p_pad}")
    tokens = np.zeros(t_pad, np.int32)
    tokens[:len(seq)] = seq
    out_pos = np.zeros(p_pad, np.int32)
    out_pos[:n] = len(s.prompt) - 1 + np.arange(n)
    return jnp.asarray(tokens), jnp.asarray(out_pos), n


def logit_gaps(cfg: dict, weights, served: Sequence[Served], *,
               t_pad: int, p_pad: int, control: bool = False
               ) -> Dict[str, float]:
    """How far the served tokens lie below the reference's best logit:
    the widest gap over every served token of ``served``, the mean gap,
    and the share of tokens that are not the reference's first choice.
    With ``control``, the same for the tokens that the reference puts
    first when its latent cache is held in float8, at the same
    positions."""
    d = dims(cfg)
    gaps, ctrl = [], []
    for s in served:
        tokens, out_pos, n = _inputs(s, t_pad, p_pad)
        ref = np.asarray(forward_logits(weights, tokens, out_pos, d))[:n]
        best = ref.max(-1)
        gaps.append(best - ref[np.arange(n), np.asarray(s.tokens)])
        if control:
            low = np.asarray(forward_logits(weights, tokens, out_pos, d,
                                            True))[:n]
            ctrl.append(best - ref[np.arange(n), low.argmax(-1)])
    out = _summary(np.concatenate(gaps))
    if control:
        out.update({f"control_{k}": v for k, v in
                    _summary(np.concatenate(ctrl)).items()})
    return out


def _summary(gaps: np.ndarray) -> Dict[str, float]:
    return {"max_logit_gap": float(gaps.max()),
            "mean_logit_gap": float(gaps.mean()),
            "argmax_differs_share": float((gaps > 0).mean()),
            "served_tokens": int(gaps.size)}


def sequence_pad(traffic: dict) -> int:
    """Reference length covering the traffic's longest prompt and output,
    rounded up to 128."""
    n = max(traffic["prompt_buckets"]) + max(traffic["output_buckets"])
    return -(-n // 128) * 128
