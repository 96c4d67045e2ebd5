"""Plain reference of the Granite mixture-of-experts transformer that the
serving cells run, and the generator of its weights.

The weights are the benchmark's input: one jitted call makes them on the
device from ``--seed`` in the checkpoint's form (bfloat16, the router in
float32), as Granite's own layers read them. The reference makes them
again from the seed; it takes nothing from the program.

The forward pass is Granite's (``granitemoe``), in float32 at the highest
matmul precision, one sequence at a time, layer by layer, with the
configuration's scalar multipliers m_emb, m_att, m_res and s_logit:

    x = m_emb . embed[tokens]
    per layer:  x += m_res . Wo . attn(rope(Wq h), rope(Wk h), Wv h),
                     h = rms(x) n1, scores = m_att . q.k
                x += m_res . sum_{e in top-k} p_e . We_o (silu(We_i h) * We_g h),
                     h = rms(x) n2, p = softmax over the top-k of h R
    logits = rms(x) nf . embed^T / s_logit       (the head tied to embed)

RMSNorm in float32 with eps from the file, rotary embedding with the
half-split rotation and ``rope_theta``, grouped-query attention under a
causal mask, top-k routing with no capacity limit (every expert takes
every token routed to it).

The generator's scales keep the random model from collapsing into its
tied head: logits have unit spread (embed at s_logit / sqrt(D)), queries
and keys give attention scores of unit spread under m_att, and every block
adds to the residual stream several times the embedding's size, so that
the last token's own embedding does not decide the next token and every
layer's attention and experts do.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0                      # largest finite float8_e4m3fn


class Dims(NamedTuple):
    L: int
    D: int
    H: int
    K: int
    dh: int
    E: int
    k: int
    F: int
    V: int
    theta: float
    eps: float
    tied: bool
    m_emb: float
    m_att: float
    m_res: float
    s_logit: float


def dims(cfg: dict) -> Dims:
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return Dims(L=cfg["num_hidden_layers"], D=D, H=H,
                K=cfg["num_key_value_heads"],
                dh=cfg.get("head_dim") or D // H,
                E=cfg["num_local_experts"], k=cfg["num_experts_per_tok"],
                F=cfg["intermediate_size"], V=cfg["vocab_size"],
                theta=float(cfg["rope_theta"]),
                eps=float(cfg["rms_norm_eps"]),
                tied=bool(cfg["tie_word_embeddings"]),
                m_emb=float(cfg["embedding_multiplier"]),
                m_att=float(cfg["attention_multiplier"]),
                m_res=float(cfg["residual_multiplier"]),
                s_logit=float(cfg["logits_scaling"]))


# --------------------------------------------------------------- weights
# spread of a block's output (before m_res) per unit of its output
# weight's scale, at unit-spread inputs: attention averages values over
# many positions, experts mix top-k silu(a) * b products. Scores of unit
# spread keep attention selective without making the random network
# chaotic: at spread 2, bfloat16 rounding alone moved the argmax at 44%
# of positions (4 layers, 1024 tokens, on the CPU)
ATTN_OUT_SPREAD = 0.3
MOE_OUT_SPREAD = 0.2
SCORE_SPREAD = 1.0


def weights(key, d: Dims) -> dict:
    """Checkpoint-form weights from ``key`` (see the module's scales)."""
    bf = jnp.bfloat16
    s_emb = d.s_logit / math.sqrt(d.D)
    # each of the 2L blocks adds sqrt(D / 2L) times the embedding's size,
    # so the last token's embedding gives its own logit about one spread
    block = math.sqrt(d.D / (2 * d.L)) * d.m_emb * s_emb / d.m_res
    s_qk = math.sqrt(SCORE_SPREAD / (d.m_att * math.sqrt(d.dh)))
    shapes = {
        "embed": ((d.V, d.D), s_emb, bf),
        "norm1": ((d.L, d.D), None, bf),
        "wq": ((d.L, d.D, d.H * d.dh), s_qk / math.sqrt(d.D), bf),
        "wk": ((d.L, d.D, d.K * d.dh), s_qk / math.sqrt(d.D), bf),
        "wv": ((d.L, d.D, d.K * d.dh), 1 / math.sqrt(d.D), bf),
        "wo": ((d.L, d.H * d.dh, d.D),
               block / (ATTN_OUT_SPREAD * math.sqrt(d.H * d.dh)), bf),
        "norm2": ((d.L, d.D), None, bf),
        "router": ((d.L, d.D, d.E), 0.02, jnp.float32),
        "ei": ((d.L, d.E, d.D, d.F), 1 / math.sqrt(d.D), bf),
        "eg": ((d.L, d.E, d.D, d.F), 1 / math.sqrt(d.D), bf),
        "eo": ((d.L, d.E, d.F, d.D),
               block / (MOE_OUT_SPREAD * math.sqrt(d.F)), bf),
        "final_norm": ((d.D,), None, bf),
    }
    if not d.tied:
        shapes["head"] = ((d.D, d.V), s_emb, bf)
    keys = jax.random.split(key, len(shapes))
    out = {}
    for kk, (name, (shape, scale, dt)) in zip(keys, shapes.items()):
        z = jax.random.normal(kk, shape, jnp.float32)
        # norm gains near 1, so that the check covers them too
        out[name] = (1.0 + 0.1 * z if scale is None else z * scale).astype(dt)
    w = {
        "embed": out["embed"],
        "blocks": {
            "norm1": out["norm1"],
            "attn": {"wq": out["wq"], "wk": out["wk"], "wv": out["wv"],
                     "wo": out["wo"]},
            "norm2": out["norm2"],
            "moe": {"router": out["router"], "wi": out["ei"],
                    "wg": out["eg"], "wo": out["eo"]},
        },
        "final_norm": out["final_norm"],
    }
    if not d.tied:
        w["head"] = out["head"]
    return w


_make = jax.jit(weights, static_argnums=(1,))


def make_weights(cfg: dict, key) -> dict:
    """All weights in one jitted call on the default device."""
    return _make(key, dims(cfg))


# ------------------------------------------------------------- precision
def as_f32(a):
    return a.astype(jnp.float32)


def fp8(a, axis: int = -2):
    """The control's precision: float8_e4m3fn with one scale per output
    channel (the max over ``axis``), the step below bfloat16."""
    a = a.astype(jnp.float32)
    if a.ndim == 1:
        axis = -1
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


# ---------------------------------------------------------------- forward
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    dh = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = (pos[:, None].astype(jnp.float32) * freqs)[:, None, :]
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


@functools.partial(jax.jit, static_argnums=(3, 4))
def forward_logits(w, tokens, out_pos, d: Dims, quantized: bool = False):
    """Logits (P, V) in float32 at positions ``out_pos`` of one sequence
    ``tokens`` (T,); positions past the sequence's end do not reach
    earlier ones (causal)."""
    q = fp8 if quantized else as_f32
    T = tokens.shape[0]
    G = d.H // d.K
    pos = jnp.arange(T)
    # one scale per vocabulary row: the table is also the head's weight
    table = fp8(w["embed"], -1) if quantized else as_f32(w["embed"])
    x = d.m_emb * table[tokens]
    causal = pos[:, None] >= pos[None, :]

    def layer(x, lw):
        a, m = lw["attn"], lw["moe"]
        h = _rms(x, q(lw["norm1"]), d.eps)
        qh = jnp.matmul(h, q(a["wq"]), precision=HIGHEST).reshape(
            T, d.K, G, d.dh)
        kh = jnp.matmul(h, q(a["wk"]), precision=HIGHEST).reshape(
            T, d.K, d.dh)
        vh = jnp.matmul(h, q(a["wv"]), precision=HIGHEST).reshape(
            T, d.K, d.dh)
        qh = _rope(qh.reshape(T, d.H, d.dh), pos, d.theta).reshape(
            T, d.K, G, d.dh)
        kh = _rope(kh, pos, d.theta)
        s = d.m_att * jnp.einsum("tkgd,skd->kgts", qh, kh, precision=HIGHEST)
        s = jnp.where(causal, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgts,skd->tkgd", p, vh,
                       precision=HIGHEST).reshape(T, d.H * d.dh)
        x = x + d.m_res * jnp.matmul(o, q(a["wo"]), precision=HIGHEST)
        h = _rms(x, q(lw["norm2"]), d.eps)
        gates = jax.nn.softmax(
            jnp.matmul(h, q(m["router"]), precision=HIGHEST), axis=-1)
        topw, tope = jax.lax.top_k(gates, d.k)
        topw = topw / topw.sum(-1, keepdims=True)
        wfull = jnp.zeros((T, d.E), jnp.float32).at[
            jnp.arange(T)[:, None], tope].set(topw)
        hi = jnp.einsum("td,edf->etf", h, q(m["wi"]), precision=HIGHEST)
        hg = jnp.einsum("td,edf->etf", h, q(m["wg"]), precision=HIGHEST)
        ye = jnp.einsum("etf,efd->etd", jax.nn.silu(hi) * hg, q(m["wo"]),
                        precision=HIGHEST)
        x = x + d.m_res * jnp.einsum("etd,te->td", ye, wfull,
                                     precision=HIGHEST)
        return x, None

    x, _ = jax.lax.scan(layer, x, w["blocks"])
    xo = _rms(x[out_pos], q(w["final_norm"]), d.eps)
    head = table.T if d.tied else q(w["head"])
    return jnp.matmul(xo, head, precision=HIGHEST) / d.s_logit


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
    With ``control``, the same for the tokens that the reference at
    float8 weights puts first, at the same positions."""
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
