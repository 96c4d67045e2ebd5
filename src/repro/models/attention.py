"""Attention: grouped-query attention, full (train/prefill) and cached
single-token decode; and multi-head latent attention (DeepSeek-V2), whose
cache is one latent per token, expanded to per-head keys and values for
a prompt and attended in absorbed form by the decode."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.common import (apply_rope, apply_rope_freqs, dense_init,
                                 dtype_of, rmsnorm)


def attn_init(key, cfg: ModelConfig):
    dh, H, K, D = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads, cfg.d_model
    pdt = dtype_of(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], D, H * dh, pdt),
        "wk": dense_init(ks[1], D, K * dh, pdt),
        "wv": dense_init(ks[2], D, K * dh, pdt),
        "wo": dense_init(ks[3], H * dh, D, pdt,
                         scale=1.0 / math.sqrt(H * dh * 2 * cfg.n_layers)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * dh,), pdt)
        p["bk"] = jnp.zeros((K * dh,), pdt)
        p["bv"] = jnp.zeros((K * dh,), pdt)
    return p


def _project_qkv(p, x, cfg: ModelConfig, positions):
    """x: (B,S,D) -> q (B,S,K,G,dh), k,v (B,S,K,dh)."""
    B, S, _ = x.shape
    dh, H, K = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    G = H // K
    cdt = dtype_of(cfg.compute_dtype)
    xc = x.astype(cdt)
    q = xc @ p["wq"].astype(cdt)
    k = xc @ p["wk"].astype(cdt)
    v = xc @ p["wv"].astype(cdt)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(cdt)
        k = k + p["bk"].astype(cdt)
        v = v + p["bv"].astype(cdt)
    q = q.reshape(B, S, H, dh)
    k = k.reshape(B, S, K, dh)
    v = v.reshape(B, S, K, dh)
    if cfg.family != "audio":           # audio stub frontend carries its own pos
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q.reshape(B, S, K, G, dh), k, v


def attn_apply(p, x, cfg: ModelConfig, positions=None,
               segment_start: Optional[jax.Array] = None):
    """Full self-attention. x: (B,S,D); positions: (S,) or (B,S)."""
    B, S, D = x.shape
    dh, H, K = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q, k, v = _project_qkv(p, x, cfg, positions)
    if cfg.shard_hints:
        # pin attention intermediates: batch on the data axes, heads on
        # model — on the kv dim when it divides the axis, else on the
        # q-group dim. Measured: without these GSPMD replicated the whole
        # attention over "data" (8x redundant compute on the baseline).
        from repro.sharding.rules import _axis_size, ambient_mesh, hint
        m = ambient_mesh()
        msz = _axis_size(m, "model") if m and "model" in m.axis_names else 1
        on_k = K % msz == 0
        q = hint(q, "dp", None, "model" if on_k else None,
                 None if on_k else "model", None)
        k = hint(k, "dp", None, "model" if on_k else None, None)
        v = hint(v, "dp", None, "model" if on_k else None, None)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k).astype(jnp.float32)
    scores = scores / math.sqrt(dh)
    if cfg.shard_hints:
        scores = hint(scores, "dp", "model" if on_k else None,
                      None if on_k else "model", None, None)
    if cfg.causal:
        qi = jnp.arange(S)[:, None]
        kj = jnp.arange(S)[None, :]
        scores = jnp.where(qi >= kj, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    o = jnp.einsum("bkgqs,bskd->bqkgd", w, v).reshape(B, S, H * dh)
    if cfg.shard_hints:
        from repro.sharding.rules import hint
        o = hint(o, "dp", None, "model")
    return o @ p["wo"].astype(o.dtype), (k, v)


def attn_decode(p, x, k_cache, v_cache, pos, cfg: ModelConfig):
    """One-token decode. x: (B,1,D); caches: (B,Smax,K,dh); pos: () int32.

    Returns (y (B,1,D), new_k_cache, new_v_cache).
    """
    B = x.shape[0]
    dh, H, K = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    G = H // K
    positions = jnp.full((B, 1), pos, dtype=jnp.int32)
    q, k_new, v_new = _project_qkv(p, x, cfg, positions)
    if cfg.shard_hints:
        # partitionable cache write: dynamic_update_slice with a runtime
        # start index on the sequence-SHARDED dim forces GSPMD to
        # all-gather the whole cache every layer (measured: 2.2 TB/token
        # on llama3-405b decode_32k). A one-hot select keeps every shard
        # local at the cost of a full cache rewrite (elementwise, fused).
        from repro.sharding.rules import hint
        upd = (jnp.arange(k_cache.shape[1]) == pos)[None, :, None, None]
        k_cache = jnp.where(upd, k_new.astype(k_cache.dtype), k_cache)
        v_cache = jnp.where(upd, v_new.astype(v_cache.dtype), v_cache)
        # ...and pin the layout: without these GSPMD kept a *replicated*
        # cache copy inside the layer loop (16.9 GB HBM/visit measured)
        k_cache = hint(k_cache, "dp", "model", None, None)
        v_cache = hint(v_cache, "dp", "model", None, None)
    else:
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k_new.astype(k_cache.dtype), (0, pos, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v_new.astype(v_cache.dtype), (0, pos, 0, 0))
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q,
                        k_cache.astype(q.dtype)).astype(jnp.float32)
    scores = scores / math.sqrt(dh)
    Smax = k_cache.shape[1]
    valid = (jnp.arange(Smax) <= pos)[None, None, None, None, :]
    scores = jnp.where(valid, scores, -jnp.inf)
    if cfg.shard_hints:
        from repro.sharding.rules import hint
        scores = hint(scores, "dp", None, None, None, "model")
    w = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    o = jnp.einsum("bkgqs,bskd->bqkgd", w, v_cache).reshape(B, 1, H * dh)
    y = o.astype(x.dtype) @ p["wo"].astype(x.dtype)
    return y, k_cache, v_cache


# ===================================================== latent attention
def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def mla_rope_freqs(cfg: ModelConfig) -> np.ndarray:
    """YaRN inverse frequencies of the rotary key: the extrapolated
    (plain) ones below the correction dim of ``beta_fast``, the
    interpolated ones (over ``rope_factor``) above that of ``beta_slow``,
    and a linear ramp between (DeepseekV2YarnRotaryEmbedding)."""
    m = cfg.mla
    dim, base = m.qk_rope_head_dim, cfg.rope_theta
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if m.rope_factor <= 1:
        return extra.astype(np.float32)

    def corr_dim(rot):
        return (dim * math.log(m.rope_original_max / (rot * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr_dim(m.beta_fast)), 0)
    high = min(math.ceil(corr_dim(m.beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                   0.0, 1.0)
    inter = extra / m.rope_factor
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def mla_rope_scale(cfg: ModelConfig) -> float:
    m = cfg.mla
    return (yarn_mscale(m.rope_factor, m.mscale)
            / yarn_mscale(m.rope_factor, m.mscale_all_dim))


def mla_softmax_scale(cfg: ModelConfig) -> float:
    m = cfg.mla
    scale = m.qk_head_dim ** -0.5
    if m.mscale_all_dim:
        scale *= yarn_mscale(m.rope_factor, m.mscale_all_dim) ** 2
    return scale


def mla_init(key, cfg: ModelConfig):
    """Projections with no query compression: ``wq`` (D, H (dn + dr)),
    per head its no-rope then its rope part; ``wkv_a`` (D, r + dr), the
    latent then the shared rotary key; ``kv_norm`` (r,); ``wkv_b``
    (r, H (dn + dv)), per head its key then its value; ``wo``."""
    m, H, D = cfg.mla, cfg.n_heads, cfg.d_model
    pdt = dtype_of(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], D, H * m.qk_head_dim, pdt),
        "wkv_a": dense_init(ks[1], D, m.kv_lora_rank + m.qk_rope_head_dim,
                            pdt),
        "kv_norm": jnp.ones((m.kv_lora_rank,), pdt),
        "wkv_b": dense_init(ks[2], m.kv_lora_rank,
                            H * (m.qk_nope_head_dim + m.v_head_dim), pdt),
        "wo": dense_init(ks[3], H * m.v_head_dim, D, pdt,
                         scale=1.0 / math.sqrt(H * m.v_head_dim * 2
                                               * cfg.n_layers)),
    }


def mla_project(p, x, cfg: ModelConfig, positions):
    """x (B,S,D) -> q_nope (B,S,H,dn), q_pe (B,S,H,dr) rotated, c_kv
    (B,S,r) after its norm, k_pe (B,S,dr) rotated: what the cache holds
    is c_kv and k_pe."""
    m, H = cfg.mla, cfg.n_heads
    B, S, _ = x.shape
    cdt = dtype_of(cfg.compute_dtype)
    xc = x.astype(cdt)
    q = (xc @ p["wq"].astype(cdt)).reshape(B, S, H, m.qk_head_dim)
    kv = xc @ p["wkv_a"].astype(cdt)
    c_kv = rmsnorm(kv[..., :m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    freqs, scale = mla_rope_freqs(cfg), mla_rope_scale(cfg)
    q_pe = apply_rope_freqs(q[..., m.qk_nope_head_dim:], positions, freqs,
                            scale)
    k_pe = apply_rope_freqs(kv[..., None, m.kv_lora_rank:], positions,
                            freqs, scale)[..., 0, :]
    return q[..., :m.qk_nope_head_dim], q_pe, c_kv, k_pe


def _kv_b(p, cfg: ModelConfig):
    """``wkv_b`` as (r, H, dn + dv) in the compute dtype."""
    m = cfg.mla
    return p["wkv_b"].astype(dtype_of(cfg.compute_dtype)).reshape(
        m.kv_lora_rank, cfg.n_heads, m.qk_nope_head_dim + m.v_head_dim)


def mla_apply(p, x, cfg: ModelConfig, positions=None):
    """Causal latent attention in its expanded form: per-head keys and
    values from the latents, the queries in blocks of ``q_block`` so that
    one block's scores (B, H, q_block, S) bound the memory, each block
    attending only the keys up to its end. Returns (y, (c_kv, k_pe))."""
    m, H = cfg.mla, cfg.n_heads
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q_nope, q_pe, c_kv, k_pe = mla_project(p, x, cfg, positions)
    kv = jnp.einsum("bsr,rhd->bshd", c_kv, _kv_b(p, cfg))
    k_nope, v = kv[..., :m.qk_nope_head_dim], kv[..., m.qk_nope_head_dim:]
    scale = mla_softmax_scale(cfg)
    qb = m.q_block if 0 < m.q_block < S else S
    outs = []
    for lo in range(0, S, qb):
        hi = min(S, lo + qb)
        s = (jnp.einsum("bqhd,bkhd->bhqk", q_nope[:, lo:hi], k_nope[:, :hi],
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bqhd,bkd->bhqk", q_pe[:, lo:hi], k_pe[:, :hi],
                          preferred_element_type=jnp.float32)) * scale
        causal = (lo + jnp.arange(hi - lo))[:, None] >= jnp.arange(hi)[None]
        s = jnp.where(causal, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", w, v[:, :hi]))
    o = jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]
    o = o.reshape(B, S, H * m.v_head_dim)
    return o @ p["wo"].astype(o.dtype), (c_kv, k_pe)


def mla_absorb_query(p, q_nope, cfg: ModelConfig):
    """q_nope (B,H,dn) -> (B,H,r): the query against the latents, the
    key half of ``wkv_b`` absorbed."""
    return jnp.einsum("bhd,rhd->bhr", q_nope,
                      _kv_b(p, cfg)[..., :cfg.mla.qk_nope_head_dim])


def mla_attend_absorbed(p, q_lat, s_pe, c_view, valid, cfg: ModelConfig):
    """One query per sequence against cached latents, in absorbed form:
    scores q_lat . c_kv + s_pe (the rotary part, (B,H,T) in float32),
    the softmax under ``valid`` (B,T), the weighted latents, then the
    value half of ``wkv_b`` and ``wo``. c_view (B,T,r). -> (B,1,D)."""
    m, H = cfg.mla, cfg.n_heads
    B = q_lat.shape[0]
    s = (jnp.einsum("bhr,btr->bht", q_lat, c_view.astype(q_lat.dtype),
                    preferred_element_type=jnp.float32) + s_pe)
    s = jnp.where(valid[:, None, :], s * mla_softmax_scale(cfg), -jnp.inf)
    w = jax.nn.softmax(s, axis=-1).astype(q_lat.dtype)
    o_lat = jnp.einsum("bht,btr->bhr", w, c_view.astype(q_lat.dtype))
    o = jnp.einsum("bhr,rhd->bhd", o_lat,
                   _kv_b(p, cfg)[..., m.qk_nope_head_dim:])
    o = o.reshape(B, 1, H * m.v_head_dim)
    return o @ p["wo"].astype(o.dtype)


def mla_decode(p, x, c_cache, pe_cache, pos, cfg: ModelConfig):
    """One-token decode over a contiguous latent cache. x (B,1,D);
    c_cache (B,Smax,r), pe_cache (B,Smax,dr); pos () int32.
    Returns (y (B,1,D), c_cache', pe_cache')."""
    B = x.shape[0]
    positions = jnp.full((B, 1), pos, dtype=jnp.int32)
    q_nope, q_pe, c_new, pe_new = mla_project(p, x, cfg, positions)
    c_cache = jax.lax.dynamic_update_slice(
        c_cache, c_new.astype(c_cache.dtype), (0, pos, 0))
    pe_cache = jax.lax.dynamic_update_slice(
        pe_cache, pe_new.astype(pe_cache.dtype), (0, pos, 0))
    s_pe = jnp.einsum("bhd,btd->bht", q_pe[:, 0],
                      pe_cache.astype(q_pe.dtype),
                      preferred_element_type=jnp.float32)
    valid = jnp.broadcast_to(jnp.arange(c_cache.shape[1])[None] <= pos,
                             (B, c_cache.shape[1]))
    y = mla_attend_absorbed(p, mla_absorb_query(p, q_nope[:, 0], cfg),
                            s_pe, c_cache, valid, cfg)
    return y.astype(x.dtype), c_cache, pe_cache
