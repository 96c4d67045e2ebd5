"""Unified model: init / forward / prefill / decode for all assigned families.

Families
  dense | moe | audio | vlm : attention + (MLP | MoE) blocks, lax.scan over
                              stacked per-layer params.
  hybrid (zamba2)           : Mamba2 mixer layers; a *shared* attention+MLP
                              block (one weight set) applied before every
                              ``attn_every``-layer group — nested scan
                              (groups x layers), no lax.cond.
  ssm (xlstm)               : groups of (slstm_every-1) mLSTM + 1 sLSTM.

All step functions are pure and jit/pjit-friendly; caches and recurrent
states are explicit pytree arguments (stacked on a leading layer/group axis
and threaded through the layer scans as xs/ys).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import mamba2, xlstm
from repro.models import mlp as mlp_mod
from repro.models.common import (cross_entropy, dense_init, dtype_of,
                                 embed_init, rmsnorm, stacked_init)

Params = Dict[str, Any]


# ============================================================ initialization
def init_params(key: jax.Array, cfg: ModelConfig) -> Params:
    pdt = dtype_of(cfg.param_dtype)
    ks = jax.random.split(key, 8)
    p: Params = {}
    if cfg.frontend != "audio_frames":
        p["embed"] = embed_init(ks[0], cfg.vocab_size, cfg.d_model, pdt)
    else:
        p["frame_proj"] = dense_init(ks[0], cfg.d_model, cfg.d_model, pdt)
    if cfg.frontend == "vision_patches":
        p["patch_proj"] = dense_init(ks[5], cfg.d_model, cfg.d_model, pdt)

    if cfg.family in ("dense", "moe", "audio", "vlm"):
        def one_layer(k, moe=cfg.family == "moe"):
            k1, k2 = jax.random.split(k)
            block = {"norm1": jnp.ones((cfg.d_model,), pdt),
                     "attn": (attn.mla_init(k1, cfg) if cfg.mla
                              else attn.attn_init(k1, cfg)),
                     "norm2": jnp.ones((cfg.d_model,), pdt)}
            if moe:
                block["moe"] = mlp_mod.moe_init(k2, cfg)
            else:
                block["mlp"] = mlp_mod.mlp_init(k2, cfg)
            return block
        p["blocks"] = stacked_init(one_layer, ks[1],
                                   cfg.n_layers - cfg.n_dense_layers)
        if cfg.n_dense_layers:
            # leading dense layers, before the scanned expert blocks
            p["dense"] = stacked_init(
                lambda k: one_layer(k, moe=False), ks[6], cfg.n_dense_layers)

    elif cfg.family == "hybrid":
        def one_layer(k):
            return {"norm": jnp.ones((cfg.d_model,), pdt),
                    "mamba": mamba2.mamba_init(k, cfg)}
        p["blocks"] = stacked_init(one_layer, ks[1], cfg.n_layers)
        k1, k2 = jax.random.split(ks[2])
        p["shared"] = {"norm1": jnp.ones((cfg.d_model,), pdt),
                       "attn": attn.attn_init(k1, cfg),
                       "norm2": jnp.ones((cfg.d_model,), pdt),
                       "mlp": mlp_mod.mlp_init(k2, cfg)}

    elif cfg.family == "ssm":
        K = cfg.xlstm.slstm_every
        assert cfg.n_layers % K == 0, (cfg.n_layers, K)
        G = cfg.n_layers // K

        def one_mlstm(k):
            return {"norm": jnp.ones((cfg.d_model,), pdt),
                    "mlstm": xlstm.mlstm_init(k, cfg)}

        def one_slstm(k):
            return {"norm": jnp.ones((cfg.d_model,), pdt),
                    "slstm": xlstm.slstm_init(k, cfg)}

        mk = jax.random.split(ks[1], G * (K - 1)).reshape(G, K - 1, 2)
        p["blocks_m"] = jax.vmap(lambda kr: jax.vmap(one_mlstm)(kr))(mk)
        p["blocks_s"] = stacked_init(one_slstm, ks[2], G)
    else:
        raise ValueError(cfg.family)

    p["final_norm"] = jnp.ones((cfg.d_model,), pdt)
    if not cfg.tie_embeddings:
        p["head"] = dense_init(ks[3], cfg.d_model, cfg.vocab_size, pdt)
    return p


# ================================================================ embedding
def _embed_inputs(p: Params, batch: Dict[str, jax.Array], cfg: ModelConfig):
    """Returns (x (B,S,D), loss_mask (B,S) or None, label_offset)."""
    cdt = dtype_of(cfg.compute_dtype)
    if cfg.frontend == "audio_frames":
        x = batch["frames"].astype(cdt) @ p["frame_proj"].astype(cdt)
        return x, batch.get("mask"), 0
    tok = p["embed"][batch["tokens"]].astype(cdt)          # (B,St,D)
    if cfg.frontend == "vision_patches":
        patches = batch["patches"].astype(cdt) @ p["patch_proj"].astype(cdt)
        x = jnp.concatenate([patches, tok], axis=1)
        return x, batch.get("mask"), patches.shape[1]
    return tok, batch.get("mask"), 0


def _head(p: Params, x, cfg: ModelConfig):
    cdt = dtype_of(cfg.compute_dtype)
    x = rmsnorm(x, p["final_norm"], cfg.norm_eps)
    w = p["embed"].T if cfg.tie_embeddings else p["head"]
    logits = x @ w.astype(cdt)
    if cfg.shard_hints:
        # keep logits vocab-sharded: the sharded-CE path never gathers the
        # (tokens, vocab) tensor (the baseline's dominant waste)
        from repro.sharding.rules import hint
        logits = hint(logits, "dp", *(None,) * (logits.ndim - 2), "model")
    return logits


def _maybe_remat(fn, remat: str):
    if remat == "none" or not remat:
        return fn
    if remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)      # "full": save nothing


# ================================================================== forward
def forward(p: Params, batch: Dict[str, jax.Array], cfg: ModelConfig,
            remat: str = "none", return_cache: bool = False,
            logits_at: Optional[jax.Array] = None):
    """Full-sequence forward. Returns (logits, aux_loss, cache|None); with
    ``logits_at`` (n,) positions, the logits (B, n, V) at those only."""
    x, _, _ = _embed_inputs(p, batch, cfg)
    B, S, D = x.shape
    positions = jnp.arange(S)[None, :]

    if cfg.family in ("dense", "moe", "audio", "vlm"):
        attend = attn.mla_apply if cfg.mla else attn.attn_apply

        def body(carry, layer):
            x, aux = carry
            h, kv = attend(
                layer["attn"], rmsnorm(x, layer["norm1"], cfg.norm_eps),
                cfg, positions)
            x = x + h
            if "moe" in layer:
                h, a = mlp_mod.moe_apply(
                    layer["moe"], rmsnorm(x, layer["norm2"], cfg.norm_eps), cfg)
                aux = aux + a
            else:
                h = mlp_mod.mlp_apply(
                    layer["mlp"], rmsnorm(x, layer["norm2"], cfg.norm_eps), cfg)
            x = x + h
            return (x, aux), kv if return_cache else None

        carry, caches = (x, jnp.float32(0.0)), []
        for stack in _layer_stacks(p, cfg):
            carry, c = jax.lax.scan(_maybe_remat(body, remat), carry, stack)
            caches.append(c)
        x, aux = carry
        cache = None
        if return_cache:
            # (L,B,S,K,dh) keys and values, or (L,B,S,r) latents and
            # (L,B,S,dr) rotary keys
            cache = dict(zip(cache_names(cfg), _cat_layers(caches)))

    elif cfg.family == "hybrid":
        G = cfg.n_layers // cfg.attn_every
        blocks = jax.tree.map(
            lambda a: a.reshape((G, cfg.attn_every) + a.shape[1:]),
            p["blocks"])
        shared = p["shared"]

        def inner(x, layer):
            h, st = mamba2.mamba_apply(
                layer["mamba"], rmsnorm(x, layer["norm"], cfg.norm_eps), cfg)
            return x + h, st if return_cache else None

        def outer(carry, xs):
            x = carry
            group = xs
            h, (k, v) = attn.attn_apply(
                shared["attn"], rmsnorm(x, shared["norm1"], cfg.norm_eps),
                cfg, positions)
            x = x + h
            x = x + mlp_mod.mlp_apply(
                shared["mlp"], rmsnorm(x, shared["norm2"], cfg.norm_eps), cfg)
            x, sts = jax.lax.scan(_maybe_remat(inner, remat), x, group)
            return x, (sts, (k, v)) if return_cache else None

        x, caches = jax.lax.scan(outer, x, blocks)
        aux = jnp.float32(0.0)
        cache = None
        if return_cache:
            sts, (k, v) = caches
            # canonical cache layout: flat layer axis (matches init_cache)
            flat = lambda a: a.reshape((cfg.n_layers,) + a.shape[2:])
            cache = {"mamba_conv": flat(sts[0]), "mamba_ssm": flat(sts[1]),
                     "attn_k": k, "attn_v": v}

    elif cfg.family == "ssm":
        K = cfg.xlstm.slstm_every
        G = cfg.n_layers // K

        def inner(x, layer):
            h, st = xlstm.mlstm_apply(
                layer["mlstm"], rmsnorm(x, layer["norm"], cfg.norm_eps), cfg)
            return x + h, st if return_cache else None

        def outer(x, xs):
            mgroup, sblock = xs
            x, msts = jax.lax.scan(_maybe_remat(inner, remat), x, mgroup)
            h, sst = xlstm.slstm_apply(
                sblock["slstm"], rmsnorm(x, sblock["norm"], cfg.norm_eps), cfg)
            x = x + h
            return x, (msts, sst) if return_cache else None

        x, caches = jax.lax.scan(outer, x, (p["blocks_m"], p["blocks_s"]))
        aux = jnp.float32(0.0)
        cache = None
        if return_cache:
            msts, sst = caches
            cache = {"m_conv": msts[0], "m_c": msts[1],
                     "s_c": sst[0], "s_n": sst[1], "s_h": sst[2],
                     "s_m": sst[3]}
    else:
        raise ValueError(cfg.family)

    if logits_at is not None:
        x = jnp.take(x, logits_at, axis=1)
    logits = _head(p, x, cfg)
    return logits, aux, cache


def cache_names(cfg: ModelConfig) -> Tuple[str, str]:
    """The attention cache's two leaves: keys and values, or the latent
    and the shared rotary key of latent attention."""
    return ("c_kv", "k_pe") if cfg.mla else ("k", "v")


def _layer_stacks(p: Params, cfg: ModelConfig):
    """The stacked layer groups in order: leading dense layers, then the
    blocks."""
    return ([p["dense"]] if cfg.n_dense_layers else []) + [p["blocks"]]


def _cat_layers(parts):
    """Per-group scan outputs joined along the layer axis."""
    if len(parts) == 1:
        return parts[0]
    return jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *parts)


# ==================================================================== loss
def loss_fn(p: Params, batch: Dict[str, jax.Array], cfg: ModelConfig,
            remat: str = "none"):
    logits, aux, _ = forward(p, batch, cfg, remat=remat)
    labels = batch["labels"]
    if cfg.frontend == "vision_patches":
        # loss only on the text positions (after the patch prefix)
        n_p = cfg.n_patches if cfg.n_patches else 0
        logits = logits[:, n_p:]
    mask = batch.get("mask")
    if cfg.shard_hints:
        from repro.models.common import cross_entropy_sharded
        ce = cross_entropy_sharded(logits, labels, mask)
    else:
        ce = cross_entropy(logits, labels, mask)
    aux_w = cfg.moe.router_aux_weight if cfg.moe else 0.0
    loss = ce + aux_w * aux / max(cfg.n_layers, 1)
    return loss, {"ce": ce, "aux": aux}


# ==================================================================== cache
def init_cache(cfg: ModelConfig, batch: int, max_seq: int):
    """Decode cache sized for ``max_seq`` positions."""
    cdt = dtype_of(cfg.compute_dtype)
    dh, Kh = cfg.head_dim, cfg.n_kv_heads
    if cfg.family in ("dense", "moe", "vlm"):
        if cfg.mla:
            lead = (cfg.n_layers, batch, max_seq)
            return {"c_kv": jnp.zeros(lead + (cfg.mla.kv_lora_rank,), cdt),
                    "k_pe": jnp.zeros(lead + (cfg.mla.qk_rope_head_dim,),
                                      cdt)}
        shape = (cfg.n_layers, batch, max_seq, Kh, dh)
        return {"k": jnp.zeros(shape, cdt), "v": jnp.zeros(shape, cdt)}
    if cfg.family == "hybrid":
        G = cfg.n_layers // cfg.attn_every
        conv, ssm_st = mamba2.mamba_state_init(cfg, batch)
        rep = lambda a, n: jnp.broadcast_to(a[None], (n,) + a.shape)
        return {
            "mamba_conv": rep(conv, cfg.n_layers),
            "mamba_ssm": rep(ssm_st, cfg.n_layers),
            "attn_k": jnp.zeros((G, batch, max_seq, Kh, dh), cdt),
            "attn_v": jnp.zeros((G, batch, max_seq, Kh, dh), cdt),
        }
    if cfg.family == "ssm":
        K = cfg.xlstm.slstm_every
        G = cfg.n_layers // K
        conv, c_st = xlstm.mlstm_state_init(cfg, batch)
        s_st = xlstm.slstm_state_init(cfg, batch)
        rep2 = lambda a: jnp.broadcast_to(a[None, None],
                                          (G, K - 1) + a.shape)
        rep1 = lambda a: jnp.broadcast_to(a[None], (G,) + a.shape)
        return {"m_conv": rep2(conv), "m_c": rep2(c_st),
                "s_c": rep1(s_st[0]), "s_n": rep1(s_st[1]),
                "s_h": rep1(s_st[2]), "s_m": rep1(s_st[3])}
    raise ValueError(f"family {cfg.family} does not decode")


# ============================================================== decode step
def decode_step(p: Params, token: jax.Array, pos: jax.Array, cache,
                cfg: ModelConfig):
    """token: (B,) int32; pos: () int32 -> (logits (B,V), new cache)."""
    cdt = dtype_of(cfg.compute_dtype)
    x = p["embed"][token][:, None, :].astype(cdt)          # (B,1,D)

    if cfg.family in ("dense", "moe", "vlm"):
        attend = attn.mla_decode if cfg.mla else attn.attn_decode
        names = cache_names(cfg)

        def body(x, xs):
            layer, kc, vc = xs
            h, kc, vc = attend(
                layer["attn"], rmsnorm(x, layer["norm1"], cfg.norm_eps),
                kc, vc, pos, cfg)
            x = x + h
            if "moe" in layer:
                h, _ = mlp_mod.moe_apply(
                    layer["moe"], rmsnorm(x, layer["norm2"], cfg.norm_eps), cfg)
            else:
                h = mlp_mod.mlp_apply(
                    layer["mlp"], rmsnorm(x, layer["norm2"], cfg.norm_eps), cfg)
            x = x + h
            return x, (kc, vc)

        parts, lo = [], 0
        for stack in _layer_stacks(p, cfg):
            n = jax.tree.leaves(stack)[0].shape[0]
            x, kv = jax.lax.scan(body, x, (stack,) + tuple(
                cache[k][lo:lo + n] for k in names))
            parts.append(kv)
            lo += n
        cache = dict(zip(names, _cat_layers(parts)))

    elif cfg.family == "hybrid":
        G = cfg.n_layers // cfg.attn_every
        blocks = jax.tree.map(
            lambda a: a.reshape((G, cfg.attn_every) + a.shape[1:]),
            p["blocks"])
        shared = p["shared"]
        mconv = cache["mamba_conv"].reshape(
            (G, cfg.attn_every) + cache["mamba_conv"].shape[1:])
        mssm = cache["mamba_ssm"].reshape(
            (G, cfg.attn_every) + cache["mamba_ssm"].shape[1:])

        def inner(x, xs):
            layer, cv, st = xs
            h, (cv, st) = mamba2.mamba_decode(
                layer["mamba"], rmsnorm(x, layer["norm"], cfg.norm_eps),
                (cv, st), cfg)
            return x + h, (cv, st)

        def outer(x, xs):
            group, cv, st, kc, vc = xs
            h, kc, vc = attn.attn_decode(
                shared["attn"], rmsnorm(x, shared["norm1"], cfg.norm_eps),
                kc, vc, pos, cfg)
            x = x + h
            x = x + mlp_mod.mlp_apply(
                shared["mlp"], rmsnorm(x, shared["norm2"], cfg.norm_eps), cfg)
            x, (cv, st) = jax.lax.scan(inner, x, (group, cv, st))
            return x, (cv, st, kc, vc)

        x, (cv, st, k, v) = jax.lax.scan(
            outer, x, (blocks, mconv, mssm, cache["attn_k"],
                       cache["attn_v"]))
        cache = {"mamba_conv": cv.reshape(cache["mamba_conv"].shape),
                 "mamba_ssm": st.reshape(cache["mamba_ssm"].shape),
                 "attn_k": k, "attn_v": v}

    elif cfg.family == "ssm":
        def inner(x, xs):
            layer, cv, cs = xs
            h, (cv, cs) = xlstm.mlstm_decode(
                layer["mlstm"], rmsnorm(x, layer["norm"], cfg.norm_eps),
                (cv, cs), cfg)
            return x + h, (cv, cs)

        def outer(x, xs):
            mgroup, sblock, mcv, mcs, sc, sn, sh, sm = xs
            x, (mcv, mcs) = jax.lax.scan(inner, x, (mgroup, mcv, mcs))
            h, sst = xlstm.slstm_decode(
                sblock["slstm"], rmsnorm(x, sblock["norm"], cfg.norm_eps),
                (sc, sn, sh, sm), cfg)
            x = x + h
            return x, (mcv, mcs) + sst

        x, ys = jax.lax.scan(
            outer, x, (p["blocks_m"], p["blocks_s"], cache["m_conv"],
                       cache["m_c"], cache["s_c"], cache["s_n"],
                       cache["s_h"], cache["s_m"]))
        cache = {"m_conv": ys[0], "m_c": ys[1], "s_c": ys[2], "s_n": ys[3],
                 "s_h": ys[4], "s_m": ys[5]}
    else:
        raise ValueError(f"family {cfg.family} does not decode")

    logits = _head(p, x, cfg)[:, 0]                        # (B,V)
    return logits, cache
