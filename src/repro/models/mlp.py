"""MLP blocks: dense (swiglu / relu2 / gelu) and mixture-of-experts.

The MoE path uses sort-based grouped dispatch with a capacity factor
(Megablocks/MaxText-dropping style): tokens are sorted by expert, packed
into an (E, C, D) buffer, processed with grouped einsums (so HLO FLOPs scale
with top_k * tokens, NOT with n_experts), and combined back with their
router weights. Experts shard over the "model" mesh axis (expert
parallelism); the pack/unpack gathers become the all-to-alls of the EP
dispatch under GSPMD.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import act_fn, dense_init, dtype_of


# ---------------------------------------------------------------- dense MLP
def mlp_init(key, cfg: ModelConfig, d_ff: int | None = None):
    D = cfg.d_model
    F = cfg.d_ff if d_ff is None else d_ff
    pdt = dtype_of(cfg.param_dtype)
    ks = jax.random.split(key, 3)
    out_scale = 1.0 / math.sqrt(F * 2 * cfg.n_layers)
    if cfg.act == "swiglu":
        return {"wi": dense_init(ks[0], D, F, pdt),
                "wg": dense_init(ks[1], D, F, pdt),
                "wo": dense_init(ks[2], F, D, pdt, scale=out_scale)}
    return {"wi": dense_init(ks[0], D, F, pdt),
            "wo": dense_init(ks[2], F, D, pdt, scale=out_scale)}


def mlp_apply(p, x, cfg: ModelConfig):
    cdt = dtype_of(cfg.compute_dtype)
    xc = x.astype(cdt)
    h = xc @ p["wi"].astype(cdt)
    if cfg.act == "swiglu":
        h = jax.nn.silu(h) * (xc @ p["wg"].astype(cdt))
    else:
        h = act_fn(cfg.act)(h)
    if cfg.shard_hints and h.ndim == 3:
        from repro.sharding.rules import hint
        h = hint(h, "dp", None, "model")
    return h @ p["wo"].astype(cdt)


# ----------------------------------------------------------------- MoE MLP
def moe_init(key, cfg: ModelConfig):
    """The router scores all ``n_experts``; the expert stacks hold the
    ``n_local`` experts this chip holds."""
    moe = cfg.moe
    D, E, Fe = cfg.d_model, moe.n_experts, moe.d_expert
    pdt = dtype_of(cfg.param_dtype)
    ks = jax.random.split(key, 5)

    def expert_stack(k, d_in, d_out, scale=None):
        kk = jax.random.split(k, E)[moe.first_held:][:moe.n_local]
        return jax.vmap(lambda kx: dense_init(kx, d_in, d_out, pdt,
                                              scale=scale))(kk)

    out_scale = 1.0 / math.sqrt(Fe * 2 * cfg.n_layers)
    p = {
        "router": dense_init(ks[0], D, E, jnp.float32, scale=0.02),
        "wi": expert_stack(ks[1], D, Fe),                    # (E, D, Fe)
        "wg": expert_stack(ks[2], D, Fe),
        "wo": expert_stack(ks[3], Fe, D, scale=out_scale),   # (E, Fe, D)
    }
    if moe.n_shared:
        p["shared"] = mlp_init(ks[4], cfg, d_ff=moe.n_shared * Fe)
    return p


def _capacity(T: int, moe) -> int:
    c = int(math.ceil(moe.top_k * T * moe.capacity_factor / moe.n_experts))
    return max(8, -(-c // 8) * 8)       # round up to a lane-friendly multiple


def moe_apply(p, x, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """x: (B,S,D) -> (y (B,S,D), aux_loss scalar).

    The layer holds experts ``first_held`` .. of the router's
    ``n_experts`` (all by default): it routes every token over all of
    them and returns the held experts' part of the result, plus the
    shared experts; a token's other top-k experts are another chip's.

    Under ``shard_hints`` with an ambient mesh, dispatch runs *locally per
    data shard* via shard_map (tokens never cross the data axis; the
    expert dimension stays auto-partitioned over "model") — the EP path.
    Otherwise the global sort-based dispatch below runs under plain GSPMD.
    """
    if cfg.shard_hints:
        from repro.sharding.rules import ambient_mesh, data_axes, _axis_size
        m = ambient_mesh()
        if m is not None:
            dp = data_axes(m)
            if x.shape[0] % _axis_size(m, dp) == 0:
                return _moe_apply_local(p, x, cfg, m, dp)
    return _moe_apply_global(p, x, cfg)


def _moe_apply_local(p, x, cfg: ModelConfig, mesh, dp):
    """Group-batched local dispatch (pure GSPMD).

    Tokens reshape to (n_groups, T_local, D) with the group dim pinned to
    the data axes; the sort/cumsum/scatter of the dispatch are vmapped per
    group, so they carry a leading dp-sharded batch dim and never cross
    data shards. The expert einsums keep E on "model" (EP) — the only
    cross-device traffic left is the buf<->expert re-layout (the EP
    all-to-all) and the FSDP weight gathers.

    (A partial-manual shard_map variant hit an XLA-CPU AllReducePromotion
    crash — 'Invalid binary instruction opcode copy' — at 256 devices;
    this formulation expresses the same locality without manual axes.)
    """
    from repro.sharding.rules import _axis_size, hint
    B, S, D = x.shape
    g = _axis_size(mesh, dp)
    xg = x.reshape(g, (B // g) * S, D)
    xg = hint(xg, "dp", None, None)

    def one_group(xt):
        return _moe_dispatch_tokens(p, xt, cfg)

    yg, aux_g = jax.vmap(one_group)(xg)
    yg = hint(yg, "dp", None, None)
    return yg.reshape(B, S, D), jnp.mean(aux_g)


def _moe_apply_global(p, x, cfg: ModelConfig, local: bool = False
                      ) -> Tuple[jax.Array, jax.Array]:
    B, S, D = x.shape
    y, aux = _moe_dispatch_tokens(p, x.reshape(B * S, D), cfg)
    return y.reshape(B, S, D), aux


def _moe_dispatch_tokens(p, xt, cfg: ModelConfig
                         ) -> Tuple[jax.Array, jax.Array]:
    """Sort-based grouped dispatch over flat tokens xt: (T, D)."""
    moe = cfg.moe
    T, D = xt.shape
    E, K = moe.n_experts, moe.top_k
    cdt = dtype_of(cfg.compute_dtype)
    xt = xt.astype(cdt)

    logits = (xt.astype(jnp.float32) @ p["router"])          # (T, E)
    gates = jax.nn.softmax(logits, axis=-1)
    topw, tope = jax.lax.top_k(gates, K)                     # (T, K)
    if moe.norm_topk:
        topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

    # load-balancing aux loss (Switch-style)
    density = jnp.mean(jax.nn.one_hot(tope[:, 0], E), axis=0)
    mean_gate = jnp.mean(gates, axis=0)
    aux = E * jnp.sum(density * mean_gate)

    # ---- sort-based grouped dispatch over the held experts
    C = _capacity(T, moe)
    fe = tope.reshape(-1)                                    # (T*K,) expert ids
    if moe.n_local < E:
        # held experts by their local index; another chip's go last
        fe = fe - moe.first_held
        fe = jnp.where((fe >= 0) & (fe < moe.n_local), fe, moe.n_local)
        E = moe.n_local
    fw = topw.reshape(-1)
    ftok = jnp.arange(T * K) // K                            # source token ids
    order = jnp.argsort(fe, stable=True)                     # group by expert
    fe_s, fw_s, ftok_s = fe[order], fw[order], ftok[order]
    # slot within expert = sorted rank - start offset of that expert group
    starts = jnp.searchsorted(fe_s, jnp.arange(E))           # (E,)
    slot = jnp.arange(T * K) - starts[jnp.minimum(fe_s, E - 1)]
    keep = (slot < C) & (fe_s < E)
    row = jnp.where(keep, fe_s, E)                           # overflow row E
    col = jnp.where(keep, slot, 0)

    buf = jnp.zeros((E + 1, C, D), cdt)
    buf = buf.at[row, col].add(xt[ftok_s])
    buf = buf[:E]                                            # (E, C, D)
    # NOTE (§Perf, refuted experiment): constraining buf to an EP layout
    # (E on "model", C on data) forced a global re-layout of the sort/
    # scatter ops and grew collective traffic 5x — the dispatch layout is
    # intentionally left to GSPMD; the shard_map local-dispatch variant is
    # the proper EP path (see EXPERIMENTS.md §Perf).

    h = jnp.einsum("ecd,edf->ecf", buf, p["wi"].astype(cdt))
    h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", buf, p["wg"].astype(cdt))
    out = jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(cdt))  # (E, C, D)

    gathered = out[row, col] * jnp.where(keep, fw_s, 0.0)[:, None].astype(cdt)
    y = jnp.zeros((T, D), cdt).at[ftok_s].add(gathered)

    if moe.n_shared:
        y = y + mlp_apply(p["shared"], xt, cfg)
    return y, aux.astype(jnp.float32)


def moe_apply_dense(p, x, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """Reference MoE path: every expert on every token, mask-combined.

    FLOPs scale with n_experts (inflated) — used only as a correctness oracle
    for the grouped dispatch in tests.
    """
    moe = cfg.moe
    B, S, D = x.shape
    E, K = moe.n_experts, moe.top_k
    cdt = dtype_of(cfg.compute_dtype)
    xt = x.reshape(B * S, D).astype(cdt)
    logits = xt.astype(jnp.float32) @ p["router"]
    gates = jax.nn.softmax(logits, axis=-1)
    topw, tope = jax.lax.top_k(gates, K)
    if moe.norm_topk:
        topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    w_full = jnp.zeros_like(gates)
    w_full = jax.vmap(lambda w, t, g: w.at[t].set(g))(w_full, tope, topw)
    w_full = w_full[:, moe.first_held:moe.first_held + moe.n_local]

    h = jnp.einsum("td,edf->etf", xt, p["wi"].astype(cdt))
    h = jax.nn.silu(h) * jnp.einsum("td,edf->etf", xt, p["wg"].astype(cdt))
    out = jnp.einsum("etf,efd->etd", h, p["wo"].astype(cdt))
    y = jnp.einsum("etd,te->td", out, w_full.astype(cdt))
    density = jnp.mean(jax.nn.one_hot(tope[:, 0], E), axis=0)
    aux = E * jnp.sum(density * jnp.mean(gates, axis=0))
    if moe.n_shared:
        y = y + mlp_apply(p["shared"], xt, cfg)
    return y.reshape(B, S, D), aux.astype(jnp.float32)
