"""Logical sharding rules: pytree-path + shape -> PartitionSpec.

Baseline layout (the paper-faithful starting point for the roofline):

  * ``model`` axis = tensor parallelism: attention head/ffn-hidden/vocab
    dims; MoE expert dim when divisible (expert parallelism), else the
    expert-hidden dim (TP inside experts).
  * ``data`` axis = batch AND fully-sharded parameters (FSDP/ZeRO-3 style:
    the contraction-side dim of each weight shards over ``data``; GSPMD
    inserts the per-layer all-gathers). Optimizer moments inherit the same
    specs (ZeRO-1 comes for free: they are already fully sharded).
  * ``pod`` axis (multi-pod mesh) = pure data parallelism over the batch.

Every rule is divisibility-guarded: a dim that doesn't divide evenly by its
target axis falls back to replication (recorded — the roofline table shows
where that costs us, e.g. granite's 40 experts on a 16-way model axis).
"""
from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeSpec


def _axis_size(mesh: Mesh, name) -> int:
    if name is None:
        return 1
    if isinstance(name, tuple):
        out = 1
        for n in name:
            out *= _axis_size(mesh, n)
        return out
    return mesh.shape[name]


def data_axes(mesh: Mesh):
    """The batch axis spec: ("pod","data") on multi-pod meshes."""
    return ("pod", "data") if "pod" in mesh.axis_names else "data"


def _fit(dim: int, axis, mesh: Mesh):
    """axis if dim divides evenly, else None (replicate)."""
    return axis if axis is not None and dim % _axis_size(mesh, axis) == 0 \
        else None


def _path_keys(path) -> Tuple[str, ...]:
    return tuple(str(getattr(e, "key", getattr(e, "name", e))).lower()
                 for e in path)


# --------------------------------------------------------------- params
def param_spec(path, shape, mesh: Mesh, cfg: ModelConfig,
               tp_only: bool = False) -> P:
    """``tp_only=True`` is the serving layout: weights shard over "model"
    only (no FSDP dim), so decode never all-gathers weights — usable
    whenever params/model_axis fits HBM (everything but the 340B/405B
    archs on a 16-way model axis)."""
    keys = _path_keys(path)
    nd = len(shape)
    last = keys[-1]
    contract_default = None if tp_only else "data"

    def two_dim(d_contract, d_out, contract_axis="data", out_axis="model"):
        """Spec for the trailing two dims; leading dims replicated."""
        if tp_only:
            contract_axis = None if contract_axis == "data" else contract_axis
            out_axis = None if out_axis == "data" else out_axis
        lead = (None,) * (nd - 2)
        return P(*lead, _fit(d_contract, contract_axis, mesh),
                 _fit(d_out, out_axis, mesh))

    # --- embeddings / head: vocab on model, feature replicated
    if last in ("embed",):
        return P(_fit(shape[0], "model", mesh), None)
    if last == "head":
        return P(_fit(shape[0], contract_default, mesh), _fit(shape[1], "model", mesh))
    if last in ("patch_proj", "frame_proj"):
        return P(_fit(shape[0], contract_default, mesh), _fit(shape[1], "model", mesh))

    # --- MoE experts: (L, E, D, Fe) / (L, E, Fe, D)
    if "moe" in keys or "experts" in keys or last == "router":
        if last == "router":
            lead = (None,) * (nd - 2)
            return P(*lead, _fit(shape[-2], contract_default, mesh), None)
        if last in ("wi", "wg", "wo") and nd >= 3:
            e, d_in, d_out = shape[-3], shape[-2], shape[-1]
            ep = _fit(e, "model", mesh)
            lead = (None,) * (nd - 3)
            if ep is not None:      # expert parallelism
                return P(*lead, ep, _fit(d_in, contract_default, mesh), None)
            # fall back: TP inside each expert
            return P(*lead, None, _fit(d_in, contract_default, mesh),
                     _fit(d_out, "model", mesh))
        # shared expert MLP (dict under moe): fall through to generic below

    # --- norms / biases / small vectors: replicate
    if nd <= 1 or "norm" in last or last in ("b", "b_i", "b_f", "bias",
                                             "conv_b", "a_log", "dt_bias",
                                             "d_skip"):
        return P(*(None,) * nd)

    # --- attention / mlp / ssm projections: contract dim on data,
    #     output-feature dim on model (or flipped for the down/out projs)
    if last in ("wo", "out_proj", "down_proj"):
        return two_dim(shape[-2], shape[-1], "model", "data")
    if last in ("wq", "wk", "wv", "wi", "wg", "in_proj", "up_proj",
                "w_in", "w_if"):
        return two_dim(shape[-2], shape[-1], "data", "model")
    if last == "conv_w":            # (W, conv_dim) depthwise
        lead = (None,) * (nd - 2)
        return P(*lead, None, _fit(shape[-1], "model", mesh))
    if last == "r_rec":             # (H, dh, 4dh) block-diag recurrent
        lead = (None,) * (nd - 3)
        return P(*lead, None, None, _fit(shape[-1], "model", mesh))
    if last in ("bq", "bk", "bv"):
        lead = (None,) * (nd - 1)
        return P(*lead, _fit(shape[-1], "model", mesh))
    # default: replicate (safe)
    return P(*(None,) * nd)


def param_shardings(params_shape, mesh: Mesh, cfg: ModelConfig,
                    tp_only: bool = False):
    """Pytree of NamedShardings matching a params (shape-)pytree."""
    def one(path, leaf):
        return NamedSharding(mesh, param_spec(path, leaf.shape, mesh, cfg,
                                              tp_only=tp_only))
    return jax.tree_util.tree_map_with_path(one, params_shape)


def opt_shardings(opt_shape, params_shape, mesh: Mesh, cfg: ModelConfig):
    """Moments inherit the param specs; scalars replicate."""
    pspecs = param_shardings(params_shape, mesh, cfg)
    return {"m": pspecs, "v": pspecs,
            "count": NamedSharding(mesh, P())}


# ---------------------------------------------------------------- batch
def batch_shardings(batch_shape, mesh: Mesh):
    dp = data_axes(mesh)

    def one(path, leaf):
        b = leaf.shape[0] if leaf.ndim else 1
        ax = dp if b % _axis_size(mesh, dp) == 0 else None
        return NamedSharding(mesh, P(ax, *(None,) * (leaf.ndim - 1)))
    return jax.tree_util.tree_map_with_path(one, batch_shape)


# ---------------------------------------------------------------- cache
def cache_spec(path, shape, mesh: Mesh, cfg: ModelConfig,
               seq_shard: bool = False) -> P:
    """Decode-cache leaves: (L, B, S, K, dh) KV, or SSM states.

    Baseline shards B over data and K-heads over model (when divisible);
    ``seq_shard=True`` moves the model axis to the sequence dim instead
    (flash-decode style; the beyond-paper variant for GQA archs whose
    kv-head count < model axis).
    """
    keys = _path_keys(path)
    last = keys[-1]
    dp = data_axes(mesh)
    nd = len(shape)
    if last in ("k", "v", "attn_k", "attn_v"):
        b, s, kh = shape[-4], shape[-3], shape[-2]
        bax = dp if b % _axis_size(mesh, dp) == 0 else None
        lead = (None,) * (nd - 4)
        if seq_shard:
            return P(*lead, bax, _fit(s, "model", mesh), None, None)
        kax = _fit(kh, "model", mesh)
        if kax is not None:
            return P(*lead, bax, None, kax, None)
        return P(*lead, bax, _fit(s, "model", mesh), None, None)
    if last in ("mamba_conv", "m_conv"):        # (..., B, W-1, conv_dim)
        b, cdim = shape[-3], shape[-1]
        lead = (None,) * (nd - 3)
        bax = dp if b % _axis_size(mesh, dp) == 0 else None
        return P(*lead, bax, None, _fit(cdim, "model", mesh))
    if last in ("mamba_ssm", "m_c"):            # (..., B, H, N, P)
        b, h = shape[-4], shape[-3]
        lead = (None,) * (nd - 4)
        bax = dp if b % _axis_size(mesh, dp) == 0 else None
        hax = _fit(h, "model", mesh)
        if hax is not None:
            return P(*lead, bax, hax, None, None)
        return P(*lead, bax, None, None, _fit(shape[-1], "model", mesh))
    if last in ("s_c", "s_n", "s_h", "s_m"):    # (G, B, D)
        b, d = shape[-2], shape[-1]
        lead = (None,) * (nd - 2)
        bax = dp if b % _axis_size(mesh, dp) == 0 else None
        return P(*lead, bax, _fit(d, "model", mesh))
    return P(*(None,) * nd)


def cache_shardings(cache_shape, mesh: Mesh, cfg: ModelConfig,
                    seq_shard: bool = False):
    def one(path, leaf):
        return NamedSharding(mesh, cache_spec(path, leaf.shape, mesh, cfg,
                                              seq_shard))
    return jax.tree_util.tree_map_with_path(one, cache_shape)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


# ------------------------------------------------- activation hints
def ambient_mesh():
    """The mesh entered with ``jax.set_mesh`` (an ``AbstractMesh``), or
    None outside one."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def hint(x, *axes):
    """with_sharding_constraint with divisibility fallback; no-op outside a
    mesh context. ``axes`` entries: None, an axis name, "dp" (the batch
    axes), or a tuple of axis names.

    GSPMD's strategy search sometimes replicates large intermediates (we
    measured attention running 8x data-replicated on the baseline) —
    explicit activation constraints pin the intended layout.
    """
    m = ambient_mesh()
    if m is None:
        return x
    spec = []
    for dim, ax in zip(x.shape, axes):
        if ax == "dp":
            ax = data_axes(m)
        if ax is None or any(a not in m.axis_names
                             for a in (ax if isinstance(ax, tuple)
                                       else (ax,))):
            spec.append(None)
        elif dim % _axis_size(m, ax) == 0:
            spec.append(ax)
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(x, P(*spec))
