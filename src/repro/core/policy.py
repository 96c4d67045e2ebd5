"""HRM policy: the region -> tier mapping (the paper's granularity dimension
at memory-region level) plus the evaluated design points (the paper's
five, and two strong-ECC extensions measured through the DEC-TED / BURST
kernels).

Regions of a training/serving job's state (the TPU analogue of the paper's
stack/heap/private classification) are derived from pytree paths:

    params/embed   token/patch/frame embeddings + LM head
    params/attn    attention projections (incl. shared hybrid block)
    params/mlp     dense MLP weights
    params/experts MoE expert weights (cold, Par+R-friendly)
    params/ssm     Mamba2 / xLSTM mixer weights
    params/norm    norms and other small vectors
    opt/m, opt/v   optimizer moments
    kv_cache       decode KV cache / recurrent states
    kv_cache/latent  latent-attention cache: one latent word feeds the
                   keys and values of every head of its token, where a
                   GQA word feeds one KV head (same bytes, different
                   vulnerability)
    activations    transient per-step tensors (policy is advisory: they are
                   never scrubbed, only accounted in the cost model)
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Tuple

import jax

from repro.core.errormodel import ErrorModel
from repro.core.tiers import Tier

REGIONS = ("params/embed", "params/attn", "params/mlp", "params/experts",
           "params/ssm", "params/norm", "opt/m", "opt/v", "kv_cache",
           "kv_cache/latent", "activations", "graph/topology", "graph/rank",
           "graph/frontier")

_SSM_KEYS = ("mamba", "mlstm", "slstm", "conv_w", "conv_b", "a_log",
             "dt_bias", "d_skip")
_EMBED_KEYS = ("embed", "head", "patch_proj", "frame_proj")
_ATTN_KEYS = ("attn", "wq", "wk", "wv", "wo", "bq", "bk", "bv")
_EXPERT_KEYS = ("moe", "experts", "router")
_CACHE_KEYS = ("k", "v", "attn_k", "attn_v", "mamba_conv", "mamba_ssm",
               "m_conv", "m_c", "s_c", "s_n", "s_h", "s_m", "c_kv", "k_pe")
_LATENT_KEYS = ("c_kv", "k_pe")
_GRAPH_TOPO_KEYS = ("topology", "indptr", "indices", "src", "dst", "outdeg")
_GRAPH_FRONTIER_KEYS = ("frontier", "visited", "dist")


def _path_keys(path) -> Tuple[str, ...]:
    out = []
    for e in path:
        if isinstance(e, jax.tree_util.DictKey):
            out.append(str(e.key).lower())
        elif isinstance(e, jax.tree_util.GetAttrKey):
            out.append(str(e.name).lower())
        else:
            out.append(str(e).lower())
    return tuple(out)


def classify_path(path, root: str = "params") -> str:
    """Map a pytree path to an HRM region name."""
    keys = _path_keys(path)
    if root == "opt":
        return "opt/m" if keys and keys[0] in ("m", "mu") else "opt/v"
    if root == "cache":
        return ("kv_cache/latent" if set(keys) & set(_LATENT_KEYS)
                else "kv_cache")
    if root == "graph":
        ks = set(keys)
        if ks & set(_GRAPH_TOPO_KEYS):
            return "graph/topology"
        if ks & set(_GRAPH_FRONTIER_KEYS):
            return "graph/frontier"
        return "graph/rank"
    ks = set(keys)
    if "moe" in ks and "shared" in ks:
        return "params/mlp"             # shared experts run on every token
    if ks & set(_EXPERT_KEYS):
        return "params/experts"
    if ks & set(_SSM_KEYS):
        return "params/ssm"
    if any(k in _EMBED_KEYS for k in keys):
        return "params/embed"
    if ks & set(_ATTN_KEYS):
        return "params/attn"
    if any("norm" in k for k in keys):
        return "params/norm"
    if any(k in ("mlp", "wi", "wg", "shared") for k in keys):
        return "params/mlp"
    return "params/mlp"


@dataclass(frozen=True)
class HRMPolicy:
    """region -> Tier, with a default for unlisted regions."""
    name: str
    tiers: Dict[str, Tier] = field(default_factory=dict)
    default: Tier = Tier.NONE
    error_model: ErrorModel = field(default_factory=ErrorModel)
    scrub_interval: int = 50           # steps between scrub passes

    def tier_of(self, region: str) -> Tier:
        return self.tiers.get(region, self.default)

    def __hash__(self):
        return hash((self.name, tuple(sorted(
            (k, v.value) for k, v in self.tiers.items())), self.default.value))


# ------------------------------------------------- the five design points
def typical_server() -> HRMPolicy:
    """Baseline: SEC-DED homogeneously everywhere (non-HRM)."""
    return HRMPolicy("typical_server",
                     {r: Tier.SECDED for r in REGIONS},
                     default=Tier.SECDED)


def consumer_pc() -> HRMPolicy:
    """No protection anywhere (non-HRM)."""
    return HRMPolicy("consumer_pc", {}, default=Tier.NONE)


def detect_recover() -> HRMPolicy:
    """HRM: Par+R on the long-lived 'private'-like regions, none elsewhere."""
    return HRMPolicy(
        "detect_recover",
        {"params/embed": Tier.PARITY_R, "params/attn": Tier.PARITY_R,
         "params/mlp": Tier.PARITY_R, "params/experts": Tier.PARITY_R,
         "params/ssm": Tier.PARITY_R, "params/norm": Tier.PARITY_R,
         "opt/m": Tier.PARITY_R, "opt/v": Tier.PARITY_R,
         "graph/topology": Tier.PARITY_R, "graph/rank": Tier.PARITY_R,
         "graph/frontier": Tier.PARITY_R},
        default=Tier.NONE)


def less_tested() -> HRMPolicy:
    """SEC-DED everywhere on less-tested devices (non-HRM)."""
    p = typical_server()
    return HRMPolicy("less_tested", dict(p.tiers), default=Tier.SECDED,
                     error_model=ErrorModel(less_tested=True))


def detect_recover_l() -> HRMPolicy:
    """HRM on less-tested devices: SEC-DED on the most vulnerable regions,
    Par+R on the bulky tolerant ones."""
    return HRMPolicy(
        "detect_recover_l",
        {"params/embed": Tier.SECDED, "params/attn": Tier.SECDED,
         "params/norm": Tier.SECDED, "params/ssm": Tier.SECDED,
         "params/mlp": Tier.PARITY_R, "params/experts": Tier.PARITY_R,
         "opt/m": Tier.PARITY_R, "opt/v": Tier.PARITY_R,
         # graph workload: the pointer-heavy topology is crash-vulnerable
         # (Fig.4 analogue) -> SEC-DED; the numeric iterate self-heals
         # under convergence -> Par+R
         "graph/topology": Tier.SECDED, "graph/rank": Tier.PARITY_R,
         "graph/frontier": Tier.PARITY_R},
        default=Tier.NONE,
        error_model=ErrorModel(less_tested=True))


def dected_server() -> HRMPolicy:
    """Strong homogeneous baseline: true DEC-TED everywhere (non-HRM).
    Prices the 15/64 code-bit premium; availability is *measured* through
    the DEC-TED Pallas kernels (``core.eccmeasure``), not assumed."""
    return HRMPolicy("dected_server",
                     {r: Tier.DECTED for r in REGIONS},
                     default=Tier.DECTED)


def burst_dr_l() -> HRMPolicy:
    """HRM on less-tested devices with burst-correcting ECC on the
    vulnerable regions: SEC-DAEC (adjacent-double correct) where
    detect_recover_l used SEC-DED, Par+R on the bulky tolerant regions.
    Survives the spatially-correlated multi-bit faults field studies
    report dominating on marginal devices."""
    base = detect_recover_l()
    tiers = {r: (Tier.BURST if t == Tier.SECDED else t)
             for r, t in base.tiers.items()}
    return HRMPolicy("burst_dr_l", tiers, default=Tier.NONE,
                     error_model=ErrorModel(less_tested=True))


def mirror_dr_l() -> HRMPolicy:
    """HRM on less-tested devices with full mirroring on the vulnerable
    regions: MIRROR (replica + parity, Table 1's most expensive tier)
    where detect_recover_l used SEC-DED, Par+R on the bulky tolerant
    regions. The top of the protection-vs-capacity curve; availability is
    *measured* through the MIRROR repair path (``core.eccmeasure``)."""
    base = detect_recover_l()
    tiers = {r: (Tier.MIRROR if t == Tier.SECDED else t)
             for r, t in base.tiers.items()}
    return HRMPolicy("mirror_dr_l", tiers, default=Tier.NONE,
                     error_model=ErrorModel(less_tested=True))


def peer_dr_l() -> HRMPolicy:
    """Replication-aware two-tier HRM on less-tested devices
    (arXiv:2309.00304 / arXiv:2502.17138): a live data-parallel replica is
    the strong tier, so every region detect_recover_l protected drops to
    cheap Par+R locally — detected errors recover by an in-memory peer
    copy (``Response.PEER_COPY``, ``PEER_COPY_SECONDS``), falling back to
    the disk reload only when all replicas of a shard are flagged."""
    base = detect_recover_l()
    tiers = {r: Tier.PARITY_R for r in base.tiers}
    return HRMPolicy("peer_dr_l", tiers, default=Tier.NONE,
                     error_model=ErrorModel(less_tested=True))


DESIGN_POINTS = {
    "typical_server": typical_server,
    "consumer_pc": consumer_pc,
    "detect_recover": detect_recover,
    "less_tested": less_tested,
    "detect_recover_l": detect_recover_l,
    "dected_server": dected_server,
    "burst_dr_l": burst_dr_l,
    "mirror_dr_l": mirror_dr_l,
    "peer_dr_l": peer_dr_l,
}
