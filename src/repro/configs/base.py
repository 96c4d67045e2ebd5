"""Configuration dataclasses for the repro framework.

Every assigned architecture is a ``ModelConfig`` built in its own module
under ``repro.configs`` and registered in ``repro.configs.registry``.
Configs are plain frozen dataclasses: hashable, comparable, and safe to use
as jit static arguments.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts block configuration."""

    n_experts: int
    top_k: int
    d_expert: int
    n_shared: int = 0            # always-on shared experts (DeepSeekMoE)
    router_aux_weight: float = 0.01
    capacity_factor: float = 1.25  # used by the dropping dispatch path
    dispatch: str = "dense"      # "dense" (einsum masking) | "a2a" (EP all-to-all)
    norm_topk: bool = True       # renormalize the top-k gates to sum to 1
    # the experts this chip holds, of the router's n_experts: experts
    # first_held .. first_held + n_held - 1 (n_held 0: all of them).
    # Expert parallelism's share: the router still scores every expert,
    # and the layer returns the held experts' part of the result
    first_held: int = 0
    n_held: int = 0

    @property
    def n_local(self) -> int:
        return self.n_held or self.n_experts


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2): keys and values come
    from one cached latent per token, ``kv_lora_rank`` wide after its
    RMSNorm, beside a ``qk_rope_head_dim`` rotary key shared by the heads.
    Rotary positions follow YaRN (``rope_factor`` over
    ``rope_original_max``, the ramp between the correction dims of
    ``beta_fast`` and ``beta_slow``); ``mscale_all_dim`` scales the
    softmax. Prefill attends in query blocks of ``q_block``."""

    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_factor: float = 1.0
    rope_original_max: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0
    q_block: int = 1024

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


@dataclass(frozen=True)
class SSMConfig:
    """Mamba2 (SSD) mixer configuration."""

    d_state: int = 64
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64           # SSD head dim (P); n_ssm_heads = expand*d_model/head_dim
    chunk: int = 256             # chunk length for the chunked SSD scan


@dataclass(frozen=True)
class XLSTMConfig:
    """xLSTM block layout (mLSTM-dominant with periodic sLSTM)."""

    slstm_every: int = 8         # one sLSTM block per this many blocks (xLSTM[7:1])
    chunk: int = 256             # chunk length for the chunked mLSTM scan
    expand: int = 2              # mLSTM up-projection factor


@dataclass(frozen=True)
class ModelConfig:
    """Architecture definition. One instance per assigned architecture."""

    name: str
    family: str                  # dense | moe | hybrid | ssm | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    d_head: int = 0              # 0 -> d_model // n_heads
    act: str = "swiglu"          # swiglu | relu2 | gelu
    qkv_bias: bool = False
    causal: bool = True
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    xlstm: Optional[XLSTMConfig] = None
    mla: Optional[MLAConfig] = None  # latent attention in place of GQA
    n_dense_layers: int = 0      # moe: leading dense layers (MLP of d_ff)
    attn_every: int = 0          # hybrid: shared attn block every k mixer layers
    frontend: str = "none"       # none | audio_frames | vision_patches
    n_patches: int = 0           # vlm: image patch embeddings prepended to text
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    moment_dtype: str = "float32"
    # beyond-paper perf: explicit activation sharding constraints (§Perf).
    # False = the measured baseline; True pins attention/MLP/logits
    # intermediates to (batch->data, features->model) layouts.
    shard_hints: bool = False

    @property
    def head_dim(self) -> int:
        return self.d_head if self.d_head else self.d_model // self.n_heads

    @property
    def has_attention(self) -> bool:
        return self.family in ("dense", "moe", "audio", "vlm") or self.attn_every > 0

    @property
    def has_kv_cache(self) -> bool:
        # encoder-only archs never decode; pure-SSM archs use recurrent state.
        return self.has_attention and self.causal

    @property
    def is_decoder(self) -> bool:
        return self.causal

    @property
    def sub_quadratic(self) -> bool:
        """True if sequence mixing is sub-quadratic (SSM / hybrid / linear attn)."""
        return self.family in ("hybrid", "ssm")

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class ShapeSpec:
    """One assigned workload shape (applies per architecture)."""

    name: str
    seq_len: int
    global_batch: int
    kind: str                    # train | prefill | decode


TRAIN_4K = ShapeSpec("train_4k", 4096, 256, "train")
PREFILL_32K = ShapeSpec("prefill_32k", 32768, 32, "prefill")
DECODE_32K = ShapeSpec("decode_32k", 32768, 128, "decode")
LONG_500K = ShapeSpec("long_500k", 524288, 1, "decode")

SHAPES: Tuple[ShapeSpec, ...] = (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)
SHAPE_BY_NAME = {s.name: s for s in SHAPES}


def shape_applicability(cfg: ModelConfig, shape: ShapeSpec) -> Optional[str]:
    """Return None if the (arch, shape) cell runs, else a skip reason."""
    if shape.kind == "decode" and not cfg.is_decoder:
        return "encoder-only arch has no decode step"
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return "long_500k requires sub-quadratic attention (full-attention arch)"
    return None


@dataclass(frozen=True)
class TrainConfig:
    """Training-step hyperparameters (shape-independent)."""

    lr: float = 3e-4
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    microbatches: int = 1        # gradient accumulation (scan over microbatches)
    remat: str = "full"          # none | full | dots  (activation checkpoint policy)
    zero_moments: bool = True    # shard optimizer moments over the data axis (ZeRO-1)
    grad_compress: bool = False  # int8 all-reduce with error feedback
    scan_layers: bool = True


@dataclass(frozen=True)
class MeshConfig:
    shape: Tuple[int, ...] = (16, 16)
    axes: Tuple[str, ...] = ("data", "model")

    @property
    def n_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n


SINGLE_POD = MeshConfig((16, 16), ("data", "model"))
MULTI_POD = MeshConfig((2, 16, 16), ("pod", "data", "model"))
