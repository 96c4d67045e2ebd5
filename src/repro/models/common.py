"""Shared building blocks for the pure-JAX model zoo (no flax/haiku).

Modules are (init, apply) function pairs over plain dict pytrees. Per-layer
parameters are stacked on a leading layer axis and consumed by
``jax.lax.scan`` so HLO size is independent of depth.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


def dtype_of(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]


def dense_init(key, d_in: int, d_out: int, dtype, scale: float | None = None):
    """Truncated-normal fan-in init."""
    if scale is None:
        scale = 1.0 / math.sqrt(d_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, (d_in, d_out),
                                        jnp.float32) * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype):
    return (jax.random.truncated_normal(key, -2.0, 2.0, (vocab, d),
                                        jnp.float32) * 0.02).astype(dtype)


def rmsnorm(x, w, eps: float):
    """RMSNorm in f32, cast back to input dtype."""
    xf = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return ((xf * rms) * w.astype(jnp.float32)).astype(x.dtype)


def act_fn(name: str):
    if name == "swiglu":          # handled by callers with a gate matrix
        return jax.nn.silu
    if name == "relu2":
        return lambda x: jnp.square(jax.nn.relu(x))
    if name == "gelu":
        return jax.nn.gelu
    raise ValueError(f"unknown activation {name!r}")


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, Dh); positions: broadcastable to (..., S)."""
    return apply_rope_freqs(x, positions, rope_freqs(x.shape[-1], theta))


def apply_rope_freqs(x, positions, freqs, scale: float = 1.0):
    """Half-split rotation of x (..., S, H, Dh) by ``freqs`` (Dh/2,);
    ``scale`` multiplies cos and sin (YaRN's attention factor)."""
    ang = positions[..., None].astype(jnp.float32) * freqs   # (..., S, Dh/2)
    ang = ang[..., None, :]                             # (..., S, 1, Dh/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def stacked_init(init_one, key, n: int):
    """vmap an init function over a leading layer axis."""
    keys = jax.random.split(key, n)
    return jax.vmap(init_one)(keys)


def cross_entropy(logits, labels, mask=None):
    """Mean CE over (possibly masked) positions. logits: (..., V) any dtype."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def cross_entropy_sharded(logits, labels, mask=None):
    """CE that never gathers a vocab-sharded logits tensor.

    Both reductions contract over the (possibly sharded) vocab axis —
    logsumexp via max+sum (GSPMD inserts psums), the gold logit via a
    one-hot contraction instead of take_along_axis (whose gather would
    force an all-gather of the full logits).
    """
    lf = logits.astype(jnp.float32)
    V = lf.shape[-1]
    m = jax.lax.stop_gradient(jnp.max(lf, axis=-1, keepdims=True))
    logz = jnp.log(jnp.sum(jnp.exp(lf - m), axis=-1)) + m[..., 0]
    onehot = jax.nn.one_hot(labels, V, dtype=lf.dtype)
    gold = jnp.sum(lf * onehot, axis=-1)
    nll = logz - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
