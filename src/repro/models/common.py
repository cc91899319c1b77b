"""Shared building blocks: norms, RoPE, activations, initializers.

All models are pure functions over param pytrees (dicts of jnp arrays).
Layer-stacked params carry a leading ``L`` axis and are consumed by
``jax.lax.scan`` so compiled HLO size is independent of depth.
"""
from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp

Initializer = Callable[[jax.Array, tuple, jnp.dtype], jax.Array]


def normal_init(stddev: float = 0.02):
    def init(key, shape, dtype=jnp.float32):
        return (stddev * jax.random.normal(key, shape)).astype(dtype)
    return init


def fan_in_init():
    def init(key, shape, dtype=jnp.float32):
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        std = 1.0 / math.sqrt(fan_in)
        return (std * jax.random.normal(key, shape)).astype(dtype)
    return init


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def activation(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return jax.nn.gelu
    if name == "relu":
        # squared relu (Nemotron/minitron); plain relu is never used gated
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(f"unknown activation {name!r}")


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    """(head_dim//2,) inverse frequencies."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotate pairs. x: (..., S, H, D); positions: broadcastable to (..., S)."""
    freqs = rope_freqs(x.shape[-1], theta)                       # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs    # (..., S, D/2)
    cos = jnp.cos(angles)[..., None, :]                          # (..., S, 1, D/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------

def cross_entropy(logits: jax.Array, labels: jax.Array,
                  ignore_index: int = -100) -> jax.Array:
    """Mean CE over non-ignored positions. logits (..., V), labels (...)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    mask = (labels != ignore_index).astype(jnp.float32)
    nll = (lse - gold) * mask
    return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)


def chunked_lm_loss(hidden: jax.Array, lm_head: jax.Array, labels: jax.Array,
                    chunk: int = 512) -> jax.Array:
    """Next-token CE without materializing (B, S, V) at once.

    Scans over sequence chunks; each chunk's logits are rematerialized in the
    backward pass (jax.checkpoint), so peak memory is (B, chunk, V).
    hidden: (B, S, d); lm_head: (d, V); labels: (B, S).
    """
    B, S, d = hidden.shape
    chunk = min(chunk, S)
    if S % chunk:
        pad = chunk - S % chunk
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-100)
        S += pad
    n = S // chunk
    hid = hidden.reshape(B, n, chunk, d).transpose(1, 0, 2, 3)
    lab = labels.reshape(B, n, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def one(h, l):
        logits = jnp.einsum("bsd,dv->bsv", h, lm_head)
        logits = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(l, 0)[..., None], axis=-1)[..., 0]
        mask = (l != -100).astype(jnp.float32)
        return jnp.sum((lse - gold) * mask), jnp.sum(mask)

    # per-chunk sums come out as scan outputs, not a carry: a constant
    # initial carry would be replicated while the sums vary over a
    # shard_map's client axis, and scan requires the two to match
    _, (nll, cnt) = jax.lax.scan(lambda c, xs: (c, one(*xs)), None,
                                 (hid, lab))
    return jnp.sum(nll) / jnp.maximum(jnp.sum(cnt), 1.0)
