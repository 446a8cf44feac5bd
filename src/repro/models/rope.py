"""Rotary position embeddings: standard RoPE, YaRN-scaled RoPE
(DeepSeek-V2) and Qwen2-VL M-RoPE."""
from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term: 0.1 mscale ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_correction_dim(rotations: float, dim: int, base: float,
                         max_pos: int) -> float:
    return (dim * math.log(max_pos / (rotations * 2 * math.pi))) \
        / (2 * math.log(base))


def rope_inv_freq(head_dim: int, theta: float, yarn=None) -> np.ndarray:
    """(head_dim//2,) float32 inverse frequencies.  With `yarn` (a
    YaRNConfig), pairs below the beta_fast correction dim keep the base
    frequency, pairs from the beta_slow one on are divided by the factor,
    and the pairs between are ramped linearly (DeepSeek-V2's
    DeepseekV2YarnRotaryEmbedding)."""
    half = head_dim // 2
    base = 1.0 / (theta ** (np.arange(half, dtype=np.float32) / half))
    if yarn is None:
        return base
    low = max(math.floor(_yarn_correction_dim(
        yarn.beta_fast, head_dim, theta, yarn.original_max_position)), 0)
    high = min(math.ceil(_yarn_correction_dim(
        yarn.beta_slow, head_dim, theta, yarn.original_max_position)),
        head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float32) - low) / (high - low),
                   0.0, 1.0)
    keep = 1.0 - ramp                       # 1: base frequency, 0: scaled
    return (base / yarn.factor * (1.0 - keep) + base * keep) \
        .astype(np.float32)


def yarn_softmax_factor(yarn) -> float:
    """The attention logits' extra scale: mscale(factor, mscale_all_dim)^2
    (1 without YaRN or where mscale_all_dim is 0)."""
    if yarn is None or not yarn.mscale_all_dim:
        return 1.0
    return yarn_mscale(yarn.factor, yarn.mscale_all_dim) ** 2


def rope_angles(positions, head_dim: int, theta: float, yarn=None):
    """positions (..., S) int -> (sin, cos) of shape (..., S, head_dim//2).
    With `yarn`, YaRN's frequencies, and sin and cos scaled by
    mscale(factor, mscale) / mscale(factor, mscale_all_dim)."""
    if yarn is None:
        half = head_dim // 2
        inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    else:
        inv_freq = jnp.asarray(rope_inv_freq(head_dim, theta, yarn))
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    if yarn is not None:
        m = yarn_mscale(yarn.factor, yarn.mscale) \
            / yarn_mscale(yarn.factor, yarn.mscale_all_dim)
        if m != 1.0:
            sin, cos = sin * m, cos * m
    return sin, cos


def mrope_angles(mpositions, sections, head_dim: int, theta: float):
    """Multimodal RoPE (Qwen2-VL).

    mpositions: (3, B, S) — temporal / height / width position streams.
    sections:   per-stream rotary half-dims, summing to head_dim//2.
    Returns (sin, cos) of shape (B, S, head_dim//2).
    """
    half = head_dim // 2
    assert sum(sections) == half, (sections, half)
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    stream = jnp.repeat(jnp.arange(len(sections)), jnp.array(sections),
                        total_repeat_length=half)          # (half,)
    pos = jnp.take(mpositions, stream, axis=0)             # (half, B, S)
    pos = jnp.moveaxis(pos, 0, -1).astype(jnp.float32)     # (B, S, half)
    ang = pos * inv_freq
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(x, sin, cos):
    """x: (..., S, H, hd); sin/cos: (..., S, hd//2) broadcast over heads.
    Half-split (llama) convention."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    s, c = sin[..., None, :], cos[..., None, :]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([x1f * c - x2f * s, x2f * c + x1f * s], axis=-1)
    return out.astype(x.dtype)
