"""Rotary position embeddings with linear / dynamic-NTK / YaRN scaling.

The reference exposes ``--rope_scaling {linear,dynamic}`` (reference
cmd/tuning/parser.py:57-60) which patches HF llama rope at runtime. Here scaling
is a first-class config knob, computed statically so everything stays jittable.

Convention: HF-llama "rotate half" — for x = [x1 | x2] split down the middle of
the head dim, rope(x) = [x1*cos - x2*sin | x2*cos + x1*sin].
"""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np


def yarn_mscale(factor: float, m: float) -> float:
    """YaRN's attention temperature: ``0.1 m ln(factor) + 1`` (1 at no scaling)."""
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_ramp(dim: int, theta: float, yarn) -> np.ndarray:
    """[dim // 2] in [0, 1]: how far each pair's frequency is divided by the
    factor. ``corr(n)`` is the pair that turns ``n`` times over the original
    length; pairs below ``corr(beta_fast)`` keep their frequency (0), pairs
    above ``corr(beta_slow)`` are interpolated whole (1), linear between."""
    def corr(turns):
        return dim * math.log(yarn.original_max_len / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(corr(yarn.beta_fast)), 0)
    high = min(math.ceil(corr(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001  # the published code's guard against a zero span
    return np.clip((np.arange(dim // 2, dtype=np.float32) - low) / (high - low), 0, 1)


def rope_cos_sin(
    positions: jnp.ndarray,  # [B, T] int32
    head_dim: int,
    *,
    theta: float = 10000.0,
    scaling_type: str | None = None,
    scaling_factor: float = 1.0,
    max_seq_len: int = 4096,
    seq_len: int | None = None,
    yarn=None,
    dtype=jnp.float32,
):
    """Returns (cos, sin) each of shape [B, T, head_dim//2]. ``yarn`` (a
    ``models/config.py:YarnScaling``) blends each pair's frequency between its
    own and its own over the factor (``yarn_ramp``) and multiplies both tables
    by ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``."""
    half = head_dim // 2
    if scaling_type == "dynamic" and seq_len is not None and seq_len > max_seq_len:
        # Dynamic NTK: inflate the base theta as the window grows past training
        # length (same formula transformers uses for rope_scaling="dynamic").
        theta = theta * (
            (scaling_factor * seq_len / max_seq_len) - (scaling_factor - 1)
        ) ** (head_dim / (head_dim - 2))
    inv_freq = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    pos = positions.astype(jnp.float32)
    if scaling_type == "linear":
        pos = pos / scaling_factor
    if yarn is not None:
        ramp = yarn_ramp(head_dim, theta, yarn)
        inv_freq = inv_freq * (1 - ramp) + inv_freq / yarn.factor * ramp
    freqs = pos[..., None] * inv_freq  # [B, T, half]
    cos, sin = jnp.cos(freqs), jnp.sin(freqs)
    if yarn is not None:
        m = yarn_mscale(yarn.factor, yarn.mscale) / yarn_mscale(yarn.factor, yarn.mscale_all_dim)
        if m != 1.0:
            cos, sin = cos * m, sin * m
    return cos.astype(dtype), sin.astype(dtype)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x: [B, T, H, head_dim]; cos/sin: [B, T, rotary_dim//2]. Where the
    tables are narrower than half the head (partial rotation), the first
    ``rotary_dim`` dims of each head are rotated, half-split among themselves,
    and the rest pass through."""
    rot = 2 * cos.shape[-1]
    if rot < x.shape[-1]:
        return jnp.concatenate(
            [apply_rope(x[..., :rot], cos, sin), x[..., rot:]], axis=-1)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[:, :, None, :].astype(x.dtype)
    s = sin[:, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
