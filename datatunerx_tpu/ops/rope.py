"""Rotary position embeddings with linear / dynamic-NTK scaling.

The reference exposes ``--rope_scaling {linear,dynamic}`` (reference
cmd/tuning/parser.py:57-60) which patches HF llama rope at runtime. Here scaling
is a first-class config knob, computed statically so everything stays jittable.

Convention: HF-llama "rotate half" — for x = [x1 | x2] split down the middle of
the head dim, rope(x) = [x1*cos - x2*sin | x2*cos + x1*sin].
"""

from __future__ import annotations

import jax.numpy as jnp


def rope_cos_sin(
    positions: jnp.ndarray,  # [B, T] int32
    head_dim: int,
    *,
    theta: float = 10000.0,
    scaling_type: str | None = None,
    scaling_factor: float = 1.0,
    max_seq_len: int = 4096,
    seq_len: int | None = None,
    dtype=jnp.float32,
):
    """Returns (cos, sin) each of shape [B, T, head_dim//2]."""
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
    freqs = pos[..., None] * inv_freq  # [B, T, half]
    return jnp.cos(freqs).astype(dtype), jnp.sin(freqs).astype(dtype)


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
