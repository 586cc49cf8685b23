"""Multi-head latent attention (MLA, DeepSeek-V2 section 2.1) in absorbed form.

A token's keys and values of every head come from ONE latent row ``c`` (width
``kv_lora_rank``, RMS-normed) through ``kv_b``, plus one rotated key ``kR``
(width ``rope_dim``) that all heads share. What is cached per token is ``[c |
kR]`` and nothing else. Attention over the cache never expands a cached row
into heads: with ``kv_b = [Wkb_h | Wvb_h]`` per head,

    q^_h   = q_nope_h Wkb_h^T                       (as wide as c)
    score  = (q^_h . c_s + q_rope_h . kR_s) * scale
    o_h    = (sum_s p_s c_s) Wvb_h

which is multi-query attention with one KV head whose key is the cached row
and whose value is the row's first ``kv_lora_rank`` lanes. ``scale`` is over
the width the model's own heads have (nope + rope), not the latent's.

RoPE here is INTERLEAVED: the pairs are lanes ``(2i, 2i + 1)``, not the two
halves of the head (ops/rope.py's convention).
"""

from __future__ import annotations

import jax.numpy as jnp


def rope_interleaved(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x [B, T, H, r]; cos/sin [B, T, r // 2]. Rotates each pair ``(2i, 2i+1)``."""
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    x1, x2 = pairs[..., 0], pairs[..., 1]
    c = cos[:, :, None, :].astype(x.dtype)
    s = sin[:, :, None, :].astype(x.dtype)
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).reshape(x.shape)


def split_kv_b(kv_b: jnp.ndarray, heads: int, nope_dim: int):
    """``kv_b`` [rank, H * (nope + v)] as (Wkb [rank, H, nope], Wvb [rank, H, v])."""
    w = kv_b.reshape(kv_b.shape[0], heads, -1)
    return w[..., :nope_dim], w[..., nope_dim:]


def absorb_query(q_nope: jnp.ndarray, wkb: jnp.ndarray) -> jnp.ndarray:
    """q_nope [B, T, H, nope] -> q^ [B, T, H, rank]."""
    return jnp.einsum("bthn,chn->bthc", q_nope, wkb.astype(q_nope.dtype))


def expand_value(o_latent: jnp.ndarray, wvb: jnp.ndarray) -> jnp.ndarray:
    """o^ [B, T, H, rank] -> o [B, T, H, v]."""
    return jnp.einsum("bthc,chv->bthv", o_latent, wvb.astype(o_latent.dtype))
