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

Over a paged cache a step of several tokens views its slot's table as far as
its context REACHES, not as wide as the table: a slot's lanes are in the order
its tokens were written, so every lane from ``len + T`` on holds nothing and
the causal bias hides it, and scoring, normalising and weighing such lanes is
work thrown away. The reach is rounded up to whole STEPS of the kind's own
width (``view_steps``: ``index_topk`` lanes where the kind selects the tokens
it reads, ops/dsa.py, else ``VIEW_STEP_LANES``; in whole blocks), one branch
of static width a count of steps inside the one program (``lax.switch``,
models/hybrid.py): one softmax a branch, the hidden lanes of its last step
contributing exact zeros. The branches read the pools as operands and return
the attention's output alone. A token step keeps the table-wide view: its
slots share one program and the longest context decides.
"""

from __future__ import annotations

import jax.numpy as jnp

# lanes a step of the view of a kind that does NOT select (Kimi's table of 12,288
# lanes: twelve widths; Ling's of 1,536: two). On a v5e Kimi's 256-token chunk
# program reads the same at equal widths under steps of 1,024 and of 2,048
# (16.19 / 16.35 ms at 2,048 lanes, 31.16 / 31.38 at the table's) and 1.5 ms
# less a 1,024 lanes not viewed: over cell 8's cursors 20.30 against 21.23 ms
# a chunk, the table-wide view 31.12 (PERF.md §6, PR 46)
VIEW_STEP_LANES = 1024


def view_steps(tokens: int, columns: int, block_size: int, index_topk: int) -> tuple:
    """The widths, in table columns, that a latent kind's step of ``tokens``
    tokens a row may give its view of a block table of ``columns`` columns,
    one a count of steps read: whole steps of ``index_topk`` lanes (of
    ``VIEW_STEP_LANES`` where that is 0: a kind that does not select; in
    whole blocks, at least one), the last cut at the table. ``()`` where the
    step keeps the table-wide view: a token step, a table of one step."""
    step = max(1, (index_topk or VIEW_STEP_LANES) // block_size)
    if tokens == 1 or columns <= step:
        return ()
    return tuple(min(n, columns) for n in range(step, columns + step, step))


def view_lanes(cursor: int, tokens: int, index_topk: int, block_size: int, columns: int) -> int:
    """Lanes of the view of one row's step of ``tokens`` tokens at lane
    cursor ``cursor``: what the scheduler counts, by the program's own rule
    (its reach ``cursor + tokens`` in whole steps, cut at the table)."""
    steps = view_steps(tokens, columns, block_size, index_topk)
    if not steps:
        return columns * block_size
    step = steps[0] * block_size
    return min(-(-(cursor + tokens) // step) * step, columns * block_size)


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
