"""Learned selection of cached tokens (DeepSeek sparse attention: the lightning
indexer and the top-k selection of DeepSeek-V3.2, arXiv:2512.02556 section 2).

Beside its latent row every token caches one INDEX KEY ``kI`` (width
``index_dim``, LayerNorm-ed, its first ``rope_dim`` lanes rotated). A query has
``index_heads`` index queries ``qI_j`` and a weight ``w_j`` a head; its score of
a cached token ``s`` is

    I[t, s] = sum_j w[t, j] * ReLU(qI[t, j] . kI[s])            (float32)

and it attends to the ``index_topk`` causally visible tokens of largest ``I``
and to no other (all of them while there are no more than that). The selection
is EXACT, a tie going to the lower lane, which is the earlier token (a slot's
lanes are in the order its tokens were written). An approximate top-k, a
threshold that admits a tie's every member, or a selection by blocks would be
another model's result. Two forms give the one set: ``top_lanes`` (the lanes
themselves, for a gather: ``jax.lax.top_k``, which the TPU runs as a stable
sort of every lane) and ``top_mask`` (the set as a mask over the view, with no
sort: the k-th largest score found bit by bit, then the tie cut by lane; on a
v5e 0.25 ms for 256 rows of 8,704 lanes where the sort takes 7.0).

What a step does with the selection depends on its shapes alone
(``selection_path``): a view no wider than ``index_topk`` selects everything,
so the step is plain latent attention; one token a slot GATHERS its chosen
rows out of the latent pool through the block table and attends to those,
never building the table-wide view of latent rows; a chunk of several tokens
keeps the view ``xla_attention`` takes and masks it a row at a time.

A chunk's view is as wide as its context REACHES, not as its table (the rule
is the latent kind's, ``ops/mla.py:view_steps``; a kind that selects steps by
its ``index_topk``): one branch of static width a count of steps inside the
one program, and each branch asks ``selection_path`` for its own width: the
first, no wider than the selection, is plain latent attention and never runs
the indexer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

K_NORM_EPS = 1e-6  # of the index key's LayerNorm (DeepSeek-V3.2's inference code)

# what one step counts (decode steps and prefill steps apart): 1 (steps), its
# live rows, the sum of their contexts, the sum of the tokens they selected
N_STATS = 4


def selection_path(tokens: int, view_width: int, index_topk: int) -> str:
    """``"all"``: no indexer (``index_topk`` 0), or every lane of the view fits
    the selection and the indexer is not run (the result is plain latent
    attention's); ``"gather"``: one token a slot, the chosen rows are
    gathered; ``"mask"``: a chunk, the view masked."""
    if not index_topk or view_width <= index_topk:
        return "all"
    return "gather" if tokens == 1 else "mask"


def key_norm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray) -> jnp.ndarray:
    """LayerNorm over the last axis, with scale and bias, in float32."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + K_NORM_EPS)
    return (x * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


def index_scores(q_idx: jnp.ndarray, w: jnp.ndarray, k_idx: jnp.ndarray) -> jnp.ndarray:
    """q_idx [B, T, Hi, d]; w [B, T, Hi] float32 (already scaled); k_idx
    [B, S, d] -> I [B, T, S] float32."""
    dots = jnp.einsum("bthd,bsd->bths", q_idx, k_idx.astype(q_idx.dtype),
                      preferred_element_type=jnp.float32)
    return jnp.einsum("bth,bths->bts", w.astype(jnp.float32), jax.nn.relu(dots))


def _ranked(scores: jnp.ndarray, visible: jnp.ndarray) -> jnp.ndarray:
    """What the selection ranks: the score where the lane is visible, ``-inf``
    elsewhere, and ONE zero: a score is ``-0.0`` where every head's product is
    negative under negative weights, ``top_k`` ranks it below ``+0.0``, and the
    two are the same score (a tie, to the earlier position)."""
    return jnp.where(visible, jnp.where(scores == 0, 0.0, scores), -jnp.inf)


def top_lanes(scores: jnp.ndarray, visible: jnp.ndarray, k: int):
    """The ``k`` visible lanes of largest score a row, a tie to the lower lane.
    scores [B, T, S] float32; visible [B, T, S] bool. Returns the lanes
    [B, T, k] int32 (best first) and which of them are real picks [B, T, k]
    (a row that sees fewer than ``k`` lanes picks all it sees; the rest of its
    ``k`` are lanes it cannot see, ranked ``-inf``, marked False)."""
    ranked, lanes = jax.lax.top_k(_ranked(scores, visible), k)
    return lanes.astype(jnp.int32), ranked > -jnp.inf


def top_mask(scores: jnp.ndarray, visible: jnp.ndarray, k: int) -> jnp.ndarray:
    """[B, T, S] bool: the set ``top_lanes`` picks, without its sort. A
    float32's bits, flipped so that they order as the numbers do, are searched
    from the top bit down for the largest value that at least ``k`` lanes of a
    row reach: the k-th largest score. Lanes above it are in; of the lanes AT
    it, the lowest, as many as are still wanted. A row that sees fewer than
    ``k`` lanes lands on ``-inf`` and takes all it sees."""
    bits = jax.lax.bitcast_convert_type(_ranked(scores, visible), jnp.uint32)
    keys = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))

    def narrow(i, floor):
        higher = floor | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        reach = jnp.sum(keys >= higher[..., None], axis=-1)
        return jnp.where(reach >= k, higher, floor)

    floor = jax.lax.fori_loop(0, 32, narrow, jnp.zeros(scores.shape[:-1], jnp.uint32))[..., None]
    above, at = keys > floor, keys == floor
    wanted = k - jnp.sum(above, axis=-1, keepdims=True)
    return visible & (above | (at & (jnp.cumsum(at, axis=-1) <= wanted)))


def gather_rows(pool: jnp.ndarray, li, lanes: jnp.ndarray, tables=None) -> jnp.ndarray:
    """Rows of layer ``li`` of a pool at each slot's chosen ``lanes`` [B, k]:
    ``[B, k, width]``. A paged pool ``[L, blocks, block_size, width]`` is read
    through ``tables`` [B, columns] (-1 where a column has no block: such a
    lane is no real pick, and reads block 0); a dense one ``[L, B, S, width]``
    by slot."""
    if tables is None:
        return pool[li, jnp.arange(lanes.shape[0])[:, None], lanes]
    block_size = pool.shape[2]
    block = jnp.take_along_axis(tables, lanes // block_size, axis=1)
    rows = jnp.maximum(block, 0) * block_size + lanes % block_size
    flat = pool.reshape(pool.shape[0], -1, pool.shape[-1])  # blocks of whole tiles: no copy
    return flat[li, rows]


def step_stats(positions: jnp.ndarray, valid, index_topk: int) -> jnp.ndarray:
    """int32 [N_STATS] of one step. A live row at rope position ``p`` sees ``p
    + 1`` tokens and selects ``min(index_topk, p + 1)`` of them in every
    layer; counted once a step, not a layer."""
    live = jnp.ones_like(positions, bool) if valid is None else valid
    context = jnp.where(live, positions + 1, 0)
    return jnp.stack([jnp.ones((), jnp.int32), jnp.sum(live), jnp.sum(context),
                      jnp.sum(jnp.minimum(context, index_topk))]).astype(jnp.int32)
