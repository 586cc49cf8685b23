"""Sparse experts of which this chip holds a share.

An expert layer is told how many experts the model has, how many of them are
held here and which (``first_held`` on). It routes every token over ALL of
them, as every chip of the deployment does, and computes the part of the
result that its own experts give: the (token, expert) pairs whose expert is
held are sorted by expert and multiplied group by group; pairs of absent
experts add nothing. With every expert held it is the whole layer. Nothing
here stands in for the absent chips or for the exchange between them.

The grouped matmul is chosen from static shapes (``grouped_matmul``). Where a
group expects a few rows (serving: a held expert has a row or two a decode
step, a handful in a prefill chunk) it is ``ops/pallas_moe.py``'s kernel
``dtx_moe_gmm`` at a row tile of 16 to 256 rows, which streams each hit
expert's weights once. Otherwise it is ``jax.lax.ragged_dot``, the only path
with a VJP: XLA lowers it to a Mosaic kernel on the TPU (a plain loop on the
CPU) whose row tile is 512, so every group with a row in a tile multiplies
its weights by all 512 rows and masks the others away: right where groups are
long, MXU-bound on masked rows where they are not.

Shapes are static: the sorted buffer has the worst case ``N * k`` rows, there
is no capacity factor and no token is ever dropped.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from datatunerx_tpu.ops import pallas_moe

# what one expert layer counts of one step: (token, expert) pairs routed to
# held experts, held experts that got a row, most rows on one expert, 1
# (layer-steps), rows with at least one held pair, real rows
N_STATS = 6


def keep_groups(c: jnp.ndarray, n_group: int, topk_group: int) -> jnp.ndarray:
    """Group-limited selection: the experts lie in ``n_group`` groups of
    consecutive experts; a group's score is the sum of its two largest
    ``c``; every expert outside the ``topk_group`` best groups gets ``-inf``.
    c [N, E] -> [N, E]."""
    N, E = c.shape
    grouped = c.reshape(N, n_group, E // n_group)
    score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)  # [N, n_group]
    _, best = jax.lax.top_k(score, topk_group)
    kept = jnp.any(best[:, :, None] == jnp.arange(n_group)[None, None, :], axis=1)
    return jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(N, E)


def route(x: jnp.ndarray, router: jnp.ndarray, correction: jnp.ndarray, *,
          top_k: int, normalize: bool, scaling: float, n_group: int = 1,
          topk_group: int = 1):
    """Sigmoid router with a selection-only correction bias (``noaux_tc``).
    x [N, D]; router [D, E]; correction [E]. Returns the chosen experts [N, k]
    int32 and their weights [N, k] float32: the ``k`` largest ``sigmoid(g) +
    correction`` are chosen (among the kept groups' experts where the model
    has groups: ``keep_groups``), and weighted by ``sigmoid(g)`` WITHOUT the
    correction, divided by their sum, times ``scaling``."""
    g = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(g)
    c = s + correction.astype(jnp.float32)
    if n_group > 1:
        c = keep_groups(c, n_group, topk_group)
    _, idx = jax.lax.top_k(c, top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if normalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * scaling


def sort_pairs(idx: jnp.ndarray, valid: jnp.ndarray | None, *,
               first_held: int, held: int):
    """(token, expert) pairs by held expert, absent experts' pairs last.
    Returns ``here`` [N, k] (the pair's expert is held and its row is real),
    ``order`` [N*k] (pair index by sorted place), ``sizes`` [held] int32."""
    local = idx - first_held
    here = (local >= 0) & (local < held)
    if valid is not None:
        here &= valid[:, None]
    key = jnp.where(here, local, held).reshape(-1)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    return here, order, sizes


def grouped_matmul(rows: int, *, top_k: int, experts_total: int, d: int,
                   f: int) -> tuple:
    """(name, row tile) of the grouped matmul an expert layer runs on ``rows``
    rows: ``pallas_moe.row_tile`` of the ``rows * top_k`` sorted pairs decides,
    from shapes alone. ``ragged_dot``'s row tile is XLA's to choose: None."""
    tm = pallas_moe.row_tile(rows * top_k, experts_total, d, f)
    return (pallas_moe.KERNEL, tm) if tm else ("ragged_dot", None)


def grouped_swiglu(xs: jnp.ndarray, sizes: jnp.ndarray, gate: jnp.ndarray,
                   up: jnp.ndarray, down: jnp.ndarray, layer=None,
                   row_tile: int | None = None) -> jnp.ndarray:
    """SwiGLU of each group's rows with its own expert. xs [M, D] sorted by
    group; gate/up [E, D, F]; down [E, F, D]. Rows past ``sum(sizes)`` belong
    to no group; what comes out for them is not used.

    With ``layer`` the weights are a whole run's, stacked ``[n, E, ...]``, and
    ``layer`` says whose turn it is. Either grouped matmul is a kernel of its
    own on the TPU and takes its operands whole, so a layer's experts sliced
    out of the stack first would be copied (1.2 GB a layer at 16 experts of
    4096 x 2048, every step). The Pallas kernel (``row_tile`` rows a tile)
    indexes the stack by ``layer``; ``ragged_dot`` reads it as ``n * E``
    groups of which only this layer's have rows."""
    if row_tile is not None:
        return pallas_moe.grouped_swiglu(xs, sizes, gate, up, down, layer, row_tile)
    if layer is not None:
        n, held = gate.shape[0], gate.shape[1]
        gate, up, down = (w.reshape((n * held,) + w.shape[2:]) for w in (gate, up, down))
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((n * held,), sizes.dtype), sizes, (layer * held,))
    dot = lambda a, b: jax.lax.ragged_dot(  # noqa: E731
        a, b.astype(a.dtype), sizes, preferred_element_type=jnp.float32)
    h = jax.nn.silu(dot(xs, gate)) * dot(xs, up)
    return dot(h.astype(xs.dtype), down)


def expert_layer(x: jnp.ndarray, valid: jnp.ndarray | None, p: dict, *,
                 experts_total: int, experts_held: int, first_held: int,
                 top_k: int, normalize: bool, scaling: float, layer=None,
                 n_group: int = 1, topk_group: int = 1):
    """This chip's part of one expert layer. x [N, D]; ``valid`` [N] marks
    real rows (pads and idle slots are routed nowhere). ``p`` holds
    ``router.kernel`` [D, E_total], ``e_score_correction_bias`` [E_total] and
    ``experts.{gate,up,down}_proj`` stacked over the held experts (over the
    layers of a run too, where ``layer`` picks one: ``grouped_swiglu``).
    Returns the partial sum [N, D] and the layer's counts (int32 [N_STATS])."""
    N, D = x.shape
    assert p["router"]["kernel"].shape[-1] == experts_total
    with jax.named_scope("dtx.moe_route"):
        idx, w = route(x, p["router"]["kernel"], p["e_score_correction_bias"],
                       top_k=top_k, normalize=normalize, scaling=scaling,
                       n_group=n_group, topk_group=topk_group)
        here, order, sizes = sort_pairs(idx, valid, first_held=first_held,
                                        held=experts_held)
        xs = x[order // top_k]
    with jax.named_scope("dtx.moe_experts"):
        ex = p["experts"]
        _, tm = grouped_matmul(N, top_k=top_k, experts_total=experts_total, d=D,
                               f=ex["gate_proj"].shape[-1])
        out = grouped_swiglu(xs, sizes, ex["gate_proj"], ex["up_proj"],
                             ex["down_proj"], layer, tm)
    with jax.named_scope("dtx.moe_combine"):
        # back to pair order, then each token's held pairs weighted and added
        place = jnp.zeros((N * top_k,), jnp.int32).at[order].set(
            jnp.arange(N * top_k, dtype=jnp.int32))
        pairs = out[place].reshape(N, top_k, D)
        pairs = jnp.where(here[:, :, None], pairs, 0.0)
        y = jnp.einsum("nk,nkd->nd", jnp.where(here, w, 0.0), pairs)
        rows = jnp.sum(valid) if valid is not None else jnp.asarray(N)
        stats = jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0),
                           jnp.max(sizes), jnp.ones((), jnp.int32),
                           jnp.sum(jnp.any(here, axis=1)), rows])
    return y.astype(x.dtype), stats.astype(jnp.int32)
