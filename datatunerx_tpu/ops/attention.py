"""Attention ops: XLA reference path + dispatch to Pallas flash / ring attention.

The reference delegates attention entirely to HF transformers CUDA kernels
(optionally flash-attn, reference cmd/tuning/parser.py:66-69). TPU-native design:
a plain einsum+softmax path that XLA fuses well (default), a Pallas flash kernel
for long sequences, and ring attention over a mesh axis for sequence parallelism
(SURVEY.md §5.7 stretch goal).

Shapes: q [B, T, H, d]; k, v [B, S, KV, d] with H = KV * G (GQA).
Bias is additive, broadcastable to [B, 1|H, T, S]; softmax runs in f32.

This module also owns the serving KV-cache interface the model writes and
reads through (``cache_positions_update`` / ``KVStep`` / ``kv_cache_update``):
a cache dict with ``block_tables`` takes the paged block-pool path
(ops/paged_attention.py); otherwise the dense contiguous layouts
(scalar-cursor prefill rows, per-slot-cursor continuous batching). The int8
``kv_quant`` representation is shared by both. The layer scan carries the
stacked leaves and a layer writes and reads them at its own index.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from datatunerx_tpu.ops.paged_attention import (
    POS_SENTINEL,
    _gather_tables,
    _write_targets,
    paged_linear_targets,
    paged_record_positions,
    paged_view_width,
    window_tables,
)


def attention_allow(
    q_positions: jnp.ndarray,  # [B, T] absolute positions of queries
    kv_positions: jnp.ndarray,  # [B, S] absolute positions of keys
    kv_valid: jnp.ndarray | None = None,  # [B, S] bool — False for padding
    *,
    sliding_window: int | None = None,
    q_segment_ids: jnp.ndarray | None = None,  # [B, T] for packed sequences
    kv_segment_ids: jnp.ndarray | None = None,  # [B, S]
    window_mask: jnp.ndarray | None = None,  # [B, T, WN] bool — see below
    window_start: jnp.ndarray | None = None,  # [B] linear start of the window
) -> jnp.ndarray:
    """The boolean attendability tensor [B, T, S] behind the causal bias.

    ``window_mask``/``window_start`` carve a per-step WINDOW out of the KV
    lanes — the ``WN`` linear cache positions starting at ``window_start``
    (a multi-token verify/draft step's own writes). Inside the window a
    lane must pass the mask column AND the causal check (tree siblings
    share a rope position, so causality alone cannot separate branches —
    and the causal check still excludes unwritten sentinel lanes); outside
    it, plain causal masking applies unchanged. A lower-triangular mask
    reproduces the chain behavior exactly, so chain verify never sets one.

    Factored out of ``make_causal_bias`` so the Pallas multi-token kernel
    consumes the SAME boolean tensor the XLA oracle biases with — mask
    parity between the two paths holds by construction."""
    ok = kv_positions[:, None, :] <= q_positions[:, :, None]  # causal
    if sliding_window is not None:
        ok &= kv_positions[:, None, :] > q_positions[:, :, None] - sliding_window
    if kv_valid is not None:
        ok &= kv_valid[:, None, :]
    if q_segment_ids is not None and kv_segment_ids is not None:
        ok &= q_segment_ids[:, :, None] == kv_segment_ids[:, None, :]
    if window_mask is not None:
        B, T, WN = window_mask.shape
        S = kv_positions.shape[1]
        lane = jnp.arange(S, dtype=jnp.int32)[None, :]
        w = lane - window_start.astype(jnp.int32)[:, None]  # [B, S]
        inside = (w >= 0) & (w < WN)
        wc = jnp.clip(w, 0, WN - 1)
        allowed = jnp.take_along_axis(
            window_mask.astype(bool),
            jnp.broadcast_to(wc[:, None, :], (B, T, S)), axis=2)
        ok &= ~inside[:, None, :] | allowed
    return ok


def make_causal_bias(
    q_positions: jnp.ndarray,  # [B, T] absolute positions of queries
    kv_positions: jnp.ndarray,  # [B, S] absolute positions of keys
    kv_valid: jnp.ndarray | None = None,  # [B, S] bool — False for padding
    *,
    sliding_window: int | None = None,
    q_segment_ids: jnp.ndarray | None = None,  # [B, T] for packed sequences
    kv_segment_ids: jnp.ndarray | None = None,  # [B, S]
    window_mask: jnp.ndarray | None = None,  # [B, T, WN] branch/window mask
    window_start: jnp.ndarray | None = None,  # [B]
    dtype=jnp.float32,
) -> jnp.ndarray:
    """Additive bias [B, 1, T, S]: 0 where attendable, -inf-ish otherwise."""
    ok = attention_allow(
        q_positions, kv_positions, kv_valid,
        sliding_window=sliding_window, q_segment_ids=q_segment_ids,
        kv_segment_ids=kv_segment_ids, window_mask=window_mask,
        window_start=window_start)
    neg = jnp.asarray(jnp.finfo(dtype).min, dtype)
    return jnp.where(ok, jnp.zeros((), dtype), neg)[:, None, :, :]


def xla_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    bias: jnp.ndarray,
    sink: jnp.ndarray | None = None,  # [H] one learned logit per head
    scale: float | None = None,  # ``d ** -0.5`` of q's width unless given
) -> jnp.ndarray:
    """Reference attention: f32 softmax, GQA via reshape. q and k share a
    head width, v may have another: returns [B, T, H, v's width].

    ``sink`` is one more column of every row's softmax, ``p = softmax([s_i,:,
    sink_h])``, dropped before ``p V``: the rows of ``p`` then sum to less
    than one."""
    B, T, H, d = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    q = q.reshape(B, T, KV, G, d)
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    logits = jnp.einsum("btkgd,bskd->bkgts", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    bias4 = bias.astype(jnp.float32)  # [B, 1|H, T, S]
    if bias4.shape[1] == 1:
        logits = logits + bias4[:, :, None, :, :]
    else:
        logits = logits + bias4.reshape(B, KV, G, T, S)
    if sink is None:
        probs = jax.nn.softmax(logits, axis=-1)
    else:
        col = sink.astype(jnp.float32).reshape(1, KV, G, 1, 1)
        top = jnp.maximum(jnp.max(logits, axis=-1, keepdims=True), col)
        e = jnp.exp(logits - top)
        probs = e / (jnp.sum(e, axis=-1, keepdims=True) + jnp.exp(col - top))
    out = jnp.einsum("bkgts,bskd->btkgd", probs.astype(v.dtype), v)
    return out.reshape(B, T, H, v.shape[-1])


# ------------------------------------------------------- KV cache interface

def kv_quantize(x: jnp.ndarray):
    """[..., head_dim] → (int8 values, per-vector scale)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    safe = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / safe[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def kv_dequantize(q: jnp.ndarray, scale: jnp.ndarray, dtype):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def kv_cache_width(cache: dict) -> int:
    """Linear key width attention sees for one slot — the rope ``seq_len``
    (dynamic-NTK inflation keys off it, ops/rope.py)."""
    if "block_tables" in cache:
        return paged_view_width(cache)
    return cache["k"].shape[2]


def cache_positions_update(cache: dict, positions: jnp.ndarray,
                           attention_mask, gather: bool = True):
    """Record the new tokens' rope positions at each slot's write cursor.

    Returns ``(pos_state, kv_positions)``: the updated position state (dense
    [B, S] table, or the paged [NB, bs] pool) and the per-slot linear
    position view ``[B, W]`` the causal bias masks against. Pads
    (attention_mask 0) get POS_SENTINEL so they are masked everywhere.
    ``gather=False`` (paged kernel decode) skips the gathered view — the
    kernel masks against the pos pool in place — returning ``(pool, None)``."""
    pos_update = positions
    if attention_mask is not None:
        pos_update = jnp.where(attention_mask.astype(bool), positions,
                               POS_SENTINEL)
    if "block_tables" in cache:
        return paged_record_positions(cache, pos_update, gather=gather)
    B, T = positions.shape
    if cache["len"].ndim == 0:
        cache_pos = jax.lax.dynamic_update_slice(
            cache["pos"], pos_update, (0, cache["len"]))
    else:
        # per-slot cursors: scatter each row at its own depth (OOB writes
        # for exhausted slots are dropped by the default scatter mode)
        rows = jnp.arange(B, dtype=jnp.int32)[:, None]
        idx = cache["len"][:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
        cache_pos = cache["pos"].at[rows, idx].set(pos_update)
    return cache_pos, cache_pos


class KVStep:
    """Where one step's ``T`` tokens land in a stacked KV leaf ``[layers,
    blocks | rows, offset | lane, ...]`` and what attention reads back:
    built once a forward, shared by its layers. The layer scan CARRIES the
    leaves and every write and read names its layer, so no layer of a leaf is
    sliced out or stacked back and XLA updates the leaf in place. One class
    for the three cache kinds (paged block pool, dense rows with a scalar
    cursor, dense rows with a cursor per slot) and for both forwards
    (models/llama.py, models/hybrid.py).

    ``window`` (a sliding-window layer over a paged cache) narrows the view
    to the table columns a query of this step can see
    (ops/paged_attention.py:window_tables); ``view_tables`` are those
    columns, the whole table otherwise."""

    def __init__(self, cache: dict, T: int, window: int | None = None):
        self.lens = cache["len"]
        self.paged = "block_tables" in cache
        if self.paged:
            num_blocks, block_size = cache["pos"].shape
            tables = cache["block_tables"]
            self.phys, self.off = _write_targets(
                tables, self.lens, T, block_size, num_blocks)
            if window is not None:
                tables = window_tables(tables, self.lens, T, window,
                                       block_size)
            self.view_tables = tables
            self._gather = _gather_tables(tables)
        elif self.lens.ndim:
            # per-slot cursors: scatter each row at its own depth (OOB
            # writes for exhausted slots are dropped by the default mode)
            B = cache["pos"].shape[0]
            self.rows = jnp.arange(B, dtype=jnp.int32)[:, None]
            self.idx = (self.lens[:, None]
                        + jnp.arange(T, dtype=jnp.int32)[None, :])

    def write(self, leaf, li, new):
        """``new [B, T, ...]`` (the leaf's dtype and trailing dims) into
        layer ``li`` of ``leaf``."""
        if self.paged:
            return leaf.at[li, self.phys, self.off].set(new)
        if self.lens.ndim == 0:
            return jax.lax.dynamic_update_slice(
                leaf, new[None],
                (li, 0, self.lens) + (0,) * (leaf.ndim - 3))
        return leaf.at[li, self.rows, self.idx].set(new)

    def read(self, leaf, li, columns: int | None = None):
        """What attention reads of layer ``li`` AFTER the write: ``[B, S,
        ...]``, the gathered per-slot linear view of a paged leaf
        (element-identical to a dense row for every written lane,
        sentinel-masked elsewhere) or the layer's dense rows. ``columns``
        (paged) reads the view's first so many table columns and no more."""
        if not self.paged:
            return leaf[li]
        gather = self._gather if columns is None else self._gather[:, :columns]
        view = leaf[li, gather]  # [B, n, bs, ...]
        return view.reshape((view.shape[0], -1) + view.shape[3:])


def kv_cache_write(step: KVStep, leaves: dict, li, k, v) -> dict:
    """One layer's cache write. ``leaves`` are the carried stacked cache
    leaves (``k``/``v`` ``[L, rows, lanes, KV * d]`` and, for the int8 cache,
    ``k_scale``/``v_scale`` ``[L, rows, lanes, KV]``); ``k``/``v`` the new
    tokens' projections ``[B, T, KV, d]``, quantized on write for the int8
    cache. Alone it is the Pallas kernel path's write: the kernel then reads
    the blocks in place."""
    B, T = k.shape[:2]
    out = dict(leaves)
    for name, new in (("k", k), ("v", v)):
        if "k_scale" in leaves:
            new, scale = kv_quantize(new)
            out[name + "_scale"] = step.write(
                leaves[name + "_scale"], li, scale)
        out[name] = step.write(
            leaves[name], li,
            new.astype(leaves[name].dtype).reshape(B, T, -1))
    return out


def kv_cache_update(step: KVStep, leaves: dict, li, k, v):
    """One layer's cache write + full-width read: the updated leaves plus
    ``k_att``/``v_att``, the ``[B, W, KV, d]`` views attention reads,
    dequantized when quantized."""
    leaves = kv_cache_write(step, leaves, li, k, v)
    KV, d = k.shape[2:]

    def view(name, like):
        x = step.read(leaves[name], li)
        x = x.reshape(x.shape[:2] + (KV, d))
        if "k_scale" in leaves:
            return kv_dequantize(
                x, step.read(leaves[name + "_scale"], li), like.dtype)
        return x.astype(like.dtype)

    return leaves, view("k", k), view("v", v)


def compact_window(cache: dict, participate: jnp.ndarray, len0: jnp.ndarray,
                   src_cols: jnp.ndarray, keep: jnp.ndarray,
                   pos0: jnp.ndarray, width: int) -> dict:
    """Collapse a tree-verify window back into chain-invariant lanes.

    A tree-verify forward writes ``width`` KV lanes per row starting at the
    pre-step cursor ``len0``: column 0 is the pending token, the rest the
    flattened tree nodes — SIBLINGS SHARING ROPE POSITIONS. After
    acceptance, only the chosen root-to-leaf path may survive: a stale
    sibling lane (rope pos ``p+1`` parked at linear lane ``len0+2``) would
    pass the plain causal check of any later read, which is exactly the
    corruption chain mode can never produce (its lane order == rope order).

    This moves the accepted path's K/V into the contiguous cursor lanes
    (``len0+1 … len0+keep``; lane ``len0`` already holds the pending token)
    and rewrites every window lane's position — ``pos0+i`` where kept,
    POS_SENTINEL otherwise — restoring the chain invariant the settle /
    export / migration paths assume. Works on both cache layouts.

    ``src_cols [B, D]`` is the window column of the path's depth-(i+1)
    node, ``keep [B]`` the accepted path length (≤ D), ``pos0 [B]`` the
    pending token's rope position. Rows with ``participate`` False are
    untouched (targets go out of bounds, the default scatter drop).
    ``len`` is NOT advanced here — the caller owns cursor math."""
    B, D = src_cols.shape
    depth_i = jnp.arange(1, D + 1, dtype=jnp.int32)[None, :]  # [1, D]
    move = participate[:, None] & (depth_i <= keep[:, None])
    src_lin = len0[:, None] + src_cols
    dst_lin = len0[:, None] + depth_i
    lane = jnp.arange(width, dtype=jnp.int32)[None, :]
    lane_lin = len0[:, None] + lane
    lane_valid = jnp.broadcast_to(participate[:, None], lane_lin.shape)
    vals = jnp.where(lane <= keep[:, None], pos0[:, None] + lane,
                     POS_SENTINEL)
    out = dict(cache)
    kv_keys = [k for k in ("k", "v", "k_scale", "v_scale") if k in cache]
    if "block_tables" in cache:
        tables = cache["block_tables"]
        num_blocks, block_size = cache["pos"].shape
        src_phys, src_off = paged_linear_targets(
            tables, src_lin, block_size, num_blocks, move)
        src_phys = jnp.minimum(src_phys, num_blocks - 1)  # gather in bounds
        dst_phys, dst_off = paged_linear_targets(
            tables, dst_lin, block_size, num_blocks, move)
        for key in kv_keys:
            leaf = cache[key]
            out[key] = leaf.at[:, dst_phys, dst_off].set(
                leaf[:, src_phys, src_off])
        lane_phys, lane_off = paged_linear_targets(
            tables, lane_lin, block_size, num_blocks, lane_valid)
        out["pos"] = cache["pos"].at[lane_phys, lane_off].set(vals)
        return out
    W = cache["pos"].shape[1]
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    src_idx = jnp.clip(src_lin, 0, W - 1)
    dst_idx = jnp.where(move, dst_lin, W)  # OOB = dropped
    for key in kv_keys:
        leaf = cache[key]
        out[key] = leaf.at[:, rows, dst_idx].set(leaf[:, rows, src_idx])
    lane_idx = jnp.where(lane_valid, lane_lin, W)
    out["pos"] = cache["pos"].at[rows, lane_idx].set(vals)
    return out


def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    bias: jnp.ndarray,
    *,
    impl: str = "xla",
    segment_ids: jnp.ndarray | None = None,
    sliding_window: int | None = None,
) -> jnp.ndarray:
    """``segment_ids`` and ``sliding_window`` reach the flash kernels, which
    mask in-kernel; the other paths read both from ``bias``."""
    if impl == "xla":
        return xla_attention(q, k, v, bias)
    if impl == "flash":
        from datatunerx_tpu.ops.flash_attention import flash_attention

        return flash_attention(q, k, v, bias, segment_ids=segment_ids,
                               sliding_window=sliding_window)
    if impl == "ring":
        from datatunerx_tpu.ops.ring_attention import (
            get_ring_context,
            ring_attention_sharded,
        )

        mesh, axis, batch_axes = get_ring_context()
        if mesh is None or mesh.shape.get(axis, 1) == 1:
            # no sequence-parallel axis active — plain attention is exact
            return xla_attention(q, k, v, bias)
        return ring_attention_sharded(q, k, v, mesh, axis_name=axis,
                                      batch_axes=batch_axes)
    raise ValueError(f"unknown attention impl {impl!r}")
