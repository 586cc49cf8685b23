"""Pallas flash attention (causal, GQA) with a flash backward pass.

TPU-first replacement for the reference's flash-attn CUDA toggle (reference
cmd/tuning/parser.py:66-69): O(T) memory — the [T, S] score matrix never
materializes in either direction. Forward stores only the per-row logsumexp;
backward recomputes probabilities tile-by-tile (standard FlashAttention-2
scheme: one kernel accumulates dQ over K tiles, one accumulates dK/dV over Q
tiles, with D = rowsum(dO ∘ O) precomputed).

Masking is handled in-kernel: causal by row index, plus packed-segment
isolation via per-row segment ids (all-equal ids degenerate to plain causal,
so unpacked right-padded batches are exact — pads sit at the tail where no
valid query can attend them), plus a sliding window by row index: inside a
packed segment the difference of row indices is the difference of rope
positions, and the segment test isolates the rest. A window no query can
feel (``window >= S``) is dropped at trace time. Cache decode falls back to
the biased XLA path (models/llama.py).

Products take their operands in the arrays' own dtype and accumulate in f32,
as the einsum path does (ops/attention.py:xla_attention): logits, running
max / sum / logsumexp and the accumulators stay f32, and the probabilities
and ``ds`` are cast to the operand dtype only where they enter a product.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128  # stats tiles padded to the TPU lane width
_SUBLANES = 8  # segment-id tiles padded to the TPU sublane width

# Mosaic requires the last two block dims to be (8k, 128k) or match the array,
# so [B, T] segment ids can't block as (1, block_q). Broadcast them instead:
# q ids ride the lane dim ([B, T, 128]), kv ids the sublane dim ([B, 8, S]) —
# inside the kernel a (bq, 1) column of the former against a (1, bk) row of
# the latter recovers the [bq, bk] pairwise mask.


def _seg3d(q_seg: jnp.ndarray, kv_seg: jnp.ndarray):
    B, T = q_seg.shape
    S = kv_seg.shape[1]
    q3 = jnp.broadcast_to(q_seg[:, :, None], (B, T, _LANES))
    kv3 = jnp.broadcast_to(kv_seg[:, None, :], (B, _SUBLANES, S))
    return q3, kv3


def _interpret() -> bool:
    from datatunerx_tpu.ops._pallas import interpret_default

    return interpret_default()


# Mosaic kernels cannot be auto-partitioned by GSPMD ("wrap the call in a
# shard_map" — raised by the REAL TPU lowering, invisible in interpret mode;
# caught by AOT certification of the dp4×fsdp4 train step, r5). The Trainer
# sets this context when a mesh is active so the flash call runs under
# shard_map: each device executes the kernel on its local (batch, head)
# shard. Sequence stays unsharded here — sp-parallel attention is ring's job.
_FLASH: dict = {"mesh": None, "batch_axes": ("dp", "fsdp"), "tp_axis": "tp"}


def set_flash_context(mesh, batch_axes=("dp", "fsdp"),
                      tp_axis: str = "tp") -> None:
    _FLASH.update(mesh=mesh, batch_axes=batch_axes, tp_axis=tp_axis)


def _flash_shard_mesh():
    """The active mesh if any sharded axis is >1 (else None: plain call)."""
    mesh = _FLASH["mesh"]
    if mesh is None:
        return None, None, None
    batch_axes = tuple(a for a in _FLASH["batch_axes"]
                       if a in mesh.shape)
    tp = _FLASH["tp_axis"] if _FLASH["tp_axis"] in mesh.shape else None
    sharded = 1
    for a in batch_axes:
        sharded *= mesh.shape[a]
    if tp:
        sharded *= mesh.shape[tp]
    if sharded == 1:
        return None, None, None
    return mesh, batch_axes, tp


def _tile_runs(i, j, block_q: int, block_k: int, causal: bool, window):
    """Whether q tile ``i`` and k tile ``j`` hold a pair the row-index masks
    let through: not wholly in the future (causal), not wholly behind every
    row's window. causal=False (ring-of-flash past chunks): every block
    contributes — the in/visible split is decided OUTSIDE the kernel per ring
    step (full vs none), so the kernel stays static."""
    if not causal:
        return j >= 0
    run = j * block_k <= i * block_q + block_q - 1
    if window is not None:
        run &= j * block_k + block_k - 1 > i * block_q - window
    return run


def _tile_mask(i, j, block_q: int, block_k: int, causal: bool, window,
               qseg_ref, kseg_ref):
    """[block_q, block_k] bool: causal and window by row index, AND
    packed-segment isolation (all-equal ids = plain causal)."""
    shape = (block_q, block_k)
    q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    mask = (k_pos <= q_pos) if causal else (k_pos >= 0)
    if causal and window is not None:
        mask &= k_pos > q_pos - window
    return mask & (qseg_ref[0][:, 0:1] == kseg_ref[0][0:1, :])


# ------------------------------------------------------------- forward

def _fwd_kernel(q_ref, k_ref, v_ref, qseg_ref, kseg_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref,
                *, block_q: int, block_k: int, scale: float,
                causal: bool = True, window=None):
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(_tile_runs(i, j, block_q, block_k, causal, window))
    def _compute():
        v = v_ref[0]
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        mask = _tile_mask(i, j, block_q, block_k, causal, window,
                          qseg_ref, kseg_ref)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)

        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(j == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, 0:1], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        lse = m_ref[:, 0:1] + jnp.log(l)
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _kv_index(H: int, G: int):
    """Map the folded (batch·q-head) grid index to the (batch·kv-head) row of
    the un-expanded K/V arrays — GQA without materializing jnp.repeat."""
    KV = H // G

    def index(b, i, j):
        return ((b // H) * KV + (b % H) // G, j, 0)

    return index


def _fwd(q, k, v, q_seg, kv_seg, *, block_q, block_k, interpret, H, G,
         causal: bool = True, window=None):
    BH, T, d = q.shape
    S = k.shape[1]
    scale = 1.0 / (d ** 0.5)
    kernel = functools.partial(
        _fwd_kernel, block_q=block_q, block_k=block_k, scale=scale,
        causal=causal, window=window,
    )
    kv_idx = _kv_index(H, G)
    q_seg3, kv_seg3 = _seg3d(q_seg, kv_seg)
    out, lse = pl.pallas_call(
        kernel,
        grid=(BH, T // block_q, S // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_idx),
            pl.BlockSpec((1, block_k, d), kv_idx),
            pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b // H, i, 0)),
            pl.BlockSpec((1, _SUBLANES, block_k), lambda b, i, j: (b // H, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, d), q.dtype),
            jax.ShapeDtypeStruct((BH, T, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        interpret=interpret,
        name="dtx_flash_fwd",
    )(q, k, v, q_seg3, kv_seg3)
    return out, lse[:, :, 0]


# ------------------------------------------------------------- backward

def _tile_p_ds(q, k, v, do, lse_ref, dsum_ref, mask, scale: float):
    """One tile's probabilities and logit gradients, both f32 [bq, bk],
    recomputed from the saved logsumexp and ``dsum = rowsum(dO * O)``."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    p = jnp.where(mask, jnp.exp(s - lse_ref[0][:, 0:1]), 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    return p, p * (dp - dsum_ref[0][:, 0:1]) * scale


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref,
                   qseg_ref, kseg_ref, dq_ref,
                   acc_ref, *, block_q: int, block_k: int, scale: float,
                   causal: bool = True, window=None):
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(_tile_runs(i, j, block_q, block_k, causal, window))
    def _compute():
        k = k_ref[0]
        mask = _tile_mask(i, j, block_q, block_k, causal, window,
                          qseg_ref, kseg_ref)
        _, ds = _tile_p_ds(q_ref[0], k, v_ref[0], do_ref[0], lse_ref,
                           dsum_ref, mask, scale)
        acc_ref[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref,
                    qseg_ref, kseg_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc,
                    *, block_q: int, block_k: int, scale: float,
                    causal: bool = True, window=None):
    j = pl.program_id(1)  # k tile
    i = pl.program_id(2)  # q tile (sequential)
    nq = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    # the same tile test, read from the k tile's side: q tiles wholly in the
    # past of the k tile, or wholly beyond its window, are skipped
    @pl.when(_tile_runs(i, j, block_q, block_k, causal, window))
    def _compute():
        q = q_ref[0]
        do = do_ref[0]  # [bq, d]
        mask = _tile_mask(i, j, block_q, block_k, causal, window,
                          qseg_ref, kseg_ref)
        p, ds = _tile_p_ds(q, k_ref[0], v_ref[0], do, lse_ref, dsum_ref,
                           mask, scale)  # [bq, bk]
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bk, d]
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bk, d]

    @pl.when(i == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd(block_q, block_k, interpret, G, res, do, causal: bool = True,
         window=None):
    """K/V arrive un-expanded [B*KV, S, d] and stay so: as in the forward,
    the index map routes each q head to its KV group's rows. dk/dv come out
    per q head and are group-summed at the end."""
    q, k, v, q_seg, kv_seg, out, lse = res
    BH, T, d = q.shape
    BKV, S = k.shape[:2]
    scale = 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = _interpret()

    H_ = BH // q_seg.shape[0]  # q heads per batch row (segment index maps)
    kv_idx = _kv_index(H_, G)
    dsum = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    lse_b = jnp.broadcast_to(lse[:, :, None], (BH, T, _LANES))
    dsum_b = jnp.broadcast_to(dsum[:, :, None], (BH, T, _LANES))
    q_seg3, kv_seg3 = _seg3d(q_seg, kv_seg)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_q=block_q, block_k=block_k,
                          scale=scale, causal=causal, window=window),
        grid=(BH, T // block_q, S // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_idx),
            pl.BlockSpec((1, block_k, d), kv_idx),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b // H_, i, 0)),
            pl.BlockSpec((1, _SUBLANES, block_k), lambda b, i, j: (b // H_, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="dtx_flash_bwd_dq",
    )(q, k, v, do, lse_b, dsum_b, q_seg3, kv_seg3)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=block_q, block_k=block_k,
                          scale=scale, causal=causal, window=window),
        grid=(BH, S // block_k, T // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: kv_idx(b, i, j)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: kv_idx(b, i, j)),
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, j, i: (b // H_, i, 0)),
            pl.BlockSpec((1, _SUBLANES, block_k), lambda b, j, i: (b // H_, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, d), k.dtype),
            jax.ShapeDtypeStruct((BH, S, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        name="dtx_flash_bwd_dkv",
    )(q, k, v, do, lse_b, dsum_b, q_seg3, kv_seg3)
    if G > 1:
        dk = dk.reshape(BKV, G, S, d).sum(axis=1)
        dv = dv.reshape(BKV, G, S, d).sum(axis=1)
    return dq, dk, dv


# --------------------------------------------------------------- public

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def flash_attention_causal(q, k, v, q_seg, kv_seg, block_q: int = 512,
                           block_k: int = 512, interpret=None, H: int = 1,
                           G: int = 1, window=None):
    """q: [B*H, T, d]; k, v: [B*KV, S, d] (un-expanded GQA);
    q_seg/kv_seg: [B, T]/[B, S] int32 segment ids (all-equal = plain causal);
    window: a static sliding window that binds (< S), or None."""
    out, _ = _fwd(q, k, v, q_seg, kv_seg, block_q=block_q, block_k=block_k,
                  interpret=_interpret() if interpret is None else interpret,
                  H=H, G=G, window=window)
    return out


def _vjp_fwd(q, k, v, q_seg, kv_seg, block_q, block_k, interpret, H, G,
             window):
    out, lse = _fwd(q, k, v, q_seg, kv_seg, block_q=block_q, block_k=block_k,
                    interpret=_interpret() if interpret is None else interpret,
                    H=H, G=G, window=window)
    return out, (q, k, v, q_seg, kv_seg, out, lse)


def _vjp_bwd(block_q, block_k, interpret, H, G, window, res, do):
    dq, dk, dv = _bwd(block_q, block_k, interpret, G, res, do, window=window)
    return dq, dk, dv, None, None


flash_attention_causal.defvjp(_vjp_fwd, _vjp_bwd)


def _pick_block(n: int, cap: int = 512) -> int:
    """Largest power-of-two divisor of n, capped (TPU-friendly tile sizes).

    The cap is measured (v5e, B 8, H 32, T 1024, d 128, bf16; PERF.md §6,
    PR 33): 512 x 512 beats every mix of 128, 256 and 512 in all three
    kernels (forward 1.91 ms against 3.41 at 256 x 256 and 8.04 at 128 x
    128), though the causal skip then runs 3 tiles of 4 and not 10 of 16: a
    grid step has a fixed cost, and skipped tiles are still fetched."""
    b = 1
    while b < cap and n % (b * 2) == 0:
        b *= 2
    return min(b, cap)


def flash_attention(
    q: jnp.ndarray,  # [B, T, H, d]
    k: jnp.ndarray,  # [B, S, KV, d]
    v: jnp.ndarray,
    bias=None,  # accepted for dispatch parity; causal handled in-kernel
    *,
    segment_ids: jnp.ndarray | None = None,  # [B, T] packed-segment ids
    sliding_window: int | None = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret=None,
) -> jnp.ndarray:
    """GQA wrapper: fold (B, H) into the grid dim; KV stays un-expanded and the
    kernel's index_map routes each q head to its KV group. With segment_ids,
    attention is additionally confined within packed segments (self-attention:
    T == S, ids shared between q and kv). With ``sliding_window``, a query
    sees the keys less than that many rows behind it; one that reaches past
    the first key of every row (``>= S``) emits the window-less kernels.

    Under an active mesh (set_flash_context) the call is wrapped in
    shard_map over the batch (+tp head) axes — Mosaic custom calls cannot
    be auto-partitioned by GSPMD, so without this the multi-chip train step
    fails to lower on real TPU toolchains."""
    mesh, batch_axes, tp = _flash_shard_mesh()
    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        if tp is not None:
            H_, KV_ = q.shape[2], k.shape[2]
            if H_ % mesh.shape[tp] or KV_ % mesh.shape[tp]:
                # GQA head counts that don't divide tp: keep heads whole in
                # the wrap (GSPMD gathers them); batch still shards
                tp = None
        qkv_spec = P(batch_axes, None, tp, None)
        seg_spec = P(batch_axes, None)

        if segment_ids is None:
            def local3(q, k, v):
                return _flash_local(q, k, v, None, sliding_window, block_q,
                                    block_k, interpret)

            return jax.shard_map(local3, mesh=mesh, in_specs=(qkv_spec,) * 3,
                                 out_specs=qkv_spec, check_vma=False)(q, k, v)

        def local(q, k, v, seg):
            return _flash_local(q, k, v, seg, sliding_window, block_q,
                                block_k, interpret)

        return jax.shard_map(local, mesh=mesh,
                             in_specs=(qkv_spec, qkv_spec, qkv_spec,
                                       seg_spec),
                             out_specs=qkv_spec, check_vma=False)(
            q, k, v, segment_ids)
    return _flash_local(q, k, v, segment_ids, sliding_window, block_q,
                        block_k, interpret)


def _flash_local(q, k, v, segment_ids, window, block_q, block_k, interpret):
    B, T, H, d = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    if window is not None and window >= S:
        window = None  # removes no key from any query
    block_q = min(block_q, _pick_block(T))
    block_k = min(block_k, _pick_block(S))
    if segment_ids is None:
        q_seg = jnp.ones((B, T), jnp.int32)
        kv_seg = jnp.ones((B, S), jnp.int32)
    else:
        assert T == S, (
            f"segment_ids requires self-attention (T == S), got T={T} S={S}")
        q_seg = segment_ids.astype(jnp.int32)
        kv_seg = q_seg  # self-attention
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, T, d)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, S, d)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, S, d)
    out = flash_attention_causal(qf, kf, vf, q_seg, kv_seg, block_q, block_k,
                                 interpret, H, G, window)
    return out.reshape(B, H, T, d).transpose(0, 2, 1, 3)
