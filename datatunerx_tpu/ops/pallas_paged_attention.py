"""Pallas in-place paged-attention decode kernel (vLLM PagedAttention done
natively — PAPERS.md; the ROADMAP "Decode fast path" arc).

The XLA gather path (ops/attention.py ``kv_cache_update``) is
token-exact but materializes a dense-equivalent ``[B, W, KV, d]`` linear
view of every slot's blocks per layer per decode step: the block pool saves
HBM *capacity* while decode still pays dense HBM *bandwidth* — a full-width
gather write plus a full-width attention read, padding included. This
kernel walks the per-slot block table with scalar prefetch and reads the
K/V blocks IN PLACE: per decode token it streams only the slot's LIVE
blocks through VMEM (K twice, V once — see below), so HBM traffic scales
with ``len(session)`` instead of ``blocks_per_slot × block_size``, and the
gathered view never exists.

Correctness contract — the gather path stays alive as the parity ORACLE,
and the PR 5 bit-parity suite asserts kernel-vs-gather token-exactness.
That drives the kernel's two-phase shape:

- **Phase 0 (stats)**: flash-style online-softmax accumulator over the
  table's blocks — running row max ``m`` and rescaled normalizer ``l`` in
  f32 VMEM scratch, exactly flash_attention.py's scheme.
- **Phase 1 (weighted sum)**: with the row's ``m``/``l`` known, each
  block's probabilities are the oracle's own ``exp(s - m) / l`` quantized
  to the compute dtype BEFORE the PV product — replicating
  ``xla_attention``'s ``probs.astype(v.dtype)`` rounding, which a
  single-pass accumulator cannot (it would normalize after the cast).
  Differences vs the oracle reduce to f32 summation order (~1e-7
  relative), which greedy/sampled token streams don't see.

The kernels take the STACKED pool the layer scan carries (``[L, NB, bs,
KV·d]``, int8 scales ``[L, NB, bs, KV]``) and the layer as a scalar-prefetch
operand: layer ``l``'s block ``n`` is row ``l·NB + n`` of the pool viewed as
``[L·NB, bs, KV·d]`` (merging leading dims moves nothing), so no layer is
sliced out of a leaf for them; the pos pool is shared by the layers and is
addressed by the plain block id.

Masking needs no bias tensor: a table entry < 0 skips its block outright
(``pl.when``), and within a block the pos pool — POS_SENTINEL on every
unwritten/pad lane — is compared against the query's rope position, the
same ``kv_pos <= q_pos`` check the oracle's causal bias encodes. GQA maps
each query-head group onto its KV head with a static in-kernel loop (no
``jnp.repeat``); int8 ``kv_quant`` pools dequantize per block inside the
kernel by the paged scale pools (pallas_quant.py's fuse-the-dequant idiom),
rounding through the compute dtype exactly as ``kv_dequantize`` does.

Testable under ``JAX_PLATFORMS=cpu`` via the shared interpret-mode gate
(ops/_pallas.py); ``DTX_PALLAS_INTERPRET=0`` forces real Mosaic lowering
for AOT certification.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # finite (flash_attention.py): -inf - -inf would NaN
_LANES = 128  # stats scratch padded to the TPU lane width


def _interpret() -> bool:
    from datatunerx_tpu.ops._pallas import interpret_default

    return interpret_default()


def _decode_kernel(tables_ref, qpos_ref, layer_ref, q_ref, k_ref, v_ref,
                   ks_ref, vs_ref, pos_ref, o_ref, acc_ref, m_ref, l_ref,
                   *, nbps: int, kv_heads: int, group: int, scale: float,
                   quant: bool):
    """One (slot, table-entry, phase) grid step.

    Grid is ``(B, 2 * nbps)``: the trailing dim walks the slot's table twice
    — ``j < nbps`` is the stats phase, ``j >= nbps`` the weighted-sum phase.
    Block j's K/V/pos land in VMEM via the scalar-prefetched table (invalid
    entries clamp to physical block 0 and are skipped by ``pl.when``);
    ``layer_ref`` is read by the index maps alone."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    jj = j - (j // nbps) * nbps  # table column this step covers
    stats_phase = j < nbps
    entry = tables_ref[b, jj]
    q_pos = qpos_ref[b]
    d = o_ref.shape[-1]

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def _heads(ref, scale_ref):
        """The block's per-head [bs, d] tiles, dequantized when quantized.

        A pool's last axis is (KV, d) MERGED ([1, bs, KV·d] blocks): Mosaic cannot slice the middle dim of an int8 tile (and
        per-head (…, 1, d) trailing block dims are illegal tilings), so the
        whole tile is loaded/converted 2D and each head is a static
        lane-dim slice — the nf4 kernel's planar-unpack idiom."""
        full = ref[0]  # [bs, KV·d]
        if quant:
            full = full.astype(jnp.float32)
        out = []
        for kv in range(kv_heads):
            h = full[:, kv * d:(kv + 1) * d]
            if quant:
                # match kv_dequantize: f32 product rounded through the
                # compute dtype before the f32 MXU pass
                h = (h * scale_ref[0][:, kv:kv + 1]).astype(o_ref.dtype)
            out.append(h.astype(jnp.float32))
        return out

    def _masked_scores(k_heads):
        """Masked f32 score rows, one [group, bs] per KV head."""
        # pos block is [1, 1, bs] (the unit middle dim keeps the trailing
        # block dims equal to the array dims — Mosaic's tiling rule)
        mask = pos_ref[0, 0:1, :] <= q_pos  # sentinel + causal in one
        out = []
        for kv in range(kv_heads):
            qg = q_ref[0, kv * group:(kv + 1) * group, :].astype(jnp.float32)
            s = jax.lax.dot_general(
                qg, k_heads[kv], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            out.append(jnp.where(mask, s, NEG_INF))
        return out

    @pl.when((entry >= 0) & stats_phase)
    def _stats():
        for kv, s in enumerate(_masked_scores(_heads(k_ref, ks_ref))):
            rows = slice(kv * group, (kv + 1) * group)
            m_prev = m_ref[rows, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            l_ref[rows, :] = (l_ref[rows, :] * corr
                              + jnp.sum(jnp.exp(s - m_new), axis=1,
                                        keepdims=True))
            m_ref[rows, :] = jnp.broadcast_to(m_new,
                                              (group, m_ref.shape[1]))

    @pl.when((entry >= 0) & ~stats_phase)
    def _weighted_sum():
        v_heads = _heads(v_ref, vs_ref)
        for kv, s in enumerate(_masked_scores(_heads(k_ref, ks_ref))):
            rows = slice(kv * group, (kv + 1) * group)
            l_row = jnp.maximum(l_ref[rows, 0:1], 1e-30)
            # the oracle's probs: normalized THEN quantized to the compute
            # dtype before the PV product (xla_attention rounds the same way)
            p = (jnp.exp(s - m_ref[rows, 0:1]) / l_row).astype(o_ref.dtype)
            acc_ref[rows, :] += jax.lax.dot_general(
                p.astype(jnp.float32), v_heads[kv],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    @pl.when(j == 2 * nbps - 1)
    def _finish():
        o_ref[0] = acc_ref[:].astype(o_ref.dtype)


def _stacked(pool: jnp.ndarray) -> jnp.ndarray:
    """A stacked leaf ``[L, NB, bs, w]`` as the kernels address it, ``[L * NB,
    bs, w]``: layer ``l``'s block ``n`` is row ``l * NB + n``. Merging the
    leading dims moves nothing, so the kernel reads the cache leaf the layer
    scan carries, in place, and no layer of it is sliced out."""
    return pool.reshape((-1,) + pool.shape[2:])


def paged_decode_attention(
    q: jnp.ndarray,          # [B, H, d] — the decode step's single token
    k_pool: jnp.ndarray,     # [L, NB, bs, KV * d] the stacked block pool
    v_pool: jnp.ndarray,
    k_scale: Optional[jnp.ndarray],  # [L, NB, bs, KV] f32 (int8) | None
    v_scale: Optional[jnp.ndarray],
    layer,                   # int32 scalar: the layer whose blocks are read
    tables: jnp.ndarray,     # [B, nbps] int32, -1 = unallocated
    pos_pool: jnp.ndarray,   # [NB, bs] int32 — POST-write (this token's rope
                             # position already scattered in)
    q_positions: jnp.ndarray,  # [B] int32 rope position of the query token
    *,
    interpret=None,
) -> jnp.ndarray:
    """In-place paged decode attention over the block pool: out [B, H, d].

    Slots whose tables hold no valid block (released / never admitted)
    produce zeros — the engine's emit mask already discards their tokens,
    mirroring the garbage the oracle's sentinel-masked uniform softmax
    yields for such rows."""
    B, H, d = q.shape
    _, NB, bs, width = k_pool.shape
    KV = width // d
    nbps = tables.shape[1]
    G = H // KV
    quant = k_scale is not None

    # the ORACLE's scale arithmetic, exactly: xla_attention computes
    # 1/sqrt(f32(d)) in f32 — a python 1/d**0.5 double differs by 1 ulp for
    # head dims like 96/112, enough to flip a bf16-rounded probability and
    # break the token-parity contract on those models
    scale = float(np.float32(1.0) / np.sqrt(np.float32(d)))  # dtxlint: disable=DTX001 — host numpy scalar (d is a static shape), no device sync
    kernel = functools.partial(
        _decode_kernel, nbps=nbps, kv_heads=KV, group=G,
        scale=scale, quant=quant)

    def pos_index(b, j, tables_ref, qpos_ref, layer_ref):
        # clamp -1 → block 0: the DMA must stay in bounds; pl.when skips
        # the compute, so the fetched garbage is never read
        return (jnp.maximum(tables_ref[b, j - (j // nbps) * nbps], 0), 0, 0)

    def kv_index(b, j, tables_ref, qpos_ref, layer_ref):
        # the layer's blocks start at row layer * NB of the stacked pool;
        # the pos pool is shared by the layers and keeps the plain id
        blk, _, _ = pos_index(b, j, tables_ref, qpos_ref, layer_ref)
        return (layer_ref[0] * NB + blk, 0, 0)

    scale_index = kv_index

    def v_index(b, j, tables_ref, qpos_ref, layer_ref):
        # V is consumed in phase 1 only; parking the index on the layer's
        # block 0 during phase 0 keeps Mosaic's same-block revisit from
        # re-DMAing anything useless (interpret mode is indifferent)
        jj = j - (j // nbps) * nbps
        return (layer_ref[0] * NB
                + jnp.maximum(tables_ref[b, jj], 0) * (j >= nbps), 0, 0)

    # a pool's last axis is (KV, d) merged, which makes every per-head
    # extraction a static LANE slice (Mosaic cannot slice the middle dim of
    # an int8 tile); the cache stores its leaves that way
    # (ops/paged_attention.py), so nothing is reshaped on the way in
    in_specs = [
        pl.BlockSpec((1, H, d), lambda b, j, t, p, l: (b, 0, 0)),
        pl.BlockSpec((1, bs, KV * d), kv_index),
        pl.BlockSpec((1, bs, KV * d), v_index),
    ]
    args = [q, _stacked(k_pool), _stacked(v_pool)]
    if quant:
        in_specs += [pl.BlockSpec((1, bs, KV), scale_index),
                     pl.BlockSpec((1, bs, KV), scale_index)]
        args += [_stacked(k_scale), _stacked(v_scale)]
    in_specs.append(pl.BlockSpec((1, 1, bs), pos_index))
    args.append(pos_pool[:, None])  # [NB, 1, bs]: Mosaic-legal tiling

    kernel_args = kernel if quant else functools.partial(
        _no_scale_kernel, kernel)
    out = pl.pallas_call(
        kernel_args,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, 2 * nbps),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, H, d),
                                   lambda b, j, t, p, l: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((H, d), jnp.float32),
                pltpu.VMEM((H, _LANES), jnp.float32),
                pltpu.VMEM((H, _LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, d), q.dtype),
        interpret=_interpret() if interpret is None else interpret,
        name="dtx_paged_decode",
    )(tables.astype(jnp.int32), q_positions.astype(jnp.int32),
      _layer_operand(layer), *args)
    return out


def _layer_operand(layer) -> jnp.ndarray:
    """The layer index as a scalar-prefetch operand: int32 ``[1]``."""
    return jnp.asarray(layer, jnp.int32).reshape(1)


def _no_scale_kernel(kernel, tables_ref, qpos_ref, layer_ref, q_ref, k_ref,
                     v_ref, pos_ref, o_ref, acc_ref, m_ref, l_ref):
    """Arity shim for the unquantized pools: no scale refs in the call."""
    kernel(tables_ref, qpos_ref, layer_ref, q_ref, k_ref, v_ref, None, None,
           pos_ref, o_ref, acc_ref, m_ref, l_ref)


def paged_attention_decode_step(q, leaves: dict, layer, cache: dict,
                                pos_pool, positions, *, interpret=None):
    """Model-facing wrapper: q ``[B, 1, H, d]`` (one decode token), the
    stacked cache leaves the layer scan carries (``k``/``v`` and, for the
    int8 cache, ``k_scale``/``v_scale``), the layer's index, the live cache
    dict (block tables), the POST-write pos pool, and the step's
    ``positions [B, 1]``. Returns ``[B, 1, H, d]`` in q.dtype — drop-in for
    the gather + ``xla_attention`` pair."""
    B, T, H, d = q.shape
    assert T == 1, f"paged decode kernel is single-token (T=1), got T={T}"
    out = paged_decode_attention(
        q[:, 0], leaves["k"], leaves["v"], leaves.get("k_scale"),
        leaves.get("v_scale"), layer, cache["block_tables"], pos_pool,
        positions[:, 0], interpret=interpret)
    return out[:, None]


# ---------------------------------------------------------------------------
# Multi-token q (chunked prefill / verify-k / tree-verify columns)
# ---------------------------------------------------------------------------
#
# Same block-table walk and two-phase online softmax as the decode kernel,
# for a bucketed q_len > 1. Masking changes shape, not mechanism: instead of
# the in-kernel ``kv_pos <= q_pos`` compare (one scalar per slot), the host
# precomputes the full boolean attendability tensor ``allow [B, T, W]`` with
# ``ops.attention.attention_allow`` — the SAME tensor the XLA oracle turns
# into its additive bias — and the kernel streams the block's [T, bs] tile
# of it alongside K/V. That one operand encodes per-row causal offsets,
# POS_SENTINEL lanes, ragged lens, sliding windows, and tree-branch
# ancestry masks uniformly, so kernel/oracle mask parity holds by
# construction (an int32 tile costs W·T·4 bytes per slot vs the KV blocks'
# 2·W·KV·d·itemsize — noise). Invalid table entries are still skipped
# outright by ``pl.when``.
#
# q enters kv-major and TILED over the query rows: ``T`` is padded to a
# sublane multiple and cut into ``nT`` tiles of ``tq`` rows, laid out
# ``[B, nT, H·tq, d]`` (row (kv·G + g)·tq + t), and the grid grows a tile
# axis ``(B, nT, 2·nbps)``. Per-(kv, g) extraction stays a static sublane
# slice and each score tile is one [tq, d] × [d, bs] MXU pass, reusing the
# decode kernel's merged-trailing-dim pool layout unchanged. The tiling is
# what bounds VMEM: the three f32 scratch buffers plus the double-buffered
# q/out blocks cost ~2.5 KB per query-head row, so a whole
# ``T = prefill_chunk`` (H·T = 8192 rows at tinyllama width) overflows the
# 16 MB scoped limit Mosaic grants a kernel; ``_MT_ROW_CAP`` rows keeps a
# grid step near 5 MB. The price is one K/V re-stream per tile.
#
# ``allow`` travels as ``[B, nT, nbps, tq, bs]`` so its block's trailing
# dims EQUAL the array's — the only legal tiling for ``bs < 128`` (a
# ``(1, T, bs)`` window of a ``[B, T, W]`` array is refused by Mosaic).
#
# Garbage contract: a fully-masked query row (inactive slot in a verify
# batch, or a pad row of the last tile) normalizes over NEG_INF scores —
# finite uniform-ish junk, like the oracle's sentinel-masked softmax but not
# bit-equal to it. Such rows only exist where the engine's emit mask
# discards them (pad rows are sliced off here); parity is asserted on rows
# with at least one attendable lane.

_MT_ROW_CAP = 2048  # query-head rows (H·tq) resident per grid step


def _mt_tiling(q_len: int, heads: int) -> tuple[int, int]:
    """(padded q_len, rows per tile): q_len rounded up to the f32 sublane
    count, and the largest sublane-multiple divisor of it that keeps
    ``heads · tq`` within ``_MT_ROW_CAP``."""
    tp = -(-q_len // 8) * 8
    cap = max(8, _MT_ROW_CAP // heads // 8 * 8)
    tq = max(t for t in range(8, min(cap, tp) + 1, 8) if tp % t == 0)
    return tp, tq


def _multitoken_kernel(tables_ref, layer_ref, q_ref, k_ref, v_ref, ks_ref,
                       vs_ref, allow_ref, o_ref, acc_ref, m_ref, l_ref,
                       *, nbps: int, kv_heads: int, group: int, q_len: int,
                       scale: float, quant: bool):
    """One (slot, query tile, table-entry × phase) grid step for ``q_len``
    query rows per head."""
    b = pl.program_id(0)
    j = pl.program_id(2)
    jj = j - (j // nbps) * nbps
    stats_phase = j < nbps
    entry = tables_ref[b, jj]
    d = o_ref.shape[-1]

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def _heads(ref, scale_ref):
        """The block's per-head [bs, d] tiles (see the decode kernel)."""
        full = ref[0]  # [bs, KV·d]
        if quant:
            full = full.astype(jnp.float32)
        out = []
        for kv in range(kv_heads):
            h = full[:, kv * d:(kv + 1) * d]
            if quant:
                h = (h * scale_ref[0][:, kv:kv + 1]).astype(o_ref.dtype)
            out.append(h.astype(jnp.float32))
        return out

    def _masked_scores(k_heads):
        """Masked f32 score tiles: one ([q_len, bs], row slice) per head."""
        mask = allow_ref[0, 0, 0] != 0  # [q_len, bs] — the oracle's bias == 0
        out = []
        for kv in range(kv_heads):
            for g in range(group):
                rows = slice((kv * group + g) * q_len,
                             (kv * group + g + 1) * q_len)
                qg = q_ref[0, 0, rows, :].astype(jnp.float32)
                s = jax.lax.dot_general(
                    qg, k_heads[kv], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * scale
                out.append((rows, kv, jnp.where(mask, s, NEG_INF)))
        return out

    @pl.when((entry >= 0) & stats_phase)
    def _stats():
        for rows, _, s in _masked_scores(_heads(k_ref, ks_ref)):
            m_prev = m_ref[rows, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            l_ref[rows, :] = (l_ref[rows, :] * corr
                              + jnp.sum(jnp.exp(s - m_new), axis=1,
                                        keepdims=True))
            m_ref[rows, :] = jnp.broadcast_to(m_new,
                                              (q_len, m_ref.shape[1]))

    @pl.when((entry >= 0) & ~stats_phase)
    def _weighted_sum():
        v_heads = _heads(v_ref, vs_ref)
        for rows, kv, s in _masked_scores(_heads(k_ref, ks_ref)):
            l_row = jnp.maximum(l_ref[rows, 0:1], 1e-30)
            # oracle rounding: normalize THEN cast before the PV product
            p = (jnp.exp(s - m_ref[rows, 0:1]) / l_row).astype(o_ref.dtype)
            acc_ref[rows, :] += jax.lax.dot_general(
                p.astype(jnp.float32), v_heads[kv],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    @pl.when(j == 2 * nbps - 1)
    def _finish():
        o_ref[0, 0] = acc_ref[:].astype(o_ref.dtype)


def paged_multitoken_attention(
    q: jnp.ndarray,          # [B, T, H, d] — the step's query columns
    k_pool: jnp.ndarray,     # [L, NB, bs, KV * d] the stacked block pool
    v_pool: jnp.ndarray,
    k_scale: Optional[jnp.ndarray],  # [L, NB, bs, KV] f32 (int8) | None
    v_scale: Optional[jnp.ndarray],
    layer,                   # int32 scalar: the layer whose blocks are read
    tables: jnp.ndarray,     # [B, nbps] int32, -1 = unallocated
    allow: jnp.ndarray,      # [B, T, nbps·bs] bool/int — attendability per
                             # (query row, linear cache lane), POST-write
    *,
    interpret=None,
) -> jnp.ndarray:
    """In-place paged attention for q_len > 1: out ``[B, T, H, d]``.

    ``allow`` must be ``attention_allow(...)`` over the POST-write gathered
    kv positions — the one tensor the gather oracle biases with."""
    B, T, H, d = q.shape
    _, NB, bs, width = k_pool.shape
    KV = width // d
    nbps = tables.shape[1]
    G = H // KV
    quant = k_scale is not None
    assert allow.shape == (B, T, nbps * bs), (
        f"allow {allow.shape} != {(B, T, nbps * bs)}")
    Tp, tq = _mt_tiling(T, H)
    nT = Tp // tq

    scale = float(np.float32(1.0) / np.sqrt(np.float32(d)))  # dtxlint: disable=DTX001 — host numpy scalar (d is a static shape), no device sync
    kernel = functools.partial(
        _multitoken_kernel, nbps=nbps, kv_heads=KV, group=G, q_len=tq,
        scale=scale, quant=quant)

    def kv_index(b, i, j, tables_ref, layer_ref):
        # as in the decode kernel: row layer * NB + block of the stacked pool
        return (layer_ref[0] * NB
                + jnp.maximum(tables_ref[b, j - (j // nbps) * nbps], 0), 0, 0)

    scale_index = kv_index

    def v_index(b, i, j, tables_ref, layer_ref):
        jj = j - (j // nbps) * nbps
        return (layer_ref[0] * NB
                + jnp.maximum(tables_ref[b, jj], 0) * (j >= nbps), 0, 0)

    def allow_index(b, i, j, tables_ref, layer_ref):
        return (b, i, j - (j // nbps) * nbps, 0, 0)

    def q_index(b, i, j, tables_ref, layer_ref):
        return (b, i, 0, 0)

    # pad rows are never attendable (allow 0) and are sliced off the output
    allow = allow.astype(jnp.int32)
    if Tp != T:
        q = jnp.pad(q, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
        allow = jnp.pad(allow, ((0, 0), (0, Tp - T), (0, 0)))
    q_km = q.reshape(B, nT, tq, H, d).transpose(0, 1, 3, 2, 4).reshape(
        B, nT, H * tq, d)
    allow_t = allow.reshape(B, nT, tq, nbps, bs).transpose(0, 1, 3, 2, 4)
    in_specs = [
        pl.BlockSpec((1, 1, H * tq, d), q_index),
        pl.BlockSpec((1, bs, KV * d), kv_index),
        pl.BlockSpec((1, bs, KV * d), v_index),
    ]
    args = [q_km, _stacked(k_pool), _stacked(v_pool)]
    if quant:
        in_specs += [pl.BlockSpec((1, bs, KV), scale_index),
                     pl.BlockSpec((1, bs, KV), scale_index)]
        args += [_stacked(k_scale), _stacked(v_scale)]
    in_specs.append(pl.BlockSpec((1, 1, 1, tq, bs), allow_index))
    args.append(allow_t)

    kernel_args = kernel if quant else functools.partial(
        _no_scale_mt_kernel, kernel)
    out = pl.pallas_call(
        kernel_args,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, nT, 2 * nbps),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, H * tq, d), q_index),
            scratch_shapes=[
                pltpu.VMEM((H * tq, d), jnp.float32),
                pltpu.VMEM((H * tq, _LANES), jnp.float32),
                pltpu.VMEM((H * tq, _LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, nT, H * tq, d), q.dtype),
        interpret=_interpret() if interpret is None else interpret,
        name="dtx_paged_multitoken",
    )(tables.astype(jnp.int32), _layer_operand(layer), *args)
    out = out.reshape(B, nT, H, tq, d).transpose(0, 1, 3, 2, 4)
    return out.reshape(B, Tp, H, d)[:, :T]


def _no_scale_mt_kernel(kernel, tables_ref, layer_ref, q_ref, k_ref, v_ref,
                        allow_ref, o_ref, acc_ref, m_ref, l_ref):
    """Arity shim for the unquantized pools: no scale refs in the call."""
    kernel(tables_ref, layer_ref, q_ref, k_ref, v_ref, None, None, allow_ref,
           o_ref, acc_ref, m_ref, l_ref)


def paged_attention_multitoken_step(q, leaves: dict, layer, cache: dict,
                                    allow, *, interpret=None):
    """Model-facing wrapper: q ``[B, T, H, d]`` (chunk / verify columns),
    the stacked cache leaves the layer scan carries, the layer's index, the
    live cache dict, and the POST-write ``allow [B, T, S]`` attendability
    tensor. Returns ``[B, T, H, d]`` in q.dtype — drop-in for the gather +
    ``xla_attention`` pair."""
    return paged_multitoken_attention(
        q, leaves["k"], leaves["v"], leaves.get("k_scale"),
        leaves.get("v_scale"), layer, cache["block_tables"], allow,
        interpret=interpret)
