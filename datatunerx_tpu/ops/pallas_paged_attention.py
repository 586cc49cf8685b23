"""Pallas in-place paged-attention kernels (vLLM PagedAttention done
natively — PAPERS.md; the ROADMAP "Decode fast path" arc).

The XLA gather path (ops/attention.py ``kv_cache_update``) is
token-exact but materializes a dense-equivalent ``[B, W, KV, d]`` linear
view of every slot's blocks per layer per decode step: the block pool saves
HBM *capacity* while decode still pays dense HBM *bandwidth* — a full-width
gather write plus a full-width attention read, padding included. These
kernels read the K/V blocks IN PLACE through the per-slot block table, so
the gathered view never exists.

**Decode (one query token a slot), ``paged_decode_attention``.** The grid is
one step a slot; nothing in it scales with the table's width. The pools stay
in HBM and a step walks its slot's table in a loop of its own, as far as the
slot's lane cursor and no further: a trip copies a lane tile of tokens
(``_blocks_per_trip`` consecutive columns' blocks) into one of two VMEM
buffers while the trip before is computed on. Columns past the cursor — the
blocks admission reserves for tokens to come among them — cost neither a
step nor a byte, so per decode token the kernel streams only the slot's LIVE
blocks (K twice, V once — see below) and both its bytes and its time follow
``len(session)`` instead of ``blocks_per_slot × block_size``. A slot with
nothing written costs a grid step that stores zeros. A model's sliding
``window`` is a static operand: one the cache's width cannot exceed is dropped
at trace time (no query has a key behind it, and the kernel emitted is the
window-less one), a narrower one gives the walk a FIRST trip as the cursor
gives it a last, so bytes and time follow ``min(len, window)``. The v pool's
rows may have a head width of their own (``[L, NB, bs, KV·dv]``: a trip
buffer, an accumulator and an output of that width) and the scores a static
``scale`` other than ``d ** -0.5``: both are a layer KIND's in a model of
several kinds (models/hybrid.py hands a sink-less kind's token step here).
With one width and no scale the call lowers to the same text as before them.

Correctness contract — the gather path stays alive as the parity ORACLE,
and the PR 5 bit-parity suite asserts kernel-vs-gather token-exactness.
That drives the kernels' two-phase shape:

- **Phase 0 (stats)**: flash-style online-softmax accumulator over the
  table's blocks in ascending order — running row max ``m`` and rescaled
  normalizer ``l`` in f32, exactly flash_attention.py's scheme.
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

Masking needs no bias tensor. The decode kernel compares the slot's linear
position view (``[B, W]`` int32, one small gather shared by the layers;
POS_SENTINEL on every unwritten, pad or unbacked lane) against the query's
rope position, the same ``kv_pos <= q_pos`` check the oracle's causal bias
encodes, and maps each query-head group onto its KV head by laying the query
rows out block-diagonally against the pools' merged ``(KV, d)`` axis (no
per-head slicing, no ``jnp.repeat``). The multi-token kernel (below) keeps
the table's columns in its GRID and skips a table entry < 0 with
``pl.when``: its steps follow the table's width. int8 ``kv_quant`` pools
dequantize per block inside the kernels by the paged scale pools
(pallas_quant.py's fuse-the-dequant idiom), rounding through the compute
dtype exactly as ``kv_dequantize`` does.

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

from datatunerx_tpu.ops.attention import attention_allow
from datatunerx_tpu.ops.paged_attention import POS_SENTINEL, gathered_positions

NEG_INF = -1e30  # finite (flash_attention.py): -inf - -inf would NaN
_LANES = 128  # the TPU lane width: stats scratch, a decode trip's score tile
_TRIP_VMEM = 8 << 20  # ceiling on the decode kernel's K and V trip buffers


def _interpret() -> bool:
    from datatunerx_tpu.ops._pallas import interpret_default

    return interpret_default()


def _blocks_per_trip(bs: int, widths: int, itemsize: int, nbps: int) -> int:
    """Table columns one trip of the decode kernel's loop covers: enough for
    a lane-dense score tile (``_LANES`` tokens), fewer where the table has
    fewer or where K and V (``widths``: their rows' widths added), each
    double-buffered at ``bs * width * itemsize`` bytes a block, would pass
    ``_TRIP_VMEM``."""
    fit = _TRIP_VMEM // (2 * bs * widths * itemsize)
    return max(1, min(-(-_LANES // bs), fit, nbps))


def _decode_kernel(tables_ref, qpos_ref, bound_ref, layer_ref, q_ref,
                   pos_ref, k_hbm, v_hbm, *refs, pool_blocks: int, trip: int,
                   kv_heads: int, group: int, scale: float, quant: bool,
                   window: Optional[int] = None, first_ref=None):
    """One slot a grid step; the walk over its block table is a loop in here.

    The pools stay in HBM. A trip covers ``trip`` consecutive table columns
    (a lane tile of tokens): their blocks are copied into one of two VMEM
    buffers while the trip before is computed on, and a column at or past
    the slot's bound ``nb`` (``bound_ref``: the columns up to its cursor) is
    not copied at all, so the loop runs ``ceil(nb / trip)`` times a phase
    and a slot with nothing written runs none. The table is walked twice, in
    ascending order both times: the stats phase (K) carries the running row
    max and normalizer, the weighted-sum phase reads K again and V.

    With a ``window`` the walk also has a first trip (``first_ref``): the
    trips before it hold no lane the window admits and are not copied
    either, the loops count trips from it (``at``), and the mask gains the
    oracle's ``pos > q_pos - window``, which decides inside the first trip.

    All heads share one MXU pass a trip: the query rows are laid out
    block-diagonally (``qbd[h, kv(h)·d:(kv(h)+1)·d] = q[h]``, zero elsewhere)
    against the pools' merged ``(KV, d)`` axis, so ``qbd · Kᵀ`` is every
    head's score row ``[H, tokens]`` (the added products are exact zeros)
    and ``p · V`` is ``[H, KV·dv]`` whose diagonal blocks are the output —
    which is also how GQA maps a query-head group onto its KV head. V's
    heads may have a width of their own (``dv``, the output's): it is read
    off the refs, K's ``d`` off the query's."""
    # the int8 pools' scale views come between the pools and the output
    ks_ref, vs_ref = refs[:2] if quant else (None, None)
    o_ref, k_buf, v_buf, qbd_ref, acc_ref, k_sem, v_sem = refs[-7:]
    b = pl.program_id(0)
    d, dv = q_ref.shape[-1], o_ref.shape[-1]
    bs = k_buf.shape[2]
    lanes = trip * bs  # tokens a trip covers
    nb = bound_ref[b]
    trips = (nb + trip - 1) // trip
    row0 = layer_ref[0] * pool_blocks  # the layer's first row of the pools
    q_pos = qpos_ref[b]
    if window is None:
        def at(r):
            return r
    else:
        # the loops below count the walk's trips from its first one; ``at``
        # gives a trip's place in the table
        first = jnp.minimum(first_ref[b], trips)
        trips = trips - first

        def at(r):
            return first + r

    def copies(t, slot, which):
        """The copies of trip ``t`` into buffer ``slot``, each under the
        condition it is issued and awaited by: its column lies inside the
        bound. A hole inside it (entry -1) reads block 0, in bounds, as the
        oracle's gather does; its lanes read as sentinel."""
        hbm, buf, sem = ((k_hbm, k_buf, k_sem) if which == "k"
                         else (v_hbm, v_buf, v_sem))
        out = []
        for i in range(trip):
            col = t * trip + i
            row = row0 + jnp.maximum(
                tables_ref[b, jnp.minimum(col, nb - 1)], 0)
            out.append((col < nb, pltpu.make_async_copy(
                hbm.at[row], buf.at[slot, i], sem.at[slot])))
        return out

    def start(t, slot, which):
        for live, c in copies(t, slot, which):
            pl.when(live)(c.start)

    def landed(t, slot, which):
        """Wait for trip ``t`` and return its ``[lanes, KV·d]`` tile in the
        compute dtype, an int8 tile dequantized as ``kv_dequantize`` does
        (the f32 product rounded through the compute dtype)."""
        for live, c in copies(t, slot, which):
            pl.when(live)(c.wait)
        buf, sc_ref, w = ((k_buf, ks_ref, d) if which == "k"
                          else (v_buf, vs_ref, dv))
        if not quant:
            return buf[slot].reshape(lanes, -1).astype(o_ref.dtype)
        full = buf[slot].astype(jnp.float32).reshape(lanes, -1)
        # the scale view holds every table column: a row past the bound
        # takes scale 0, not a reserved block's scales
        row = jax.lax.broadcasted_iota(jnp.int32, (lanes, 1), 0)
        sc = jnp.where(t * lanes + row < nb * bs, sc_ref[0, t], 0.0)
        return jnp.concatenate(
            [full[:, kv * w:(kv + 1) * w] * sc[:, kv:kv + 1]
             for kv in range(kv_heads)], axis=1).astype(o_ref.dtype)

    @pl.when(b == 0)
    def _first():
        # qbd's off-diagonal stays zero for the whole call. A column past a
        # slot's bound is never copied, so its buffer rows keep what they
        # held. For V that is zeros from here or an earlier trip's pool
        # data, finite either way, which is what a masked lane's 0 · v needs
        # to be 0; K's stale rows only reach scores the mask replaces.
        qbd_ref[...] = jnp.zeros_like(qbd_ref)
        v_buf[...] = jnp.zeros_like(v_buf)

    @pl.when(trips == 0)
    def _nothing_written():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(trips > 0)
    def _walk():
        start(at(0), 0, "k")
        start(at(0), 0, "v")  # V's first trip lands while K is walked
        for kv in range(kv_heads):
            rows = slice(kv * group, (kv + 1) * group)
            qbd_ref[rows, kv * d:(kv + 1) * d] = q_ref[0, rows, :]
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)

        def scores(t, slot):
            """Trip ``t``'s masked f32 scores ``[H, lanes]``, K in ``slot``."""
            s = jax.lax.dot_general(
                qbd_ref[...], landed(t, slot, "k"), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            # sentinel + causal in one compare, as the oracle's bias; the
            # index bound drops the lanes of the columns not copied
            mask = (pos_ref[0, t] <= q_pos) & (t * lanes + lane < nb * bs)
            if window is not None:  # attention_allow's compare
                mask &= pos_ref[0, t] > q_pos - window
            return jnp.where(mask, s, NEG_INF)

        def stats(r, carry):
            m_prev, l_prev = carry
            # K's copies run on through both phases: after the last trip of
            # this one comes the first of the next, again
            nxt = jnp.where(r + 1 < trips, r + 1, 0)
            start(at(nxt), (r + 1) % 2, "k")
            s = scores(at(r), r % 2)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            l_new = (l_prev * jnp.exp(m_prev - m_new)
                     + jnp.sum(jnp.exp(s - m_new), axis=1, keepdims=True))
            return m_new, l_new

        heads = kv_heads * group
        m, l = jax.lax.fori_loop(
            0, trips, stats,
            (jnp.full((heads, 1), NEG_INF, jnp.float32),
             jnp.zeros((heads, 1), jnp.float32)))
        l_row = jnp.maximum(l, 1e-30)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def weighted_sum(r, carry):
            k_slot = (trips + r) % 2

            @pl.when(r + 1 < trips)
            def _():
                start(at(r + 1), 1 - k_slot, "k")
                start(at(r + 1), (r + 1) % 2, "v")

            s = scores(at(r), k_slot)
            # the oracle's probs: normalized THEN quantized to the compute
            # dtype before the PV product (xla_attention rounds the same way)
            p = (jnp.exp(s - m) / l_row).astype(o_ref.dtype)
            acc_ref[...] += jax.lax.dot_general(
                p, landed(at(r), r % 2, "v"), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, trips, weighted_sum, None)
        for kv in range(kv_heads):
            rows = slice(kv * group, (kv + 1) * group)
            o_ref[0, rows, :] = acc_ref[rows, kv * dv:(kv + 1) * dv].astype(
                o_ref.dtype)


def _stacked(pool: jnp.ndarray) -> jnp.ndarray:
    """A stacked leaf ``[L, NB, bs, w]`` as the kernels address it, ``[L * NB,
    bs, w]``: layer ``l``'s block ``n`` is row ``l * NB + n``. Merging the
    leading dims moves nothing, so the kernel reads the cache leaf the layer
    scan carries, in place, and no layer of it is sliced out."""
    return pool.reshape((-1,) + pool.shape[2:])


def paged_decode_attention(
    q: jnp.ndarray,          # [B, H, d] — the decode step's single token
    k_pool: jnp.ndarray,     # [L, NB, bs, KV * d] the stacked block pool
    v_pool: jnp.ndarray,     # [L, NB, bs, KV * dv]: v heads of their own width
    k_scale: Optional[jnp.ndarray],  # [L, NB, bs, KV] f32 (int8) | None
    v_scale: Optional[jnp.ndarray],
    layer,                   # int32 scalar: the layer whose blocks are read
    tables: jnp.ndarray,     # [B, nbps] int32, -1 = unallocated
    pos_pool: jnp.ndarray,   # [NB, bs] int32 — POST-write (this token's rope
                             # position already scattered in)
    q_positions: jnp.ndarray,  # [B] int32 rope position of the query token
    cursor: jnp.ndarray,     # [B] int32 the linear lane this token was
                             # written at (the cache's ``len`` before the step)
    *,
    window: Optional[int] = None,  # the model's sliding window, static
    scale: Optional[float] = None,  # of the scores, static; d ** -0.5 if None
    interpret=None,
) -> jnp.ndarray:
    """In-place paged decode attention over the block pool: out [B, H, dv],
    ``dv`` the v pool's head width (the k pool's ``d`` in most models).

    A slot's walk ends at the column its cursor lies in (left pads make the
    rope position undercount the lanes, so the cursor and not ``q_positions``
    is the bound), or at its last column that holds a block if that comes
    first. Under a ``window`` narrower than the cache it starts at the trip
    that holds the first lane the window admits, read off the slot's position
    view and not reckoned from the cursor: while a row's pads all lie at its
    left they shift its lanes against its rope positions by a constant
    (``lane = position + pads``, keys and query alike) and that lane is
    ``cursor + 1 - window`` whatever the pads, but a prefix-cache extension
    leaves pads mid-row, which put it that many lanes earlier. A ``window``
    of the cache's width (``table columns × block size``) or more has no lane
    behind it: it is dropped here, from the shapes, and the kernel emitted
    is the window-less one. Slots with nothing to walk (released: a table of
    -1, whatever cursor they kept) produce zeros — the engine's emit mask
    already discards their tokens, mirroring the garbage the oracle's
    sentinel-masked uniform softmax yields for such rows."""
    B, H, d = q.shape
    _, NB, bs, width = k_pool.shape
    KV = width // d
    v_width = v_pool.shape[-1]
    dv = v_width // KV
    nbps = tables.shape[1]
    quant = k_scale is not None
    if window is not None and window >= nbps * bs:
        window = None
    trip = _blocks_per_trip(bs, width + v_width, k_pool.dtype.itemsize, nbps)
    trips = -(-nbps // trip)
    lanes = trip * bs

    # the ORACLE's scale arithmetic, exactly: xla_attention computes
    # 1/sqrt(f32(d)) in f32 — a python 1/d**0.5 double differs by 1 ulp for
    # head dims like 96/112, enough to flip a bf16-rounded probability and
    # break the token-parity contract on those models
    if scale is None:
        scale = float(np.float32(1.0) / np.sqrt(np.float32(d)))  # dtxlint: disable=DTX001 — host numpy scalar (d is a static shape), no device sync
    kernel = functools.partial(
        _decode_kernel, pool_blocks=NB, trip=trip, kv_heads=KV,
        group=H // KV, scale=scale, quant=quant, window=window)

    tables = tables.astype(jnp.int32)
    held = jnp.max(jnp.where(tables >= 0,
                             jnp.arange(1, nbps + 1, dtype=jnp.int32), 0),
                   axis=1)
    bound = jnp.minimum(cursor.astype(jnp.int32) // bs + 1, held)
    # the slot's linear position view (a small gather, the same for every
    # layer) as [trips, 1, lanes], so a trip's row is a leading index; a
    # lane backed by no block, and the pad past the table, read as sentinel
    pos = jnp.pad(gathered_positions(pos_pool, tables),
                  ((0, 0), (0, trips * lanes - nbps * bs)),
                  constant_values=POS_SENTINEL)
    q_positions = q_positions.astype(jnp.int32)
    prefetch = [tables, q_positions, bound, _layer_operand(layer)]
    if window is not None:
        # the trip of the first lane the mask will admit (``trips``: none)
        q_pos = q_positions[:, None]
        admits = jnp.pad((pos <= q_pos) & (pos > q_pos - window),
                         ((0, 0), (0, 1)), constant_values=True)
        prefetch.append(
            (jnp.argmax(admits, axis=1) // lanes).astype(jnp.int32))
        kernel = functools.partial(_windowed_decode_kernel, kernel)
    pos = pos.reshape(B, trips, 1, lanes)

    # a pool's last axis is (KV, d) merged and the cache stores its leaves
    # that way (ops/paged_attention.py), so nothing is reshaped on the way in
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [
        pl.BlockSpec((1, H, d), lambda b, *_: (b, 0, 0)),
        pl.BlockSpec((1, trips, 1, lanes), lambda b, *_: (b, 0, 0, 0)),
        hbm, hbm,
    ]
    args = [q, pos, _stacked(k_pool), _stacked(v_pool)]
    scratch = [pltpu.VMEM((2, trip, bs, width), k_pool.dtype),
               pltpu.VMEM((2, trip, bs, v_width), v_pool.dtype)]
    if quant:
        # Mosaic cuts no copy out of an HBM array whose minor dim (the KV
        # heads) is under a lane tile, so the scales come as each slot's
        # gathered view [trips, lanes, KV] (a hole reads block 0, as K and V
        # do). That gather is as wide as the table: 4 bytes a head and lane,
        # against the d bytes a head the blocks cost a written lane
        def view(scales):
            rows = layer * NB + jnp.maximum(tables, 0)
            got = _stacked(scales)[rows].reshape(B, -1, KV)
            return jnp.pad(
                got, ((0, 0), (0, trips * lanes - nbps * bs), (0, 0))
            ).reshape(B, trips, lanes, KV)

        in_specs += [pl.BlockSpec((1, trips, lanes, KV),
                                  lambda b, *_: (b, 0, 0, 0))] * 2
        args += [view(k_scale), view(v_scale)]
    scratch += [
        pltpu.VMEM((H, width), q.dtype),      # block-diagonal q
        pltpu.VMEM((H, v_width), jnp.float32),  # p · V, all blocks
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
    ]

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(B,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, H, dv), lambda b, *_: (b, 0, 0)),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, dv), q.dtype),
        # the scratch buffers are zeroed in the first step for all of them
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret() if interpret is None else interpret,
        name="dtx_paged_decode",
    )(*prefetch, *args)


def _windowed_decode_kernel(kernel, tables_ref, qpos_ref, bound_ref,
                            layer_ref, first_ref, *refs):
    """Arity shim for a ``window`` narrower than the cache: the walk's first
    trip is the last scalar-prefetch operand of the call."""
    kernel(tables_ref, qpos_ref, bound_ref, layer_ref, *refs,
           first_ref=first_ref)


def _layer_operand(layer) -> jnp.ndarray:
    """The layer index as a scalar-prefetch operand: int32 ``[1]``."""
    return jnp.asarray(layer, jnp.int32).reshape(1)


def walks_in_place(k_pool, v_pool, interpret=None) -> bool:
    """Whether the decode kernel can walk these pools: the chip's compiler
    cuts a block out of an HBM array only along whole lane tiles, so a pool
    whose rows are not a multiple of that (the debug presets; KV heads x head
    width of every published model is one) cannot be read by hand-issued
    copies. Interpret mode takes any width."""
    interpret = _interpret() if interpret is None else interpret
    return interpret or not (k_pool.shape[-1] % _LANES
                             or v_pool.shape[-1] % _LANES)


def paged_attention_decode_step(q, leaves: dict, layer, cache: dict,
                                pos_pool, positions, *, window=None,
                                scale=None, interpret=None):
    """Model-facing wrapper: q ``[B, 1, H, d]`` (one decode token), the
    stacked cache leaves the layer scan carries (``k``/``v`` and, for the
    int8 cache, ``k_scale``/``v_scale``), the layer's index, the cache dict
    the step was handed (block tables, and ``len``: the lane this step's
    token was written at, which bounds the walk), the POST-write pos pool,
    the step's ``positions [B, 1]``, the model's sliding ``window`` (static;
    None: every earlier key is seen) and its score ``scale`` (static; None:
    ``d ** -0.5``). Returns ``[B, 1, H, dv]`` in q.dtype, ``dv`` the v
    pool's head width — drop-in for the gather + ``xla_attention`` pair."""
    B, T, H, d = q.shape
    assert T == 1, f"paged decode kernel is single-token (T=1), got T={T}"
    interpret = _interpret() if interpret is None else interpret
    if not walks_in_place(leaves["k"], leaves["v"], interpret):
        # such pools take the multi-token kernel at q_len 1, whose blocks
        # arrive through BlockSpecs; it has one head width and one scale
        assert scale is None and leaves["k"].shape == leaves["v"].shape
        kv_pos = gathered_positions(pos_pool, cache["block_tables"])
        return paged_attention_multitoken_step(
            q, leaves, layer, cache,
            attention_allow(positions, kv_pos, sliding_window=window),
            interpret=interpret)
    out = paged_decode_attention(
        q[:, 0], leaves["k"], leaves["v"], leaves.get("k_scale"),
        leaves.get("v_scale"), layer, cache["block_tables"], pos_pool,
        positions[:, 0], cache["len"], window=window, scale=scale,
        interpret=interpret)
    return out[:, None]


# ---------------------------------------------------------------------------
# Multi-token q (chunked prefill / verify-k / tree-verify columns)
# ---------------------------------------------------------------------------
#
# The decode kernel's two-phase arithmetic for a bucketed q_len > 1, with the
# table's columns in the GRID: the trailing grid dim walks the slot's table
# twice (``j < nbps`` the stats phase, ``j >= nbps`` the weighted-sum phase,
# K read in both), a column's K/V block lands in VMEM through the
# scalar-prefetched table (an invalid entry clamps to block 0 and is skipped
# by ``pl.when``), and the running max and normalizer live in f32 VMEM
# scratch. Masking changes shape, not mechanism: instead of
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
# slice and each score tile is one [tq, d] × [d, bs] MXU pass over the
# pools' merged-trailing-dim layout. The tiling is
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
        """The block's per-head [bs, d] tiles, dequantized when quantized.

        A pool's last axis is (KV, d) MERGED ([1, bs, KV·d] blocks): Mosaic
        cannot slice the middle dim of an int8 tile (and per-head (…, 1, d)
        trailing block dims are illegal tilings), so the whole tile is
        loaded/converted 2D and each head is a static lane-dim slice — the
        nf4 kernel's planar-unpack idiom."""
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
        # the layer's blocks start at row layer * NB of the stacked pool;
        # clamp -1 → block 0: the DMA must stay in bounds, and pl.when skips
        # the compute, so the fetched garbage is never read
        return (layer_ref[0] * NB
                + jnp.maximum(tables_ref[b, j - (j // nbps) * nbps], 0), 0, 0)

    scale_index = kv_index

    def v_index(b, i, j, tables_ref, layer_ref):
        # V is consumed in phase 1 only; parking the index on the layer's
        # block 0 during phase 0 keeps Mosaic's same-block revisit from
        # re-DMAing anything useless (interpret mode is indifferent)
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
