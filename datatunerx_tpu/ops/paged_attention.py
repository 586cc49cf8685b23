"""Paged KV cache: block pool + per-slot block tables (vLLM PagedAttention,
Sarathi chunked prefill — PAPERS.md).

The dense serving cache reserves ``slots × max_seq_len`` KV rows up front, so
a 40-token chat strands the other 984 positions of its slot in HBM for its
whole lifetime. Here the cache is a POOL of fixed-size blocks
(``block_size`` tokens each, shaped ``[L, num_blocks, block_size, KV * d]``:
heads and width are one axis, the layout the Pallas kernels read a block in,
so a program hands them the leaf as it is stored) plus a per-slot block table
mapping linear cache positions to physical blocks. Admission reserves ``ceil((prompt + max_new) / block_size)`` blocks
from a host-side free list instead of a full-width row, so short requests
release most of the HBM a dense slot would strand and the same pool admits
more concurrent work (or the same work in less HBM).

Reads go through a GATHER over the block table: the slot's blocks are
gathered back into a ``[B, blocks_per_slot × block_size]`` linear view and
attention runs over it exactly as over a dense row — the gathered view is
element-identical to the dense layout (token at linear index ``i`` lives in
block ``i // block_size`` at offset ``i % block_size``), so paged and dense
decode produce the same tokens. Unallocated table entries (-1) gather block
0's values but their rope positions are forced to ``POS_SENTINEL``, which
the causal bias masks exactly like a dense cache's unwritten tail. Writes
scatter through the table; invalid targets (exhausted slot, -1 entry) map to
index ``num_blocks`` — out of bounds, which JAX scatter drops.

The int8 ``kv_quant`` path is preserved: scale pools are paged alongside the
value pools with the same tables.

This module is wired into the model through ``ops/attention.py``'s cache
interface (``cache_positions_update`` / ``KVStep`` / ``kv_cache_update``): a
cache dict carrying ``block_tables`` takes the paged path, anything else the
dense one. The layer scan CARRIES the stacked leaves: a layer scatters its
tokens at ``[layer, block, offset]`` and gathers ``leaf[layer, tables]``, so
no program slices a layer out of a leaf or stacks one back, and a program
that returns the cache is given it to consume (donated) and writes in place.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

# Marks invalid/pad cache slots: the causal check kv_pos <= q_pos then masks
# them with no separate validity plumbing. A plain int (NOT jnp.int32): a
# module-level device array would initialize the XLA backend at import time,
# breaking jax.distributed.initialize for multi-host trainer processes.
POS_SENTINEL = 2**30


class BlockAllocatorError(ValueError):
    """A ``free()`` that would corrupt the free list: out-of-range block id,
    double-free of an already-free block, or duplicate ids in one call.
    Raised BEFORE any mutation — a rejected free changes nothing — because
    the silent alternative is worse than a crash: a double-freed id gets
    handed out twice and two live slots then scatter into the same physical
    block."""


class BlockAllocator:
    """Host-side REFCOUNTED free-list over the physical block pool.

    The scheduler thread is the only allocator writer, but gauges
    (``/metrics``, gateway stats) read ``free_count`` from HTTP threads —
    hence the lock. Blocks are handed out lowest-id-first and returned to
    the head of the free list, so tests can assert deterministic reuse.

    Refcounts are the copy-on-write substrate: ``alloc`` hands blocks out
    at refcount 1, ``incref`` lets a second owner (another slot's block
    table, a prefix-cache entry) map the same physical block, and ``free``
    DECREMENTS — a block only returns to the free list when its last owner
    lets go. Every owner calls plain ``free`` on release, so the sharing is
    invisible to release paths. ``free()``/``incref()`` validate against
    the refcount table and raise BlockAllocatorError instead of admitting
    a corruption: a double-freed id would get handed out twice and two
    live slots would then scatter into the same physical block."""

    def __init__(self, num_blocks: int):
        if num_blocks < 1:
            raise ValueError(f"num_blocks must be >= 1, got {num_blocks}")
        self.num_blocks = num_blocks
        self._free: List[int] = list(range(num_blocks))
        self._ref = [0] * num_blocks  # 0 = on the free list
        self._lock = threading.Lock()

    @property
    def free_count(self) -> int:
        with self._lock:
            return len(self._free)

    def refcount(self, block: int) -> int:
        """Owners of one block (0 = free) — tests and forensics."""
        with self._lock:
            return self._ref[int(block)]

    def alloc(self, n: int) -> Optional[List[int]]:
        """Reserve ``n`` blocks at refcount 1; None (and no change) when
        the pool can't cover the request — the caller keeps the request
        queued."""
        if n <= 0:
            return []
        with self._lock:
            if n > len(self._free):
                return None
            out, self._free = self._free[:n], self._free[n:]
            for b in out:
                self._ref[b] = 1
            return out

    def _validate(self, blocks: List[int], op: str) -> List[int]:
        ids = [int(b) for b in blocks]
        bad = [b for b in ids if not 0 <= b < self.num_blocks]
        if bad:
            raise BlockAllocatorError(
                f"{op} of out-of-range block id(s) {bad} "
                f"(pool has {self.num_blocks} blocks)")
        if len(set(ids)) != len(ids):
            dupes = sorted({b for b in ids if ids.count(b) > 1})
            raise BlockAllocatorError(
                f"{op} lists block id(s) {dupes} more than once")
        return ids

    def incref(self, blocks: List[int]):
        """Add one owner to each LIVE block (copy-on-write sharing: a new
        slot's table or a prefix-cache entry mapping blocks it did not
        allocate). Increffing a free block is the same corruption class as
        a double-free — rejected before any mutation."""
        if not blocks:
            return
        with self._lock:
            ids = self._validate(blocks, "incref()")
            dead = sorted(b for b in ids if self._ref[b] == 0)
            if dead:
                raise BlockAllocatorError(
                    f"incref() of free block id(s) {dead}: a shared "
                    "mapping must target live blocks")
            for b in ids:
                self._ref[b] += 1

    def free(self, blocks: List[int]):
        """Drop one owner per block; blocks whose last owner left return
        to the free list. Rejected (typed, pre-mutation) on out-of-range
        ids, duplicates in one call, and frees of already-free blocks."""
        if not blocks:
            return
        with self._lock:
            ids = self._validate(blocks, "free()")
            double = sorted(b for b in ids if self._ref[b] == 0)
            if double:
                raise BlockAllocatorError(
                    f"double-free of block id(s) {double}: already on the "
                    "free list")
            released = []
            for b in ids:
                self._ref[b] -= 1
                if self._ref[b] == 0:
                    released.append(b)
            if released:
                self._free = sorted(released) + self._free


def blocks_for_depth(depth: int, block_size: int, overshoot: int = 0,
                     cap_depth: Optional[int] = None) -> int:
    """Blocks a slot must reserve to hold ``depth`` tokens of KV plus
    ``overshoot`` scratch tokens — the admission reserve math.

    ``overshoot`` exists for speculative decoding: a verify-k forward writes
    up to ``k + 1`` tokens beyond the row's live cursor (the pending token
    plus k proposals), and while accepted tokens always land within the
    plain ``depth`` extent, reserving the overshoot keeps REJECTED-lane
    writes physical too — no verify distribution is ever computed over a
    dropped write, and the slot's blocks tell the whole story when
    debugging. ``cap_depth`` (normally ``max_seq_len``) bounds the reserve
    at the block-table width so overshoot can never demand more blocks than
    a table row can hold."""
    total = depth + max(0, overshoot)
    if cap_depth is not None:
        total = min(total, cap_depth)
    return -(-total // block_size)


def kv_leaf_keys(cache: Dict) -> List[str]:
    """The cache's KV leaves: ``k``/``v`` (and the int8 ``k_scale``/
    ``v_scale``) of a model whose layers are all of one kind, or one
    ``k_<kind>``/``v_<kind>`` pool per attention kind (models/hybrid.py).
    Every one is laid out ``[layers, blocks | rows, offset | lane, ...]``, so
    whatever moves a row (insert, extract, trim, copy-on-write, the migration
    wire) moves each alike; ``len``, ``pos`` and ``block_tables`` are shared."""
    return [key for key in cache if key[:1] in ("k", "v")]


def state_leaf_keys(cache: Dict) -> List[str]:
    """The cache's recurrent-state leaves (a linear-attention layer's memory,
    models/hybrid.py): ``[layers of the kind, slots, ...]``, of constant size
    per slot. They are NOT rows: nothing trims them at a cursor or finds them
    through a block table; whatever moves a slot's row moves the slot's
    entry of each whole."""
    return [key for key in cache if key.startswith("state_")]


def state_slot(leaf: jnp.ndarray, slot) -> jnp.ndarray:
    """One slot's entry ``[layers, 1, ...]`` of a state leaf."""
    return jax.lax.dynamic_slice(
        leaf, (0, slot) + (0,) * (leaf.ndim - 2),
        (leaf.shape[0], 1) + leaf.shape[2:])


def state_insert(leaf: jnp.ndarray, slot, entry: jnp.ndarray) -> jnp.ndarray:
    """Put one slot's entry ``[layers, 1, ...]`` back into a state leaf."""
    return jax.lax.dynamic_update_slice(
        leaf, entry.astype(leaf.dtype), (0, slot) + (0,) * (leaf.ndim - 2))


def init_paged_cache(cfg, slots: int, num_blocks: int, block_size: int,
                     blocks_per_slot: int, dtype=jnp.bfloat16,
                     quantize: Optional[str] = None) -> Dict:
    """Block-pool KV cache. ``block_tables`` is ``[slots, blocks_per_slot]``
    int32 (-1 = unallocated); ``len`` is the per-slot linear write cursor;
    ``pos`` records each written token's rope position per (block, offset)."""
    if cfg.hybrid:
        from datatunerx_tpu.models.hybrid import init_paged_cache as init

        return init(cfg, slots, num_blocks, block_size, blocks_per_slot,
                    dtype=dtype, quantize=quantize)
    L = cfg.num_layers
    shape = (L, num_blocks, block_size, cfg.num_kv_heads * cfg.head_dim)
    scales = shape[:-1] + (cfg.num_kv_heads,)
    cache: Dict = {
        "len": jnp.zeros((slots,), jnp.int32),
        "pos": jnp.full((num_blocks, block_size), POS_SENTINEL, jnp.int32),
        "block_tables": jnp.full((slots, blocks_per_slot), -1, jnp.int32),
    }
    if quantize == "int8":
        cache["k"] = jnp.zeros(shape, jnp.int8)
        cache["v"] = jnp.zeros(shape, jnp.int8)
        cache["k_scale"] = jnp.zeros(scales, jnp.float32)
        cache["v_scale"] = jnp.zeros(scales, jnp.float32)
    elif quantize:
        raise ValueError(f"unsupported cache quantization {quantize!r}")
    else:
        cache["k"] = jnp.zeros(shape, dtype)
        cache["v"] = jnp.zeros(shape, dtype)
    return cache


def paged_view_width(cache: Dict) -> int:
    """Linear width of the gathered per-slot view (= dense-row equivalent)."""
    return cache["block_tables"].shape[1] * cache["pos"].shape[1]


def _write_targets(tables: jnp.ndarray, lens: jnp.ndarray, T: int,
                   block_size: int, num_blocks: int):
    """Physical (block, offset) for the next ``T`` linear positions of each
    slot. Invalid targets (slot exhausted, table entry -1) get physical index
    ``num_blocks`` — out of bounds, so the scatter drops them."""
    idx = lens[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]  # [B, T]
    blk, off = idx // block_size, idx % block_size
    nbps = tables.shape[1]
    tbl = jnp.take_along_axis(tables, jnp.clip(blk, 0, nbps - 1), axis=1)
    phys = jnp.where((blk < nbps) & (tbl >= 0), tbl, num_blocks)
    return phys, off


def paged_linear_targets(tables: jnp.ndarray, lin: jnp.ndarray,
                         block_size: int, num_blocks: int,
                         valid: jnp.ndarray):
    """Physical (block, offset) for ARBITRARY linear positions ``lin``
    [B, N] — ``_write_targets`` generalized beyond a cursor-contiguous run
    (tree-verify window compaction moves non-contiguous window columns).
    Positions with ``valid`` False, past the table, or backed by no block
    get physical index ``num_blocks`` so scatters drop them."""
    blk, off = lin // block_size, lin % block_size
    nbps = tables.shape[1]
    tbl = jnp.take_along_axis(tables, jnp.clip(blk, 0, nbps - 1), axis=1)
    phys = jnp.where(valid & (blk >= 0) & (blk < nbps) & (tbl >= 0),
                     tbl, num_blocks)
    return phys, off


def _gather_tables(tables: jnp.ndarray) -> jnp.ndarray:
    """Table with -1 entries clamped to block 0 (gather must stay in
    bounds; the garbage it reads is masked via sentinel positions)."""
    return jnp.where(tables >= 0, tables, 0)


def window_tables(tables: jnp.ndarray, lens: jnp.ndarray, T: int,
                  window: int, block_size: int) -> jnp.ndarray:
    """Of each slot's block table, the columns that can hold a key visible to
    some query of this step through a sliding ``window``: the step's queries
    lie at linear indices ``len .. len+T-1`` and a row's pads lie at its
    left, so linear index and position differ by a constant per row and the
    visible keys lie in ``(len - window, len + T)``. That is
    ``ceil((window + T - 1) / block_size) + 1`` columns from the block of
    ``len - window + 1``; columns past the table read as unallocated (-1).
    The mask is still made from positions. A table no wider is returned whole."""
    nbps = tables.shape[1]
    n = -(-(window + T - 1) // block_size) + 1
    if n >= nbps:
        return tables
    first = jnp.maximum(lens - (window - 1), 0) // block_size
    cols = first[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :]
    tbl = jnp.take_along_axis(tables, jnp.clip(cols, 0, nbps - 1), axis=1)
    return jnp.where(cols < nbps, tbl, -1)


def gathered_positions(pool: jnp.ndarray, tables: jnp.ndarray) -> jnp.ndarray:
    """The linear position view ``[B, columns * block_size]`` of the given
    table columns; lanes backed by no block read as POS_SENTINEL."""
    gathered = pool[_gather_tables(tables)]  # [B, n, bs]
    gathered = jnp.where((tables >= 0)[:, :, None], gathered, POS_SENTINEL)
    return gathered.reshape(tables.shape[0], -1)


def paged_record_positions(cache: Dict, pos_update: jnp.ndarray,
                           gather: bool = True):
    """Scatter the new tokens' rope positions through the block tables and
    return ``(new_pos_pool, kv_positions [B, W])`` — the gathered linear
    position view attention's causal bias masks against. Lanes backed by no
    block read as POS_SENTINEL.

    ``gather=False`` (the Pallas kernel decode path) skips the gathered view
    entirely — the kernel masks against the pos POOL through the block table
    in place — and returns ``(new_pos_pool, None)``."""
    tables, lens, pool = cache["block_tables"], cache["len"], cache["pos"]
    num_blocks, block_size = pool.shape
    phys, off = _write_targets(tables, lens, pos_update.shape[1],
                               block_size, num_blocks)
    new_pool = pool.at[phys, off].set(pos_update)
    if not gather:
        return new_pool, None
    gathered = new_pool[_gather_tables(tables)]  # [B, nbps, bs]
    gathered = jnp.where((tables >= 0)[:, :, None], gathered, POS_SENTINEL)
    return new_pool, gathered.reshape(tables.shape[0], -1)


# --------------------------------------------------------- row import/export
def _row_targets(table_row: jnp.ndarray, width: int, block_size: int,
                 num_blocks: int):
    idx = jnp.arange(width, dtype=jnp.int32)
    blk, off = idx // block_size, idx % block_size
    nbps = table_row.shape[0]
    tbl = table_row[jnp.clip(blk, 0, nbps - 1)]
    phys = jnp.where((blk < nbps) & (tbl >= 0), tbl, num_blocks)
    return phys, off


def paged_insert_row(cache: Dict, slot, table_row: jnp.ndarray,
                     row_cache: Dict) -> Dict:
    """Scatter a dense single-row cache (a prefill/prefix-cache product,
    ``k [L, 1, W, KV * d]``) into the slot's blocks and install its table.
    Positions beyond the row's cursor are POS_SENTINEL in the row already,
    so writing the full width doubles as the block scrub. Linear positions
    past the slot's allocation are dropped (no block — nothing to strand)."""
    num_blocks, block_size = cache["pos"].shape
    W = row_cache["pos"].shape[1]
    phys, off = _row_targets(table_row, W, block_size, num_blocks)
    out = dict(cache)
    out["block_tables"] = jax.lax.dynamic_update_slice(
        cache["block_tables"], table_row[None], (slot, 0))
    for key in kv_leaf_keys(cache):
        out[key] = cache[key].at[:, phys, off].set(row_cache[key][:, 0])
    for key in state_leaf_keys(cache):
        out[key] = state_insert(cache[key], slot, row_cache[key])
    out["pos"] = cache["pos"].at[phys, off].set(row_cache["pos"][0])
    return out


def row_trim(row: Dict, width: int) -> Dict:
    """Trim a dense single-row cache to its first ``width`` linear
    positions — the live prefix of a migrating session (serving/migration
    serializes only real KV, not the row's unwritten tail). Device-side
    slicing, so the host transfer that follows moves ``width`` columns
    instead of the full ``max_seq_len`` row. The inverse (sentinel-padding
    back to full width) lives in ``serving/migration.unpack_kv_row``."""
    width = min(width, row["pos"].shape[1])
    out: Dict = {"len": row.get("len")}
    for key in kv_leaf_keys(row):
        out[key] = row[key][:, :, :width]
    for key in state_leaf_keys(row):
        out[key] = row[key]
    out["pos"] = row["pos"][:, :width]
    return out


def row_pad(row: Dict, width: int) -> Dict:
    """Sentinel-pad a cursor-trimmed dense row cache back to ``width``
    (``row_trim``'s inverse). Stored prefix rows are trimmed to their live cursor (no full
    ``max_seq_len`` gather per insert), but the extension program keeps ONE
    compiled geometry — full width — so padding happens here, once per
    extension, instead of a compile per stored prefix length."""
    W = row["pos"].shape[1]
    if W >= width:
        return row
    out = dict(row)
    pad5 = [(0, 0), (0, 0), (0, width - W), (0, 0), (0, 0)]
    for key in kv_leaf_keys(row):
        out[key] = jnp.pad(row[key], pad5[:row[key].ndim])
    out["pos"] = jnp.pad(row["pos"], [(0, 0), (0, width - W)],
                         constant_values=POS_SENTINEL)
    return out


def paged_install_table(cache: Dict, slot, table_row: jnp.ndarray) -> Dict:
    """Hand ``slot`` the blocks of ``table_row`` ([blocks_per_slot], -1 past
    the last one) for a prompt that is prefilled in place: install the row,
    scrub the blocks' recycled positions to the sentinel (chunked prefill
    shows the whole table to attention before every lane is written) and
    rewind the slot's cursor. ONE program whatever the number of blocks: done
    eagerly, each of the three updates is a handful of small programs and the
    scrub compiles them anew for every block count a workload has."""
    out = dict(cache)
    out["block_tables"] = jax.lax.dynamic_update_slice(
        cache["block_tables"], table_row[None], (slot, 0))
    # an unused column points past the pool and is dropped
    blocks = jnp.where(table_row >= 0, table_row, cache["pos"].shape[0])
    out["pos"] = cache["pos"].at[blocks].set(POS_SENTINEL, mode="drop")
    out["len"] = cache["len"].at[slot].set(0)
    return out


def paged_clear_rows(block_tables: jnp.ndarray, slots: jnp.ndarray) -> jnp.ndarray:
    """The table with the rows of ``slots`` cleared to ``-1``: what a
    scheduler pass gives up, in ONE program whatever the count. ``slots`` is
    ``int32[number of slots]``, padded with an index past the table, which is
    dropped. A function of the table alone (one array in, one out, donated):
    eagerly, ``.at[slot].set(-1)`` is a handful of small programs a slot."""
    return block_tables.at[slots].set(-1, mode="drop")


def paged_copy_block(cache: Dict, src, dst, keep) -> Dict:
    """Copy one physical block (K/V pools, int8 scales, pos row) onto
    another — the copy-on-write primitive. Position lanes at offset >=
    ``keep`` are scrubbed to POS_SENTINEL in the destination, so copying a
    partially-written tail block never leaks the source's later tokens to
    the new owner's attention (decode only ever appends at the cursor, so
    this copy is the at-most-once COW event per shared tail block)."""
    out = dict(cache)
    block_size = cache["pos"].shape[1]
    for key in kv_leaf_keys(cache):
        out[key] = cache[key].at[:, dst].set(cache[key][:, src])
    row = jnp.where(jnp.arange(block_size, dtype=jnp.int32) < keep,
                    cache["pos"][src], POS_SENTINEL)
    out["pos"] = cache["pos"].at[dst].set(row)
    return out


def paged_extract_row(cache: Dict, slot, cursor, *,
                      width: Optional[int] = None) -> Dict:
    """Gather a slot's blocks back into a dense single-row cache (the
    prefix-cache / migration-wire storage format). The inverse of
    ``paged_insert_row``; ``cursor`` becomes the row's scalar write cursor
    so suffix extension picks up exactly where the prompt ended.

    ``width`` (static under jit) trims the gather to the first
    ``ceil(width / block_size)`` blocks — a short prefix then moves
    ``width`` columns of HBM instead of a full ``max_seq_len`` row, which
    is what the prefix-cache export and migration paths pay per session.
    Default None keeps the full-table gather (width = blocks_per_slot ×
    block_size = max_seq_len)."""
    nbps_total = cache["block_tables"].shape[1]
    block_size = cache["pos"].shape[1]
    nbps = nbps_total if width is None else max(
        1, min(nbps_total, -(-int(width) // block_size)))
    table_row = jax.lax.dynamic_slice(
        cache["block_tables"], (slot, 0), (1, nbps))[0]
    tbl = _gather_tables(table_row)
    W = nbps * block_size
    row: Dict = {"len": jnp.asarray(cursor, jnp.int32)}
    for key in kv_leaf_keys(cache):
        leaf = cache[key]  # [L, NB, bs, ...] -> [L, 1, W, ...]
        row[key] = leaf[:, tbl].reshape(
            (leaf.shape[0], 1, W) + leaf.shape[3:])
    for key in state_leaf_keys(cache):
        row[key] = state_slot(cache[key], slot)
    pos = cache["pos"][tbl]  # [nbps, bs]
    pos = jnp.where((table_row >= 0)[:, None], pos, POS_SENTINEL)
    row["pos"] = pos.reshape(1, W)
    return row
