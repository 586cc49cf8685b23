"""The paged KV block pool's one owner (host side).

``BatchedEngine`` builds a ``KVPool`` when ``kv_block_size > 0`` and asks it
how a slot comes to hold blocks and gives them back: the refcounted
``BlockAllocator`` (ops/paged_attention.py), the reserve math (spec overshoot,
``max_seq_len`` cap, the overcommit rule), each slot's blocks and eager
demand, the table-row format, and the device writes the host makes to
``cache["block_tables"]`` and ``cache["pos"]``: two EAGER ones (scrub, set a
slot's row) and ONE jitted, donated program a pass that clears the rows of
every slot the pass gives up (``release``; an eager clear was 2.8 ms of an
idle chip a finished request). Policy stays with the scheduler: who is
admitted or preempted, when growth runs, what a migration payload holds. The
pool writes the engine's cache dict in place, reached through the getter it
was built with; the jitted programs keep taking and returning
``engine._cache`` whole.

The contract, which the methods' order of operations enforces (two slots that
scatter into one physical block corrupt both sessions silently):

- recycled blocks are scrubbed to ``POS_SENTINEL`` BEFORE any table reveals
  them to attention (``grow``; ``scrub`` ahead of an install whose program
  does not scrub the blocks itself);
- a slot's row is cleared BEFORE its blocks return to the allocator
  (``release``, for every slot of its list): a masked decode write from the
  slot must never land in a block already re-issued;
- a failed install returns what it took and leaves the slot's lists as they
  were (``occupy``: own blocks freed, shared ones decref'd);
- a shared block is incref'd exactly once an owner (``take``), and every owner
  lets go through the allocator's plain ``free``.
"""

from __future__ import annotations

import collections
import contextlib
from typing import Callable, Dict, Iterator, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from datatunerx_tpu.ops.paged_attention import (
    POS_SENTINEL,
    BlockAllocator,
    blocks_for_depth,
    paged_clear_rows,
)

# the table is consumed and written in place, as the engine's programs do
# with the cache they return
_clear_rows = jax.jit(paged_clear_rows, donate_argnums=(0,))


class KVPool:
    def __init__(self, slots: int, max_seq_len: int, block_size: int,
                 kv_blocks: Optional[int], *, overshoot: int,
                 advance: Optional[int], cache: Callable[[], Dict]):
        """``overshoot``: tokens one speculative verify step writes past a
        row's cursor (0 without a draft), reserved on top of every depth
        (``blocks_for_depth``). ``advance``: the most lanes one scheduler tick
        consumes per slot where the engine overcommits, None to reserve a
        request's whole extent eagerly. ``cache`` returns the engine's
        current cache dict."""
        if max_seq_len % block_size:
            raise ValueError(
                f"kv_block_size {block_size} must divide "
                f"max_seq_len {max_seq_len}")
        self.block_size = block_size
        self.max_seq_len = max_seq_len
        self.blocks_per_slot = max_seq_len // block_size
        total_blocks = int(kv_blocks or slots * self.blocks_per_slot)
        if total_blocks < self.blocks_per_slot:
            raise ValueError(
                f"kv_blocks {total_blocks} cannot hold one full-length "
                f"request ({self.blocks_per_slot} blocks of "
                f"{block_size})")
        self.allocator = BlockAllocator(total_blocks)
        self._overshoot = overshoot
        self._advance = advance
        self._cache = cache
        self._held: List[List[int]] = [[] for _ in range(slots)]
        # per-slot EAGER-equivalent reserve (what the overcommit-off engine
        # would hold): the dtx_serving_kv_overcommit_ratio numerator
        self._demand: List[int] = [0] * slots
        # each finished session's physical block footprint (== its peak:
        # tables only ever grow)
        self.session_blocks: "collections.deque[int]" = \
            collections.deque(maxlen=4096)

    # dtxlint: hot-begin -- the scheduler calls everything below through
    # ``engine._pool``, an attribute the call graph does not follow
    # ------------------------------------------------------------- gauges
    # read from any thread, racy like every other scrape-path read
    @property
    def total(self) -> int:
        return self.allocator.num_blocks

    @property
    def free(self) -> int:
        return self.allocator.free_count

    @property
    def overcommit_ratio(self) -> float:
        """Live sessions' EAGER-equivalent block demand over the physical
        pool: > 1.0 means more logical reserve is admitted than HBM holds."""
        return round(sum(self._demand) / max(1, self.allocator.num_blocks), 4)

    def held(self, slot: int) -> List[int]:
        """The blocks ``slot``'s table names, in table order (read-only)."""
        return self._held[slot]

    # ------------------------------------------------------------ reserve
    def reserve_depth(self, cursor: int, max_new: int) -> int:
        """Token depth admission reserves blocks for: the full decode
        extent eagerly, or the context plus one scheduler tick's advance
        when overcommitted (``grow`` keeps the table ahead of the cursor
        from there; the spec overshoot rides on top in ``_blocks``)."""
        if self._advance is not None:
            return cursor + min(max_new, self._advance)
        return cursor + max_new

    def _blocks(self, depth: int) -> int:
        return blocks_for_depth(depth, self.block_size,
                                overshoot=self._overshoot,
                                cap_depth=self.max_seq_len)

    def take(self, n: int, shared: Sequence[int] = ()) -> Optional[List[int]]:
        """``shared`` (live blocks another owner allocated: copy-on-write)
        followed by ``n`` fresh blocks, one new reference each; None, and
        nothing changed, when the pool cannot cover the fresh ones."""
        own = self.allocator.alloc(n)
        if own is None:
            return None
        shared = list(shared)
        self.allocator.incref(shared)
        return shared + own

    def reserve(self, cursor: int, max_new: int,
                shared: Sequence[int] = ()) -> Optional[List[int]]:
        """The blocks a session at ``cursor`` with ``max_new`` tokens to go
        is admitted with, the leading ``shared`` ones mapped and not
        allocated; None when the pool is exhausted (the request stays
        queued with nothing held)."""
        return self.take(self._blocks(self.reserve_depth(cursor, max_new))
                         - len(shared), shared)

    def free_entry(self, blocks: Sequence[int]):
        """Drop one reference to each block of an owner that is no slot (a
        prefix-cache entry, blocks taken for an install that never began).
        Runs on whichever thread evicted: the allocator's lock covers it."""
        self.allocator.free(list(blocks))

    # ------------------------------------------------------------- a slot
    def row(self, blocks: Sequence[int]) -> jnp.ndarray:
        """The table row that names ``blocks``: ``-1`` past the last one."""
        row = np.full((self.blocks_per_slot,), -1, np.int32)
        row[: len(blocks)] = blocks
        return jnp.asarray(row)

    @contextlib.contextmanager
    def occupy(self, slot: int, blocks: List[int],
               extent: int) -> Iterator[jnp.ndarray]:
        """``with pool.occupy(slot, blocks, cursor + max_new) as row:`` wraps
        the caller's install of taken ``blocks`` into ``slot``. An exception
        inside returns every reference and leaves the slot's lists as they
        were; on success the slot holds the blocks and its eager demand is
        what ``extent`` tokens would reserve."""
        try:
            yield self.row(blocks)
        except Exception:
            self.allocator.free(blocks)
            raise
        self._held[slot] = blocks
        self._demand[slot] = self._blocks(extent)

    def scrub(self, blocks: Sequence[int]):
        """Recycled blocks' positions to the sentinel: BEFORE a table names
        them, wherever the install's own program does not scrub. The list is
        padded with its last block to a table row's length, so that the eager
        scatter is ONE program whatever the count (a suffix of any length, a
        tick's growth): a count of its own compiled one each, mid-traffic."""
        if not len(blocks):
            return
        ids = np.full((-(-len(blocks) // self.blocks_per_slot) * self.blocks_per_slot,),
                      blocks[-1], np.int32)
        ids[: len(blocks)] = blocks
        cache = self._cache()
        cache["pos"] = cache["pos"].at[jnp.asarray(ids)].set(POS_SENTINEL)

    def set_row(self, slot: int, row):
        """One eager write of ``slot``'s whole table row."""
        cache = self._cache()
        cache["block_tables"] = cache["block_tables"].at[slot].set(row)

    def grow(self, slot: int, depth: int) -> Optional[int]:
        """Extend ``slot``'s table to cover ``depth`` tokens (plus the spec
        overshoot, capped at the table's width): scrub the new blocks, then
        reveal them. The number of blocks added (0: covered already, nothing
        written), or None when the pool cannot cover them."""
        held = self._held[slot]
        need = self._blocks(depth) - len(held)
        if need <= 0:
            return 0
        new = self.allocator.alloc(need)
        if new is None:
            return None
        held.extend(new)
        self.scrub(new)
        self.set_row(slot, self.row(held))
        return need

    def release(self, slots: Sequence[int], note_session: bool = True):
        """Give the blocks of every slot of ``slots`` (what one scheduler
        pass gives up) back: clear their rows FIRST, in one program for all
        of them, then free. A slot that holds nothing is skipped, and a list
        of such slots writes nothing. ``note_session`` records each count as
        a finished session's footprint (preemptions pass False: the session
        isn't over)."""
        given = []
        for slot in slots:
            self._demand[slot] = 0
            blocks, self._held[slot] = self._held[slot], []
            if not blocks:
                continue
            if note_session:
                self.session_blocks.append(len(blocks))
            given.append((slot, blocks))
        if not given:
            return
        n = len(self._held)
        rows = np.full((n,), n, np.int32)  # past the table: dropped
        rows[: len(given)] = [slot for slot, _ in given]
        cache = self._cache()
        cache["block_tables"] = _clear_rows(cache["block_tables"], rows)
        # slot by slot: two of them may share a block, one reference each
        for _, blocks in given:
            self.allocator.free(blocks)

    @contextlib.contextmanager
    def mounted(self, slot: int,
                blocks: Sequence[int]) -> Iterator[jnp.ndarray]:
        """Lend a FREE slot's table row (nothing reads it) to ``blocks`` an
        entry owns: yields their row for the caller to install and puts the
        slot's own back on the way out, whatever happened inside."""
        saved = self._cache()["block_tables"][slot]
        try:
            yield self.row(blocks)
        finally:
            self.set_row(slot, saved)
