"""serving/kv_pool.py alone: a toy cache dict, no model, no engine thread.

The contract the pool's docstring states, held at the pool: scrub before a
table reveals, clear the row before the allocator re-issues, a failed install
returns what it took, a shared block goes home with its last owner.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from datatunerx_tpu.ops.paged_attention import POS_SENTINEL, blocks_for_depth
from datatunerx_tpu.serving.kv_pool import KVPool

SLOTS, BS, MAX_LEN, BLOCKS = 3, 4, 32, 12  # 8 table columns a slot


def make(overshoot=0, advance=None, kv_blocks=BLOCKS, slots=SLOTS):
    """A pool over a toy cache whose every position is 7 (a recycled block's
    stale content) and whose leaf ``k`` nothing here may touch."""
    holder = {}
    pool = KVPool(slots, MAX_LEN, BS, kv_blocks, overshoot=overshoot,
                  advance=advance, cache=lambda: holder["cache"])
    holder["cache"] = {
        "block_tables": jnp.full((slots, pool.blocks_per_slot), -1, jnp.int32),
        "pos": jnp.full((pool.total, BS), 7, jnp.int32),
        "k": jnp.arange(pool.total * BS, dtype=jnp.float32),
    }
    return pool, holder


def table(holder, slot):
    return np.asarray(holder["cache"]["block_tables"][slot]).tolist()


def pos(holder, blocks):
    return np.asarray(holder["cache"]["pos"])[list(blocks)]


class Writes:
    """A cache dict's leaf writes, in order, with the allocator's free count at
    each: what "before" and "only then" are judged by."""

    def __init__(self, pool, holder):
        self.log = []
        outer = self

        class Cache(dict):
            def __setitem__(self, key, value):
                outer.log.append((key, pool.free))
                super().__setitem__(key, value)

        holder["cache"] = Cache(holder["cache"])

    def keys(self):
        return [key for key, _ in self.log]


def test_geometry_is_refused_as_the_engine_refused_it():
    with pytest.raises(ValueError, match="kv_block_size 5 must divide"):
        KVPool(2, 32, 5, None, overshoot=0, advance=None, cache=dict)
    with pytest.raises(ValueError, match="cannot hold one full-length"):
        KVPool(2, 32, 4, 7, overshoot=0, advance=None, cache=dict)
    pool = KVPool(2, 32, 4, None, overshoot=0, advance=None, cache=dict)
    assert (pool.total, pool.blocks_per_slot) == (16, 8)  # dense parity


@pytest.mark.parametrize("overshoot", [0, 5])
@pytest.mark.parametrize("advance", [None, 6])
def test_reserve_applies_overshoot_cap_and_the_overcommit_rule(overshoot,
                                                               advance):
    pool, _ = make(overshoot=overshoot, advance=advance)
    for cursor, max_new in ((3, 2), (10, 9), (9, 40), (30, 2)):
        depth = cursor + (max_new if advance is None
                          else min(max_new, advance))
        assert pool.reserve_depth(cursor, max_new) == depth
        free = pool.free
        blocks = pool.reserve(cursor, max_new)
        want = blocks_for_depth(depth, BS, overshoot=overshoot,
                                cap_depth=MAX_LEN)
        assert len(blocks) == want <= pool.blocks_per_slot
        assert pool.free == free - want
        pool.free_entry(blocks)
    pool.take(pool.free - 1)
    assert pool.reserve(0, MAX_LEN) is None
    assert pool.free == 1  # a refusal takes nothing


def test_row_format_and_occupy_records_blocks_and_demand():
    pool, holder = make(overshoot=3, advance=4)
    blocks = pool.reserve(5, 20)  # overcommitted: 5 + 4 (+3) tokens
    assert len(blocks) == 3
    with pool.occupy(0, blocks, 5 + 20) as row:
        assert np.asarray(row).tolist() == blocks + [-1] * 5
        assert row.dtype == jnp.int32
        assert pool.held(0) == [] and pool.overcommit_ratio == 0.0
    assert pool.held(0) == blocks
    # the eager engine's reserve for the same session: ceil((25 + 3) / 4)
    assert pool.overcommit_ratio == round(7 / BLOCKS, 4)
    assert table(holder, 0) == [-1] * 8  # the install is the caller's program


def test_demand_and_ratio_after_admit_growth_and_release():
    pool, _ = make(advance=4)
    for slot, (cursor, max_new) in enumerate(((4, 28), (8, 24))):
        with pool.occupy(slot, pool.reserve(cursor, max_new),
                         cursor + max_new):
            pass
    assert (pool.total, pool.free) == (12, 7)
    assert pool.overcommit_ratio == round(16 / 12, 4)  # > 1: overcommitted
    assert pool.grow(0, 4 + 9) == 2
    assert pool.overcommit_ratio == round(16 / 12, 4)  # growth is not demand
    assert pool.free == 5
    pool.release([0])
    assert pool.overcommit_ratio == round(8 / 12, 4)
    pool.release([1], note_session=False)
    assert pool.overcommit_ratio == 0.0 and pool.free == pool.total
    assert list(pool.session_blocks) == [4]  # a preemption is no session's end


def test_grow_scrubs_recycled_blocks_before_the_row_names_them():
    pool, holder = make(advance=4)
    blocks = pool.reserve(2, 30)
    with pool.occupy(1, blocks, 32) as row:
        pool.scrub(blocks)
        pool.set_row(1, row)
    assert (pos(holder, pool.held(1)) == POS_SENTINEL).all()
    writes = Writes(pool, holder)
    assert pool.grow(1, 6) == 0 and writes.log == []  # covered: no device op
    assert pool.grow(1, 17) == 3
    assert writes.keys() == ["pos", "block_tables"]  # scrub, THEN reveal
    new = pool.held(1)[2:]
    assert (pos(holder, new) == POS_SENTINEL).all()
    assert table(holder, 1) == pool.held(1) + [-1] * 3
    rest = [b for b in range(BLOCKS) if b not in pool.held(1)]
    assert (pos(holder, rest) == 7).all()  # only the new blocks were scrubbed
    assert pool.grow(1, 100) == 3  # capped at the table's width
    assert len(pool.held(1)) == pool.blocks_per_slot


def test_grow_refused_takes_nothing_and_writes_nothing():
    pool, holder = make(advance=4)
    for slot in (0, 1):
        with pool.occupy(slot, pool.reserve(0, 20), 20):
            pass
    pool.take(pool.free - 1)  # an entry holds all but one
    writes = Writes(pool, holder)
    held = list(pool.held(0))
    assert pool.grow(0, 16) is None
    assert pool.held(0) == held and pool.free == 1 and writes.log == []


@pytest.mark.parametrize("note_session", [True, False], ids=["end", "preempt"])
@pytest.mark.parametrize("given", [[2], [3, 0, 1], [0, 1, 2, 3]],
                         ids=["1", "3", "all"])
def test_release_clears_the_row_and_only_then_frees(given, note_session):
    pool, holder = make(slots=4, kv_blocks=16)
    sizes = {0: 10, 1: 3, 2: 12, 3: 5}  # 3 + 1 + 3 + 2 blocks; slot 3 shares
    for slot in (0, 1, 2):
        with pool.occupy(slot, pool.reserve(0, sizes[slot]), sizes[slot]) as row:
            pool.set_row(slot, row)
    with pool.occupy(3, pool.reserve(4, 1, shared=pool.held(0)[:1]), 5) as row:
        pool.set_row(3, row)
    before = {slot: table(holder, slot) for slot in range(4)}
    held = {slot: list(pool.held(slot)) for slot in range(4)}
    out = 16 - pool.free
    assert out == 3 + 1 + 3 + 1 and held[3][0] == held[0][0]
    table_before = holder["cache"]["block_tables"]
    writes = Writes(pool, holder)
    pool.release(given, note_session)
    # ONE write for the whole list, made while every block was still out
    assert writes.log == [("block_tables", 16 - out)]
    assert table_before.is_deleted()  # the program consumed the table it was given
    for slot in range(4):
        assert table(holder, slot) == ([-1] * 8 if slot in given else before[slot])
        assert pool.held(slot) == ([] if slot in given else held[slot])
    # a block two slots share goes home with the second of them
    kept = {b for slot in range(4) if slot not in given for b in held[slot]}
    assert pool.free == 16 - len(kept)
    assert list(pool.session_blocks) == (
        [len(held[slot]) for slot in given] if note_session else [])
    # an empty slot in a list is skipped; a list of only empty slots, and an
    # empty list, write nothing and free nothing
    rest = [slot for slot in range(4) if slot not in given]
    if rest:
        pool.release([given[0], rest[0]], note_session)
        assert len(writes.log) == 2 and table(holder, rest[0]) == [-1] * 8
        assert len(pool.session_blocks) == (len(given) + 1 if note_session else 0)
    done, free = len(writes.log), pool.free
    pool.release(given)
    pool.release([])
    assert (len(writes.log), pool.free) == (done, free)
    assert len(pool.session_blocks) == (len(given) + len(rest[:1]) if note_session else 0)
    assert np.asarray(holder["cache"]["k"]).tolist() == list(range(64))


@pytest.mark.parametrize("shared", [False, True], ids=["own", "own+shared"])
def test_an_exception_inside_occupy_returns_what_it_took(shared):
    pool, _ = make()
    entry = pool.take(2) if shared else []  # a prefix entry's blocks
    with pool.occupy(0, pool.reserve(0, 8), 8):
        pass
    before = (pool.free, list(pool.held(0)), list(pool.held(1)),
              pool.overcommit_ratio,
              [pool.allocator.refcount(b) for b in range(BLOCKS)])
    blocks = pool.reserve(9, 7, shared=entry)
    assert len(blocks) == 4 and blocks[:len(entry)] == entry
    assert all(pool.allocator.refcount(b) == 2 for b in entry)
    with pytest.raises(RuntimeError, match="the install failed"):
        with pool.occupy(1, blocks, 16):
            raise RuntimeError("the install failed")
    assert before == (pool.free, pool.held(0), pool.held(1),
                      pool.overcommit_ratio,
                      [pool.allocator.refcount(b) for b in range(BLOCKS)])


def test_a_shared_block_survives_its_first_owner_and_returns_on_the_last():
    pool, _ = make()
    with pool.occupy(0, pool.reserve(0, 10), 10):  # the donor: 3 blocks
        pass
    donor = list(pool.held(0))
    # the entry shares the donor's two full blocks and owns a copied tail
    entry = pool.take(1, shared=donor[:2])
    assert entry[:2] == donor[:2] and entry[2] not in donor
    # a second session maps the entry's full blocks, with a block of its own
    with pool.occupy(1, pool.reserve(8, 4, shared=entry[:2]), 12):
        pass
    assert [pool.allocator.refcount(b) for b in donor[:2]] == [3, 3]
    assert pool.free == BLOCKS - 5
    pool.release([0])
    assert pool.free == BLOCKS - 4  # the donor's own third block went home
    pool.release([1])
    assert [pool.allocator.refcount(b) for b in donor[:2]] == [1, 1]
    assert pool.free == BLOCKS - 3  # the entry's blocks are still out
    pool.free_entry(entry)
    assert pool.free == pool.total
    assert pool.take(pool.total + 1, shared=()) is None


@pytest.mark.parametrize("fail", [False, True], ids=["success", "exception"])
def test_mounted_restores_the_saved_row(fail):
    pool, holder = make()
    with pool.occupy(1, pool.reserve(0, 6), 6) as row:
        pool.set_row(1, row)
    own = table(holder, 1)
    entry = pool.take(3)
    try:
        with pool.mounted(1, entry) as row:
            assert np.asarray(row).tolist() == entry + [-1] * 5
            # the caller's install may hand the engine a NEW dict: the row is
            # put back into whichever the engine holds on the way out
            holder["cache"] = dict(holder["cache"])
            pool.set_row(1, row)
            assert table(holder, 1)[:3] == entry
            if fail:
                raise KeyError("extract failed")
    except KeyError:
        assert fail
    assert table(holder, 1) == own
    assert pool.held(1) == own[:2] and pool.free == BLOCKS - 5  # no list moved
