"""Device hash-table tests (the state backbone of agg/join/mview)."""

import jax.numpy as jnp
import numpy as np
import pytest

from risingwave_tpu.state.hash_table import HashTable


def _i64(vals):
    return jnp.asarray(np.asarray(vals, np.int64))


def _valid(n, cap=None):
    cap = cap or n
    v = np.zeros(cap, np.bool_)
    v[:n] = True
    return jnp.asarray(v)


def test_insert_and_lookup():
    t = HashTable.create([jnp.zeros((1,), jnp.int64)], 64)
    keys = [_i64([10, 20, 30, 10])]
    t, slots, inserted, overflow = t.lookup_or_insert(keys, _valid(4))
    s = np.asarray(slots)
    assert not np.asarray(overflow).any()
    # duplicate key resolves to the same slot
    assert s[0] == s[3]
    assert len({s[0], s[1], s[2]}) == 3
    # exactly one insert for the duplicated key
    assert np.asarray(inserted).sum() == 3
    assert int(t.count()) == 3

    slots2, found = t.lookup([_i64([20, 99, 10, 0])], _valid(3, 4))
    f = np.asarray(found)
    assert list(f) == [True, False, True, False]
    assert np.asarray(slots2)[0] == s[1]


def test_collision_heavy_small_table():
    # 16 slots, 12 keys — forced probing
    t = HashTable.create([jnp.zeros((1,), jnp.int64)], 16)
    keys = np.arange(12, dtype=np.int64) * 1000
    t, slots, _, overflow = t.lookup_or_insert([_i64(keys)], _valid(12))
    assert not np.asarray(overflow).any()
    assert int(t.count()) == 12
    # every key findable, distinct slots
    slots2, found = t.lookup([_i64(keys)], _valid(12))
    assert np.asarray(found).all()
    assert len(set(np.asarray(slots2).tolist())) == 12
    assert (np.asarray(slots2) == np.asarray(slots)).all()


def test_overflow_reported():
    t = HashTable.create([jnp.zeros((1,), jnp.int64)], 4)
    keys = np.arange(8, dtype=np.int64)
    t, _, _, overflow = t.lookup_or_insert([_i64(keys)], _valid(8))
    assert np.asarray(overflow).sum() == 4
    assert int(t.count()) == 4


def test_tombstone_preserves_probe_chain():
    t = HashTable.create([jnp.zeros((1,), jnp.int64)], 8)
    # insert keys until some collide, then delete an early chain member
    keys = np.asarray([1, 9, 17, 25], np.int64)  # likely same bucket mod 8
    t, slots, _, _ = t.lookup_or_insert([_i64(keys)], _valid(4))
    s = np.asarray(slots)
    # delete the first key's slot
    t = t.clear_slots(jnp.asarray([s[0]], jnp.int32), jnp.asarray([True]))
    # the rest must still be findable (chain not broken)
    slots2, found = t.lookup([_i64(keys)], _valid(4))
    f = np.asarray(found)
    assert list(f) == [False, True, True, True]
    # re-insert the deleted key: must not duplicate others
    t, slots3, ins, _ = t.lookup_or_insert([_i64([1])], _valid(1))
    assert np.asarray(ins)[0]
    slots4, found4 = t.lookup([_i64(keys)], _valid(4))
    assert np.asarray(found4).all()


def test_multi_column_and_string_keys():
    from risingwave_tpu.common.chunk import encode_strings, StrCol

    data, lens = encode_strings(["abc", "abd", "abc"], 8)
    scol = StrCol(jnp.asarray(data), jnp.asarray(lens))
    icol = _i64([1, 1, 1])
    t = HashTable.create(
        [jnp.zeros((1,), jnp.int64),
         StrCol(jnp.zeros((1, 8), jnp.uint8), jnp.zeros((1,), jnp.int32))],
        32,
    )
    t, slots, _, _ = t.lookup_or_insert([icol, scol], _valid(3))
    s = np.asarray(slots)
    assert s[0] == s[2] and s[0] != s[1]


def test_rehash_reclaims_tombstones():
    t = HashTable.create([jnp.zeros((1,), jnp.int64)], 16)
    keys = np.arange(10, dtype=np.int64)
    t, slots, _, _ = t.lookup_or_insert([_i64(keys)], _valid(10))
    t = t.clear_slots(slots, jnp.asarray([True] * 5 + [False] * 5))
    assert int(t.tombstone_count()) == 5
    fresh, moved = t.rehashed()
    assert int(fresh.tombstone_count()) == 0
    assert int(fresh.count()) == 5
    slots2, found = fresh.lookup([_i64(keys)], _valid(10))
    assert list(np.asarray(found)) == [False] * 5 + [True] * 5


def _mix(keys):
    """A fixed 64-bit hash of int64 keys (splitmix64's finaliser): the
    test hands the table its hashes, so the reference needs no other."""
    with np.errstate(over="ignore"):
        x = keys.astype(np.uint64) + np.uint64(0x9E3779B97F4A7C15)
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return x ^ (x >> np.uint64(31))


def _plain_probe(occupied, tombstone, stored, keys, home, valid, insert,
                 max_iters):
    """Linear probing in lockstep rounds, one row at a time: each open
    row looks at its next slot; a match resolves it; among the rows
    that want one true-empty slot the lowest row claims it, and the
    others look at it again next round; an occupied slot of another key
    or a tombstone moves a row on.  Returns ``(occupied, stored, slots,
    inserted | found, overflow, rounds)``."""
    size = occupied.size
    occupied, stored = occupied.copy(), stored.copy()
    n = keys.size
    done = ~valid
    slots = np.full(n, size, np.int64)
    won = np.zeros(n, np.bool_)
    off = np.zeros(n, np.int64)
    rounds = 0
    while not done.all() and rounds < max_iters:
        rounds += 1
        cand = (home + off) % size
        occ = occupied[cand]
        tomb = tombstone[cand] & ~occ
        match = occ & (stored[cand] == keys)
        for r in np.flatnonzero(~done):
            if match[r]:
                slots[r], done[r] = cand[r], True
            elif not occ[r] and not tomb[r]:
                if not insert:
                    done[r] = True
                elif not occupied[cand[r]]:
                    occupied[cand[r]], stored[cand[r]] = True, keys[r]
                    slots[r], won[r], done[r] = cand[r], True, True
            else:
                off[r] += 1
    overflow = ~done
    if insert:
        return occupied, stored, slots, won, overflow, rounds
    return occupied, stored, slots, valid & done & (slots < size), \
        overflow, rounds


@pytest.mark.parametrize("insert", [True, False], ids=["insert", "lookup"])
def test_narrowed_probe_equals_plain_linear_probe(insert):
    """A chunk of 8,192 rows, twice the widest straggler tile, into a
    table at load 0.70 with tombstones: present keys, deleted keys,
    absent keys repeated within the chunk, rows not valid.  The table
    has 512 slots, no more than the narrowest tile's claim scratch (4 x
    128), so the device's claim race is exact at every width, as the
    reference's is."""
    size, cap = 512, 8192
    rng = np.random.default_rng(40)
    pool = rng.choice(1 << 40, 390, replace=False).astype(np.int64)
    present, gone, absent = pool[:360], pool[:40], pool[360:]
    t = HashTable.create([jnp.zeros((1,), jnp.int64)], size)
    t, slots, _, over = t.lookup_or_insert(
        [_i64(present)], _valid(360), hashes=jnp.asarray(_mix(present)))
    assert not np.asarray(over).any()
    t = t.clear_slots(slots, jnp.asarray(np.arange(360) < 40))
    occupied = np.asarray(t.occupied)
    tombstone = np.asarray(t.tombstone)
    stored = np.asarray(t.key_cols[0])
    assert (occupied | tombstone).sum() / size == pytest.approx(0.70, 0.01)

    kinds = rng.choice(3, cap, p=[0.6, 0.1, 0.3])
    keys = np.where(kinds == 0, rng.choice(present[40:], cap),
                    np.where(kinds == 1, rng.choice(gone, cap),
                             rng.choice(absent, cap)))
    valid = rng.random(cap) < 0.9
    hashes = _mix(keys)
    want = _plain_probe(occupied, tombstone, stored, keys,
                        (hashes % np.uint64(size)).astype(np.int64), valid,
                        insert, max_iters=size + 2)
    w_occupied, w_stored, w_slots, w_flag, w_over, w_rounds = want

    args = ([_i64(keys)], jnp.asarray(valid), jnp.asarray(hashes))
    if insert:
        t2, slots, flag, over = t.lookup_or_insert(*args)
        assert (np.asarray(t2.occupied) == w_occupied).all()
        got = np.asarray(t2.key_cols[0])
        assert (got[w_occupied] == w_stored[w_occupied]).all()
        n_over = int(np.asarray(over).sum())
    else:
        slots, flag, n_over = t.lookup_counted(*args)
    assert (np.asarray(slots) == w_slots).all()
    assert (np.asarray(flag) == w_flag).all()
    assert int(n_over) == int(w_over.sum()) == 0

    # the tail the tiles exist for was there, and the chunk's width ran
    # only the first few rounds of it
    _, _, _, _, (wide, tile) = t._probe(*args[:2], insert=insert,
                                        hashes=args[2])
    assert int(wide) + int(tile) == w_rounds > 8
    assert int(wide) * 3 <= w_rounds
