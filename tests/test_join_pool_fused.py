"""Fused (hash, rank) pool join: changelog equivalence vs the dense
bucket path, probe-count guarantees, bump allocation, and compaction.

The PR-2 tentpole rebuilt the append-only pool side around ONE fused
(key-hash, rank) table + a bump-allocated row pool (see
stream/hash_join.py PoolSideState).  The dense bucket path is the
unchanged reference implementation, so these tests pin the new design
to it: identical folded changelogs across the join matrix, including
burst drains (tiny emission windows) and outer-join retraction
cascades driven from a retractable dense side.
"""

from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import risingwave_tpu  # noqa: F401
from risingwave_tpu.common.chunk import Chunk
from risingwave_tpu.common.types import DataType, Schema
from risingwave_tpu.expr.node import col
from risingwave_tpu.stream.hash_join import HashJoinExecutor, JoinClean

from tests.test_join_matrix import fold

L = Schema.of(("k", DataType.INT64), ("a", DataType.INT64))
R = Schema.of(("k", DataType.INT64), ("b", DataType.INT64))


def _chunk(schema, rows, ops):
    names = [f.name for f in schema]
    txt = "I I\n" + "\n".join(
        f"{'+' if o == 0 else '-'} {r[0]} {r[1]}"
        for r, o in zip(rows, ops)
    )
    return Chunk.from_pretty(txt, names=names)


def _executor(storage, join_type, out_capacity):
    kw = dict(
        table_size=256, bucket_cap=64, out_capacity=out_capacity,
        join_type=join_type,
    )
    if storage == "pool":
        kw.update(
            left_storage="pool", right_storage="pool",
            left_pool_size=2048, right_pool_size=2048,
        )
    return HashJoinExecutor(L, R, [col("k")], [col("k")], **kw)


def _drain_all(j, st, chunk, side, acc):
    st, pending = j.apply_begin(st, chunk, side)
    build = j.build_rows_of(st, side)
    total = int(pending.total)
    w = 0
    while w == 0 or w * j.out_capacity < total:
        out, probe_bound = j.emit_window(build, pending, jnp.int32(w), side)
        assert int(probe_bound) == 0
        fold(acc, out)
        w += 1
    return st


def _append_script(seed, chunks=5, cap=16):
    """Skewed append-only scripts for both sides (one hot key)."""
    rng = np.random.default_rng(seed)
    script = []
    for i in range(chunks):
        side = "left" if i % 2 == 0 else "right"
        keys = np.where(
            rng.random(cap) < 0.5, 7, rng.integers(0, 6, cap)
        ).astype(np.int64)
        vals = rng.integers(0, 1000, cap).astype(np.int64)
        script.append((side, list(zip(keys.tolist(), vals.tolist())),
                       [0] * cap))
    return script


@pytest.mark.parametrize("join_type", [
    "inner", "left_outer", "right_outer", "full_outer",
    "left_semi", "left_anti", "right_semi", "right_anti",
])
def test_fused_pool_changelog_equivalent_to_dense(join_type):
    """Property: on append-only inputs the fused pool path emits a
    changelog that folds to EXACTLY the dense bucket path's, for every
    join type, including hot-key skew and windowed burst drains (the
    pool runs out_capacity=32 so amplified chunks span many windows)."""
    script = _append_script(seed=11)
    jd = _executor("dense", join_type, out_capacity=4096)
    jp = _executor("pool", join_type, out_capacity=32)
    sd, sp = jd.init_state(), jp.init_state()
    acc_d, acc_p = Counter(), Counter()
    for side, rows, ops in script:
        schema = L if side == "left" else R
        chunk = _chunk(schema, rows, ops)
        sd = _drain_all(jd, sd, chunk, side, acc_d)
        sp = _drain_all(jp, sp, chunk, side, acc_p)
        assert +acc_p == +acc_d, f"{join_type} diverged after {side}"
    for s in (sp.left, sp.right):
        assert int(s.overflow) == 0
        assert int(s.inconsistency) == 0
    assert int(sp.emit_overflow) == 0


@pytest.mark.parametrize("join_type", ["left_outer", "left_semi",
                                       "left_anti"])
def test_retraction_cascade_through_pool_build_side(join_type):
    """A retractable DENSE left side joined against a fused-pool right
    side: left deletes cascade pad/semi/anti transitions that gather
    build rows from the pool — the dense/dense run is ground truth."""
    def run(right_storage):
        kw = dict(table_size=256, bucket_cap=64, out_capacity=8,
                  join_type=join_type)
        if right_storage == "pool":
            kw.update(right_storage="pool", right_pool_size=2048)
        j = HashJoinExecutor(L, R, [col("k")], [col("k")], **kw)
        st = j.init_state()
        acc = Counter()
        rng = np.random.default_rng(3)
        live = []
        for step in range(6):
            if step % 2 == 0:  # appends to the pool (right) side
                rows = [(int(rng.integers(0, 5)),
                         int(rng.integers(0, 100))) for _ in range(6)]
                st = _drain_all(j, st, _chunk(R, rows, [0] * 6),
                                "right", acc)
            else:  # inserts AND deletes on the retractable left side
                ins = [(int(rng.integers(0, 5)),
                        int(rng.integers(0, 100))) for _ in range(4)]
                ops = [0] * 4
                rows = list(ins)
                if live:  # retract an earlier row (cascade)
                    rows.append(live.pop(0))
                    ops.append(1)
                live.extend(ins)
                st = _drain_all(j, st, _chunk(L, rows, ops), "left", acc)
        assert int(st.left.inconsistency) == 0
        assert int(st.right.inconsistency) == 0
        return +acc

    assert run("pool") == run("dense")


def test_update_is_one_lookup_or_insert_per_chunk():
    """The acceptance-criterion probe count: tracing the append-only
    pool update compiles EXACTLY ONE lookup_or_insert and ZERO plain
    lookups — the fused probe replaced the key-table + rank-index
    pair."""
    from risingwave_tpu.state.hash_table import (
        PROBE_STATS,
        reset_probe_stats,
    )

    j = _executor("pool", "inner", out_capacity=64)
    st = j.init_state()
    chunk = _chunk(L, [(1, 10), (1, 11), (2, 20)], [0, 0, 0])
    reset_probe_stats()
    jax.eval_shape(
        lambda s, c: j._update_side_pool(s, c, j.left_keys, None),
        st.left, chunk,
    )
    assert PROBE_STATS == {"lookup": 0, "lookup_or_insert": 1}


def test_bump_allocator_positions_are_contiguous():
    """Accepted inserts take consecutive pool positions per chunk (the
    locality contract) and the cursor advances by exactly the accepted
    count."""
    j = _executor("pool", "inner", out_capacity=64)
    st = j.init_state()
    st, _ = j.apply(st, _chunk(L, [(5, i) for i in range(8)],
                               [0] * 8), "left")
    assert int(st.left.head) == 8
    # every entry's pool position is in [0, 8) and all are distinct
    occ = np.asarray(st.left.table.occupied)
    pos = np.asarray(st.left.pool_pos)[occ]
    assert sorted(pos.tolist()) == list(range(8))
    st, _ = j.apply(st, _chunk(L, [(6, i) for i in range(4)],
                               [0] * 4), "left")
    assert int(st.left.head) == 12


def test_ring_reuses_cleaned_pool_rows():
    """After watermark cleaning retires most keys, the ring's tail has
    moved past their rows, maintenance gives their table slots back,
    the freed space takes new rows, and the join still produces exact
    results."""
    j = HashJoinExecutor(
        L, R, [col("k")], [col("k")],
        table_size=64, out_capacity=64,
        left_storage="pool", right_storage="pool",
        left_pool_size=64, right_pool_size=64,
    )
    j.left_clean = JoinClean(j.left_keys[0], 0, 0)
    st = j.init_state()
    # fill 48/64 of the pool: cursor is past the 3/4 compaction gate
    lrows = [(k, 10 * k + i) for k in range(12) for i in range(4)]
    txt = "I I\n" + "\n".join(f"+ {k} {v}" for k, v in lrows)
    st, _ = j.apply(st, Chunk.from_pretty(txt, names=["k", "a"]), "left")
    assert int(st.left.head) == 48
    st = j.clean_below(st, "left", 10)  # keys 0..9 die (40 rows)
    assert int(st.left.table.tombstone_count()) == 40
    st = j.maybe_rehash(st)
    assert int(st.left.head - st.left.tail) == 8   # the survivors
    assert int(st.left.table.count()) == 8
    assert int(st.left.table.tombstone_count()) == 0
    assert int(st.left.reclaim_slots) == 40
    # 40 more rows wrap around the ring's end into the freed space
    more = [(k, 10 * k + i) for k in range(20, 30) for i in range(4)]
    txt = "I I\n" + "\n".join(f"+ {k} {v}" for k, v in more)
    st, _ = j.apply(st, Chunk.from_pretty(txt, names=["k", "a"]), "left")
    assert int(st.left.overflow) == 0
    assert int(st.left.head - st.left.tail) == 48
    # survivors (keys 10, 11) still join exactly
    st, pending = j.apply_begin(
        st, _chunk(R, [(10, 500), (3, 600)], [0, 0]), "right"
    )
    build = j.build_rows_of(st, "right")
    got = []
    w = 0
    while w == 0 or w * j.out_capacity < int(pending.total):
        got.extend(j.emit_window(
            build, pending, jnp.int32(w), "right")[0].to_rows())
        w += 1
    want = sorted((0, 10, a, 10, 500) for kk, a in lrows if kk == 10)
    assert sorted(got) == want


def test_pool_overflow_is_loud_not_silent():
    """Rows beyond pool capacity surface in the overflow counter and
    never corrupt surviving state."""
    j = HashJoinExecutor(
        L, R, [col("k")], [col("k")],
        table_size=64, out_capacity=64,
        left_storage="pool", right_storage="pool",
        left_pool_size=16, right_pool_size=16,
    )
    st = j.init_state()
    rows = [(k, k) for k in range(24)]  # 24 rows > 16-slot pool
    st, _ = j.apply(st, _chunk(L, rows, [0] * 24), "left")
    assert int(st.left.overflow) == 24 - 16
    assert int(st.left.head) == 16
