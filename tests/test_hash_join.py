"""Streaming hash-join tests (inner join, retraction, multiset)."""

import numpy as np

from risingwave_tpu.common.chunk import Chunk
from risingwave_tpu.common.types import DataType, Schema
from risingwave_tpu.expr.node import col
from risingwave_tpu.stream.fragment import Fragment
from risingwave_tpu.stream.hash_join import HashJoinExecutor, JoinClean
from risingwave_tpu.stream.materialize import AppendOnlyMaterialize
from risingwave_tpu.stream.dag import DagJob

L = Schema.of(("k", DataType.INT64), ("a", DataType.INT64))
R = Schema.of(("k", DataType.INT64), ("b", DataType.INT64))


def _join(**kw):
    return HashJoinExecutor(
        L, R, [col("k")], [col("k")],
        table_size=64, bucket_cap=4, out_capacity=64, **kw,
    )


def _lc(text):
    return Chunk.from_pretty(text, names=["k", "a"])


def _rc(text):
    return Chunk.from_pretty(text, names=["k", "b"])


def _apply(j, st, chunk, side):
    st, out = j.apply(st, chunk, side)
    return st, sorted(out.to_rows())


def test_inner_join_basic():
    j = _join()
    st = j.init_state()
    st, rows = _apply(j, st, _lc("""
        I I
        + 1 10
        + 2 20
    """), "left")
    assert rows == []  # right empty

    st, rows = _apply(j, st, _rc("""
        I I
        + 1 100
        + 1 101
        + 3 300
    """), "right")
    # right rows probe left: k=1 matches once each
    assert rows == [(0, 1, 10, 1, 100), (0, 1, 10, 1, 101)]

    st, rows = _apply(j, st, _lc("""
        I I
        + 1 11
    """), "left")
    # new left row matches both right k=1 rows
    assert rows == [(0, 1, 11, 1, 100), (0, 1, 11, 1, 101)]


def test_join_retraction():
    j = _join()
    st = j.init_state()
    st, _ = _apply(j, st, _lc("""
        I I
        + 1 10
    """), "left")
    st, _ = _apply(j, st, _rc("""
        I I
        + 1 100
    """), "right")
    # delete the left row: must retract the joined row
    st, rows = _apply(j, st, _lc("""
        I I
        - 1 10
    """), "left")
    assert rows == [(1, 1, 10, 1, 100)]
    # left side now empty: new right row matches nothing
    st, rows = _apply(j, st, _rc("""
        I I
        + 1 101
    """), "right")
    assert rows == []


def test_join_multiset_duplicates(accel_tuned):
    j = _join()
    st = j.init_state()
    # two identical left rows — multiset semantics
    st, _ = _apply(j, st, _lc("""
        I I
        + 1 10
        + 1 10
    """), "left")
    st, rows = _apply(j, st, _rc("""
        I I
        + 1 100
    """), "right")
    assert rows == [(0, 1, 10, 1, 100), (0, 1, 10, 1, 100)]
    # delete ONE copy
    st, rows = _apply(j, st, _lc("""
        I I
        - 1 10
    """), "left")
    assert rows == [(1, 1, 10, 1, 100)]
    # one copy left
    st, rows = _apply(j, st, _rc("""
        I I
        + 1 101
    """), "right")
    assert rows == [(0, 1, 10, 1, 101)]


def test_join_delete_then_insert_same_chunk_reuses_hole():
    j = _join()
    st = j.init_state()
    st, _ = _apply(j, st, _lc("""
        I I
        + 1 10
        + 1 11
        + 1 12
        + 1 13
    """), "left")  # bucket_cap=4: full
    st, rows = _apply(j, st, _lc("""
        I I
        - 1 10
        + 1 14
    """), "left")
    assert int(st.left.overflow) == 0  # hole reused, no overflow
    assert int(st.left.count[np.argmax(st.left.count)]) == 4


def test_join_state_cleaning():
    j = _join()
    st = j.init_state()
    st, _ = _apply(j, st, _lc("""
        I I
        + 1 10
        + 5 50
    """), "left")
    j.left_clean = JoinClean(j.left_keys[0], 0, 0)
    st = j.clean_below(st, "left", 3)  # drop keys < 3
    st, rows = _apply(j, st, _rc("""
        I I
        + 1 100
        + 5 500
    """), "right")
    assert rows == [(0, 5, 50, 5, 500)]


def test_binary_job_end_to_end():
    class ListSource:
        def __init__(self, chunks):
            self.chunks = list(chunks)
            self.i = 0

        def next_chunk(self):
            c = self.chunks[self.i % len(self.chunks)]
            self.i += 1
            return c

    j = _join()
    mv = AppendOnlyMaterialize(j.out_schema, ring_size=256)
    job = DagJob.binary(
        ListSource([_lc("""
            I I
            + 1 10
        """), _lc("""
            I I
            + 2 20
        """)]),
        ListSource([_rc("""
            I I
            + 1 100
        """), _rc("""
            I I
            + 2 200
        """)]),
        j,
        Fragment([mv]),
    )
    job.run(barriers=1, chunks_per_barrier=2)
    # nodes: [join, post] — the post fragment holds the MV
    rows = mv.to_host(job.states[1][0])
    assert sorted(rows) == [(1, 10, 1, 100), (2, 20, 2, 200)]
    assert job.committed_epoch > 0


def test_join_insert_then_delete_same_chunk_annihilates():
    """Regression: [+row, -row] in ONE chunk must not ghost-insert."""
    j = _join()
    st = j.init_state()
    st, _ = _apply(j, st, _lc("""
        I I
        + 1 10
        - 1 10
    """), "left")
    # left state must be empty: a new right row matches nothing
    st, rows = _apply(j, st, _rc("""
        I I
        + 1 100
    """), "right")
    assert rows == []
    assert int(st.left.inconsistency) == 0


def test_join_delete_of_absent_key_no_ghost():
    """Regression: deletes must not insert ghost keys into the table."""
    j = _join()
    st = j.init_state()
    st, _ = _apply(j, st, _lc("""
        I I
        - 7 70
    """), "left")
    assert int(st.left.key_table.count()) == 0  # no ghost key slot
    assert int(st.left.inconsistency) == 1      # surfaced, not silent


def test_binary_job_recover():
    class ReplaySource:
        def __init__(self, chunks):
            self.chunks = list(chunks)
            self.offset = 0

        def next_chunk(self):
            c = self.chunks[self.offset % len(self.chunks)]
            self.offset += 1
            return c

        def state(self):
            return {"offset": self.offset}

    j = _join()
    mv = AppendOnlyMaterialize(j.out_schema, ring_size=256)
    job = DagJob.binary(
        ReplaySource([_lc("""
            I I
            + 1 10
        """)]),
        ReplaySource([_rc("""
            I I
            + 1 100
        """)]),
        j, Fragment([mv]),
    )
    job.run(barriers=1, chunks_per_barrier=1)
    committed = job.committed_epoch
    n_rows = len(mv.to_host(job.states[1][0]))
    # process more, then crash before the barrier
    job.run_chunk("left")
    job.recover()
    assert job.sources["left"].offset == 1
    assert len(mv.to_host(job.states[1][0])) == n_rows
    assert job.committed_epoch == committed


# -- degree-adaptive pool storage (round-3: shared row pool, no per-key
# -- cap; ref JoinHashMap's unbounded rows, hash_join.rs:169) ----------

def _pool_join(**kw):
    return HashJoinExecutor(
        L, R, [col("k")], [col("k")],
        table_size=64, out_capacity=64,
        left_storage="pool", right_storage="pool",
        left_pool_size=1024, right_pool_size=1024, **kw,
    )


def _brute_inner(lrows, rrows):
    return sorted(
        (0, lk, a, rk, b)
        for lk, a in lrows for rk, b in rrows if lk == rk
    )


def test_pool_join_hot_key_exceeds_any_bucket(accel_tuned):
    """One key holding 200 rows (far past any dense bucket_cap) joins
    fully: the pool has no per-key depth limit."""
    import jax

    j = _pool_join()
    st = j.init_state()
    lrows = [(7, i) for i in range(200)] + [(1, 900), (2, 901)]
    rows_txt = "I I\n" + "\n".join(f"+ {k} {v}" for k, v in lrows)
    st, out = j.apply(st, Chunk.from_pretty(rows_txt, names=["k", "a"]),
                      "left")
    st, rows = _apply(j, st, _rc("""
        I I
        + 7 500
        + 2 600
    """), "right")
    want = _brute_inner(lrows, [(7, 500), (2, 600)])
    # out_capacity=64 < 201 matches: drain the remaining windows the
    # way the DAG runtime does
    assert int(st.left.overflow) == 0 and int(st.right.overflow) == 0
    assert len(rows) == 64  # first window full
    # full-match check via the windowed interface
    st2 = j.init_state()
    st2, _ = j.apply(st2, Chunk.from_pretty(rows_txt, names=["k", "a"]),
                     "left")
    chunk = _rc("""
        I I
        + 7 500
        + 2 600
    """)
    st2, pending = j.apply_begin(st2, chunk, "right")
    build = j.build_rows_of(st2, "right")
    got = []
    import jax.numpy as jnp
    w = 0
    while w * j.out_capacity < int(pending.total):
        got.extend(
            j.emit_window(build, pending, jnp.int32(w), "right")[0].to_rows()
        )
        w += 1
    assert sorted(got) == want


def test_pool_join_10x_skew_matches_brute_force():
    """10x hot-key skew across multiple chunks: exact results, zero
    overflow, no per-key tuning (round-2 verdict item 4 done-criterion)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(7)
    j = _pool_join()
    st = j.init_state()
    lrows, rrows = [], []
    got = []

    def drain(pending, side):
        build = j.build_rows_of(st, side)
        w = 0
        while w * j.out_capacity < int(pending.total):
            got.extend(j.emit_window(
                build, pending, jnp.int32(w), side)[0].to_rows())
            w += 1

    for step in range(6):
        # 90% of rows on key 7 (10x skew vs the other 9 keys)
        lk = np.where(rng.random(32) < 0.9, 7,
                      rng.integers(0, 9, 32)).astype(np.int64)
        la = rng.integers(0, 1000, 32).astype(np.int64)
        rk = np.where(rng.random(32) < 0.9, 7,
                      rng.integers(0, 9, 32)).astype(np.int64)
        rb = rng.integers(0, 1000, 32).astype(np.int64)
        lchunk = "I I\n" + "\n".join(
            f"+ {k} {v}" for k, v in zip(lk, la))
        rchunk = "I I\n" + "\n".join(
            f"+ {k} {v}" for k, v in zip(rk, rb))
        st, pending = j.apply_begin(
            st, Chunk.from_pretty(lchunk, names=["k", "a"]), "left")
        drain(pending, "left")
        lrows.extend(zip(lk.tolist(), la.tolist()))
        st, pending = j.apply_begin(
            st, Chunk.from_pretty(rchunk, names=["k", "b"]), "right")
        drain(pending, "right")
        rrows.extend(zip(rk.tolist(), rb.tolist()))

    assert int(st.left.overflow) == 0 and int(st.right.overflow) == 0
    assert sorted(got) == _brute_inner(lrows, rrows)


def test_pool_join_watermark_cleaning_bounds_state():
    """clean_below on a pool side retires the expired prefix of its
    ring (here whole keys, all their fused (hash, rank) entries); ranks
    stay consistent for survivors."""
    import jax.numpy as jnp

    j = _pool_join()
    j.left_clean = JoinClean(j.left_keys[0], 0, 0)  # clean left keys below threshold
    st = j.init_state()
    lrows = [(k, 10 * k + i) for k in range(8) for i in range(5)]
    txt = "I I\n" + "\n".join(f"+ {k} {v}" for k, v in lrows)
    st, _ = j.apply(st, Chunk.from_pretty(txt, names=["k", "a"]), "left")
    assert int(st.left.table.count()) == 40
    assert int(st.left.head - st.left.tail) == 40

    st = j.clean_below(st, "left", 5)  # drop keys 0..4
    assert int(st.left.table.count()) == 15  # 3 keys x 5 rows remain
    assert int(st.left.head - st.left.tail) == 15
    assert int(st.left.cleaned_rows) == 25

    # survivors still join correctly (ranks intact)
    st, pending = j.apply_begin(st, _rc("""
        I I
        + 6 600
        + 2 200
    """), "right")
    build = j.build_rows_of(st, "right")
    got = []
    w = 0
    while w * j.out_capacity < int(pending.total):
        got.extend(j.emit_window(
            build, pending, jnp.int32(w), "right")[0].to_rows())
        w += 1
    want = _brute_inner([r for r in lrows if r[0] >= 5], [(6, 600)])
    assert sorted(got) == want


def test_pool_join_retraction_is_loud():
    """A delete reaching an append-only pool side surfaces as
    inconsistency, never silent corruption."""
    j = _pool_join()
    st = j.init_state()
    st, _ = j.apply(st, _lc("""
        I I
        + 1 10
    """), "left")
    st, _ = j.apply(st, _lc("""
        I I
        - 1 10
    """), "left")
    assert int(st.left.inconsistency) == 1
