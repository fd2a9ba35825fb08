"""Streaming hash-join tests (inner join, retraction, multiset)."""

from collections import Counter

import numpy as np
import pytest

from risingwave_tpu.common.chunk import Chunk
from risingwave_tpu.common.types import DataType, Field, Schema
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


# -- a keyed build side probed by sort (no residual) ---------------------

KL = Schema((Field("k", DataType.INT64, nullable=True),
             Field("s", DataType.VARCHAR, str_width=8),
             Field("a", DataType.INT64)))
KR = Schema((Field("k", DataType.INT64, nullable=True),
             Field("s", DataType.VARCHAR, str_width=8),
             Field("b", DataType.INT64)))


def _kchunk(schema, rows, ops, cap=32):
    """A chunk of ``(k | None, s, value)`` rows with op codes ``ops``."""
    import jax.numpy as jnp

    from risingwave_tpu.common.chunk import NCol, StrCol

    pad = cap - len(rows)
    rows = list(rows) + [(None, "", 0)] * pad
    k = NCol(jnp.asarray([r[0] or 0 for r in rows], jnp.int64),
             jnp.asarray([r[0] is None for r in rows]))
    data = np.zeros((cap, 8), np.uint8)
    for i, r in enumerate(rows):
        data[i, :len(r[1])] = np.frombuffer(r[1].encode(), np.uint8)
    s = StrCol(jnp.asarray(data),
               jnp.asarray([len(r[1]) for r in rows], jnp.int32))
    v = jnp.asarray([r[2] for r in rows], jnp.int64)
    return Chunk((k, s, v), jnp.asarray(list(ops) + [0] * pad, jnp.int8),
                 jnp.asarray([True] * (cap - pad) + [False] * pad), schema)


def _keyed_steps(case):
    """``[(side, rows, ops, sorts) | ("reclaim",)]`` for one case: op 0
    inserts, 1 deletes, 2/3 an update pair; ``sorts``: the chunk finds
    its pairs by the sorted order (else by the masked pass)."""
    from risingwave_tpu.stream.hash_join import SORTED_PROBE_MIN_ROWS as K

    hot = [(7, "hot", i) for i in range(20)]
    base = [("left", hot + [(1, "one", 100), (1, "uno", 101)],
             [0] * 22, False)]
    keys = [(7, "hot"), (1, "one"), (2, "two"), (1, "uno"), (7, "hot"),
            (3, "hot"), (7, "ho"), (1, "one")]
    # 12 rows: degrees 20, 1, 0, 1, 20, 0, 0, 1, ...; key 7's 60 pairs
    # span windows of 8
    big = [(*keys[i % 8], 500 + i) for i in range(12)]
    probe = ("right", big, [0] * 12, True)
    small = ("right", big[:K - 1], [0] * (K - 1), False)
    assert 1 < K <= 12
    if case == "degrees":
        return base + [probe, small]
    if case == "tombstones":
        # deleted slots tombstoned, then reclaimed (rows move), then new
        # rows: the probe sorts whatever slots the side holds now
        return base + [
            ("left", hot[::2] + [(1, "one", 100)], [1] * 11, False),
            probe,
            ("reclaim",),
            ("left", [(7, "hot", 50), (2, "two", 51), (1, "one", 100)],
             [0] * 3, False),
            probe]
    if case == "deletes":
        # the probing chunk retracts what it inserted before, and an
        # update pair moves a row from key 1 to key 7
        return base + [probe, (
            "right", big[1:] + [(1, "one", 900), (7, "hot", 900)],
            [1] * 11 + [2, 3], True)]
    if case == "nulls":
        # NULL keys match nothing on either side, and do not count
        # towards the rows a chunk sorts for
        return [("left", hot[:3] + [(None, "hot", 9), (1, "one", 100)],
                 [0] * 5, False),
                ("right", big + [(None, "hot", 1), (None, "", 3)],
                 [0] * 14, True),
                ("right", big[:K - 1] + [(None, "hot", 1)], [0] * K,
                 False)]
    if case == "empty":
        return base + [("right", [], [], False)]
    assert case == "collisions"
    # the hash reads the first key column only: (1, "one") and (1, "uno")
    # of the build side share a hash, so their rows interleave in one
    # run, and the probe's (7, "ho") finds the run of (7, "hot"); each
    # probe row pairs with its own key's rows only
    return base + [probe]


def _keyed_joins():
    """{"sorted": (join, begin, emit), "masked": ...}: a keyed left side
    probed with no residual, and with one that holds wherever the keys
    are equal (which keeps the masked pass); ``begin`` and ``emit``
    jitted, so that a step compiles nothing new."""
    import jax

    from risingwave_tpu.expr.node import InputRef

    out = {}
    for name, residual in (("sorted", None),
                           ("masked", InputRef(0) == InputRef(3))):
        j = HashJoinExecutor(
            KL, KR, [col("k"), col("s")], [col("k"), col("s")],
            table_size=64, bucket_cap=8, out_capacity=8,
            left_storage="keyed", left_row_key=[0, 1, 2])
        j.residual = residual
        out[name] = (j, jax.jit(j.apply_begin, static_argnums=2),
                     jax.jit(lambda st, p, w, side, j=j: j.emit_window(
                         j.build_rows_of(st, side), p, w, side),
                         static_argnums=3))
    return out


@pytest.fixture(scope="module")
def keyed_joins():
    return _keyed_joins()


@pytest.mark.parametrize("case", [
    "degrees", "tombstones", "deletes", "nulls", "empty", "collisions"])
def test_keyed_build_side_probe(case, keyed_joins, monkeypatch):
    """A keyed left side probed by the right side with no residual:
    what each chunk emits equals a brute-force join of what the sides
    held and the masked pass (the path a residual keeps), through
    emission windows of 8 rows; the side counts each chunk that found
    its pairs by the sorted order."""
    import jax.numpy as jnp

    from risingwave_tpu.stream import hash_join

    joins = keyed_joins
    if case == "collisions":
        plain = hash_join.hash64_columns
        monkeypatch.setattr(hash_join, "hash64_columns",
                            lambda cols: plain(list(cols)[:1]))
        joins = _keyed_joins()
    states = {n: j.init_state() for n, (j, _, _) in joins.items()}
    left: Counter = Counter()
    right: Counter = Counter()
    sorted_chunks = 0

    def norm(row):
        return tuple(x.item() if hasattr(x, "item") else x for x in row)

    for step in _keyed_steps(case):
        if step == ("reclaim",):
            for n, (j, _, _) in joins.items():
                states[n] = j.maybe_rehash(states[n])
                assert int(states[n].left.tombstones) > 0
            continue
        side, rows, ops, sorts = step
        want: Counter = Counter()
        for r, o in zip(rows, ops):
            if r[0] is None:
                continue
            op = 0 if o in (0, 3) else 1
            held = left if side == "right" else right
            for h, n in held.items():
                if h[:2] == r[:2]:
                    want[(op,) + ((*h, *r) if side == "right"
                                  else (*r, *h))] += n
        for r, o in zip(rows, ops):
            if r[0] is not None:
                (left if side == "left" else right)[r] += \
                    1 if o in (0, 3) else -1
        left, right = +left, +right
        chunk = _kchunk(KL if side == "left" else KR, rows, ops)
        for n, (j, begin, emit) in joins.items():
            st, pending = begin(states[n], chunk, side)
            assert bool(pending.by_sort) == (sorts and n == "sorted")
            got: Counter = Counter()
            for w in range(-(-int(pending.total) // 8) or 1):
                win, bound = emit(st, pending, jnp.int32(w), side)
                assert int(bound) == 0
                got.update(norm(r) for r in win.to_rows())
            assert got == want, (case, n, step)
            states[n] = st
        sorted_chunks += sorts
    for n, st in states.items():
        assert int(st.left.inconsistency) == 0 and int(st.left.overflow) == 0
        assert int(st.emit_overflow) == 0
    assert int(states["sorted"].left.sorted_probe_chunks) == sorted_chunks
    assert int(states["masked"].left.sorted_probe_chunks) == 0
