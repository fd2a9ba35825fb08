"""Band-bounded join state (PR 35): the whole of Nexmark q7 through
``Engine`` against the benchmark's plain reference, per-row expiry in the
pool ring and in dense buckets, the planner's cleaning rules, one read of
a source named twice, and ``TagTable.reclaimed`` against a rebuilt table.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from risingwave_tpu.common.chunk import Chunk
from risingwave_tpu.common.config import RwConfig
from risingwave_tpu.common.types import DataType, Schema
from risingwave_tpu.expr.node import InputRef, col
from risingwave_tpu.sql import Engine
from risingwave_tpu.state.hash_table import TagTable, pair_tag
from risingwave_tpu.stream.dag import JoinNode
from risingwave_tpu.stream.hash_join import HashJoinExecutor, JoinClean

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                "benchmark", "reference"))
import compare  # noqa: E402
import nexmark_q7_numpy as ref  # noqa: E402

S = 1_000_000
BID = ("CREATE SOURCE bid (auction BIGINT, bidder BIGINT, price BIGINT, "
       "channel VARCHAR, url VARCHAR, date_time TIMESTAMP, WATERMARK FOR "
       "date_time AS date_time - INTERVAL '4' SECOND) WITH (connector = "
       "'nexmark', nexmark.table = 'bid', nexmark.event.rate = '{rate}')")
Q7 = """CREATE MATERIALIZED VIEW q7 AS
SELECT B.auction, B.price, B.bidder, B.date_time
FROM bid B
JOIN (SELECT MAX(price) AS maxprice, window_end AS date_time
      FROM TUMBLE(bid, date_time, INTERVAL '10' SECOND) GROUP BY window_end) B1
  ON B.price = B1.maxprice
WHERE B.date_time BETWEEN B1.date_time - INTERVAL '10' SECOND AND B1.date_time"""
WINDOWS = {"column": "date_time", "size_us": 10 * S, "lag_us": 4 * S}
CHUNK, PER_BARRIER = 256, 8


def _engine(rate: int, **state) -> Engine:
    sizes = {"agg_table_size": 1024, "agg_emit_capacity": 256,
             "join_left_table_size": 1 << 16, "join_right_table_size": 1024,
             "join_pool_size": 1 << 15, "join_out_capacity": 512,
             "join_right_bucket_cap": 16, "mv_table_size": 4096}
    eng = Engine(RwConfig.from_dict({
        "streaming": {"chunk_size": CHUNK}, "state": {**sizes, **state}}))
    eng.execute(BID.format(rate=rate))
    return eng


def _join_state(job):
    idx = next(i for i, n in enumerate(job.nodes) if isinstance(n, JoinNode))
    return job.nodes[idx].join, job.states[idx]


# -- the whole of q7 through the engine ---------------------------------

@pytest.fixture(scope="module")
def q7_run():
    """Sixty barriers of q7 at 2,000 events/s (1.11 s of event time a
    barrier: six closed windows), the join's levels after every one."""
    rate, seed, barriers = 2000, 0, 60  # NexmarkConfig's default seed
    eng = _engine(rate)
    eng.execute(Q7)
    job = eng.jobs[0]
    levels = []
    for _ in range(barriers):
        eng.tick(chunks_per_barrier=PER_BARRIER)
        _, st = _join_state(job)
        levels.append({
            "live": int(st.left.head - st.left.tail),
            "right": int(jnp.sum(st.right.count)),
            "overflow": int(st.left.overflow) + int(st.right.overflow)
            + int(st.emit_overflow),
            "inconsistency": int(st.left.inconsistency)
            + int(st.right.inconsistency),
        })
    rows = eng.execute("SELECT auction, price, bidder, date_time FROM q7")
    return {"eng": eng, "job": job, "levels": levels, "rows": rows,
            "n": barriers * PER_BARRIER * CHUNK, "rate": rate, "seed": seed}


def test_q7_equals_the_reference_over_closed_windows(q7_run):
    want = ref.reference_rows("q7", q7_run["n"], q7_run["rate"],
                              q7_run["seed"], [q7_run["n"]])
    newest = int(want["event_time_at"][0])
    got = {n: np.asarray([r[i] for r in q7_run["rows"]], np.int64)
           for i, n in enumerate(ref.COLUMNS)}
    got = compare.closed(got, WINDOWS, newest)
    want = compare.closed({n: want[n] for n in ref.COLUMNS}, WINDOWS,
                          newest)
    assert compare.rows_differ(got, want, ref.COLUMNS) == (0, "")
    # 66.7 s of event time less lag and band: the maxima of five windows
    ends = np.unique(want["date_time"] // (10 * S))
    assert ends.shape[0] >= 3


def test_q7_retracts_pairs_through_the_join(q7_run):
    """(a) A window's maximum changes while it is open, across barriers:
    the aggregate retracts the old (maxprice, window_end), the join the
    old pair.  The view ends with one row a window although the right
    side took many more inserts."""
    _, st = _join_state(q7_run["job"])
    n_windows = len({r[3] // (10 * S) for r in q7_run["rows"]})
    assert int(st.right.insert_rows) > 2 * n_windows
    # pairs staged by right-side chunks: inserts and their retractions
    assert int(st.right.emit_rows) > n_windows
    assert len(q7_run["rows"]) <= n_windows + 1


def test_q7_join_state_is_bounded_by_band_and_lag(q7_run):
    """(c) Live left rows never above rate x 14 s + one barrier; the
    right side holds the open windows; no counter rose in 60 barriers."""
    bids_per_s = q7_run["rate"] * 46 // 50
    bound = bids_per_s * 14 + PER_BARRIER * CHUNK
    assert max(lv["live"] for lv in q7_run["levels"]) <= bound
    assert q7_run["levels"][-1]["live"] >= bids_per_s * 14
    assert max(lv["right"] for lv in q7_run["levels"]) <= 3
    assert all(lv["overflow"] == 0 and lv["inconsistency"] == 0
               for lv in q7_run["levels"])


def test_q7_plan_reads_bid_once_and_cleans_both_sides(q7_run):
    job, eng = q7_run["job"], q7_run["eng"]
    assert len(job.sources) == 1
    join, _ = _join_state(job)
    left, right = join.clean_rule("left"), join.clean_rule("right")
    # a left row dies 10 s behind the watermark, a right row at it
    assert (left.lag_us, right.lag_us) == (10 * S, 0)
    # each side's own watermark filter, in its own pruned schema
    assert (left.src_col, left.other_src_col) == (3, 1)
    assert (right.src_col, right.other_src_col) == (1, 3)
    assert join.left_storage == "pool" and join.right_storage == "dense"
    # the ring stores the four columns the view reads, not bid's six
    assert [f.name for f in join.left_schema] == [
        "auction", "bidder", "price", "date_time"]
    # every event is counted once
    rows = eng.metrics.get("stream_rows_total", job=job.name)
    assert rows == q7_run["n"]


def test_q7_join_counters_reach_the_metrics(q7_run):
    m, name = q7_run["eng"].metrics, q7_run["job"].name
    ins = m.get("hash_join_insert_rows_total", job=name, side="left")
    # the counters are the barrier's, read a barrier later
    assert q7_run["n"] - PER_BARRIER * CHUNK <= ins <= q7_run["n"]
    assert m.get("hash_join_cleaned_rows_total", job=name,
                 side="left") > 0
    assert m.get("hash_join_reclaim_slots_total", job=name,
                 side="left") > 0
    assert m.get("hash_join_probe_steps_total", job=name, side="left") \
        >= ins
    live = m.get("hash_join_live_rows", job=name, side="left")
    assert abs(live - q7_run["levels"][-1]["live"]) <= PER_BARRIER * CHUNK
    assert m.get("hash_join_table_slots", job=name, side="left") == 1 << 16
    # none of them is an error counter
    with pytest.raises(KeyError):
        m.get("maintenance_counter_rows", job=name, kind="insert_rows")


# -- planner rules --------------------------------------------------------

@pytest.mark.parametrize("where,left,right", [
    ("B.date_time BETWEEN B1.date_time - INTERVAL '10' SECOND "
     "AND B1.date_time", 10 * S, 0),
    ("B1.date_time >= B.date_time AND "
     "B.date_time + INTERVAL '10' SECOND >= B1.date_time", 10 * S, 0),
    ("B.date_time >= B1.date_time - INTERVAL '3' SECOND", 3 * S, None),
    ("B.date_time < B1.date_time + INTERVAL '2' SECOND", None, 2 * S),
    ("B.price > 5", None, None),
])
def test_band_becomes_the_cleaning_rule(where, left, right):
    """Any constant band, either direction, either bound alone."""
    eng = _engine(2000)
    eng.execute(Q7.split("WHERE")[0] + "WHERE " + where)
    join, _ = _join_state(eng.jobs[0])
    got = [r and r.lag_us for r in
           (join.clean_rule("left"), join.clean_rule("right"))]
    assert got == [left, right]


def test_window_key_join_still_cleans_by_its_key():
    """The q8 pattern: a window column in the join key cleans each side
    a window size behind the watermark, as before."""
    eng = _engine(2000)
    eng.execute(
        "CREATE SOURCE person (id BIGINT, name VARCHAR, date_time "
        "TIMESTAMP, WATERMARK FOR date_time AS date_time - INTERVAL '4' "
        "SECOND) WITH (connector = 'nexmark', nexmark.table = 'person', "
        "nexmark.event.rate = '2000')")
    eng.execute(
        "CREATE SOURCE auction (id BIGINT, seller BIGINT, reserve BIGINT, "
        "expires TIMESTAMP, date_time TIMESTAMP, WATERMARK FOR date_time "
        "AS date_time - INTERVAL '4' SECOND) WITH (connector = 'nexmark', "
        "nexmark.table = 'auction', nexmark.event.rate = '2000')")
    eng.execute(
        "CREATE MATERIALIZED VIEW q8 AS SELECT P.id, P.name, "
        "P.window_start FROM TUMBLE(person, date_time, INTERVAL '10' "
        "SECOND) P JOIN TUMBLE(auction, date_time, INTERVAL '10' SECOND) "
        "A ON P.id = A.seller AND P.window_start = A.window_start")
    job = eng.jobs[0]
    assert len(job.sources) == 2
    join, _ = _join_state(job)
    for side in ("left", "right"):
        rule = join.clean_rule(side)
        assert rule.lag_us == 10 * S and rule.other_src_col is None
        keys = join.left_keys if side == "left" else join.right_keys
        assert rule.expr is keys[1]
    for _ in range(30):
        eng.tick(chunks_per_barrier=4)
    _, st = _join_state(job)
    # 30 barriers took in ~15 windows of persons; two or three are held
    assert 0 < int(st.left.head - st.left.tail) < int(st.left.head) // 3
    assert int(st.left.cleaned_rows) > 0 and int(st.left.overflow) == 0


def test_self_join_reads_its_table_once():
    eng = _engine(2000)
    eng.execute("CREATE TABLE t (k BIGINT, v BIGINT)")
    eng.execute("CREATE MATERIALIZED VIEW pairs AS SELECT a.k, a.v AS av, "
                "b.v AS bv FROM t a JOIN t b ON a.k = b.k")
    eng.execute("INSERT INTO t VALUES (1, 10), (1, 11), (2, 20)")
    eng.tick()
    job = eng.jobs[0]
    assert len(job.sources) == 1
    assert sorted(eng.execute("SELECT k, av, bv FROM pairs")) == [
        (1, 10, 10), (1, 10, 11), (1, 11, 10), (1, 11, 11), (2, 20, 20)]


# -- per-row expiry in the executor --------------------------------------

L = Schema.of(("k", DataType.INT64), ("t", DataType.INT64))
R = Schema.of(("k", DataType.INT64), ("u", DataType.INT64))


def _chunk(schema, rows, ops=None):
    ops = ops or [0] * len(rows)
    txt = "I I\n" + "\n".join(
        f"{'+' if o == 0 else '-'} {a} {b}" for (a, b), o in zip(rows, ops))
    return Chunk.from_pretty(txt, names=[f.name for f in schema])


def _probe(j, st, rows):
    """Right rows in, the (left t, right u) pairs out."""
    st, pending = j.apply_begin(st, _chunk(R, rows), "right")
    build = j.build_rows_of(st, "right")
    got, w = [], 0
    while w == 0 or w * j.out_capacity < int(pending.total):
        out, bound = j.emit_window(build, pending, jnp.int32(w), "right")
        assert int(bound) == 0
        got += [(r[2], r[4]) for r in out.to_rows()]
        w += 1
    return st, sorted(got)


@pytest.mark.parametrize("storage", ["pool", "dense"])
def test_a_deep_key_loses_its_oldest_rows_and_keeps_its_newest(storage):
    """(b) One key holds 40 rows over four inserts; the watermark takes
    them ten at a time, head first.  After every step a probe returns
    exactly the live rows, new rows of the key take the next ranks, and
    a key that lost every row starts again from nothing."""
    j = HashJoinExecutor(
        L, R, [col("k")], [col("k")], table_size=64, bucket_cap=64,
        out_capacity=16, left_storage=storage, right_storage="dense",
        left_pool_size=64)
    j.left_clean = JoinClean(InputRef(1), 0, 0)
    st = j.init_state()
    times: list[int] = []
    for step in range(4):
        new = [100 * step + i for i in range(10)]
        rows = [(7, t) for t in new] + [(8 + step, t) for t in new[:2]]
        st, _ = j.apply(st, _chunk(L, rows), "left")
        times += new
        st, got = _probe(j, st, [(7, -step)])
        assert got == [(t, -step) for t in times]
    for step in range(4):
        st = j.clean_below(st, "left", 100 * step + 10)
        st = j.maybe_rehash(st)
        live = [t for t in times if t >= 100 * step + 10]
        st, got = _probe(j, st, [(7, step)])
        assert got == [(t, step) for t in live]
        # the other keys went with their rows
        st, got = _probe(j, st, [(8 + step, step)])
        assert got == []
    assert int(st.left.cleaned_rows) == 48
    assert int(st.left.overflow) == 0
    table = st.left.table if storage == "pool" else st.left.key_table
    assert int(table.count()) == 0 and int(table.tombstone_count()) == 0
    # the key comes back: ranks start again at its head
    st, _ = j.apply(st, _chunk(L, [(7, 900), (7, 901)]), "left")
    st, got = _probe(j, st, [(7, 9)])
    assert got == [(900, 9), (901, 9)]


def test_a_row_out_of_order_keeps_what_lies_behind_it():
    """The ring retires a prefix: a newer row that arrived before older
    ones holds them until it has expired itself (it errs on the side of
    keeping), and nothing live is ever lost."""
    j = HashJoinExecutor(
        L, R, [col("k")], [col("k")], table_size=64, out_capacity=16,
        left_storage="pool", right_storage="dense", left_pool_size=32)
    j.left_clean = JoinClean(InputRef(1), 0, 0)
    st = j.init_state()
    st, _ = j.apply(st, _chunk(L, [(1, 5), (1, 50), (1, 6), (2, 7)]),
                    "left")
    st = j.clean_below(st, "left", 10)
    st, got = _probe(j, st, [(1, 0), (2, 0)])
    assert got == [(6, 0), (7, 0), (50, 0)]
    st = j.clean_below(st, "left", 60)
    st, got = _probe(j, st, [(1, 1), (2, 1)])
    assert got == []


def test_dense_side_frees_the_keys_its_deletes_emptied():
    j = HashJoinExecutor(L, R, [col("k")], [col("k")], table_size=16,
                         bucket_cap=4, out_capacity=16)
    j.right_clean = JoinClean(InputRef(1), 0, 0)
    st = j.init_state()
    st, _ = j.apply(st, _chunk(R, [(1, 10), (2, 20)]), "right")
    st, _ = j.apply(st, _chunk(R, [(1, 10), (1, 30)], [1, 0]), "right")
    st, _ = j.apply(st, _chunk(R, [(2, 20)], [1]), "right")
    assert int(st.right.key_table.count()) == 2
    st = j.maybe_rehash(j.clean_below(st, "right", 0))
    assert int(st.right.key_table.count()) == 1  # key 2 held no row
    st = j.maybe_rehash(j.clean_below(st, "right", 31))
    assert int(st.right.key_table.count()) == 0
    assert int(st.right.cleaned_rows) == 1


# -- the tag table's reclaim ----------------------------------------------

@pytest.mark.parametrize("size,load,dead", [
    (16, 1.0, 0.5), (64, 0.9, 0.3), (1024, 0.8, 0.1), (1024, 0.8, 0.6),
    (4096, 0.5, 0.07), (2048, 0.6, 0.3)])
def test_tag_table_reclaim_against_a_rebuilt_table(size, load, dead):
    """Same lookups as a table built from the live entries alone, no
    tombstone left, per-slot values still with their entries."""
    rng = np.random.default_rng(size + int(100 * dead))
    n = int(size * load)
    hashes = jnp.asarray(rng.integers(1, 1 << 62, n).astype(np.uint64))
    ranks = jnp.asarray(rng.integers(0, 3, n).astype(np.int32))
    tags = pair_tag(hashes, ranks)
    ok = jnp.ones((n,), jnp.bool_)
    table, slots, _, over, _ = TagTable.create(size)._probe_tags(
        tags, ok, insert=True)
    table = TagTable(table, size)
    assert not bool(jnp.any(over))
    val = jnp.zeros((size,), jnp.int32).at[slots].set(
        jnp.arange(n, dtype=jnp.int32) + 1)
    kill = rng.random(n) < dead
    table = table.clear_slots(slots, jnp.asarray(kill))
    assert int(table.tombstone_count()) == int(kill.sum())

    got, (val2,), lost = jax.jit(
        lambda t, v: t.reclaimed((v,)))(table, val)
    assert int(lost) == 0 and int(got.tombstone_count()) == 0
    assert int(got.count()) == n - int(kill.sum())
    rebuilt, _, _, _, _ = TagTable.create(size)._probe_tags(
        tags, jnp.asarray(~kill), insert=True)
    rebuilt = TagTable(rebuilt, size)
    for t in (got, rebuilt):
        s, found, bound = t.lookup_pair_counted(hashes, ranks, ok)
        assert int(bound) == 0
        assert np.array_equal(np.asarray(found), ~kill)
    s, found, _ = got.lookup_pair_counted(hashes, ranks, ok)
    held = np.asarray(val2)[np.asarray(s)[~kill]]
    assert np.array_equal(held, np.arange(n)[~kill] + 1)
    # every slot left empty is reset
    assert not np.any(np.asarray(val2)[~np.asarray(got.occupied)])


def test_ranked_insert_finishes_its_stragglers_in_a_tile():
    """Chunks wider than ``STRAGGLER_TILES`` at load 0.75: the few rows
    with long chains leave the chunk-wide rounds for a tile, then a
    narrower one, and come back.  Every (key, rank) is then found once, ranks are dense per
    key, and nothing else is."""
    from risingwave_tpu.state import hash_table
    from risingwave_tpu.stream.hash_join import _rank_by_sorted

    cap, size = 4096, 16384
    assert cap > max(hash_table.STRAGGLER_TILES)
    rng = np.random.default_rng(5)
    table = TagTable.create(size)
    count = jnp.zeros((size,), jnp.int32)
    ok = jnp.ones((cap,), jnp.bool_)
    insert = jax.jit(lambda t, c, h, r: t.lookup_or_insert_ranked(
        h, r, c, ok))
    held: dict[int, int] = {}
    rounds = []
    for _ in range(3):
        keys = np.where(rng.random(cap) < 0.3, rng.integers(1, 40, cap),
                        rng.integers(40, 1 << 40, cap)).astype(np.uint64)
        h = jnp.asarray(keys * np.uint64(0x9E3779B97F4A7C15))
        cr, _, _ = _rank_by_sorted(h, ok)
        (table, slots, rank, head, inserted, existed, over, iters,
         steps) = insert(table, count, h, cr)
        assert not bool(jnp.any(over)) and not bool(jnp.any(existed))
        assert bool(jnp.all(inserted))
        assert len(set(np.asarray(slots).tolist())) == cap
        rounds.append(int(iters))
        want_rank = []
        for k in keys.tolist():
            want_rank.append(held.get(k, 0))
            held[k] = held.get(k, 0) + 1
        assert np.asarray(rank).tolist() == want_rank
        # the callers' part: the key's total at its head
        first = np.asarray(cr) == 0
        uniq, inv, n = np.unique(keys, return_inverse=True,
                                 return_counts=True)
        count = count.at[jnp.where(first, head, size)].add(
            jnp.where(first, jnp.asarray(n[inv], jnp.int32), 0),
            mode="drop")
    assert max(rounds) > 8  # the tail the tile exists for
    keys = np.asarray(list(held), np.uint64)
    h = jnp.asarray(keys * np.uint64(0x9E3779B97F4A7C15))
    n = jnp.asarray([held[k] for k in keys.tolist()], jnp.int32)
    every = jnp.ones((keys.shape[0],), jnp.bool_)
    for r, want in ((jnp.zeros_like(n), True), (n - 1, True), (n, False)):
        _, found, bound = table.lookup_pair_counted(h, r, every)
        assert int(bound) == 0 and bool(jnp.all(found == want))
    assert int(table.count()) == 3 * cap
