"""The whole of Nexmark q5 ("hot items") on the DAG runtime: a max over a
retractable input whose groups hold thousands of values (the counted
materialised input of ``hash_agg.py``), a join whose retractable side is
stored by its stream key (``hash_join.KeyedSideState``), the inequality
applied inside the join, and the inner aggregate planned once.

- the whole text through ``Engine`` against the benchmark's plain
  reference, past window + lag so that every store is cleaned;
- arbitrary retraction through the same plan shape over DML tables (the
  current maximum deleted, then a whole group), and the keyed side alone
  against a brute-force join with emission windows that overflow;
- the plan; the reference against a slower writing of the same; the six
  layer readers; ``benchmark/run.py`` end to end over a tiny preset.
"""

import json
import os
import sys
from collections import Counter

import jax.numpy as jnp
import numpy as np
import pytest

from risingwave_tpu.common.chunk import Chunk
from risingwave_tpu.common.config import RwConfig
from risingwave_tpu.common.types import DataType, Field, Schema
from risingwave_tpu.expr.node import InputRef
from risingwave_tpu.sql import Engine
from risingwave_tpu.stream.dag import FragNode, JoinNode
from risingwave_tpu.stream.executor import FilterExecutor
from risingwave_tpu.stream.hash_agg import AggState, HashAggExecutor
from risingwave_tpu.stream.hash_join import (
    HashJoinExecutor,
    KeyedSideState,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in ("benchmark", os.path.join("benchmark", "reference")):
    sys.path.insert(0, os.path.join(ROOT, p))
import nexmark_q5_numpy as ref  # noqa: E402
import run  # noqa: E402

REAL = os.path.join(ROOT, "BENCHMARK.json")
RATE = 20_000          # events/s: 18,400 bids/s
CHUNK, CHUNKS, BARRIERS = 1024, 18, 26   # ~1 s of event time a barrier
SOURCE = f"""
    CREATE SOURCE bid (auction BIGINT, bidder BIGINT, price BIGINT,
        channel VARCHAR, url VARCHAR, date_time TIMESTAMP,
        WATERMARK FOR date_time AS date_time - INTERVAL '4' SECOND)
    WITH (connector = 'nexmark', nexmark.table = 'bid',
          nexmark.event.rate = '{RATE}')"""
Q5 = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "nexmark_q5.json")))["view"]["sql"]
NEW_LAYERS = ["flush_chain_dev_ms", "join_retract_rows_per_barrier",
              "join_emit_rows_per_change", "minput_changes_per_barrier",
              "minput_live_values", "retract_side_live_rows"]


def _q5_engine():
    eng = Engine(RwConfig.from_dict({
        "streaming": {"chunk_size": CHUNK},
        "state": {"agg_table_size": 16384, "agg_emit_capacity": 1024,
                  "join_out_capacity": 1024, "mv_table_size": 4096},
    }))
    eng.execute(SOURCE)
    eng.execute(Q5)
    return eng


def _states(job, kind):
    import jax
    return [st for st in jax.tree.leaves(
        job.states, is_leaf=lambda x: isinstance(x, kind))
        if isinstance(st, kind)]


# ---------------------------------------------------------------------------
# (a) the whole text against the plain reference

def test_q5_view_equals_reference_past_window_and_lag(accel_tuned):
    eng = _q5_engine()
    job = eng.jobs[0]
    eng.tick(barriers=BARRIERS, chunks_per_barrier=CHUNKS)
    n = CHUNK * CHUNKS * BARRIERS
    want = ref.reference_rows("q5", n, RATE, 0, [n])
    wm = int(want["event_time_at"][0]) - 4_000_000
    keep = want["starttime"] + 10_000_000 <= wm
    want_rows = sorted(zip(*(want[c][keep].tolist() for c in ref.COLUMNS)))
    got = sorted(
        (int(a), int(b), int(w)) for a, b, w in
        eng.execute("SELECT auction, num, starttime FROM q5")
        if int(w) + 10_000_000 <= wm)
    assert len(want_rows) >= 6 and got == want_rows
    # each bid is counted once, though the text names the source twice
    assert eng.metrics.get("stream_rows_total", job="q5") == n
    # nothing was dropped anywhere, and every store was cleaned behind
    # the watermark and reclaimed by the maintenance pass
    aggs = _states(job, AggState)
    (join,) = [s for s in job.states if hasattr(s, "left")]
    for st in aggs:
        for attr in ("overflow", "minput_overflow", "distinct_overflow",
                     "inconsistency"):
            assert int(getattr(st, attr)) == 0, attr
        assert int(st.table.tombstone_count()) == 0
        for mt in st.minput_tables:
            assert int(mt.tombstone_count()) == 0
    assert int(join.emit_overflow) == 0
    for side in (join.left, join.right):
        assert int(side.overflow) == 0 and int(side.inconsistency) == 0
        assert int(side.cleaned_rows) > 0 and int(side.delete_rows) > 0
    assert isinstance(join.left, KeyedSideState)
    assert int(join.left.table.tombstone_count()) == 0
    # the residual filters a key's run, so the max side keeps the masked
    # pass and never sorts the keyed side
    assert int(join.left.sorted_probe_chunks) == 0
    assert eng.metrics.get("hash_join_sorted_probe_chunks_total",
                           job="q5", side="left") == 0
    # the max holds a group a window still open, and its materialised
    # input the distinct counts of those windows: cleaned, not grown
    (top,) = [st for st in aggs if st.minput_tables]
    assert int(top.table.count()) <= 9
    assert 0 < int(top.minput_tables[0].count()) < 2000
    assert int(top.minput_changes) > 10_000
    # the predicate inside the join: a change emits what qualifies, not
    # its window (thousands of rows a change behind the join)
    emitted = int(join.left.emit_rows) + int(join.right.emit_rows)
    changes = sum(int(getattr(s, a)) for s in (join.left, join.right)
                  for a in ("insert_rows", "delete_rows"))
    assert emitted < changes / 10


# ---------------------------------------------------------------------------
# (b) arbitrary retraction

HOT = """
    CREATE MATERIALIZED VIEW hot AS
    SELECT c.k, c.n, c.g FROM (
        SELECT k, count(*) AS n, g FROM t GROUP BY g, k
    ) AS c JOIN (
        SELECT max(c2.n) AS mx, c2.g2 FROM (
            SELECT count(*) AS n, g AS g2 FROM t GROUP BY k, g
        ) AS c2 GROUP BY c2.g2
    ) AS m ON c.g = m.g2 AND c.n >= m.mx"""


def _hot(rows: list) -> list:
    """Brute force: the keys of each group with the most rows."""
    n = Counter((g, k) for g, k, _ in rows)
    top: dict = {}
    for (g, _), c in n.items():
        top[g] = max(top.get(g, 0), c)
    return sorted((k, c, g) for (g, k), c in n.items() if c == top[g])


def test_the_maximum_and_a_whole_group_are_retracted():
    from tests.test_dag import small_engine

    eng = small_engine()
    eng.execute("CREATE TABLE t (g BIGINT, k BIGINT, id BIGINT) "
                "WITH (retract = 'true')")
    eng.execute(HOT)
    job = eng.jobs[-1]
    joins = [n.join for n in job.nodes if isinstance(n, JoinNode)]
    assert [(j.left_storage, j.right_storage) for j in joins] \
        == [("keyed", "dense")]
    rows: list = []
    ids = iter(range(10_000))

    def change(op: str, picked: list) -> None:
        eng.execute(f"{op} t VALUES " + ", ".join(
            f"({g}, {k}, {i})" for g, k, i in picked))
        for r in picked:
            rows.append(r) if op == "INSERT INTO" else rows.remove(r)
        eng.tick(barriers=2, chunks_per_barrier=1)
        got = sorted((int(k), int(n), int(g))
                     for k, n, g in eng.execute("SELECT * FROM hot"))
        assert got == _hot(rows), (op, picked)

    # three groups; in group 1 key 7 leads with 5 rows, key 8 has 4, 9 has 4
    first = [(1, 7, next(ids)) for _ in range(5)] \
        + [(1, 8, next(ids)) for _ in range(4)] \
        + [(1, 9, next(ids)) for _ in range(4)] \
        + [(2, 7, next(ids)) for _ in range(2)] \
        + [(3, k, next(ids)) for k in (1, 2, 3)]
    change("INSERT INTO", first)
    # the current maximum is deleted: 7 falls to 3, the next one (a tie
    # of 8 and 9 at 4) must come out — for the aggregate
    change("DELETE FROM", [r for r in rows if r[:2] == (1, 7)][:2])
    # the maximum's whole key goes, then a whole group — for the join side
    change("DELETE FROM", [r for r in rows if r[:2] == (1, 7)])
    change("DELETE FROM", [r for r in rows if r[0] == 3])
    assert all(g != 3 for _, _, g in _hot(rows))
    # and grows back from nothing, past the old values
    change("INSERT INTO", [(3, 5, next(ids)) for _ in range(6)])
    change("DELETE FROM", [r for r in rows if r[0] == 1])
    change("INSERT INTO", [(1, 8, next(ids))])
    for st in job.states:
        for s in ([st.left, st.right] if hasattr(st, "left") else []):
            assert int(s.inconsistency) == 0 and int(s.overflow) == 0


L = Schema((Field("k", DataType.INT64), Field("n", DataType.INT64),
            Field("w", DataType.INT64)))
R = Schema((Field("mx", DataType.INT64), Field("w2", DataType.INT64)))


def _chunk(schema, rows, ops, cap=16):
    pad = cap - len(rows)
    cols = tuple(jnp.asarray([r[i] for r in rows] + [0] * pad, jnp.int64)
                 for i in range(len(schema)))
    return Chunk(cols, jnp.asarray(list(ops) + [0] * pad, jnp.int8),
                 jnp.asarray([True] * len(rows) + [False] * pad), schema)


def test_keyed_side_against_a_brute_force_join(accel_tuned):
    """Random changes on both sides of ``L.w = R.w2 AND L.n >= R.mx``,
    L stored by (k, w): the folded output equals the join of what the
    sides hold, through emission windows of 8 rows that a change of
    ``mx`` overflows many times."""
    join = HashJoinExecutor(
        L, R, [InputRef(2)], [InputRef(1)], table_size=64, bucket_cap=4,
        out_capacity=8, left_storage="keyed", left_row_key=[0, 2],
        right_storage="dense")
    join.residual = InputRef(1) >= InputRef(3)
    st = join.init_state()
    rng = np.random.default_rng(5)
    left: dict = {}      # (k, w) -> n
    right: dict = {}     # w -> mx
    out: Counter = Counter()

    def feed(chunk, side):
        nonlocal st
        st, pending = join.apply_begin(st, chunk, side)
        build = join.build_rows_of(st, side)
        for w in range(-(-int(pending.total) // 8) or 1):
            win, bound = join.emit_window(build, pending, jnp.int32(w), side)
            assert int(bound) == 0
            sign = np.asarray(win.signs())
            cols = [np.asarray(c) for c in win.columns]
            for i in np.flatnonzero(np.asarray(win.valid)):
                out[tuple(int(c[i]) for c in cols)] += int(sign[i])

    for step in range(40):
        if step % 3 != 2:
            rows, ops = [], []
            for _ in range(int(rng.integers(1, 8))):
                key = (int(rng.integers(0, 12)), int(rng.integers(0, 3)))
                if key in left:   # an update pair, or a plain delete
                    rows.append((key[0], left.pop(key), key[1]))
                    ops.append(1)
                if rng.random() < 0.8:
                    left[key] = int(rng.integers(1, 6))
                    rows.append((key[0], left[key], key[1]))
                    ops.append(0)
            if rows:
                feed(_chunk(L, rows, ops), "left")
        else:
            w = int(rng.integers(0, 3))
            rows, ops = [], []
            if w in right:
                rows.append((right.pop(w), w))
                ops.append(1)
            if rng.random() < 0.85:
                right[w] = int(rng.integers(1, 6))
                rows.append((right[w], w))
                ops.append(0)
            if rows:
                feed(_chunk(R, rows, ops), "right")
        want = Counter({(k, n, w, right[w], w): 1
                        for (k, w), n in left.items()
                        if w in right and n >= right[w]})
        assert +out == want, step
    assert int(st.left.inconsistency) == 0 and int(st.left.overflow) == 0
    assert int(st.left.table.count()) == len(left)
    # a delete of a row the side does not hold is counted, not ignored
    st, _ = join.apply_begin(st, _chunk(L, [(99, 1, 0)], [1]), "left")
    assert int(st.left.inconsistency) == 1


# ---------------------------------------------------------------------------
# (c) the plan

def test_the_plan_shares_the_inner_aggregate_and_joins_inside():
    job = _q5_engine().jobs[0]
    frags = [n for n in job.nodes if isinstance(n, FragNode)]
    (jn,) = [n for n in job.nodes if isinstance(n, JoinNode)]
    execs = [ex for n in frags for ex in n.fragment.executors]
    aggs = [ex for ex in execs if isinstance(ex, HashAggExecutor)]
    # one inner aggregate, on panes (two executors: panes, then windows)
    # and the max over it: three, not five; one window node
    assert len(aggs) == 3
    assert sum(type(ex).__name__ == "WatermarkFilterExecutor"
               for ex in execs) == 1
    assert len(job.sources) == 1
    pane, final, top = aggs
    assert [a.kind for a in top.aggs] == ["max"] and top._minput_aggs
    assert not pane._minput_aggs and not final._minput_aggs
    # both consumers of the shared aggregate hang off the one node
    shared = next(i for i, n in enumerate(job.nodes)
                  if isinstance(n, FragNode)
                  and final in n.fragment.executors)
    assert len(job._consumers[("node", shared)]) == 2
    # sizes the planner states: a group a window still open, a
    # materialised-input slot an input row, a slot a row of the counts
    assert top.table_size == 64 and top.emit_capacity == 64
    assert top.minput_table_size == final.table_size == 16384
    assert top.watermark_group_idx == 0 and top.spill_ring == 0
    j = jn.join
    assert (j.left_storage, j.right_storage) == ("keyed", "dense")
    assert j.left_row_key and j.left_table_size == 16384
    assert (j.right_table_size, j.right_bucket_cap) == (64, 4)
    # the inequality is the join's, and nothing filters behind it
    assert j.residual is not None
    assert not any(isinstance(ex, FilterExecutor) for ex in execs)
    assert j.left_clean is not None and j.right_clean is not None


def test_no_flag_picks_the_store():
    """``join_force_dense`` can veto the keyed store (conformance runs
    compile flat), nothing selects it but the plan's shape: the same
    text with the inequality's sides unrelated to the stream keys stays
    dense, and q7's retractable side stays dense."""
    eng = Engine(RwConfig.from_dict({
        "streaming": {"chunk_size": CHUNK},
        "state": {"agg_table_size": 1024, "join_force_dense": True}}))
    eng.execute(SOURCE)
    eng.execute(Q5)
    (j,) = [n.join for n in eng.jobs[0].nodes if isinstance(n, JoinNode)]
    assert (j.left_storage, j.right_storage) == ("dense", "dense")
    assert j.residual is not None
    eng = Engine(RwConfig.from_dict({
        "streaming": {"chunk_size": CHUNK},
        "state": {"agg_table_size": 1024}}))
    eng.execute(SOURCE)
    eng.execute(json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "nexmark_q7.json")))["view"]["sql"])
    (j,) = [n.join for n in eng.jobs[0].nodes if isinstance(n, JoinNode)]
    assert (j.left_storage, j.right_storage) == ("pool", "dense")
    assert j.residual is None   # behind a pool side the band stays a filter


# ---------------------------------------------------------------------------
# (d) the reference against a slower writing of the same

def test_reference_against_dicts_and_loops():
    b = ref.gen_columns("bid", 40_000, ["auction", "date_time"], 2_000, 3)
    # two auctions tie for the most bids of the five windows that hold
    # an instant a minute after everything else: both come out of each
    far = int(b["date_time"].max()) + 60_000_000
    b = {"auction": np.concatenate([b["auction"], [5] * 50 + [6] * 50]),
         "date_time": np.concatenate([b["date_time"], [far] * 100])}
    counts: dict = {}
    for a, t in zip(b["auction"].tolist(), b["date_time"].tolist()):
        newest = t // ref.SLIDE_US * ref.SLIDE_US
        for k in range(5):
            key = (a, newest - k * ref.SLIDE_US)
            counts[key] = counts.get(key, 0) + 1
    top: dict = {}
    for (_, w), n in counts.items():
        top[w] = max(top.get(w, 0), n)
    want = sorted((a, n, w) for (a, w), n in counts.items() if n >= top[w])
    got = ref.q5_rows(b["auction"], b["date_time"])
    assert sorted(zip(*(got[c].tolist() for c in ref.COLUMNS))) == want
    assert len(want) == len(top) + 5   # ties kept
    assert sum(1 for a, n, _ in want if (a, n) in ((5, 50), (6, 50))) == 10


# ---------------------------------------------------------------------------
# (e) the readers, and run.py end to end over a tiny preset

def _window(first: list, last: list, trace=None) -> dict:
    def sample(m):
        return {"t_req": 0.0, "t_resp": 0.0, "m": {
            (k, tuple(sorted(lb.items()))): v for k, lb, v in m}}
    return {"job": "q5", "scrape_start": sample(first),
            "scrape_end": sample(last), "trace": trace}


def test_the_six_layer_readers():
    j = {"job": "q5"}
    left, right = dict(j, side="left"), dict(j, side="right")
    first = [("barrier_latency_seconds_count", j, 20.0),
             ("hash_join_delete_rows_total", left, 1000.0),
             ("hash_join_delete_rows_total", right, 10.0),
             ("hash_join_insert_rows_total", left, 1100.0),
             ("hash_join_insert_rows_total", right, 12.0),
             ("hash_join_emit_rows_total", left, 50.0),
             ("hash_join_emit_rows_total", right, 70.0),
             ("hash_agg_minput_changes_total", j, 2000.0)]
    last = [("barrier_latency_seconds_count", j, 30.0),
            ("hash_join_delete_rows_total", left, 2000.0),
            ("hash_join_delete_rows_total", right, 30.0),
            ("hash_join_insert_rows_total", left, 2100.0),
            ("hash_join_insert_rows_total", right, 32.0),
            ("hash_join_emit_rows_total", left, 2050.0),
            ("hash_join_emit_rows_total", right, 2150.0),
            ("hash_agg_minput_changes_total", j, 4500.0),
            ("hash_agg_minput_live_values", j, 321.0),
            ("hash_join_live_rows", left, 7000.0),
            ("hash_join_live_rows", right, 7.0)]
    trace = {"modules": {"jit__barrier_impl": (8, 1.6)}}
    w = _window(first, last, trace)
    got = {n: run.load_module(run.reader_path("per_layer", n)).read(w)
           for n in NEW_LAYERS}
    assert got == {"flush_chain_dev_ms": 200.0,
                   "join_retract_rows_per_barrier": 102.0,
                   "join_emit_rows_per_change": 2.0,
                   "minput_changes_per_barrier": 250.0,
                   "minput_live_values": 321.0,
                   "retract_side_live_rows": 7000.0}
    # a program without the counters (the parent commit), an untraced
    # run: nothing to read, and no reader raises
    bare = _window(first[:1], last[:1] + [
        ("hash_join_live_rows", left, 5.0)])
    for n in NEW_LAYERS:
        assert run.load_module(
            run.reader_path("per_layer", n)).read(bare) is None
    bench = json.load(open(REAL))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for n in NEW_LAYERS:
        assert by_name[n]["workloads"] == ["q5_join_backlog"]
        assert by_name[n]["moves"] == "rows_per_s"
    cell = run.load_cell(REAL, "q5_join_backlog")
    assert cell["config"]["reduced"] == [
        "rate_events_per_s", "horizon_rows", "bid_extra_column"]
    assert cell["config"]["guarantees"] == run.load_cell(
        REAL, "q5_inner_agg_backlog")["config"]["guarantees"]
    assert cell["traffic"]["readers"] == []
    assert {m["name"] for m in cell["end_to_end"]} == {
        "rows_per_s", "setup_s"}


def test_rehearsal_of_the_q5_join_cell_is_correct(tmp_path):
    bench = json.load(open(REAL))
    cells = json.load(open(os.path.join(
        ROOT, "benchmark", "tests", "preset", "cells_q5_join.json")))
    bench.update(configs=cells["configs"], workloads=cells["workloads"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny_q5_join_backlog"] \
                if "q5_join_backlog" in m["workloads"] else []
    path = os.path.join(str(tmp_path), "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(bench, f)
    result, window = run.run_cell(
        "tiny_q5_join_backlog", 2**31 + 4242, 3.0, False, bench_path=path,
        require_tpu=False, out_root=str(tmp_path))
    assert result["device"]["platform"] == "cpu"
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0
    assert result["checks"]["closed_windows"][0] >= 1
    assert result["checks"]["view_rows_differ"][0] == 0
    assert result["checks"]["counter_rows"][0] == 0
    assert result["checks"]["fused_fallbacks"][0] == 0
    assert len(window["epochs_on_disk"]) >= window["barriers"] >= 2
    # each bid counted once: a barrier is 32 chunks of 1,024 rows
    took = run.arith.rows(window["scrape_end"], "q5") \
        - run.arith.rows(window["scrape_start"], "q5")
    assert took == window["barriers"] * 32 * 1024
    got = {n: run.load_module(run.reader_path("per_layer", n)).read(window)
           for n in NEW_LAYERS if n != "flush_chain_dev_ms"}
    assert 5_000 < got["retract_side_live_rows"] < 16_384
    # most (auction, window) groups of this source hold one bid and
    # never change: the retractions are the hot auctions' counts
    assert got["join_retract_rows_per_barrier"] > 5
    assert got["minput_changes_per_barrier"] > 500
    assert 0 < got["minput_live_values"] < 2_000
    assert 0 < got["join_emit_rows_per_change"] < 10
