"""The whole of Nexmark q8 ("new users") on the DAG runtime: two generated
sources in one window program, two GROUP BYs with no aggregate call (one
keyed by a VARCHAR) joined on three columns, and a window's groups retired
at once when the watermark closes it.

- the whole text through ``Engine`` against the benchmark's plain
  reference on all three columns, past two closes, at two seeds (at the
  second, the auctions' cold sellers are people who exist);
- every store cleaned at each close and reclaimed; the sources counted
  apart; each seller group emitted once;
- a restart from a checkpoint taken inside a window (string keys in the
  restored tables) gives the view an unbroken run gives;
- the plan; the reference against a slower writing of the same; the two
  layer readers; ``benchmark/run.py`` end to end over a tiny preset.
"""

import dataclasses
import json
import os
import sys

import jax
import numpy as np
import pytest

from risingwave_tpu.common.config import RwConfig
from risingwave_tpu.connector import nexmark
from risingwave_tpu.sql import Engine
from risingwave_tpu.stream.dag import JoinNode
from risingwave_tpu.stream.hash_agg import AggState, HashAggExecutor
from risingwave_tpu.stream.hash_join import KeyedSideState, SideState

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in ("benchmark", os.path.join("benchmark", "reference")):
    sys.path.insert(0, os.path.join(ROOT, p))
import nexmark_q8_numpy as ref  # noqa: E402
import run  # noqa: E402

REAL = os.path.join(ROOT, "BENCHMARK.json")
CONFIG = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "nexmark_q8.json")))
RATE = 20_000          # events/s: 400 persons and 1,200 auctions a second
CHUNK, ROUNDS = 1024, 2          # 2 rounds of 1 + 3 chunks: 5.12 s a barrier
ROWS_PER_BARRIER = ROUNDS * 4 * CHUNK
STATE = {"agg_table_size": 16384, "agg_emit_capacity": 1024,
         "join_out_capacity": 1024, "join_right_table_size": 2048,
         "mv_table_size": 8192}
LAYERS = ["agg_cleaned_groups_per_barrier", "agg_emit_rows_per_barrier"]


@pytest.fixture
def seeded(monkeypatch):
    """The generator's seed, which ``WITH (...)`` cannot set, replaced in
    the configuration every generator is given (as the benchmark's
    server child does)."""
    plain = nexmark.NexmarkGenerator.__init__

    def use(seed: int) -> None:
        def init(self, config=nexmark.NexmarkConfig()):
            plain(self, dataclasses.replace(config, seed=seed))
        monkeypatch.setattr(nexmark.NexmarkGenerator, "__init__", init)

    return use


def _engine(data_dir=None) -> Engine:
    eng = Engine(RwConfig.from_dict({"streaming": {"chunk_size": CHUNK},
                                     "state": STATE}),
                 data_dir=None if data_dir is None else str(data_dir))
    if not eng.jobs:
        for stmt in CONFIG["sources_sql"]:
            eng.execute(stmt.replace("'1000000'", f"'{RATE}'"))
        eng.execute(CONFIG["view"]["sql"])
    return eng


def _tick(eng: Engine, barriers: int) -> None:
    eng.tick(barriers=barriers, chunks_per_barrier=ROUNDS)


def _view(eng: Engine) -> list:
    return sorted((int(i), str(n), int(t)) for i, n, t in eng.execute(
        "SELECT id, name, starttime FROM nexmark_q8"))


def _states(job, kind) -> list:
    return [st for st in jax.tree.leaves(
        job.states, is_leaf=lambda x: isinstance(x, kind))
        if isinstance(st, kind)]


def _aggs(job) -> tuple[AggState, AggState]:
    """(P's state, A's state): P's group key holds the name."""
    p, a = sorted(_states(job, AggState),
                  key=lambda s: -len(s.table.key_cols))
    return p, a


# ---------------------------------------------------------------------------
# (a) the whole text against the plain reference

@pytest.mark.parametrize("seed", [0, 7])
def test_q8_view_equals_reference_past_two_closes(accel_tuned, seeded, seed):
    seeded(seed)
    eng = _engine()
    job = eng.jobs[0]
    def cleaned():
        p, a = _aggs(job)
        join = job.states[4]
        return [int(p.cleaned_groups), int(a.cleaned_groups),
                int(join.left.cleaned_rows), int(join.right.cleaned_rows)]

    closes = 0
    for _ in range(8):     # 41 s of event time: three windows close
        before = cleaned()
        _tick(eng, 1)
        p, a = _aggs(job)
        join = job.states[4]
        after = cleaned()
        if after[0] > before[0]:
            # a close: every store retired the window at this barrier,
            # and the maintenance pass gave every slot back
            closes += 1
            assert all(x > y for x, y in zip(after, before)), after
            for st in (p, a):
                assert int(st.table.tombstone_count()) == 0
            assert int(join.left.table.tombstone_count()) == 0
            assert int(join.right.key_table.tombstone_count()) == 0
    assert closes >= 2
    n = 8 * ROWS_PER_BARRIER
    assert eng.metrics.get("stream_rows_total", job="nexmark_q8") == n
    want = ref.reference_rows("q8", n, RATE, seed, [n])
    wm = int(want["event_time_at"][0]) - 4_000_000
    keep = want["starttime"] + 10_000_000 <= wm
    want_rows = sorted(zip(*(want[c][keep].tolist() for c in ref.COLUMNS)))
    got = [r for r in _view(eng) if r[2] + 10_000_000 <= wm]
    assert len({r[2] for r in want_rows}) >= 3 and len(want_rows) > 300
    assert got == want_rows
    # the cold sellers name people: a seller group matches a person of
    # its window about as often at every seed (at seed 7 the parent's
    # generator drew them ~2e10 ids past anyone)
    per_window = len(want_rows) / len({r[2] for r in want_rows})
    assert per_window > 100
    # the join has no residual: A's chunks find their persons in P by one
    # sort of P's slots each, not by a masked pass over them a row
    assert int(join.left.sorted_probe_chunks) > 0
    assert eng.metrics.get("hash_join_sorted_probe_chunks_total",
                           job="nexmark_q8", side="left") > 0
    for st in _states(job, AggState) + [join.left, join.right]:
        assert int(st.overflow) == 0 and int(st.inconsistency) == 0


def test_sources_are_counted_apart_and_groups_emitted_once(accel_tuned):
    eng = _engine()
    job = eng.jobs[0]
    _tick(eng, 5)
    m = eng.metrics
    persons = m.get("source_rows_total", job="nexmark_q8", table="person")
    auctions = m.get("source_rows_total", job="nexmark_q8",
                     table="auction")
    assert auctions == 3 * persons
    assert persons + auctions == m.get("stream_rows_total", job="nexmark_q8")
    # a barrier's counters are read at the next maintenance pass: one
    # more barrier, which brings no rows
    eng.tick(barriers=1, chunks_per_barrier=0)
    p, a = _aggs(job)
    n = 5 * ROWS_PER_BARRIER
    auct = ref._blocks(ref.auction_columns, 3 * n // 4, RATE, 0)
    # the barriers in which each seller group took an auction
    per_barrier = 3 * CHUNK * ROUNDS
    seen: dict = {}
    for k, (s, t) in enumerate(zip(auct["seller"].tolist(),
                                   auct["date_time"].tolist())):
        seen.setdefault((s, t // ref.WINDOW_US), set()).add(
            k // per_barrier)
    # every person is a group of its own, emitted once; a seller group is
    # an insert at its first barrier and, unchanged, a U-/U+ pair at each
    # later barrier that touches it again (a hot seller across a barrier's
    # edge), which the join folds: its side takes each group once
    assert int(p.emit_rows) == n // 4
    assert int(a.emit_rows) == sum(2 * len(b) - 1 for b in seen.values())
    assert int(a.emit_rows) > len(seen)
    right = job.states[4].right
    assert int(right.insert_rows) == len(seen)
    assert int(right.delete_rows) == 0
    assert m.get("hash_agg_emit_rows_total", job="nexmark_q8") \
        == int(p.emit_rows) + int(a.emit_rows)
    assert m.get("hash_agg_cleaned_groups_total", job="nexmark_q8") \
        == int(p.cleaned_groups) + int(a.cleaned_groups) > 0


# ---------------------------------------------------------------------------
# (b) a restart from a checkpoint taken inside a window

def test_restart_inside_a_window_gives_the_unbroken_view(accel_tuned,
                                                          tmp_path):
    whole = _engine()
    _tick(whole, 7)
    first = _engine(tmp_path)
    # 3 barriers: 15.4 s of event time, a window open and one closed
    _tick(first, 3)
    p, _ = _aggs(first.jobs[0])
    assert int(p.table.count()) > 0 and int(p.cleaned_groups) > 0
    del first
    again = _engine(tmp_path)
    p, _ = _aggs(again.jobs[0])
    (names,) = [c for c in p.table.key_cols if hasattr(c, "lens")]
    assert int(p.table.count()) > 0 and np.asarray(names.lens).max() > 0
    _tick(again, 4)
    assert _view(again) == _view(whole)
    assert again.metrics.get("stream_rows_total", job="nexmark_q8") \
        == 4 * ROWS_PER_BARRIER


# ---------------------------------------------------------------------------
# (c) the plan

def test_the_plan_keys_p_densely_stores_a_and_cleans_both():
    job = _engine().jobs[0]
    assert job._pulls == [("person", 1), ("auction", 3)]
    aggs = [ex for n in job.nodes if not isinstance(n, JoinNode)
            for ex in n.fragment.executors
            if isinstance(ex, HashAggExecutor)]
    assert len(aggs) == 2 and all(not a.aggs for a in aggs)
    assert all(a.table_size == STATE["agg_table_size"] for a in aggs)
    (j,) = [n.join for n in job.nodes if isinstance(n, JoinNode)]
    assert (j.left_storage, j.right_storage) == ("keyed", "dense")
    assert j.left_table_size == STATE["agg_table_size"]
    assert (j.right_table_size, j.right_bucket_cap) \
        == (STATE["join_right_table_size"], 4)
    assert j.left_clean is not None and j.right_clean is not None
    st = job.states[4]
    assert isinstance(st.left, KeyedSideState)
    assert isinstance(st.right, SideState)


# ---------------------------------------------------------------------------
# (d) the reference against a slower writing of the same

def test_reference_against_sets_and_loops():
    rows = 4 * 6000
    got = ref.reference_rows("q8", rows, 2_000, 3, [rows])
    pers = ref._blocks(ref.person_columns, rows // 4, 2_000, 3)
    auct = ref._blocks(ref.auction_columns, 3 * rows // 4, 2_000, 3)
    w = ref.WINDOW_US
    people = {(i, str(n), t // w * w) for i, n, t in zip(
        pers["id"].tolist(), pers["name"].tolist(),
        pers["date_time"].tolist())}
    sellers = {(s, t // w * w) for s, t in zip(
        auct["seller"].tolist(), auct["date_time"].tolist())}
    want = sorted((i, n, t) for i, n, t in people if (i, t) in sellers)
    assert sorted(zip(*(got[c].tolist() for c in ref.COLUMNS))) == want
    assert len({t for _, _, t in want}) >= 5 and len(want) > 100
    # a person and a seller of another window do not meet
    assert any((s, t) not in {(i, u) for i, _, u in people}
               and any(i == s for i, _, _ in people)
               for s, t in sellers)
    # the newest event is the last auction's
    assert got["event_time_at"][0] == auct["date_time"].max()
    with pytest.raises(SystemExit):
        ref.reference_rows("q8", rows + 2, 2_000, 3, [])


def test_reference_sellers_match_the_generator():
    """The reference's auctions are the program's, field for field, at a
    seed past the first: the cold sellers' people follow the event."""
    gen = nexmark.NexmarkGenerator(nexmark.NexmarkConfig(
        inter_event_us=50, seed=7))
    got = gen.gen_auctions(3000, 4096)
    host = ref.auction_columns(3000, 3000 + 4096, RATE, 7)
    assert np.array_equal(np.asarray(got.columns[7]), host["seller"])
    assert np.array_equal(np.asarray(got.columns[5]), host["date_time"])
    people = ref.person_columns(0, 4096, RATE, 7)
    p = gen.gen_persons(0, 4096)
    assert np.array_equal(np.asarray(p.columns[0]), people["id"])
    names = p.columns[1]
    lens = np.asarray(names.lens)
    data = np.asarray(names.data)
    assert [bytes(d[:n]).decode() for d, n in zip(data, lens)] \
        == people["name"].tolist()


# ---------------------------------------------------------------------------
# (e) the readers, and run.py end to end over a tiny preset

def _window(first: list, last: list) -> dict:
    def sample(m):
        return {"t_req": 0.0, "t_resp": 0.0, "m": {
            (k, tuple(sorted(lb.items()))): v for k, lb, v in m}}
    return {"job": "nexmark_q8", "scrape_start": sample(first),
            "scrape_end": sample(last), "trace": None}


def test_the_two_layer_readers():
    bench = json.load(open(REAL))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for n in LAYERS:
        assert by_name[n]["workloads"] == ["q8_backlog"]
        assert by_name[n]["moves"] == "rows_per_s"
    cell = run.load_cell(REAL, "q8_backlog")
    assert cell["config"]["reduced"] == [
        "horizon_rows", "person_extra_column", "auction_extra_column"]
    assert cell["config"]["guarantees"] == run.load_cell(
        REAL, "q5_join_backlog")["config"]["guarantees"]
    assert cell["traffic"]["readers"] == []
    assert {m["name"] for m in cell["end_to_end"]} == {"rows_per_s",
                                                       "setup_s"}


def test_rehearsal_of_the_q8_cell_is_correct(tmp_path):
    bench = json.load(open(REAL))
    cells = json.load(open(os.path.join(
        ROOT, "benchmark", "tests", "preset", "cells_q8.json")))
    bench.update(configs=cells["configs"], workloads=cells["workloads"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny_q8_backlog"] \
                if "q8_backlog" in m["workloads"] else []
    path = os.path.join(str(tmp_path), "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(bench, f)
    result, window = run.run_cell(
        "tiny_q8_backlog", 2**31 + 8888, 3.0, False, bench_path=path,
        require_tpu=False, out_root=str(tmp_path))
    assert result["device"]["platform"] == "cpu"
    assert result["correct"] is True, result["checks"]
    assert result["checks"]["closed_windows"][0] >= 2
    assert result["checks"]["fused_fallbacks"][0] == 0
    assert len(window["epochs_on_disk"]) >= window["barriers"] >= 2
    a, b = window["scrape_start"], window["scrape_end"]
    took = run.arith.rows(b, "nexmark_q8") - run.arith.rows(a, "nexmark_q8")
    assert took == window["barriers"] * 2 * 4 * 1024
    persons = run.arith.delta(a, b, "source_rows_total", job="nexmark_q8",
                              table="person")
    assert persons * 4 == took
    got = {n: run.load_module(run.reader_path("per_layer", n)).read(window)
           for n in LAYERS}
    # a barrier brings 2,048 persons, each a new group, and ~700 seller
    # groups; a close retires a window's ~4,000 persons and its sellers
    assert 2_048 < got["agg_emit_rows_per_barrier"] < 4_096
    assert got["agg_cleaned_groups_per_barrier"] > 500
