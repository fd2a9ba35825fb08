"""Dispatch-ahead of the next window (ISSUE 38): the served ticker
sends window n+1 to the chip once epoch n is sealed and its upload's
device reads are queued, and only then drains epoch n
(``Engine.tick(ahead=...)``).  A statement waiting for the engine lock
keeps the window back; any other holder of the lock but the scrape
settles a window ahead first (``Engine.settle``).  Every case compares
against the serial order, or against the plain reference of
``benchmark/reference``."""

import importlib.util
import os
import threading
import time

import jax
import numpy as np
import pytest

from risingwave_tpu.pgwire import SimpleClient
from risingwave_tpu.server import SingleNode
from risingwave_tpu.sql import Engine
from risingwave_tpu.sql.planner import PlannerConfig
from risingwave_tpu.stream.dag import DagJob
from risingwave_tpu.stream.runtime import StreamingJob

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RATE = 2000
CPB = 8
CONFIG = dict(
    chunk_capacity=256, agg_table_size=4096, agg_emit_capacity=1024,
    join_left_table_size=1 << 16, join_right_table_size=1024,
    join_pool_size=1 << 15, join_out_capacity=1024,
    mv_table_size=1 << 14, mv_ring_size=1 << 16, topn_pool_size=4096,
)
BID = ("CREATE SOURCE bid (auction BIGINT, bidder BIGINT, price BIGINT, "
       "channel VARCHAR, url VARCHAR, date_time TIMESTAMP, WATERMARK FOR "
       "date_time AS date_time - INTERVAL '4' SECOND) WITH (connector = "
       f"'nexmark', nexmark.table = 'bid', nexmark.event.rate = '{RATE}')")
#: name -> (view, its columns, runtime, reference module and query)
VIEWS = {
    # the whole of q7: DagJob's window program emits join rows into the
    # view inside the window
    "q7": ("CREATE MATERIALIZED VIEW v AS SELECT B.auction, B.price, "
           "B.bidder, B.date_time FROM bid B JOIN (SELECT MAX(price) AS "
           "maxprice, window_end AS date_time FROM TUMBLE(bid, date_time, "
           "INTERVAL '10' SECOND) GROUP BY window_end) B1 ON B.price = "
           "B1.maxprice WHERE B.date_time BETWEEN B1.date_time - INTERVAL "
           "'10' SECOND AND B1.date_time",
           ["auction", "price", "bidder", "date_time"], DagJob,
           ("nexmark_q7_numpy", "q7")),
    "q5_inner": ("CREATE MATERIALIZED VIEW v AS SELECT auction, "
                 "window_start, count(*) AS bids FROM HOP(bid, date_time, "
                 "INTERVAL '2' SECOND, INTERVAL '10' SECOND) GROUP BY "
                 "auction, window_start",
                 ["auction", "window_start", "bids"], StreamingJob,
                 ("nexmark_numpy", "q5")),
}


def _reference(view: str):
    name = VIEWS[view][3][0]
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "benchmark", "reference", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _want(view: str, ref, bids: int) -> list:
    """The plain reference's view after ``bids`` bids, open windows
    and all, sorted."""
    out = ref.reference_rows(VIEWS[view][3][1], bids, RATE, 0, [bids])
    return sorted(zip(*(out[c].tolist() for c in VIEWS[view][1])))


def _engine(data_dir, view: str = "q7", pre=()) -> Engine:
    eng = Engine(PlannerConfig(**CONFIG), data_dir=str(data_dir))
    eng.execute(BID)
    for stmt in pre:
        eng.execute(stmt)
    eng.execute(VIEWS[view][0])
    eng.execute(f"ALTER SYSTEM SET chunks_per_barrier = {CPB}")
    return eng


def _ahead(eng: Engine, ticks: int) -> None:
    for _ in range(ticks):
        eng.tick(1, ahead=lambda: True)


def _counter(eng: Engine, name: str, **labels) -> float:
    try:
        return eng.metrics.get(name, job="v", **labels)
    except KeyError:
        return 0.0


def _same(a: Engine, b: Engine) -> None:
    """The view and every state leaf of the two engines' one job."""
    assert sorted(a.execute("SELECT * FROM v")) \
        == sorted(b.execute("SELECT * FROM v"))
    la, lb = (jax.tree.leaves(e.jobs[0].states) for e in (a, b))
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert a.jobs[0].window_ahead is None


def _bids(job) -> int:
    """Bids the job has taken: one reader of ``bid``, however often
    the plan names it."""
    srcs = list(job.sources.values()) if isinstance(job, DagJob) \
        else [job.source]
    assert len(srcs) == 1
    return int(srcs[0].offset)


@pytest.mark.parametrize("view", sorted(VIEWS))
def test_served_reads_see_a_committed_epoch(view, tmp_path):
    """(a) The served node, its ticker and pgwire: every read runs with
    no window in flight, on an epoch the store lists as committed,
    whose source cursor is the job's, and its rows are the plain
    reference's after that many bids."""
    node = SingleNode(PlannerConfig(**CONFIG), data_dir=str(tmp_path))
    eng = node.engine
    eng.execute(BID)
    eng.execute(VIEWS[view][0])
    eng.execute(f"ALTER SYSTEM SET chunks_per_barrier = {CPB}")
    eng.execute("ALTER SYSTEM SET barrier_interval_ms = 50")
    job = eng.jobs[0]
    assert type(job) is VIEWS[view][2]
    store = eng.checkpoint_store
    seen = []
    read_rows = eng._mv_rows

    def recorded(entry):
        rows = read_rows(entry)
        loaded = store.load(job.ckpt_key, job.committed_epoch)
        seen.append({
            "in_flight": job.window_ahead is not None,
            "durable": job.committed_epoch == job.sealed_epoch
            and job.committed_epoch in store.epochs(job.ckpt_key),
            "cursor": loaded is not None
            and loaded[2] == job._source_state(),
            "bids": _bids(job), "rows": rows})
        return rows

    eng._mv_rows = recorded
    server = node.start(port=0)
    try:
        client = SimpleClient("127.0.0.1", server.server_address[1])
        deadline = time.monotonic() + 240
        while len(seen) < 10 and time.monotonic() < deadline:
            client.query("SELECT * FROM v")
            # reads land inside ticks and between them
            time.sleep(0.05 * (len(seen) % 4))
        client.close()
    finally:
        node.stop()
        server.shutdown()
    assert len(seen) >= 10
    assert _counter(eng, "barrier_windows_ahead_total") >= 1
    ref = _reference(view)
    for s in seen:
        assert not s["in_flight"] and s["durable"] and s["cursor"], s
        if not s["bids"]:
            assert s["rows"] == []
            continue
        # every row, the open windows' too: a window in flight would
        # show in them
        assert sorted(tuple(map(int, r)) for r in s["rows"]) \
            == _want(view, ref, s["bids"])


@pytest.mark.parametrize("op", ["flush", "ddl", "stop", "recover"])
def test_a_window_ahead_settles_as_the_serial_barrier(op, tmp_path):
    """(b) FLUSH, CREATE/DROP MATERIALIZED VIEW, stop() and recover()
    with a window ahead: the view and state of a serial engine at the
    same barrier count, and ``barrier_windows_settled_total{by}``."""
    ticks = 4
    nodes = [SingleNode(PlannerConfig(**CONFIG), data_dir=str(tmp_path / s))
             for s in ("ahead", "serial")]
    for node in nodes:
        for stmt in (BID, VIEWS["q7"][0],
                     f"ALTER SYSTEM SET chunks_per_barrier = {CPB}"):
            node.engine.execute(stmt)
    a, b = (n.engine for n in nodes)
    # every epoch object's bytes, in commit order: the same work on the
    # same epochs writes the same checkpoints
    npz = ([], [])

    def recording(put, sizes):
        def recorded(key, data):
            if key.endswith(".npz"):
                sizes.append(len(data))
            return put(key, data)
        return recorded

    for eng, sizes in zip((a, b), npz):
        store = eng.checkpoint_store.store
        store.put = recording(store.put, sizes)
    _ahead(a, ticks)
    assert a.jobs[0].window_ahead is not None
    b.tick(ticks + 1)
    for node, eng in zip(nodes, (a, b)):
        if op == "flush":
            eng.execute("FLUSH")
        elif op == "ddl":
            eng.execute("CREATE MATERIALIZED VIEW w AS SELECT auction, "
                        "count(*) AS n FROM bid GROUP BY auction")
            eng.execute("DROP MATERIALIZED VIEW w")
        elif op == "stop":
            node.stop()
        else:
            eng.recover()
    by = {"ddl": "ddl"}.get(op, op)
    assert _counter(a, "barrier_windows_settled_total", by=by) == 1
    assert _counter(b, "barrier_windows_settled_total", by=by) == 0
    assert a.jobs[0].committed_epoch == a.jobs[0].sealed_epoch
    _same(a, b)
    assert len(npz[0]) > ticks and npz[0] == npz[1]


def test_a_kill_with_a_window_ahead_replays_to_the_serial_view(tmp_path):
    """(c) A process that dies after the dispatch-ahead and before its
    barrier: a new engine on the same data directory recovers the
    epoch before it and replays the window's rows."""
    ticks, more = 4, 3
    a = _engine(tmp_path / "ahead")
    _ahead(a, ticks)
    assert a.jobs[0].window_ahead is not None
    committed = a.jobs[0].committed_epoch
    # the kill: nothing of the engine runs again
    del a
    c = Engine(PlannerConfig(**CONFIG), data_dir=str(tmp_path / "ahead"))
    assert c.jobs[0].committed_epoch == committed
    assert c.jobs[0].window_ahead is None
    c.tick(more)
    b = _engine(tmp_path / "serial")
    b.tick(ticks + more)
    _same(c, b)


def test_a_waiting_statement_holds_the_window_back(tmp_path):
    """(d) A statement waiting for the engine lock at the dispatch
    point: no window goes ahead, and the read it makes needs no
    settling.  None waiting: a window ahead at every barrier."""
    node = SingleNode(PlannerConfig(**CONFIG), data_dir=str(tmp_path))
    eng = node.engine
    for stmt in (BID, VIEWS["q7"][0],
                 f"ALTER SYSTEM SET chunks_per_barrier = {CPB}"):
        eng.execute(stmt)
    lock = node._lock
    got = []

    def read():
        with lock.statement():
            got.append(lock.statements)
            eng.execute("SELECT * FROM v")

    with lock:
        reader = threading.Thread(target=read)
        reader.start()
        while lock.statements == 0:
            time.sleep(0.01)
        eng.tick(1, ahead=lambda: lock.statements == 0)
        assert eng.jobs[0].window_ahead is None
    reader.join()
    # the read ran on a tree with nothing in flight, and nothing settled
    assert got == [1] and lock.statements == 0
    assert _counter(eng, "barrier_windows_settled_total",
                    by="statement") == 0
    assert _counter(eng, "barrier_windows_ahead_total") == 0
    before = eng.jobs[0].barriers_seen
    for _ in range(5):
        node._tick_once()
        assert eng.jobs[0].window_ahead is not None
    assert _counter(eng, "barrier_windows_ahead_total") == 5
    # five barriers, each after the first sealing the window the tick
    # before it sent ahead
    assert eng.jobs[0].barriers_seen - before == 5
    node.stop()


def test_the_trace_shows_the_window_ahead_and_its_settle(tmp_path):
    """The served tick that sends a window ahead holds
    ``wait_dispatched`` and a ``run_chunks`` with ``ahead=1`` after its
    barrier; a read that finds the window ahead settles it under
    ``read.execute`` (``settle``, attr ``by``: its barrier and drain)."""
    from risingwave_tpu.common.trace import GLOBAL_TRACE

    role, sample_n = GLOBAL_TRACE.role, GLOBAL_TRACE.sample_n
    GLOBAL_TRACE.configure(role="proc", sample_n=1)
    GLOBAL_TRACE.clear()
    node = SingleNode(PlannerConfig(**CONFIG), data_dir=str(tmp_path))
    server = node.start(port=0, ticker=False)
    try:
        c = SimpleClient("127.0.0.1", server.server_address[1])
        for stmt in (BID, VIEWS["q7"][0],
                     f"ALTER SYSTEM SET chunks_per_barrier = {CPB}"):
            c.query(stmt)
        node._tick_once()
        node._tick_once()
        c.query("SELECT * FROM v")
        c.close()
        trees = {}
        for sp in GLOBAL_TRACE.dump():
            trees.setdefault(sp["trace_id"], []).append(sp)
        tick = trees[max((t for t in trees if t.startswith("tick-")),
                         key=lambda t: int(t.split("-")[1]))]
        names = [sp["name"] for sp in tick]
        runs = [sp for sp in tick if sp["name"] == "run_chunks"]
        assert [sp["attrs"].get("ahead") for sp in runs] == [1]
        assert names.index("wait_dispatched") \
            < names.index("drain_uploads")
        (read,) = [spans for t, spans in trees.items()
                   if t.startswith("read-")
                   and any(sp["name"] == "_mv_rows" for sp in spans)]
        by_id = {sp["span_id"]: sp for sp in read}
        (settle,) = [sp for sp in read if sp["name"] == "settle"]
        assert settle["attrs"]["by"] == "statement"
        assert by_id[settle["parent_id"]]["name"] == "read.execute"
        under = {sp["name"] for sp in read
                 if sp["parent_id"] == settle["span_id"]}
        assert {"inject_barrier", "drain_uploads"} <= under
    finally:
        node.stop()
        server.shutdown()
        server.server_close()
        GLOBAL_TRACE.configure(role=role, sample_n=sample_n)
        GLOBAL_TRACE.clear()


@pytest.mark.parametrize("case", ["staged", "checkpoint_frequency_2"])
def test_what_is_not_eligible_never_goes_ahead(case, tmp_path):
    """(e) A staged DagJob (its window is many host-hop dispatches) and
    a barrier that seals no snapshot keep today's serial order."""
    if case == "staged":
        eng = _engine(tmp_path)
        job = eng.jobs[0]
        job._staged_hint = True
        job._rebuild()
        assert job.staged and not job.window_one_dispatch(CPB)
        _ahead(eng, 3)
        assert job.window_ahead is None
        assert _counter(eng, "barrier_windows_ahead_total") == 0
        return
    eng = _engine(tmp_path)
    eng.execute("ALTER SYSTEM SET checkpoint_frequency = 2")
    job = eng.jobs[0]
    went = []
    for _ in range(6):
        eng.tick(1, ahead=lambda: True)
        went.append(job.window_ahead is not None)
        assert went[-1] == job.sealed_snapshot
    assert went.count(True) == 3 and went.count(False) == 3
    assert _counter(eng, "barrier_windows_ahead_total") == 3


#: q8's shape: the join the planner shards over a mesh
PERSON_AUCTION = [
    f"CREATE SOURCE {t} WITH (connector = 'nexmark', nexmark.table = "
    f"'{t.split()[0]}', nexmark.event.rate = '{RATE}')" for t in (
        "person (id BIGINT, name VARCHAR, date_time TIMESTAMP, WATERMARK "
        "FOR date_time AS date_time - INTERVAL '4' SECOND)",
        "auction (id BIGINT, seller BIGINT, reserve BIGINT, expires "
        "TIMESTAMP, date_time TIMESTAMP, WATERMARK FOR date_time AS "
        "date_time - INTERVAL '4' SECOND)")]
Q8 = ("CREATE MATERIALIZED VIEW v AS SELECT p.id AS id, p.name AS name, "
      "a.reserve AS reserve FROM TUMBLE(person, date_time, INTERVAL '1' "
      "SECOND) p JOIN TUMBLE(auction, date_time, INTERVAL '1' SECOND) a "
      "ON p.id = a.seller AND p.window_start = a.window_start")


@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="needs 4 virtual devices")
def test_the_mesh_dag_job_ahead_matches_serial(tmp_path):
    """(f) The mesh DagJob on the CPU's virtual mesh: its window is one
    ``shard_map`` dispatch, goes ahead, and lands where serial does."""
    engines = []
    for side in ("ahead", "serial"):
        eng = Engine(PlannerConfig(
            chunk_capacity=128, join_left_table_size=1 << 12,
            join_left_bucket_cap=4, join_right_table_size=1 << 10,
            join_right_bucket_cap=512, join_out_capacity=1 << 12,
            mv_table_size=4096, mv_ring_size=1 << 15,
        ), data_dir=str(tmp_path / side))
        for stmt in PERSON_AUCTION + ["SET streaming_parallelism = 4", Q8,
                                      "ALTER SYSTEM SET chunks_per_barrier"
                                      " = 4"]:
            eng.execute(stmt)
        engines.append(eng)
    a, b = engines
    job = a.jobs[0]
    assert isinstance(job, DagJob) and job.mesh is not None
    _ahead(a, 4)
    assert job.window_ahead is not None
    assert _counter(a, "barrier_windows_ahead_total") == 4
    b.tick(5)
    _same(a, b)
    assert len(a.execute("SELECT * FROM v")) > 0
