"""One barrier loop, three runtimes: ``BarrierLoop`` (stream/runtime.py)
owns cadence, maintain, commit and recover; ``StreamingJob``, ``DagJob``
and ``ShardedStreamingJob`` are device programs + hooks.  Every case
here runs against each runtime, built through ``Engine`` with a durable
store, and leaves the shared engine as it found it."""

import jax
import pytest

from risingwave_tpu.sql import Engine
from risingwave_tpu.sql.planner import PlannerConfig
from risingwave_tpu.stream.dag import DagJob
from risingwave_tpu.stream.runtime import BarrierLoop, StreamingJob
from risingwave_tpu.stream.sharded import ShardedStreamingJob

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs 4 virtual devices"
)

BID = ("CREATE SOURCE bid (auction BIGINT, price BIGINT, date_time "
       "TIMESTAMP) WITH (connector='nexmark', nexmark.table='bid')")
AGG = ("CREATE MATERIALIZED VIEW v AS SELECT auction, count(*) AS n "
       "FROM bid GROUP BY auction")
PERSON_AUCTION = """
CREATE SOURCE person (
    id BIGINT, name VARCHAR, date_time TIMESTAMP,
    WATERMARK FOR date_time AS date_time - INTERVAL '4' SECOND
) WITH (connector = 'nexmark', nexmark.table = 'person',
        nexmark.event.rate = '2000');
CREATE SOURCE auction (
    id BIGINT, seller BIGINT, reserve BIGINT, expires TIMESTAMP,
    date_time TIMESTAMP,
    WATERMARK FOR date_time AS date_time - INTERVAL '4' SECOND
) WITH (connector = 'nexmark', nexmark.table = 'auction',
        nexmark.event.rate = '2000');
"""
JOIN = """
CREATE MATERIALIZED VIEW v AS
SELECT p.id AS id, p.name AS name, a.reserve AS reserve
FROM TUMBLE(person, date_time, INTERVAL '1' SECOND) p
JOIN TUMBLE(auction, date_time, INTERVAL '1' SECOND) a
ON p.id = a.seller AND p.window_start = a.window_start;
"""

#: runtime -> (class, DDL before the view, the view)
RUNTIMES = {
    "linear": (StreamingJob, [BID], AGG),
    "dag": (DagJob, [PERSON_AUCTION], JOIN),
    "sharded": (ShardedStreamingJob,
                [BID, "SET streaming_parallelism = 4"], AGG),
}


def _sources(job) -> list:
    return list(job.sources.values()) if isinstance(job, DagJob) \
        else [job.source]


def _spans(eng, name: str) -> int:
    try:
        return int(eng.metrics.get("trace_span_total", span=name,
                                   job="v"))
    except KeyError:  # no such span yet
        return 0


def _build(runtime: str, data_dir) -> Engine:
    cls, ddl, view = RUNTIMES[runtime]
    eng = Engine(PlannerConfig(
        chunk_capacity=128, agg_table_size=512, agg_emit_capacity=128,
        join_left_table_size=1 << 10, join_left_bucket_cap=4,
        join_right_table_size=1 << 10, join_right_bucket_cap=64,
        join_out_capacity=1 << 10,
        mv_table_size=1024, mv_ring_size=1 << 12,
    ), data_dir=str(data_dir))
    for stmt in ddl:
        eng.execute(stmt)
    eng.execute(view)
    assert type(eng.jobs[0]) is cls and isinstance(eng.jobs[0], BarrierLoop)
    return eng


@pytest.fixture(scope="module", params=list(RUNTIMES))
def rt(request, tmp_path_factory):
    """(engine, job, what a recover() before any commit left behind):
    the one experiment that needs a job with nothing committed runs
    here, before the first tick."""
    eng = _build(request.param, tmp_path_factory.mktemp(request.param))
    job = eng.jobs[0]
    job.run_chunks(2)
    moved = [s.offset for s in _sources(job)]
    job.recover()
    fresh = {
        "moved": moved,
        "offsets": [s.offset for s in _sources(job)],
        "states_equal_init": jax.tree.all(jax.tree.map(
            lambda a, b: bool((a == b).all()),
            job.states, job._init_states())),
        "epochs": (job.committed_epoch, job.sealed_epoch),
    }
    yield eng, job, fresh
    eng.drain_uploads()


def _cadence(eng, job, fresh):
    """checkpoint_frequency 2, maintenance and snapshot every second
    checkpoint: 8 barriers are 4 checkpoints, 2 maintains, 2 seals."""
    names = ("inject_barrier.dispatch", "_maintain",
             "_maintain.device_wait", "_commit_checkpoint", "snapshot")
    for stmt in ("checkpoint_frequency = 2",
                 "maintenance_interval_checkpoints = 2",
                 "snapshot_interval_checkpoints = 2"):
        eng.execute(f"ALTER SYSTEM SET {stmt}")
    try:
        before = {n: _spans(eng, n) for n in names}
        sealed = job.sealed_epoch
        eng.tick(barriers=8, chunks_per_barrier=1)
        got = {n: _spans(eng, n) - before[n] for n in names}
    finally:
        for stmt in ("checkpoint_frequency = 1",
                     "maintenance_interval_checkpoints = 1",
                     "snapshot_interval_checkpoints = 1"):
            eng.execute(f"ALTER SYSTEM SET {stmt}")
    assert got == {"inject_barrier.dispatch": 8, "_maintain": 2,
                   "_maintain.device_wait": 2, "_commit_checkpoint": 2,
                   "snapshot": 2}, got
    assert job.sealed_epoch > sealed
    assert job._ckpts_since_maintain == job._ckpts_since_snapshot == 0


def _seal_then_commit(eng, job, fresh):
    """Within a tick an epoch is sealed before it is durable; the tick
    returns with every sealed epoch on disk."""
    for _ in range(3):
        job.run_chunks(1)
        job.inject_barrier()
        assert job.sealed_epoch >= job.committed_epoch
    sealed = job.sealed_epoch
    assert sealed > 0
    eng.tick(barriers=2, chunks_per_barrier=1)
    assert job.sealed_epoch == job.committed_epoch > sealed
    assert job.upload_queue_depth() == 0
    assert eng.checkpoint_store.epochs("v")[-1] == job.committed_epoch
    assert eng.job_epochs("v") == {
        "sealed": job.sealed_epoch, "durable": job.committed_epoch,
        "upload_queue": 0,
    }


def _recover_fresh(eng, job, fresh):
    """recover() with nothing committed: initial states, sources at 0."""
    assert all(m > 0 for m in fresh["moved"]), fresh
    assert all(o == 0 for o in fresh["offsets"]), fresh
    assert fresh["states_equal_init"]
    assert fresh["epochs"] == (0, 0)


def _overflow_raises(eng, job, fresh):
    """A lost row is loud: the maintain step reads the one counters
    vector and raises with the job's name and the executor's label;
    recover() then rewinds to the last durable epoch."""
    eng.tick(barriers=1, chunks_per_barrier=1)
    committed = job.committed_epoch
    rows = sorted(map(tuple, eng.execute("SELECT * FROM v")))
    poked = []

    def poke(st):
        if hasattr(st, "_fields") and "overflow" in st._fields \
                and not poked:
            poked.append(type(st).__name__)
            return st._replace(overflow=st.overflow + 1)
        if isinstance(st, tuple) and not hasattr(st, "_fields"):
            return tuple(poke(x) for x in st)
        return st

    job.states = poke(job.states)
    assert poked
    with pytest.raises(RuntimeError, match=r"^v/.*overflow") as exc:
        job.inject_barrier()
    assert any(lb.endswith(".overflow") and lb in str(exc.value)
               for lb in job.counter_labels), (exc.value,
                                               job.counter_labels)
    job.recover()
    assert job.committed_epoch == job.sealed_epoch == committed
    assert sorted(map(tuple, eng.execute("SELECT * FROM v"))) == rows
    eng.tick(barriers=1, chunks_per_barrier=1)
    assert job.committed_epoch > committed


CASES = {
    "cadence": _cadence,
    "seal_then_commit": _seal_then_commit,
    "recover_fresh": _recover_fresh,
    "overflow_raises": _overflow_raises,
}


@pytest.mark.parametrize("case", list(CASES))
def test_barrier_loop(rt, case):
    CASES[case](*rt)


# -- what the sharded runtime gained by inheritance ----------------------
@pytest.fixture(scope="module")
def sharded(tmp_path_factory):
    eng = _build("sharded", tmp_path_factory.mktemp("sharded_own"))
    for _ in range(4):
        eng.tick(barriers=1, chunks_per_barrier=1)
    yield eng, eng.jobs[0]
    eng.drain_uploads()


def test_sharded_spans_carry_the_job_label(sharded):
    """The spans the layer metrics read (`device_wait_ms`,
    `upload_drain_ms`, `ckpt_fetch_ms`, ...) exist for a sharded job."""
    eng, job = sharded
    for name in ("inject_barrier.dispatch", "_maintain",
                 "_maintain.device_wait", "_commit_checkpoint",
                 "snapshot", "drain_uploads", "ckpt_prepare",
                 "ckpt_commit"):
        assert _spans(eng, name) == 4, name
        assert eng.metrics.get("trace_span_seconds_total", span=name,
                               job="v") > 0, name


def test_sharded_checkpoints_one_full_then_deltas(sharded):
    """The shadow digests in one lane a shard and feeds the delta
    store: after the first full, saves are dirty-fraction deltas."""
    eng, job = sharded
    store = eng.checkpoint_store
    epochs = store.epochs("v")
    assert epochs[-1] == job.committed_epoch == job.sealed_epoch
    kinds = [store.checkpoint_kind("v", e) for e in epochs]
    assert "delta" in kinds and set(kinds) <= {"full", "delta"}, kinds
    assert kinds[1:] == ["delta"] * (len(kinds) - 1), kinds
    assert job._shadow is not None and job._shadow.shard_rows == 4
    assert job._shadow.lanes and job._uploader.uploads_total == 4
