"""Introspection (ctl/dashboard analog) + troublemaker chaos tests."""

import pytest

from risingwave_tpu.ctl import cluster_info, describe_job
from risingwave_tpu.sql import Engine
from risingwave_tpu.sql.planner import PlannerConfig


def _engine():
    return Engine(PlannerConfig(
        chunk_capacity=128, agg_table_size=512, agg_emit_capacity=128,
        mv_table_size=512, mv_ring_size=1024,
    ))


def test_describe_job_and_cluster_info():
    eng = _engine()
    eng.execute("""
        CREATE SOURCE t (k BIGINT, v BIGINT) WITH (connector='datagen');
        CREATE MATERIALIZED VIEW m AS
        SELECT k % 8 AS g, count(*) AS n FROM t GROUP BY k % 8;
    """)
    eng.tick(barriers=2, chunks_per_barrier=1)
    info = describe_job(eng.jobs[0])
    assert info["name"] == "m"
    assert info["committed_epoch"] > 0
    execs = {e["executor"]: e for e in info["executors"]}
    agg = next(v for k, v in execs.items() if "HashAgg" in k)
    assert agg["groups"] == 8
    assert agg["overflow"] == 0 and agg["inconsistency"] == 0
    mv = next(v for k, v in execs.items() if "Materialize" in k)
    assert mv["groups"] == 8

    ci = cluster_info(eng)
    assert any(c["name"] == "m" and c["kind"] == "mview"
               for c in ci["catalog"])
    assert ci["system_params"]["checkpoint_frequency"] == 1


def test_ctl_cluster_subcommands(tmp_path):
    """``ctl cluster {workers,jobs,epochs}`` against a RUNNING meta
    (online RPC, mirroring the offline ``ctl storage`` pattern)."""
    from risingwave_tpu.cluster import ComputeWorker, MetaService
    from risingwave_tpu.common.config import RwConfig
    from risingwave_tpu.ctl import (
        cluster_epochs,
        cluster_faults,
        cluster_jobs,
        cluster_workers,
    )

    cfg = RwConfig.from_dict({
        "streaming": {"chunk_size": 64},
        "state": {"agg_table_size": 256, "agg_emit_capacity": 64,
                  "mv_table_size": 256, "mv_ring_size": 512},
    })
    meta = MetaService(str(tmp_path), heartbeat_timeout_s=5.0)
    meta.start(port=0, monitor=False)
    addr = f"127.0.0.1:{meta.rpc_port}"
    w = ComputeWorker(addr, str(tmp_path), config=cfg,
                      heartbeat_interval_s=0.5).start()
    try:
        meta.execute_ddl(
            "CREATE SOURCE t (k BIGINT) WITH (connector='datagen');"
            "CREATE MATERIALIZED VIEW cv AS "
            "SELECT k % 2 AS b, count(*) AS n FROM t GROUP BY k % 2"
        )
        assert meta.tick(1)["committed"]

        workers = cluster_workers(addr)
        assert len(workers) == 1
        assert workers[0]["alive"] is True
        assert workers[0]["jobs"] == ["cv"]
        assert workers[0]["heartbeat_age_s"] >= 0.0

        jobs = cluster_jobs(addr)
        assert jobs == [{
            "name": "cv", "mvs": ["cv"],
            "worker": w.worker_id, "rounds": 1,
            "pinned_epoch": jobs[0]["pinned_epoch"],
            "committed_epoch": jobs[0]["committed_epoch"],
            "sealed_epoch": jobs[0]["sealed_epoch"],
            "durable_epoch": jobs[0]["durable_epoch"],
            "partitions": None,
        }]
        assert jobs[0]["pinned_epoch"] > 0
        assert jobs[0]["pinned_epoch"] == jobs[0]["committed_epoch"]
        # a committed round implies every upload acked: seal == durable
        assert jobs[0]["durable_epoch"] == jobs[0]["sealed_epoch"]

        ep = cluster_epochs(addr)
        assert ep["cluster_epoch"] == 1
        assert ep["manifest_epoch"] == jobs[0]["pinned_epoch"]
        assert ep["failovers"] == 0
        assert ep["jobs"]["cv"]["rounds"] == 1
        # the async-checkpoint split is visible in the ctl surface
        assert ep["jobs"]["cv"]["sealed_epoch"] > 0
        assert ep["jobs"]["cv"]["upload_lag_epochs"] == 0

        # ``ctl cluster faults``: the chaos observability surface —
        # injected/retried/gave-up counters per node (no fabric armed
        # here, so everything reads zero/None but the SHAPE is live)
        fl = cluster_faults(addr)
        assert fl["meta"]["fabric"] is None
        assert fl["meta"]["rpc_retries_total"] == 0
        assert fl["meta"]["rpc_retry_gave_up_total"] == 0
        wf = fl["workers"][str(w.worker_id)] \
            if str(w.worker_id) in fl["workers"] \
            else fl["workers"][w.worker_id]
        assert wf["registrations"] == 1
        assert wf["checkpoint_upload_retries_total"] == 0
    finally:
        w.stop()
        meta.stop()


def test_ctl_cluster_metrics_and_trace(tmp_path):
    """``ctl cluster metrics`` (one aggregated labeled scrape) and
    ``ctl cluster trace --chrome`` (one cross-role round tree) against
    a RUNNING meta, via the same online-RPC helpers the CLI calls."""
    import json

    from risingwave_tpu.cluster import ComputeWorker, MetaService
    from risingwave_tpu.common.config import RwConfig
    from risingwave_tpu.common.trace import GLOBAL_TRACE
    from risingwave_tpu.ctl import cluster_metrics, cluster_trace

    cfg = RwConfig.from_dict({
        "streaming": {"chunk_size": 64},
        "state": {"agg_table_size": 256, "agg_emit_capacity": 64,
                  "mv_table_size": 256, "mv_ring_size": 512},
    })
    role, n = GLOBAL_TRACE.role, GLOBAL_TRACE.sample_n
    GLOBAL_TRACE.configure(role="proc", sample_n=1)
    GLOBAL_TRACE.clear()
    meta = MetaService(str(tmp_path), heartbeat_timeout_s=5.0)
    meta.start(port=0, monitor=False)
    addr = f"127.0.0.1:{meta.rpc_port}"
    w = ComputeWorker(addr, str(tmp_path), config=cfg,
                      heartbeat_interval_s=0.5).start()
    try:
        meta.execute_ddl(
            "CREATE SOURCE t (k BIGINT) WITH (connector='datagen');"
            "CREATE MATERIALIZED VIEW cv AS "
            "SELECT k % 2 AS b, count(*) AS n FROM t GROUP BY k % 2"
        )
        assert meta.tick(1)["committed"]

        text = cluster_metrics(addr)
        assert 'role="meta"' in text
        assert 'barrier_phase_seconds_bucket{job="cv"' in text
        assert text.count("# TYPE cluster_epoch_committed gauge") == 1

        chrome = tmp_path / "round1.json"
        tr = cluster_trace(addr, round=1, chrome=str(chrome))
        assert tr["round"] == 1 and tr["check"]["complete"]
        names = set(tr["check"]["names"])
        assert {"round", "barrier", "commit", "inject_barrier"} <= names
        ct = json.loads(chrome.read_text())
        assert any(e.get("ph") == "X" for e in ct["traceEvents"])
    finally:
        GLOBAL_TRACE.configure(role=role, sample_n=n)
        GLOBAL_TRACE.clear()
        w.stop()
        meta.stop()


def test_ctl_pushdown_online_and_offline_agree(tmp_path, capsys):
    """ISSUE 18 satellite: ``ctl cluster pushdown <meta>`` (online)
    and ``ctl storage policy <dir>`` (offline, over the cold data_dir)
    report the SAME manifest-carried expiry-policy doc — a live
    compactor and an offline ``ctl storage compact`` can never
    disagree on a horizon."""
    import json

    from risingwave_tpu.cluster import ComputeWorker, MetaService
    from risingwave_tpu.common.config import RwConfig
    from risingwave_tpu.ctl import _storage_main, cluster_pushdown

    cfg = RwConfig.from_dict({
        "streaming": {"chunk_size": 64},
        "state": {"agg_table_size": 256, "agg_emit_capacity": 64,
                  "mv_table_size": 256, "mv_ring_size": 512},
    })
    meta = MetaService(str(tmp_path), heartbeat_timeout_s=5.0)
    meta.start(port=0, monitor=False)
    addr = f"127.0.0.1:{meta.rpc_port}"
    w = ComputeWorker(addr, str(tmp_path), config=cfg,
                      heartbeat_interval_s=0.5).start()
    try:
        meta.execute_ddl(
            "CREATE SOURCE t (k BIGINT) WITH (connector='datagen');"
            "CREATE MATERIALIZED VIEW cv WITH (ttl = '1') AS "
            "SELECT k % 2 AS b, count(*) AS n FROM t GROUP BY k % 2"
        )
        assert meta.tick(2)["committed"]

        pd = cluster_pushdown(addr)
        assert pd["version_id"] >= 1
        pol = pd["pushdown"]["policies"]["cv"]
        # the worker derived horizon = max(b) - ttl = 1 - 1 at export;
        # the meta folded the doc into the round's manifest delta
        assert pol["mode"] == "ttl"
        assert pol["column"] == "b" and pol["ttl"] == 1
        assert pol["horizon"] == 0
        assert pd["pushdown"]["rows_elided"] >= 0
        assert pd["serving"] == {}  # no replicas registered here
    finally:
        w.stop()
        meta.stop()

    # OFFLINE round-trip: the policy rides the manifest, so the CLI
    # over the stopped cluster's data_dir prints the identical doc
    _storage_main(["policy", str(tmp_path)])
    off = json.loads(capsys.readouterr().out)
    assert off["policies"]["cv"] == pol
    assert off["version_id"] >= pd["version_id"]


def test_troublemaker_corruption_is_caught():
    """Injected op corruption must surface via consistency counters,
    never silently wrong results (ref RW_UNSAFE_ENABLE_INSANE_MODE)."""
    from risingwave_tpu.expr.agg import AggCall
    from risingwave_tpu.expr.node import col
    from risingwave_tpu.stream.fragment import Fragment
    from risingwave_tpu.stream.hash_join import HashJoinExecutor
    from risingwave_tpu.stream.troublemaker import TroublemakerExecutor
    from risingwave_tpu.common.chunk import Chunk
    from risingwave_tpu.common.types import DataType, Schema
    import numpy as np

    schema = Schema.of(("k", DataType.INT64), ("v", DataType.INT64))
    tm = TroublemakerExecutor(schema, seed=7, ratio=4)
    frag = Fragment([tm])
    st = frag.init_states()
    arrays = [np.arange(64, dtype=np.int64),
              np.arange(64, dtype=np.int64)]
    st, out = frag.step(st, Chunk.from_numpy(schema, arrays))
    ops = [r[0] for r in out.to_rows()]
    assert ops.count(1) > 0  # some inserts flipped to deletes

    # the corrupted stream hits a join side: deletes of never-inserted
    # rows must be COUNTED as inconsistencies
    join = HashJoinExecutor(
        schema, schema, [col("k")], [col("k")],
        table_size=256, bucket_cap=4, out_capacity=256,
    )
    jst = join.init_state()
    jst, _ = join.apply(jst, out, "left")
    assert int(jst.left.inconsistency) > 0


def test_ctl_storage_scrub_offline_finds_planted_bit_flip(tmp_path):
    """Integrity satellite: ``ctl storage scrub <dir>`` verifies every
    SST, the version log chain, and every checkpoint object OFFLINE —
    a planted bit-flip is reported, a clean dir passes."""
    import os

    import numpy as np

    from risingwave_tpu.ctl import storage_scrub
    from risingwave_tpu.storage.checkpoint_store import CheckpointStore
    from risingwave_tpu.storage.hummock import (
        HummockStorage,
        LocalFsObjectStore,
    )

    data_dir = str(tmp_path)
    storage = HummockStorage(
        LocalFsObjectStore(os.path.join(data_dir, "hummock")))
    keys = [f"k{i:04d}".encode() for i in range(150)]
    storage.write_batch([(k, b"v" + k) for k in keys], epoch=1)
    ck = CheckpointStore(data_dir, keep_epochs=8)
    ck.save("job", 1, {"a": np.arange(64, dtype=np.int64)},
            {"offset": 1})

    clean = storage_scrub(data_dir)
    assert clean["ok"] is True
    assert clean["ssts_verified"] == 1
    assert clean["checkpoints_verified"] == 2  # npz + meta
    assert clean["corrupt"] == []

    # plant one bit flip in the SST and one in the checkpoint object
    sst_key = next(iter(storage.versions.current.all_keys()))
    with open(os.path.join(data_dir, "hummock", sst_key),
              "r+b") as f:
        f.seek(20)
        b = f.read(1)
        f.seek(20)
        f.write(bytes([b[0] ^ 2]))
    with open(os.path.join(data_dir, "job", "epoch_1.npz"),
              "r+b") as f:
        f.seek(12)
        f.write(b"\x3c")

    dirty = storage_scrub(data_dir)
    assert dirty["ok"] is False
    kinds = sorted(k for k, _ in dirty["corrupt"])
    assert kinds == ["checkpoint", "sst"]
    assert ("sst", sst_key) in dirty["corrupt"]
    assert ("checkpoint", "job/epoch_1.npz") in dirty["corrupt"]
