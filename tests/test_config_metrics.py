"""Config layers, metrics, EXPLAIN, engine-level durability/recovery."""

import numpy as np
import pytest

from risingwave_tpu.common.config import (
    RwConfig,
    SessionConfig,
    SystemParams,
)
from risingwave_tpu.common.metrics import MetricsRegistry
from risingwave_tpu.sql import Engine
from risingwave_tpu.sql.planner import PlannerConfig


def test_rw_config_from_dict():
    cfg = RwConfig.from_dict({
        "streaming": {"chunk_size": 1024},
        "state": {"agg_table_size": 256},
    })
    assert cfg.streaming.chunk_size == 1024
    assert cfg.state.agg_table_size == 256
    with pytest.raises(KeyError):
        RwConfig.from_dict({"streaming": {"nope": 1}})


def test_system_params_mutability():
    sp = SystemParams()
    assert sp.get("barrier_interval_ms") == 1000
    sp.set("checkpoint_frequency", 5)
    assert sp.get("checkpoint_frequency") == 5
    with pytest.raises(KeyError):
        sp.set("unknown", 1)


def test_session_config():
    sc = SessionConfig()
    sc.set("query_epoch", 42)
    assert sc.get("query_epoch") == 42
    assert any(k == "timezone" for k, _, _ in sc.show_all())


def test_metrics_registry():
    m = MetricsRegistry()
    m.inc("rows", 10, job="a")
    m.inc("rows", 5, job="a")
    m.set_gauge("epoch", 7, job="a")
    m.observe("lat", 0.003, job="a")
    m.observe("lat", 0.2, job="a")
    assert m.get("rows", job="a") == 15
    assert m.get("epoch", job="a") == 7
    assert m.quantile("lat", 0.5, job="a") <= 0.005
    text = m.render_prometheus()
    assert 'rows{job="a"} 15' in text
    assert "lat_count" in text


def test_engine_set_show_explain():
    eng = Engine(PlannerConfig(chunk_capacity=64))
    eng.execute("""
        CREATE SOURCE t (k BIGINT, v BIGINT) WITH (connector='datagen');
    """)
    eng.execute("SET query_epoch = 9")
    assert eng.session_config.get("query_epoch") == 9
    eng.execute("ALTER SYSTEM SET checkpoint_frequency = 3")
    assert eng.system_params.get("checkpoint_frequency") == 3
    params = eng.execute("SHOW PARAMETERS")
    assert any(row[0] == "barrier_interval_ms" for row in params)

    plan = eng.execute(
        "EXPLAIN SELECT k, count(*) FROM t GROUP BY k"
    )
    text = "\n".join(r[0] for r in plan)
    assert "HashAggExecutor" in text and "MaterializeExecutor" in text


def test_engine_durable_recovery(tmp_path):
    """Engine restart: catalog re-created via DDL, state via recover()."""
    ddl = """
        CREATE SOURCE t (k BIGINT, v BIGINT) WITH (connector='datagen');
        CREATE MATERIALIZED VIEW m AS
        SELECT k % 2 AS b, count(*) AS n FROM t GROUP BY k % 2;
    """
    cfg = PlannerConfig(chunk_capacity=64, agg_table_size=256,
                        agg_emit_capacity=64, mv_table_size=256)
    eng = Engine(cfg, data_dir=str(tmp_path))
    eng.execute(ddl)
    eng.tick(barriers=2, chunks_per_barrier=1)
    want = sorted(eng.execute("SELECT b, n FROM m"))

    # restart: the fresh engine bootstraps DDL + state from data_dir
    eng2 = Engine(cfg, data_dir=str(tmp_path))
    assert sorted(eng2.execute("SELECT b, n FROM m")) == want
    # continues from the checkpointed source offset, not from zero
    eng2.tick(barriers=1, chunks_per_barrier=1)
    rows = dict(eng2.execute("SELECT b, n FROM m"))
    assert rows[0] + rows[1] == 3 * 64


def test_engine_metrics_populated():
    eng = Engine(PlannerConfig(chunk_capacity=64))
    eng.execute("""
        CREATE SOURCE t (k BIGINT) WITH (connector='datagen');
        CREATE MATERIALIZED VIEW m AS SELECT k FROM t;
    """)
    eng.tick(barriers=2, chunks_per_barrier=1)
    assert eng.metrics.get("stream_rows_total", job="m") >= 128
    assert eng.metrics.get("committed_epoch", job="m") > 0


def test_metrics_timer_context():
    m = MetricsRegistry()
    with m.timer("op_seconds", stage="merge"):
        pass
    assert m.quantile("op_seconds", 0.5, stage="merge") <= 0.005
    assert "op_seconds_count" in m.render_prometheus()


def test_storage_service_metrics_and_exporter(tmp_path):
    """Compactor/GC/stall/bloom metrics flow into the engine registry
    and out the Prometheus text exporter (ISSUE 1 satellite)."""
    import struct

    eng = Engine(PlannerConfig(chunk_capacity=64),
                 data_dir=str(tmp_path))
    h = eng.hummock
    h.l0_trigger = 2
    h.stall_l0 = 3
    for i in range(4):
        h.write_batch([(struct.pack(">I", j), b"v")
                       for j in range(i, i + 20)], epoch=i + 1)
    h.wait_below_stall(timeout=0.02)      # times out: records stall
    while h.compact_once():
        pass
    assert h.get(struct.pack(">I", 0)) == b"v"
    assert h.get(struct.pack(">I", 999)) is None
    eng.storage_vacuum()

    m = eng.metrics
    # 4 ingest uploads + the compaction outputs
    assert m.get("storage_sst_uploads_total") >= 4
    assert m.get("storage_compaction_tasks_total", level="0") >= 1
    assert m.get("storage_compaction_bytes_total") > 0
    assert m.get("storage_gc_objects_total") >= 1
    assert m.get("storage_write_stall_seconds_total") > 0
    assert m.get("storage_l0_runs") == 0
    assert m.get("storage_version_id") >= 5
    assert m.get("storage_pinned_versions") == 0
    assert m.get("storage_bloom_filter_total", result="hit") >= 1

    text = m.render_prometheus()
    for name in (
        'storage_compaction_tasks_total{level="0"}',
        "storage_compaction_bytes_total",
        "storage_gc_objects_total",
        "storage_write_stall_seconds_total",
        "storage_l0_runs",
        "storage_sst_files",
    ):
        assert name in text, name


def test_serving_metrics_exported(tmp_path):
    """Serving-tier observability (ISSUE 5 satellite): pinned epoch,
    block-cache hit/miss/bytes, read counters and the per-read latency
    histogram flow out the replica's Prometheus exporter."""
    from risingwave_tpu.serve import ServingWorker

    eng = Engine(PlannerConfig(chunk_capacity=64,
                               agg_table_size=256,
                               agg_emit_capacity=64,
                               mv_table_size=256),
                 data_dir=str(tmp_path))
    eng.execute(
        "CREATE SOURCE t (k BIGINT, v BIGINT) "
        "WITH (connector='datagen');"
        "CREATE MATERIALIZED VIEW sm AS "
        "SELECT k % 4 AS g, count(*) AS n FROM t GROUP BY k % 4"
    )
    eng.tick(barriers=2, chunks_per_barrier=1)
    eng.storage_export_mv("sm")

    sv = ServingWorker(None, str(tmp_path)).start()
    try:
        for _ in range(3):
            cols, rows, epoch = sv.read("SELECT g, n FROM sm")
            assert len(rows) == 4 and epoch > 0
        sv.read("SELECT g, n FROM sm WHERE g = 1")
        m = sv.metrics
        assert m.get("serving_reads_total") == 4
        # the repeat scans HIT the result cache (same sql, same vid)
        assert m.get("serving_result_cache_hits") >= 2
        assert m.get("serving_result_cache_misses") >= 1
        assert m.get("serving_result_cache_bytes") > 0
        assert m.get("serving_result_cache_entries") >= 1
        assert 0.0 < m.get("serving_result_cache_hit_ratio") <= 1.0
        assert m.get("serving_pinned_epoch") > 0
        assert m.get("serving_block_cache_hits") >= 1
        assert m.get("serving_block_cache_misses") >= 1
        assert m.get("serving_block_cache_fill_bytes") > 0
        assert 0.0 < m.get("serving_block_cache_hit_ratio") <= 1.0
        assert m.get("serving_bloom_filter_total", result="hit") >= 1
        assert m.quantile("serving_read_seconds", 0.5) < float("inf")

        text = m.render_prometheus()
        for name in (
            "serving_reads_total",
            "serving_pinned_epoch",
            "serving_block_cache_hit_ratio",
            "serving_block_cache_fill_bytes",
            "serving_read_seconds_count",
            "serving_result_cache_hit_ratio",
            "serving_result_cache_bytes",
        ):
            assert name in text, name
        # error counter absent until an error actually happens
        assert sv.read_errors == 0
    finally:
        sv.stop()


def test_pushdown_metrics_exported(tmp_path):
    """Pushdown-plane observability (ISSUE 18 satellite): the elision
    counter is labeled by WHERE the work happened (compactor-side TTL
    drops vs replica-side block-walk filtering), block skips count,
    and the negative cache exports hit/entry gauges."""
    from risingwave_tpu.serve import ServingWorker

    eng = Engine(PlannerConfig(chunk_capacity=64, agg_table_size=256,
                               agg_emit_capacity=64, mv_table_size=256),
                 data_dir=str(tmp_path))
    eng.execute("CREATE TABLE e (seq BIGINT, v BIGINT, "
                "PRIMARY KEY (seq)) WITH (retract='true')")
    eng.execute("CREATE MATERIALIZED VIEW pe WITH (ttl = '10') AS "
                "SELECT seq, v FROM e")
    eng.execute("INSERT INTO e VALUES " +
                ", ".join(f"({i}, {i * 3})" for i in range(10)))
    eng.execute("FLUSH")
    eng.storage_export_mv("pe")
    # second cycle advances the horizon to 19: what the FIRST export
    # wrote below it is now the compactor's to drop
    eng.execute("INSERT INTO e VALUES " +
                ", ".join(f"({i}, {i * 3})" for i in range(10, 30)))
    eng.execute("FLUSH")
    eng.storage_export_mv("pe")
    eng.hummock.l0_trigger = 1
    while eng.hummock.compact_once():
        pass
    m = eng.metrics
    assert m.get("pushdown_rows_elided_total", where="compactor") > 0
    assert 'pushdown_rows_elided_total{where="compactor"}' \
        in m.render_prometheus()

    sv = ServingWorker(None, str(tmp_path)).start()
    try:
        # residual (non-pk) predicate: the block-walk evaluator runs
        # replica-side and counts the rows the client never saw
        _, rows, _ = sv.read("SELECT seq, v FROM pe WHERE v >= 66")
        assert rows and all(r[1] >= 66 for r in rows)
        sm = sv.metrics
        assert sm.get("pushdown_rows_elided_total", where="replica") > 0
        assert sm.get("pushdown_blocks_skipped_total") >= 0
        # missing-pk probes populate, then hit, the negative cache
        sv.multi_get("pe", [[990], [991]], cols=["seq", "v"])
        sv.multi_get("pe", [[990], [991]], cols=["seq", "v"])
        assert sm.get("serving_negative_cache_hits") >= 1
        assert sm.get("serving_negative_cache_entries") >= 1
        text = sm.render_prometheus()
        for name in (
            'pushdown_rows_elided_total{where="replica"}',
            "pushdown_blocks_skipped_total",
            "serving_negative_cache_hits",
            "serving_negative_cache_entries",
        ):
            assert name in text, name
    finally:
        sv.stop()


def test_single_node_orderly_stop_commits(tmp_path):
    """ISSUE 3 satellite: SingleNode.stop() seals + commits a final
    barrier — progress made since the last checkpoint survives a clean
    exit instead of being replayed-or-lost."""
    from risingwave_tpu.server import SingleNode

    cfg = PlannerConfig(chunk_capacity=64, agg_table_size=256,
                        agg_emit_capacity=64, mv_table_size=256)
    n = SingleNode(cfg, data_dir=str(tmp_path))
    n.engine.execute(
        "CREATE SOURCE t (k BIGINT) WITH (connector='datagen');"
        "CREATE MATERIALIZED VIEW m AS SELECT count(*) AS c FROM t"
    )
    n.tick(barriers=1, chunks_per_barrier=1)     # committed: 64 rows
    n.engine.jobs[0].run_chunk()                 # past the checkpoint
    n.stop()                                     # must commit 128

    eng2 = Engine(cfg, data_dir=str(tmp_path))
    assert eng2.execute("SELECT c FROM m") == [(128,)]


def test_cluster_metrics_exported(tmp_path):
    """ISSUE 3 satellite: control-plane observability — per-worker
    heartbeat age, live worker count, in-flight vs committed cluster
    epoch, barrier commit latency, failovers total — through the meta
    registry and the Prometheus exporter."""
    import time

    from risingwave_tpu.cluster import ComputeWorker, MetaService
    from risingwave_tpu.common.config import RwConfig

    cfg = RwConfig.from_dict({
        "streaming": {"chunk_size": 64},
        "state": {"agg_table_size": 256, "agg_emit_capacity": 64,
                  "mv_table_size": 256, "mv_ring_size": 512},
    })
    meta = MetaService(str(tmp_path), heartbeat_timeout_s=0.8)
    meta.start(port=0, monitor=False)
    w = ComputeWorker(f"127.0.0.1:{meta.rpc_port}", str(tmp_path),
                      config=cfg, heartbeat_interval_s=0.2).start()
    try:
        meta.execute_ddl(
            "CREATE SOURCE t (k BIGINT) WITH (connector='datagen');"
        )
        meta.execute_ddl(
            "CREATE MATERIALIZED VIEW cm AS "
            "SELECT k % 2 AS b, count(*) AS n FROM t GROUP BY k % 2"
        )
        for _ in range(2):
            assert meta.tick(1)["committed"]
        meta.check_heartbeats()

        m = meta.metrics
        assert m.get("cluster_live_workers") == 1
        assert m.get("cluster_jobs") == 1
        assert m.get("cluster_epoch_in_flight") == 2
        assert m.get("cluster_epoch_committed") == 2
        assert m.get("cluster_manifest_epoch") > 0
        age = m.get("cluster_worker_heartbeat_age_seconds",
                    worker=str(w.worker_id))
        assert 0.0 <= age < 0.8
        assert m.quantile("cluster_barrier_commit_seconds", 0.5) \
            < float("inf")

        # kill the worker silently: failover counter fires, its
        # heartbeat-age series is retired, live count drops to 0
        w.stop()
        deadline = time.monotonic() + 10
        while meta.failovers == 0:
            assert time.monotonic() < deadline
            time.sleep(0.1)
            meta.check_heartbeats()
        assert m.get("cluster_failovers_total") == 1
        assert m.get("cluster_live_workers") == 0
        with pytest.raises(KeyError):
            m.get("cluster_worker_heartbeat_age_seconds",
                  worker=str(w.worker_id))

        text = m.render_prometheus()
        for name in (
            "cluster_live_workers",
            "cluster_jobs",
            "cluster_epoch_in_flight",
            "cluster_epoch_committed",
            "cluster_manifest_epoch",
            "cluster_failovers_total",
            "cluster_barrier_commit_seconds_count",
        ):
            assert name in text, name
    finally:
        w.stop()
        meta.stop()


def test_worker_removal_retires_per_worker_series(tmp_path):
    """ISSUE 7 satellite: after a worker is REMOVED — scale-in
    deregistration or death — every one of its per-worker labeled
    series (heartbeat age, vnode count) leaves the scrape surface
    instead of lingering forever."""
    import time

    from risingwave_tpu.cluster import ComputeWorker, MetaService

    meta = MetaService(str(tmp_path), heartbeat_timeout_s=0.8,
                       scale_partitioning=True, n_vnodes=16)
    meta.start(port=0, monitor=False)
    addr = f"127.0.0.1:{meta.rpc_port}"
    w1 = ComputeWorker(addr, str(tmp_path),
                       heartbeat_interval_s=0.2).start()
    w2 = ComputeWorker(addr, str(tmp_path),
                       heartbeat_interval_s=0.2).start()
    try:
        meta.scale(2)  # cuts the map: per-worker vnode gauges exist
        meta.check_heartbeats()
        m = meta.metrics
        for w in (w1, w2):
            assert m.get("cluster_worker_vnodes",
                         worker=str(w.worker_id)) == 8
            assert m.get("cluster_worker_heartbeat_age_seconds",
                         worker=str(w.worker_id)) >= 0.0

        # graceful deregistration (the scale-in decommission path);
        # the process stops FIRST — a live worker would re-register
        # through its heartbeat loop, which is exactly the point of
        # that loop
        w2.stop()
        meta.rpc_unregister_worker(w2.worker_id)
        text = m.render_prometheus()
        assert f'worker="{w2.worker_id}"' not in text
        assert f'worker="{w1.worker_id}"' in text
        for name in ("cluster_worker_heartbeat_age_seconds",
                     "cluster_worker_vnodes"):
            with pytest.raises(KeyError):
                m.get(name, worker=str(w2.worker_id))
        assert w2.worker_id not in meta.workers  # fully removed

        # death path retires the same series
        w1.stop()
        deadline = time.monotonic() + 10
        while meta.metrics.get("cluster_live_workers") > 0:
            assert time.monotonic() < deadline
            time.sleep(0.1)
            meta.check_heartbeats()
        assert f'worker="{w1.worker_id}"' \
            not in m.render_prometheus()
    finally:
        w1.stop()
        w2.stop()
        meta.stop()


def test_fault_and_retry_gauges_exported(tmp_path):
    """ISSUE 6 satellite: the chaos fabric's injected counters and the
    unified RetryPolicy's budget spend are first-class metrics — per-op
    retry counters plus process gauges on the meta's scrape surface
    (the ``ctl cluster faults`` backing data)."""
    from risingwave_tpu.cluster import MetaService
    from risingwave_tpu.common import faults as faults_mod
    from risingwave_tpu.common.faults import (
        FaultFabric,
        FaultInjected,
        RetryPolicy,
    )

    meta = MetaService(str(tmp_path))
    fab = faults_mod.install(FaultFabric(seed=3))
    try:
        fab.fail_rpc(substr="a>b/", mode="drop", times=2)
        for _ in range(2):
            with pytest.raises(FaultInjected):
                fab.rpc_before_send("a>b/barrier")

        # spend the meta's retry budget against a dead endpoint
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise ConnectionError("transient")
            return "ok"

        meta.retry.sleeper = lambda _: None
        assert meta.retry.run(flaky, label="barrier") == "ok"

        fl = meta.cluster_faults()
        assert fl["meta"]["fabric"]["injected_total"] == 2
        assert fl["meta"]["rpc_retries_total"] == 2

        m = meta.metrics
        assert m.get("faults_injected_total") == 2
        assert m.get("rpc_retries_spent_total") == 2
        assert m.get("rpc_retry_gave_up_spent_total") == 0
        assert m.get("rpc_retries_total", op="barrier") == 2
        text = m.render_prometheus()
        for name in ("faults_injected_total",
                     "rpc_retries_spent_total",
                     "rpc_retry_gave_up_spent_total",
                     "rpc_retries_total"):
            assert name in text, name

        # a per-policy budget exhaustion lands on the gave-up counter
        p = RetryPolicy(max_attempts=2, base_delay_s=0.001,
                        metrics=m, sleeper=lambda _: None)

        def dead():
            raise ConnectionError("down")

        with pytest.raises(ConnectionError):
            p.run(dead, label="upload")
        assert m.get("rpc_retry_gave_up_total", op="upload") == 1
    finally:
        faults_mod.install(None)


def test_meta_store_crash_safe_append_and_torn_tail(tmp_path):
    """ISSUE 3 satellite: a worker killed mid-append leaves a torn
    trailing JSONL line — replay drops it (with a warning) instead of
    poisoning recovery; damage anywhere else stays loud."""
    import pytest as _pytest

    from risingwave_tpu.meta.store import MetaStore, MetaStoreCorruption

    store = MetaStore(str(tmp_path))
    store.append_ddl("CREATE TABLE a (x BIGINT)")
    store.append_ddl("CREATE TABLE b (x BIGINT)")
    path = store._ddl_path
    # crash mid-append: truncated JSON, no trailing newline
    with open(path, "a") as f:
        f.write('{"sql": "CREATE TAB')
    assert store.ddl_log() == [
        "CREATE TABLE a (x BIGINT)", "CREATE TABLE b (x BIGINT)",
    ]
    # appending after recovery overwrites nothing and replays cleanly
    # (the torn bytes stay, but the reader stops at them — matching
    # the write path, which only ever appends)
    with open(path) as f:
        lines = f.read().splitlines()
    assert len(lines) == 3

    # a valid-JSON line missing its newline was also never acked
    store2 = MetaStore(str(tmp_path / "t2"))
    store2.append_ddl("CREATE TABLE c (x BIGINT)")
    with open(store2._ddl_path, "a") as f:
        f.write('{"sql": "SET x = 1"}')  # no \n: fsync never covered it
    assert store2.ddl_log() == ["CREATE TABLE c (x BIGINT)"]

    # corruption MID-log (not a crash artifact) must raise, not
    # silently truncate acknowledged history
    store3 = MetaStore(str(tmp_path / "t3"))
    store3.append_ddl("CREATE TABLE d (x BIGINT)")
    store3.append_ddl("CREATE TABLE e (x BIGINT)")
    with open(store3._ddl_path) as f:
        content = f.read()
    with open(store3._ddl_path, "w") as f:
        f.write(content.replace('TABLE d', 'TAB"LE d', 1))
    with _pytest.raises(MetaStoreCorruption):
        store3.ddl_log()


def test_checkpoint_pipeline_metrics_exported(tmp_path):
    """ISSUE 4 satellite: checkpoint-pipeline observability — upload
    queue depth, sealed-vs-committed epoch lag, snapshot dirty-block
    ratio, and snapshot/upload seconds — through the engine registry
    and the Prometheus exporter."""
    eng = Engine(PlannerConfig(chunk_capacity=64, agg_table_size=256,
                               agg_emit_capacity=64, mv_table_size=256),
                 data_dir=str(tmp_path))
    eng.execute(
        "CREATE SOURCE t (k BIGINT) WITH (connector='datagen');"
        "CREATE MATERIALIZED VIEW m AS "
        "SELECT k % 2 AS b, count(*) AS n FROM t GROUP BY k % 2"
    )
    eng.tick(barriers=3, chunks_per_barrier=1)
    eng.collect_checkpoint_metrics()
    m = eng.metrics
    job = eng.jobs[0].name
    assert m.get("sealed_epoch", job=job) > 0
    assert m.get("sealed_epoch", job=job) \
        == m.get("committed_epoch", job=job)
    # tick() drains at the batch boundary: lag and queue are 0
    assert m.get("checkpoint_seal_lag_epochs", job=job) == 0
    assert m.get("checkpoint_upload_queue_depth", job=job) == 0
    assert m.get("checkpoint_uploads_total", job=job) >= 3
    assert m.get("checkpoint_upload_seconds_total", job=job) > 0
    ratio = m.get("snapshot_dirty_block_ratio", job=job)
    assert 0.0 <= ratio <= 1.0
    assert m.get("snapshot_shadow_blocks", job=job) > 0
    # histogram from the uploader thread
    assert m.quantile("checkpoint_upload_seconds", 0.5, job=job) \
        < float("inf")

    text = m.render_prometheus()
    for name in (
        "sealed_epoch",
        "checkpoint_seal_lag_epochs",
        "checkpoint_upload_queue_depth",
        "checkpoint_uploads_total",
        "checkpoint_upload_seconds_total",
        "snapshot_dirty_block_ratio",
        "snapshot_shadow_blocks",
        "checkpoint_upload_seconds_count",
    ):
        assert name in text, name

    # steady-state durable epochs persist as deltas (the shared-digest
    # incremental path is live end-to-end)
    store = eng.checkpoint_store
    kinds = [store.checkpoint_kind(job, e) for e in store.epochs(job)]
    assert "delta" in kinds, kinds


def test_join_path_metrics_exported():
    """ISSUE 2 satellite: the join path exports probes-per-chunk, pool
    occupancy, emission-window fill, and drain-loop gauges through the
    Prometheus registry (Engine.collect_join_metrics +
    audit_join_probe_counts)."""
    eng = Engine(PlannerConfig(
        chunk_capacity=128,
        join_left_table_size=1 << 10, join_right_table_size=1 << 10,
        join_pool_size=1 << 12, join_out_capacity=128,
        mv_table_size=1 << 10, mv_ring_size=1 << 12,
    ))
    eng.execute("""
    CREATE SOURCE person (
        id BIGINT, name VARCHAR, date_time TIMESTAMP,
        WATERMARK FOR date_time AS date_time - INTERVAL '4' SECOND
    ) WITH (connector = 'nexmark', nexmark.table = 'person',
            nexmark.event.rate = '1000000');
    CREATE SOURCE auction (
        id BIGINT, seller BIGINT, reserve BIGINT, expires TIMESTAMP,
        date_time TIMESTAMP,
        WATERMARK FOR date_time AS date_time - INTERVAL '4' SECOND
    ) WITH (connector = 'nexmark', nexmark.table = 'auction',
            nexmark.event.rate = '1000000');
    CREATE MATERIALIZED VIEW jm AS
    SELECT p.id AS id, a.reserve AS reserve
    FROM TUMBLE(person, date_time, INTERVAL '1' SECOND) p
    JOIN TUMBLE(auction, date_time, INTERVAL '1' SECOND) a
    ON p.id = a.seller AND p.window_start = a.window_start;
    """)
    eng.tick(barriers=2, chunks_per_barrier=1)

    # trace-time audit: the fused (hash, rank) update compiles exactly
    # ONE lookup_or_insert per append-only side (acceptance criterion)
    audit = eng.audit_join_probe_counts()
    assert audit, "q8-shaped plan should have pool join sides"
    for stats in audit.values():
        assert stats == {"lookup": 0, "lookup_or_insert": 1}

    eng.collect_join_metrics()
    m = eng.metrics
    text = m.render_prometheus()
    for name in (
        "join_probe_calls_per_chunk",
        "join_probe_iters_per_chunk",
        "join_pool_occupancy",
        "join_emit_window_fill_ratio",
        "join_drain_windows_per_chunk",
    ):
        assert name in text, name
    # both pool sides occupy some of their pools after two barriers
    job = eng.jobs[0].name
    from risingwave_tpu.stream.dag import JoinNode
    jidx = next(i for i, n in enumerate(eng.jobs[0].nodes)
                if isinstance(n, JoinNode))
    for side in ("left", "right"):
        occ = m.get("join_pool_occupancy", job=job, node=str(jidx),
                    side=side)
        assert 0.0 < occ <= 1.0


def test_integrity_and_scrub_metrics_exported(tmp_path):
    """Integrity satellite: the full metric surface — typed error
    counters, quarantine gauge, scrub progress gauges, repair
    counters — lands on the Prometheus scrape surface."""
    import os

    from risingwave_tpu.storage.checkpoint_store import CheckpointStore
    from risingwave_tpu.storage.hummock import (
        HummockStorage,
        LocalFsObjectStore,
    )
    from risingwave_tpu.storage.hummock.scrubber import ScrubberService

    m = MetricsRegistry()
    storage = HummockStorage(
        LocalFsObjectStore(str(tmp_path / "hummock")), metrics=m)
    keys = [f"k{i:04d}".encode() for i in range(200)]
    storage.write_batch([(k, b"v" + k) for k in keys], epoch=1)

    # the meta's wiring, in miniature: scrub detection -> typed
    # counter + durable quarantine note
    def on_corruption(kind, key, _ctx):
        m.inc("integrity_errors_total", kind=kind)
        storage.quarantine_sst(key, "scrub mismatch")

    scrub = ScrubberService(storage, metrics=m, pace_s=0.0,
                            on_corruption=on_corruption)
    assert scrub.run_once()["corrupt"] == []

    sst_key = next(iter(storage.versions.current.all_keys()))
    path = os.path.join(str(tmp_path / "hummock"), sst_key)
    with open(path, "r+b") as f:
        f.seek(40)
        f.write(b"\x99")
    assert scrub.run_once()["corrupt"] == [("sst", sst_key)]

    # checkpoint corruption + self-healing rewind (repair counter)
    ck = CheckpointStore(str(tmp_path / "ck"), keep_epochs=8,
                         metrics=m)
    for e in (1, 2):
        ck.save("j", e, {"a": np.arange(32, dtype=np.int64)},
                {"offset": e})
    with open(os.path.join(str(tmp_path / "ck"), "j",
                           "epoch_2.npz"), "r+b") as f:
        f.seek(10)
        f.write(b"\x77")
    assert ck.load("j")[0] == 1  # healed back to the verified epoch

    rendered = m.render_prometheus()
    assert 'integrity_errors_total{kind="sst"}' in rendered
    assert 'integrity_errors_total{kind="checkpoint"}' in rendered
    assert 'integrity_repairs_total{kind="checkpoint_rewind"}' \
        in rendered
    assert "quarantined_objects" in rendered
    assert m.get("quarantined_objects") >= 1
    assert "scrub_objects_verified_total" in rendered
    assert m.get("scrub_objects_verified_total") >= 1
    assert "scrub_cursor_age_s" in rendered
    assert 'scrub_corruptions_total{kind="sst"}' in rendered
    assert "scrub_cycles_total" in rendered


def test_dag_fused_fallback_counter_exported():
    """ISSUE 9 satellite: a DagJob window that cannot run as ONE fused
    dispatch (host-chunk DML sources here) is counted by reason and
    exported as ``dag_fused_fallback_total{reason}`` — the silent
    per-chunk degradation becomes observable."""
    eng = Engine(PlannerConfig(
        chunk_capacity=64,
        join_table_size=512, join_bucket_cap=16,
        join_out_capacity=1 << 10,
        mv_table_size=512, mv_ring_size=1 << 12,
    ))
    eng.execute("CREATE TABLE lt (k BIGINT, v BIGINT)")
    eng.execute("CREATE TABLE rt (k BIGINT, w BIGINT)")
    eng.execute("INSERT INTO lt VALUES (1, 10), (2, 20)")
    eng.execute("INSERT INTO rt VALUES (1, 100), (2, 200)")
    eng.execute(
        "CREATE MATERIALIZED VIEW jm AS SELECT lt.k AS k, lt.v AS v, "
        "rt.w AS w FROM lt JOIN rt ON lt.k = rt.k"
    )
    eng.tick(barriers=1, chunks_per_barrier=4)
    job = eng.jobs[0]
    assert job.fused_fallbacks.get("host_chunk_source", 0) >= 1
    got = eng.metrics.get("dag_fused_fallback_total", job=job.name,
                          reason="host_chunk_source")
    assert got >= 1
    assert "dag_fused_fallback_total" in eng.metrics.render_prometheus()


def test_exchange_metrics_exported_and_retired(tmp_path):
    """Exchange-lite satellite: the sliced peer exchange exports
    per-EDGE counters (rows/bytes/batches) plus a per-batch latency
    histogram on the sending worker, and the meta mirrors per-worker
    exchange gauges that are RETIRED with the worker — exactly the
    PR-7/PR-10 per-peer series discipline."""
    from risingwave_tpu.cluster import ComputeWorker, MetaService

    meta = MetaService(str(tmp_path), heartbeat_timeout_s=60.0,
                       scale_partitioning=True, n_vnodes=16)
    meta.start(port=0, monitor=False)
    addr = f"127.0.0.1:{meta.rpc_port}"
    w1 = ComputeWorker(addr, str(tmp_path),
                       heartbeat_interval_s=5.0).start()
    w2 = ComputeWorker(addr, str(tmp_path),
                       heartbeat_interval_s=5.0).start()
    try:
        meta.scale(2)
        meta.execute_ddl("CREATE TABLE t (k BIGINT, v BIGINT)")
        meta.execute_ddl(
            "CREATE MATERIALIZED VIEW agg AS "
            "SELECT k, count(*) AS n FROM t GROUP BY k"
        )
        # the compiled choreography marks the table shuffled
        ex = meta.state()["exchange"]
        assert ex["tables"]["t"]["mode"] == "shuffle"
        assert ex["tables"]["t"]["key_col"] == 0
        assert any(s["edge"] == "src:t>agg" for s in ex["specs"])
        vals = ",".join(f"({i % 7},{i})" for i in range(64))
        meta.execute_ddl(f"INSERT INTO t VALUES {vals}")
        for _ in range(3):
            assert meta.tick(1)["committed"]

        # per-edge counters + latency histogram on the SENDING worker
        leader = w1 if "agg" in {j.name for j in w1.engine.jobs} \
            and w1.worker_id == min(w1.worker_id, w2.worker_id) \
            else w2
        text = leader.engine.metrics.render_prometheus()
        assert 'cluster_exchange_rows_total{edge="src:t>agg"}' in text
        assert 'cluster_exchange_bytes_total{edge="src:t>agg"}' in text
        assert 'cluster_exchange_batches_total{edge="src:t>agg"}' \
            in text
        assert 'cluster_exchange_batch_seconds_count' \
            '{edge="src:t>agg"}' in text
        assert leader.rpc_metrics()["prometheus"] == text

        # meta-side per-worker mirrors exist for the leader...
        lead_id = str(leader.worker_id)
        assert meta.metrics.get("cluster_worker_exchange_rows_out",
                                worker=lead_id) > 0
        # ...and are RETIRED with the worker
        (dead := w2).stop()
        meta.rpc_unregister_worker(dead.worker_id)
        text = meta.metrics.render_prometheus()
        assert f'worker="{dead.worker_id}"' not in text
    finally:
        w1.stop()
        w2.stop()
        meta.stop()


def test_workload_txn_metrics_exported():
    """ISSUE 16 satellite: the CH driver's per-transaction families —
    ``workload_txn_total{type}``, ``workload_txn_rows_total`` and the
    wide-grid ``workload_txn_seconds{type}`` histogram — land on the
    registry in exportable shape (one series per transaction type,
    bucket bounds past the default 10s grid)."""
    from risingwave_tpu.common.metrics import MetricsRegistry
    from risingwave_tpu.workload.driver import observe_txn

    m = MetricsRegistry()
    observe_txn("new_order", 0.05, 12, metrics=m)
    observe_txn("new_order", 42.0, 9, metrics=m)
    observe_txn("payment", 0.02, 6, metrics=m)
    observe_txn("delivery", 0.3, 15, metrics=m)

    assert m.get("workload_txn_total", type="new_order") == 2
    assert m.get("workload_txn_total", type="payment") == 1
    assert m.get("workload_txn_total", type="delivery") == 1
    assert m.get("workload_txn_rows_total") == 42

    text = m.render_prometheus()
    assert '# TYPE workload_txn_seconds histogram' in text
    for kind in ("new_order", "payment", "delivery"):
        assert f'workload_txn_seconds_count{{type="{kind}"}} ' in text
    # the wide grid keeps a 42s txn out of the +Inf bucket
    assert 'le="60"' in text
    assert m.quantile("workload_txn_seconds", 0.99,
                      type="new_order") == 60.0


def test_state_section_reaches_the_planner_whole():
    """Every key of the config file's ``state`` section is a planner
    size: join and pool sizes come from the file too (``PlannerConfig``
    is ``StateConfig`` plus the chunk capacity)."""
    import dataclasses

    from risingwave_tpu.common.config import RwConfig, StateConfig

    cfg = RwConfig.from_dict({
        "streaming": {"chunk_size": 2048},
        "state": {"join_left_table_size": 1 << 10,
                  "join_right_table_size": 1 << 9,
                  "join_pool_size": 1 << 11, "mv_ring_size": 1 << 12},
    })
    planner = Engine(cfg).config
    assert planner.chunk_capacity == 2048
    assert planner.join_left_table_size == 1 << 10
    assert planner.join_right_table_size == 1 << 9
    assert planner.join_pool_size == 1 << 11
    assert planner.mv_ring_size == 1 << 12
    for f in dataclasses.fields(StateConfig):
        assert getattr(planner, f.name) == getattr(cfg.state, f.name)
