"""Serve-hot (ISSUE 10): epoch-keyed result cache, batched multi-get,
secondary-index MVs, and DROP-MV tombstoning — the fast in-process
guard for the memcached-class read path (the slow bench wrapper
asserts throughput/latency floors; here correctness only)."""

import time

import pytest

from risingwave_tpu.cluster import ComputeWorker, MetaService
from risingwave_tpu.common.config import RwConfig
from risingwave_tpu.serve import ServingWorker
from risingwave_tpu.serve.worker import ResultCache, plan_read


def _cfg():
    return RwConfig.from_dict({
        "streaming": {"chunk_size": 128},
        "state": {"agg_table_size": 512, "agg_emit_capacity": 128,
                  "mv_table_size": 512, "mv_ring_size": 1024},
        "storage": {"checkpoint_keep_epochs": 4},
    })


def _rows(served):
    return sorted(tuple(r) for r in served[1])


# -- result cache (unit) -------------------------------------------------
def test_result_cache_lru_bytes_and_stale_sweep():
    rc = ResultCache(max_bytes=64 << 10)
    big = [(i, "x" * 64) for i in range(8)]
    rc.put(("q1", 1), (["a"], big, 7))
    assert rc.get(("q1", 1)) == (["a"], big, 7)
    assert rc.bytes > 0 and len(rc) == 1
    # a different vid is a different key: epoch advance re-keys
    assert rc.get(("q1", 2)) is None
    rc.put(("q1", 2), (["a"], big, 8))
    rc.evict_stale(2)  # sweeps every non-current-vid entry
    assert rc.get(("q1", 1)) is None and rc.get(("q1", 2)) is not None
    # byte budget evicts LRU-first
    for i in range(64):
        rc.put((f"q{i}", 2), (["a"], big, 8))
    assert rc.bytes <= rc.max_bytes
    # jumbo entries never enter (they would churn the whole LRU)
    jumbo = [(i, "y" * 64) for i in range(1000)]
    before = rc.bytes
    rc.put(("jumbo", 2), (["a"], jumbo, 8))
    assert rc.get(("jumbo", 2)) is None and rc.bytes == before
    assert 0.0 <= rc.hit_ratio() <= 1.0


# -- index rewrite (unit) ------------------------------------------------
def test_plan_read_index_rewrite():
    from risingwave_tpu.serve.reader import MvSchema
    from risingwave_tpu.sql import ast
    from risingwave_tpu.sql.parser import parse

    prim = MvSchema({
        "mv": "m",
        "columns": [
            {"name": "g", "kind": "int", "scale": 0, "hidden": False},
            {"name": "n", "kind": "int", "scale": 0, "hidden": False},
        ],
        "pk": [0],
        "indexes": [{"name": "m_n", "cols": ["n"]}],
    })
    ix = MvSchema({
        "mv": "m_n",
        "columns": [
            {"name": "n", "kind": "int", "scale": 0, "hidden": False},
            {"name": "g", "kind": "int", "scale": 0, "hidden": False},
        ],
        "pk": [0, 1],
        "index_of": "m", "index_width": 1, "since_epoch": 5,
    })
    schemas = {"m": prim, "m_n": ix}

    def plan(sql, at_epoch=10):
        (sel,) = parse(sql)
        assert isinstance(sel, ast.Select)
        return plan_read(sel, prim, schema_of=schemas.get,
                         at_epoch=at_epoch)

    p = plan("SELECT g FROM m WHERE n = 42")
    assert p.mode == "index" and p.index_mv == "m_n"
    assert p.index_width == 1 and p.lo.startswith(b"m:m_n\x00")
    assert p.hi is not None and p.hi > p.lo
    # pk predicates still take the point-get path, not the index
    assert plan("SELECT g FROM m WHERE g = 1").mode == "get"
    # a pin OLDER than the index's first export must not use it: the
    # read is a filtered scan of the primary (the non-pk compare rides
    # as a residual of the block-walk evaluator), never the index
    p_old = plan("SELECT g FROM m WHERE n = 42", at_epoch=3)
    assert p_old.mode == "scan" and not p_old.index_mv
    assert p_old.lo.startswith(b"m:m\x00") and p_old.hi > p_old.lo
    assert p_old.residual == [(1, "equal", 42)]
    # index RANGE scan (Exchange-lite round): WHERE n > x bounds the
    # index byte range — the memcomparable encoding already sorts
    p = plan("SELECT g FROM m WHERE n > 42")
    assert p.mode == "index" and p.index_mv == "m_n"
    assert p.lo > b"m:m_n\x00" and p.hi is not None
    # the range predicate also rides as a residual (exactness guard)
    assert (1, "greater_than", 42) in (p.residual or [])
    p2 = plan("SELECT g FROM m WHERE n >= 10 AND n < 42")
    assert p2.mode == "index" and p2.lo < p.lo
    # composite predicate: index prefix + residual filter on a column
    # the index bytes cannot bound
    p3 = plan("SELECT g FROM m WHERE n = 42 AND g > 7")
    assert p3.mode == "index" and p3.index_mv == "m_n"
    assert (0, "greater_than", 7) in (p3.residual or [])
    # no schema_of (no index discovery): the same filtered scan
    (sel,) = parse("SELECT g FROM m WHERE n = 42")
    p4 = plan_read(sel, prim)
    assert p4.mode == "scan" and not p4.index_mv
    assert (p4.lo, p4.hi, p4.residual) == \
        (p_old.lo, p_old.hi, p_old.residual)


# -- the in-process cluster smoke (tier-1 fast) --------------------------
def test_serve_hot_cluster_smoke(tmp_path):
    """One cluster boot guards the whole hot path: result-cache hits
    with epoch-advance invalidation (a write committed at e+1 is
    visible after the lease re-grant, byte-identical to the owning
    worker), serve_batch with per-item owner fallback, first-class
    multi-get, secondary-index reads byte-identical to the full scan,
    and DROP MATERIALIZED VIEW tombstoning the shared keyspace."""
    meta = MetaService(str(tmp_path), heartbeat_timeout_s=5.0)
    meta.start(port=0, monitor=False, compactor=False)
    addr = f"127.0.0.1:{meta.rpc_port}"
    w = ComputeWorker(addr, str(tmp_path), config=_cfg(),
                      heartbeat_interval_s=0.5).start()
    meta.execute_ddl(
        "CREATE SOURCE t (k BIGINT, v BIGINT) "
        "WITH (connector='datagen')"
    )
    meta.execute_ddl(
        "CREATE MATERIALIZED VIEW m1 AS "
        "SELECT k % 8 AS g, count(*) AS n FROM t GROUP BY k % 8"
    )
    meta.execute_ddl("CREATE INDEX m1_n ON m1(n)")
    for _ in range(3):
        assert meta.tick(1)["committed"]
    sv = ServingWorker(addr, str(tmp_path),
                       heartbeat_interval_s=0.1).start()
    try:
        # -- batched reads: point-gets share one multi-get pass;
        # engine-only shapes fall back per item to the owner
        res = meta.serve_batch([
            "SELECT n FROM m1 WHERE g = 3",
            "SELECT g, n FROM m1 WHERE g >= 2 AND g < 5",
            "SELECT count(*) FROM m1",
        ])
        assert _rows(res[0]) == [(48,)]
        assert _rows(res[1]) == [(g, 48) for g in (2, 3, 4)]
        assert _rows(res[2]) == [(8,)]
        # a final per-item error surfaces like the single-read path
        with pytest.raises(Exception, match="does not exist"):
            meta.serve_batch(["SELECT nope FROM m1"])

        # -- the repeat read HITS the result cache (same sql modulo
        # whitespace, same pinned vid) and stays byte-identical
        first = meta.serve_batch(["SELECT n FROM m1 WHERE g = 3"])[0]
        hits0 = sv.result_cache.hits
        again = meta.serve_batch(["SELECT  n  FROM m1 WHERE g = 3"])[0]
        assert again == first
        assert sv.result_cache.hits > hits0
        assert sv.metrics.get("serving_result_cache_hits") >= 1

        # -- epoch-advance invalidation: the next committed round
        # re-keys the cache; the SAME sql returns the NEW rows,
        # byte-identical to the owning worker
        for _ in range(2):
            assert meta.tick(1)["committed"]
        (cols, rows) = meta.serve_batch(
            ["SELECT n FROM m1 WHERE g = 3"]
        )[0]
        assert rows == [(80,)], rows
        with meta._lock:
            job = meta.jobs[meta._mv_to_job["m1"]]
            wk = meta.workers[job.worker_id]
            pin = job.pinned_epoch
        owner = wk.client.call(
            "serve", sql="SELECT n FROM m1 WHERE g = 3",
            query_epoch=pin,
        )
        assert rows == [tuple(r) for r in owner["rows"]]

        # -- first-class multi-get: rows in encoded-pk order, missing
        # pks omitted
        cols, rows = meta.serve_multi_get(
            "m1", [[5], [1], [99]], cols=["g", "n"]
        )
        assert cols == ["g", "n"] and rows == [(1, 80), (5, 80)]

        # -- secondary index: byte-identical to the full scan's
        # filtered rows, and actually exercised (metrics move)
        _, allr = meta.serve("SELECT g, n FROM m1")
        want = sorted(r for r in allr if r[1] == 80)
        assert _rows(meta.serve("SELECT g, n FROM m1 WHERE n = 80")) \
            == want
        assert sv.metrics.get("serving_index_lookups_total") >= 1

        # -- index RANGE scan over the memcomparable encoding
        # (Exchange-lite satellite): byte-identical to full scan +
        # filter, including the empty range
        want = sorted(r for r in allr if r[1] > 79)
        assert _rows(meta.serve(
            "SELECT g, n FROM m1 WHERE n > 79")) == want
        assert _rows(meta.serve(
            "SELECT g, n FROM m1 WHERE n > 80")) == []
        want = sorted(r for r in allr if 1 <= r[1] < 81)
        assert _rows(meta.serve(
            "SELECT g, n FROM m1 WHERE n >= 1 AND n < 81")) == want
        # composite: index prefix + residual filter on g
        want = sorted(r for r in allr if r[1] == 80 and r[0] > 3)
        assert _rows(meta.serve(
            "SELECT g, n FROM m1 WHERE n = 80 AND g > 3")) == want

        # -- DROP: protection first, then tombstones + "does not
        # exist" instead of stale rows
        with pytest.raises(Exception, match="depend on it"):
            meta.execute_ddl("DROP MATERIALIZED VIEW m1")
        meta.execute_ddl("DROP INDEX m1_n")
        meta.execute_ddl("DROP MATERIALIZED VIEW m1")
        with pytest.raises(ValueError, match="does not exist"):
            meta.serve("SELECT g, n FROM m1")
        sv._grant_refresh()
        assert sv.view.scan_mv("m1") == []
        assert sv.view.scan_mv("m1_n") == []
        assert sv.view.schema("m1") is None
    finally:
        sv.stop()
        w.stop()
        meta.stop()


# -- index maintenance through retraction churn (single node) ------------
def test_index_byte_identity_through_retraction_churn(tmp_path):
    """DML updates retract old index rows (the group's aggregate
    moves): after every export the index path answers byte-identical
    rows to the full scan, and entries for DEAD aggregate values are
    gone (no resurrection)."""
    from risingwave_tpu.sql import Engine

    eng = Engine(_cfg(), data_dir=str(tmp_path))
    eng.execute("CREATE TABLE pt (k BIGINT, v BIGINT)")
    eng.execute(
        "CREATE MATERIALIZED VIEW am AS "
        "SELECT k % 4 AS g, sum(v) AS s FROM pt GROUP BY k % 4"
    )
    eng.execute("CREATE INDEX am_s ON am(s)")
    sv = ServingWorker(None, str(tmp_path))
    started = False
    try:
        seen_s: set = set()
        for rnd in range(3):
            for k in range(8):
                eng.execute(
                    f"INSERT INTO pt VALUES ({k}, {10 * (rnd + 1)})"
                )
            eng.execute("FLUSH")
            eng.storage_export_mv("am")
            eng.storage_export_mv("am_s")
            if not started:
                sv.start()
                started = True
            else:
                sv.view.refresh(None)
            rows = eng.storage_serve_mv("am")
            scan = sorted(tuple(r) for r in rows)
            svals = sorted({r[1] for r in scan})
            assert len(svals) == 1  # every group moved together
            s_live = svals[0]
            _, got, _ = sv.read(f"SELECT g, s FROM am WHERE s = {s_live}")
            assert sorted(got) == scan
            # previous rounds' aggregate values retracted out of the
            # index: a probe for them returns NOTHING (not stale rows)
            for s_dead in seen_s:
                _, dead, _ = sv.read(
                    f"SELECT g, s FROM am WHERE s = {s_dead}"
                )
                assert dead == []
            seen_s.add(s_live)
        # drop the index: the upstream doc stops advertising it, so
        # the replica answers with a filtered scan of the primary —
        # the same rows, never the tombstoned index rows
        eng.execute("DROP INDEX am_s")
        sv.view.refresh(None)
        sql = f"SELECT g, s FROM am WHERE s = {max(seen_s)}"
        assert sv._plan(sql).mode == "scan"
        _, got, _ = sv.read(sql)
        assert sorted(got) == scan
        for s_dead in seen_s - {max(seen_s)}:
            assert sv.read(
                f"SELECT g, s FROM am WHERE s = {s_dead}")[1] == []
    finally:
        if started:
            sv.stop()


# -- pushdown plane: negative cache + warmup + filtered scan -------------
def test_negative_cache_warmup_and_filtered_scan(tmp_path):
    """ISSUE 18: (1) a residual predicate on a NON-indexed, non-pk
    column runs inside the replica's block-walk evaluator,
    byte-identical to fetch-then-filter; (2) repeated missing-pk
    lookups are absorbed by the per-vid negative cache; (3) on epoch
    advance the negative fact is structurally invalidated (the
    materialized row appears — zero stale rows) and the hottest
    result-cache keys are re-warmed against the new vid with FRESH
    rows."""
    from risingwave_tpu.sql import Engine

    eng = Engine(_cfg(), data_dir=str(tmp_path))
    eng.execute("CREATE TABLE pt (k BIGINT, v BIGINT)")
    eng.execute(
        "CREATE MATERIALIZED VIEW pm AS "
        "SELECT k, sum(v) AS s FROM pt GROUP BY k"
    )
    for k in range(8):
        eng.execute(f"INSERT INTO pt VALUES ({k}, {k * 10})")
    eng.execute("FLUSH")
    eng.storage_export_mv("pm")
    sv = ServingWorker(None, str(tmp_path))
    sv.start()
    try:
        # -- filtered scan: no index on s, so the predicate runs as a
        # residual inside the merge scan (never an owner fallback)
        _, allr, _ = sv.read("SELECT k, s FROM pm")
        want = sorted(r for r in allr if r[1] >= 40)
        _, got, _ = sv.read("SELECT k, s FROM pm WHERE s >= 40")
        assert sorted(got) == want
        assert sv.metrics.get("pushdown_rows_elided_total",
                              where="replica") > 0

        # -- negative cache: the second miss for the same absent pk
        # is absorbed without another SstView pass
        _, rows, _ = sv.multi_get("pm", [[99]], cols=["k", "s"])
        assert rows == []
        h0 = sv.neg_cache.hits
        _, rows, _ = sv.multi_get("pm", [[99]], cols=["k", "s"])
        assert rows == [] and sv.neg_cache.hits > h0
        assert len(sv.neg_cache) >= 1
        assert sv.metrics.get("serving_negative_cache_entries") >= 1

        # heat one key so the re-grant has something to warm
        for _ in range(3):
            sv.read("SELECT s FROM pm WHERE k = 1")

        # -- epoch advance: pk 99 materializes and k=1 moves; the
        # re-grant must drop the negative fact AND re-warm the hot
        # key at the new vid with the NEW rows
        eng.execute("INSERT INTO pt VALUES (99, 7)")
        eng.execute("INSERT INTO pt VALUES (1, 5)")
        eng.execute("FLUSH")
        eng.storage_export_mv("pm")
        r0 = sv.warmup_replays
        sv._grant_refresh()
        assert sv.warmup_replays > r0
        vid = sv.view.version.vid
        assert sv.result_cache.contains(
            ("SELECT s FROM pm WHERE k = 1", vid)
        )
        _, rows, _ = sv.read("SELECT s FROM pm WHERE k = 1")
        assert rows == [(15,)], rows
        _, rows, _ = sv.multi_get("pm", [[99]], cols=["k", "s"])
        assert rows == [(99, 7)], rows  # zero stale rows
    finally:
        sv.stop()


# -- per-replica gauge retirement ---------------------------------------
def test_serving_replica_reap_retires_gauges(tmp_path):
    """ISSUE 10 satellite: a reaped (or deregistered) serving replica
    leaves NO frozen per-replica series on the meta's scrape surface,
    mirroring the PR-7 per-worker retirement."""
    meta = MetaService(str(tmp_path), heartbeat_timeout_s=0.6)
    meta.start(port=0, monitor=False, compactor=False)
    addr = f"127.0.0.1:{meta.rpc_port}"
    sv1 = ServingWorker(addr, str(tmp_path),
                        heartbeat_interval_s=0.1).start()
    sv2 = ServingWorker(addr, str(tmp_path),
                        heartbeat_interval_s=0.1).start()
    try:
        meta.check_heartbeats()
        m = meta.metrics
        for sv in (sv1, sv2):
            rid = str(sv.replica_id)
            assert m.get("cluster_serving_heartbeat_age_seconds",
                         replica=rid) >= 0.0
            assert m.get("cluster_serving_granted_vid",
                         replica=rid) >= 0
        # graceful deregistration retires the series
        r2 = sv2.replica_id
        sv2.stop()
        text = m.render_prometheus()
        assert f'replica="{r2}"' not in text
        assert f'replica="{sv1.replica_id}"' in text
        # hard death (no unregister): heartbeat expiry reaps + retires
        r1 = sv1.replica_id
        sv1._stop.set()
        sv1._server.stop()
        sv1._server = None
        deadline = time.monotonic() + 10
        while meta.state()["serving"]:
            meta.check_heartbeats()
            assert time.monotonic() < deadline, "lease never reaped"
            time.sleep(0.1)
        text = m.render_prometheus()
        assert f'replica="{r1}"' not in text
        assert meta.versions.pinned_count() == 0
    finally:
        sv1.stop()
        sv2.stop()
        meta.stop()
