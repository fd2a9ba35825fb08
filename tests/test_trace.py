"""Trace-lite (ISSUE 14): span recorder semantics, cross-role trace
assembly, propagation under injected faults, metrics-plane merging,
and DROP-time series retirement."""

import json
import os
import re
import subprocess
import sys
import threading
import urllib.request

import pytest

from risingwave_tpu.common import faults as faults_mod
from risingwave_tpu.common.metrics import (
    MetricsRegistry,
    merge_prometheus,
)
from risingwave_tpu.common.trace import (
    GLOBAL_TRACE,
    NULL_SPAN,
    SpanRecorder,
    merge_dumps,
    round_ids,
    spans_for_round,
    to_chrome_trace,
    tree_check,
)


@pytest.fixture(autouse=True)
def _clean_globals():
    """Each test gets a clean global recorder and NO fault fabric; both
    are restored so unrelated suites never see leaked state."""
    role, n, cap = (GLOBAL_TRACE.role, GLOBAL_TRACE.sample_n,
                    GLOBAL_TRACE.capacity)
    sink, annotate = GLOBAL_TRACE._metrics, GLOBAL_TRACE._annotate
    GLOBAL_TRACE.configure(role="proc", sample_n=1)
    GLOBAL_TRACE.clear()
    faults_mod.install(None)
    yield
    faults_mod.install(None)
    GLOBAL_TRACE.configure(role=role, sample_n=n, capacity=cap)
    GLOBAL_TRACE._metrics, GLOBAL_TRACE._annotate = sink, annotate
    GLOBAL_TRACE.clear()


# -- recorder semantics --------------------------------------------------
def test_disabled_tracing_is_the_null_singleton():
    """sample_n=0 is the overhead contract: span() hands back ONE
    shared null object — no allocation, no clock read, empty ring."""
    rec = SpanRecorder(role="w", sample_n=0)
    assert rec.span("round", trace_id="round-1") is NULL_SPAN
    assert rec.sampled_span("read") is NULL_SPAN
    assert rec.activate(("round-1", "w:1")) is NULL_SPAN
    with rec.span("x", trace_id="round-1") as s:
        assert s.set(k=1) is NULL_SPAN and s.ctx is None
    assert rec.dump() == []


def test_span_without_any_context_is_null():
    rec = SpanRecorder(role="w", sample_n=1)
    # enabled, but no active trace, no explicit ctx, no trace_id:
    # nothing to attach to — the chunk path stays allocation-free
    assert rec.span("orphan") is NULL_SPAN
    assert rec.dump() == []


def test_nesting_and_cross_thread_ctx_propagation():
    rec = SpanRecorder(role="meta", sample_n=1)
    with rec.span("round", trace_id="round-7", epoch=7) as root:
        with rec.span("barrier", unit="u0") as b:
            assert b.parent_id == root.span_id
        rctx = root.ctx

        def fan_out():
            # fan-out threads have an empty TLS stack: the explicit
            # ctx= is the only way spans parent correctly
            with rec.span("barrier", ctx=rctx, unit="u1"):
                pass

        t = threading.Thread(target=fan_out)
        t.start()
        t.join()
    spans = rec.dump("round-7")
    assert {s["name"] for s in spans} == {"round", "barrier"}
    chk = tree_check(spans)
    assert chk["complete"] and chk["root_covers"], chk
    parents = {s["parent_id"] for s in spans if s["name"] == "barrier"}
    assert parents == {root.span_id}


def test_ring_is_bounded_flight_recorder():
    rec = SpanRecorder(role="w", sample_n=1, capacity=8)
    for i in range(20):
        with rec.span("s", trace_id="round-1", i=i):
            pass
    spans = rec.dump()
    assert len(spans) == 8
    # oldest fell off, newest survive, order preserved
    assert [s["attrs"]["i"] for s in spans] == list(range(12, 20))


def test_activate_adopts_remote_context():
    """The RPC server seam: a frame's trace key becomes the handler
    thread's context, so handler-side spans parent across processes."""
    rec = SpanRecorder(role="worker1", sample_n=1)
    with rec.activate(("round-3", "meta:9")):
        with rec.span("dispatch") as d:
            pass
    assert rec.current() is None  # guard popped
    (s,) = rec.dump()
    assert s["trace_id"] == "round-3" and s["parent_id"] == "meta:9"
    assert d.span_id.startswith("worker1:")


def test_sampled_span_one_in_n_and_ctx_parenting():
    rec = SpanRecorder(role="serving1", sample_n=3)
    for _ in range(9):
        with rec.sampled_span("serving_read"):
            pass
    spans = rec.dump()
    assert len(spans) == 3
    assert all(s["trace_id"] == "sampled-serving1" for s in spans)
    # ctx= pulls the sampled read INTO the round's tree instead
    with rec.sampled_span("serving_read", ctx=("round-5", "meta:1")):
        pass
    tagged = rec.dump("round-5")
    assert len(tagged) == 1 and tagged[0]["parent_id"] == "meta:1"


def test_exception_inside_span_records_error_attr():
    rec = SpanRecorder(role="w", sample_n=1)
    with pytest.raises(ValueError):
        with rec.span("seal", trace_id="round-1"):
            raise ValueError("boom")
    (s,) = rec.dump()
    assert s["attrs"]["error"] == "ValueError"
    assert rec.current() is None  # TLS stack unwound despite the raise


def test_merge_dumps_dedups_and_orders():
    rec = SpanRecorder(role="w", sample_n=1)
    with rec.span("a", trace_id="round-1"):
        pass
    with rec.span("b", trace_id="round-1"):
        pass
    d = rec.dump()
    merged = merge_dumps([d, d, [d[1]]])  # pulled twice + partial
    assert [s["name"] for s in merged] == ["a", "b"]
    assert round_ids(merged) == [1]
    assert len(spans_for_round(merged, 1)) == 2


def test_truncated_dump_is_parseable_not_fatal():
    """The SIGKILL contract: a dead role's spans are simply absent.
    tree_check reports orphans/missing roots instead of raising."""
    meta = SpanRecorder(role="meta", sample_n=1)
    worker = SpanRecorder(role="worker1", sample_n=1)
    with meta.span("round", trace_id="round-2") as root:
        with worker.span("seal", ctx=root.ctx):
            pass
    # meta's dump lost (meta SIGKILLed): worker spans orphaned
    chk = tree_check(merge_dumps([worker.dump()]))
    assert not chk["complete"] and chk["orphans"]
    # worker's dump lost: meta-only tree still checks out
    chk2 = tree_check(merge_dumps([meta.dump()]))
    assert chk2["complete"] and chk2["roots"]


def test_chrome_export_is_loadable_trace_event_json():
    rec = SpanRecorder(role="meta", sample_n=1)
    with rec.span("round", trace_id="round-1", epoch=1):
        with rec.span("commit"):
            pass
    ct = json.loads(json.dumps(to_chrome_trace(rec.dump())))
    xs = [e for e in ct["traceEvents"] if e["ph"] == "X"]
    ms = [e for e in ct["traceEvents"] if e["ph"] == "M"]
    assert len(xs) == 2 and ms  # complete events + pid/tid metadata
    assert all(e["ts"] > 0 and e["dur"] >= 0 for e in xs)  # microsecs
    assert {e["name"] for e in xs} == {"round", "commit"}


# -- metrics plane -------------------------------------------------------
def test_render_prometheus_type_lines_and_le_convention():
    m = MetricsRegistry()
    m.inc("reqs", job="a")
    m.observe("lat_seconds", 0.003, job="a")
    m.observe("lat_seconds", 0.003, job="b")
    text = m.render_prometheus()
    # one # TYPE per family, not per labelset
    assert text.count("# TYPE lat_seconds histogram") == 1
    assert text.count("# TYPE reqs counter") == 1
    # le bounds render bare (0.005, not 5e-03 / 0.00500)
    assert 'le="0.005"' in text and 'le="+Inf"' in text
    assert "5e-" not in text


def test_merge_prometheus_injects_identity_labels():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.set_gauge("up", 1)
    a.inc("rows", job="q1")
    b.inc("rows", job="q1")
    merged = merge_prometheus([
        ({"role": "meta"}, a.render_prometheus()),
        ({"role": "worker1", "worker": "1"}, b.render_prometheus()),
    ])
    assert 'up{role="meta"} 1' in merged
    assert 'rows{job="q1",role="meta"} 1' in merged
    assert 'rows{job="q1",role="worker1",worker="1"} 1' in merged
    # TYPE lines dedup across scrapes and lead the output
    assert merged.count("# TYPE rows counter") == 1
    body = merged.split("\n")
    last_type = max(i for i, l in enumerate(body)
                    if l.startswith("# TYPE"))
    first_sample = min(i for i, l in enumerate(body)
                       if l and not l.startswith("#"))
    assert last_type < first_sample


def test_quantile_returns_bucket_upper_bound():
    m = MetricsRegistry()
    for v in (0.003, 0.003, 0.004, 0.2):
        m.observe("lat_seconds", v, job="a")
    from risingwave_tpu.common.metrics import _DEFAULT_BUCKETS

    # the answer is a bucket UPPER BOUND (conservative estimate): the
    # least boundary whose cumulative count reaches the quantile
    assert m.quantile("lat_seconds", 0.5, job="a") == 0.005
    assert m.quantile("lat_seconds", 1.0, job="a") == 0.25
    assert all(m.quantile("lat_seconds", q, job="a")
               in _DEFAULT_BUCKETS for q in (0.1, 0.5, 0.9))


# -- in-process cluster: propagation under faults ------------------------
def _cluster_cfg():
    from risingwave_tpu.common.config import RwConfig

    return RwConfig.from_dict({
        "streaming": {"chunk_size": 128},
        "state": {"agg_table_size": 512, "agg_emit_capacity": 128,
                  "mv_table_size": 512, "mv_ring_size": 1024},
        "storage": {"checkpoint_keep_epochs": 4},
    })


def _boot(tmp_path):
    from risingwave_tpu.cluster import ComputeWorker, MetaService

    meta = MetaService(str(tmp_path), heartbeat_timeout_s=60.0)
    meta.start(port=0, monitor=False, compactor=False)
    w = ComputeWorker(f"127.0.0.1:{meta.rpc_port}", str(tmp_path),
                      config=_cluster_cfg(),
                      heartbeat_interval_s=5.0).start()
    meta.execute_ddl(
        "CREATE SOURCE t (k BIGINT, v BIGINT) "
        "WITH (connector='datagen')"
    )
    meta.execute_ddl(
        "CREATE MATERIALIZED VIEW tm AS "
        "SELECT k % 4 AS g, count(*) AS n FROM t GROUP BY k % 4"
    )
    return meta, w


def test_retried_barrier_yields_exactly_one_span_tree(tmp_path):
    """FaultFabric eats two barrier RESPONSES: the meta's RetryPolicy
    re-sends, the worker answers from its round cache (re-running no
    chunks, recording no duplicate spans) — each round still assembles
    exactly ONE complete tree with one root and one seal."""
    from risingwave_tpu.common.faults import FaultFabric

    meta, w = _boot(tmp_path)
    try:
        assert meta.tick(1)["committed"]
        fab = faults_mod.install(FaultFabric())
        fab.fail_rpc(substr=">worker1/barrier",
                     mode="error_after_send", times=2)
        try:
            assert meta.tick(1)["committed"]
        finally:
            faults_mod.install(None)
        assert fab.injected.get("rpc", 0) >= 1

        tr = meta.cluster_trace(round=2)
        chk = tr["check"]
        assert chk["complete"], chk
        names = [s["name"] for s in tr["spans"]]
        assert names.count("round") == 1  # exactly one root
        # chunks ran exactly once
        assert names.count("inject_barrier") == 1
        assert names.count("barrier") == 1  # one meta-side RPC span
        assert "commit" in names and "run_chunks" in names
    finally:
        faults_mod.install(None)
        w.stop()
        meta.stop()


def test_failed_tick_reuses_round_root_no_duplicate_trees(tmp_path):
    """Multi-attempt dedup: a tick whose barrier is dropped outright
    leaves the round uncommitted; the NEXT tick for the same round
    attaches an ``attempt`` child to the CACHED root instead of
    opening a second root — one tree per round, by construction."""
    from risingwave_tpu.common.faults import FaultFabric

    meta, w = _boot(tmp_path)
    try:
        assert meta.tick(1)["committed"]
        # make barrier failure fast and terminal for ONE tick
        meta.retry.max_attempts = 1
        fab = faults_mod.install(FaultFabric())
        fab.fail_rpc(substr=">worker1/barrier", mode="drop", times=1)
        try:
            assert not meta.tick(1)["committed"]
        finally:
            faults_mod.install(None)
            meta.retry.max_attempts = 5
        res = meta.tick(1)
        assert res["committed"] and res["round"] == 2

        tr = meta.cluster_trace(round=2)
        chk = tr["check"]
        assert chk["complete"], chk
        names = [s["name"] for s in tr["spans"]]
        assert names.count("round") == 1
        assert "attempt" in names  # the retry rode the cached root
        assert names.count("inject_barrier") == 1
    finally:
        faults_mod.install(None)
        w.stop()
        meta.stop()


# -- DROP retires the scrape surface -------------------------------------
def test_drop_mv_and_index_retire_job_labeled_series():
    from risingwave_tpu.sql.engine import Engine

    eng = Engine(_cluster_cfg())
    eng.execute(
        "CREATE SOURCE t (k BIGINT, v BIGINT) "
        "WITH (connector='datagen')"
    )
    eng.execute(
        "CREATE MATERIALIZED VIEW m1 AS "
        "SELECT k % 4 AS g, count(*) AS n FROM t GROUP BY k % 4"
    )
    eng.execute("CREATE INDEX m1_g ON m1(g)")
    # enough barriers for the rolling spike-ratio gauge (min samples)
    eng.tick(barriers=10, chunks_per_barrier=1)
    text = eng.metrics.render_prometheus()
    assert 'barrier_phase_seconds_bucket{job="m1"' in text
    assert 'barrier_spike_ratio{job="m1"' in text

    eng.execute("DROP INDEX m1_g")
    text = eng.metrics.render_prometheus()
    assert 'job="m1_g"' not in text  # index series gone...
    assert 'barrier_phase_seconds_bucket{job="m1"' in text  # host stays

    eng.execute("DROP MATERIALIZED VIEW m1")
    text = eng.metrics.render_prometheus()
    assert 'job="m1"' not in text  # ...and the MV's whole footprint


# -- the served single node traces itself (ISSUE 26) ----------------------
_Q7 = (
    "CREATE SOURCE bid (auction BIGINT, bidder BIGINT, price BIGINT, "
    "channel VARCHAR, url VARCHAR, date_time TIMESTAMP, WATERMARK FOR "
    "date_time AS date_time - INTERVAL '4' SECOND) WITH (connector = "
    "'nexmark', nexmark.table = 'bid', nexmark.event.rate = '1000000')",
    "CREATE MATERIALIZED VIEW q7 AS SELECT window_start, max(price) AS "
    "max_price, count(*) AS bids FROM TUMBLE(bid, date_time, INTERVAL "
    "'10' SECOND) GROUP BY window_start",
)

#: every span of one barrier of a durable job, uploader thread included
_TICK_SPANS = {
    "tick", "run_chunks", "inject_barrier", "inject_barrier.dispatch",
    "_maintain", "_maintain.device_wait", "_commit_checkpoint",
    "_commit_checkpoint.sinks", "_commit_checkpoint.wait_window",
    "snapshot", "drain_uploads", "ckpt_prepare", "ckpt_prepare.digests",
    "ckpt_prepare.fetch", "ckpt_commit", "ckpt_commit.encode",
    "ckpt_commit.put", "ckpt_commit.manifest",
}


def _trees(prefix: str) -> dict[str, list[dict]]:
    out: dict[str, list[dict]] = {}
    for s in GLOBAL_TRACE.dump():
        if s["trace_id"].startswith(prefix):
            out.setdefault(s["trace_id"], []).append(s)
    return out


def _span_series(text: str, family: str) -> dict[tuple, float]:
    """{(span, job or None): value} of one ``trace_span_*`` family."""
    out = {}
    for line in text.splitlines():
        m = re.match(family + r"\{(.*)\} (\S+)$", line)
        if m:
            lb = dict(kv.split("=") for kv in m.group(1).split(","))
            out[(lb["span"].strip('"'),
                 lb.get("job", "").strip('"') or None)] = float(m.group(2))
    return out


def test_engine_three_barriers_yield_three_tick_trees(tmp_path):
    """An in-process engine opens the ``tick`` root itself: one tree a
    call, the uploader thread's spans parented across the thread."""
    from risingwave_tpu.sql.engine import Engine

    eng = Engine(_cluster_cfg(), data_dir=str(tmp_path))
    for stmt in _Q7:
        eng.execute(stmt)
    for _ in range(3):
        eng.tick(barriers=1, chunks_per_barrier=2)
    trees = _trees("tick-")
    assert len(trees) == 3
    for spans in trees.values():
        chk = tree_check(spans)
        assert chk["complete"] and chk["root_covers"], chk
        assert _TICK_SPANS <= set(chk["names"]), \
            _TICK_SPANS - set(chk["names"])
        by_id = {s["span_id"]: s for s in spans}
        by_name = {s["name"]: s for s in spans}
        # one root, and one span of each name: a barrier, not a chunk
        assert len(by_name) == len(spans)
        up = by_name["ckpt_commit.encode"]
        assert up["thread"].startswith("ckpt-upload-")
        assert by_id[up["parent_id"]]["name"] == "ckpt_commit"
        assert by_id[by_name["ckpt_prepare"]["parent_id"]]["name"] \
            == "_commit_checkpoint"
        assert by_id[by_name["_maintain.device_wait"]["parent_id"]][
            "name"] == "_maintain"
        assert all(s["t_mono"] > 0 for s in spans)
        root = by_name["tick"]
        assert root["attrs"]["rows"] == 2 * 128 and root["attrs"]["epoch"]
    # every span is a counter too, in the engine's own registry
    text = eng.metrics.render_prometheus()
    counts = _span_series(text, "trace_span_total")
    secs = _span_series(text, "trace_span_seconds_total")
    assert counts[("tick", None)] == 3
    for name in _TICK_SPANS - {"tick"}:
        assert counts[(name, "q7")] == 3, name
        assert secs[(name, "q7")] > 0, name
    # the cadence counters: one maintenance and one snapshot a barrier
    assert counts[("_maintain", "q7")] == counts[("snapshot", "q7")] \
        == counts[("inject_barrier", "q7")]


def test_spans_count_into_their_own_engines_registry():
    """GLOBAL_TRACE is one a process, engines are many: an engine's
    spans never land in whichever registry was configured last."""
    from risingwave_tpu.sql.engine import Engine

    a, b = Engine(_cluster_cfg()), Engine(_cluster_cfg())
    other = MetricsRegistry()
    GLOBAL_TRACE.configure(metrics=other)  # the fixture restores it
    for eng in (a, b):
        for stmt in _Q7:
            eng.execute(stmt)
    a.tick(barriers=1, chunks_per_barrier=1)
    b.tick(barriers=2, chunks_per_barrier=1)
    with GLOBAL_TRACE.span("loose", trace_id="x-1"):
        pass
    ca = _span_series(a.metrics.render_prometheus(), "trace_span_total")
    cb = _span_series(b.metrics.render_prometheus(), "trace_span_total")
    assert ca[("inject_barrier", "q7")] == 1 and ca[("tick", None)] == 1
    assert cb[("inject_barrier", "q7")] == 2 and cb[("tick", None)] == 1
    # a span with no owner falls to the configured default, alone
    assert _span_series(other.render_prometheus(),
                        "trace_span_total") == {("loose", None): 1}


def test_single_node_read_tree_counters_and_drop(tmp_path):
    """One pgwire statement is one ``read-<n>`` tree; the served
    node's tick holds the wait for the engine lock and no second
    ``tick`` span; /metrics counts both and DROP retires the job's."""
    from risingwave_tpu.pgwire import SimpleClient
    from risingwave_tpu.server import SingleNode

    node = SingleNode(_cluster_cfg(), data_dir=str(tmp_path))
    server = node.start(port=0, ticker=False)
    try:
        c = SimpleClient("127.0.0.1", server.server_address[1])
        for stmt in _Q7:
            c.query(stmt)
        for _ in range(2):
            node._tick_once()
        # the served tick sent its next window ahead: settle it outside
        # any trace, so the read's tree is the read's alone (a read that
        # finds one settles it under ``read.execute``: test_window_ahead)
        node.engine.settle("tick")
        GLOBAL_TRACE.clear()
        _, rows = c.query("SELECT window_start, max_price, bids FROM q7")
        (spans,) = _trees("read-").values()
        chk = tree_check(spans)
        assert chk["complete"] and chk["root_covers"], chk
        assert set(chk["names"]) == {
            "read", "read.lock_wait", "read.execute", "read.send",
            "_mv_rows", "_mv_rows.to_host"}
        by_id = {s["span_id"]: s for s in spans}
        by_name = {s["name"]: s for s in spans}
        assert by_name["read"]["attrs"] == {"kind": "SELECT",
                                            "rows": len(rows)}
        assert by_id[by_name["_mv_rows"]["parent_id"]]["name"] \
            == "read.execute"
        assert by_name["read.send"]["parent_id"] \
            == by_name["read"]["span_id"]

        node._tick_once()
        (tick,) = _trees("tick-").values()
        names = [s["name"] for s in tick]
        assert names.count("tick") == 1 and "tick.lock_wait" in names
        assert tree_check(tick)["complete"]

        text = node.render_metrics()
        counts = _span_series(text, "trace_span_total")
        assert counts[("read", None)] == 3  # two DDL, one SELECT
        assert counts[("read.execute", None)] == 3
        assert counts[("_mv_rows", None)] == 1
        assert counts[("tick", None)] == counts[("tick.lock_wait", None)] \
            == counts[("inject_barrier", "q7")] == 3
        (scrape,) = _trees("scrape-").values()
        assert {s["name"] for s in scrape} == {
            "render_metrics", "render_metrics.lock_wait",
            "render_metrics.collect"}

        c.query("DROP MATERIALIZED VIEW q7")
        text = node.render_metrics()
        assert 'job="q7"' not in text
        assert 'trace_span_total{span="read"} 4' in text
        c.close()
    finally:
        node.stop()
        server.shutdown()
        server.server_close()


def test_sample_n_zero_records_nothing_and_exports_no_series():
    """The overhead contract on the single-node path: off means the
    null singleton at every call site, an empty ring, no series."""
    from risingwave_tpu.sql.engine import Engine

    GLOBAL_TRACE.configure(sample_n=0)
    lock = threading.Lock()
    assert GLOBAL_TRACE.root("tick", "tick") is NULL_SPAN
    with GLOBAL_TRACE.held(lock, "tick.lock_wait"):
        assert lock.locked()
    assert not lock.locked()
    eng = Engine(_cluster_cfg())
    for stmt in _Q7:
        eng.execute(stmt)
    eng.tick(barriers=2, chunks_per_barrier=1)
    assert eng.query("SELECT * FROM q7")[0]
    assert GLOBAL_TRACE.dump() == []
    assert "trace_span" not in eng.metrics.render_prometheus()


def test_annotation_factory_wraps_every_span():
    """``configure(annotate=...)`` is how the roles that hold a chip
    put spans on the profiler's clock; the module never imports jax."""
    seen = []

    class Ann:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("in", self.name))

        def __exit__(self, *exc):
            seen.append(("out", self.name))

    rec = SpanRecorder(role="single", sample_n=1).configure(annotate=Ann)
    with rec.root("tick", "tick"):
        with rec.span("run_chunks"):
            pass
    assert seen == [("in", "tick"), ("in", "run_chunks"),
                    ("out", "run_chunks"), ("out", "tick")]
    assert [s["name"] for s in rec.dump()] == ["run_chunks", "tick"]


def test_trace_module_and_server_import_no_jax():
    """``--role serving`` is engine-free: the recorder, its counters
    and the server's entry module load and record without jax."""
    code = (
        "import sys\n"
        "from risingwave_tpu import server\n"
        "from risingwave_tpu.common.metrics import MetricsRegistry\n"
        "from risingwave_tpu.common.trace import GLOBAL_TRACE\n"
        "m = MetricsRegistry()\n"
        "GLOBAL_TRACE.configure(role='serving', sample_n=1, metrics=m)\n"
        "with GLOBAL_TRACE.root('read', 'read'):\n"
        "    pass\n"
        "assert m.get('trace_span_total', span='read') == 1\n"
        "assert server._render_trace('format=chrome')\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n"
    )
    env = dict(os.environ, RWT_NO_JAX="1")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]


def test_trace_endpoint_beside_metrics():
    """``GET /trace`` on the metrics port: the ring as JSON, or Chrome
    ``trace_event`` JSON; ``/metrics`` as before."""
    from risingwave_tpu.server import _start_metrics_http

    with GLOBAL_TRACE.root("tick", "tick", rows=7):
        with GLOBAL_TRACE.span("run_chunks"):
            pass
    httpd = _start_metrics_http(lambda: "up 1\n", "127.0.0.1", 0)
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        def get(path):
            with urllib.request.urlopen(base + path, timeout=30) as r:
                return r.read().decode()

        out = json.loads(get("/trace"))
        assert out["role"] == "proc"
        assert [s["name"] for s in out["spans"]] == ["run_chunks", "tick"]
        assert out["spans"][1]["attrs"] == {"rows": 7}
        tid = out["spans"][0]["trace_id"]
        assert len(json.loads(get(f"/trace?trace_id={tid}"))["spans"]) == 2
        assert json.loads(get("/trace?trace_id=none"))["spans"] == []
        chrome = json.loads(get("/trace?format=chrome"))
        assert {e["name"] for e in chrome["traceEvents"]
                if e["ph"] == "X"} == {"tick", "run_chunks"}
        assert get("/metrics") == "up 1\n"
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_window_program_names_generator_and_every_executor():
    """``jax.named_scope`` on the device side: the q7-shaped window
    program's lowered text holds ``gen`` and ``<Class>.<i>/apply`` for
    every executor; the barrier and maintain programs their phases."""
    import jax.numpy as jnp

    from risingwave_tpu.sql.engine import Engine

    eng = Engine(_cluster_cfg())
    for stmt in _Q7:
        eng.execute(stmt)
    job = eng.jobs[0]

    def locs(lowered) -> set[str]:
        return set(re.findall(r'loc\("([^"]*)"',
                              lowered.as_text(debug_info=True)))

    window = locs(job._multi_prog(4).lower(job.states, jnp.int64(0)))
    assert any(name.startswith("gen/") for name in window)
    barrier = locs(job.fragment._barrier.lower(job.states, 5))
    execs = job.fragment.executors
    names = [type(ex).__name__.removesuffix("Executor") for ex in execs]
    assert names == ["WatermarkFilter", "HopWindow", "HashAgg", "Project",
                     "Materialize"]

    def applied(prog: set[str]) -> set[int]:
        return {i for i, n in enumerate(names)
                if any(f"{n}.{i}/apply" in loc for loc in prog)}

    # the window program runs the chain up to the aggregate (it emits
    # on flush); the barrier program runs the aggregate's changelog
    # through the rest: between them, every executor that leaves an
    # operation to name (the projection only picks columns)
    assert applied(window) == {0, 1, 2}
    assert applied(barrier) - {3} == {4}
    barrier = " ".join(barrier)
    for phase in ("flush", "watermark", "counters"):
        assert f"HashAgg.2/{phase}" in barrier
    maintain = " ".join(locs(job.fragment._maintain.lower(job.states)))
    assert "HashAgg.2/reclaim" in maintain


def test_dropped_span_leaves_no_record():
    """An idle poll is not worth a ring entry (the compactor polls a
    hundred times a second): ``drop()`` unwinds, records nothing."""
    m = MetricsRegistry()
    rec = SpanRecorder(role="single", sample_n=1).configure(metrics=m)
    with rec.sampled_span("compact_cycle") as idle:
        idle.drop()
    with rec.sampled_span("compact_cycle"):
        pass
    NULL_SPAN.drop()
    assert rec.current() is None
    assert len(rec.dump()) == 1
    assert m.get("trace_span_total", span="compact_cycle") == 1


@pytest.mark.parametrize("view, tiles_per_chunk", [
    # one 10 s window holds every bid of a 512-row chunk: one group
    (_Q7[1], "one"),
    # a group a price: hundreds of representatives a chunk, so several
    # tiles of hash_agg.REP_TILE
    ("CREATE MATERIALIZED VIEW q7 AS SELECT price, count(*) AS bids "
     "FROM bid GROUP BY price", "several"),
], ids=["one_group", "many_groups"])
def test_hash_agg_tallies_reach_metrics(tmp_path, accel_branch, view,
                                        tiles_per_chunk):
    """The chip branch's engagement counters ride the maintenance
    barrier's counters vector to /metrics under their own names, and
    are not lost rows: ``maintenance_counter_rows`` stays 0."""
    from risingwave_tpu.common.config import RwConfig
    from risingwave_tpu.server import SingleNode
    from risingwave_tpu.stream import hash_agg

    accel_branch(True)
    node = SingleNode(RwConfig.from_dict({
        "streaming": {"chunk_size": 512},
        "state": {"agg_table_size": 8192, "agg_emit_capacity": 2048,
                  "mv_table_size": 8192, "mv_ring_size": 1024},
    }), data_dir=str(tmp_path))
    try:
        node.engine.execute(_Q7[0])
        node.engine.execute(view)
        node.engine.tick(barriers=2, chunks_per_barrier=3)
        text = node.render_metrics()
    finally:
        node.stop()

    def series(name):
        (v,) = re.findall(rf'^{name}{{job="q7"}} (\S+)$', text, re.M)
        return float(v)

    assert "# TYPE hash_agg_rep_tiles_total counter" in text
    chunks = series("hash_agg_apply_chunks_total")
    tiles = series("hash_agg_rep_tiles_total")
    reps = series("hash_agg_rep_rows_total")
    assert chunks == 6
    if tiles_per_chunk == "one":
        assert tiles / chunks == 1 and 1 <= reps / chunks <= 2
    else:
        assert tiles / chunks > 1
        assert reps / chunks > hash_agg.REP_TILE
    rows = re.findall(
        r'^maintenance_counter_rows{job="q7",kind="(\w+)"} (\S+)$',
        text, re.M)
    assert rows and all(float(v) == 0 for _, v in rows)
    assert not {k for k, _ in rows} & {
        "apply_chunks", "rep_rows", "rep_tiles", "reclaim_passes",
        "reclaim_slots", "live_groups", "tombstones", "table_slots"}
    # the reclaim's tallies and the table's levels, read with the same
    # vector: nothing has retired after two barriers, so no pass yet
    assert "# TYPE hash_agg_reclaim_passes_total counter" in text
    assert "# TYPE hash_agg_live_groups gauge" in text
    assert series("hash_agg_reclaim_passes_total") == 0
    assert series("hash_agg_reclaim_slots_total") == 0
    assert series("hash_agg_tombstones") == 0
    assert series("hash_agg_live_groups") >= 1
    assert series("hash_agg_table_slots") == 8192
