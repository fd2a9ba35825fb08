"""pgwire protocol tests with a raw-socket minimal client
(no postgres driver in the image; the client speaks protocol 3.0
simple-query flow exactly as psql would)."""

import socket
import struct

import pytest

from risingwave_tpu.server import SingleNode
from risingwave_tpu.sql.planner import PlannerConfig


from risingwave_tpu.pgwire import SimpleClient as MiniPgClient  # noqa: E402


@pytest.fixture()
def node():
    n = SingleNode(PlannerConfig(
        chunk_capacity=128, agg_table_size=256, agg_emit_capacity=64,
        mv_table_size=256, mv_ring_size=1024,
    ))
    # port 0 = ephemeral
    server = n.start(port=0, ticker=False)  # deterministic ticks
    host, port = server.server_address
    yield n, host, port
    n.stop()
    server.shutdown()


def test_pgwire_end_to_end(node):
    n, host, port = node
    c = MiniPgClient(host, port)
    try:
        c.query("""
            CREATE SOURCE t (k BIGINT, v BIGINT)
            WITH (connector = 'datagen')
        """)
        c.query("""
            CREATE MATERIALIZED VIEW m AS
            SELECT k % 2 AS b, count(*) AS n FROM t GROUP BY k % 2
        """)
        # drive the dataflow deterministically (the background ticker
        # paces at barrier_interval_ms; FLUSH-style direct ticks are
        # exact for the assertion)
        n.tick(barriers=2, chunks_per_barrier=1)
        cols, rows = c.query("SELECT b, n FROM m ORDER BY b")
        assert cols == ["b", "n"]
        assert [(r[0], r[1]) for r in rows] == [("0", "128"), ("1", "128")]

        cols, rows = c.query("SHOW MATERIALIZED VIEWS")
        assert rows == [("m",)]
    finally:
        c.close()


def test_pgwire_error_keeps_session(node):
    n, host, port = node
    c = MiniPgClient(host, port)
    try:
        with pytest.raises(RuntimeError):
            c.query("SELECT broken FROM nowhere")
        # session still usable after an error
        cols, rows = c.query("SHOW SOURCES")
        assert rows == []
    finally:
        c.close()


def test_pgwire_concurrent_sessions(node):
    n, host, port = node
    a = MiniPgClient(host, port)
    b = MiniPgClient(host, port)
    try:
        a.query("CREATE SOURCE s1 (k BIGINT) WITH (connector='datagen')")
        b.query("CREATE SOURCE s2 (k BIGINT) WITH (connector='datagen')")
        _, rows = a.query("SHOW SOURCES")
        assert sorted(rows) == [("s1",), ("s2",)]
    finally:
        a.close()
        b.close()


def test_pgwire_extended_protocol(node):
    """Parse/Bind/Describe/Execute/Sync with a parameter — the message
    flow psycopg/JDBC default to (ref pg_protocol.rs:340,
    e2e_extended_mode)."""
    n, host, port = node
    c = MiniPgClient(host, port)
    try:
        c.query("CREATE TABLE t (k BIGINT, v BIGINT)")
        c.query("INSERT INTO t VALUES (1,10),(2,20),(1,30),(3,7)")
        c.query("""
            CREATE MATERIALIZED VIEW m AS
            SELECT k, count(*) AS n, sum(v) AS s FROM t GROUP BY k
        """)
        c.query("FLUSH")
        cols, rows = c.execute_prepared(
            "SELECT n, s FROM m WHERE k = $1", params=(1,)
        )
        assert cols == ["n", "s"]
        assert rows == [("2", "40")]
        # string parameter quoting round-trips
        cols, rows = c.execute_prepared(
            "SELECT count(*) AS c FROM m WHERE k = $1 OR k = $2",
            params=(2, 3),
        )
        assert rows == [("2",)]
        # error inside a batch discards until Sync; session survives
        with pytest.raises(RuntimeError):
            c.execute_prepared("SELECT nope FROM nowhere")
        _, rows = c.execute_prepared("SELECT k FROM m WHERE k = $1",
                                     params=(3,))
        assert rows == [("3",)]
    finally:
        c.close()


def test_pgwire_cleartext_auth():
    """Password-gated startup (AuthenticationCleartextPassword)."""
    from risingwave_tpu.sql import Engine

    from risingwave_tpu.pgwire import pg_serve

    eng = Engine(PlannerConfig(
        chunk_capacity=64, agg_table_size=256, agg_emit_capacity=64,
        mv_table_size=256, mv_ring_size=1024,
    ))
    server = pg_serve(eng, port=0, password="sekret")
    try:
        host, port = server.server_address
        c = MiniPgClient(host, port, password="sekret")
        _, rows = c.query("SHOW SOURCES")
        assert rows == []
        c.close()
        with pytest.raises((RuntimeError, ConnectionError)):
            MiniPgClient(host, port, password="wrong")
    finally:
        server.shutdown()


def test_background_ticker_advances_jobs():
    """The barrier ticker (barrier_interval_ms) drives jobs on its own."""
    import time

    n = SingleNode(PlannerConfig(
        chunk_capacity=64, agg_table_size=256, agg_emit_capacity=64,
        mv_table_size=256, mv_ring_size=1024,
    ))
    n.engine.system_params.set("barrier_interval_ms", 50)
    server = n.start(port=0)
    try:
        host, port = server.server_address
        c = MiniPgClient(host, port)
        c.query("CREATE SOURCE t (k BIGINT) WITH (connector='datagen')")
        c.query("CREATE MATERIALIZED VIEW m AS SELECT count(*) AS n FROM t")
        deadline = time.time() + 15
        total = 0
        while time.time() < deadline:
            _, rows = c.query("SELECT n FROM m")
            if rows and int(rows[0][0]) > 0:
                total = int(rows[0][0])
                break
            time.sleep(0.1)
        assert total > 0  # the ticker advanced the dataflow by itself
        c.close()
    finally:
        n.stop()
        server.shutdown()


def test_entry_points_import_without_a_backend():
    """One process for each chip: a parent that only imports the entry
    points (and ``chip_smoke``, which is only ever a parent) must not
    initialise a JAX backend — it would hold the chip its children
    need."""
    import os
    import subprocess
    import sys

    code = (
        "import risingwave_tpu.server, risingwave_tpu.sql.engine, "
        "risingwave_tpu.cluster.meta_service, risingwave_tpu.pgwire, "
        "risingwave_tpu.ctl, chip_smoke\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", code], cwd=root,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]


def test_single_node_handshake_and_config_json(tmp_path):
    """The default role prints one JSON handshake line like the others
    — with the device it sees, whether the native codec loaded and
    which crc32c loop it picked (``/metrics`` says that too) — and
    honours ``--config-json``; SIGINT stops it in order, exit code 0."""
    import json
    import os
    import signal
    import socket
    import subprocess
    import sys
    import urllib.request

    with socket.socket() as s, socket.socket() as s2:
        s.bind(("127.0.0.1", 0))
        s2.bind(("127.0.0.1", 0))
        port, mport = s.getsockname()[1], s2.getsockname()[1]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.Popen(
        [sys.executable, "-m", "risingwave_tpu.server", "--port",
         str(port), "--metrics-port", str(mport),
         "--data-dir", str(tmp_path), "--config-json",
         json.dumps({"streaming": {"chunk_size": 256},
                     "state": {"join_pool_size": 1 << 12}})],
        cwd=root, stdout=subprocess.PIPE, text=True)
    try:
        hs = json.loads(proc.stdout.readline())
        assert hs["role"] == "single" and hs["pgwire_port"] == port
        assert hs["platform"] == "cpu" and hs["device_count"] >= 1
        assert hs["native_codec"] is True
        assert hs["crc32c_impl"] in ("hw", "slice8")
        with urllib.request.urlopen(
                f"http://127.0.0.1:{mport}/metrics", timeout=60) as r:
            assert ('codec_crc32c_impl{impl="%s"} 1' % hs["crc32c_impl"]
                    in r.read().decode().splitlines())
        c = MiniPgClient("127.0.0.1", port)
        c.query("CREATE TABLE t (k BIGINT, v BIGINT)")
        c.query("CREATE MATERIALIZED VIEW m AS "
                "SELECT k, sum(v) AS s FROM t GROUP BY k")
        c.query("INSERT INTO t VALUES (1, 2), (1, 3)")
        c.query("FLUSH")
        assert c.query("SELECT k, s FROM m")[1] == [("1", "5")]
        c.close()
        proc.send_signal(signal.SIGINT)
        assert proc.wait(60) == 0
    finally:
        if proc.poll() is None:
            proc.kill()


def test_sigint_taken_by_another_thread_still_stops_the_node():
    """The kernel may hand a process's SIGINT to any thread; Python
    raises KeyboardInterrupt only in the main one, when it next runs
    bytecode.  The roles' main threads must notice within a moment,
    not when an hour's sleep ends."""
    import os
    import subprocess
    import sys

    code = (
        "import signal, sys, threading, time\n"
        "from risingwave_tpu import server\n"
        "def poke():\n"
        "    time.sleep(0.3)\n"
        "    signal.pthread_kill(threading.get_ident(), signal.SIGINT)\n"
        "    time.sleep(60)\n"
        "threading.Thread(target=poke, daemon=True).start()\n"
        "t0 = time.monotonic()\n"
        "try:\n"
        "    server._wait_for_sigint()\n"
        "except KeyboardInterrupt:\n"
        "    sys.exit(0 if time.monotonic() - t0 < 5 else 3)\n"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", code], cwd=root,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr[-2000:]



def test_reads_between_ticks_gather_the_views_blocks():
    """A served node with its ticker running: every read of a windowed
    view, taken between two barriers, returns the reference's rows; the
    readback's span says what it moved and ``/metrics`` counts it under
    the view."""
    import time

    import numpy as np

    from risingwave_tpu.common.trace import GLOBAL_TRACE
    from risingwave_tpu.connector.nexmark import (
        NexmarkConfig,
        NexmarkGenerator,
    )

    slots = 1 << 19
    n = SingleNode(PlannerConfig(
        chunk_capacity=256, agg_table_size=256, agg_emit_capacity=64,
        mv_table_size=slots, mv_ring_size=1024,
    ))
    n.engine.system_params.set("barrier_interval_ms", 20)
    server = n.start(port=0)
    reads = []
    try:
        c = MiniPgClient(*server.server_address)
        c.query(
            "CREATE SOURCE bid (auction BIGINT, price BIGINT, "
            "date_time TIMESTAMP, "
            "WATERMARK FOR date_time AS date_time - INTERVAL '4' SECOND) "
            "WITH (connector='nexmark', nexmark.table='bid', "
            "nexmark.event.rate='2000')")
        c.query(
            "CREATE MATERIALIZED VIEW v AS SELECT window_start, "
            "max(price) AS hi, count(*) AS n "
            "FROM TUMBLE(bid, date_time, INTERVAL '2' SECOND) "
            "GROUP BY window_start")
        GLOBAL_TRACE.clear()
        deadline = time.time() + 60
        while time.time() < deadline and (
                len(reads) < 8 or len(reads[-1]) < 4):
            _, rows = c.query(
                "SELECT window_start, hi, n FROM v ORDER BY window_start")
            reads.append([(int(hi), int(cnt)) for _, hi, cnt in rows])
            time.sleep(0.03)
        c.close()
        text = n.render_metrics()
    finally:
        n.stop()
        server.shutdown()
    assert len(reads) >= 8 and len(reads[-1]) >= 4, reads[-1]
    # the reference: bids come in event-time order, so every window but
    # the newest of a read is complete
    taken = int(n.engine.metrics.get("stream_rows_total", job="v"))
    _, cols, _ = NexmarkGenerator(
        NexmarkConfig(inter_event_us=500)).gen_bids(0, taken).to_host()
    price, ts = np.asarray(cols[2]), np.asarray(cols[5])
    starts = ts - ts % 2_000_000
    want = [(int(price[starts == w].max()), int((starts == w).sum()))
            for w in np.unique(starts)]
    for got in reads:
        # (a read before the view's first barrier holds no window)
        assert got[:-1] == want[:max(len(got) - 1, 0)], (got, want)
        if got:
            hi, cnt = want[len(got) - 1]
            assert got[-1][0] <= hi and got[-1][1] <= cnt
    spans = [s["attrs"] for s in GLOBAL_TRACE.dump()
             if s["name"] == "_mv_rows.to_host"]
    assert len(spans) >= len(reads)
    table_bytes = slots * (3 * 8 + 1)
    for attrs in spans:
        assert attrs["path"] == "gathered", attrs
        assert 0 < attrs["blocks"] <= slots // 512 // 64
        assert 0 < attrs["bytes"] < table_bytes / 32
    line, = [ln for ln in text.splitlines()
             if ln.startswith("mv_read_bytes_total{")]
    assert 'job="v"' in line and 'path="gathered"' in line
    assert float(line.rsplit(" ", 1)[1]) == sum(a["bytes"] for a in spans)
