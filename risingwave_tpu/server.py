"""Node entrypoint: single-binary, meta, compute, or serving process.

Reference counterparts: the single-binary mode (``src/cmd_all/src/
single_node.rs``) bundling frontend + meta + compute into one process,
and the per-role binaries (``src/cmd/src/bin/{meta,compute}_node.rs``;
the frontend/serving node) the multi-process deployment launches.

    # everything in one process (the default)
    python -m risingwave_tpu.server --port 4566 --data-dir ./data

    # a 1-meta + 2-compute cluster over one shared data_dir
    python -m risingwave_tpu.server --role meta --port 4566 \
        --rpc-port 4600 --data-dir ./data
    python -m risingwave_tpu.server --role compute \
        --meta 127.0.0.1:4600 --data-dir ./data   # run twice

    # N stateless serving replicas (ENGINE-FREE: the process never
    # imports jax — it reads MV rows straight from shared SSTs at the
    # meta's pinned epoch)
    python -m risingwave_tpu.server --role serving \
        --meta 127.0.0.1:4600 --data-dir ./data   # run N times

The meta process hosts the pgwire front door: DDL places streaming
jobs on workers, SELECTs route round-robin across live serving
replicas pinned at the last cluster-committed epoch (falling back to
the owning worker — cluster/meta_service.py).

Engine imports stay INSIDE the non-serving paths: ``--role serving``
must boot without jax (the package __init__ skips the jax import for
that role; see risingwave_tpu/__init__.py).
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time
import traceback

from risingwave_tpu.common.trace import GLOBAL_TRACE, to_chrome_trace


def _start_metrics_http(render, host: str, port: int):
    """Per-role stdlib ``/metrics`` endpoint (the unified metrics
    plane's per-process scrape surface — the meta's ``ctl cluster
    metrics`` aggregates the same text over RPC, so a Prometheus
    deployment can scrape either each process or just the meta), and
    ``/trace`` beside it (``_render_trace``)."""
    import http.server

    class _Handler(http.server.BaseHTTPRequestHandler):
        def do_GET(self):  # noqa: N802 — http.server API
            path, _, query = self.path.partition("?")
            if path == "/trace":
                body, ctype = _render_trace(query), "application/json"
            elif path in ("/", "/metrics"):
                body = render().encode()
                ctype = "text/plain; version=0.0.4"
            else:
                self.send_error(404)
                return
            self.send_response(200)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):  # per-scrape stderr spam
            pass

    httpd = http.server.ThreadingHTTPServer((host, port), _Handler)
    threading.Thread(target=httpd.serve_forever,
                     name="metrics-http", daemon=True).start()
    return httpd


def _render_trace(query: str) -> bytes:
    """``GET /trace``: this process's span ring as JSON, oldest first
    (``?format=chrome``: Chrome ``trace_event`` JSON for Perfetto) —
    for one process what ``ctl cluster trace`` is for a cluster.  Takes
    no engine lock."""
    from urllib.parse import parse_qs

    q = parse_qs(query)
    spans = GLOBAL_TRACE.dump((q.get("trace_id") or [None])[0])
    if (q.get("format") or [""])[0] == "chrome":
        return json.dumps(to_chrome_trace(spans)).encode()
    return json.dumps({"role": GLOBAL_TRACE.role, "spans": spans}).encode()


def _handshake(role: str, metrics, **fields) -> None:
    """The one JSON line every role prints once it is up: ports and
    ids, whether the native codec loaded (built and checked here if
    need be) and which crc32c loop this CPU gets from it (also the
    gauge ``codec_crc32c_impl{impl}`` of the role's ``metrics``), and
    what JAX sees.  The meta never asks for devices and the serving
    replica never imports jax — they say only that much (``fields``)."""
    from risingwave_tpu.storage.codec import crc32c_impl, native_available

    line = {"role": role, **fields, "native_codec": native_available(),
            "crc32c_impl": crc32c_impl()}
    metrics.set_gauge("codec_crc32c_impl", 1, impl=line["crc32c_impl"])
    if role in ("single", "compute"):
        import jax

        devs = jax.devices()
        line.update(platform=devs[0].platform,
                    device_kind=devs[0].device_kind,
                    device_count=len(devs))
    print(json.dumps(line), flush=True)


def _wait_for_sigint() -> None:
    """Park a role's main thread until SIGINT raises KeyboardInterrupt
    in it.  Python runs a signal's handler in the main thread only, and
    only when that thread next runs bytecode: a SIGINT the kernel hands
    to another thread (the runtime's, an uploader's) does not end a
    long ``sleep`` here, and the orderly stop would not begin until the
    sleep did (seen on the chip: the node still ticking 60 s after the
    signal, 2 runs of 39).  So the sleep is short."""
    while True:
        time.sleep(0.2)


class SingleNode:
    def __init__(self, config=None, data_dir: str | None = None):
        from risingwave_tpu.pgwire import EngineLock
        from risingwave_tpu.sql.engine import Engine

        self.engine = Engine(config, data_dir=data_dir)
        self._stop = threading.Event()
        self._ticker: threading.Thread | None = None
        self._lock = EngineLock()

    # -- barrier loop ---------------------------------------------------
    def _tick_loop(self) -> None:
        while not self._stop.is_set():
            interval = int(
                self.engine.system_params.get("barrier_interval_ms")
            ) / 1000.0
            t0 = time.monotonic()
            try:
                self._tick_once()
            except Exception as e:
                # a barrier that raised (state overflow, a failed
                # upload) is counted and logged, never a silently dead
                # ticker behind a live pgwire port
                self.engine.metrics.inc("barrier_loop_errors_total")
                print(f"barrier loop failed: {e!r}", file=sys.stderr)
                traceback.print_exc()
            elapsed = time.monotonic() - t0
            # never zero: a saturated loop that re-takes the engine
            # lock at once starves the pgwire sessions waiting for it
            self._stop.wait(max(interval - elapsed, 0.001))

    def _tick_once(self) -> None:
        """One barrier of the loop, one ``tick-<n>`` span tree: the
        root runs from asking for the engine lock (``tick.lock_wait``
        is what reads and scrapes take out of every tick) to
        ``Engine.tick`` returning.  Another tick follows, so the tick
        may send the next window ahead while no statement waits."""
        eng = self.engine
        if not eng.jobs or all(map(eng.ingest_waits, eng.jobs)):
            return  # nothing to drive: no barrier, no tree
        with GLOBAL_TRACE.root("tick", "tick",
                               metrics=self.engine.metrics):
            with GLOBAL_TRACE.held(self._lock, "tick.lock_wait"):
                if self.engine.jobs:
                    self.engine.tick(
                        barriers=1,
                        ahead=lambda: self._lock.statements == 0)

    def start(self, host: str = "127.0.0.1", port: int = 4566,
              ticker: bool = True):
        from risingwave_tpu.pgwire import pg_serve

        if ticker:
            self._ticker = threading.Thread(
                target=self._tick_loop, daemon=True
            )
            self._ticker.start()
        # durable nodes run the storage service's background compactor
        # (the fourth node role, embedded single-binary style)
        self.engine.start_storage_service()
        # pgwire statements and the ticker share the engine lock
        server = pg_serve(self.engine, host, port, engine_lock=self._lock)
        return server

    def render_metrics(self) -> str:
        """The scrape: the registry, after the on-demand collectors
        (device readbacks, so between barriers, under the engine
        lock).  One ``scrape-<n>`` span tree."""
        with GLOBAL_TRACE.root("scrape", "render_metrics",
                               metrics=self.engine.metrics):
            with GLOBAL_TRACE.held(self._lock,
                                   "render_metrics.lock_wait"):
                with GLOBAL_TRACE.span("render_metrics.collect"):
                    self.engine.collect_join_metrics()
                    self.engine.collect_checkpoint_metrics()
                    self.engine.collect_shard_metrics()
            return self.engine.metrics.render_prometheus()

    def tick(self, barriers: int = 1,
             chunks_per_barrier: int | None = None) -> None:
        """Deterministic manual ticks (tests/FLUSH); lock-coordinated
        with the background ticker."""
        with self._lock:
            self.engine.tick(barriers, chunks_per_barrier)

    def stop(self) -> None:
        """Orderly shutdown: stop the ticker, then seal + commit ONE
        final barrier before the compactor/pgwire go away — every
        acked write (chunks processed since the last barrier) lands in
        a committed checkpoint instead of dying with the process."""
        self._stop.set()
        if self._ticker is not None:
            self._ticker.join(timeout=5)
            self._ticker = None
        try:
            with self._lock:
                if self.engine.jobs:
                    # a window the ticker sent ahead seals first; then
                    # chunks_per_barrier=0: flush/commit what already
                    # flowed, pull nothing new on the way out (tick's
                    # batch boundary also drains the upload queue)
                    self.engine.settle("stop")
                    self.engine.tick(barriers=1, chunks_per_barrier=0)
        finally:
            try:
                self.engine.drain_uploads()
            finally:
                self.engine.stop_storage_service()


def _run_meta(args) -> None:
    from risingwave_tpu.cluster import MetaFrontend, MetaService
    from risingwave_tpu.pgwire import pg_serve

    meta = MetaService(
        args.data_dir or "./data",
        heartbeat_timeout_s=args.heartbeat_timeout,
        n_vnodes=args.n_vnodes,
        scale_partitioning=args.scale_partitioning,
        shuffle_ingest=not args.no_shuffle_ingest,
        scrub_interval_s=args.scrub_interval,
        serve_retry_timeout_s=args.serve_retry_timeout,
    ).start(args.host, args.rpc_port,
            scrubber=args.scrub_interval > 0)
    front = MetaFrontend(meta)
    server = pg_serve(front, args.host, args.port)
    if args.metrics_port:
        _start_metrics_http(meta.metrics.render_prometheus,
                            args.host, args.metrics_port)
    _handshake("meta", meta.metrics, pgwire_port=args.port,
               rpc_port=meta.rpc_port,
               metrics_port=args.metrics_port or None,
               backend_initialized=meta.state()["backend_initialized"])

    stop = threading.Event()

    def tick_loop():
        while not stop.is_set():
            t0 = time.monotonic()
            try:
                meta.tick()
            except Exception as e:
                # incomplete rounds retry next interval — counted and
                # logged, never silent
                meta.metrics.inc("cluster_tick_errors_total")
                print(f"meta tick failed: {e!r}", file=sys.stderr)
                traceback.print_exc()
            elapsed = time.monotonic() - t0
            stop.wait(max(args.barrier_interval_ms / 1000.0 - elapsed,
                          0.0))

    # --barrier-interval-ms 0: NO self-ticker — an external driver
    # owns the round cadence through ``rpc_tick`` (the deterministic
    # mode the chaos campaign uses to count committed rounds exactly)
    if args.barrier_interval_ms > 0:
        threading.Thread(target=tick_loop, daemon=True).start()
    try:
        _wait_for_sigint()
    except KeyboardInterrupt:
        stop.set()
        meta.stop()
        server.shutdown()


def _node_config(args):
    """``--config-json`` as an RwConfig (single and compute roles)."""
    from risingwave_tpu.common.config import RwConfig

    return RwConfig.from_dict(json.loads(args.config_json)) \
        if args.config_json else None


def _run_compute(args) -> None:
    from risingwave_tpu.cluster import ComputeWorker

    worker = ComputeWorker(
        args.meta, args.data_dir or "./data", config=_node_config(args),
        host=args.host, port=args.rpc_port,
        heartbeat_interval_s=args.heartbeat_interval,
    ).start()
    GLOBAL_TRACE.configure(metrics=worker.engine.metrics)
    if args.metrics_port:
        _start_metrics_http(worker.engine.metrics.render_prometheus,
                            args.host, args.metrics_port)
    _handshake("compute", worker.engine.metrics,
               worker_id=worker.worker_id, port=worker.port,
               metrics_port=args.metrics_port or None)
    try:
        _wait_for_sigint()
    except KeyboardInterrupt:
        worker.stop()


def _run_serving(args) -> None:
    from risingwave_tpu.serve import ServingWorker

    replica = ServingWorker(
        args.meta, args.data_dir or "./data",
        host=args.host, port=args.rpc_port,
        heartbeat_interval_s=args.heartbeat_interval,
        cache_blocks=args.serving_cache_blocks,
        result_cache_bytes=args.serving_result_cache_bytes,
        negative_cache_keys=args.serving_negative_cache_keys,
        warmup_keys=args.serving_warmup_keys,
    ).start()
    if args.metrics_port:
        _start_metrics_http(replica.metrics.render_prometheus,
                            args.host, args.metrics_port)
    # the engine-free contract: tests parse this line and assert jax
    # never loaded
    _handshake("serving", replica.metrics,
               replica_id=replica.replica_id,
               port=replica.port,
               metrics_port=args.metrics_port or None,
               jax_loaded="jax" in sys.modules)
    try:
        _wait_for_sigint()
    except KeyboardInterrupt:
        replica.stop()


def main() -> None:
    p = argparse.ArgumentParser(description="risingwave_tpu node")
    p.add_argument("--role",
                   choices=["single", "meta", "compute", "serving"],
                   default="single")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=4566,
                   help="pgwire port (single/meta roles)")
    p.add_argument("--rpc-port", type=int, default=0,
                   help="control RPC port (meta/compute; 0 = ephemeral)")
    p.add_argument("--meta", default="127.0.0.1:4600",
                   help="meta RPC address (compute/serving roles)")
    p.add_argument("--data-dir", default=None)
    p.add_argument("--config-json", default=None,
                   help="RwConfig overrides as JSON (single/compute roles)")
    p.add_argument("--heartbeat-interval", type=float, default=0.5)
    p.add_argument("--heartbeat-timeout", type=float, default=3.0)
    p.add_argument("--barrier-interval-ms", type=int, default=1000)
    p.add_argument("--scrub-interval", type=float, default=30.0,
                   help="seconds between background integrity-scrub "
                        "cycles on the meta (0 = disabled)")
    p.add_argument("--serve-retry-timeout", type=float, default=60.0,
                   help="how long a serving read waits through "
                        "failover/repair windows before erroring")
    p.add_argument("--serving-cache-blocks", type=int, default=1024,
                   help="serving block-cache capacity (serving role)")
    p.add_argument("--serving-result-cache-bytes", type=int,
                   default=32 << 20,
                   help="serving result-cache budget in bytes "
                        "(serving role; 0 disables)")
    p.add_argument("--serving-negative-cache-keys", type=int,
                   default=65536,
                   help="serving per-vid negative-cache capacity "
                        "(known-missing pks; 0 disables)")
    p.add_argument("--serving-warmup-keys", type=int, default=8,
                   help="hottest sqls replayed against each fresh "
                        "lease grant (result-cache warmup; "
                        "0 disables)")
    p.add_argument("--n-vnodes", type=int, default=64,
                   help="scale plane: vnode ring size (meta role)")
    p.add_argument("--scale-partitioning", action="store_true",
                   help="scale plane: partition eligible jobs over "
                        "the vnode map (meta role); `ctl cluster "
                        "scale N` then moves only vnodes")
    p.add_argument("--no-shuffle-ingest", action="store_true",
                   help="exchange plane: disable sliced ingest "
                        "(meta role) — DML batches replicate to "
                        "every partition host and the VnodeGate "
                        "filters (the PR-7 baseline)")
    p.add_argument("--metrics-port", type=int, default=0,
                   help="HTTP /metrics port for this process "
                        "(0 = disabled); the unified plane also "
                        "aggregates over RPC via `ctl cluster "
                        "metrics`")
    p.add_argument("--trace-sample-n", type=int, default=1,
                   help="trace-lite sampling: 0 disables tracing "
                        "entirely; N>=1 records every control-plane "
                        "span and 1-in-N data-plane spans")
    p.add_argument("--trace-buffer-spans", type=int, default=4096,
                   help="per-process span flight-recorder capacity")
    args = p.parse_args()

    # trace-lite identity + sampling, wired BEFORE any role boots so
    # even registration RPCs carry (or drop) trace context uniformly.
    # A compute --config-json may override via ClusterConfig.
    sample_n, capacity = args.trace_sample_n, args.trace_buffer_spans
    if args.config_json:
        try:
            cj = json.loads(args.config_json).get("cluster") or {}
            sample_n = int(cj.get("trace_sample_n", sample_n))
            capacity = int(cj.get("trace_buffer_spans", capacity))
        except (ValueError, TypeError, AttributeError):
            pass
    GLOBAL_TRACE.configure(role=args.role, sample_n=sample_n,
                           capacity=capacity)
    if args.role in ("single", "compute"):
        # the roles that hold a chip import jax anyway: their spans
        # are also annotations on the profiler's host plane, on one
        # clock with the device's operations (a TraceMe with no
        # session active costs tens of nanoseconds)
        import jax.profiler

        GLOBAL_TRACE.configure(annotate=jax.profiler.TraceAnnotation)

    if args.role == "meta":
        _run_meta(args)
        return
    if args.role == "compute":
        _run_compute(args)
        return
    if args.role == "serving":
        _run_serving(args)
        return
    node = SingleNode(_node_config(args), data_dir=args.data_dir)
    GLOBAL_TRACE.configure(metrics=node.engine.metrics)
    server = node.start(args.host, args.port)
    if args.metrics_port:
        _start_metrics_http(node.render_metrics,
                            args.host, args.metrics_port)
    _handshake("single", node.engine.metrics, pgwire_port=args.port,
               metrics_port=args.metrics_port or None)
    try:
        _wait_for_sigint()
    except KeyboardInterrupt:
        node.stop()
        server.shutdown()


if __name__ == "__main__":
    main()
