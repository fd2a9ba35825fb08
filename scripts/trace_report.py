"""Trace-lite acceptance harness: cross-role round traces + overhead.

Two gates for the observability plane (ISSUE 14):

1. **Round-trace assembly** — a 4-role subprocess cluster (1 meta +
   2 compute + 1 serving, real processes) runs N driver-paced rounds;
   for EVERY committed round ``ctl cluster trace`` must assemble one
   complete cross-role span tree: the meta round span parenting the
   worker barrier-phase spans (run_chunks / inject_barrier /
   mv_export), the uploader's prepare/commit spans, a meta commit span that covers
   every worker seal span, and (for rounds after the first serving
   read) at least one sampled serving read span.  The ``--chrome``
   export must be loadable ``trace_event`` JSON, and the meta's
   ``/metrics`` HTTP endpoint plus the aggregated ``cluster_metrics``
   scrape must carry ``barrier_phase_seconds`` for the live job.

2. **Overhead contract** — tracing enabled vs ``trace_sample_n=0``
   A/B on an in-process q1-style bench loop must differ by < 2%
   (medians over interleaved segments; disabled tracing is a null-
   object fast path, not a branch per span).

Run standalone (prints one JSON summary line)::

    python scripts/trace_report.py --rounds 6 --assert

or the ``slow``-marked pytest wrapper (tests/test_trace_report.py).
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, ".")  # repo root

CONFIG = {
    "streaming": {"chunk_size": 256},
    "state": {"agg_table_size": 1 << 10, "agg_emit_capacity": 256,
              "mv_table_size": 1 << 10, "mv_ring_size": 1 << 12},
    "storage": {"checkpoint_keep_epochs": 4},
}

DDL = [
    """CREATE SOURCE bid (
        auction BIGINT, bidder BIGINT, price BIGINT,
        channel VARCHAR, url VARCHAR, date_time TIMESTAMP
    ) WITH (connector = 'nexmark', nexmark.table = 'bid')""",
    """CREATE MATERIALIZED VIEW qcnt AS
    SELECT auction % 16 AS a, count(*) AS n, sum(price) AS vol
    FROM bid GROUP BY auction % 16""",
]

READ = "SELECT a, n, vol FROM qcnt"

#: span names the meta records on the barrier path of every round
META_SPANS = {"round", "barrier", "await_durable", "commit"}
#: span names the owning worker records inside its barrier handling
WORKER_SPANS = {"run_chunks", "inject_barrier", "mv_export"}


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _env() -> dict:
    env = dict(os.environ)
    env.pop("RWT_FAULTS", None)
    return env


def _spawn(role: str, data_dir: str, rpc_port: int,
           metrics_port: int = 0, idx: int = 0):
    argv = [sys.executable, "-m", "risingwave_tpu.server",
            "--role", role, "--data-dir", data_dir,
            "--trace-sample-n", "1"]
    if role == "meta":
        argv += ["--port", str(_free_port()),
                 "--rpc-port", str(rpc_port),
                 "--heartbeat-timeout", "3.0",
                 "--barrier-interval-ms", "0",  # driver-paced rounds
                 "--scrub-interval", "0"]
        if metrics_port:
            argv += ["--metrics-port", str(metrics_port)]
    else:
        argv += ["--meta", f"127.0.0.1:{rpc_port}",
                 "--heartbeat-interval", "0.25"]
        if role == "compute":
            argv += ["--config-json", json.dumps(CONFIG)]
    return subprocess.Popen(
        argv, stdout=subprocess.DEVNULL,
        stderr=open(os.path.join(data_dir, f"{role}{idx}.log"), "wb"),
        env=_env(),
    )


class MetaDriver:
    """Patient RPC driver (scripts/chaos_campaign.py idiom)."""

    def __init__(self, rpc_port: int):
        from risingwave_tpu.cluster.rpc import RpcClient

        self.client = RpcClient("127.0.0.1", rpc_port, timeout=120.0,
                                src="driver", dst="meta")

    def call(self, method: str, deadline_s: float = 120.0, **params):
        from risingwave_tpu.cluster.rpc import RpcError

        deadline = time.monotonic() + deadline_s
        while True:
            try:
                return self.client.call(method, **params)
            except RpcError:
                raise
            except (ConnectionError, OSError):
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.1)

    def close(self) -> None:
        self.client.close()


def _span_window(spans: list, name: str) -> "tuple | None":
    picked = [s for s in spans if s["name"] == name]
    if not picked:
        return None
    return (min(s["ts"] for s in picked),
            max(s["ts"] + s["dur"] for s in picked))


def run_cluster(rounds: int = 6, workers: int = 2,
                chrome: str | None = None,
                data_dir: str | None = None) -> dict:
    """Gate 1: the 4-role round-trace assembly run."""
    data_dir = data_dir or tempfile.mkdtemp(prefix="trace_report_")
    rpc_port = _free_port()
    metrics_port = _free_port()
    procs = [_spawn("meta", data_dir, rpc_port,
                    metrics_port=metrics_port)]
    procs += [_spawn("compute", data_dir, rpc_port, idx=i)
              for i in range(workers)]
    procs.append(_spawn("serving", data_dir, rpc_port))
    driver = MetaDriver(rpc_port)
    failures: list[str] = []
    try:
        deadline = time.monotonic() + 120
        while True:
            st = driver.call("cluster_state", deadline_s=120.0)
            live = [w for w in st["workers"] if w["alive"]]
            replicas = [r for r in st.get("serving", []) if r["alive"]]
            if len(live) >= workers and replicas:
                break
            if time.monotonic() > deadline:
                raise TimeoutError("cluster never fully registered")
            for p in procs:
                if p.poll() is not None:
                    raise RuntimeError(
                        f"a role died at startup (logs in {data_dir})")
            time.sleep(0.25)

        for sql in DDL:
            driver.call("execute_ddl", sql=sql)

        committed: list[int] = []
        for _ in range(rounds):
            round_deadline = time.monotonic() + 240
            while True:
                res = driver.call("tick", chunks_per_barrier=1)
                if res["committed"]:
                    committed.append(res["round"])
                    break
                if time.monotonic() > round_deadline:
                    raise TimeoutError("round never committed")
                time.sleep(0.2)
            # a serving read per round: once the replica's heartbeat
            # picks up the round ctx, sampled read spans join the tree
            driver.call("serve", sql=READ, deadline_s=180.0)
        # let serving heartbeats fetch the last round ctx + read once
        time.sleep(0.6)
        driver.call("serve", sql=READ, deadline_s=180.0)
        # drain the async uploaders' ckpt spans into the ring
        time.sleep(0.5)

        round_reports = {}
        serving_rounds = 0
        for rn in committed:
            tr = driver.call("cluster_trace", round=rn)
            names = {s["name"] for s in tr["spans"]}
            chk = tr["check"]
            if not chk["complete"]:
                failures.append(f"round {rn}: tree incomplete {chk}")
            missing = (META_SPANS | WORKER_SPANS) - names
            if missing:
                failures.append(
                    f"round {rn}: missing spans {sorted(missing)}")
            # the meta round span must COVER every worker seal span
            root = _span_window(tr["spans"], "round")
            seal = _span_window(tr["spans"], "inject_barrier")
            if root and seal:
                slack = 0.25
                if seal[0] < root[0] - slack or seal[1] > root[1] + slack:
                    failures.append(
                        f"round {rn}: seal window {seal} outside "
                        f"round window {root}")
            if "serving_read" in names:
                serving_rounds += 1
            round_reports[rn] = {"names": sorted(names),
                                 "check": chk}
        # uploader spans are async: require them in at least one round
        all_names = {n for r in round_reports.values()
                     for n in r["names"]}
        for want in ("ckpt_prepare", "ckpt_commit"):
            if want not in all_names:
                failures.append(f"no {want} span in any round")
        if serving_rounds == 0:
            failures.append("no sampled serving_read span joined "
                            "any round trace")

        # chrome export loads as trace_event JSON
        last = driver.call("cluster_trace", round=committed[-1])
        from risingwave_tpu.common.trace import to_chrome_trace
        ct = to_chrome_trace(last["spans"])
        if chrome:
            with open(chrome, "w") as f:
                json.dump(ct, f)
        if not ct["traceEvents"] or not any(
                e.get("ph") == "X" for e in ct["traceEvents"]):
            failures.append("chrome export has no complete events")

        # unified metrics plane: aggregated scrape + meta /metrics
        mtext = driver.call("cluster_metrics")["prometheus"]
        if 'barrier_phase_seconds_bucket{job="qcnt"' not in mtext:
            failures.append(
                "aggregated scrape lacks barrier_phase_seconds for "
                "the live job")
        if 'role="meta"' not in mtext or "worker=" not in mtext:
            failures.append("aggregated scrape lacks identity labels")
        import urllib.request
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{metrics_port}/metrics",
                    timeout=10) as resp:
                http_text = resp.read().decode()
            if "cluster_epoch" not in http_text:
                failures.append("/metrics endpoint missing meta gauges")
        except OSError as e:
            failures.append(f"/metrics endpoint unreachable: {e!r}")

        return {
            "rounds_committed": committed,
            "serving_read_rounds": serving_rounds,
            "round_reports": round_reports,
            "chrome_events": len(ct["traceEvents"]),
            "failures": failures,
            "data_dir": data_dir,
        }
    finally:
        driver.close()
        for p in procs:
            if p.poll() is None:
                p.kill()
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass


def run_overhead(iters: int = 6, chunks: int = 4) -> dict:
    """Gate 2: tracing on/off A/B on an in-process q1-style loop.
    Interleaved segments, medians — the contract is that DISABLED
    tracing costs nothing measurable on the chunk path."""
    from risingwave_tpu.common.config import RwConfig
    from risingwave_tpu.common.trace import GLOBAL_TRACE
    from risingwave_tpu.sql.engine import Engine

    eng = Engine(RwConfig.from_dict(CONFIG))
    eng.execute(DDL[0])
    eng.execute(
        # q1-style stateless projection over the bid stream
        "CREATE MATERIALIZED VIEW q1 AS "
        "SELECT auction % 32 AS a, count(*) AS n FROM bid "
        "GROUP BY auction % 32"
    )
    eng.tick(barriers=2, chunks_per_barrier=chunks)  # warm/compile

    def segment() -> float:
        t0 = time.perf_counter()
        eng.tick(barriers=1, chunks_per_barrier=chunks)
        return time.perf_counter() - t0

    on: list[float] = []
    off: list[float] = []
    prev = GLOBAL_TRACE.sample_n
    try:
        for _ in range(iters):
            GLOBAL_TRACE.configure(sample_n=1)
            on.append(segment())
            GLOBAL_TRACE.configure(sample_n=0)
            off.append(segment())
    finally:
        GLOBAL_TRACE.configure(sample_n=prev)
    med_on = sorted(on)[len(on) // 2]
    med_off = sorted(off)[len(off) // 2]
    overhead = (med_on - med_off) / med_off if med_off > 0 else 0.0
    return {"median_on_s": round(med_on, 5),
            "median_off_s": round(med_off, 5),
            "overhead_frac": round(overhead, 4)}


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--rounds", type=int, default=6)
    p.add_argument("--workers", type=int, default=2)
    p.add_argument("--chrome", default=None,
                   help="also write Chrome trace_event JSON here")
    p.add_argument("--overhead-iters", type=int, default=6)
    p.add_argument("--overhead-budget", type=float, default=0.02)
    p.add_argument("--skip-overhead", action="store_true")
    p.add_argument("--skip-cluster", action="store_true")
    p.add_argument("--assert", dest="check", action="store_true",
                   help="exit nonzero unless every committed round "
                        "assembles a complete cross-role span tree "
                        "and the A/B overhead is under budget")
    args = p.parse_args()

    summary: dict = {}
    ok = True
    if not args.skip_cluster:
        cl = run_cluster(rounds=args.rounds, workers=args.workers,
                         chrome=args.chrome)
        summary["cluster"] = {k: v for k, v in cl.items()
                              if k != "round_reports"}
        ok &= not cl["failures"]
    if not args.skip_overhead:
        ov = run_overhead(iters=args.overhead_iters)
        summary["overhead"] = ov
        ov["budget"] = args.overhead_budget
        ov["ok"] = ov["overhead_frac"] < args.overhead_budget
        ok &= ov["ok"]
    summary["ok"] = bool(ok)
    print(json.dumps(summary))
    if args.check:
        raise SystemExit(0 if ok else 1)


if __name__ == "__main__":
    main()
