"""CH-benCHmark closed-loop driver: OLTP + MV maintenance + serving.

One process plays the benchmark coordinator against a REAL 4-role
cluster — in-process meta (driver-paced barrier rounds, direct metrics
access), N compute worker subprocesses, one serving-replica
subprocess — and keeps three planes busy SIMULTANEOUSLY:

- **ingest**: a dedicated thread pumps the seeded ``TxGen`` transaction
  mix (NewOrder/Payment/Delivery) as multi-table DML batches with
  exact-full-row retractions, routed through the meta's DML forwarding
  (ingest leaders for partitioned jobs);
- **maintenance**: the main thread drives global barrier rounds; every
  CH view (including the MV-on-MV chain and the secondary index)
  advances through the same commits;
- **serving**: reader threads mix ``serve_batch`` full-view reads,
  ``serve_multi_get`` point lookups, and secondary-index equality
  reads, all pinned at committed epochs.

The run ends with the workload plane's strongest check: every CH view
on the cluster must be BYTE-IDENTICAL to a single-node replay of the
same seeded transaction log (``TxGen`` is the log — same seed, same
bytes).  ``check()`` folds throughput floors, the barrier-commit p99
ceiling, the serving p99.9 ceiling, zero read errors, and the
byte-identity verdict into one assertion; ``write_artifact`` emits
``CH_BENCH.json`` in the bench-artifact shape.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import threading
import time

from risingwave_tpu.common.metrics import (GLOBAL_METRICS,
                                           WIDE_SECONDS_BUCKETS)
from risingwave_tpu.workload.queries import (CH_INDEXES, CH_READS,
                                             query_group)
from risingwave_tpu.workload.schema import CHScale, schema_ddl
from risingwave_tpu.workload.txgen import TxGen

#: shared by the compute workers AND the single-node replay engine —
#: byte identity only means something when both sides run one config
CONFIG = {
    "streaming": {"chunk_size": 256},
    "state": {"agg_table_size": 1 << 11, "agg_emit_capacity": 512,
              "mv_table_size": 1 << 11, "mv_ring_size": 1 << 13},
    "storage": {"checkpoint_keep_epochs": 4},
}


def observe_txn(kind: str, seconds: float, rows: int,
                metrics=None) -> None:
    """Record one transaction on the workload metric families:
    ``workload_txn_total{type=...}``, ``workload_txn_rows_total`` and
    ``workload_txn_seconds{type=...}`` (wide grid: a txn stalled
    behind a compile-heavy barrier legitimately takes seconds)."""
    m = metrics if metrics is not None else GLOBAL_METRICS
    m.inc("workload_txn_total", type=kind)
    m.inc("workload_txn_rows_total", rows)
    m.observe("workload_txn_seconds", seconds,
              buckets=WIDE_SECONDS_BUCKETS, type=kind)


def _dml_rows(sql: str) -> int:
    """Row count of one generated DML statement.  TxGen emits only
    integer and paren-free string literals, so every ``(`` opens
    exactly one VALUES tuple."""
    return sql.count("(")


def _percentile(samples: list, q: float) -> float:
    """Weighted percentile over (latency_s, n_reads) batch samples
    (the serve_bench idiom: every read in a batch experiences the
    batch's latency)."""
    if not samples:
        return 0.0
    ordered = sorted(samples)
    total = sum(n for _, n in ordered)
    target = q * total
    seen = 0
    for lat, n in ordered:
        seen += n
        if seen >= target:
            return lat
    return ordered[-1][0]


def _spawn(role: str, meta_port: int, data_dir: str, idx: int = 0):
    argv = [sys.executable, "-m", "risingwave_tpu.server",
            "--role", role, "--meta", f"127.0.0.1:{meta_port}",
            "--data-dir", data_dir, "--heartbeat-interval", "0.25"]
    if role == "compute":
        argv += ["--config-json", json.dumps(CONFIG)]
    # the child inherits this environment as it is
    return subprocess.Popen(
        argv, stdout=subprocess.DEVNULL,
        stderr=open(os.path.join(data_dir, f"{role}{idx}.log"), "wb"),
    )


def _norm(rows) -> list:
    return sorted(
        tuple(x if isinstance(x, str) else int(x) for x in r)
        for r in rows
    )


def run(rounds: int = 60, seed: int = 11, workers: int = 2,
        readers: int = 2, small: bool = False,
        chunks_per_barrier: int = 1, txn_pause_s: float = 0.0,
        scale: CHScale | None = None,
        data_dir: str | None = None) -> dict:
    from risingwave_tpu.cluster import MetaService
    from risingwave_tpu.common.config import RwConfig
    from risingwave_tpu.sql.engine import Engine

    scale = scale or CHScale()
    group = query_group(small=small)
    group_names = [n for n, _ in group]
    reads = {n: CH_READS[n] for n in group_names}

    data_dir = data_dir or tempfile.mkdtemp(prefix="ch_bench_")
    meta = MetaService(data_dir, heartbeat_timeout_s=4.0)
    meta.start(port=0)
    procs = [_spawn("compute", meta.rpc_port, data_dir, i)
             for i in range(workers)]
    procs.append(_spawn("serving", meta.rpc_port, data_dir))

    state = {
        "reads": 0, "read_errors": [], "ingest_errors": [],
        "rounds_committed": 0, "tick_retries": 0,
        "txns": {"new_order": 0, "payment": 0, "delivery": 0},
        "ingest_rows": 0, "multi_gets": 0, "index_reads": 0,
        "last_cnt": None,
    }
    samples: list[tuple[float, int]] = []
    replay_log: list[str] = []
    stop_ingest = threading.Event()
    stop_read = threading.Event()
    gen = TxGen(seed, scale)

    def ingest_loop():
        while not stop_ingest.is_set():
            kind, stmts = gen.next_transaction()
            if not stmts:  # a delivery with nothing undelivered
                state["txns"][kind] += 1
                continue
            # one multi-statement text per transaction: the meta
            # parses once and forwards statement-by-statement, and
            # the replay engine applies the identical text
            text = ";\n".join(stmts)
            nrows = _dml_rows(text)
            t0 = time.perf_counter()
            try:
                meta.execute_ddl(text)
                replay_log.append(text)
            except Exception as e:  # noqa: BLE001
                state["ingest_errors"].append(repr(e))
                stop_ingest.set()
                return
            observe_txn(kind, time.perf_counter() - t0, nrows)
            state["txns"][kind] += 1
            state["ingest_rows"] += nrows
            if txn_pause_s:
                time.sleep(txn_pause_s)

    def read_loop():
        batch = list(reads.values())
        mg_keys = [[n] for n in range(1, scale.max_lines + 2)]
        while not stop_read.is_set():
            try:
                t0 = time.perf_counter()
                res = meta.serve_batch(batch)
                samples.append((time.perf_counter() - t0, len(batch)))
                state["reads"] += len(batch)
                for (cols, rows), name in zip(res, reads):
                    if name == "ch_q1" and rows:
                        state["last_cnt"] = int(rows[0][-1])
                t0 = time.perf_counter()
                meta.serve_multi_get(
                    "ch_q1", mg_keys,
                    cols=["ol_number", "count_order"])
                samples.append((time.perf_counter() - t0, 1))
                state["reads"] += 1
                state["multi_gets"] += 1
                cnt = state["last_cnt"]
                if cnt is not None:
                    # equality probe on the indexed non-key column:
                    # served through the ch_q1_cnt secondary index
                    t0 = time.perf_counter()
                    meta.serve(
                        "SELECT ol_number, count_order FROM ch_q1 "
                        f"WHERE count_order = {cnt}")
                    samples.append((time.perf_counter() - t0, 1))
                    state["reads"] += 1
                    state["index_reads"] += 1
            except Exception as e:  # noqa: BLE001
                state["read_errors"].append(repr(e))
            time.sleep(0.02)

    def tick_committed(deadline_s: float = 900.0) -> None:
        deadline = time.monotonic() + deadline_s
        while True:
            if meta.tick(chunks_per_barrier)["committed"]:
                return
            state["tick_retries"] += 1
            if time.monotonic() > deadline:
                raise TimeoutError("barrier round never committed")
            time.sleep(0.2)

    threads: list[threading.Thread] = []
    try:
        deadline = time.monotonic() + 120
        while len(meta.live_workers()) < workers:
            if time.monotonic() > deadline:
                raise TimeoutError("workers never registered")
            for p in procs:
                if p.poll() is not None:
                    raise RuntimeError(
                        f"a role died at startup (logs in {data_dir})")
            time.sleep(0.25)

        # DDL + static load, recorded verbatim for the replay engine
        ddl: list[str] = list(schema_ddl())
        ddl += [d for _, d in group]
        if "ch_q1" in group_names:
            ddl += [d for _, d in CH_INDEXES]
        for sql in ddl:
            meta.execute_ddl(sql)
            replay_log.append(sql)
        for sql in gen.initial_load():
            meta.execute_ddl(sql)
            replay_log.append(sql)

        # warmup: rounds 1-2 pay the jit compiles; the barrier-commit
        # p99 gate starts from this snapshot
        for _ in range(2):
            tick_committed()
        state["rounds_committed"] = 2
        barrier_baseline = meta.metrics.hist_counts(
            "cluster_barrier_commit_seconds")

        ingester = threading.Thread(target=ingest_loop, daemon=True)
        ingester.start()
        threads = [threading.Thread(target=read_loop, daemon=True)
                   for _ in range(readers)]
        for t in threads:
            t.start()

        t_ingest0 = time.monotonic()
        for r in range(3, rounds + 1):
            tick_committed()
            state["rounds_committed"] = r
            if state["ingest_errors"]:
                break

        stop_ingest.set()
        ingester.join(timeout=60)
        ingest_wall = max(time.monotonic() - t_ingest0, 1e-9)
        stop_read.set()
        for t in threads:
            t.join(timeout=10)

        # single-node replay of the SAME seeded log (DDL + load + txn
        # stream in recorded order) — the byte-identity oracle
        eng = Engine(RwConfig.from_dict(CONFIG))
        for sql in replay_log:
            eng.execute(sql)
        eng.execute("FLUSH")
        expected = {n: _norm(eng.execute(q))
                    for n, q in reads.items()}

        # convergence fence: keep committing rounds until the cluster
        # has drained every forwarded row and each CH view matches
        mismatched = list(reads)
        fence_ticks = 0
        deadline = time.monotonic() + 600
        while mismatched and time.monotonic() < deadline:
            tick_committed()
            fence_ticks += 1
            mismatched = [
                n for n, q in reads.items()
                if _norm(meta.serve(q)[1]) != expected[n]
            ]
        query_rows = {n: len(expected[n]) for n in reads}

        barrier_commits = sum(meta.metrics.hist_counts(
            "cluster_barrier_commit_seconds"))
        barrier_p99 = meta.metrics.quantile_delta(
            "cluster_barrier_commit_seconds", 0.99, barrier_baseline)

        return {
            "rounds": rounds,
            "rounds_committed": state["rounds_committed"],
            "fence_ticks": fence_ticks,
            "tick_retries": state["tick_retries"],
            "workers": workers,
            "seed": seed,
            "small": small,
            "queries": list(reads),
            "query_rows": query_rows,
            "txns": dict(state["txns"]),
            "txn_total": sum(state["txns"].values()),
            "ingest_rows": state["ingest_rows"],
            "ingest_rows_per_s": round(
                state["ingest_rows"] / ingest_wall, 2),
            "ingest_errors": len(state["ingest_errors"]),
            "ingest_error_samples": state["ingest_errors"][:3],
            "reads": state["reads"],
            "multi_gets": state["multi_gets"],
            "index_reads": state["index_reads"],
            "read_errors": len(state["read_errors"]),
            "read_error_samples": state["read_errors"][:3],
            "latency_ms": {
                "p50": round(_percentile(samples, 0.50) * 1e3, 3),
                "p99": round(_percentile(samples, 0.99) * 1e3, 3),
                "p999": round(_percentile(samples, 0.999) * 1e3, 3),
            },
            "barrier_commits": barrier_commits,
            "barrier_commit_p99_s": barrier_p99,
            "mv_mismatches": len(mismatched),
            "mv_mismatched": mismatched,
            "data_dir": data_dir,
        }
    finally:
        stop_ingest.set()
        stop_read.set()
        for p in procs:
            if p.poll() is None:
                p.kill()
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass
        meta.stop()


def check(summary: dict, min_ingest_rows_s: float = 5.0,
          max_barrier_p99_s: float = 120.0,
          max_serve_p999_ms: float = 2000.0) -> list[str]:
    """The --assert SLO gate; returns violations (empty = pass)."""
    bad = []
    if summary["rounds_committed"] < summary["rounds"]:
        bad.append(f"rounds_committed={summary['rounds_committed']} "
                   f"< {summary['rounds']}")
    if summary["read_errors"] != 0:
        bad.append(f"read_errors={summary['read_errors']} != 0 "
                   f"({summary['read_error_samples']})")
    if summary["ingest_errors"] != 0:
        bad.append(f"ingest_errors={summary['ingest_errors']} != 0 "
                   f"({summary['ingest_error_samples']})")
    if summary["mv_mismatches"] != 0:
        bad.append("byte-identity FAILED for "
                   f"{summary['mv_mismatched']}")
    if summary["ingest_rows_per_s"] < min_ingest_rows_s:
        bad.append(f"ingest_rows_per_s={summary['ingest_rows_per_s']} "
                   f"< {min_ingest_rows_s}")
    if not (0.0 < summary["barrier_commit_p99_s"]
            <= max_barrier_p99_s):
        bad.append("barrier_commit_p99_s="
                   f"{summary['barrier_commit_p99_s']} not in "
                   f"(0, {max_barrier_p99_s}]")
    if summary["latency_ms"]["p999"] > max_serve_p999_ms:
        bad.append(f"serving p99.9={summary['latency_ms']['p999']}ms "
                   f"> {max_serve_p999_ms}ms")
    for kind, n in summary["txns"].items():
        if n <= 0:
            bad.append(f"txn mix never exercised {kind!r}")
    for name, n in summary["query_rows"].items():
        if n <= 0:
            bad.append(f"CH view {name!r} ended empty")
    if summary["multi_gets"] <= 0 or summary["index_reads"] <= 0:
        bad.append("serving mix missed multi_get or index reads")
    return bad


def write_artifact(summary: dict, path: str | None = None) -> None:
    """``CH_BENCH.json`` in the bench-artifact shape (next to
    SERVE_BENCH.json / MULTICHIP_BENCH.json)."""
    rec = {
        "benchmark": "ch_bench",
        "value": summary["ingest_rows_per_s"],
        "unit": "rows/s",
        "latency_ms": summary["latency_ms"],
        "queries": {
            name: {"rows": summary["query_rows"][name]}
            for name in summary["queries"]
        },
        "invariants": {
            "read_errors": summary["read_errors"],
            "ingest_errors": summary["ingest_errors"],
            "mv_mismatches": summary["mv_mismatches"],
            "rounds_committed": summary["rounds_committed"],
            "barrier_commit_p99_s": summary["barrier_commit_p99_s"],
            "txns": summary["txns"],
        },
        "errors": (summary["read_error_samples"]
                   + summary["ingest_error_samples"]) or None,
        "blocker": None,
    }
    if path is None:
        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))),
            "CH_BENCH.json",
        )
    try:
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
            f.write("\n")
    except OSError:
        pass
