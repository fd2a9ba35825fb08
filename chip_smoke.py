#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip and answer right?

Drives the main path once through the entry points a user starts: a
``python -m risingwave_tpu.server`` child that holds the chip, SQL over
pgwire, the server's own barrier loop, checkpoints on disk, an orderly
stop — at the table sizes and the 1,000,000 events/s of the Nexmark
bench, past two of q7's 10 s windows.  What the views hold is compared,
exactly and in full, with ``scripts/baseline_numpy.py`` (plain numpy over
the same ordinals, run as a CPU child).

    python chip_smoke.py            # one chip: phases server, cluster
    python chip_smoke.py --mesh     # four chips: q5 + q8 at parallelism 4
    python chip_smoke.py --rehearse # no chip: the same control flow at a
                                    # small rate; never prints the result
                                    # line, never exits 0

This process is only ever a parent: it never initialises a JAX backend
(checked at exit), so every child it starts can have the chip, one at a
time, with the environment passed on untouched.  Any failed check, dead
child or raised phase ends it non-zero.  Rates on its lines are
information, never a metric.  Last line on success, and only then:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np

from risingwave_tpu.pgwire import SimpleClient

ROOT = os.path.dirname(os.path.abspath(__file__))
#: data directories of the children (removed at start; ignored by git)
OUT = os.path.join(ROOT, "smoke_out")
#: child logs, small enough to come back from the chip
LOGS = os.path.join(ROOT, "chiprun_out", "smoke")

S = 1_000_000  # us per second
WINDOW_S, LAG_S = 10, 4  # q7's window and the sources' watermark lag

#: the deployment: Nexmark at the reference generator's rate, state
#: sized as bench.py sizes it — except the agg and MV tables, which at
#: bench.py's 2^18 overflow 5 s into q5 (it never ran past 2.1 s)
FULL = {
    "rate": 1_000_000,
    "chunk": 8192,
    # q5/q7 read one bid chunk a round (8,904 events); a q8 round is one
    # person and three auction chunks (409,600 events), so q8 joins
    # later, at its own setting: one for all would have it 46x ahead.
    # About a second and three seconds of event time a barrier: every
    # barrier checkpoints, and q5 and q8 write most of their state then
    "chunks_per_barrier": 128,
    "chunks_per_barrier_q8": 8,
    "state": {
        "agg_table_size": 1 << 20,
        "agg_emit_capacity": 4096,
        "join_left_table_size": 1 << 22,
        "join_right_table_size": 1 << 18,
        "join_pool_size": 1 << 22,
        "join_out_capacity": 1 << 12,
        "mv_table_size": 1 << 21,
        "mv_ring_size": 1 << 23,
        "topn_pool_size": 1 << 14,
    },
}
#: the same control flow on a CPU, in a minute
REHEARSAL = {
    "rate": 20_000,
    "chunk": 1024,
    "chunks_per_barrier": 32,
    "chunks_per_barrier_q8": 2,
    "state": {
        "agg_table_size": 1 << 16,
        "agg_emit_capacity": 4096,
        "join_left_table_size": 1 << 16,
        "join_right_table_size": 1 << 14,
        "join_pool_size": 1 << 17,
        "join_out_capacity": 1 << 12,
        "mv_table_size": 1 << 17,
        "mv_ring_size": 1 << 18,
        "topn_pool_size": 1 << 14,
    },
}

SOURCES = """
CREATE SOURCE bid (
    auction BIGINT, bidder BIGINT, price BIGINT,
    channel VARCHAR, url VARCHAR, date_time TIMESTAMP,
    WATERMARK FOR date_time AS date_time - INTERVAL '4' SECOND
) WITH (connector = 'nexmark', nexmark.table = 'bid',
        nexmark.event.rate = '{rate}');
CREATE SOURCE person (
    id BIGINT, name VARCHAR, date_time TIMESTAMP,
    WATERMARK FOR date_time AS date_time - INTERVAL '4' SECOND
) WITH (connector = 'nexmark', nexmark.table = 'person',
        nexmark.event.rate = '{rate}');
CREATE SOURCE auction (
    id BIGINT, seller BIGINT, reserve BIGINT, expires TIMESTAMP,
    date_time TIMESTAMP,
    WATERMARK FOR date_time AS date_time - INTERVAL '4' SECOND
) WITH (connector = 'nexmark', nexmark.table = 'auction',
        nexmark.event.rate = '{rate}');
"""

VIEWS = {
    "q5": """
        CREATE MATERIALIZED VIEW q5 AS
        SELECT auction, window_start, count(*) AS bids
        FROM HOP(bid, date_time, INTERVAL '2' SECOND, INTERVAL '10' SECOND)
        GROUP BY auction, window_start;
    """,
    "q7": """
        CREATE MATERIALIZED VIEW q7 AS
        SELECT window_start, max(price) AS max_price, count(*) AS bids
        FROM TUMBLE(bid, date_time, INTERVAL '10' SECOND)
        GROUP BY window_start;
    """,
    "q8": """
        CREATE MATERIALIZED VIEW q8 AS
        SELECT p.id AS id, p.name AS name, a.reserve AS reserve
        FROM TUMBLE(person, date_time, INTERVAL '1' SECOND) p
        JOIN TUMBLE(auction, date_time, INTERVAL '1' SECOND) a
        ON p.id = a.seller AND p.window_start = a.window_start;
    """,
}

#: events per source row (Nexmark's 1:3:46 person:auction:bid in 50)
EVENTS_PER_ROW = {"bid": 50 / 46, "person": 50.0}


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(phase: str, msg: str) -> None:
    print(f"smoke {phase}: {msg}", flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# children

_CHILDREN: list["Child"] = []


class Child:
    """One process this script started; all of them are stopped at exit."""

    def __init__(self, name: str, argv: list[str], env: dict | None = None):
        os.makedirs(LOGS, exist_ok=True)
        self.name = name
        self.log_path = os.path.join(LOGS, f"{name}.log")
        self._log = open(self.log_path, "w")
        # env=None: the child inherits this process's environment as is
        self.proc = subprocess.Popen(
            argv, cwd=ROOT, env=env, stdout=subprocess.PIPE,
            stderr=self._log, text=True,
        )
        self.lines: list[str] = []
        self._got_line = threading.Event()
        threading.Thread(target=self._pump, daemon=True).start()
        _CHILDREN.append(self)

    def _pump(self) -> None:
        for line in self.proc.stdout:
            self.lines.append(line.rstrip("\n"))
            self._log.write(line)
            self._got_line.set()
        self._got_line.set()

    def handshake(self, timeout: float = 300.0) -> dict:
        """The role's one JSON line."""
        self._got_line.wait(timeout)
        check(bool(self.lines),
              f"{self.name}: no handshake line in {timeout:.0f}s "
              f"(rc={self.proc.poll()}): {self.log_tail()}")
        return json.loads(self.lines[0])

    def alive(self) -> bool:
        return self.proc.poll() is None

    def log_tail(self, n: int = 1500) -> str:
        self._log.flush()
        with open(self.log_path) as f:
            return f.read()[-n:]

    def wait(self, timeout: float) -> int:
        try:
            return self.proc.wait(timeout)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"{self.name}: still running after "
                               f"{timeout:.0f}s: {self.log_tail()}")

    def stop(self, timeout: float = 180.0) -> int:
        """SIGINT, the orderly stop; returns the exit code."""
        if self.alive():
            self.proc.send_signal(signal.SIGINT)
        return self.wait(timeout)

    def kill(self) -> None:
        if self.alive():
            self.proc.kill()
            self.proc.wait()
        self._log.close()


def server_child(name: str, *args: str) -> Child:
    return Child(name, [sys.executable, "-m", "risingwave_tpu.server",
                        *args])


def scrape(port: int) -> dict[tuple, float]:
    """One /metrics scrape: {(name, (label pairs...)): value}."""
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=600) as r:
        text = r.read().decode()
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        head, value = line.rsplit(" ", 1)
        name, _, rest = head.partition("{")
        labels = tuple(
            tuple(kv.split("=", 1)) for kv in rest.rstrip("}").split(",")
        ) if rest else ()
        out[(name, tuple((k, v.strip('"')) for k, v in labels))] = \
            float(value)
    return out


def metric(m: dict, name: str, **labels) -> float | None:
    return m.get((name, tuple(sorted(labels.items()))))


def metric_family(m: dict, name: str) -> dict:
    return {lb: v for (n, lb), v in m.items() if n == name}


# ---------------------------------------------------------------------------
# the reference and the comparison

def start_reference(query: str, rows: int, rate: int) -> tuple[Child, str]:
    """``scripts/baseline_numpy.py`` as a child pinned to the CPU: the
    chip is the server's."""
    out = os.path.join(OUT, f"ref_{query}.npz")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return Child(f"ref_{query}", [
        sys.executable, os.path.join(ROOT, "scripts", "baseline_numpy.py"),
        query, "--rows", str(rows), "--rate", str(rate), "--out", out,
    ], env=env), out


def finish_reference(child: Child, path: str) -> dict[str, np.ndarray]:
    rc = child.wait(600)
    check(rc == 0, f"{child.name}: rc={rc}: {child.log_tail()}")
    with np.load(path) as z:
        return {k: z[k] for k in z.files}


def sorted_cols(cols: list[np.ndarray]) -> list[np.ndarray]:
    order = np.lexsort(cols[::-1])
    return [c[order] for c in cols]


def compare(view: str, got: list[np.ndarray], want: list[np.ndarray],
            names: list[str]) -> None:
    """Exact, full, order-free equality of two row sets (as columns)."""
    n_got, n_want = got[0].shape[0], want[0].shape[0]
    got, want = sorted_cols(got), sorted_cols(want)
    if n_got == n_want and all(
            np.array_equal(g, w) for g, w in zip(got, want)):
        return
    detail = f"{n_got} rows, reference has {n_want}"
    if n_got == n_want:
        bad = np.zeros(n_got, bool)
        per_col = {}
        for nm, g, w in zip(names, got, want):
            per_col[nm] = g != w
            bad |= per_col[nm]
        detail = f"{int(bad.sum())} of {n_got} rows differ"
        if "max_price" in per_col:
            # the generator's float64 price is emulated on the chip and
            # need not round as a host does: suspect it before operators
            others = np.zeros(n_got, bool)
            for nm, d in per_col.items():
                if nm != "max_price":
                    others |= d
            only = int((per_col["max_price"] & ~others).sum())
            detail += f"; {only} differ in price alone"
        i = int(np.flatnonzero(bad)[0])
        detail += (f"; first: got {[c[i] for c in got]} "
                   f"want {[c[i] for c in want]}")
    raise SmokeFailure(f"{view}: rows differ from the reference: {detail}")


def pg(port: int) -> SimpleClient:
    c = SimpleClient("127.0.0.1", port)
    # a statement waits for the barrier in flight, first compiles included
    c.sock.settimeout(1500)
    return c


def int_col(rows: list[tuple], i: int) -> np.ndarray:
    return np.fromiter((int(r[i]) for r in rows), np.int64, len(rows))


# ---------------------------------------------------------------------------
# phase: server

def wait_rows(child: Child, mport: int, jobs: list[str], target: int,
              timeout: float) -> float:
    """Let the server's own barrier loop run until every one of ``jobs``
    has taken in ``target`` rows; returns the seconds until all of them
    had their first barrier behind them (their programs compiled)."""
    t0 = time.monotonic()
    first = None
    while True:
        check(child.alive(), f"{child.name} died: {child.log_tail()}")
        m = scrape(mport)
        errs = metric(m, "barrier_loop_errors_total") or 0
        check(errs == 0, f"{child.name}: barrier loop raised: "
                         f"{child.log_tail()}")
        rows = min(metric(m, "stream_rows_total", job=j) or 0.0
                   for j in jobs)
        if first is None and rows > 0:
            first = time.monotonic() - t0
        if rows >= target:
            return first
        check(time.monotonic() - t0 < timeout,
              f"{jobs}: {rows:.0f} of {target} rows after {timeout:.0f}s: "
              f"{child.log_tail()}")
        time.sleep(0.5)


def timing(m: dict, job: str) -> str:
    """Where the job's barriers spent their time, from its own metrics
    (host clock; information, not a metric of the device)."""
    def phase(p):
        return metric(m, "barrier_phase_seconds_sum", job=job,
                      phase=p) or 0.0

    def of(name):
        return metric(m, name, job=job) or 0.0

    return (f"barriers={int(of('barrier_latency_seconds_count'))} "
            f"barrier_s={of('barrier_latency_seconds_sum'):.1f} "
            f"(dispatch {phase('dispatch'):.1f}, seal {phase('seal'):.1f}) "
            f"upload_s={of('checkpoint_upload_seconds_total'):.1f} "
            f"upload_stall_s="
            f"{of('checkpoint_upload_stall_seconds_total'):.1f}")


def run_views(phase: str, cfg: dict, child: Child, c: SimpleClient,
              mport: int, dev: str, views: list[str], table: str,
              cpb: int, shards: int = 1) -> dict:
    """Create ``views``, let them run past two q7 windows, hold the
    sources, compare what each holds with the reference."""
    # the watermark must close two windows: event time past
    # 2 x 10 s + 4 s, rounded up to whole barriers.  A round reads
    # one chunk of ``table`` (and, for q8, three of auctions)
    # — on every shard of a mesh
    rate = cfg["rate"]
    rows_per_round = cfg["chunk"] * shards * (4 if table == "person" else 1)
    need = (2 * WINDOW_S + LAG_S) * rate / (
        cfg["chunk"] * shards * EVENTS_PER_ROW[table])
    target = -(-int(need + 1) // cpb) * cpb * rows_per_round
    c.query(f"ALTER SYSTEM SET chunks_per_barrier = {cpb}")
    t0 = time.monotonic()
    for v in views:
        c.query(VIEWS[v])
    first = wait_rows(child, mport, views, target, timeout=900)
    # hold the sources: barriers go on, state stands still
    c.query("ALTER SYSTEM SET chunks_per_barrier = 0")
    wall = time.monotonic() - t0
    m = scrape(mport)
    refs = {}
    totals = {}
    for v in views:
        totals[v] = int(metric(m, "stream_rows_total", job=v))
        # q8's count is persons + auctions, 1:3
        refs[v] = start_reference(
            v, totals[v] // 4 if v == "q8" else totals[v], rate)
    for v in views:
        t1 = time.monotonic()
        names = {"q5": ["auction", "window_start", "bids"],
                 "q7": ["window_start", "max_price", "bids"],
                 "q8": ["id", "name", "reserve"]}[v]
        _, rows = c.query(f"SELECT {', '.join(names)} FROM {v}")
        want = finish_reference(*refs[v])
        wm = int(want["event_time_max"]) - LAG_S * S
        ref = [want[n] for n in names]
        if v == "q8":
            # no window column: everything it holds is compared;
            # its own windows are 1 s long
            got = [int_col(rows, 0),
                   np.array([r[1].encode() for r in rows],
                            want["name"].dtype),
                   int_col(rows, 2)]
            windows = (wm - int(want["event_time_min"]) // S * S) // S
        else:
            got = [int_col(rows, i) for i in range(3)]
            wi = names.index("window_start")

            def closed(cols):
                # the window's end is at or below the watermark
                keep = cols[wi] + WINDOW_S * S <= wm
                return [x[keep] for x in cols]

            got, ref = closed(got), closed(ref)
            windows = np.unique(ref[wi]).shape[0]
        check(windows >= 2, f"{v}: the watermark closed {windows} "
                            "windows, fewer than two")
        compare(v, got, ref, names)
        say(phase,
            f"{v}: rows_in={totals[v]} rows_out={got[0].shape[0]} "
            f"closed_windows_compared={windows} equal=true "
            f"select_s={time.monotonic() - t1:.1f}")
        say(phase,
            f"{v}: information, host clock, on {dev}: wall_s={wall:.1f} "
            f"of which {first:.1f} before every view's first barrier "
            f"(compile); {timing(m, v)}")
    return m


def check_counters(phase: str, m: dict) -> None:
    """What the last maintenance barrier read (all zero) and the windows
    that fell back from the fused program (none)."""
    counters = {
        f"{dict(lb)['job']}.{dict(lb)['kind']}": int(v)
        for lb, v in metric_family(m, "maintenance_counter_rows").items()
    }
    say(phase, f"maintenance counters {json.dumps(counters, sort_keys=True)}")
    check(counters != {} and all(v == 0 for v in counters.values()),
          f"overflow/inconsistency counters not all zero: {counters}")
    fallbacks = {
        f"{dict(lb)['job']}.{dict(lb)['reason']}": int(v)
        for lb, v in metric_family(m, "dag_fused_fallback_total").items()
    }
    say(phase, f"fused_fallbacks {json.dumps(fallbacks)}")
    check(fallbacks == {}, f"fused window fell back: {fallbacks}")


def node_config(cfg: dict) -> str:
    """``--config-json`` of a single node or a compute worker."""
    return json.dumps({"streaming": {"chunk_size": cfg["chunk"]},
                       "state": cfg["state"]})


def start_single(phase: str, cfg: dict, rehearse: bool):
    """A single-node child with its sources declared; returns (child,
    pgwire session, metrics port, device as the child reports it)."""
    port, mport = free_port(), free_port()
    child = server_child(
        phase, "--port", str(port), "--metrics-port", str(mport),
        "--data-dir", os.path.join(OUT, phase),
        "--config-json", node_config(cfg))
    hs = child.handshake()
    say(phase, f"handshake {json.dumps(hs)}")
    device = {"platform": hs["platform"], "kind": hs["device_kind"],
              "count": hs["device_count"]}
    check(rehearse or device["platform"] == "tpu",
          f"the child found no chip: {device}")
    check(hs["native_codec"] is True, "native codec not loaded")
    check(glob.glob(os.path.join(ROOT, "native", "*.so")) != [],
          "native codec was not built in this run")
    c = pg(port)
    c.query(SOURCES.format(rate=cfg["rate"]))
    c.query("ALTER SYSTEM SET barrier_interval_ms = 100")
    # checkpoint, snapshot and maintenance stay at their defaults:
    # every barrier
    return child, c, mport, device


def dev_str(device: dict) -> str:
    return f"{device['platform']} {device['kind']} x{device['count']}"


def phase_server(cfg: dict, rehearse: bool) -> dict:
    phase = "server"
    data_dir = os.path.join(OUT, phase)
    t_start = time.monotonic()
    child, c, mport, device = start_single(phase, cfg, rehearse)
    dev = dev_str(device)
    run_views(phase, cfg, child, c, mport, dev, ["q7", "q5"], "bid",
              cfg["chunks_per_barrier"])
    run_views(phase, cfg, child, c, mport, dev, ["q8"], "person",
              cfg["chunks_per_barrier_q8"])

    # what the last maintenance barrier read, and what is on disk
    time.sleep(1.0)
    m = scrape(mport)
    check((metric(m, "barrier_loop_errors_total") or 0) == 0,
          f"barrier loop raised: {child.log_tail()}")
    check_counters(phase, m)
    epochs = {v: int(metric(m, "committed_epoch", job=v) or 0)
              for v in VIEWS}
    say(phase, f"committed_epoch {json.dumps(epochs)}")
    check(all(e > 0 for e in epochs.values()),
          f"a view has no committed checkpoint: {epochs}")
    c.close()

    rc = child.stop()
    check(rc == 0, f"server stop: rc={rc}: {child.log_tail()}")
    n_ckpt = sum(len(fs) for _, _, fs in os.walk(data_dir))
    n_sst = len(glob.glob(os.path.join(data_dir, "**", "*.sst"),
                          recursive=True))
    say(phase, f"orderly stop rc=0; {n_ckpt} files under the data "
               f"directory ({n_sst} SSTs: the single node checkpoints "
               "state, only the cluster's workers export SSTs)")
    check(n_ckpt > 0, "nothing was written under the data directory")
    say(phase, f"passed in {time.monotonic() - t_start:.0f}s")
    return device


# ---------------------------------------------------------------------------
# phase: mesh (four chips, --mesh)

def phase_mesh(cfg: dict, rehearse: bool) -> dict:
    """One single-node child over four devices: q5 on the
    ShardedStreamingJob path, q8 on the fused mesh program, at
    parallelism 4, against the same reference."""
    phase = "mesh"
    shards = 4
    t_start = time.monotonic()
    child, c, mport, device = start_single(phase, cfg, rehearse)
    check(device["count"] >= shards,
          f"the child sees {device['count']} devices, not {shards}")
    dev = dev_str(device)
    c.query(f"SET streaming_parallelism = {shards}")
    for view, table, cpb in (
            ("q5", "bid", cfg["chunks_per_barrier"]),
            # a round reads four shards' chunks; two rounds a barrier
            # at least, one is not a fused window
            ("q8", "person", max(cfg["chunks_per_barrier_q8"] // 2, 2))):
        m = run_views(phase, cfg, child, c, mport, dev, [view], table,
                      cpb, shards)
        rows = {}
        for lb, v in metric_family(m, "shard_rows").items():
            lb = dict(lb)
            if lb["job"] == view:
                rows.setdefault(lb["op"], [0] * shards)[
                    int(lb["shard"])] = int(v)
        nbytes = {dict(lb)["device"]: int(v) for lb, v in
                  metric_family(m, "shard_state_bytes").items()
                  if dict(lb)["job"] == view}
        say(phase, f"{view}: devices={device['count']} rows_by_shard="
                   f"{json.dumps(rows, sort_keys=True)} state_bytes_by_"
                   f"device={json.dumps(nbytes, sort_keys=True)}")
        check(rows != {} and all(
            all(x > 0 for x in per) for per in rows.values()),
            f"{view}: a shard processed no rows: {rows}")
        check(len(nbytes) == shards and all(
            b > 0 for b in nbytes.values()),
            f"{view}: state is not on {shards} devices: {nbytes}")
    m = scrape(mport)
    check_counters(phase, m)
    c.close()
    rc = child.stop()
    check(rc == 0, f"mesh child stop: rc={rc}: {child.log_tail()}")
    say(phase, f"passed in {time.monotonic() - t_start:.0f}s")
    return device


# ---------------------------------------------------------------------------
# phase: cluster

def phase_cluster(cfg: dict, rehearse: bool) -> None:
    """One meta, one compute worker (the chip is its alone), one JAX-free
    serving replica, on one data directory; q7 until a window closes;
    the read goes through the meta and is answered by the replica."""
    from risingwave_tpu import ctl
    from risingwave_tpu.cluster.rpc import RpcClient

    phase = "cluster"
    rate, cpb = cfg["rate"], cfg["chunks_per_barrier"]
    data_dir = os.path.join(OUT, "cluster")
    port, rpc_port = free_port(), free_port()
    addr = f"127.0.0.1:{rpc_port}"
    t_start = time.monotonic()
    # --barrier-interval-ms 0: the rounds are driven from here, at
    # chunks_per_barrier (the meta's own ticker runs one chunk a round)
    meta = server_child(
        "meta", "--role", "meta", "--port", str(port), "--rpc-port",
        str(rpc_port), "--data-dir", data_dir,
        "--barrier-interval-ms", "0")
    hs = meta.handshake()
    say(phase, f"handshake {json.dumps(hs)}")
    check(hs["backend_initialized"] is False,
          "the meta initialised a JAX backend at start-up")
    worker = server_child(
        "compute", "--role", "compute", "--meta", addr, "--data-dir",
        data_dir, "--config-json", node_config(cfg))
    hs = worker.handshake()
    say(phase, f"handshake {json.dumps(hs)}")
    check(rehearse or hs["platform"] == "tpu",
          f"the compute worker found no chip: {hs}")
    serving = server_child(
        "serving", "--role", "serving", "--meta", addr, "--data-dir",
        data_dir)
    hs = serving.handshake()
    say(phase, f"handshake {json.dumps(hs)}")
    check(hs["jax_loaded"] is False, "the serving replica loaded jax")

    c = pg(port)
    c.query(SOURCES.format(rate=rate))
    c.query(VIEWS["q7"])
    # one window closed: event time past 10 s + 4 s
    need = (WINDOW_S + LAG_S) * rate / (cfg["chunk"] * EVENTS_PER_ROW["bid"])
    rounds = -(-int(need + 1) // cpb)
    driver = RpcClient("127.0.0.1", rpc_port, timeout=900.0)
    epoch0 = ctl.cluster_epochs(addr)["cluster_epoch"]
    t0 = time.monotonic()
    committed = 0
    while committed < rounds:
        for ch in (meta, worker, serving):
            check(ch.alive(), f"{ch.name} died: {ch.log_tail()}")
        check(time.monotonic() - t0 < 600,
              f"{committed} of {rounds} rounds committed in 600s: "
              f"{worker.log_tail()}")
        if driver.call("tick", chunks_per_barrier=cpb)["committed"]:
            committed += 1
        else:
            time.sleep(0.1)  # the round's uploads are still in flight
    wall = time.monotonic() - t0
    driver.close()
    epochs = ctl.cluster_epochs(addr)
    say(phase, f"epochs cluster_epoch {epoch0} -> "
               f"{epochs['cluster_epoch']} "
               f"q7={json.dumps(epochs['jobs'].get('q7'))}")
    check(epochs["cluster_epoch"] >= epoch0 + rounds,
          f"committed epochs did not advance: {epochs}")

    rows_in = rounds * cpb * cfg["chunk"]
    ref = start_reference("q7", rows_in, rate)
    names = ["window_start", "max_price", "bids"]
    t1 = time.monotonic()
    _, rows = c.query(f"SELECT {', '.join(names)} FROM q7")
    c.close()
    want = finish_reference(*ref)
    wm = int(want["event_time_max"]) - LAG_S * S
    keep = want["window_start"] + WINDOW_S * S <= wm
    got = [int_col(rows, i) for i in range(3)]
    got = [x[got[0] + WINDOW_S * S <= wm] for x in got]
    check(int(keep.sum()) >= 1, "no q7 window closed")
    compare("q7", got, [want[n][keep] for n in names], names)

    replicas = ctl.cluster_serving(addr)
    text = ctl.cluster_metrics(addr)
    reads = sum(
        float(line.rsplit(" ", 1)[1]) for line in text.splitlines()
        if line.startswith("cluster_serving_reads_total"))
    say(phase, f"serving {json.dumps(replicas)} "
               f"cluster_serving_reads_total={reads:.0f}")
    check(len(replicas) == 1 and replicas[0]["alive"] and reads > 0,
          "the read was not answered by the serving replica")
    tick_errors = sum(
        float(line.rsplit(" ", 1)[1]) for line in text.splitlines()
        if line.startswith("cluster_tick_errors_total"))
    check(tick_errors == 0, f"the meta's tick loop raised: "
                            f"{meta.log_tail()}")
    state = RpcClient("127.0.0.1", rpc_port, timeout=30.0)
    backend = state.call("cluster_state")["backend_initialized"]
    state.close()
    check(backend is False,
          "the live meta initialised a JAX backend")
    n_sst = len(glob.glob(os.path.join(data_dir, "**", "*.sst"),
                          recursive=True))
    check(n_sst > 0, "no SST under the cluster's data directory")
    say(phase,
        f"q7: rows_in={rows_in} rows_out={got[0].shape[0]} "
        f"closed_windows_compared={int(keep.sum())} equal=true "
        f"rounds={rounds} ssts={n_sst} meta_backend_initialized=false "
        f"(information, host clock: {wall:.1f}s for the rounds, "
        f"{time.monotonic() - t1:.1f}s for the read)")
    for ch in (serving, worker, meta):
        rc = ch.stop()
        check(rc == 0, f"{ch.name} stop: rc={rc}: {ch.log_tail()}")
    say(phase, f"passed in {time.monotonic() - t_start:.0f}s")


# ---------------------------------------------------------------------------

def cache_entries() -> tuple[str, int]:
    """Where the children keep compiled programs, and how many."""
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(ROOT, ".jax_cache")
    return d, len(os.listdir(d)) if os.path.isdir(d) else 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--mesh", action="store_true",
                    help="four chips: q5 and q8 at parallelism 4, "
                         "and nothing else")
    ap.add_argument("--rehearse", action="store_true",
                    help="no chip: small rate, no result line, exit 3")
    args = ap.parse_args()
    cfg = REHEARSAL if args.rehearse else FULL

    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)
    # the codec is built in this run, by the first child that needs it
    for so in glob.glob(os.path.join(ROOT, "native", "*.so")):
        os.unlink(so)
    cache_dir, before = cache_entries()
    say("cache", f"dir={cache_dir} entries_before={before}")
    try:
        if args.mesh:
            device = phase_mesh(cfg, args.rehearse)
        else:
            device = phase_server(cfg, args.rehearse)
            phase_cluster(cfg, args.rehearse)
        _, after = cache_entries()
        say("cache", f"dir={cache_dir} entries_before={before} "
                     f"entries_after={after}")
    except SmokeFailure as e:
        print(f"smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        for ch in _CHILDREN:
            ch.kill()
    if "jax" in sys.modules:
        from jax._src import xla_bridge
        if xla_bridge.backends_are_initialized():
            print("smoke FAILED: the parent initialised a JAX backend",
                  file=sys.stderr)
            return 1
    if args.rehearse or device["platform"] != "tpu":
        print("smoke: rehearsal passed; no accelerator, so no result",
              file=sys.stderr)
        return 3
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
