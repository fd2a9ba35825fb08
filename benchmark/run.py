#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The parent never touches a JAX backend.  It starts one child,
``server_child.py``, which runs ``risingwave_tpu.server``'s own
``main()`` on the chip with a fresh durable ``data_dir``, and drives it
as a user does: SQL over pgwire, the server's own barrier loop, its
``/metrics``.  Set-up is everything until the view's first window has
closed and every statement of the cell has run once; then the window is
measured for ``--seconds``; then the sources are held, the view is read,
the server is stopped with SIGINT, what it left on disk is checked and
the view is compared with the plain reference.

Nothing here knows a cell, a configuration or a metric by name: a cell's
files are found by the names in ``BENCHMARK.json`` (``configs/<config>``
as its ``file`` says, ``workloads/<traffic>.json``), an end-to-end metric in
``end_to_end/<name>.py`` and a per-layer metric in ``layers/<name>.py``,
each a ``read(window) -> number or None``.

The last line on standard output is the result (``--trace 0``: the
cell's end-to-end metrics; ``--trace 1``: its per-layer metrics, from a
run with the profiler on for a few barriers).  A run that finds no TPU,
or fewer chips than the cell asks for, prints none and exits 2.
"""

from __future__ import annotations

import argparse
import glob
import importlib.util
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
import zipfile

T_START = time.monotonic()

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import arith  # noqa: E402
from pgclient import PgClient  # noqa: E402


class RunFailure(Exception):
    """The run cannot give a result (not: the result is incorrect)."""


def say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def need(ok: bool, what: str) -> None:
    if not ok:
        raise RunFailure(what)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def load_module(path: str):
    need(os.path.isfile(path), f"no such file: {path}")
    name = "bench_" + os.path.relpath(path, ROOT).replace(os.sep, "_")[:-3]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# the cell, from data

def load_cell(bench_path: str, workload: str) -> dict:
    """Everything one run needs, from ``BENCHMARK.json`` and the files it
    names."""
    bench = load_json(bench_path)
    cells = {w["name"]: w for w in bench["workloads"]}
    need(workload in cells, f"no workload {workload!r} in {bench_path}; "
                            f"it has {sorted(cells)}")
    entry = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    home = os.path.dirname(os.path.dirname(
        os.path.join(ROOT, cfg_entry["file"])))
    traffic = load_json(os.path.join(home, "workloads",
                                     f"{entry['traffic']}.json"))

    def reports(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    return {
        "name": workload, "chips": entry["chips"], "config": config,
        "traffic": traffic, "job": config["view"]["name"],
        "end_to_end": [m for m in bench["end_to_end"] if reports(m)],
        "per_layer": [m for m in bench["per_layer"] if reports(m)],
    }


def reader_path(kind: str, metric: str) -> str:
    """The file that reads a metric: ``<kind's directory>/<name>.py``."""
    folder = {"end_to_end": "end_to_end", "per_layer": "layers"}[kind]
    return os.path.join(BENCH, folder, metric + ".py")


def load_peaks(kind: str) -> dict:
    peaks = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    need(kind in peaks, f"device kind {kind!r} is not in peaks.json "
                        f"({sorted(peaks)}): add it with its source")
    return peaks[kind]


# ---------------------------------------------------------------------------
# the child

class Child:
    """The server process.  Standard output carries the handshake and the
    control thread's replies; standard error goes to a log file."""

    def __init__(self, argv: list[str], log_path: str):
        self.log_path = log_path
        self._log = open(log_path, "w")
        self.proc = subprocess.Popen(
            argv, cwd=ROOT, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=self._log, text=True)
        self._lines: list[str] = []
        self._cv = threading.Condition()
        self._taken = 0
        threading.Thread(target=self._pump, daemon=True).start()

    def _pump(self) -> None:
        for line in self.proc.stdout:
            with self._cv:
                self._lines.append(line.rstrip("\n"))
                self._cv.notify_all()
        with self._cv:
            self._lines.append("")  # end of output
            self._cv.notify_all()

    def next_json(self, timeout: float, what: str) -> dict:
        """The child's next JSON line."""
        end = time.monotonic() + timeout
        with self._cv:
            while True:
                while self._taken < len(self._lines):
                    line = self._lines[self._taken]
                    self._taken += 1
                    if line.startswith("{"):
                        return json.loads(line)
                left = end - time.monotonic()
                need(left > 0 and self.alive(),
                     f"no {what} from the server child in {timeout:.0f}s "
                     f"(rc={self.proc.poll()}): {self.log_tail()}")
                self._cv.wait(min(left, 1.0))

    def ctl(self, command: str, timeout: float = 120.0) -> dict:
        self.proc.stdin.write(command + "\n")
        self.proc.stdin.flush()
        r = self.next_json(timeout, f"answer to {command!r}")["ctl"]
        need(r.get("ok") is True, f"the child refused {command!r}: {r}")
        return r

    def alive(self) -> bool:
        return self.proc.poll() is None

    def log_tail(self, n: int = 1500) -> str:
        self._log.flush()
        with open(self.log_path) as f:
            return f.read()[-n:]

    def stop(self, timeout: float = 180.0) -> int:
        """SIGINT, the orderly stop; returns the exit code."""
        if self.alive():
            self.proc.send_signal(signal.SIGINT)
        try:
            return self.proc.wait(timeout)
        except subprocess.TimeoutExpired:
            raise RunFailure(f"the server child was still running "
                             f"{timeout:.0f}s after SIGINT: "
                             f"{self.log_tail()}")

    def kill(self) -> None:
        if self.alive():
            self.proc.kill()
        self.proc.wait()
        self._log.close()


# ---------------------------------------------------------------------------
# the data directory, as the disk shows it (nothing of the program's word)

class DiskWatch(threading.Thread):
    """Which epochs of the job were committed on disk, and how large: an
    epoch counts once a look found its two objects (``epoch_<n>.npz``,
    ``.meta``), neither empty, and a manifest that names it among the
    job's epochs.  The store keeps two epochs, so an epoch is there for
    two barriers; a look is made after every scrape and every
    ``period`` seconds besides."""

    def __init__(self, data_dir: str, job: str, period: float):
        super().__init__(name="disk-watch", daemon=True)
        self.data_dir, self.job, self.period = data_dir, job, period
        #: epoch -> bytes of its two objects, as committed
        self.committed_bytes: dict[int, int] = {}
        self._halt = threading.Event()
        self._lock = threading.Lock()

    def look(self) -> int | None:
        """One look; returns the epoch the manifest calls committed."""
        committed = None
        for path in glob.glob(os.path.join(self.data_dir, "**",
                                           "MANIFEST.json"), recursive=True):
            try:
                entry = load_json(path).get("jobs", {}).get(self.job)
            except (OSError, ValueError):
                continue  # replaced between the listing and the read
            if entry is None:
                continue
            committed = int(entry.get("committed", -1))
            for epoch in entry.get("epochs", []):
                base = os.path.join(os.path.dirname(path), self.job,
                                    f"epoch_{epoch}")
                try:
                    sizes = [os.path.getsize(base + ext)
                             for ext in (".npz", ".meta")]
                except OSError:
                    continue  # collected between the read and the stat
                if min(sizes) > 0:
                    with self._lock:
                        self.committed_bytes[int(epoch)] = sum(sizes)
        return committed

    def run(self) -> None:
        while not self._halt.wait(self.period):
            self.look()

    def halt(self) -> None:
        self._halt.set()
        self.join(timeout=60)

    def between(self, first: int, last: int) -> dict[int, int]:
        """The epochs seen committed after ``first`` and up to ``last``."""
        with self._lock:
            return {e: b for e, b in self.committed_bytes.items()
                    if first < e <= last}


# ---------------------------------------------------------------------------
# the poller: /metrics and the data directory, on one clock

class Poller(threading.Thread):
    def __init__(self, port: int, disk: DiskWatch, period: float):
        super().__init__(name="poller", daemon=True)
        self.url = f"http://127.0.0.1:{port}/metrics"
        self.disk, self.period = disk, period
        self.samples: list[dict] = []
        self.error: str | None = None
        self._halt = threading.Event()
        self._lock = threading.Lock()

    def scrape(self) -> dict:
        t_req = time.monotonic()
        with urllib.request.urlopen(self.url, timeout=600) as r:
            text = r.read().decode()
        s = {"t_req": t_req, "t_resp": time.monotonic(),
             "m": arith.parse_scrape(text)}
        # what the disk calls committed as this scrape is answered
        s["disk_committed"] = self.disk.look()
        with self._lock:
            self.samples.append(s)
        return s

    def run(self) -> None:
        while not self._halt.is_set():
            try:
                self.scrape()
            except Exception as e:
                self.error = repr(e)
                return
            self._halt.wait(self.period)

    def halt(self, wait: bool = True) -> None:
        self._halt.set()
        if wait:
            self.join(timeout=700)

    def last(self) -> dict | None:
        with self._lock:
            return self.samples[-1] if self.samples else None

    def since(self, t: float) -> list[dict]:
        with self._lock:
            return [s for s in self.samples if s["t_resp"] >= t]


# ---------------------------------------------------------------------------
# the readers: open loop, each read timed from when it was due

class Reader(threading.Thread):
    def __init__(self, port: int, sql: str):
        super().__init__(name="reader", daemon=True)
        self.client = PgClient("127.0.0.1", port, timeout=600)
        self.sql = sql
        self.due: list[float] = []
        self.reads: list[dict] = []
        #: set when the window closes early: reads not yet due are dropped
        self.halt = threading.Event()

    def run(self) -> None:
        for due in self.due:
            if self.halt.wait(max(due - time.monotonic(), 0.0)):
                break
            sent = time.monotonic()
            try:
                _, rows = self.client.query(self.sql)
                ok, err = True, None
            except Exception as e:
                rows, ok, err = [], False, repr(e)
            self.reads.append({"due": due, "sent": sent,
                               "done": time.monotonic(), "ok": ok,
                               "rows": rows, "error": err})
            if not ok:
                break
        try:
            self.client.close()
        except OSError:
            pass


# ---------------------------------------------------------------------------

def wait_for(poller: Poller, child: Child, cond, what: str,
             timeout: float) -> dict:
    """The first sample from now on for which ``cond(sample)`` holds."""
    t0 = time.monotonic()
    seen = 0
    while True:
        need(child.alive(), f"the server child died: {child.log_tail()}")
        need(poller.error is None, f"scrape failed: {poller.error}")
        for s in poller.since(t0)[seen:]:
            seen += 1
            need(arith.metric(s["m"], "barrier_loop_errors_total", 0.0)
                 == 0, f"the barrier loop raised: {child.log_tail()}")
            if cond(s):
                return s
        need(time.monotonic() - t0 < timeout,
             f"{what}: not reached in {timeout:.0f}s: {child.log_tail()}")
        time.sleep(0.05)


def set_params(c: PgClient, params: dict) -> None:
    for k, v in params.items():
        c.query(f"ALTER SYSTEM SET {k} = {v}")


def int_columns(rows: list[tuple], names: list[str]) -> dict:
    import numpy as np

    return {n: np.fromiter((int(r[i]) for r in rows), np.int64, len(rows))
            for i, n in enumerate(names)}


def check_disk(data_dir: str, job: str, last_epoch: int) -> tuple[int, str]:
    """What the stopped server left: the manifest names a committed epoch
    at or past the last one the window saw, and every epoch it retains
    is there and reads back whole.  Returns (epochs missing or bad,
    detail)."""
    manifests = glob.glob(os.path.join(data_dir, "**", "MANIFEST.json"),
                          recursive=True)
    for path in manifests:
        entry = load_json(path).get("jobs", {}).get(job)
        if entry is None:
            continue
        bad = []
        if int(entry.get("committed", -1)) < last_epoch:
            bad.append(f"manifest committed={entry.get('committed')} "
                       f"< {last_epoch}")
        for epoch in entry.get("epochs", []):
            base = os.path.join(os.path.dirname(path), job,
                                f"epoch_{epoch}")
            try:
                with zipfile.ZipFile(base + ".npz") as z:
                    broken = z.testzip()
                if broken is not None or \
                        os.path.getsize(base + ".meta") == 0:
                    bad.append(f"epoch {epoch}: {broken or 'empty meta'}")
            except (OSError, zipfile.BadZipFile) as e:
                bad.append(f"epoch {epoch}: {e!r}")
        return len(bad), (f"{path}: epochs {entry.get('epochs')} "
                          f"committed {entry.get('committed')}"
                          + ("; " + "; ".join(bad) if bad else ""))
    return 1, f"no manifest names job {job!r} under {data_dir}"


def compare_with_reference(config: dict, gen_seed: int,
                           view_rows: list[tuple], rows_final: int,
                           ok_reads: list[dict], samples: list[dict],
                           job: str, rows_per_barrier: int
                           ) -> tuple[int, str, int, int, str]:
    """The view as read after the hold, and every read of the window,
    against the plain reference, over the windows the watermark had
    closed.  Returns (view rows that differ, detail, closed windows
    compared, read rows that differ, detail).  Each read's rows are
    replaced by their count."""
    ref = load_module(os.path.join(
        BENCH, "reference", config["reference"]["module"] + ".py"))
    compare = load_module(os.path.join(BENCH, "reference", "compare.py"))
    names = config["view"]["columns"]
    windows = config["view"]["windows"]
    # each read saw the view as of one barrier between its start and end
    for r in ok_reads:
        lo = max((arith.rows(s, job) for s in samples
                  if s["t_resp"] <= r["sent"]), default=0.0)
        hi = min((arith.rows(s, job) for s in samples
                  if s["t_req"] >= r["done"]), default=float(rows_final))
        r["candidates"] = [n for n in range(
            int(lo), int(hi) + 1, rows_per_barrier)
            if n > 0 and n % rows_per_barrier == 0] or [int(hi)]
    at = sorted({n for r in ok_reads for n in r["candidates"]}
                | {rows_final})
    want = ref.reference_rows(
        config["reference"]["query"], rows_final,
        config["rate_events_per_s"], gen_seed, at)
    newest = dict(zip(want["at"].tolist(), want["event_time_at"].tolist()))
    want_cols = {n: want[n] for n in names}
    got = compare.closed(int_columns(view_rows, names), windows,
                         newest[rows_final])
    want_closed = compare.closed(want_cols, windows, newest[rows_final])
    view_differ, view_detail = compare.rows_differ(got, want_closed, names)
    n_closed = compare.distinct_windows(want_closed, windows)
    read_differ, read_detail = 0, ""
    for r in ok_reads:
        held_rows = int_columns(r["rows"], names)
        best = None
        for n in r["candidates"]:
            d, detail = compare.rows_differ(
                compare.closed(held_rows, windows, newest[n]),
                compare.closed(want_cols, windows, newest[n]), names)
            if best is None or d < best[0]:
                best = (d, detail)
        read_differ += best[0]
        read_detail = read_detail or best[1]
        r["rows"] = len(r["rows"])
    return view_differ, view_detail, n_closed, read_differ, read_detail


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             bench_path: str | None = None, child_script: str | None = None,
             require_tpu: bool = True, overrides: dict | None = None,
             out_root: str | None = None) -> tuple[dict, dict]:
    """One run.  Returns (result line, the window as the readers saw it).
    ``overrides`` replaces system parameters of the configuration: the
    controls of ``tests/`` break a guarantee with it, no cell does."""
    marks: list[str] = []

    def mark(what: str) -> None:
        marks.append(f"{what} at {time.monotonic() - T_START:.1f}s")

    cell = load_cell(bench_path or os.path.join(ROOT, "BENCHMARK.json"),
                     workload)
    config, traffic, job = cell["config"], cell["traffic"], cell["job"]
    params = dict(config["system_params"], **(overrides or {}))
    out = os.path.join(out_root or os.path.join(ROOT, "benchmark_out"),
                       workload)
    data_dir = os.path.join(out, "data")
    trace_dir = os.path.join(out, "trace")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    # the generator folds the seed in as seed * 2**40 in int64
    gen_seed = 1 + seed % (1 << 20)
    port, mport = free_port(), free_port()
    child = Child(
        [sys.executable,
         child_script or os.path.join(BENCH, "server_child.py"),
         "--gen-seed", str(gen_seed), "--",
         "--role", config["server"]["role"], "--port", str(port),
         "--metrics-port", str(mport), "--data-dir", data_dir,
         "--config-json", json.dumps(config["server"]["config_json"])],
        os.path.join(out, "server.log"))
    poller = disk = None
    readers: list[Reader] = []
    try:
        hs = child.next_json(900, "handshake")
        say(f"handshake {json.dumps(hs)}")
        mark("handshake")
        device = {"platform": hs["platform"], "kind": hs["device_kind"],
                  "count": hs["device_count"]}
        if require_tpu:
            if device["platform"] != "tpu" or device["count"] < cell["chips"]:
                say(f"no result: the cell needs {cell['chips']} TPU "
                    f"chip(s), the child found {device}")
                raise SystemExit(2)
            peaks = load_peaks(device["kind"])
        else:
            peaks = None

        c = PgClient("127.0.0.1", port, timeout=1500)
        for stmt in config["sources_sql"]:
            c.query(stmt)
        set_params(c, params)
        c.query(config["view"]["sql"])
        mark("view created")
        disk = DiskWatch(data_dir, job, traffic.get("poll_s", 0.5) / 2)
        disk.start()
        poller = Poller(mport, disk, traffic.get("poll_s", 0.5))
        poller.start()

        # -- set-up: the warm-up phases, then every reader statement once
        rows_per_barrier = params["chunks_per_barrier"] * \
            config["server"]["config_json"]["streaming"]["chunk_size"]
        for phase in traffic["warmup"]:
            set_params(c, phase.get("set", {}))
            until = phase["until"]
            base = poller.last()
            b0 = arith.barriers(base, job) if base else 0.0
            if "barriers" in until:
                cond = (lambda s, n=b0 + until["barriers"]:
                        arith.barriers(s, job) >= n)
            else:
                cond = (lambda s, n=until["rows"]: arith.rows(s, job) >= n)
            if not any(m.startswith("first barrier") for m in marks):
                wait_for(poller, child,
                         lambda s: arith.barriers(s, job) >= 1,
                         "first barrier", 1100)
                mark("first barrier")
            wait_for(poller, child, cond, f"warm-up {until}", 1100)
            mark(f"warm-up {json.dumps(until)}")
        for r in traffic.get("readers", []):
            c.query(r["sql"])
            readers += [Reader(port, r["sql"]) for _ in range(r["sessions"])]
        # the window opens on a barrier's edge
        before = child.ctl("stats")
        first = wait_for(
            poller, child,
            lambda s, n=arith.barriers(poller.last(), job):
            arith.barriers(s, job) > n, "window start", 600)
        t0 = first["t_resp"]
        setup_s = t0 - T_START
        if trace:
            child.ctl(f"trace_start {trace_dir}")
        session = iter(readers)
        for r in traffic.get("readers", []):
            for due in arith.schedule(t0, seconds, r["sessions"],
                                      r["reads_per_s"], r["jitter"], seed):
                next(session).due = due
        for rd in readers:
            rd.start()

        # -- the window
        t_end = t0 + seconds
        t_trace_end = t0 + traffic["trace"]["seconds"] if trace else None
        horizon = config["horizon_rows"]
        # a cell whose barriers are few and uneven measures a fixed
        # number of rows instead, if they are in before the time is up
        work = traffic.get("window", {}).get("rows")
        ended_by, t1 = "time", None
        while time.monotonic() < t_end:
            need(child.alive(), f"the server child died: "
                                f"{child.log_tail()}")
            need(poller.error is None, f"scrape failed: {poller.error}")
            if t_trace_end is not None and time.monotonic() >= t_trace_end:
                child.ctl("trace_stop", timeout=300)
                t_trace_end = None
            last = poller.last()
            if work and arith.rows(last, job) - arith.rows(first, job) \
                    >= work:
                ended_by, t1 = "window.rows", last["t_resp"]
                break
            if arith.rows(last, job) + rows_per_barrier > horizon:
                ended_by = "horizon_rows"
                break
            time.sleep(0.05)
        t1 = t1 or time.monotonic()
        for rd in readers:
            rd.halt.set()
        if t_trace_end is not None:
            child.ctl("trace_stop", timeout=300)
        # hold the sources: barriers go on, state stands still
        c.query("ALTER SYSTEM SET chunks_per_barrier = 0")
        for rd in readers:
            rd.join(timeout=120)
            need(not rd.is_alive(), "a reader did not come back in 120s")
        after = child.ctl("stats")
        # two barriers on: the last maintenance barrier's counters, and
        # every upload of the window acknowledged
        held = wait_for(
            poller, child,
            lambda s, n=arith.barriers(poller.last(), job) + 2:
            arith.barriers(s, job) >= n, "barriers after the hold", 600)
        t_read = time.monotonic()
        _, view_rows = c.query(
            f"SELECT {', '.join(config['view']['columns'])} FROM {job}")
        read_s = time.monotonic() - t_read
        poller.halt()
        disk.halt()
        final = poller.scrape()
        need(arith.rows(final, job) == arith.rows(held, job),
             "the sources did not hold")
        c.close()
        rc = child.stop()
        need(rc == 0, f"the server's orderly stop gave rc={rc}: "
                      f"{child.log_tail()}")
    finally:
        if poller is not None:
            poller.halt(wait=False)
        if disk is not None:
            disk.halt()
        child.kill()

    # -- the window, as the metric readers see it
    edges = [first] + arith.barrier_edges(
        [first] + [s for s in poller.samples if t0 < s["t_resp"] <= t1],
        job)
    reads = [r for rd in readers for r in rd.reads]
    n_barriers = int(arith.barriers(edges[-1], job)
                     - arith.barriers(edges[0], job))
    # the epochs the disk showed committed between the window's first and
    # last barrier, by what the manifest on disk called committed at each
    on_disk = disk.between(edges[0]["disk_committed"] or 0,
                           edges[-1]["disk_committed"] or 0)
    window = {
        "cell": cell, "job": job, "seconds": seconds, "peaks": peaks,
        "scrape_start": edges[0], "scrape_end": edges[-1],
        "edges": edges, "reads": reads, "setup_s": setup_s,
        "barriers": n_barriers, "epochs_on_disk": on_disk,
        "trace": None, "device": device,
    }
    need(n_barriers >= 2, f"the window saw {n_barriers} barriers: too "
                          "few to measure between")

    # -- what is compared, each beside its limit
    rows_final = int(arith.rows(final, job))
    # the guarantee, judged by the disk: a committed epoch for every
    # barrier of the window.  The program's own count of uploads stands
    # beside it as a cross-check only.
    no_epoch = n_barriers - len(on_disk)
    uploads = arith.delta(edges[0], edges[-1], "checkpoint_uploads_total",
                          job=job)
    uncommitted = n_barriers - int(uploads or 0)
    disk_bad, disk_detail = check_disk(
        data_dir, job, int(arith.metric(final["m"], "committed_epoch", 0,
                                        job=job)))
    counters = arith.family(final["m"], "maintenance_counter_rows", job=job)
    fallbacks = arith.family(final["m"], "dag_fused_fallback_total", job=job)
    loop_errors = int(arith.metric(final["m"], "barrier_loop_errors_total",
                                   0.0))
    compiles = after["compiles"] - before["compiles"]

    ok_reads = [r for r in reads if r["ok"]]
    t_ref = time.monotonic()
    view_differ, view_detail, n_closed, read_differ, read_detail = \
        compare_with_reference(config, gen_seed, view_rows, rows_final,
                               ok_reads, poller.samples, job,
                               rows_per_barrier)
    ref_s = time.monotonic() - t_ref
    failed_reads = len(reads) - len(ok_reads)
    checks = {
        "view_rows_differ": [view_differ, "<=", 0],
        "closed_windows": [n_closed, ">=", 1],
        "read_rows_differ": [read_differ, "<=", 0],
        "reads_failed": [failed_reads, "<=", 0],
        "barriers_without_epoch_on_disk": [no_epoch, "<=", 0],
        "barriers_uncommitted": [uncommitted, "<=", 0],
        "disk_epochs_bad": [disk_bad, "<=", 0],
        "counter_rows": [int(sum(counters.values())) if counters else -1,
                         "==", 0],
        "fused_fallbacks": [int(sum(fallbacks.values())), "<=", 0],
        "barrier_errors": [loop_errors, "<=", 0],
        "compiles_in_window": [compiles, "<=", 0],
    }
    holds = {"<=": lambda a, b: a <= b, ">=": lambda a, b: a >= b,
             "==": lambda a, b: a == b}
    correct = all(holds[op](v, lim) for v, op, lim in checks.values())

    # -- the trace, reduced
    if trace:
        import trace_reduce
        t_tr = time.monotonic()
        window["trace"] = trace_reduce.reduce_dir(
            trace_dir, traffic["trace"])
        say(f"trace: {window['trace']['summary']} "
            f"(read in {time.monotonic() - t_tr:.1f}s)")

    metrics = {}
    kind = "per_layer" if trace else "end_to_end"
    for m in cell[kind]:
        value = load_module(reader_path(kind, m["name"])).read(window)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    late = [1000.0 * (r["sent"] - r["due"]) for r in reads]
    dev = dict(device, memory_peak_bytes=after["memory_peak_bytes"])
    result = {"correct": correct,
              "attempted": len(reads) + n_barriers,
              "failed": failed_reads + max(no_epoch, uncommitted, 0)
              + loop_errors,
              "metrics": metrics, "device": dev}
    if trace:
        tr = window["trace"]
        dev.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    result["checks"] = checks

    say(f"window: {edges[-1]['t_resp'] - edges[0]['t_resp']:.3f}s between "
        f"the first and last of {n_barriers} barriers seen, of "
        f"{seconds:g}s asked; ended by {ended_by}; "
        f"rows in window {int(arith.rows(edges[-1], job) - arith.rows(edges[0], job))}, "
        f"rows in all {rows_final} (horizon {horizon}); "
        f"compiles inside the window {compiles} "
        f"{after['last_compiled'] if compiles else ''}")
    lat = arith.read_latencies_ms(reads)
    say(f"reads: {len(reads)} ({failed_reads} failed), from due time to last "
        f"row median {arith.median(lat) or 0:.1f} ms, p95 "
        f"{arith.percentile(lat, 0.95) or 0:.1f} ms; the generator sent "
        f"them late by median {arith.median(late) or 0:.1f} ms, p95 "
        f"{arith.percentile(late, 0.95) or 0:.1f} ms (a session's earlier "
        "read still running counts)")
    say(f"set-up {setup_s:.1f}s ({', '.join(marks)}; {before['compiles']} "
        f"programs loaded, {before['cache_misses']} of them not in the "
        f"compile cache); final read of {len(view_rows)} rows "
        f"{read_s:.1f}s; reference {ref_s:.1f}s; disk: {disk_detail}; "
        f"epochs seen committed on disk: {len(on_disk)} of the window's "
        f"{n_barriers} barriers, {len(disk.committed_bytes)} in all")
    if view_detail or read_detail:
        say(f"differs: view: {view_detail or '-'}; reads: "
            f"{read_detail or '-'}")
    say("compared, each beside its limit: " + "; ".join(
        f"{k} {v} {op} {lim}" for k, (v, op, lim) in checks.items())
        + f"; correct={str(correct).lower()}")
    shutil.rmtree(data_dir, ignore_errors=True)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return result, window


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    try:
        result, _ = run_cell(args.workload, args.seed, args.seconds,
                             bool(args.trace))
    except RunFailure as e:
        say(f"FAILED, no result: {e}")
        return 1
    if "jax" in sys.modules:
        from jax._src import xla_bridge
        if xla_bridge.backends_are_initialized():
            say("FAILED, no result: the parent initialised a JAX backend")
            return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
