"""Chaos-lite campaign: seeded deterministic fault schedules against a
real multi-process cluster.

The acceptance harness for ISSUE 6 (the madsim-campaign analog): a
1-meta + 2-compute + 1-serving cluster (ALL four roles are real
processes) maintains two nexmark MVs through a seeded fault schedule
while concurrent serving reads run end-to-end.  Every schedule must
finish with

- ZERO read errors (reads retry through transient windows and must
  eventually answer from committed state only),
- ZERO stuck rounds (every requested global round commits),
- byte-identical final MV contents vs an undisturbed single-node run
  of the same config and round count.

Schedules (all deterministic: the fabric is counter-addressed and the
schedule expands from the seed via splitmix64 — same seed, same
faults, same replay):

- ``rpc_drop_storm``   drop + error-after-send storms on the meta's
                       control RPCs and the workers' meta-bound RPCs
                       (heartbeats included); retry/backoff and
                       round-tagged barriers must absorb everything;
- ``meta_kill``        SIGKILL the meta MID-ROUND, restart it on the
                       same RPC port over the same data_dir: it must
                       rebuild jobs + round position from the durable
                       MetaStore/manifest, workers and the serving
                       replica must re-register via backoff, the
                       interrupted round re-seals, and committing
                       resumes with no operator action;
- ``store_faults``     object-store put faults on the workers'
                       checkpoint uploads (lost AND durable-then-error
                       modes) during the pipelined async upload; the
                       uploader's RetryPolicy absorbs them off the
                       barrier path;
- ``scale_storm``      RPC drops on the worker↔worker EXCHANGE seam
                       (fan-out + catch-up fetch of a vnode-
                       partitioned job's replicated table) while the
                       cluster SCALES OUT mid-stream: retries plus
                       the barrier-fence repair fetch must absorb
                       every drop, the handover must move exactly the
                       minimal vnode set, and the MV must converge
                       byte-identically;
- ``corruption_storm`` seeded ``bit_flip``/``truncate`` payload
                       corruption on the workers' object-store puts
                       (MV-export SSTs AND checkpoint epoch uploads)
                       while rounds, serving reads, the compactor and
                       the meta scrubber all run: EVERY planted
                       corruption must be detected (typed
                       IntegrityError → durable quarantine note),
                       repaired (SST re-export from live job state /
                       checkpoint lineage rewind), with ZERO client-
                       visible read errors, zero silent wrong reads,
                       and byte-identical convergence;
- ``scale_kill``       SIGKILL the slice-transplant RECIPIENT between
                       the transplant and the donors' mask swap
                       during ``ctl cluster scale N`` (a seeded fabric
                       delay on the donor's repartition RPC holds the
                       window open): the transplanted state must
                       survive through the durably-sealed lineage,
                       the op must roll forward on retry, 0 read
                       errors, byte-identical convergence.

Run standalone (prints one JSON summary line per schedule)::

    python scripts/chaos_campaign.py --assert            # all three
    python scripts/chaos_campaign.py --schedule meta_kill --seed 11

or the short ``slow``-marked pytest wrapper
(tests/test_chaos_campaign.py).
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import threading
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
    """CREATE MATERIALIZED VIEW q7 AS
    SELECT window_start, max(price) AS max_price, count(*) AS bids
    FROM TUMBLE(bid, date_time, INTERVAL '1' SECOND)
    GROUP BY window_start""",
    """CREATE MATERIALIZED VIEW qcnt AS
    SELECT auction % 16 AS a, count(*) AS n, sum(price) AS vol
    FROM bid GROUP BY auction % 16""",
]

READS = [
    "SELECT window_start, max_price, bids FROM q7",
    "SELECT a, n, vol FROM qcnt",
]

SCHEDULES = ("rpc_drop_storm", "meta_kill", "store_faults",
             "scale_storm", "corruption_storm", "scale_kill",
             "shuffle_storm")

#: scale_storm topology: a vnode-partitioned aggregation over a
#: replicated DML table (the worker↔worker exchange seam under test)
SCALE_DDL = [
    "CREATE TABLE t (k BIGINT, v BIGINT)",
    """CREATE MATERIALIZED VIEW agg AS
    SELECT k, count(*) AS n, sum(v) AS s, max(v) AS mx
    FROM t GROUP BY k""",
]
SCALE_READ = "SELECT k, n, s, mx FROM agg"


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _wait_port(port: int, deadline_s: float = 120.0) -> None:
    """Block until something LISTENS on the port (a freshly spawned
    meta takes seconds to boot before peers can register)."""
    deadline = time.monotonic() + deadline_s
    while True:
        try:
            socket.create_connection(("127.0.0.1", port),
                                     timeout=1.0).close()
            return
        except OSError:
            if time.monotonic() > deadline:
                raise TimeoutError(f"port {port} never listened")
            time.sleep(0.2)


def _env(fault_env: dict | None) -> dict:
    env = dict(os.environ)
    env.pop("RWT_FAULTS", None)
    if fault_env:
        env["RWT_FAULTS"] = json.dumps(fault_env)
    return env


def _spawn_meta(data_dir: str, rpc_port: int, tag: str,
                fault_env: dict | None = None,
                scale_partitioning: bool = False,
                scrub_interval: float | None = None,
                serve_retry_timeout: float | None = None):
    argv = [sys.executable, "-m", "risingwave_tpu.server",
            "--role", "meta", "--port", str(_free_port()),
            "--rpc-port", str(rpc_port), "--data-dir", data_dir,
            "--heartbeat-timeout", "3.0",
            "--barrier-interval-ms", "0"]  # the driver owns the cadence
    if scale_partitioning:
        argv.append("--scale-partitioning")
    if scrub_interval is not None:
        argv += ["--scrub-interval", str(scrub_interval)]
    if serve_retry_timeout is not None:
        argv += ["--serve-retry-timeout", str(serve_retry_timeout)]
    proc = subprocess.Popen(
        argv,
        stdout=subprocess.DEVNULL,
        stderr=open(os.path.join(data_dir, f"meta_{tag}.log"), "wb"),
        env=_env(fault_env),
    )
    return proc


def _spawn_worker(rpc_port: int, data_dir: str, idx: int,
                  fault_env: dict | None = None):
    return subprocess.Popen(
        [sys.executable, "-m", "risingwave_tpu.server",
         "--role", "compute", "--meta", f"127.0.0.1:{rpc_port}",
         "--data-dir", data_dir, "--config-json", json.dumps(CONFIG),
         "--heartbeat-interval", "0.25"],
        stdout=subprocess.DEVNULL,
        stderr=open(os.path.join(data_dir, f"worker{idx}.log"), "wb"),
        env=_env(fault_env),
    )


def _spawn_serving(rpc_port: int, data_dir: str,
                   fault_env: dict | None = None):
    return subprocess.Popen(
        [sys.executable, "-m", "risingwave_tpu.server",
         "--role", "serving", "--meta", f"127.0.0.1:{rpc_port}",
         "--data-dir", data_dir, "--heartbeat-interval", "0.25"],
        stdout=subprocess.DEVNULL,
        stderr=open(os.path.join(data_dir, "serving.log"), "wb"),
        env=_env(fault_env),
    )


class MetaDriver:
    """Patient RPC driver: survives the meta being down/restarting
    (the client reconnects to whatever process owns the port)."""

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
                raise  # the meta answered: final
            except (ConnectionError, OSError):
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.1)

    def close(self) -> None:
        self.client.close()


def _fault_envs(schedule: str, seed: int) -> dict:
    """Expand one (schedule, seed) into per-role ``RWT_FAULTS`` JSON.
    Pure function of the inputs — the determinism contract."""
    from risingwave_tpu.common.faults import FaultFabric

    if schedule == "rpc_drop_storm":
        meta_fab = FaultFabric.storm(
            seed, op="rpc", n=10, span=60,
            modes=("drop", "error_after_send"),
        )
        peer_fab = FaultFabric.storm(
            seed ^ 0x5A5A, op="rpc", substr=">meta/", n=5, span=80,
            modes=("drop",),
        )
        return {"meta": meta_fab.to_json(),
                "worker": peer_fab.to_json(),
                "serving": peer_fab.to_json()}
    if schedule == "store_faults":
        worker_fab = FaultFabric.storm(
            seed, op="put", substr="epoch_", n=6, span=50,
            modes=("before", "after"),
        )
        return {"worker": worker_fab.to_json()}
    if schedule == "scale_storm":
        # drops on the worker↔worker peer seam only: exchange fan-out,
        # catch-up fetch_table, repartition-era forwards — the labels
        # are ``worker{i}>worker{j}/<method>``, so ``>worker`` never
        # matches a worker's meta-bound RPCs
        peer_fab = FaultFabric.storm(
            seed, op="rpc", substr=">worker", n=8, span=8,
            modes=("drop",),
        )
        return {"worker": peer_fab.to_json()}
    if schedule == "corruption_storm":
        # payload corruption on the workers' shared-store uploads:
        # bit_flips on MV-export SSTs, bit_flip+truncate on checkpoint
        # epoch objects — every byte of both is crc-covered, so every
        # firing MUST surface as a typed IntegrityError somewhere
        # (serving read, compaction merge, or the scrub walk)
        fab = FaultFabric.storm(
            seed, op="put", substr="sst/", n=3, span=8,
            modes=("bit_flip",),
        )
        ck = FaultFabric.storm(
            seed ^ 0xC0FF, op="put", substr="/epoch_", n=2, span=20,
            modes=("bit_flip", "truncate"),
        )
        fab.rules += ck.rules
        return {"worker": fab.to_json()}
    if schedule == "shuffle_storm":
        # Exchange-lite seam under storm: seeded DROPS on the sliced
        # peer exchange plus ONE bounded one-way partition
        # (worker1>worker2 dark while worker2>worker1 flows) during
        # partitioned-JOIN ingest — lost sliced batches and the dark
        # direction must heal through the fence completeness audit
        # (fetch_slice / fetch_positions), never through the gate
        peer_fab = FaultFabric.storm(
            seed, op="rpc", substr=">worker", n=8, span=10,
            modes=("drop",),
        )
        peer_fab.partition("worker1", "worker2", times=4, after=20)
        return {"worker": peer_fab.to_json()}
    if schedule == "scale_kill":
        # ONE seeded delay on the donor's mask-swap RPC during the
        # handover (meta-side label ``meta>worker1/repartition``): the
        # recipient's transplant has landed, the donor's narrow is
        # held open — the deterministic window where the campaign
        # SIGKILLs the recipient
        fab = FaultFabric(seed=seed)
        fab.fail_rpc(substr=">worker1/repartition", after=0,
                     mode="delay", times=1, delay_s=3.0)
        return {"meta": fab.to_json()}
    return {}


def run_schedule(schedule: str, seed: int = 7, rounds: int = 10,
                 kill_at_round: int = 4, readers: int = 2,
                 data_dir: str | None = None) -> dict:
    assert schedule in SCHEDULES, schedule
    if schedule == "scale_storm":
        return run_scale_storm(seed=seed, rounds=rounds,
                               scale_at_round=kill_at_round,
                               readers=readers, data_dir=data_dir)
    if schedule == "scale_kill":
        return run_scale_kill(seed=seed, rounds=rounds,
                              scale_at_round=kill_at_round,
                              readers=readers, data_dir=data_dir)
    if schedule == "shuffle_storm":
        return run_shuffle_storm(seed=seed, rounds=rounds,
                                 scale_at_round=kill_at_round,
                                 readers=readers, data_dir=data_dir)
    data_dir = data_dir or tempfile.mkdtemp(
        prefix=f"chaos_{schedule}_")
    envs = _fault_envs(schedule, seed)
    # determinism spot-check: the same (schedule, seed) must expand to
    # the byte-identical fault schedule (no RNG anywhere in the path)
    deterministic = envs == _fault_envs(schedule, seed)

    storm = schedule == "corruption_storm"
    rpc_port = _free_port()
    meta_proc = _spawn_meta(
        data_dir, rpc_port, "a", fault_env=envs.get("meta"),
        # corruption_storm: fast background scrub cycles + patient
        # serving reads (repairs happen inside the read window)
        scrub_interval=2.0 if storm else None,
        serve_retry_timeout=180.0 if storm else None,
    )
    _wait_port(rpc_port)  # peers register against a LIVE meta
    procs = [_spawn_worker(rpc_port, data_dir, i,
                           fault_env=envs.get("worker"))
             for i in range(2)]
    serving_proc = _spawn_serving(rpc_port, data_dir,
                                  fault_env=envs.get("serving"))
    driver = MetaDriver(rpc_port)
    state = {"reads": 0, "read_errors": [], "tick_retries": 0,
             "meta_restarts": 0}
    stop = threading.Event()

    def read_loop():
        while not stop.is_set():
            for sql in READS:
                try:
                    driver.call("serve", sql=sql, deadline_s=180.0)
                    state["reads"] += 1
                except Exception as e:  # noqa: BLE001
                    state["read_errors"].append(repr(e))
            time.sleep(0.05)

    def drive_round(deadline_s: float = 240.0) -> None:
        deadline = time.monotonic() + deadline_s
        while True:
            try:
                res = driver.call("tick", chunks_per_barrier=1)
                if res["committed"]:
                    return
            except Exception:  # noqa: BLE001 — meta mid-restart
                pass
            state["tick_retries"] += 1
            if time.monotonic() > deadline:
                raise TimeoutError(f"round never committed "
                                   f"({schedule}, seed {seed})")
            time.sleep(0.2)

    try:
        deadline = time.monotonic() + 180
        while True:
            st = driver.call("cluster_state", deadline_s=120.0)
            if sum(w["alive"] for w in st["workers"]) >= 2 \
                    and st["serving"]:
                break
            for p in procs:
                if p.poll() is not None:
                    raise RuntimeError(
                        f"worker died at startup (logs in {data_dir})")
            if time.monotonic() > deadline:
                raise TimeoutError("cluster never assembled")
            time.sleep(0.25)

        for sql in DDL:
            driver.call("execute_ddl", sql=sql)

        threads = [threading.Thread(target=read_loop, daemon=True)
                   for _ in range(readers)]
        for t in threads:
            t.start()

        committed = 0
        while committed < rounds:
            drive_round()
            committed = int(driver.call(
                "cluster_state")["cluster_epoch"])
            if storm:
                # scrub EVERY round: a corrupt checkpoint epoch must
                # be caught before retention GC rotates it out —
                # detection + (synchronous) repair per cycle
                driver.call("cluster_scrub", deadline_s=300.0)
            if schedule == "meta_kill" and committed == kill_at_round \
                    and state["meta_restarts"] == 0:
                # SIGKILL MID-ROUND: launch the next round, give the
                # barriers a moment to be in flight, then kill
                t = threading.Thread(
                    target=lambda: _swallow(
                        lambda: driver.call("tick",
                                            chunks_per_barrier=1)),
                    daemon=True)
                t.start()
                time.sleep(0.3)
                meta_proc.send_signal(signal.SIGKILL)
                meta_proc.wait(timeout=10)
                t.join(timeout=30)
                meta_proc = _spawn_meta(data_dir, rpc_port, "b",
                                        fault_env=envs.get("meta"))
                state["meta_restarts"] += 1

        stop.set()
        for t in threads:
            t.join(timeout=15)

        final_scrub = None
        if storm:
            # drain: keep scrubbing until nothing corrupt remains in
            # reach (repairs are synchronous within each cycle)
            for _ in range(6):
                final_scrub = driver.call("cluster_scrub",
                                          deadline_s=300.0)
                if not final_scrub["corrupt"]:
                    break
                time.sleep(0.5)
        final_state = driver.call("cluster_state")
        faults = driver.call("cluster_faults")
        cluster_rows = [
            sorted(tuple(v) for v in driver.call(
                "serve", sql=sql)["rows"])
            for sql in READS
        ]
    finally:
        stop.set()
        for p in procs + [serving_proc, meta_proc]:
            if p.poll() is None:
                p.kill()
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass
        driver.close()

    # undisturbed single-node reference (same config + rounds)
    from risingwave_tpu.common.config import RwConfig
    from risingwave_tpu.sql.engine import Engine

    eng = Engine(RwConfig.from_dict(CONFIG))
    for sql in DDL:
        eng.execute(sql)
    eng.tick(barriers=rounds, chunks_per_barrier=1)
    single_rows = [
        sorted(tuple(int(x) for x in r) for r in eng.execute(sql))
        for sql in READS
    ]
    mismatches = sum(c != s for c, s in zip(cluster_rows, single_rows))

    worker_faults = [v for v in faults["workers"].values() if v]
    injected = sum((v["fabric"] or {}).get("injected_total", 0)
                   for v in worker_faults + [faults["meta"]]
                   + [v for v in faults["serving"].values() if v])
    peer_retries = sum(v["rpc_retries_total"] for v in worker_faults)
    upload_retries = sum(v.get("checkpoint_upload_retries_total", 0)
                         for v in worker_faults)
    planted = sorted({
        k for v in worker_faults
        for k in (v["fabric"] or {}).get("corrupted_keys", [])
    })
    detected = sorted(set((final_scrub or {}).get("quarantined", [])))
    summary = {
        "schedule": schedule,
        "seed": seed,
        "deterministic_expansion": deterministic,
        "rounds": rounds,
        "rounds_committed": int(final_state["cluster_epoch"]),
        "meta_recovered": bool(final_state.get("recovered")),
        "meta_restarts": state["meta_restarts"],
        "live_workers": sum(w["alive"]
                            for w in final_state["workers"]),
        "serving_replicas": len(final_state["serving"]),
        "worker_registrations": sum(
            v.get("registrations", 0) for v in worker_faults),
        "reads": state["reads"],
        "read_errors": len(state["read_errors"]),
        "read_error_samples": state["read_errors"][:3],
        "tick_retries": state["tick_retries"],
        "faults_injected": injected,
        "meta_rpc_retries": faults["meta"]["rpc_retries_total"],
        "peer_rpc_retries": peer_retries,
        "upload_retries": upload_retries,
        "corruptions_planted": planted,
        "corruptions_detected": detected,
        "all_corruptions_detected":
            bool(planted) and set(planted) <= set(detected),
        "repairs": (final_scrub or {}).get("repairs", {}),
        "scrub_unrepaired":
            len((final_scrub or {}).get("corrupt", [])),
        "mv_mismatches": mismatches,
        "mv_rows": [len(r) for r in cluster_rows],
        "data_dir": data_dir,
    }
    summary["ok"] = bool(
        summary["deterministic_expansion"]
        and summary["read_errors"] == 0
        and summary["rounds_committed"] >= rounds
        and summary["mv_mismatches"] == 0
        and summary["live_workers"] == 2
        and _schedule_ok(schedule, summary)
    )
    return summary


def _schedule_ok(schedule: str, s: dict) -> bool:
    if schedule == "rpc_drop_storm":
        # the storm actually fired and the retry budget absorbed it
        return s["faults_injected"] > 0 \
            and (s["meta_rpc_retries"] + s["peer_rpc_retries"]
                 + s["tick_retries"]) > 0
    if schedule == "meta_kill":
        # the restarted meta REBUILT its state from the durable logs
        # and every peer re-registered without operator action
        return s["meta_restarts"] == 1 and s["meta_recovered"] \
            and s["worker_registrations"] >= 4 \
            and s["serving_replicas"] >= 1
    if schedule == "store_faults":
        # faults hit the async upload path and were retried there
        return s["faults_injected"] > 0 and s["upload_retries"] > 0
    if schedule == "corruption_storm":
        # every planted corruption detected (quarantine note per
        # corrupted object), every reachable one repaired, and at
        # least one repair of each class actually ran
        return s["all_corruptions_detected"] \
            and s["scrub_unrepaired"] == 0 \
            and sum(s["repairs"].values()) > 0
    return True


def run_scale_storm(seed: int = 7, rounds: int = 10,
                    scale_at_round: int = 4, readers: int = 2,
                    data_dir: str | None = None) -> dict:
    """Seeded drops on the worker↔worker exchange seam while the
    cluster scales out mid-stream (see module docstring)."""
    data_dir = data_dir or tempfile.mkdtemp(prefix="chaos_scale_")
    envs = _fault_envs("scale_storm", seed)
    deterministic = envs == _fault_envs("scale_storm", seed)

    rpc_port = _free_port()
    meta_proc = _spawn_meta(data_dir, rpc_port, "a",
                            scale_partitioning=True)
    _wait_port(rpc_port)
    procs = [_spawn_worker(rpc_port, data_dir, i,
                           fault_env=envs.get("worker"))
             for i in range(2)]
    driver = MetaDriver(rpc_port)
    state = {"reads": 0, "read_errors": [], "tick_retries": 0,
             "rows": []}
    stop = threading.Event()

    def read_loop():
        while not stop.is_set():
            try:
                driver.call("serve", sql=SCALE_READ, deadline_s=180.0)
                state["reads"] += 1
            except Exception as e:  # noqa: BLE001
                state["read_errors"].append(repr(e))
            time.sleep(0.05)

    def drive_round(deadline_s: float = 240.0) -> None:
        deadline = time.monotonic() + deadline_s
        while True:
            res = driver.call("tick", chunks_per_barrier=2)
            if res["committed"]:
                return
            state["tick_retries"] += 1
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"round never committed (scale_storm, seed {seed})")
            time.sleep(0.2)

    try:
        deadline = time.monotonic() + 180
        while True:
            st = driver.call("cluster_state", deadline_s=120.0)
            if sum(w["alive"] for w in st["workers"]) >= 2:
                break
            for p in procs:
                if p.poll() is not None:
                    raise RuntimeError(
                        f"worker died at startup (logs in {data_dir})")
            if time.monotonic() > deadline:
                raise TimeoutError("cluster never assembled")
            time.sleep(0.25)

        driver.call("cluster_scale", n=1)  # capacity starts at ONE
        for sql in SCALE_DDL:
            driver.call("execute_ddl", sql=sql)

        def ingest(i0: int, n: int) -> None:
            rows = [((i0 + j) % 97, 3 * (i0 + j) + 1) for j in range(n)]
            vals = ",".join(f"({k},{v})" for k, v in rows)
            # the meta forwards ONE statement to the ingest leader;
            # the leader's fan-out (the seam under storm) is peer RPC
            driver.call("execute_ddl",
                        sql=f"INSERT INTO t VALUES {vals}")
            state["rows"].extend(rows)

        threads = [threading.Thread(target=read_loop, daemon=True)
                   for _ in range(readers)]
        for t in threads:
            t.start()

        scale_out = None
        i0 = 0
        committed = 0
        while committed < rounds:
            # several small batches per round: each fan-out is one
            # peer RPC, so the storm has real traffic to hit
            for _ in range(4):
                ingest(i0, 24)
                i0 += 24
            drive_round()
            committed = int(driver.call(
                "cluster_state")["cluster_epoch"])
            if scale_out is None and committed >= scale_at_round:
                # DOUBLE mid-stream, exchange storm active
                scale_out = driver.call("cluster_scale", n=2,
                                        deadline_s=600.0)
        total = len(state["rows"])
        drain_deadline = time.monotonic() + 300
        while True:
            drive_round()
            rows = driver.call("serve", sql=SCALE_READ)["rows"]
            if sum(int(r[1]) for r in rows) == total:
                break
            if time.monotonic() > drain_deadline:
                raise TimeoutError("scale_storm never drained")

        stop.set()
        for t in threads:
            t.join(timeout=15)
        faults = driver.call("cluster_faults")
        final_state = driver.call("cluster_state")
        cluster_rows = sorted(
            tuple(int(x) for x in r)
            for r in driver.call("serve", sql=SCALE_READ)["rows"]
        )
    finally:
        stop.set()
        for p in procs + [meta_proc]:
            if p.poll() is None:
                p.kill()
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass
        driver.close()

    from risingwave_tpu.common.config import RwConfig
    from risingwave_tpu.sql.engine import Engine

    eng = Engine(RwConfig.from_dict(CONFIG))
    for sql in SCALE_DDL:
        eng.execute(sql)
    sent = state["rows"]
    for i in range(0, len(sent), 1024):
        vals = ",".join(f"({k},{v})" for k, v in sent[i:i + 1024])
        eng.execute(f"INSERT INTO t VALUES {vals}")
    for _ in range(4096):
        eng.tick(barriers=1, chunks_per_barrier=2)
        if sum(int(r[1]) for r in eng.execute(SCALE_READ)) \
                == len(sent):
            break
    single_rows = sorted(
        tuple(int(x) for x in r) for r in eng.execute(SCALE_READ)
    )

    worker_faults = [v for v in faults["workers"].values() if v]
    injected = sum((v["fabric"] or {}).get("injected_total", 0)
                   for v in worker_faults)
    absorbed = sum(v["rpc_retries_total"]
                   + v.get("exchange_fetches", 0)
                   + v.get("exchange_send_failures", 0)
                   for v in worker_faults)
    summary = {
        "schedule": "scale_storm",
        "seed": seed,
        "deterministic_expansion": deterministic,
        "rounds": rounds,
        "rounds_committed": int(final_state["cluster_epoch"]),
        "rows_ingested": len(sent),
        "reads": state["reads"],
        "read_errors": len(state["read_errors"]),
        "read_error_samples": state["read_errors"][:3],
        "tick_retries": state["tick_retries"],
        "scale_out_moved_vnodes":
            scale_out["moved_vnodes"] if scale_out else 0,
        "active_workers":
            final_state["scale"]["active_workers"],
        "faults_injected": injected,
        "exchange_faults_absorbed": absorbed,
        "exchange_rows_in": sum(v.get("exchange_rows_in", 0)
                                for v in worker_faults),
        "mv_mismatches": int(cluster_rows != single_rows),
        "mv_rows": len(cluster_rows),
        "data_dir": data_dir,
    }
    summary["ok"] = bool(
        summary["deterministic_expansion"]
        and summary["read_errors"] == 0
        and summary["rounds_committed"] >= rounds
        and summary["mv_mismatches"] == 0
        and summary["scale_out_moved_vnodes"] == 32
        and summary["faults_injected"] > 0
        and summary["exchange_faults_absorbed"] > 0
        and summary["active_workers"] == [1, 2]
    )
    return summary


#: shuffle_storm topology: a vnode-PARTITIONED JOIN over two sliced-
#: ingest tables — the Exchange-lite seam under storm.  LEFT OUTER so
#: mid-stream b-arrivals retract their pad rows (retraction churn
#: through the chaos window).
SHUFFLE_DDL = [
    "CREATE TABLE a (k BIGINT, v BIGINT)",
    "CREATE TABLE b (k BIGINT, w BIGINT)",
    """CREATE MATERIALIZED VIEW j AS
    SELECT a.k AS k, a.v AS v, b.w AS w
    FROM a LEFT JOIN b ON a.k = b.k""",
]
SHUFFLE_READ = "SELECT k, v, w FROM j"
SHUFFLE_KEYS = 97


def run_shuffle_storm(seed: int = 7, rounds: int = 10,
                      scale_at_round: int = 4, readers: int = 2,
                      data_dir: str | None = None) -> dict:
    """Seeded drops + a one-way partition on the SLICED exchange seam
    during partitioned-JOIN ingest (see module docstring): lost
    sliced batches heal through the fence completeness audit, reads
    stay zero-error, the join MV converges byte-identical, and the
    gate audit counters prove no row ever reached a partition it did
    not own."""
    data_dir = data_dir or tempfile.mkdtemp(prefix="chaos_shuffle_")
    envs = _fault_envs("shuffle_storm", seed)
    deterministic = envs == _fault_envs("shuffle_storm", seed)

    rpc_port = _free_port()
    meta_proc = _spawn_meta(data_dir, rpc_port, "a",
                            scale_partitioning=True)
    _wait_port(rpc_port)
    procs = [_spawn_worker(rpc_port, data_dir, i,
                           fault_env=envs.get("worker"))
             for i in range(2)]
    driver = MetaDriver(rpc_port)
    state = {"reads": 0, "read_errors": [], "tick_retries": 0,
             "rows_a": [], "rows_b": []}
    stop = threading.Event()

    def read_loop():
        while not stop.is_set():
            try:
                driver.call("serve", sql=SHUFFLE_READ,
                            deadline_s=180.0)
                state["reads"] += 1
            except Exception as e:  # noqa: BLE001
                state["read_errors"].append(repr(e))
            time.sleep(0.05)

    def drive_round(deadline_s: float = 240.0) -> None:
        deadline = time.monotonic() + deadline_s
        while True:
            res = driver.call("tick", chunks_per_barrier=2)
            if res["committed"]:
                return
            state["tick_retries"] += 1
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"round never committed (shuffle_storm, "
                    f"seed {seed})")
            time.sleep(0.2)

    try:
        deadline = time.monotonic() + 180
        while True:
            st = driver.call("cluster_state", deadline_s=120.0)
            if sum(w["alive"] for w in st["workers"]) >= 2:
                break
            for p in procs:
                if p.poll() is not None:
                    raise RuntimeError(
                        f"worker died at startup (logs in {data_dir})")
            if time.monotonic() > deadline:
                raise TimeoutError("cluster never assembled")
            time.sleep(0.25)

        driver.call("cluster_scale", n=2)  # partitioned from round 0
        for sql in SHUFFLE_DDL:
            driver.call("execute_ddl", sql=sql)

        def ingest_a(i0: int, n: int) -> None:
            rows = [((i0 + j) % SHUFFLE_KEYS, 3 * (i0 + j) + 1)
                    for j in range(n)]
            vals = ",".join(f"({k},{v})" for k, v in rows)
            driver.call("execute_ddl",
                        sql=f"INSERT INTO a VALUES {vals}")
            state["rows_a"].extend(rows)

        def ingest_b(ks) -> None:
            rows = [(k, 1000 + 7 * k) for k in ks]
            vals = ",".join(f"({k},{w})" for k, w in rows)
            driver.call("execute_ddl",
                        sql=f"INSERT INTO b VALUES {vals}")
            state["rows_b"].extend(rows)

        # half the keys matched up front; the other half arrives
        # MID-storm so every pad row retracts under fire
        ingest_b(range(0, SHUFFLE_KEYS, 2))

        threads = [threading.Thread(target=read_loop, daemon=True)
                   for _ in range(readers)]
        for t in threads:
            t.start()

        i0 = 0
        committed = 0
        b_late = False
        while committed < rounds:
            for _ in range(4):
                ingest_a(i0, 24)
                i0 += 24
            drive_round()
            committed = int(driver.call(
                "cluster_state")["cluster_epoch"])
            if not b_late and committed >= scale_at_round:
                b_late = True
                ingest_b(range(1, SHUFFLE_KEYS, 2))
        total_a = len(state["rows_a"])
        # left outer with exactly one b-row per key: |j| == |a|
        drain_deadline = time.monotonic() + 300
        while True:
            drive_round()
            rows = driver.call("serve", sql=SHUFFLE_READ)["rows"]
            if len(rows) == total_a \
                    and all(r[2] is not None for r in rows):
                break
            if time.monotonic() > drain_deadline:
                raise TimeoutError("shuffle_storm never drained")

        stop.set()
        for t in threads:
            t.join(timeout=15)
        faults = driver.call("cluster_faults")
        final_state = driver.call("cluster_state")
        cluster_rows = sorted(
            tuple(int(x) for x in r)
            for r in driver.call("serve", sql=SHUFFLE_READ)["rows"]
        )
    finally:
        stop.set()
        for p in procs + [meta_proc]:
            if p.poll() is None:
                p.kill()
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass
        driver.close()

    from risingwave_tpu.common.config import RwConfig
    from risingwave_tpu.sql.engine import Engine

    eng = Engine(RwConfig.from_dict(CONFIG))
    for sql in SHUFFLE_DDL:
        eng.execute(sql)
    b1 = state["rows_b"][:len(range(0, SHUFFLE_KEYS, 2))]
    b2 = state["rows_b"][len(b1):]
    eng.execute("INSERT INTO b VALUES "
                + ",".join(f"({k},{w})" for k, w in b1))
    sent = state["rows_a"]
    # replay a in the same interleaving: first-half b, then a up to
    # the late-b position, then late b, then the rest — the join is
    # retraction-consistent so only the FINAL state must match, and
    # it does for any interleaving once all rows land
    for i in range(0, len(sent), 1024):
        vals = ",".join(f"({k},{v})" for k, v in sent[i:i + 1024])
        eng.execute(f"INSERT INTO a VALUES {vals}")
    if b2:
        eng.execute("INSERT INTO b VALUES "
                    + ",".join(f"({k},{w})" for k, w in b2))
    for _ in range(4096):
        eng.tick(barriers=1, chunks_per_barrier=2)
        rows = eng.execute(SHUFFLE_READ)
        if len(rows) == len(sent) \
                and all(r[2] is not None for r in rows):
            break
    single_rows = sorted(
        tuple(int(x) for x in r) for r in eng.execute(SHUFFLE_READ)
    )

    worker_faults = [v for v in faults["workers"].values() if v]
    injected = sum((v["fabric"] or {}).get("injected_total", 0)
                   for v in worker_faults)
    absorbed = sum(v["rpc_retries_total"]
                   + v.get("exchange_fetches", 0)
                   + v.get("exchange_send_failures", 0)
                   for v in worker_faults)
    summary = {
        "schedule": "shuffle_storm",
        "seed": seed,
        "deterministic_expansion": deterministic,
        "rounds": rounds,
        "rounds_committed": int(final_state["cluster_epoch"]),
        "rows_ingested": len(sent),
        "reads": state["reads"],
        "read_errors": len(state["read_errors"]),
        "read_error_samples": state["read_errors"][:3],
        "tick_retries": state["tick_retries"],
        "faults_injected": injected,
        "exchange_faults_absorbed": absorbed,
        "shuffled_tables": list((final_state.get("exchange") or {})
                                .get("tables", {})),
        "mv_mismatches": int(cluster_rows != single_rows),
        "mv_rows": len(cluster_rows),
        "partitions": len(final_state["jobs"][0]["partitions"] or []),
        "data_dir": data_dir,
    }
    summary["ok"] = bool(
        summary["deterministic_expansion"]
        and summary["read_errors"] == 0
        and summary["rounds_committed"] >= rounds
        and summary["mv_mismatches"] == 0
        and summary["partitions"] == 2
        and summary["faults_injected"] > 0
        and summary["exchange_faults_absorbed"] > 0
        and sorted(summary["shuffled_tables"]) == ["a", "b"]
    )
    return summary


def run_scale_kill(seed: int = 7, rounds: int = 8,
                   scale_at_round: int = 3, readers: int = 2,
                   data_dir: str | None = None) -> dict:
    """SIGKILL the slice-transplant recipient mid-``cluster scale``
    (see module docstring): the seeded fabric delays the DONOR's
    mask-swap RPC, holding open the window between the recipient's
    transplant and the donors' narrow; the campaign kills the
    recipient inside it.  The transplanted state must survive through
    the durably-sealed lineage (failover re-adopts it on the spare
    worker), the interrupted scale op must roll forward on retry, and
    the MV must converge byte-identically with 0 read errors."""
    data_dir = data_dir or tempfile.mkdtemp(prefix="chaos_scalekill_")
    envs = _fault_envs("scale_kill", seed)
    deterministic = envs == _fault_envs("scale_kill", seed)

    rpc_port = _free_port()
    meta_proc = _spawn_meta(data_dir, rpc_port, "a",
                            fault_env=envs.get("meta"),
                            scale_partitioning=True,
                            serve_retry_timeout=300.0)
    _wait_port(rpc_port)
    driver = MetaDriver(rpc_port)
    scaler = MetaDriver(rpc_port)  # scale blocks for minutes: own conn
    procs = []
    state = {"reads": 0, "read_errors": [], "tick_retries": 0,
             "rows": []}
    stop = threading.Event()

    def read_loop():
        while not stop.is_set():
            try:
                driver.call("serve", sql=SCALE_READ, deadline_s=420.0)
                state["reads"] += 1
            except Exception as e:  # noqa: BLE001
                state["read_errors"].append(repr(e))
            time.sleep(0.05)

    def drive_round(deadline_s: float = 420.0) -> None:
        deadline = time.monotonic() + deadline_s
        while True:
            try:
                res = driver.call("tick", chunks_per_barrier=2)
                if res["committed"]:
                    return
            except Exception:  # noqa: BLE001 — stalled scale window
                pass
            state["tick_retries"] += 1
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"round never committed (scale_kill, seed {seed})")
            time.sleep(0.2)

    def ingest(i0: int, n: int) -> None:
        rows = [((i0 + j) % 83, 5 * (i0 + j) + 2) for j in range(n)]
        vals = ",".join(f"({k},{v})" for k, v in rows)
        driver.call("execute_ddl", sql=f"INSERT INTO t VALUES {vals}")
        state["rows"].extend(rows)

    scale_res: dict = {}
    try:
        # spawn workers ONE AT A TIME: registration order fixes the
        # worker ids the seeded schedule addresses (worker1 = donor)
        deadline = time.monotonic() + 240
        for i in range(3):
            procs.append(_spawn_worker(rpc_port, data_dir, i))
            while True:
                st = driver.call("cluster_state", deadline_s=120.0)
                if sum(w["alive"] for w in st["workers"]) >= i + 1:
                    break
                if procs[i].poll() is not None:
                    raise RuntimeError(
                        f"worker died at startup (logs in {data_dir})")
                if time.monotonic() > deadline:
                    raise TimeoutError("cluster never assembled")
                time.sleep(0.25)

        driver.call("cluster_scale", n=1)  # donor owns everything
        for sql in SCALE_DDL:
            driver.call("execute_ddl", sql=sql)

        threads = [threading.Thread(target=read_loop, daemon=True)
                   for _ in range(readers)]
        for t in threads:
            t.start()

        i0 = 0
        committed = 0
        while committed < scale_at_round:
            for _ in range(3):
                ingest(i0, 24)
                i0 += 24
            drive_round()
            committed = int(driver.call(
                "cluster_state")["cluster_epoch"])

        # scale 1 -> 2 in a thread; the donor's narrow is delayed by
        # the fabric, so the recipient's transplant is observable
        # BEFORE the mask swap — the kill window
        def do_scale():
            try:
                scale_res["first"] = scaler.call(
                    "cluster_scale", n=2, deadline_s=600.0)
            except Exception as e:  # noqa: BLE001 — expected: stall
                scale_res["first_error"] = repr(e)

        t_scale = threading.Thread(target=do_scale, daemon=True)
        t_scale.start()
        kill_deadline = time.monotonic() + 120
        while True:
            st = driver.call("cluster_state", deadline_s=120.0)
            job = next((j for j in st["jobs"] if j["name"] == "agg"),
                       None)
            parts = (job or {}).get("partitions") or []
            if any(p["worker"] == 2 and p["vnodes"] > 0
                   for p in parts):
                break  # transplant landed on the recipient
            if time.monotonic() > kill_deadline:
                raise TimeoutError("transplant to recipient never "
                                   "became visible")
            time.sleep(0.05)
        procs[1].send_signal(signal.SIGKILL)  # the recipient dies
        procs[1].wait(timeout=10)
        t_scale.join(timeout=600)

        # failover: the dead recipient's lineage (WITH the durably
        # sealed transplanted slice) re-adopts on the spare worker
        drive_round(deadline_s=420.0)
        # the interrupted op rolls forward on retry
        scale_res["retry"] = scaler.call("cluster_scale", n=2,
                                         deadline_s=600.0)

        while committed < rounds:
            for _ in range(3):
                ingest(i0, 24)
                i0 += 24
            drive_round()
            committed = int(driver.call(
                "cluster_state")["cluster_epoch"])
        total = len(state["rows"])
        drain_deadline = time.monotonic() + 420
        while True:
            drive_round()
            rows = driver.call("serve", sql=SCALE_READ)["rows"]
            if sum(int(r[1]) for r in rows) == total:
                break
            if time.monotonic() > drain_deadline:
                raise TimeoutError("scale_kill never drained")

        stop.set()
        for t in threads:
            t.join(timeout=15)
        final_state = driver.call("cluster_state")
        cluster_rows = sorted(
            tuple(int(x) for x in r)
            for r in driver.call("serve", sql=SCALE_READ)["rows"]
        )
    finally:
        stop.set()
        for p in procs + [meta_proc]:
            if p.poll() is None:
                p.kill()
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass
        driver.close()
        scaler.close()

    from risingwave_tpu.common.config import RwConfig
    from risingwave_tpu.sql.engine import Engine

    eng = Engine(RwConfig.from_dict(CONFIG))
    for sql in SCALE_DDL:
        eng.execute(sql)
    sent = state["rows"]
    for i in range(0, len(sent), 1024):
        vals = ",".join(f"({k},{v})" for k, v in sent[i:i + 1024])
        eng.execute(f"INSERT INTO t VALUES {vals}")
    for _ in range(4096):
        eng.tick(barriers=1, chunks_per_barrier=2)
        if sum(int(r[1]) for r in eng.execute(SCALE_READ)) \
                == len(sent):
            break
    single_rows = sorted(
        tuple(int(x) for x in r) for r in eng.execute(SCALE_READ)
    )

    summary = {
        "schedule": "scale_kill",
        "seed": seed,
        "deterministic_expansion": deterministic,
        "rounds": rounds,
        "rounds_committed": int(final_state["cluster_epoch"]),
        "rows_ingested": len(sent),
        "reads": state["reads"],
        "read_errors": len(state["read_errors"]),
        "read_error_samples": state["read_errors"][:3],
        "tick_retries": state["tick_retries"],
        "first_scale_error": scale_res.get("first_error"),
        "first_scale": scale_res.get("first"),
        "retry_scale_ok": "retry" in scale_res,
        "active_workers": final_state["scale"]["active_workers"],
        "mv_mismatches": int(cluster_rows != single_rows),
        "mv_rows": len(cluster_rows),
        "data_dir": data_dir,
    }
    summary["ok"] = bool(
        summary["deterministic_expansion"]
        and summary["read_errors"] == 0
        and summary["rounds_committed"] >= rounds
        and summary["mv_mismatches"] == 0
        and summary["retry_scale_ok"]
        # the kill interrupted the first op OR the op absorbed the
        # death entirely — either way the retry rolled it forward
        and (summary["first_scale_error"] is not None
             or summary["first_scale"] is not None)
    )
    return summary


def _swallow(fn) -> None:
    try:
        fn()
    except Exception:  # noqa: BLE001 — the kill window eats the call
        pass


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--schedule", choices=SCHEDULES + ("all",),
                   default="all")
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--rounds", type=int, default=10)
    p.add_argument("--kill-at-round", type=int, default=4)
    p.add_argument("--readers", type=int, default=2)
    p.add_argument("--assert", dest="check", action="store_true",
                   help="exit nonzero unless every schedule converged "
                        "with 0 read errors and 0 stuck rounds")
    args = p.parse_args()

    names = SCHEDULES if args.schedule == "all" else (args.schedule,)
    ok = True
    for name in names:
        summary = run_schedule(
            name, seed=args.seed, rounds=args.rounds,
            kill_at_round=args.kill_at_round, readers=args.readers,
        )
        print(json.dumps(summary), flush=True)
        ok = ok and summary["ok"]
    if args.check:
        raise SystemExit(0 if ok else 1)


if __name__ == "__main__":
    main()
