"""MetaService: the cluster's coordination brain (meta node role).

Reference counterparts, collapsed into one object:

- ``ClusterController`` worker registry + heartbeat expiry
  (src/meta/src/manager/cluster.rs) — workers register, beat, and are
  declared dead after ``heartbeat_timeout_s`` of silence;
- ``DdlController`` + streaming job placement
  (src/meta/src/rpc/ddl_controller.rs) — DDL lands in the durable
  catalog log, streaming jobs are scheduled onto compute workers
  (job-level placement: least-loaded live worker, MV-on-MV co-located
  with its upstream job);
- ``GlobalBarrierWorker`` (src/meta/src/barrier/worker.rs:378) — the
  global checkpoint protocol: one *round* injects a barrier into every
  job on every worker, collects per-job epoch seals, and only when ALL
  jobs sealed the round commits ONE cluster epoch through the
  versioned manifest (storage/hummock/version.py) — so a snapshot
  read pinned at that commit sees every MV at the same round;
- recovery (SURVEY.md §3.5) — on missed heartbeats the worker is
  marked dead, its jobs are reassigned to survivors and recovered
  from their last durable checkpoint; counter-addressed sources make
  the replay exact, so the cluster converges to the byte-identical
  result of an undisturbed run.

Pacing contract: compute workers have NO self-ticker — every chunk
and barrier a job processes is driven by a meta ``tick()`` round.
That makes the meta the global serializer for checkpoint-store
commits (one barrier RPC in flight at a time), which is what keeps
the shared manifest single-writer without a distributed lock.
"""

from __future__ import annotations

import bisect
import os
import threading
import time
from dataclasses import dataclass, field

from risingwave_tpu.cluster.rpc import (
    RpcClient,
    RpcError,
    RpcServer,
)
from risingwave_tpu.common.faults import RetryPolicy, get_fabric
from risingwave_tpu.common.metrics import MetricsRegistry, merge_prometheus
from risingwave_tpu.common.trace import (
    GLOBAL_TRACE,
    merge_dumps,
    round_ids,
    spans_for_round,
    tree_check,
)
from risingwave_tpu.meta.store import MetaStore


@dataclass
class WorkerInfo:
    """One registered compute worker (ref WorkerNode)."""

    worker_id: int
    host: str
    port: int
    pid: int | None = None
    alive: bool = True
    last_seen: float = field(default_factory=time.monotonic)
    #: job names assigned to this worker
    jobs: set = field(default_factory=set)
    client: RpcClient | None = None
    #: SST keys allocated to this worker for MV exports, not yet
    #: returned in a barrier seal (released as orphans on death)
    sst_keys: set = field(default_factory=set)

    @property
    def addr(self) -> str:
        return f"{self.host}:{self.port}"


@dataclass
class ServingReplicaInfo:
    """One registered serving replica (the stateless read tier).

    ``pins`` maps manifest vid → meta-side pin id: the replica's HELD
    version and its latest GRANT both stay pinned in the meta's
    ``VersionManager``, so vacuum counts them in its keep-set — a
    serving read can never lose an SST underneath it.  The lease
    advances on heartbeats (the replica reports the vid it holds; the
    meta releases older pins and pins the current version as the next
    grant) and is reaped wholesale when the replica's heartbeat
    expires."""

    replica_id: int
    host: str
    port: int
    pid: int | None = None
    alive: bool = True
    last_seen: float = field(default_factory=time.monotonic)
    client: RpcClient | None = None
    #: manifest vid -> VersionManager pin id
    pins: dict = field(default_factory=dict)
    granted_vid: int = 0

    @property
    def addr(self) -> str:
        return f"{self.host}:{self.port}"


@dataclass
class PartitionInfo:
    """One vnode partition of a partitioned job (the scale plane's
    barrier unit).  ``lineage`` is the partition's checkpoint key in
    the SHARED store — it survives worker moves (failover re-adopts
    the lineage on a new worker; scale-in slices it into recipients).
    Field names mirror ``JobInfo`` so the round protocol drives jobs
    and partitions through one code path."""

    lineage: str
    worker_id: int | None = None
    #: owned vnode ids (current map)
    vnodes: list = field(default_factory=list)
    #: lost its last vnode in a handover: no longer a barrier unit,
    #: but keeps serving reads pinned at PRE-handover rounds until the
    #: first post-handover commit publishes a new serve plan — then it
    #: is dropped and released
    retiring: bool = False
    #: cluster round this partition has sealed up to
    rounds: int = 0
    #: (round, epoch_value) per sealed barrier, round-ascending
    seal_log: list = field(default_factory=list)
    pinned_epoch: int = 0
    #: vnode set at the last cluster commit — reads pinned at that
    #: round route with THIS set, so a mid-handover read still sees
    #: every row exactly once
    pinned_vnodes: list = field(default_factory=list)
    durable_epoch: int = 0

    @property
    def name(self) -> str:  # unit key in seal records / pending SSTs
        return self.lineage


@dataclass
class JobInfo:
    """One placed streaming job (ref TableFragments / StreamingJob).

    ``mvs`` lists every MV/sink riding the job (MV-on-MV attaches to
    its upstream's job, exactly like the engine merges DagJobs).
    ``seal_log`` records (round, committed_epoch) per successful
    barrier — the map recovery uses to translate a recovered epoch
    back into a round position.
    """

    name: str
    ddl: list = field(default_factory=list)
    mvs: list = field(default_factory=list)
    worker_id: int | None = None
    #: cluster round this job has sealed up to
    rounds: int = 0
    #: (round, epoch_value) per sealed barrier, round-ascending
    seal_log: list = field(default_factory=list)
    #: epoch value serving reads pin for this job (last CLUSTER commit)
    pinned_epoch: int = 0
    #: last durable (upload-acked) epoch the worker reported — the
    #: cluster epoch commits only when this catches the round's seal
    durable_epoch: int = 0
    #: vnode partitions (scale plane) — None = whole-job placement;
    #: keyed by checkpoint lineage, ONE partition per owning worker
    partitions: "dict[str, PartitionInfo] | None" = None
    #: DML tables the job's source reads (exchanged worker↔worker)
    dml_tables: list = field(default_factory=list)
    #: Exchange-lite: raw source column each DML table routes by
    #: (absent/None = untraceable → the table's edge replicates)
    shuffle_cols: dict = field(default_factory=dict)
    #: edge taxonomy per table ("source" ingest / "join" side)
    edge_kinds: dict = field(default_factory=dict)
    #: MV-on-MV attach edges riding this job: (upstream, downstream)
    attach_edges: list = field(default_factory=list)
    #: read-routing plan published ATOMICALLY at each cluster commit:
    #: [(worker_id, pinned_epoch, vnodes)] — all entries from the SAME
    #: round, so a fan-out read sees every vnode exactly once even
    #: while a handover is reshaping the live partition set
    serve_plan: list | None = None


#: SQL aggregate names (the serve router refuses to union these
#: across partitions — per-partition partials are not the answer)
_AGG_FUNCS = frozenset({
    "count", "sum", "sum0", "min", "max", "avg", "stddev_pop",
    "stddev_samp", "var_pop", "var_samp", "bool_and", "bool_or",
    "string_agg", "approx_count_distinct",
})


def _sql_literal(v) -> str:
    """Render one pk value as a SQL literal (the multi-get owner
    fallback synthesizes per-pk SELECTs)."""
    if isinstance(v, bool):
        return "true" if v else "false"
    if isinstance(v, (int, float)):
        return repr(v)
    return "'" + str(v).replace("'", "''") + "'"


def _select_needs_engine_merge(sel) -> bool:
    """True when a SELECT over a partitioned MV cannot be answered by
    unioning per-partition rows (aggregates / GROUP BY / DISTINCT
    merge rows ACROSS partitions)."""
    from risingwave_tpu.sql import ast

    if sel.group_by or sel.having is not None \
            or getattr(sel, "distinct", False):
        return True

    def has_agg(e) -> bool:
        if isinstance(e, ast.FuncCall) and e.name in _AGG_FUNCS:
            return True
        for a in ("left", "right", "operand", "expr"):
            v = getattr(e, a, None)
            if v is not None and has_agg(v):
                return True
        return any(has_agg(x) for x in getattr(e, "args", ())
                   if not isinstance(x, ast.Star))

    return any(has_agg(item.expr) for item in sel.items
               if not isinstance(item.expr, ast.Star))


class MetaService:
    """The meta node.  ``start()`` brings up the RPC server and the
    heartbeat monitor; tests may also drive every method in-process."""

    def __init__(self, data_dir: str, heartbeat_timeout_s: float = 3.0,
                 metrics: MetricsRegistry | None = None,
                 serve_retry_timeout_s: float = 60.0,
                 rpc_timeout_s: float = 180.0,
                 durable_wait_s: float = 15.0,
                 retry_max_attempts: int = 4,
                 retry_base_delay_s: float = 0.05,
                 retry_max_delay_s: float = 0.5,
                 n_vnodes: int = 64,
                 scale_partitioning: bool = False,
                 scrub_interval_s: float = 30.0,
                 shuffle_ingest: bool = True):
        from risingwave_tpu.storage.hummock import (
            CompactorService,
            HummockStorage,
            LocalFsObjectStore,
            ScrubberService,
        )

        self.data_dir = data_dir
        self.heartbeat_timeout_s = heartbeat_timeout_s
        self.serve_retry_timeout_s = serve_retry_timeout_s
        self.rpc_timeout_s = rpc_timeout_s
        #: how long one tick() waits for the round's checkpoint
        #: uploads to ack before returning the round uncommitted
        #: (retried by the next tick — rounds never commit past a
        #: non-durable seal)
        self.durable_wait_s = durable_wait_s
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        #: durable DDL log — the same store a single node replays, so a
        #: restarted meta (or a single-node takeover) can rebuild the
        #: cluster catalog
        self.store = MetaStore(data_dir)
        #: the meta-owned storage service over the shared data_dir:
        #: the version manifest (meta is its SINGLE writer — workers
        #: upload SST objects under meta-allocated keys and hand the
        #: descriptors back through barrier seals), the background
        #: compactor, and pin-aware vacuum.  ``versions`` stays the
        #: cluster-epoch commit point it always was.
        self.hummock = HummockStorage(
            LocalFsObjectStore(os.path.join(data_dir, "hummock")),
            metrics=self.metrics,
        )
        self.versions = self.hummock.versions
        # gentler poll than the embedded default: the meta shares its
        # core with the barrier loop and the RPC server
        self.compactor = CompactorService(self.hummock,
                                          poll_interval_s=0.05)
        # -- integrity: scrub + quarantine + self-healing repair -------
        #: corrupt objects currently under repair (dedups concurrent
        #: detections of the same object)
        self._repairing: set = set()
        self._repair_lock = threading.Lock()
        self.repairs = {"sst": 0, "checkpoint": 0}
        #: corrupt SST keys workers surfaced through barrier responses
        #: (repaired after the round, outside the tick lock)
        self._corrupt_reports: list = []
        #: every detection point routes here: compaction reads, scrub
        #: walks, serving-replica reports — quarantine + repair, off
        #: the latency path
        self.hummock.on_corruption = self._on_corruption
        #: the background scrubber (meta-owned, a compactor sibling):
        #: paced off-barrier verification of every pinned-version SST
        #: and retained checkpoint lineage over the SHARED data_dir
        self.scrubber = ScrubberService(
            self.hummock,
            ckpt_object_store=LocalFsObjectStore(data_dir),
            metrics=self.metrics,
            interval_s=scrub_interval_s,
            on_corruption=self._on_corruption,
        )
        self._lock = threading.RLock()
        #: serializes barrier rounds AND failover reassignment: a job
        #: is never adopted while one of its barrier RPCs is in flight
        self._tick_lock = threading.Lock()
        #: single-flights _assign_pending: the monitor loop, DDL
        #: placement, and registration all drive it — two assigners
        #: interleaving their adopt probes would point a worker's
        #: checkpoint lineage somewhere the registry never records
        self._assign_lock = threading.Lock()
        self.workers: dict[int, WorkerInfo] = {}
        #: registered serving replicas (the stateless read tier)
        self.serving: dict[int, ServingReplicaInfo] = {}
        self._next_replica = 1
        #: round-robin cursor for serving-read routing
        self._serve_rr = 0
        #: (job_name, round) -> uploaded-but-uncommitted MV export SST
        #: descriptors; committed into the manifest with the round's
        #: cluster epoch, replaced when a failover re-seals the round
        self._pending_ssts: dict[tuple, list] = {}
        #: pushdown plane: expiry-policy docs staged by barrier
        #: responses (table → doc, None = DROP), committed into the
        #: same manifest delta as the round's export SSTs
        self._pending_policies: dict = {}
        self.jobs: dict[str, JobInfo] = {}
        #: mv/sink name -> owning JobInfo name
        self._mv_to_job: dict[str, str] = {}
        #: secondary indexes: index name → upstream MV name (an MV
        #: with live indexes refuses DROP until they are dropped)
        self._indexes: dict[str, str] = {}
        #: non-job DDL in arrival order (sources/tables/SETs/functions)
        #: — shipped to a worker the first time a job needs them
        self.prelude: list[str] = []
        self._next_worker = 1
        #: committed cluster epoch (round number, 0 = nothing committed)
        self.cluster_epoch = 0
        self.failovers = 0
        # -- trace-lite (common/trace.py) round-trace state ------------
        #: the round whose root span ``_trace_root_ctx`` belongs to: a
        #: RETRIED round (previous tick didn't commit) parents its new
        #: attempt under the ORIGINAL root, so trace "round-N" keeps
        #: exactly one root span however many ticks the round takes
        self._trace_round = 0
        self._trace_root_ctx: tuple | None = None
        #: last COMMITTED round's root ctx — piggybacked on serving
        #: lease grants so sampled replica read spans join the round
        #: tree of the epoch they actually read
        self._last_round_ctx: tuple | None = None
        #: unified backoff for every retry-safe control RPC the meta
        #: issues (barrier/job_epochs/adopt are idempotent or
        #: round-guarded; RpcError — the peer REFUSED — never retries)
        self.retry = RetryPolicy(
            max_attempts=retry_max_attempts,
            base_delay_s=retry_base_delay_s,
            max_delay_s=retry_max_delay_s,
            metrics=self.metrics, op="meta",
        )
        self._server: RpcServer | None = None
        self._monitor: threading.Thread | None = None
        self._stop = threading.Event()
        # -- elastic scale plane (cluster/scale) -----------------------
        #: ring size of the global vnode keyspace
        self.n_vnodes = int(n_vnodes)
        #: opt-in: place ELIGIBLE jobs as vnode partitions over the
        #: active worker set (``ctl cluster scale N`` then moves only
        #: vnodes).  Off = whole-job placement (the pre-scale plane).
        self.scale_partitioning = bool(scale_partitioning)
        #: Exchange-lite sliced ingest (default ON).  Off = the PR-7
        #: replicate-everything fan-out — kept as the A/B baseline the
        #: scale_stress throughput gate measures against, and the
        #: escape hatch if a traced shuffle key misbehaves in the
        #: field.  Flipping it re-pushes the choreography.
        self.shuffle_ingest = bool(shuffle_ingest)
        #: vnode → worker_id (None until the first map is cut)
        self.vnode_map: list[int] | None = None
        #: the ACTIVE worker set (capacity follows ``scale N``, not
        #: registration — spare workers idle until scaled in)
        self.active_workers: list[int] = []
        self._next_lineage = 1
        self._routing_version = 0
        self.scale_ops = 0
        #: per-round DML fence cache (a retried round reuses the fence
        #: its survivors sealed with — cursor alignment across retries)
        self._fence_round = 0
        self._fence_cache: dict[str, int] = {}
        #: True when this meta rebuilt jobs from a durable catalog (a
        #: restart) — introspection for operators and chaos asserts
        self.recovered = False
        self._recover_from_store()
        self._set_worker_gauges()

    # -- crash recovery ---------------------------------------------------
    def _recover_from_store(self) -> None:
        """Meta restart: rebuild the cluster catalog (jobs, MV→job map,
        prelude) by replaying the durable DDL log, then restore the
        round position from the last committed-round record.  Every
        job comes back UNASSIGNED — workers detect the dead meta
        through heartbeat errors, re-register with backoff, and
        ``_assign_pending`` re-adopts their jobs from the last durable
        checkpoint; ``_rewind_job`` translates each recovered epoch
        back into a round (crediting a round the old meta sealed but
        never committed — the in-flight round re-seals, it never
        re-runs).  No operator action anywhere on this path."""
        ddl = self.store.ddl_log()
        if not ddl:
            return
        self.recovered = True
        for sql in ddl:
            self.execute_ddl(sql, replay=True)
        # scale plane: the last scale event restores the vnode map and
        # each partitioned job's lineage layout.  Worker ids in the map
        # are STALE (a restarted meta hands out fresh ids) — every
        # partition comes back unassigned and ``_assign_pending``
        # re-adopts its lineage (recover=True) on re-registered
        # workers, re-pointing the map as it goes.
        ev = self.store.last_scale_event()
        if ev is not None:
            self.scale_partitioning = True
            self.n_vnodes = int(ev.get("n_vnodes", self.n_vnodes))
            self.vnode_map = [int(w) for w in ev["map"]] \
                if ev.get("map") else None
            self._next_lineage = int(ev.get("next_lineage", 1))
            for jname, parts in (ev.get("partitions") or {}).items():
                job = self.jobs.get(jname)
                if job is None:
                    continue
                job.partitions = {
                    p["lineage"]: PartitionInfo(
                        lineage=p["lineage"],
                        worker_id=None,
                        vnodes=[int(v) for v in p["vnodes"]],
                    )
                    for p in parts
                }
                job.dml_tables = list(ev.get("dml_tables", {})
                                      .get(jname, []))
                job.shuffle_cols = {
                    t: (int(c) if c is not None else None)
                    for t, c in (ev.get("shuffle_cols", {})
                                 .get(jname, {})).items()
                }
                job.edge_kinds = dict(ev.get("edge_kinds", {})
                                      .get(jname, {}))
                job.attach_edges = [
                    tuple(e) for e in (ev.get("attach_edges", {})
                                       .get(jname, []))
                ]
        rec = self.store.last_cluster_commit()
        if rec is None:
            return
        self.cluster_epoch = int(rec["round"])
        for job in self.jobs.values():
            job.rounds = self.cluster_epoch
            for unit in (job.partitions.values() if job.partitions
                         else [job]):
                seal = rec["seals"].get(unit.name)
                unit.rounds = self.cluster_epoch
                if seal is not None:
                    unit.seal_log = [(self.cluster_epoch, int(seal))]
                    unit.pinned_epoch = int(seal)
                    if unit is not job:
                        unit.pinned_vnodes = list(unit.vnodes)
        self.metrics.set_gauge("cluster_epoch_committed",
                               self.cluster_epoch)
        self.metrics.set_gauge("cluster_manifest_epoch",
                               self.versions.max_committed_epoch)

    # -- lifecycle ------------------------------------------------------
    @property
    def rpc_port(self) -> int:
        return self._server.port if self._server is not None else 0

    def start(self, host: str = "127.0.0.1", port: int = 0,
              monitor: bool = True, compactor: bool = True,
              scrubber: bool = True) -> "MetaService":
        self._stop.clear()
        self._server = RpcServer(self, host, port).start()
        if monitor:
            self._monitor = threading.Thread(
                target=self._monitor_loop, name="meta-monitor",
                daemon=True,
            )
            self._monitor.start()
        if compactor:
            # the shared-storage compactor rides the meta process (the
            # manifest's single writer); in-process tests may pass
            # compactor=False and drive hummock.compact_once directly
            self.compactor.start()
        if scrubber:
            # the scrub walk is read-only + paced; repairs go through
            # the same quarantine pipeline every detection point uses
            self.scrubber.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        self.scrubber.stop()
        self.compactor.stop()
        if self._monitor is not None:
            self._monitor.join(timeout=5)
            self._monitor = None
        if self._server is not None:
            self._server.stop()
            self._server = None
        with self._lock:
            for w in self.workers.values():
                if w.client is not None:
                    w.client.close()
            for r in self.serving.values():
                if r.client is not None:
                    r.client.close()

    # -- worker registry / heartbeats -----------------------------------
    def rpc_register_worker(self, host: str, port: int,
                            pid: int | None = None) -> dict:
        with self._lock:
            wid = self._next_worker
            self._next_worker += 1
            w = WorkerInfo(wid, host, int(port), pid)
            w.client = RpcClient(host, int(port),
                                 timeout=self.rpc_timeout_s,
                                 src="meta", dst=f"worker{wid}")
            self.workers[wid] = w
            self._set_worker_gauges()
        # a fresh worker can pick up any stranded jobs immediately
        self._assign_pending()
        return {"worker_id": wid, "cluster_epoch": self.cluster_epoch}

    def rpc_heartbeat(self, worker_id: int,
                      port: int | None = None) -> dict:
        with self._lock:
            w = self.workers.get(int(worker_id))
            if w is None or not w.alive \
                    or (port is not None and int(port) != w.port):
                # a dead-marked worker must re-register: its jobs may
                # already run elsewhere (ref: expired workers rejoin
                # through the registration path).  So must one whose
                # id a RESTARTED meta (ids count from 1 again) has
                # since given to another worker: the port tells them
                # apart, or its beats would keep the other alive and
                # it would never rejoin
                raise ValueError(f"unknown or expired worker {worker_id}")
            w.last_seen = time.monotonic()
        return {"ok": True, "cluster_epoch": self.cluster_epoch}

    def rpc_unregister_worker(self, worker_id: int) -> dict:
        """Graceful deregistration (scale-in decommission, orderly
        shutdown): the worker leaves the registry ENTIRELY — jobs
        reassign exactly like a death, and every per-worker metric
        series is retired so the scrape surface reflects the live
        membership, not tombstones."""
        with self._lock:
            w = self.workers.get(int(worker_id))
        if w is None:
            return {"ok": True, "known": False}
        self._on_worker_dead(w)
        with self._lock:
            self.workers.pop(w.worker_id, None)
            self._remove_worker_series(w.worker_id)
            self._set_worker_gauges()
        self._push_routing()
        return {"ok": True, "known": True}

    def _remove_worker_series(self, worker_id: int) -> None:
        """Retire EVERY per-worker labeled series of one worker (death
        or deregistration) — stale gauges must not linger forever on
        the scrape surface."""
        for name in ("cluster_worker_heartbeat_age_seconds",
                     "cluster_worker_vnodes"):
            self.metrics.remove_series(name, worker=str(worker_id))
        if worker_id in getattr(self, "_exchange_series", set()):
            self._exchange_series.discard(worker_id)
            for k in ("rows_out", "rows_in", "batches_out",
                      "batches_in", "send_failures"):
                self.metrics.remove_series(
                    f"cluster_worker_exchange_{k}",
                    worker=str(worker_id),
                )

    def live_workers(self) -> list[WorkerInfo]:
        with self._lock:
            return [w for w in self.workers.values() if w.alive]

    def _set_worker_gauges(self) -> None:
        self.metrics.set_gauge(
            "cluster_live_workers",
            sum(1 for w in self.workers.values() if w.alive),
        )
        self.metrics.set_gauge("cluster_jobs", len(self.jobs))
        self.metrics.set_gauge(
            "cluster_serving_replicas",
            sum(1 for r in self.serving.values() if r.alive),
        )
        self.metrics.set_gauge(
            "cluster_serving_pins",
            sum(len(r.pins) for r in self.serving.values()),
        )

    def _monitor_loop(self) -> None:
        interval = min(self.heartbeat_timeout_s / 4, 0.5)
        while not self._stop.wait(interval):
            self.check_heartbeats()

    def check_heartbeats(self) -> None:
        """One monitor pass: refresh age gauges, expire silent workers,
        reassign their jobs (also called directly by tests).  Serving
        replicas expire on the same cadence — a dead replica's epoch
        pin lease is reaped immediately so it can never block vacuum
        forever."""
        now = time.monotonic()
        expired: list[WorkerInfo] = []
        stale_serving: list[ServingReplicaInfo] = []
        with self._lock:
            for w in self.workers.values():
                if not w.alive:
                    continue
                age = now - w.last_seen
                self.metrics.set_gauge(
                    "cluster_worker_heartbeat_age_seconds", age,
                    worker=str(w.worker_id),
                )
                if age > self.heartbeat_timeout_s:
                    expired.append(w)
            for r in self.serving.values():
                if not r.alive:
                    continue
                self.metrics.set_gauge(
                    "cluster_serving_heartbeat_age_seconds",
                    now - r.last_seen, replica=str(r.replica_id),
                )
                if now - r.last_seen > self.heartbeat_timeout_s:
                    stale_serving.append(r)
        for w in expired:
            self._on_worker_dead(w)
        for r in stale_serving:
            self._on_serving_dead(r)
        pending = any(
            (j.worker_id is None if j.partitions is None
             else any(p.worker_id is None
                      for p in j.partitions.values()))
            for j in self.jobs.values()
        )
        if expired or pending:
            self._assign_pending()

    def _on_serving_dead(self, r: ServingReplicaInfo) -> None:
        """Reap one serving replica: drop it from routing, release
        every pin of its lease (stale leases must not hold GC keep-set
        entries for a process that will never read again), and RETIRE
        its per-replica metric series — a reaped replica must not
        leave frozen gauges on the scrape surface (mirrors the
        per-worker retirement)."""
        with self._lock:
            if not r.alive:
                return
            r.alive = False
            for pin_id in r.pins.values():
                self.versions.unpin(pin_id)
            r.pins.clear()
            if r.client is not None:
                r.client.close()
            self.serving.pop(r.replica_id, None)
            self._remove_serving_series(r.replica_id)
            self._set_worker_gauges()

    def _remove_serving_series(self, replica_id: int) -> None:
        """Retire EVERY per-replica labeled series of one serving
        replica (lease reaped or deregistered)."""
        for name in ("cluster_serving_heartbeat_age_seconds",
                     "cluster_serving_granted_vid"):
            self.metrics.remove_series(name, replica=str(replica_id))

    def _on_worker_dead(self, w: WorkerInfo) -> None:
        # under the tick lock: never declare dead / reassign while one
        # of the worker's barrier RPCs is still in flight (a stale
        # barrier finishing late must not interleave checkpoint writes
        # with the new owner's)
        with self._tick_lock:
            with self._lock:
                if not w.alive:
                    return
                w.alive = False
                self.failovers += 1
                self.metrics.inc("cluster_failovers_total")
                self._remove_worker_series(w.worker_id)
                for name in list(w.jobs):
                    job = self.jobs[name]
                    if job.partitions:
                        # the partition's LINEAGE survives in the
                        # shared store; _assign_pending re-adopts it
                        # (state + vnodes) on a free worker
                        for p in job.partitions.values():
                            if p.worker_id == w.worker_id:
                                p.worker_id = None
                    else:
                        job.worker_id = None
                w.jobs.clear()
                # allocated-but-never-sealed export keys become
                # vacuumable orphans; keys already riding a sealed
                # round stay protected in _pending_ssts
                pending = {s["key"] for ssts in
                           self._pending_ssts.values() for s in ssts}
                for key in w.sst_keys - pending:
                    self.hummock.release_external_sst_key(key)
                w.sst_keys.clear()
                if w.client is not None:
                    w.client.close()
                self._set_worker_gauges()

    # -- serving replicas: registry + epoch pin leases -------------------
    def rpc_register_serving(self, host: str, port: int,
                             pid: int | None = None) -> dict:
        """Register a serving replica and grant its FIRST epoch pin
        lease: the current manifest version is pinned meta-side BEFORE
        the grant leaves, so every SST the replica can reach stays in
        the vacuum keep-set from the very first read."""
        with self._lock:
            rid = self._next_replica
            self._next_replica += 1
            r = ServingReplicaInfo(rid, host, int(port), pid)
            # pooled connections: concurrent serving-read routers must
            # not serialize behind one in-flight batch frame
            r.client = RpcClient(host, int(port),
                                 timeout=self.rpc_timeout_s,
                                 src="meta", dst=f"serving{rid}",
                                 pool=4)
            pin_id, version = self.versions.pin()
            r.pins[version.vid] = pin_id
            r.granted_vid = version.vid
            self.serving[rid] = r
            self.metrics.set_gauge("cluster_serving_granted_vid",
                                   r.granted_vid, replica=str(rid))
            self._set_worker_gauges()
        self.hummock._update_gauges()
        return {
            "replica_id": rid,
            "granted_vid": r.granted_vid,
            "cluster_epoch": self.cluster_epoch,
            "manifest_epoch": self.versions.max_committed_epoch,
            "trace_ctx": list(self._last_round_ctx)
            if self._last_round_ctx else None,
        }

    def rpc_serving_heartbeat(self, replica_id: int,
                              vid: int = 0) -> dict:
        """One lease round-trip: the replica reports the manifest vid
        it HOLDS (acking older grants), the meta releases pins below
        it, pins the current version as the next grant, and returns
        the grant.  The replica only ever advances to granted vids, so
        its held version is pinned at all times — vacuum can never
        reap an SST under a live serving read."""
        with self._lock:
            r = self.serving.get(int(replica_id))
            if r is None or not r.alive:
                raise ValueError(
                    f"unknown or expired serving replica {replica_id}"
                )
            r.last_seen = time.monotonic()
            held = int(vid)
            pin_id, version = self.versions.pin()
            if version.vid in r.pins:
                self.versions.unpin(pin_id)
            else:
                r.pins[version.vid] = pin_id
            r.granted_vid = version.vid
            # keep exactly the held version and the fresh grant; every
            # pin in between was a grant the replica skipped past
            keep = {held, version.vid}
            for pv in [p for p in r.pins if p not in keep]:
                self.versions.unpin(r.pins.pop(pv))
            self.metrics.set_gauge(
                "cluster_serving_granted_vid", r.granted_vid,
                replica=str(r.replica_id),
            )
            self._set_worker_gauges()
        return {
            "ok": True,
            "granted_vid": r.granted_vid,
            "cluster_epoch": self.cluster_epoch,
            "manifest_epoch": self.versions.max_committed_epoch,
            # last committed round's root span ctx: the replica tags
            # its SAMPLED read spans with it, so each round trace
            # carries the reads served at that epoch
            "trace_ctx": list(self._last_round_ctx)
            if self._last_round_ctx else None,
        }

    def rpc_unregister_serving(self, replica_id: int) -> dict:
        with self._lock:
            r = self.serving.get(int(replica_id))
        if r is not None:
            self._on_serving_dead(r)
        return {"ok": True}

    # -- external SST allocation (worker MV exports) ---------------------
    def rpc_alloc_sst(self, worker_id: int) -> dict:
        """Allocate one vacuum-protected SST key for a worker's MV
        export upload (the single allocator keeps keys collision-free
        across worker processes)."""
        with self._lock:
            w = self.workers.get(int(worker_id))
            if w is None or not w.alive:
                raise ValueError(f"unknown or expired worker {worker_id}")
        key = self.hummock.alloc_external_sst_key()
        with self._lock:
            w.sst_keys.add(key)
        return {"key": key}

    # -- storage service (vacuum rides the meta) -------------------------
    def storage_vacuum(self) -> dict:
        """GC pass over the shared store: deletes SST objects
        unreferenced by the current version, any serving pin lease, or
        an in-flight allocation."""
        deleted = self.hummock.vacuum()
        return {"deleted_objects": deleted,
                "remaining_objects": self.hummock.stats()["objects"]}

    def rpc_storage_vacuum(self) -> dict:
        return self.storage_vacuum()

    # -- integrity: corruption reports, quarantine, self-healing repair --
    def _on_corruption(self, kind: str, key: str,
                       context: "dict | None" = None) -> None:
        """Sink for every meta-side detection point (scrub walk,
        compaction read).  Repairs run synchronously on the calling
        background thread — both are already off the latency path."""
        self.report_corruption(key, kind=kind,
                               reason=(context or {}).get("error", ""),
                               by="scrubber", sync=True)

    def rpc_report_corruption(self, key: str, kind: str = "sst",
                              reason: str = "", by: str = "") -> dict:
        """A peer (serving replica, compute worker) hit corrupt shared
        bytes: quarantine immediately, repair in the background so the
        reporter's read path is never blocked on the repair."""
        return self.report_corruption(key, kind=kind, reason=reason,
                                      by=by, sync=False)

    def report_corruption(self, key: str, kind: str = "sst",
                          reason: str = "", by: str = "",
                          sync: bool = True) -> dict:
        self.metrics.inc("integrity_errors_total", kind=kind)
        with self._repair_lock:
            if key in self._repairing:
                return {"ok": True, "repair": "in_progress"}
            self._repairing.add(key)

        def _run() -> dict:
            try:
                if kind in ("sst", "sst_block", "sst_footer"):
                    self.hummock.quarantine_sst(
                        key, reason or "reported", by=by or "report")
                    repaired = self._repair_sst(key)
                    cat = "sst"
                elif kind == "checkpoint":
                    repaired = self._repair_checkpoint(key)
                    cat = "checkpoint"
                else:
                    # manifest chain damage has no re-derivable source:
                    # durable note + loud metric, operator escalation
                    from risingwave_tpu.storage.integrity import (
                        quarantine,
                    )
                    quarantine(self.hummock.store, key,
                               reason or "manifest corruption",
                               by=by or "report",
                               metrics=self.metrics)
                    return {"ok": True, "repair": "quarantined"}
                if repaired is True:
                    with self._repair_lock:
                        self.repairs[cat] = self.repairs.get(cat, 0) + 1
                    self.metrics.inc("integrity_repairs_total",
                                     kind=cat)
                return {"ok": True,
                        "repair": "done" if repaired else "pending"}
            finally:
                with self._repair_lock:
                    self._repairing.discard(key)

        if sync:
            return _run()
        threading.Thread(target=_run, name="integrity-repair",
                         daemon=True).start()
        return {"ok": True, "repair": "scheduled"}

    def _mvs_overlapping(self, info) -> list[str]:
        """MV names whose storage key range intersects one SstInfo —
        the owners whose rows a corrupt export SST may carry."""
        from risingwave_tpu.serve.reader import mv_key_range

        out = []
        with self._lock:
            mvs = list(self._mv_to_job)
        for mv in mvs:
            lo, hi = mv_key_range(mv)
            if info.last_key >= lo and info.first_key < hi:
                out.append(mv)
        return out

    def _repair_sst(self, key: str) -> bool:
        """Self-heal one corrupt MV-export SST: every owning job's live
        worker re-exports the affected MVs IN FULL (diff base re-seeded
        from the manifest minus the corrupt object, so shadowed
        tombstones re-emit), then ONE version delta atomically swaps
        the corrupt SST for the fresh exports — readers never see a
        window with the rows missing.  Owners that are dead/unassigned
        leave the repair pending; the next scrub cycle retries."""
        with self._tick_lock:
            v = self.hummock.versions.current
            info = next((s for lv in v.levels for s in lv
                         if s.key == key), None)
            if info is None:
                # already swapped out (or never committed): nothing to
                # repair — truthy so the caller stops retrying, but
                # distinct so it is not COUNTED as a repair
                return "noop"
            jobs = sorted({self._mv_to_job[m]
                           for m in self._mvs_overlapping(info)
                           if m in self._mv_to_job})
            targets: list = []
            with self._lock:
                for jname in jobs:
                    job = self.jobs.get(jname)
                    if job is None:
                        continue
                    units = list(job.partitions.values()) \
                        if job.partitions else [job]
                    for u in units:
                        if getattr(u, "retiring", False):
                            continue
                        w = self.workers.get(u.worker_id) \
                            if u.worker_id is not None else None
                        if w is None or not w.alive:
                            return False  # owner mid-failover: retry
                        targets.append((jname, w))
            from risingwave_tpu.storage.hummock.version import SstInfo

            fresh: list[SstInfo] = []
            for jname, w in targets:
                try:
                    res = self.retry.run(
                        lambda w=w, jname=jname: w.client.call(
                            "reexport", job=jname, exclude=[key]),
                        label="reexport",
                    )
                except (RpcError, ConnectionError, OSError):
                    return False  # keep the corrupt SST until healed
                for s in res.get("ssts") or []:
                    fresh.append(SstInfo(
                        key=s["key"],
                        first_key=bytes.fromhex(s["first_key"]),
                        last_key=bytes.fromhex(s["last_key"]),
                        n_records=int(s["n_records"]),
                        size=int(s["size"]),
                    ))
            self.hummock.replace_sst(key, fresh)
            return True

    def _repair_checkpoint(self, key: str) -> bool:
        """Route a corrupt checkpoint epoch object to its OWNING worker
        for lineage repair (quarantine + truncate to the last verified
        epoch — the worker holds the manifest lock for its own
        commits).  An ownerless lineage self-heals at its next
        adoption: the verified load rewinds past the corruption."""
        lineage = key.split("/epoch_")[0].split("@spill")[0]
        with self._lock:
            target = None
            for j in self.jobs.values():
                if j.partitions:
                    p = j.partitions.get(lineage)
                    if p is not None and p.worker_id is not None:
                        target = (self.workers.get(p.worker_id), j.name)
                elif j.name == lineage and j.worker_id is not None:
                    target = (self.workers.get(j.worker_id), j.name)
        if target is None or target[0] is None or not target[0].alive:
            return False
        w, _jname = target
        try:
            res = self.retry.run(
                lambda: w.client.call("repair_checkpoint",
                                      lineage=lineage),
                label="repair_checkpoint",
            )
        except (RpcError, ConnectionError, OSError):
            return False
        return bool(res.get("ok"))

    def _drain_corrupt_reports(self) -> None:
        """Repair corrupt SSTs workers surfaced in barrier responses
        (collected under the tick lock, repaired outside it)."""
        with self._lock:
            due, self._corrupt_reports = self._corrupt_reports, []
        for key in due:
            self.report_corruption(key, kind="sst",
                                   reason="worker export seam",
                                   by="worker", sync=True)

    def rpc_cluster_scrub(self) -> dict:
        return self.cluster_scrub()

    def cluster_scrub(self) -> dict:
        """``ctl cluster scrub``: ONE full synchronous scrub cycle over
        every pinned-version SST and retained checkpoint lineage, with
        the quarantine/repair pipeline armed — plus the integrity
        bookkeeping an operator needs."""
        from risingwave_tpu.storage.integrity import quarantine_list

        report = self.scrubber.run_once()
        report["quarantined"] = [
            n.get("key") for n in quarantine_list(self.hummock.store)
        ]
        if self.scrubber.ckpt_store is not None:
            # checkpoint quarantine notes live in the checkpoint root
            # (written by the owning worker's lineage repair)
            report["quarantined"] += [
                n.get("key")
                for n in quarantine_list(self.scrubber.ckpt_store)
            ]
        with self._repair_lock:
            report["repairs"] = dict(self.repairs)
        return report

    # -- DDL / placement -------------------------------------------------
    def rpc_execute_ddl(self, sql: str) -> dict:
        return self.execute_ddl(sql)

    def execute_ddl(self, sql: str, replay: bool = False) -> dict:
        """Apply one or more statements at the cluster level: job DDL
        places a streaming job, everything else joins the prelude all
        future jobs replay.  ``replay=True`` (meta crash recovery)
        rebuilds the in-memory catalog from the already-durable log:
        nothing is re-appended, no worker is called, no job assigned
        (workers re-register and re-adopt on their own schedule)."""
        from risingwave_tpu.sql import ast
        from risingwave_tpu.sql.parser import parse_with_text

        placed: list[str] = []
        for text, stmt in parse_with_text(sql):
            if isinstance(stmt, (ast.CreateMaterializedView,
                                 ast.CreateSink)):
                self._place_job(text, stmt.name, replay=replay)
                placed.append(stmt.name)
            elif isinstance(stmt, ast.CreateIndex):
                # a secondary-index MV rides its upstream's job (the
                # engine attaches it MV-on-MV and exports it into the
                # shared serving keyspace like any MV)
                self._place_job(text, stmt.name, replay=replay,
                                upstream_mv=stmt.table)
                self._indexes[stmt.name] = stmt.table
                placed.append(stmt.name)
            elif isinstance(stmt, ast.DropStatement) \
                    and stmt.kind in ("materialized view", "index"):
                self._drop_mv(text, stmt, replay=replay)
            elif isinstance(stmt, (ast.Insert, ast.Delete,
                                   ast.Update)):
                # never reaches the DDL log; forwarded rows (marked
                # marker-tail for DELETE; UPDATE desugars to the
                # retraction pair on the owning worker) live in the
                # workers' durable table history + checkpoints
                if not replay:
                    self._forward_dml(text, stmt.table)
            else:
                if not replay:
                    self.store.append_ddl(text)
                self.prelude.append(text)
        return {"ok": True, "placed": placed,
                "cluster_epoch": self.cluster_epoch}

    def _co_located_job(self, text: str) -> "JobInfo | None":
        """MV-on-MV placement: a query referencing an existing MV must
        land on that MV's job (the engine attaches it to the same
        DagJob there)."""
        import re

        for mv, jname in self._mv_to_job.items():
            if re.search(rf"\b{re.escape(mv)}\b", text):
                # partitioned upstreams attach too (Exchange-lite):
                # every partition worker adopts the same delta; the
                # engine validates the attach-edge exchange is the
                # identity choreography and refuses reduced-key shapes
                return self.jobs[jname]
        return None

    def _place_job(self, text: str, name: str,
                   replay: bool = False,
                   upstream_mv: str | None = None) -> None:
        import re

        if name in self._mv_to_job:
            raise ValueError(f"{name!r} already exists")
        if upstream_mv is not None:
            # an index ALWAYS co-locates onto its upstream's job
            # (validated BEFORE the durable append so a refused
            # statement can never poison the replay log)
            if upstream_mv not in self._mv_to_job:
                raise ValueError(
                    f"CREATE INDEX on {upstream_mv!r}: "
                    f"{upstream_mv!r} does not exist"
                )
            upstream = self.jobs[self._mv_to_job[upstream_mv]]
            if upstream.partitions:
                raise ValueError(
                    f"CREATE INDEX over partitioned job "
                    f"{upstream.name!r}: next round (attach would "
                    "need a cross-partition exchange)"
                )
        else:
            upstream = self._co_located_job(text)
        if not replay:
            self.store.append_ddl(text)
        if upstream is not None:
            # ship only the prelude delta the job hasn't seen yet plus
            # the new statement; the worker attaches it to the live job
            sent = len(upstream.ddl) - len(upstream.mvs)
            delta = self.prelude[sent:] + [text]
            if upstream.partitions:
                # partitioned upstream: EVERY partition worker attaches
                # the same chain (the engine's _plan_partition_attach
                # proves the attach edge needs no cross-partition row
                # movement).  Probe the FIRST partition before
                # mutating any meta state — a refused plan must leave
                # the catalog (and the durable log position) untouched
                with self._lock:
                    ws = [self.workers[p.worker_id]
                          for p in upstream.partitions.values()
                          if p.worker_id is not None
                          and not p.retiring]
                if not replay:
                    if not ws:
                        raise ValueError(
                            f"MV-on-MV over {upstream.name!r}: no "
                            "live partition worker to attach on"
                        )
                    self.retry.run(
                        lambda: ws[0].client.call(
                            "adopt", ddl=delta, name=upstream.name,
                            recover=False),
                        label="adopt",
                    )
                    for w in ws[1:]:
                        self.retry.run(
                            lambda w=w: w.client.call(
                                "adopt", ddl=delta,
                                name=upstream.name, recover=False),
                            label="adopt",
                        )
                upstream.ddl.extend(delta)
                upstream.mvs.append(name)
                with self._lock:
                    self._mv_to_job[name] = upstream.name
                    up_mv = next(
                        (m for m in self._mv_to_job
                         if m != name and re.search(
                             rf"\b{re.escape(m)}\b", text)
                         and self._mv_to_job[m] == upstream.name),
                        upstream.name,
                    )
                    upstream.attach_edges.append((up_mv, name))
                self._push_routing()
                return
            upstream.ddl.extend(delta)
            upstream.mvs.append(name)
            with self._lock:
                self._mv_to_job[name] = upstream.name
            if not replay and upstream.worker_id is not None:
                w = self.workers[upstream.worker_id]
                self.retry.run(
                    lambda: w.client.call("adopt", ddl=delta,
                                          name=upstream.name,
                                          recover=False),
                    label="adopt",
                )
            return
        job = JobInfo(name=name, ddl=list(self.prelude) + [text],
                      mvs=[name])
        # a job created after commits joins at the current round: it
        # seals the NEXT round with everyone else
        job.rounds = self.cluster_epoch
        with self._lock:
            self.jobs[name] = job
            self._mv_to_job[name] = name
            self._set_worker_gauges()
        if not replay:
            self._assign_pending()

    def _drop_mv(self, text: str, stmt, replay: bool = False) -> None:
        """DROP MATERIALIZED VIEW / DROP INDEX at the cluster level:
        the owning worker drops it from its engine (the DROP also
        joins ``job.ddl`` so future adopts replay it), the meta
        unplaces it (last MV ⇒ the job leaves the round protocol),
        writes TOMBSTONES for every exported row in one delta, and
        deletes the serve-schema doc — serving answers "does not
        exist" instead of stale rows (ROADMAP round-8 follow-up).

        Ordering matters for replicas: schema docs are rewritten
        BEFORE the tombstone delta commits, so a replica pinned at a
        pre-drop version still sees consistent doc+data, and one that
        refreshes past the tombstones reloads the rewritten docs
        (its schema cache clears on every vid advance)."""
        import json as _json

        from risingwave_tpu.serve.reader import (
            mv_key_range,
            schema_key,
        )
        from risingwave_tpu.storage.hummock.object_store import (
            ObjectError,
        )

        name = stmt.name
        with self._lock:
            jname = self._mv_to_job.get(name)
        if jname is None:
            if stmt.if_exists:
                return
            raise ValueError(f"{name!r} does not exist")
        if stmt.kind == "index" and name not in self._indexes:
            raise ValueError(f"{name!r} is not an index")
        deps = sorted(ix for ix, mv in self._indexes.items()
                      if mv == name)
        if deps:
            raise ValueError(
                f"cannot drop {name!r}: indexes {deps} depend on it "
                "(DROP INDEX first)"
            )
        if not replay:
            self.store.append_ddl(text)
        with self._tick_lock:
            with self._lock:
                job = self.jobs[jname]
                w = self.workers.get(job.worker_id) \
                    if job.worker_id is not None else None
            job.ddl.append(text)
            if not replay and w is not None and w.alive:
                self.retry.run(
                    lambda: w.client.call("execute", sql=text),
                    label="drop",
                )
            with self._lock:
                if name in job.mvs:
                    job.mvs.remove(name)
                self._mv_to_job.pop(name, None)
                upstream_of = self._indexes.pop(name, None)
                if not job.mvs:
                    # last MV gone: the job leaves the round protocol
                    self.jobs.pop(jname, None)
                    self._pending_ssts.pop(jname, None)
                    if w is not None:
                        w.jobs.discard(jname)
                self._set_worker_gauges()
            if replay:
                return  # storage already holds the tombstones
            if upstream_of is not None:
                # the upstream's doc must stop advertising the index
                # BEFORE its rows are tombstoned (a replica reloading
                # the doc post-tombstone must not plan through it)
                try:
                    doc = _json.loads(
                        self.hummock.store.get(schema_key(upstream_of))
                    )
                    doc["indexes"] = [
                        e for e in doc.get("indexes", [])
                        if e.get("name") != name
                    ]
                    if not doc["indexes"]:
                        doc.pop("indexes")
                    self.hummock.store.put(
                        schema_key(upstream_of),
                        _json.dumps(doc).encode(),
                    )
                except ObjectError:
                    pass  # upstream never exported
            try:
                self.hummock.store.delete(schema_key(name))
            except ObjectError:
                pass  # never exported
            lo, hi = mv_key_range(name)
            keys = [k for k, _ in self.hummock.scan(lo, hi)]
            if keys:
                self.hummock.delete_batch(
                    keys, epoch=self.versions.max_committed_epoch
                )
            self.metrics.inc("cluster_mv_drops_total")

    def _forward_dml(self, text: str, table: str) -> None:
        """INSERTs fan out to every worker whose catalog has the table
        (each job's private reader consumes its worker-local history —
        the same per-job readers a single node plans).  A table a
        PARTITIONED job reads routes to its ingest LEADER instead —
        the leader fans the position-stamped batch out worker↔worker,
        so the meta stays one control hop, never the data path."""
        self.metrics.inc("cluster_dml_forward_total")
        leader = self._table_leader(table)
        if leader is not None:
            with self._lock:
                w = self.workers.get(leader)
            if w is None or not w.alive:
                raise ValueError(
                    f"INSERT into {table!r}: ingest leader "
                    f"{leader} is not live"
                )
            w.client.call("execute", sql=text)
            self.store.append_dml_sql(text)
            return
        delivered = 0
        for w in self.live_workers():
            try:
                w.client.call("execute", sql=text)
                delivered += 1
            except RpcError as e:
                # a worker without the table answers KeyError("relation
                # ... does not exist") — that worker just isn't a host
                if "does not exist" in str(e):
                    continue
                raise
            except (ConnectionError, OSError):
                continue  # heartbeat monitor will expire it
        if delivered == 0:
            raise ValueError(
                f"INSERT into {table!r}: no live worker has the table "
                "(create it and place a job first)"
            )
        # durable only once at least one host accepted it (rejected
        # statements must not resurrect at replay)
        self.store.append_dml_sql(text)

    def _assign_pending(self) -> None:
        """Place pending barrier units: unassigned vnode PARTITIONS
        re-adopt their checkpoint lineage on a free worker (failover /
        meta restart — state AND vnode ownership follow the lineage),
        fresh jobs take partitioned placement over the vnode map when
        the scale plane is on and the plan is eligible, and everything
        else lands whole on the least-loaded live worker.  ONE
        assigner at a time: concurrent assigners (monitor + DDL path)
        would interleave adopt probes and desynchronize worker-side
        checkpoint lineages from the registry."""
        with self._assign_lock:
            self._assign_pending_locked()

    def _assign_pending_locked(self) -> None:
        while True:
            with self._lock:
                live = [w for w in self.workers.values() if w.alive]
                part_pending = [
                    (j, p) for j in self.jobs.values() if j.partitions
                    for p in j.partitions.values()
                    if p.worker_id is None and not p.retiring
                ]
                job_pending = [j for j in self.jobs.values()
                               if j.partitions is None
                               and j.worker_id is None]
                if not live or not (part_pending or job_pending):
                    return
            if part_pending:
                res = self._assign_partition(*part_pending[0])
                if res == "no_host":
                    # no spare worker can host the dead partition's
                    # lineage: merge its vnodes into a survivor via
                    # the scale-in slice-transplant path instead of
                    # stalling the round forever
                    if self._merge_dead_partition(*part_pending[0]):
                        continue
                    return
                if not res:
                    return
                continue
            job = job_pending[0]
            if self.scale_partitioning:
                placed = self._try_partition_place(job)
                if placed:
                    continue
                with self._lock:
                    if job.worker_id is not None or job.partitions:
                        continue
            with self._lock:
                live = [w for w in self.workers.values() if w.alive]
                if not live:
                    return
                # capacity follows the ACTIVE set once a map was cut
                if self.active_workers:
                    active = [w for w in live
                              if w.worker_id in self.active_workers]
                    live = active or live
                target = min(live,
                             key=lambda w: (len(w.jobs), w.worker_id))
            try:
                # adopt is idempotent (already-present DDL is skipped,
                # recovery rewinds to the same durable epoch) — safe to
                # retry through transient drops
                res = self.retry.run(
                    lambda: target.client.call(
                        "adopt", ddl=job.ddl, name=job.name,
                        recover=True,
                    ),
                    label="adopt",
                )
            except (RpcError, ConnectionError, OSError):
                # adoption failed: leave unassigned; the monitor loop
                # retries (and may expire the worker first)
                return
            recovered = int(res.get("committed_epoch", 0))
            with self._lock:
                if job.worker_id is not None:
                    continue  # raced with another assigner
                job.worker_id = target.worker_id
                target.jobs.add(job.name)
                self._rewind_job(job, recovered)

    def _assign_partition(self, job: JobInfo,
                          p: "PartitionInfo") -> bool:
        """Re-adopt one unassigned partition's LINEAGE on a live
        worker not already hosting this job: the worker recovers the
        partition's state + cursors from the shared checkpoint store
        and the vnode map re-points — failover is lineage migration,
        no state is recomputed."""
        with self._lock:
            taken = {q.worker_id for q in job.partitions.values()
                     if q.worker_id is not None}
            cands = [w for w in self.workers.values()
                     if w.alive and w.worker_id not in taken]
            if not cands:
                return "no_host"  # every live worker already hosts one
            target = min(cands, key=lambda w: (len(w.jobs),
                                               w.worker_id))
        try:
            res = self.retry.run(
                lambda: target.client.call(
                    "adopt", ddl=job.ddl, name=job.name,
                    recover=True, vnodes=sorted(p.vnodes),
                    n_vnodes=self.n_vnodes, ckpt_key=p.lineage,
                ),
                label="adopt",
            )
        except (RpcError, ConnectionError, OSError):
            return False
        if not res.get("partitioned"):
            return False  # deterministic plans: should not happen
        with self._lock:
            if p.worker_id is not None:
                return True  # raced
            p.worker_id = target.worker_id
            target.jobs.add(job.name)
            if self.vnode_map is not None:
                for v in p.vnodes:
                    self.vnode_map[v] = target.worker_id
            if res.get("dml_tables"):
                job.dml_tables = list(res["dml_tables"])
            if res.get("shuffle_cols"):
                job.shuffle_cols = {
                    t: (int(c) if c is not None else None)
                    for t, c in res["shuffle_cols"].items()
                }
            if res.get("edge_kinds"):
                job.edge_kinds = dict(res["edge_kinds"])
            self._rewind_job(p, int(res.get("committed_epoch", 0)))
        self._push_routing()
        self._set_vnode_gauges()
        return True

    def _merge_dead_partition(self, job: JobInfo,
                              p: "PartitionInfo") -> bool:
        """Merge-failover (the ROADMAP remaining item): a partitioned
        job's worker died and NO spare worker can host its lineage —
        instead of stalling the round forever, merge the dead
        partition's vnodes into a surviving partition through the
        scale-in slice-transplant path: the recipient rewinds to its
        own checkpoint at the last COMMITTED round, transplants the
        dead lineage's slice at that same round (all partitions sealed
        it durably — the commit required the acks), and widens its
        mask.  Capacity shrinks; correctness doesn't."""
        # non-blocking tick-lock acquire: a scale op mid-flight calls
        # _assign_pending with the lock held — defer to the monitor's
        # next pass rather than deadlocking
        if not self._tick_lock.acquire(blocking=False):
            return False
        try:
            round_c = self.cluster_epoch
            if round_c <= 0:
                return False
            with self._lock:
                epoch_p = next((e for r, e in reversed(p.seal_log)
                                if r == round_c), None)
                cands = [
                    q for q in job.partitions.values()
                    if q is not p and not q.retiring
                    and q.worker_id is not None
                    and (w := self.workers.get(q.worker_id)) is not None
                    and w.alive
                ]
                if not cands:
                    return False
                q = min(cands, key=lambda x: (len(x.vnodes), x.lineage))
                epoch_q = next((e for r, e in reversed(q.seal_log)
                                if r == round_c), None)
                w = self.workers[q.worker_id]
            if epoch_q is None or (p.vnodes and epoch_p is None):
                return False
            merged = sorted(set(q.vnodes) | set(p.vnodes))
            transfers = [{"ckpt": p.lineage, "epoch": epoch_p,
                          "vnodes": sorted(p.vnodes)}] if p.vnodes \
                else []
            try:
                self.retry.run(
                    lambda: w.client.call(
                        "repartition", job=job.name, vnodes=merged,
                        transfers=transfers, rewind_epoch=epoch_q,
                    ),
                    label="repartition",
                )
            except (RpcError, ConnectionError, OSError):
                return False
            with self._lock:
                q.vnodes = merged
                # the recipient rewound to the committed round: drop
                # any later (uncommitted) seal so the next round
                # re-seals against the merged state
                q.seal_log = [(r, e) for r, e in q.seal_log
                              if r <= round_c]
                q.rounds = round_c
                q.durable_epoch = epoch_q
                job.partitions.pop(p.lineage, None)
                if self.vnode_map is not None:
                    for v in p.vnodes:
                        self.vnode_map[v] = q.worker_id
                    self.active_workers = sorted(set(self.vnode_map))
                self.metrics.inc("cluster_merge_failovers_total")
            self._log_scale_event()
            self._push_routing()
            self._set_vnode_gauges()
            return True
        finally:
            self._tick_lock.release()

    def _try_partition_place(self, job: JobInfo) -> bool:
        """Fresh partitioned placement: adopt one partition per vnode
        map owner.  The FIRST owner probes plan eligibility — a
        refusal falls back to whole-job placement on that worker (the
        job is already adopted there)."""
        from risingwave_tpu.cluster.scale.vnode import (
            initial_map,
            owned_vnodes,
        )

        with self._lock:
            if job.partitions is not None or job.worker_id is not None:
                return True  # raced with another assigner
            live = {w.worker_id: w for w in self.workers.values()
                    if w.alive}
            if not live:
                return False
            if self.vnode_map is None:
                self.active_workers = sorted(live)
                self.vnode_map = initial_map(self.active_workers,
                                             self.n_vnodes)
            owners = sorted(set(self.vnode_map))
            if any(o not in live for o in owners):
                return False  # owner mid-failover: retry later
            vmap = list(self.vnode_map)
        placements = []
        for wid in owners:
            with self._lock:
                lineage = f"{job.name}::p{self._next_lineage}"
                self._next_lineage += 1
            placements.append((wid, lineage, owned_vnodes(vmap, wid)))
        first_wid, first_lineage, first_vns = placements[0]
        first_w = live[first_wid]
        try:
            res = self.retry.run(
                lambda: first_w.client.call(
                    "adopt", ddl=job.ddl, name=job.name,
                    recover=False, vnodes=first_vns,
                    n_vnodes=self.n_vnodes, ckpt_key=first_lineage,
                ),
                label="adopt",
            )
        except (RpcError, ConnectionError, OSError):
            return False
        if not res.get("partitioned"):
            # plan not scale-eligible: the probe adoption IS a valid
            # whole-job placement — keep it
            with self._lock:
                job.worker_id = first_wid
                first_w.jobs.add(job.name)
            return True
        with self._lock:
            if job.partitions is not None:
                return True  # raced: the other assigner's layout wins
            job.partitions = {
                first_lineage: PartitionInfo(
                    lineage=first_lineage, worker_id=first_wid,
                    vnodes=list(first_vns), rounds=self.cluster_epoch,
                )
            }
            job.dml_tables = list(res.get("dml_tables") or [])
            job.shuffle_cols = {
                t: (int(c) if c is not None else None)
                for t, c in (res.get("shuffle_cols") or {}).items()
            }
            job.edge_kinds = dict(res.get("edge_kinds") or {})
            first_w.jobs.add(job.name)
        for wid, lineage, vns in placements[1:]:
            w = live[wid]
            with self._lock:
                job.partitions[lineage] = PartitionInfo(
                    lineage=lineage, worker_id=None,
                    vnodes=list(vns), rounds=self.cluster_epoch,
                )
            try:
                self.retry.run(
                    lambda w=w, vns=vns, lineage=lineage:
                    w.client.call(
                        "adopt", ddl=job.ddl, name=job.name,
                        recover=False, vnodes=vns,
                        n_vnodes=self.n_vnodes, ckpt_key=lineage,
                    ),
                    label="adopt",
                )
            except (RpcError, ConnectionError, OSError):
                continue  # stays unassigned; _assign_pending retries
            with self._lock:
                job.partitions[lineage].worker_id = wid
                job.partitions[lineage].rounds = self.cluster_epoch
                w.jobs.add(job.name)
        self._log_scale_event()
        self._push_routing()
        self._set_vnode_gauges()
        return True

    def _rewind_job(self, job: JobInfo, epoch: int) -> None:
        """Translate a recovered committed epoch back into the round
        the job actually reached (its checkpoint may include a round
        meta never saw acknowledged)."""
        # the recovered epoch IS durable (adoption loads the manifest)
        job.durable_epoch = max(epoch, 0)
        epochs = [e for _, e in job.seal_log]
        if epoch <= 0:
            # no durable checkpoint: the job replays every round it
            # was credited with (fresh state, sources at zero)
            if job.seal_log:
                job.rounds = job.seal_log[0][0] - 1
            else:
                job.rounds = min(job.rounds, self.cluster_epoch)
            job.seal_log = []
            return
        i = bisect.bisect_right(epochs, epoch)
        if i > 0 and epochs[i - 1] == epoch:
            job.seal_log = job.seal_log[:i]
            job.rounds = job.seal_log[-1][0]
        elif i == len(epochs):
            # sealed + checkpointed, died before acking: credit the
            # in-flight round
            round_ = (job.seal_log[-1][0] + 1) if job.seal_log \
                else job.rounds + 1
            job.seal_log.append((round_, epoch))
            job.rounds = round_
        else:
            # an epoch meta never recorded, older than later seals —
            # cannot happen with meta-serialized rounds; resync hard
            job.seal_log = job.seal_log[:i]
            job.rounds = job.seal_log[-1][0] if job.seal_log else 0

    # -- the elastic scale plane ------------------------------------------
    def rpc_cluster_scale(self, n: int) -> dict:
        return self.scale(int(n))

    def scale(self, n: int) -> dict:
        """``ctl cluster scale N``: resize the ACTIVE worker set to the
        N lowest-id live workers and rebalance the vnode map minimally
        (only moved vnodes — and the state behind them — transfer).

        Protocol, under the tick lock (no rounds in flight):

        1. drive one COMMITTED round — every partition is sealed AND
           durable at the handover epoch, and since nothing runs
           between that commit and the handover, live state == the
           checkpoint at that epoch everywhere;
        2. compute the new map (``scale.vnode.rebalance``: ±1
           balanced, minimal movement, deterministic);
        3. per partitioned job: recipients transplant each donor's
           checkpoint SLICE (only moved vnodes leave disk), donors
           narrow their gate mask, empty donors are released;
        4. durably log the scale event, re-push peer routing;
        5. drive one more committed round so serving pins (and their
           pinned vnode sets) move past the handover — reads stay
           zero-error throughout.

        Retry-safe: a failed handover leaves the map uncut; re-running
        ``scale`` re-applies the same transfers against the same
        checkpoints."""
        with self._tick_lock:
            return self._scale_locked(int(n))

    def _scale_locked(self, n: int) -> dict:
        from risingwave_tpu.cluster.scale.vnode import (
            initial_map,
            moved_vnodes,
            rebalance,
        )

        with self._lock:
            live = sorted(w.worker_id for w in self.workers.values()
                          if w.alive)
        if n < 1 or n > len(live):
            raise ValueError(
                f"scale {n}: cluster has {len(live)} live workers "
                "(register more first)"
            )
        active = live[:n]
        if self.vnode_map is None:
            # first scale cuts the initial map; jobs placed afterwards
            # partition over it
            self.scale_partitioning = True
            self.vnode_map = initial_map(active, self.n_vnodes)
            self.active_workers = active
            self._log_scale_event()
            self._push_routing()
            self._set_vnode_gauges()
            return {"active": active, "moved_vnodes": 0,
                    "map_initialized": True}
        # 1. the handover anchor round
        self._drive_committed_round()
        handover_round = self.cluster_epoch
        old_map = list(self.vnode_map)
        new_map = rebalance(old_map, active, self.n_vnodes)
        moved = moved_vnodes(old_map, new_map)
        transfers = []
        with self._lock:
            part_jobs = [j for j in self.jobs.values() if j.partitions]
        for job in part_jobs:
            transfers += self._handover_job(job, new_map, moved,
                                            handover_round)
        self.vnode_map = new_map
        self.active_workers = active
        self.scale_ops += 1
        moved_count = sum(len(v) for v in moved.values())
        self.metrics.inc("cluster_scale_ops_total")
        self.metrics.inc("cluster_scale_moved_vnodes_total",
                         moved_count)
        self._log_scale_event()
        self._push_routing()
        self._set_vnode_gauges()
        # 2. move whole (non-partitioned) jobs off inactive workers
        self._evacuate_inactive(set(active))
        # 3. serving pins move past the handover
        post = self._drive_committed_round()
        return {
            "active": active,
            "handover_round": handover_round,
            "committed_round": post["cluster_epoch"],
            "moved_vnodes": moved_count,
            "moved": {f"{s}>{d}": len(v)
                      for (s, d), v in moved.items()},
            "transfers": transfers,
        }

    def _drive_committed_round(self, timeout_s: float = 120.0) -> dict:
        deadline = time.monotonic() + timeout_s
        while True:
            res = self._tick_locked(1)
            if res["committed"] or res.get("units", res["jobs"]) == 0:
                return res
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"scale: round {res['round']} never committed "
                    f"({res['sealed']}/{res.get('units')} sealed)"
                )
            time.sleep(0.05)

    def _handover_job(self, job: JobInfo, new_map: list[int],
                      moved: dict, handover_round: int) -> list[dict]:
        """Apply one scale step to a partitioned job: transplant moved
        slices into recipients (existing partitions merge in place;
        fresh workers adopt a NEW lineage built purely from
        transfers), then narrow/release donors."""
        from risingwave_tpu.cluster.scale.vnode import owned_vnodes

        with self._lock:
            by_worker = {p.worker_id: p
                         for p in job.partitions.values()
                         if not p.retiring}
            seal_at = {}
            for p in by_worker.values():
                if not p.seal_log \
                        or p.seal_log[-1][0] != handover_round:
                    raise RuntimeError(
                        f"scale: partition {p.lineage} not sealed at "
                        f"round {handover_round}"
                    )
                seal_at[p.worker_id] = p.seal_log[-1][1]
        gains: dict[int, list] = {}
        for (src, dst), vns in moved.items():
            if src not in by_worker:
                continue  # vnode owned by a worker without this job
            gains.setdefault(dst, []).append((src, vns))
        stats = []
        for dst, srcs in gains.items():
            new_set = owned_vnodes(new_map, dst)
            xfers = [{"ckpt": by_worker[src].lineage,
                      "epoch": seal_at[src], "vnodes": vns}
                     for src, vns in srcs]
            with self._lock:
                w = self.workers.get(dst)
            if w is None or not w.alive:
                raise RuntimeError(f"scale: recipient {dst} is dead")
            p = by_worker.get(dst)
            if p is None:
                with self._lock:
                    lineage = f"{job.name}::p{self._next_lineage}"
                    self._next_lineage += 1
                self.retry.run(
                    lambda: w.client.call(
                        "adopt", ddl=job.ddl, name=job.name,
                        recover=False, vnodes=[],
                        n_vnodes=self.n_vnodes, ckpt_key=lineage,
                    ),
                    label="adopt",
                )
                p = PartitionInfo(lineage=lineage, worker_id=dst,
                                  rounds=handover_round)
                with self._lock:
                    job.partitions[lineage] = p
                    w.jobs.add(job.name)
            res = self.retry.run(
                lambda: w.client.call(
                    "repartition", job=job.name, vnodes=new_set,
                    transfers=xfers,
                ),
                label="repartition",
            )
            with self._lock:
                p.vnodes = list(new_set)
            stats.append({"job": job.name, "worker": dst,
                          "gained": sum(len(v) for _, v in srcs),
                          "entries": sum(t["entries"]
                                         for t in res["transfers"]),
                          "transfers": res["transfers"]})
        # donors narrow (or RETIRE: keep serving pre-handover pins
        # until the post-handover commit publishes the new serve plan)
        donor_ids = {src for (src, _dst) in moved if src in by_worker}
        for src in sorted(donor_ids):
            p = by_worker[src]
            new_set = owned_vnodes(new_map, src)
            with self._lock:
                w = self.workers.get(src)
            if not new_set:
                with self._lock:
                    p.retiring = True
                continue
            if w is None or not w.alive:
                raise RuntimeError(f"scale: donor {src} is dead")
            self.retry.run(
                lambda: w.client.call(
                    "repartition", job=job.name, vnodes=new_set,
                    transfers=[],
                ),
                label="repartition",
            )
            with self._lock:
                p.vnodes = list(new_set)
        return stats

    def _evacuate_inactive(self, active: set[int]) -> None:
        """Whole-job placements follow capacity too: jobs on workers
        outside the active set go back to pending and re-adopt (from
        their durable checkpoint) on an active worker."""
        with self._lock:
            for job in self.jobs.values():
                if job.partitions is not None \
                        or job.worker_id is None \
                        or job.worker_id in active:
                    continue
                w = self.workers.get(job.worker_id)
                if w is not None:
                    w.jobs.discard(job.name)
                job.worker_id = None
        self._assign_pending()

    def _log_scale_event(self) -> None:
        """Durably record the scale plane's layout (map + partition
        lineages) — a restarted meta replays the tail event and
        re-adopts every lineage (see ``_recover_from_store``)."""
        with self._lock:
            ev = {
                "round": self.cluster_epoch,
                "n_vnodes": self.n_vnodes,
                "map": list(self.vnode_map or []),
                "active": list(self.active_workers),
                "next_lineage": self._next_lineage,
                "partitions": {
                    j.name: [{"lineage": p.lineage,
                              "vnodes": list(p.vnodes)}
                             for p in j.partitions.values()
                             if not p.retiring]
                    for j in self.jobs.values() if j.partitions
                },
                "dml_tables": {
                    j.name: list(j.dml_tables)
                    for j in self.jobs.values() if j.partitions
                },
                "shuffle_cols": {
                    j.name: dict(j.shuffle_cols)
                    for j in self.jobs.values() if j.partitions
                },
                "edge_kinds": {
                    j.name: dict(j.edge_kinds)
                    for j in self.jobs.values() if j.partitions
                },
                "attach_edges": {
                    j.name: [list(e) for e in j.attach_edges]
                    for j in self.jobs.values() if j.partitions
                },
            }
        self.store.append_scale_event(ev)

    def _push_routing(self) -> None:
        """Push the placement choreography to every live worker: peer
        addresses, per-replicated-table hosts + ingest leader, AND the
        compiled Exchange-lite choreography (per-table shuffle key,
        vnode slices, standby, edge specs).  The per-chunk exchange
        then flows worker↔worker — the meta's only involvement with
        the data path is this control push (compile once, execute
        forever: the Suki discipline)."""
        from risingwave_tpu.cluster.exchange import ExchangePlanner

        with self._lock:
            self._routing_version += 1
            version = self._routing_version
            peers = {w.worker_id: [w.host, w.port]
                     for w in self.workers.values() if w.alive}
            tables: dict[str, dict] = {}
            plan_jobs: list[dict] = []
            for j in self.jobs.values():
                if not j.partitions:
                    continue
                hosts = sorted({p.worker_id
                                for p in j.partitions.values()
                                if p.worker_id is not None})
                if not hosts:
                    continue
                for t in j.dml_tables:
                    cur = tables.setdefault(
                        t, {"leader": hosts[0], "hosts": []}
                    )
                    cur["hosts"] = sorted(set(cur["hosts"]) | set(hosts))
                    cur["leader"] = min(cur["hosts"])
                owners: dict[int, list] = {}
                for p in j.partitions.values():
                    if p.worker_id is not None and not p.retiring:
                        owners.setdefault(p.worker_id, [])
                        owners[p.worker_id] = sorted(
                            set(owners[p.worker_id]) | set(p.vnodes)
                        )
                plan_jobs.append({
                    "name": j.name,
                    "dml_tables": list(j.dml_tables),
                    "shuffle_cols": dict(j.shuffle_cols)
                    if self.shuffle_ingest else {},
                    "kinds": dict(j.edge_kinds),
                    "attach_edges": list(j.attach_edges),
                    "owners": owners,
                })
            targets = [w for w in self.workers.values() if w.alive]
        choreo = ExchangePlanner.compile(
            plan_jobs, self.n_vnodes, version=version
        ).to_doc()
        self._choreography = choreo
        for w in targets:
            try:
                w.client.call("update_routing", version=version,
                              peers=peers, tables=tables,
                              exchange=choreo)
            except (RpcError, ConnectionError, OSError):
                pass  # it pulls fresh routing at re-registration

    def _set_vnode_gauges(self) -> None:
        with self._lock:
            vmap = self.vnode_map or []
            counts: dict[int, int] = {}
            for wid in vmap:
                counts[wid] = counts.get(wid, 0) + 1
            for w in self.workers.values():
                if w.alive:
                    self.metrics.set_gauge(
                        "cluster_worker_vnodes",
                        counts.get(w.worker_id, 0),
                        worker=str(w.worker_id),
                    )

    # -- the global checkpoint protocol ---------------------------------
    def rpc_tick(self, chunks_per_barrier: int = 1) -> dict:
        return self.tick(chunks_per_barrier)

    def _barrier_units(self, jobs: list[JobInfo]):
        """The round's barrier units: (job, unit) pairs where ``unit``
        is the JobInfo itself (whole-job placement) or each of its
        vnode partitions — both carry the same round-protocol fields,
        so the seal/durable/commit path below drives either."""
        units = []
        for job in jobs:
            if job.partitions:
                units += [(job, p) for p in job.partitions.values()
                          if not p.retiring]
            else:
                units.append((job, job))
        return units

    def _round_fences(self, jobs: list[JobInfo]) -> dict:
        """Per-table consumption fences for this round: the ingest
        leader's current history position.  Every partition of a job
        consumes the IDENTICAL prefix up to the fence, so source
        cursors stay aligned across workers (what makes
        checkpoint-slice handover exact).  One control RPC per
        replicated table per round — the per-chunk data path stays
        worker↔worker."""
        fences: dict[str, int] = {}
        for job in jobs:
            if not job.partitions:
                continue
            for t in job.dml_tables:
                if t in fences:
                    continue
                cached = self._fence_cache.get(t)
                if cached is not None:
                    fences[t] = cached
                    continue
                leader = self._table_leader(t)
                w = self.workers.get(leader) \
                    if leader is not None else None
                if w is None or not w.alive:
                    continue
                try:
                    res = self.retry.run(
                        lambda: w.client.call("table_len", table=t),
                        label="table_len",
                    )
                    fences[t] = int(res["len"])
                except (RpcError, ConnectionError, OSError):
                    continue  # round stalls for this job's partitions
        return fences

    def _table_leader(self, table: str) -> int | None:
        with self._lock:
            hosts = sorted({
                p.worker_id
                for j in self.jobs.values() if j.partitions
                and table in j.dml_tables
                for p in j.partitions.values()
                if p.worker_id is not None
            })
        return hosts[0] if hosts else None

    def tick(self, chunks_per_barrier: int = 1) -> dict:
        with self._tick_lock:
            res = self._tick_locked(chunks_per_barrier)
        # corrupt SSTs surfaced by worker export seams during the
        # round repair OUTSIDE the tick lock (repair re-enters it)
        self._drain_corrupt_reports()
        return res

    def _tick_locked(self, chunks_per_barrier: int = 1) -> dict:
        """Drive ONE global barrier round: every barrier unit (job or
        vnode partition) SEALS round ``cluster_epoch + 1`` (the
        barrier RPC returns as soon as the epoch is sealed — its
        checkpoint upload runs in the worker's background uploader);
        the cluster epoch commits through the versioned manifest only
        when every unit's upload has ACKED the sealed epoch.
        Incomplete rounds (dead/unassigned workers, uploads still in
        flight) commit nothing — the cluster epoch never moves past a
        hole, and survivors run at most one round ahead."""
        t0 = time.perf_counter()
        target = self.cluster_epoch + 1
        with self._lock:
            jobs = list(self.jobs.values())
        units = self._barrier_units(jobs)
        if not units:
            return {"round": target, "committed": False,
                    "jobs": 0, "sealed": 0}
        self.metrics.set_gauge("cluster_epoch_in_flight", target)
        # trace-lite: ONE root span per round trace, however many tick
        # attempts the round takes — an attempt that didn't commit
        # leaves ``_trace_root_ctx`` in place, and the retry parents a
        # child "attempt" span under the ORIGINAL root instead of
        # opening a second root (tree_check requires exactly one)
        if self._trace_round != target or self._trace_root_ctx is None:
            self._trace_round = target
            tick_span = GLOBAL_TRACE.span(
                "round", trace_id=f"round-{target}",
                epoch=target, units=len(units),
            )
            self._trace_root_ctx = tick_span.ctx
        else:
            tick_span = GLOBAL_TRACE.span(
                "attempt", ctx=self._trace_root_ctx, epoch=target,
            )
        with tick_span as rspan:
            res = self._tick_attempt(
                target, jobs, units, chunks_per_barrier, t0,
                rspan.ctx,
            )
            rspan.set(committed=res["committed"],
                      sealed=res["sealed"])
        if res["committed"]:
            # serving lease grants piggyback this ctx so sampled
            # replica reads join the round tree they actually read
            self._last_round_ctx = self._trace_root_ctx
        self._export_fault_gauges()
        return res

    def _tick_attempt(self, target: int, jobs, units,
                      chunks_per_barrier: int, t0: float,
                      rctx: "tuple | None") -> dict:
        """One tick attempt at round ``target`` (the body of
        ``_tick_locked``, running under that round's trace span —
        ``rctx`` is passed EXPLICITLY into the per-worker fan-out
        threads, whose thread-local trace stacks are empty)."""
        # consumption fences are PER ROUND: a retried round (worker
        # failure mid-round) reuses the fence its survivors already
        # sealed with, so a re-adopted partition consumes the same
        # prefix and cursors stay aligned
        if self._fence_round != target:
            self._fence_round = target
            self._fence_cache = {}
        fences = self._round_fences(jobs)
        self._fence_cache.update(fences)
        sealed = 0
        by_worker: dict[int, list] = {}
        for job, unit in units:
            if unit.rounds >= target:
                sealed += 1
                continue
            with self._lock:
                w = self.workers.get(unit.worker_id) \
                    if unit.worker_id is not None else None
            if w is None or not w.alive:
                continue
            limits = {t: fences[t] for t in job.dml_tables
                      if t in fences} if job.partitions else None
            if job.partitions and job.dml_tables and not limits:
                continue  # fence unavailable: stall, never diverge
            by_worker.setdefault(w.worker_id, []).append(
                (job, unit, w, limits)
            )

        def _barrier_one(job, unit, w, limits) -> bool:
            try:
                # round-tagged: the worker caches each job's last
                # (round, seal) and answers a replay from the
                # cache, so retrying after a lost RESPONSE cannot
                # run the round twice (epoch-guarded idempotence)
                with GLOBAL_TRACE.span("barrier", ctx=rctx,
                                       job=job.name, unit=unit.name,
                                       worker=w.worker_id):
                    res = self.retry.run(
                        lambda: w.client.call(
                            "barrier", job=job.name,
                            chunks=int(chunks_per_barrier),
                            round=target, limits=limits,
                        ),
                        label="barrier",
                    )
            except (RpcError, ConnectionError, OSError):
                return False  # monitor expires the worker; stall
            epoch = int(res.get("sealed_epoch",
                                res["committed_epoch"]))
            ssts = res.get("ssts") or []
            if res.get("corrupt"):
                with self._lock:
                    self._corrupt_reports.extend(res["corrupt"])
            self._mirror_exchange_gauges(w.worker_id,
                                         res.get("exchange"))
            with self._lock:
                unit.rounds = target
                unit.seal_log.append((target, epoch))
                unit.durable_epoch = int(
                    res.get("durable_epoch", epoch)
                )
                # a failover re-seal replaces the dead attempt's
                # pending export (same round, recomputed bytes)
                for s in self._pending_ssts.pop((unit.name, target),
                                                []):
                    self.hummock.release_external_sst_key(s["key"])
                if ssts:
                    self._pending_ssts[(unit.name, target)] = ssts
                    w.sst_keys.difference_update(
                        {s["key"] for s in ssts}
                    )
                for table, doc in (res.get("policies") or {}).items():
                    self._pending_policies[table] = doc
            return True

        # barrier RPCs fan out PER WORKER (units on one worker stay
        # serial — its engine lock serializes anyway; units on
        # DIFFERENT workers run their chunks concurrently).  This is
        # what lets a shuffled round's wall time track the SLOWEST
        # partition instead of the SUM of partitions — the other half
        # of "ingest throughput tracks worker count".  Checkpoint
        # uploads stay safe: each partition writes its own lineage
        # keys, export SSTs ride meta-allocated collision-free keys,
        # and this thread alone commits the manifest afterwards.
        groups = list(by_worker.values())
        if len(groups) == 1:
            sealed += sum(_barrier_one(*item) for item in groups[0])
        elif groups:
            results: list[int] = [0] * len(groups)

            def _run_group(gi: int, items) -> None:
                results[gi] = sum(_barrier_one(*item)
                                  for item in items)

            threads = [
                threading.Thread(target=_run_group, args=(gi, items),
                                 name=f"meta-barrier-w{gi}")
                for gi, items in enumerate(groups)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            sealed += sum(results)
        committed = sealed == len(units)
        if committed:
            with GLOBAL_TRACE.span("await_durable", epoch=target):
                committed = self._await_durable(units, target)
        if committed:
            with GLOBAL_TRACE.span("commit", epoch=target):
                self._commit_cluster_epoch(target, units)
            from risingwave_tpu.common.metrics import (
                WIDE_SECONDS_BUCKETS,
            )
            self.metrics.observe(
                "cluster_barrier_commit_seconds",
                time.perf_counter() - t0,
                buckets=WIDE_SECONDS_BUCKETS,
            )
        return {"round": target, "committed": committed,
                "jobs": len(jobs), "units": len(units),
                "sealed": sealed,
                "cluster_epoch": self.cluster_epoch}

    def _mirror_exchange_gauges(self, worker_id: int,
                                ex: "dict | None") -> None:
        """Mirror a worker's exchange counters as per-worker gauges
        (cheap piggyback on the barrier response).  Tracked so
        ``_remove_worker_series`` retires them with the worker —
        exactly the PR-7/PR-10 per-peer gauge discipline."""
        if not ex:
            return
        if not hasattr(self, "_exchange_series"):
            self._exchange_series = set()
        for k in ("rows_out", "rows_in", "batches_out",
                  "batches_in", "send_failures"):
            self.metrics.set_gauge(
                f"cluster_worker_exchange_{k}",
                int(ex.get(k, 0)), worker=str(worker_id),
            )
        self._exchange_series.add(worker_id)

    def _await_durable(self, units, target: int) -> bool:
        """The seal-vs-ack split: poll each sealed unit's worker until
        its durable (upload-acked) epoch reaches the round's seal, or
        the bounded wait expires (round retried by the next tick).
        Workers poll in PARALLEL (their uploads already run in
        parallel background threads) — the wait is bounded by the
        slowest worker, not the sum."""
        by_worker: dict = {}
        for job, unit in units:
            by_worker.setdefault(unit.worker_id, []).append(
                (job, unit)
            )
        if len(by_worker) <= 1:
            return self._await_durable_units(units, target)
        results: list[bool] = [False] * len(by_worker)
        groups = list(by_worker.values())

        def _run(gi: int, items) -> None:
            results[gi] = self._await_durable_units(items, target)

        threads = [
            threading.Thread(target=_run, args=(gi, items),
                             name=f"meta-durable-{gi}")
            for gi, items in enumerate(groups)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return all(results)

    def _await_durable_units(self, units, target: int) -> bool:
        deadline = time.monotonic() + self.durable_wait_s
        for job, unit in units:
            with self._lock:
                if not unit.seal_log:
                    return False
                want = unit.seal_log[-1][1]
                w = self.workers.get(unit.worker_id) \
                    if unit.worker_id is not None else None
            lag_gauge = lambda v: self.metrics.set_gauge(  # noqa: E731
                "cluster_job_durable_lag_epochs", v, job=unit.name,
            )
            if unit.durable_epoch >= want:
                lag_gauge(0)
                continue
            if w is None or not w.alive:
                return False
            while True:
                try:
                    # read-only poll: always retry-safe
                    res = self.retry.run(
                        lambda: w.client.call("job_epochs",
                                              job=job.name),
                        label="job_epochs",
                    )
                except (RpcError, ConnectionError, OSError):
                    return False
                with self._lock:
                    unit.durable_epoch = int(res.get("durable", 0))
                lag_gauge(max(0, want - unit.durable_epoch))
                self.metrics.set_gauge(
                    "cluster_job_upload_queue_depth",
                    int(res.get("upload_queue", 0)), job=unit.name,
                )
                if unit.durable_epoch >= want:
                    break
                if time.monotonic() > deadline:
                    return False
                time.sleep(0.02)
        return True

    def _commit_cluster_epoch(self, round_: int, units) -> None:
        """All units sealed ``round_``: ONE manifest delta records the
        global consistency point — carrying every MV export SST the
        round's seals uploaded (newest round first, so L0 reader order
        stays newest-first) — then serving pins move forward: a
        snapshot read after this sees every MV at the same round."""
        from risingwave_tpu.storage.hummock.version import SstInfo

        epoch_val = min(u.seal_log[-1][1] for _, u in units)
        with self._lock:
            due = sorted(
                [k for k in self._pending_ssts if k[1] <= round_],
                key=lambda k: -k[1],
            )
            adds = [
                SstInfo(
                    key=s["key"],
                    first_key=bytes.fromhex(s["first_key"]),
                    last_key=bytes.fromhex(s["last_key"]),
                    n_records=int(s["n_records"]),
                    size=int(s["size"]),
                )
                for k in due for s in self._pending_ssts[k]
            ]
            for k in due:
                del self._pending_ssts[k]
            policies = self._pending_policies
            self._pending_policies = {}
        self.hummock.commit_external(epoch_val, adds,
                                     policies=policies or None)
        # durable round record AFTER the manifest commit: a crash in
        # between re-commits the round idempotently at restart (empty
        # delta, same epoch stamp) — never a lost or double round
        self.store.append_cluster_commit(
            round_, epoch_val,
            {u.name: u.seal_log[-1][1] for _, u in units},
        )
        retired: list[tuple[int, str]] = []
        with self._lock:
            self.cluster_epoch = round_
            plans: dict[str, list] = {}
            for job, u in units:
                job.rounds = round_
                u.pinned_epoch = u.seal_log[-1][1]
                if u is not job:
                    # reads pinned at this round route with the vnode
                    # set of this round — consistent through handover
                    u.pinned_vnodes = list(u.vnodes)
                    plans.setdefault(job.name, []).append(
                        (u.worker_id, u.pinned_epoch, list(u.vnodes))
                    )
                # seal_log only needs entries recovery can rewind to;
                # everything at/before the global commit is final
                if len(u.seal_log) > 64:
                    u.seal_log = u.seal_log[-64:]
            for job, _ in units:
                if job.name in plans:
                    # the ATOMIC routing switch: fan-out reads now see
                    # this round's owners/vnodes — never a mixed-round
                    # union; retiring donors are safe to drop
                    job.serve_plan = plans[job.name]
                    for p in [p for p in job.partitions.values()
                              if p.retiring]:
                        job.partitions.pop(p.lineage, None)
                        if p.worker_id is not None:
                            retired.append((p.worker_id, job.name))
                            w = self.workers.get(p.worker_id)
                            if w is not None:
                                w.jobs.discard(job.name)
        for wid, jname in retired:
            with self._lock:
                w = self.workers.get(wid)
            if w is not None and w.alive:
                try:
                    w.client.call("release", job=jname)
                except (RpcError, ConnectionError, OSError):
                    pass  # best-effort; the idle partition is inert
        self.metrics.set_gauge("cluster_epoch_committed", round_)
        self.metrics.set_gauge("cluster_manifest_epoch", epoch_val)

    # -- serving reads ---------------------------------------------------
    def rpc_serve(self, sql: str) -> dict:
        cols, rows = self.serve(sql)
        return {"cols": cols, "rows": rows}

    def serve(self, sql: str):
        """Route a serving read.  SELECTs go ROUND-ROBIN across live
        serving replicas (the stateless read tier over shared SSTs,
        pinned at the last cluster-committed manifest epoch); when no
        replica is registered, a replica refuses the statement shape
        (``ServeUnsupported``), or every replica is unreachable, the
        read falls back to the MV's OWNING worker pinned at the job's
        last cluster-committed epoch.  While the owner is dead/
        unassigned (failover in progress) the read WAITS for the
        reassignment instead of erroring — reads never observe partial
        state and never fail across a worker OR replica kill."""
        from risingwave_tpu.sql import ast
        from risingwave_tpu.sql.parser import parse

        stmts = parse(sql)
        if len(stmts) != 1 or not isinstance(stmts[0], ast.Select):
            raise ValueError("cluster serving handles a single SELECT")
        sel = stmts[0]
        if not isinstance(sel.from_, ast.TableRef):
            raise ValueError(
                "cluster serving reads are SELECT ... FROM <mv>"
            )
        mv = sel.from_.name
        deadline = time.monotonic() + self.serve_retry_timeout_s
        try_replicas = True
        while True:
            with self._lock:
                jname = self._mv_to_job.get(mv)
                if jname is None:
                    raise ValueError(
                        f"{mv!r} does not exist (not a placed MV)"
                    )
                job = self.jobs[jname]
                parts = list(job.partitions.values()) \
                    if job.partitions else None
                w = self.workers.get(job.worker_id) \
                    if job.worker_id is not None else None
                pin = job.pinned_epoch
                manifest_pin = self.versions.max_committed_epoch
                replicas = [r for r in self.serving.values() if r.alive]
                self._serve_rr += 1
                start = self._serve_rr
            if parts is not None and _select_needs_engine_merge(sel):
                # per-partition results of an aggregate-shaped SELECT
                # cannot be unioned — a loud refusal, never a wrong row
                raise ValueError(
                    "aggregate serving reads over a partitioned MV: "
                    "create a materialized view for the aggregation"
                )
            if try_replicas and replicas:
                for i in range(len(replicas)):
                    r = replicas[(start + i) % len(replicas)]
                    try:
                        res = r.client.call("read", sql=sql,
                                            min_epoch=manifest_pin)
                        self.metrics.inc("cluster_serving_reads_total")
                        return res["cols"], [tuple(row)
                                             for row in res["rows"]]
                    except RpcError as e:
                        if "ServeUnsupported" in str(e):
                            # statement shape needs the engine — the
                            # owning worker serves it (and every retry
                            # of this read)
                            try_replicas = False
                            break
                        if "ServeUnavailable" in str(e):
                            # replica transiently stuck (lease refresh
                            # lost, behind the pin): route around it —
                            # next replica or the owner, never an error
                            continue
                        raise  # replica answered with a real failure
                    except (ConnectionError, OSError):
                        continue  # replica died mid-read: next one
            if parts is not None:
                # partitioned MV: fan out per the serve PLAN (the
                # atomically-published routing of the last commit — a
                # consistent single-round view through handovers) and
                # union the disjoint slices; any owner mid-failover ⇒
                # wait and retry the whole read (never a partial
                # answer)
                with self._lock:
                    plan = list(job.serve_plan) if job.serve_plan \
                        else [(p.worker_id, p.pinned_epoch,
                               list(p.pinned_vnodes)
                               or list(p.vnodes))
                              for p in job.partitions.values()
                              if not p.retiring]
                    owners = [
                        (self.workers.get(wid)
                         if wid is not None else None, pe, pv)
                        for wid, pe, pv in plan
                    ]
                if all(w2 is not None and w2.alive
                       for w2, _, _ in owners):
                    rows: list[tuple] = []
                    cols: list = []
                    complete = True
                    for w2, pe, pv in owners:
                        try:
                            res = w2.client.call(
                                "serve", sql=sql, query_epoch=pe,
                                vnodes=pv,
                            )
                        except RpcError as e:
                            if "does not exist" in str(e) \
                                    or "is not retained" in str(e):
                                # stale routing (released donor), or a
                                # checkpoint repair truncated the
                                # pinned epoch — both transient: the
                                # next commit republishes plan + pins.
                                # Retry, never a failed read
                                complete = False
                                break
                            raise  # the engine refused: final
                        except (ConnectionError, OSError):
                            complete = False
                            break
                        cols = res["cols"]
                        rows += [tuple(r) for r in res["rows"]]
                    if complete:
                        self.metrics.inc(
                            "cluster_partitioned_reads_total"
                        )
                        return cols, rows
            elif w is not None and w.alive:
                try:
                    res = w.client.call("serve", sql=sql,
                                        query_epoch=pin)
                    return res["cols"], [tuple(r) for r in res["rows"]]
                except RpcError as e:
                    if "is not retained" in str(e):
                        # a checkpoint repair truncated the pinned
                        # epoch: wait for the next commit to re-pin
                        pass
                    else:
                        raise  # the engine refused: final
                except (ConnectionError, OSError):
                    pass  # owner died mid-read: wait for reassignment
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"no live owner for {mv!r} within "
                    f"{self.serve_retry_timeout_s}s"
                )
            time.sleep(0.05)

    def rpc_serve_batch(self, sqls: list) -> dict:
        return {"results": [
            {"cols": cols, "rows": [list(r) for r in rows]}
            for cols, rows in self.serve_batch(list(sqls))
        ]}

    def serve_batch(self, sqls: list) -> list:
        """Route N SELECTs through ONE replica RPC frame (the batched
        multi-get protocol).  Items the replica cannot serve
        (``unsupported``) fall back PER ITEM to the single-read router
        (owning worker); a final per-item error (unknown column/MV)
        raises like the single-read path would.  With no live replica
        every item takes the single-read router."""
        with self._lock:
            replicas = [r for r in self.serving.values() if r.alive]
            manifest_pin = self.versions.max_committed_epoch
            self._serve_rr += 1
            start = self._serve_rr
        for i in range(len(replicas)):
            r = replicas[(start + i) % len(replicas)]
            try:
                res = r.client.call("read_batch", sqls=sqls,
                                    min_epoch=manifest_pin)
            except RpcError as e:
                if "ServeUnavailable" in str(e):
                    continue  # replica stuck behind the pin: next one
                raise
            except (ConnectionError, OSError):
                continue  # replica died mid-batch: next one
            out = []
            for item, sql in zip(res["results"], sqls):
                if item.get("error") is not None:
                    raise ValueError(item["error"])
                if "unsupported" in item:
                    out.append(self.serve(sql))
                else:
                    out.append((item["cols"],
                                [tuple(row) for row in item["rows"]]))
            self.metrics.inc("cluster_serving_batch_reads_total",
                             len(sqls))
            return out
        return [self.serve(sql) for sql in sqls]

    def rpc_serve_multi_get(self, mv: str, pks: list,
                            cols: list | None = None) -> dict:
        names, rows = self.serve_multi_get(mv, pks, cols)
        return {"cols": names, "rows": [list(r) for r in rows]}

    def serve_multi_get(self, mv: str, pks: list,
                        cols: list | None = None):
        """First-class multi-get: one MV + N full pks in one frame.
        Routes to a replica (one sorted SstView pass); with none live
        it falls back to per-pk SELECTs against the single-read
        router, union sorted by encoded pk — the same row order the
        replica path answers.  Missing pks are omitted."""
        from risingwave_tpu.serve.reader import MvSchema, schema_key

        with self._lock:
            if mv not in self._mv_to_job:
                raise ValueError(
                    f"{mv!r} does not exist (not a placed MV)"
                )
            replicas = [r for r in self.serving.values() if r.alive]
            manifest_pin = self.versions.max_committed_epoch
            self._serve_rr += 1
            start = self._serve_rr
        for i in range(len(replicas)):
            r = replicas[(start + i) % len(replicas)]
            try:
                res = r.client.call("multi_get", mv=mv, pks=pks,
                                    cols=cols, min_epoch=manifest_pin)
                self.metrics.inc("cluster_serving_batch_reads_total",
                                 len(pks))
                return res["cols"], [tuple(row) for row in res["rows"]]
            except RpcError as e:
                if "ServeUnavailable" in str(e) \
                        or "ServeUnsupported" in str(e):
                    # stuck replica, or the MV's schema doc has not
                    # landed yet: fall through (next replica / owner)
                    continue
                raise
            except (ConnectionError, OSError):
                continue
        # owner fallback: per-pk SELECTs, union in encoded-pk order
        import json as _json

        try:
            schema = MvSchema(_json.loads(
                self.hummock.store.get(schema_key(mv))
            ))
        except Exception:  # noqa: BLE001 — never exported yet
            schema = None
        if schema is None:
            raise ValueError(
                f"multi_get on {mv!r}: no schema published and no "
                "live serving replica"
            )
        pk_names = [schema.columns[i].name for i in schema.pk]
        keyed = []
        out_cols: list = []
        for pk in pks:
            where = " AND ".join(
                f"{n} = {_sql_literal(v)}"
                for n, v in zip(pk_names, pk)
            )
            proj = ", ".join(cols) if cols else "*"
            c, rows = self.serve(
                f"SELECT {proj} FROM {mv} WHERE {where}"
            )
            out_cols = c or out_cols
            enc = b"".join(
                schema.encode_pk_value(ci, v)
                for ci, v in zip(schema.pk, pk)
            )
            keyed += [(enc, tuple(row)) for row in rows]
        keyed.sort(key=lambda kv: kv[0])
        return out_cols, [row for _, row in keyed]

    # -- introspection ----------------------------------------------------
    def rpc_cluster_state(self) -> dict:
        return self.state()

    def rpc_metrics(self) -> dict:
        return {"prometheus": self.metrics.render_prometheus()}

    def rpc_trace_dump(self, trace_id: str | None = None) -> dict:
        return {"role": "meta",
                "spans": GLOBAL_TRACE.dump(trace_id)}

    def rpc_cluster_trace(self, round: "int | None" = None) -> dict:
        return self.cluster_trace(round)

    def cluster_trace(self, round: "int | None" = None) -> dict:
        """Assemble ONE cross-role span tree for a round (``ctl
        cluster trace``): the meta's own flight recorder merged with
        every live worker's and serving replica's ``trace_dump``
        (best-effort — a dead peer's spans are simply absent, leaving
        a truncated-but-parseable tree).  Defaults to the most recent
        round that has spans at or below the committed cluster epoch;
        returns the filtered spans plus a ``tree_check`` verdict and
        the full list of rounds the recorders still hold."""
        dumps = [GLOBAL_TRACE.dump()]
        with self._lock:
            workers = [w for w in self.workers.values() if w.alive]
            serving = [r for r in self.serving.values() if r.alive]
        for peer in workers + serving:
            try:
                d = peer.client.call("trace_dump")
                dumps.append(d.get("spans") or [])
            except (RpcError, ConnectionError, OSError):
                pass
        spans = merge_dumps(dumps)
        rounds = round_ids(spans)
        if round is not None:
            rn = int(round)
        else:
            committed = [r for r in rounds if r <= self.cluster_epoch]
            rn = committed[-1] if committed \
                else (rounds[-1] if rounds else 0)
        picked = spans_for_round(spans, rn)
        return {
            "round": rn,
            "rounds": rounds,
            "cluster_epoch": self.cluster_epoch,
            "spans": picked,
            "check": tree_check(picked),
        }

    def rpc_cluster_metrics(self) -> dict:
        return {"prometheus": self.cluster_metrics()}

    def cluster_metrics(self) -> str:
        """ONE aggregated Prometheus scrape for the whole cluster
        (``ctl cluster metrics``): the meta's own registry plus every
        live worker's and serving replica's ``rpc_metrics`` text,
        merged with ``role``/``worker``/``replica`` identity labels
        injected per sample (best-effort — an unreachable peer's
        section is absent, never an error)."""
        scrapes: list[tuple[dict, str]] = [
            ({"role": "meta"}, self.metrics.render_prometheus()),
        ]
        with self._lock:
            workers = [w for w in self.workers.values() if w.alive]
            serving = [r for r in self.serving.values() if r.alive]
        for w in workers:
            try:
                text = w.client.call("metrics").get("prometheus", "")
                scrapes.append((
                    {"role": f"worker{w.worker_id}",
                     "worker": str(w.worker_id)}, text,
                ))
            except (RpcError, ConnectionError, OSError):
                pass
        for r in serving:
            try:
                text = r.client.call("metrics").get("prometheus", "")
                scrapes.append((
                    {"role": f"serving{r.replica_id}",
                     "replica": str(r.replica_id)}, text,
                ))
            except (RpcError, ConnectionError, OSError):
                pass
        return merge_prometheus(scrapes)

    def rpc_cluster_pushdown(self) -> dict:
        return self.cluster_pushdown()

    def cluster_pushdown(self) -> dict:
        """The pushdown-plane observability surface (``ctl cluster
        pushdown``): the manifest's per-table expiry policy docs plus
        the meta-side compactor elision counters, and each live
        serving replica's negative-cache / warmup numbers from its
        ``state`` RPC (best-effort — an unreachable replica reports
        null rather than failing the whole view)."""
        stats = self.hummock.stats()
        out = {
            "version_id": stats.get("version_id"),
            "pushdown": stats.get("pushdown") or {},
            "serving": {},
        }
        with self._lock:
            serving = [r for r in self.serving.values() if r.alive]
        for r in serving:
            try:
                st = r.client.call("state")
                out["serving"][r.replica_id] = {
                    "negative_cache_hits":
                        st.get("negative_cache_hits"),
                    "negative_cache_entries":
                        st.get("negative_cache_entries"),
                    "warmup_replays": st.get("warmup_replays"),
                }
            except (RpcError, ConnectionError, OSError):
                out["serving"][r.replica_id] = None
        return out

    def rpc_cluster_faults(self) -> dict:
        return self.cluster_faults()

    def cluster_faults(self) -> dict:
        """The chaos observability surface (``ctl cluster faults``):
        this process' injected-fault counters plus the meta's retry
        budget, and the same two numbers from every live worker and
        serving replica (best-effort — an unreachable peer reports
        null rather than failing the whole view)."""
        self._export_fault_gauges()
        fabric = get_fabric()
        out = {
            "meta": {
                "fabric": fabric.stats() if fabric is not None else None,
                "rpc_retries_total": self.retry.retries,
                "rpc_retry_gave_up_total": self.retry.gave_up,
            },
            "workers": {},
            "serving": {},
        }
        with self._lock:
            workers = [w for w in self.workers.values() if w.alive]
            serving = [r for r in self.serving.values() if r.alive]
        for w in workers:
            try:
                out["workers"][w.worker_id] = w.client.call("faults")
            except (RpcError, ConnectionError, OSError):
                out["workers"][w.worker_id] = None
        for r in serving:
            try:
                out["serving"][r.replica_id] = r.client.call("faults")
            except (RpcError, ConnectionError, OSError):
                out["serving"][r.replica_id] = None
        return out

    def _export_fault_gauges(self) -> None:
        fabric = get_fabric()
        self.metrics.set_gauge(
            "faults_injected_total",
            fabric.injected_total() if fabric is not None else 0,
        )
        self.metrics.set_gauge("rpc_retries_spent_total",
                               self.retry.retries)
        self.metrics.set_gauge("rpc_retry_gave_up_spent_total",
                               self.retry.gave_up)

    def state(self) -> dict:
        """The ctl/dashboard surface (risectl cluster-info analog)."""
        import sys

        backend = False
        if "jax" in sys.modules:
            from jax._src import xla_bridge
            backend = xla_bridge.backends_are_initialized()
        now = time.monotonic()
        with self._lock:
            return {
                # the meta plans and routes; it must never take a chip
                # from a compute worker on the same host
                "backend_initialized": backend,
                "cluster_epoch": self.cluster_epoch,
                "manifest_epoch":
                    self.versions.current.max_committed_epoch,
                "failovers": self.failovers,
                "recovered": self.recovered,
                "workers": [
                    {"id": w.worker_id, "addr": w.addr,
                     "alive": w.alive, "pid": w.pid,
                     "heartbeat_age_s": round(now - w.last_seen, 3),
                     "jobs": sorted(w.jobs)}
                    for w in self.workers.values()
                ],
                "serving": [
                    {"id": r.replica_id, "addr": r.addr,
                     "alive": r.alive, "pid": r.pid,
                     "heartbeat_age_s": round(now - r.last_seen, 3),
                     "granted_vid": r.granted_vid,
                     "pinned_vids": sorted(r.pins)}
                    for r in self.serving.values()
                ],
                "jobs": [
                    {"name": j.name, "mvs": list(j.mvs),
                     "worker": j.worker_id, "rounds": j.rounds,
                     "pinned_epoch": j.pinned_epoch,
                     "sealed_epoch":
                         j.seal_log[-1][1] if j.seal_log else 0,
                     "durable_epoch": j.durable_epoch,
                     "committed_epoch":
                         j.seal_log[-1][1] if j.seal_log else 0,
                     "partitions": [
                         {"lineage": p.lineage,
                          "worker": p.worker_id,
                          "vnodes": len(p.vnodes),
                          "rounds": p.rounds,
                          "pinned_epoch": p.pinned_epoch}
                         for p in j.partitions.values()
                     ] if j.partitions else None}
                    for j in self.jobs.values()
                ],
                "integrity": {
                    "scrub_cycles": self.scrubber.cycles,
                    "scrub_objects_verified":
                        self.scrubber.objects_verified,
                    "scrub_corruptions": self.scrubber.corruptions,
                    "repairs": dict(self.repairs),
                },
                "exchange": {
                    "version": (self._choreography or {}).get(
                        "version", 0
                    ) if hasattr(self, "_choreography") else 0,
                    "tables": {
                        t: {"leader": e["leader"],
                            "standby": e.get("standby"),
                            "mode": e["mode"],
                            "key_col": e.get("key_col")}
                        for t, e in ((self._choreography or {})
                                     .get("tables", {})).items()
                    } if hasattr(self, "_choreography") else {},
                    "specs": list((self._choreography or {})
                                  .get("specs", []))
                    if hasattr(self, "_choreography") else [],
                },
                "scale": {
                    "partitioning": self.scale_partitioning,
                    "n_vnodes": self.n_vnodes,
                    "active_workers": list(self.active_workers),
                    "scale_ops": self.scale_ops,
                    "vnode_map": {
                        str(w): sum(1 for x in self.vnode_map
                                    if x == w)
                        for w in sorted(set(self.vnode_map))
                    } if self.vnode_map else None,
                },
            }


class MetaFrontend:
    """The thin pgwire façade over a MetaService: SELECTs route to
    workers through the pinned epoch, everything else is cluster DDL.
    Duck-types Engine.query, so ``pgwire.pg_serve`` hosts it as-is
    (the frontend node stays a router, exactly the reference split)."""

    def __init__(self, meta: MetaService):
        self.meta = meta

    def query(self, sql: str):
        from risingwave_tpu.sql import ast
        from risingwave_tpu.sql.parser import parse

        stmts = parse(sql)
        if len(stmts) == 1 and isinstance(stmts[0], ast.Select):
            return self.meta.serve(sql)
        self.meta.execute_ddl(sql)
        return [], []

    def query_batch(self, sqls: list) -> list:
        """Batched serving reads: N SELECTs through one replica RPC
        frame (``MetaService.serve_batch``); per-item owner fallback
        keeps the SQL surface identical to ``query``."""
        return self.meta.serve_batch(list(sqls))

    def multi_get(self, mv: str, pks: list,
                  cols: list | None = None):
        """First-class multi-get: one MV + N pks in one frame, rows
        back in encoded-pk order (missing pks omitted)."""
        return self.meta.serve_multi_get(mv, list(pks), cols)
