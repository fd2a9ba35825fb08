"""ComputeWorker: one compute-node process in the cluster.

Reference counterpart: the compute node (``src/compute``) — hosts
streaming actors, answers the meta's barrier injections, serves batch
reads over its local state, and reports liveness through heartbeats
(src/compute/src/server.rs; heartbeats in meta's ClusterController).

Shape here: an ``Engine`` in ``role="compute"`` (shared durable
checkpoint store under the cluster ``data_dir``, no meta store, no
hummock manifest — meta owns both), driven ENTIRELY by meta RPCs:

- ``adopt``  — execute the job's DDL (skipping objects already in the
  local catalog) and recover it from its last durable checkpoint; the
  placement AND the failover path are the same call;
- ``barrier`` — process N chunks + inject one barrier for ONE job
  (the meta drives rounds job-by-job, so the shared checkpoint
  manifest has a single writer at any instant).  Barriers are
  ROUND-TAGGED: the worker caches each job's last (round, seal)
  answer and replays it verbatim when the meta retries a round whose
  response was lost — a retried barrier can never run chunks twice;
- ``serve``  — a batch read, optionally pinned at ``query_epoch``
  (the meta passes its last cluster-committed epoch);
- ``execute`` — generic statement forwarding (INSERT fan-out).

A worker has no self-ticker: if the meta dies, the cluster freezes
consistently instead of diverging.  The heartbeat thread, however,
never dies with the meta: transient unreachability backs off and
keeps beating, and a meta that answers "unknown worker" (it restarted
and lost the registry, or expired us across a partition) triggers
RE-REGISTRATION — the meta then re-adopts our jobs from the durable
checkpoint chain, with no operator in the loop.
"""

from __future__ import annotations

import os
import threading
import time

from risingwave_tpu.cluster.rpc import (
    RpcClient,
    RpcError,
    RpcServer,
    parse_addr,
)
from risingwave_tpu.common.faults import RetryPolicy, get_fabric
from risingwave_tpu.common.trace import GLOBAL_TRACE


class ComputeWorker:
    def __init__(self, meta_addr: str, data_dir: str, config=None,
                 host: str = "127.0.0.1", port: int = 0,
                 heartbeat_interval_s: float = 0.5):
        from risingwave_tpu.sql.engine import Engine

        self.meta_host, self.meta_port = parse_addr(meta_addr)
        self.engine = Engine(config, data_dir=data_dir, role="compute")
        self.host = host
        self._port_req = port
        self.heartbeat_interval_s = heartbeat_interval_s
        self.worker_id: int | None = None
        self._lock = threading.Lock()
        self._server: RpcServer | None = None
        self._meta_client: RpcClient | None = None
        self._hb_thread: threading.Thread | None = None
        self._stop = threading.Event()
        cl = getattr(config, "cluster", None)
        self.retry = RetryPolicy(
            max_attempts=cl.rpc_retry_max_attempts if cl else 4,
            base_delay_s=cl.rpc_retry_base_delay_s if cl else 0.05,
            max_delay_s=cl.rpc_retry_max_delay_s if cl else 0.5,
            op="worker",
        )
        #: per-job idempotence cache of the last ROUND-TAGGED barrier:
        #: {"round", "sealed", "result"} — ``result`` is the full
        #: answer a meta retry replays; ``sealed`` alone survives a
        #: mid-handler failure (e.g. the export upload died AFTER the
        #: chunks ran), so the retry redoes only the export, never the
        #: chunks.  Cleared on adopt (an ownership change must never
        #: answer from a stale seal).
        self._round_cache: dict[str, dict] = {}
        #: heartbeats delivered (introspection/tests)
        self.heartbeats_sent = 0
        #: heartbeats that failed transiently (meta down / partition)
        self.heartbeat_failures = 0
        #: times this worker (re-)registered with a meta
        self.registrations = 0
        # -- worker↔worker exchange (the scale plane's data path) -------
        #: meta-pushed routing: peer addresses + replicated-table hosts
        #: (the choreography — per-chunk data then flows peer-to-peer,
        #: the meta keeps only control traffic)
        self._routing: dict = {"version": -1, "peers": {}, "tables": {}}
        self._routing_lock = threading.Lock()
        #: lazily-opened peer channels, labeled worker{i}>worker{j} so
        #: the fault fabric can storm the exchange seam
        self._peers: dict[int, RpcClient] = {}
        #: exchange counters (stress/chaos observability)
        self.exchange_rows_out = 0
        self.exchange_rows_in = 0
        self.exchange_batches_out = 0
        self.exchange_batches_in = 0
        self.exchange_fetches = 0
        self.exchange_send_failures = 0
        # -- Exchange-lite: the compiled shuffle choreography ----------
        #: executes the meta-compiled choreography: slices each ingest
        #: batch by vnode ONCE and ships each peer only its owned
        #: slice (plus the leader's slice to the standby); per-edge
        #: rows/bytes/batches counters + latency histogram land in the
        #: engine's metrics registry
        from risingwave_tpu.cluster.exchange import ShuffleService

        self.shuffle = ShuffleService(metrics=self.engine.metrics)

    @property
    def port(self) -> int:
        return self._server.port if self._server is not None else 0

    # -- lifecycle ------------------------------------------------------
    def start(self, heartbeat: bool = True) -> "ComputeWorker":
        self._stop.clear()
        self._server = RpcServer(self, self.host, self._port_req).start()
        self._meta_client = RpcClient(self.meta_host, self.meta_port,
                                      timeout=30.0, src="worker",
                                      dst="meta")
        # the FIRST registration is patient beyond the retry budget: a
        # worker booting alongside its meta (deployment races, chaos
        # restarts) waits for the meta to listen instead of dying
        deadline = time.monotonic() + 60.0
        while True:
            try:
                self._register()
                break
            except (ConnectionError, OSError):
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.25)
        # MV export SST keys come from the meta (single allocator:
        # collision-free across workers, vacuum-protected until the
        # round's cluster epoch commits them into the manifest).
        # worker_id is read at CALL time, so re-registration after a
        # meta restart transparently re-points the allocator.
        self.engine.sst_key_allocator = lambda: self.retry.call(
            self._meta_client, "alloc_sst", worker_id=self.worker_id,
        )["key"]
        if heartbeat:
            self._hb_thread = threading.Thread(
                target=self._heartbeat_loop,
                name=f"worker-{self.worker_id}-hb", daemon=True,
            )
            self._hb_thread.start()
        return self

    def _register(self) -> None:
        """(Re-)register with the meta.  A fresh meta hands out a new
        worker id; the old id's entry (if any) stays dead on its side.
        Retried with backoff — registration is idempotent from the
        worker's view (only the NEWEST id is ever used again)."""
        res = self.retry.call(
            self._meta_client, "register_worker",
            host=self.host, port=self.port, pid=os.getpid(),
        )
        self.worker_id = int(res["worker_id"])
        self._meta_client.src = f"worker{self.worker_id}"
        self.shuffle.worker_id = self.worker_id
        self.registrations += 1
        if GLOBAL_TRACE.role == "compute":
            # a dedicated compute process (server.py boot): trace spans
            # carry the meta-assigned identity so merged cluster dumps
            # keep each worker on its own chrome pid lane.  In-process
            # test clusters share one recorder and keep its role.
            GLOBAL_TRACE.configure(role=f"worker{self.worker_id}")

    def _heartbeat_loop(self) -> None:
        # independent of the engine lock: a worker busy compiling or
        # crossing a barrier still beats (liveness != idleness)
        while not self._stop.wait(self.heartbeat_interval_s):
            try:
                self._meta_client.call("heartbeat",
                                       worker_id=self.worker_id,
                                       port=self.port)
                self.heartbeats_sent += 1
            except (ConnectionError, OSError):
                # meta unreachable (restarting / partitioned): the
                # thread SURVIVES and keeps beating — the loop cadence
                # is the backoff
                self.heartbeat_failures += 1
            except RpcError:
                # the meta answered but doesn't know us: it restarted
                # (lost registry) or expired us — re-register so it
                # can re-adopt our jobs; on failure the next beat
                # retries
                self.heartbeat_failures += 1
                try:
                    self._register()
                except (RpcError, ConnectionError, OSError):
                    pass
            except Exception:  # noqa: BLE001 — never kill the thread
                self.heartbeat_failures += 1

    def stop(self) -> None:
        try:
            with self._lock:
                # orderly exit: sealed epochs finish becoming durable
                self.engine.drain_uploads()
        except Exception:  # noqa: BLE001 — a failed upload rewinds
            pass
        self._stop.set()
        if self._hb_thread is not None:
            self._hb_thread.join(timeout=5)
            self._hb_thread = None
        if self._server is not None:
            self._server.stop()
            self._server = None
        with self._routing_lock:
            for c in self._peers.values():
                c.close()
            self._peers.clear()
        if self._meta_client is not None:
            self._meta_client.close()
            self._meta_client = None

    # -- worker↔worker exchange (scale plane data path) -----------------
    def rpc_update_routing(self, version: int, peers: dict,
                           tables: dict,
                           exchange: dict | None = None) -> dict:
        """Meta-pushed placement choreography: peer worker addresses,
        per replicated DML table its hosts + ingest leader, and (when
        the exchange plane is compiled) the full Exchange-lite
        choreography — per-table shuffle key, vnode slices, standby.
        The per-chunk fan-out below never touches the meta again."""
        with self._routing_lock:
            if int(version) >= self._routing["version"]:
                self._routing = {
                    "version": int(version),
                    "peers": {int(k): tuple(v)
                              for k, v in peers.items()},
                    "tables": {t: {"leader": int(i["leader"]),
                                   "hosts": [int(h)
                                             for h in i["hosts"]]}
                               for t, i in tables.items()},
                }
                # drop channels to peers that left the ring
                for wid in [w for w in self._peers
                            if w not in self._routing["peers"]]:
                    self._peers.pop(wid).close()
        if exchange is not None:
            self.shuffle.update(exchange)
            with self._lock:
                self.engine.apply_shuffle_plan(
                    self.shuffle.choreography.tables
                )
        return {"ok": True}

    def _peer(self, wid: int) -> RpcClient:
        with self._routing_lock:
            c = self._peers.get(wid)
            if c is None:
                host, port = self._routing["peers"][wid]
                c = RpcClient(host, int(port), timeout=30.0,
                              src=f"worker{self.worker_id}",
                              dst=f"worker{wid}")
                self._peers[wid] = c
            return c

    def _table_route(self, table: str) -> dict | None:
        with self._routing_lock:
            return self._routing["tables"].get(table)

    def _dml_manager(self, table: str):
        entry = self.engine.catalog.get(table)
        if entry.dml is None:
            raise ValueError(f"{table!r} is not a DML table")
        return entry.dml

    def rpc_execute(self, sql: str) -> dict:
        """Generic statement execution.  INSERTs into a replicated
        table take the choreographed path: a non-leader forwards to
        the table's ingest leader (worker↔worker); the leader applies
        locally and fans the position-stamped batch out to every other
        host over peer channels — the meta never sees a data chunk."""
        from risingwave_tpu.sql import ast
        from risingwave_tpu.sql.parser import parse

        stmts = parse(sql)
        route = None
        if len(stmts) == 1 and isinstance(stmts[0],
                                          (ast.Insert, ast.Delete)):
            # DELETE routes identically: the leader executes the SQL,
            # the history slice it ships already carries the
            # marker-tail op encoding (connector/dml.py)
            route = self._table_route(stmts[0].table)
        if route is None:
            with self._lock:
                self.engine.execute(sql)
            return {"ok": True}
        table = stmts[0].table
        if route["leader"] != self.worker_id:
            # worker↔worker forward; the leader's answer is ours
            return self.retry.run(
                lambda: self._peer(route["leader"]).call(
                    "execute", sql=sql),
                label="execute_forward",
            )
        with self._lock:
            mgr = self._dml_manager(table)
            seq = mgr.history_len()
            self.engine.execute(sql)
            rows = mgr.history_slice(seq)
        # Exchange-lite: slice the batch by vnode ONCE, ship each peer
        # only its owned slice (standby additionally carries the
        # leader's slice); replicate-mode tables keep the PR-7 full
        # fan-out.  All OUTSIDE the engine lock (peers may be
        # forwarding to us concurrently); a dropped delivery
        # self-heals at the next barrier's fence repair.
        payloads = self.shuffle.route_batch(table, seq, rows)
        if not payloads:
            # choreography not yet pushed (registration race): the
            # legacy full fan-out keeps every host convergent
            payloads = {w: {"seq": seq, "rows": rows}
                        for w in route["hosts"] if w != self.worker_id}
        sliced = any("end" in p for p in payloads.values())
        if sliced:
            # stamp the leader's own vnode log (receivers get theirs
            # from the payload): every host can audit ownership
            from risingwave_tpu.cluster.exchange.shuffle import (
                unpack_vnodes,
            )

            first = next(iter(payloads.values()))
            with self._lock:
                mgr.set_vnode_range(seq, unpack_vnodes(first))
        edge = self.shuffle.edge_of(table)
        for wid, payload in payloads.items():
            method = "exchange_sparse" if "end" in payload \
                else "exchange"
            n_rows = len(payload.get("rows", ()))
            try:
                with self.shuffle.timed() as t:
                    self.retry.run(
                        lambda w=wid, p=payload, m=method:
                        self._peer(w).call(m, table=table, **p),
                        label="exchange",
                    )
                self.shuffle.note_send(edge, payload, t.dt)
                self.exchange_rows_out += n_rows
                self.exchange_batches_out += 1
            except (RpcError, ConnectionError, OSError, KeyError):
                self.exchange_send_failures += 1
        return {"ok": True, "seq": seq, "rows": len(rows)}

    def rpc_exchange(self, table: str, seq: int, rows: list) -> dict:
        """Receive one position-stamped batch from a peer.  Duplicate
        positions are skipped; a batch beyond the local tail is
        refused (the barrier-time catch-up fetch fills the gap from
        the leader — ordered, idempotent delivery without a broker)."""
        with self._lock:
            mgr = self._dml_manager(table)
            try:
                applied = mgr.insert_at(
                    int(seq), [tuple(r) for r in rows]
                )
            except ValueError:
                return {"ok": False, "have": mgr.history_len()}
        self.exchange_rows_in += applied
        self.exchange_batches_in += 1
        return {"ok": True, "applied": applied}

    def rpc_exchange_sparse(self, table: str, seq: int, end: int,
                            vnodes: list | None = None,
                            vn64: str | None = None,
                            rows: list | None = None,
                            own: list | None = None,
                            items: list | None = None) -> dict:
        """Receive one SLICED position-stamped batch (Exchange-lite):
        this host's owned rows (positions derived from the batch's
        vnode log + the covered-vnode set — rows cross the wire once,
        without per-row positions), placeholders elsewhere.
        Idempotent; placeholder holes fill on redelivery; a batch
        beyond the local tail is refused (fence repair fills the gap
        from the leader)."""
        from risingwave_tpu.cluster.exchange import ShuffleService

        payload = {"seq": int(seq), "end": int(end),
                   "vnodes": vnodes or (), "rows": rows or (),
                   "own": own or ()}
        if vn64 is not None:
            payload["vn64"] = vn64
        if items is not None:
            payload["items"] = items
        with self._lock:
            mgr = self._dml_manager(table)
            try:
                applied = ShuffleService.apply_batch(mgr, payload)
            except ValueError:
                return {"ok": False, "have": mgr.history_len()}
        self.exchange_rows_in += applied
        self.exchange_batches_in += 1
        return {"ok": True, "applied": applied}

    def rpc_fetch_table(self, table: str, from_seq: int = 0) -> dict:
        """Peer catch-up: the table's history from a position (the
        handover/new-host backfill and the gap repair path)."""
        with self._lock:
            mgr = self._dml_manager(table)
            return {"seq": int(from_seq),
                    "rows": mgr.history_slice(int(from_seq))}

    def rpc_fetch_slice(self, table: str, from_seq: int = 0,
                        to_seq: int | None = None,
                        vnodes: list | None = None) -> dict:
        """Sliced peer catch-up: one vnode set's rows over a history
        range, plus the vnode log (gap repair on the shuffled path and
        gained-vnode backfill after a repartition).  Positions this
        host never stored are absent — the caller peer-fills."""
        with self._lock:
            mgr = self._dml_manager(table)
            return self.shuffle.slice_history(
                mgr, int(from_seq), to_seq, vnodes or (), table
            )

    def rpc_fetch_positions(self, table: str, positions: list) -> dict:
        """Point catch-up: specific global positions this host holds
        (the peer-fill path when the leader itself has holes — e.g. a
        standby promoted past a dead leader)."""
        with self._lock:
            mgr = self._dml_manager(table)
            items = []
            for p in positions:
                row = mgr.history_row(int(p))
                if row is not None:
                    items.append([int(p), list(row)])
            return {"items": items}

    def rpc_table_len(self, table: str) -> dict:
        with self._lock:
            return {"len": self._dml_manager(table).history_len()}

    def _owned_vnodes_for(self, table: str) -> "set[int] | None":
        """Union of this worker's owned vnodes across partitioned jobs
        reading a SHUFFLED table (None = table not shuffled here)."""
        plan = self.shuffle.table_plan(table)
        if plan is None or plan["mode"] != "shuffle":
            return None
        own: set[int] = set()
        with self._lock:
            for job in self.engine.jobs:
                if getattr(job, "n_vnodes", None) is None:
                    continue
                if table in getattr(job, "shuffle_cols", {}):
                    own |= {int(v) for v in job.vnodes}
        # the standby audits the leader's slice too (it must hold a
        # full copy so a promoted standby can serve every fetch)
        if plan.get("standby") == self.worker_id \
                and plan["leader"] in plan["slices"]:
            own |= {int(v) for v in plan["slices"][plan["leader"]]}
        return own

    def _peer_fill(self, table: str, positions: list[int]) -> int:
        """Fill specific missing positions from any live peer (double-
        failure repair: the leader died and its successor has holes)."""
        filled = 0
        with self._routing_lock:
            peer_ids = [w for w in self._routing["peers"]
                        if w != self.worker_id]
        for wid in peer_ids:
            if not positions:
                break
            try:
                res = self._peer(wid).call(
                    "fetch_positions", table=table,
                    positions=positions,
                )
            except (RpcError, ConnectionError, OSError, KeyError):
                continue
            got = {int(p): tuple(r) for p, r in res["items"]}
            if not got:
                continue
            with self._lock:
                mgr = self._dml_manager(table)
                for p, r in got.items():
                    filled += mgr.insert_sparse(
                        p, p + 1, [(p, r)], []
                    )
            positions = [p for p in positions if p not in got]
        self.exchange_rows_in += filled
        return filled

    def _ensure_table_len(self, table: str, want: int) -> None:
        """Catch the local replica up to the round's consumption fence
        before the barrier runs — exchange drops (chaos) repair here.
        On a shuffled table "caught up" means TWO things: history long
        enough AND every OWNED position below the fence actually holds
        a row (a sliced delivery lost to chaos leaves a hole the
        length check alone would miss)."""
        with self._lock:
            mgr = self._dml_manager(table)
            have = mgr.history_len()
        own = self._owned_vnodes_for(table)
        route = self._table_route(table)
        is_leader = route is not None \
            and route["leader"] == self.worker_id
        if have < want:
            if route is None or is_leader:
                raise RuntimeError(
                    f"{table!r} behind its fence ({have} < {want}) "
                    "with no leader to fetch from"
                )
            if own is None:
                res = self.retry.run(
                    lambda: self._peer(route["leader"]).call(
                        "fetch_table", table=table, from_seq=have),
                    label="fetch_table",
                )
                rows = [tuple(r) for r in res["rows"]
                        if r is not None]
                with self._lock:
                    applied = self._dml_manager(table).insert_at(
                        int(res["seq"]), rows
                    )
            else:
                res = self.retry.run(
                    lambda: self._peer(route["leader"]).call(
                        "fetch_slice", table=table, from_seq=have,
                        to_seq=want, vnodes=sorted(own)),
                    label="fetch_slice",
                )
                with self._lock:
                    applied = self._dml_manager(table).insert_sparse(
                        int(res["seq"]), int(res["end"]),
                        [(int(p), tuple(r)) for p, r in res["items"]],
                        [int(v) for v in res.get("vnodes") or ()],
                    )
            self.exchange_fetches += 1
            self.exchange_rows_in += applied
            if applied:
                self.exchange_batches_in += 1
        if own is None:
            return
        # completeness audit below the fence (sliced path): scan only
        # the still-unconsumed window — holes below every reader's
        # cursor can never be read again
        with self._lock:
            lo = self.engine.table_consumption_floor(table)
            missing = self._dml_manager(table).missing_positions(
                own, lo, want
            )
        if not missing:
            return
        if route is not None and not is_leader:
            try:
                res = self.retry.run(
                    lambda: self._peer(route["leader"]).call(
                        "fetch_positions", table=table,
                        positions=missing),
                    label="fetch_positions",
                )
                got = [(int(p), tuple(r)) for p, r in res["items"]]
                with self._lock:
                    mgr = self._dml_manager(table)
                    for p, r in got:
                        mgr.insert_sparse(p, p + 1, [(p, r)], [])
                self.exchange_fetches += 1
                self.exchange_rows_in += len(got)
                missing = [p for p in missing
                           if p not in {g[0] for g in got}]
            except (RpcError, ConnectionError, OSError, KeyError):
                pass
        if missing:
            self._peer_fill(table, missing)

    # -- RPC surface ----------------------------------------------------
    def rpc_ping(self) -> dict:
        return {"ok": True, "worker_id": self.worker_id,
                "jobs": [j.name for j in self.engine.jobs]}

    def rpc_scale_stats(self) -> dict:
        """Exchange/partition observability (scale_stress asserts the
        per-chunk path flows worker↔worker AND, on shuffled edges,
        that the gate audit counters stayed at zero)."""
        with self._lock:
            parts = self.engine.partition_stats()
        return {
            "exchange_rows_out": self.exchange_rows_out,
            "exchange_rows_in": self.exchange_rows_in,
            "exchange_batches_out": self.exchange_batches_out,
            "exchange_batches_in": self.exchange_batches_in,
            "exchange_fetches": self.exchange_fetches,
            "exchange_send_failures": self.exchange_send_failures,
            "routing_version": self._routing["version"],
            "shuffle": self.shuffle.stats(),
            "gate_dropped": sum(p["gate_dropped"]
                                for p in parts.values()),
            "reader_filtered": sum(p["reader_filtered"]
                                   for p in parts.values()),
            "partition_stats": parts,
            "partitions": {
                j.name: sorted(j.vnodes)
                for j in self.engine.jobs
                if hasattr(j, "vnodes")
            },
        }

    def rpc_metrics(self) -> dict:
        """This worker process' metric surface (exchange counters,
        engine gauges) — per-edge series live HERE; the meta keeps
        per-worker aggregates it retires on death."""
        return {"prometheus": self.engine.metrics.render_prometheus()}

    def rpc_trace_dump(self, trace_id: str | None = None) -> dict:
        """This process' span flight recorder (optionally filtered to
        one trace) — the meta merges per-role dumps into the round
        timeline ``ctl cluster trace`` renders."""
        return {"role": GLOBAL_TRACE.role,
                "spans": GLOBAL_TRACE.dump(trace_id)}

    def rpc_adopt(self, ddl: list, name: str, recover: bool = True,
                  vnodes: list | None = None, n_vnodes: int = 0,
                  ckpt_key: str | None = None) -> dict:
        """Adopt (or extend) a streaming job: replay its DDL, then
        recover from the last durable checkpoint (exact replay: the
        checkpoint holds state + source cursors of the same commit).

        With ``vnodes`` the meta asks for a PARTITIONED adoption: the
        job is rebuilt as one vnode partition (gate before the agg,
        checkpoint lineage ``ckpt_key``) owning the given set.  An
        ineligible plan answers ``partitioned: false`` and stays a
        whole job — the meta falls back to job-level placement."""
        from risingwave_tpu.sql.planner import PlanError

        with self._lock:
            # a (re-)adoption invalidates any cached seal: the next
            # round must run against the recovered state
            self._round_cache.pop(name, None)
            if vnodes is None:
                epoch = self.engine.adopt_job(list(ddl), name,
                                              recover=recover)
                return {"ok": True, "committed_epoch": epoch,
                        "partitioned": False}
            self.engine.adopt_job(list(ddl), name, recover=False)
            try:
                spec = self.engine.partition_job(
                    name, int(n_vnodes), ckpt_key or name
                )
            except PlanError as e:
                # not scale-eligible: finish as a plain adoption
                entry = self.engine.catalog.get(name)
                if recover:
                    entry.job.recover()
                return {"ok": True, "partitioned": False,
                        "reason": str(e),
                        "committed_epoch": entry.job.committed_epoch}
            entry = self.engine.catalog.get(name)
            if recover:
                # the partition's OWN lineage (failover / meta restart)
                entry.job.recover()
            self.engine.set_job_vnodes(name, vnodes)
            return {"ok": True, "partitioned": True,
                    "committed_epoch": entry.job.committed_epoch,
                    **spec}

    def rpc_repartition(self, job: str, vnodes: list, transfers: list,
                        rewind_epoch: int | None = None) -> dict:
        """One handover step on this worker's partition (see
        Engine.repartition_job).  Clears the round cache — ownership
        changed, a cached seal must never answer for the new set."""
        with self._lock:
            self._round_cache.pop(job, None)
            res = self.engine.repartition_job(
                job, vnodes, list(transfers or ()),
                rewind_epoch=rewind_epoch,
            )
        return {"ok": True, **res}

    def rpc_release(self, job: str) -> dict:
        """Drop a partition that lost its last vnode (scale-in): the
        MV leaves this engine; sources (and their histories) stay for
        a future re-adoption."""
        with self._lock:
            self._round_cache.pop(job, None)
            if job in self.engine.catalog:
                self.engine.execute(
                    f"DROP MATERIALIZED VIEW {job}"
                )
        return {"ok": True}

    def rpc_barrier(self, job: str, chunks: int = 1,
                    round: int = 0, limits: dict | None = None) -> dict:
        """Process ``chunks`` chunks + one barrier for one job — the
        meta's global round, applied locally.  Returns the SEALED
        epoch immediately (the checkpoint upload runs in the job's
        background uploader) plus the round's MV export SSTs; meta
        polls ``job_epochs`` for the durable ack before committing the
        cluster epoch.  ``round`` tags the call for idempotence: a
        replay of the round we last sealed answers from the cache
        without touching the engine (the meta retries barriers whose
        response was lost in flight).  ``limits`` is the round's
        consumption fence per replicated DML table (scale plane): the
        local replica first catches up to the fence over the peer
        exchange, then consumes exactly up to it — every partition of
        a job sees the identical prefix per round."""
        rnd = int(round or 0)
        if limits:
            for table, want in limits.items():
                try:
                    self._ensure_table_len(table, int(want))
                except (ValueError, KeyError):
                    pass  # not a hosted DML table on this worker
        with self._lock:
            cached = self._round_cache.get(job) if rnd else None
            if cached is not None and cached["round"] == rnd \
                    and cached["result"] is not None:
                return cached["result"]
            if cached is not None and cached["round"] == rnd:
                # chunks already ran for this round; only the export/
                # response was lost — redo the cheap tail
                sealed = cached["sealed"]
            else:
                sealed = self.engine.tick_job(job, int(chunks),
                                              source_limits=limits)
                if rnd:
                    self._round_cache[job] = {"round": rnd,
                                              "sealed": sealed,
                                              "result": None}
            from risingwave_tpu.storage.integrity import IntegrityError

            corrupt: list[str] = []
            t0 = time.perf_counter()
            try:
                with GLOBAL_TRACE.span("mv_export", job=job) as _sp:
                    ssts = self.engine.export_mv_deltas(job, sealed)
                    _sp.set(ssts=len(ssts))
            except IntegrityError as e:
                # a corrupt shared SST under the export's diff-base
                # seeding: seal the round anyway (exports retry next
                # round) and surface the key so the meta repairs it
                ssts = []
                if e.key:
                    corrupt.append(e.key)
            self.engine.metrics.observe(
                "barrier_phase_seconds", time.perf_counter() - t0,
                job=job, phase="mv_export",
            )
            positions = self.engine.job_epochs(job)
            res = {"ok": True, "committed_epoch": sealed,
                   "sealed_epoch": sealed,
                   "durable_epoch": positions["durable"],
                   "ssts": ssts, "corrupt": corrupt,
                   # pushdown plane: expiry-policy docs staged by this
                   # round's exports (None = DROP); the meta folds
                   # them into the same manifest delta as the SSTs
                   "policies": self.engine.take_pending_policies(),
                   # cheap exchange summary (host counters only): the
                   # meta mirrors these as per-worker gauges retired
                   # with the worker
                   "exchange": {
                       "rows_out": self.exchange_rows_out,
                       "rows_in": self.exchange_rows_in,
                       "batches_out": self.exchange_batches_out,
                       "batches_in": self.exchange_batches_in,
                       "send_failures": self.exchange_send_failures,
                   }}
            if rnd:
                self._round_cache[job]["result"] = res
        return res

    def rpc_reexport(self, job: str, exclude: list | None = None) -> dict:
        """Integrity repair: re-export the job's MVs IN FULL against a
        diff base re-seeded from the shared manifest MINUS the
        quarantined keys in ``exclude`` — upserts for every row the
        corrupt SST carried, tombstones for rows it shadowed.  The meta
        commits the returned SSTs atomically with the corrupt object's
        removal."""
        with self._lock:
            ssts = self.engine.reexport_job_mvs(
                job, exclude=exclude or ())
        return {"ok": True, "ssts": ssts}

    def rpc_repair_checkpoint(self, lineage: str) -> dict:
        """Integrity repair: verify + truncate one checkpoint lineage
        this worker owns (quarantine corrupt epoch objects, rewind the
        chain to the last verified epoch).  The next save re-bases with
        a full snapshot, so the lineage converges forward; a recovery
        in the window rewinds to the verified epoch and the meta's
        round-credit rewind replays the gap."""
        with self._lock:
            if self.engine.checkpoint_store is None:
                return {"ok": False, "reason": "no durable store"}
            rep = self.engine.checkpoint_store.repair_lineage(lineage)
        return {"ok": True, **rep}

    def rpc_job_epochs(self, job: str) -> dict:
        """Seal-vs-durable positions of one job (also services its
        pending upload acks — see Engine.job_epochs)."""
        with self._lock:
            return self.engine.job_epochs(job)

    def rpc_serve(self, sql: str, query_epoch: int = 0,
                  vnodes: list | None = None) -> dict:
        """Batch read; ``query_epoch`` pins the retained checkpoint of
        the meta's last cluster commit (reads never see state a global
        commit hasn't covered).  ``vnodes`` narrows a partitioned MV
        read to the vnode set this partition owned AT THE PINNED ROUND
        (the meta fans a partitioned read across owners and unions the
        disjoint slices)."""
        qe = int(query_epoch or 0)
        with self._lock:
            if qe:
                self.engine.session_config.set("query_epoch", qe)
            if vnodes is not None:
                self.engine._serve_vnodes = frozenset(
                    int(v) for v in vnodes
                )
            try:
                cols, rows = self.engine.query(sql)
            finally:
                if qe:
                    self.engine.session_config.set("query_epoch", 0)
                self.engine._serve_vnodes = None
        return {"cols": cols, "rows": [list(r) for r in rows]}

    def rpc_faults(self) -> dict:
        """This process' chaos counters (aggregated by the meta's
        ``cluster_faults`` for the ctl surface)."""
        fabric = get_fabric()
        upload_retries = 0
        for j in self.engine.jobs:
            up = j._uploader
            if up is not None:
                upload_retries += getattr(up, "retries_total", 0)
        return {
            "fabric": fabric.stats() if fabric is not None else None,
            "rpc_retries_total": self.retry.retries,
            "rpc_retry_gave_up_total": self.retry.gave_up,
            "heartbeat_failures": self.heartbeat_failures,
            "registrations": self.registrations,
            "checkpoint_upload_retries_total": upload_retries,
            # the worker↔worker exchange seam (scale_storm and
            # shuffle_storm assert the fabric's faults here were
            # absorbed/repaired)
            "exchange_rows_out": self.exchange_rows_out,
            "exchange_rows_in": self.exchange_rows_in,
            "exchange_fetches": self.exchange_fetches,
            "exchange_send_failures": self.exchange_send_failures,
        }
