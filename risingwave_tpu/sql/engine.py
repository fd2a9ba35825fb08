"""The single-process SQL engine: DDL, streaming jobs, serving reads.

Reference counterparts: the frontend ``handler`` dispatch
(src/frontend/src/handler/mod.rs:278), meta's DDL controller + barrier
scheduler (SURVEY.md §2.4), and the batch local-execution mode
(src/frontend/src/scheduler/local.rs:60) — collapsed into one object:

    eng = Engine()
    eng.execute("CREATE SOURCE bid (...) WITH (connector='nexmark', ...)")
    eng.execute("CREATE MATERIALIZED VIEW v AS SELECT ...")
    eng.tick(barriers=5)          # the global barrier loop
    eng.execute("SELECT * FROM v ORDER BY x LIMIT 10")   # serving read
"""

from __future__ import annotations

import dataclasses
import sys
import time
from collections import deque
from typing import Any, Callable, Sequence

import numpy as np

from risingwave_tpu.common.chunk import Chunk, split_col
from risingwave_tpu.common.config import RwConfig, SessionConfig, SystemParams
from risingwave_tpu.common.metrics import MetricsRegistry
from risingwave_tpu.common.trace import GLOBAL_TRACE
from risingwave_tpu.common.types import DataType, Field, Schema
from risingwave_tpu.connector.nexmark import (
    AUCTION_SCHEMA,
    BID_SCHEMA,
    PERSON_SCHEMA,
    NexmarkConfig,
    NexmarkGenerator,
    NexmarkSplitReader,
)
from risingwave_tpu.meta.catalog import Catalog, CatalogEntry
from risingwave_tpu.sql import ast
from risingwave_tpu.sql.binder import Binder, Scope
from risingwave_tpu.sql.parser import parse
from risingwave_tpu.sql.planner import (
    DagPlan,
    MvTap,
    PlanError,
    Planner,
    PlannerConfig,
    UnaryPlan,
)
from risingwave_tpu.storage.checkpoint_store import _mc_encode_value
from risingwave_tpu.stream.dag import DagJob, FragNode, JoinNode
from risingwave_tpu.stream.materialize import view_rows
from risingwave_tpu.stream.runtime import StreamingJob


def _ast_map(node, fn):
    """Bottom-up structural map over the (frozen-dataclass) SQL AST."""
    if dataclasses.is_dataclass(node) and not isinstance(node, type):
        changed = {}
        for f in dataclasses.fields(node):
            v = getattr(node, f.name)
            nv = _ast_map(v, fn)
            if nv is not v:
                changed[f.name] = nv
        if changed:
            node = dataclasses.replace(node, **changed)
        return fn(node)
    if isinstance(node, tuple):
        mapped = tuple(_ast_map(x, fn) for x in node)
        return mapped if any(m is not x for m, x in zip(mapped, node)) \
            else node
    if isinstance(node, list):
        mapped = [_ast_map(x, fn) for x in node]
        return mapped if any(m is not x for m, x in zip(mapped, node)) \
            else node
    return node


def inline_udfs(stmt, udfs: dict, depth: int = 0):
    """Expand SQL-UDF calls by AST substitution (the reference inlines
    SQL UDFs in the frontend binder the same way)."""
    if not udfs:
        return stmt
    if depth > 8:
        raise ValueError("SQL UDF recursion exceeds depth 8")

    def expand(node):
        if not isinstance(node, ast.FuncCall) or node.name not in udfs:
            return node
        params, body = udfs[node.name]
        if len(node.args) != len(params):
            raise ValueError(
                f"{node.name} takes {len(params)} arguments, "
                f"got {len(node.args)}"
            )
        sub = dict(zip(params, node.args))

        def substitute(n):
            if isinstance(n, ast.ColumnRef) and n.table is None \
                    and n.name in sub:
                return sub[n.name]
            return n

        expanded = _ast_map(body, substitute)
        # the body may itself call UDFs
        return inline_udfs(expanded, udfs, depth + 1)

    return _ast_map(stmt, expand)


def _empty_chunk(schema: Schema, cap: int) -> Chunk:
    """All-invalid chunk prototype (shape-only trace input for audits)."""
    import jax.numpy as jnp

    from risingwave_tpu.common.chunk import NCol, StrCol

    cols = []
    for f in schema:
        if f.data_type.is_string:
            col = StrCol(
                jnp.zeros((cap, f.str_width), jnp.uint8),
                jnp.zeros((cap,), jnp.int32),
            )
        else:
            col = jnp.zeros((cap,), f.data_type.physical_dtype)
        if f.nullable:
            col = NCol(col, jnp.zeros((cap,), jnp.bool_))
        cols.append(col)
    return Chunk(
        tuple(cols), jnp.zeros((cap,), jnp.int8),
        jnp.zeros((cap,), jnp.bool_), schema,
    )


def _join_exchange_keys(key_exprs, chunk):
    """Evaluate join keys for vnode routing, nullability-normalized.

    compute_vnodes hashes an NCol as [zeroed-payload, null-flag] but a
    plain column as [payload] — so a key nullable on one join side and
    NOT NULL on the other would route equal non-NULL values to
    different shards.  Join equality discards NULL keys anyway (they
    match nothing), so routing hashes the zeroed payload alone: equal
    non-NULL values collide regardless of declared nullability, and
    NULL-keyed rows land (consistently) with payload-zero rows, where
    they emit as unmatched like anywhere else."""
    from risingwave_tpu.common.hash import normalize_null_col

    keys = []
    for e in key_exprs:
        keys.append(normalize_null_col(e.eval(chunk))[0])
    return keys


class Engine:
    def __init__(self, config: "PlannerConfig | RwConfig | None" = None,
                 data_dir: str | None = None, role: str = "single"):
        self.catalog = Catalog()
        if isinstance(config, RwConfig):
            self.rw_config = config
            self.config = PlannerConfig(
                chunk_capacity=config.streaming.chunk_size,
                **dataclasses.asdict(config.state),
            )
            data_dir = data_dir or config.storage.data_directory
        else:
            self.rw_config = RwConfig()
            self.config = config or PlannerConfig()
        self.planner = Planner(self.catalog, self.config)
        self.jobs: list[Any] = []
        self.system_params = SystemParams()
        self.session_config = SessionConfig()
        # per-engine registry: restarted engines must not inherit a
        # dead engine's counters for same-named jobs
        self.metrics = MetricsRegistry()
        #: rolling per-job barrier latencies feeding the
        #: ``barrier_spike_ratio`` gauge (p99/median over the window)
        self._barrier_lat: dict[str, deque] = {}
        self.checkpoint_store = None
        #: SQL UDFs: name -> (param names, body expr AST), inlined at
        #: parse time (ref: frontend SQL-UDF inlining)
        self.functions: dict[str, tuple] = {}
        self.meta_store = None
        #: the Hummock-lite storage service (object store + versioned
        #: manifest + background compactor + vacuum); built alongside
        #: the checkpoint store whenever the engine is durable
        self.hummock = None
        self.compactor = None
        #: True while replaying the durable DDL/DML logs (suppresses
        #: re-logging)
        self._replaying = False
        #: "single" owns every durable subsystem; "compute" is a
        #: cluster worker — it shares the cluster's checkpoint store
        #: but the META process owns the DDL log and the version
        #: manifest (a second VersionManager over the same object
        #: store would fork the version chain)
        self.role = role
        #: shared object store for MV export SSTs in compute role (the
        #: META owns the version manifest over the same store; workers
        #: only upload objects and hand descriptors back)
        self.shared_store = None
        #: key allocator for exported SSTs (cluster workers point this
        #: at the meta's ``alloc_sst`` RPC — single-allocator keys
        #: never collide across workers and stay vacuum-protected
        #: until their round commits)
        self.sst_key_allocator = None
        #: last exported (key → pickled row) per MV — the incremental
        #: export diff base; seeded from the shared manifest on adopt
        self._exported: dict[str, dict] = {}
        #: MV names whose serve-schema doc this process already
        #: published; CREATE/DROP INDEX discards the upstream so the
        #: doc republishes with the new index list on the next export
        self._schema_published: set = set()
        #: per-read vnode override for partitioned MV serving (the
        #: cluster worker pins reads to the map at the pinned round)
        self._serve_vnodes = None
        #: SST keys the export diff-base seeding must skip (quarantined
        #: corrupt objects mid-repair — see reexport_job_mvs)
        self._seed_exclude: frozenset = frozenset()
        #: pushdown plane — per-TTL-MV expiry horizons (max observed
        #: leading export-pk value − ttl, MONOTONE per table: the
        #: watermark proxy derived at export time) and the matching
        #: storage-key cutoffs (``expire_below`` bounds) the export
        #: path filters both sides of its diff through
        self._ttl_horizons: dict[str, int] = {}
        self._ttl_cutoffs: dict[str, bytes] = {}
        #: policy docs staged for the NEXT barrier response (cluster
        #: compute role): the meta folds them into the same manifest
        #: delta that commits the round's export SSTs
        self.pending_policies: dict = {}
        if data_dir is not None and role == "compute":
            import os as _os

            from risingwave_tpu.storage import CheckpointStore
            from risingwave_tpu.storage.hummock import (
                LocalFsObjectStore,
            )
            self.checkpoint_store = CheckpointStore(
                data_dir,
                keep_epochs=self.rw_config.storage.checkpoint_keep_epochs,
                metrics=self.metrics,
            )
            self.shared_store = LocalFsObjectStore(
                _os.path.join(data_dir, "hummock")
            )
        elif data_dir is not None:
            import os as _os

            from risingwave_tpu.meta.store import MetaStore
            from risingwave_tpu.storage import CheckpointStore
            from risingwave_tpu.storage.hummock import (
                CompactorService,
                HummockStorage,
                LocalFsObjectStore,
            )
            self.checkpoint_store = CheckpointStore(
                data_dir,
                keep_epochs=self.rw_config.storage.checkpoint_keep_epochs,
                metrics=self.metrics,
            )
            self.meta_store = MetaStore(data_dir)
            self.hummock = HummockStorage(
                LocalFsObjectStore(_os.path.join(data_dir, "hummock")),
                metrics=self.metrics,
            )
            # not started: tests/embedded use drive compaction
            # synchronously; long-running nodes call
            # start_storage_service() (server.py does)
            self.compactor = CompactorService(self.hummock)
            if self.meta_store.has_catalog():
                self._bootstrap()

    def _bootstrap(self) -> None:
        """Cold-start recovery (ref DdlController + recovery,
        ddl_controller.rs:1096, SURVEY.md §3.5): replay the durable DDL
        log to rebuild catalog + jobs, reload each DML table's history,
        then restore every job's state and source cursors from the last
        committed checkpoint."""
        self._replaying = True
        try:
            for sql in self.meta_store.ddl_log():
                self.execute(sql)
            self.recover()
        finally:
            self._replaying = False

    # ------------------------------------------------------------------
    #: DDL statement kinds recorded in the durable catalog log — the
    #: full set whose replay reconstructs catalog + job topology +
    #: plan-relevant parameters (session SETs included: they steer
    #: planning, e.g. streaming_parallelism)
    _LOGGED_DDL = (
        ast.CreateSource, ast.CreateMaterializedView, ast.CreateSink,
        ast.CreateIndex, ast.CreateFunction, ast.DropStatement,
        ast.AlterParallelism, ast.SetStatement,
    )

    def execute(self, sql: str):
        """Run one or more statements; returns the last result."""
        from risingwave_tpu.sql.parser import parse_with_text

        result = None
        for text, stmt in parse_with_text(sql):
            # a window the served ticker sent ahead is sealed and
            # durable before any statement sees the jobs
            self.settle(
                "flush" if isinstance(stmt, ast.FlushStatement)
                else "ddl" if isinstance(stmt, self._LOGGED_DDL)
                else "statement")
            # the statement's raw SQL, recorded as the catalog entry's
            # definition (re-parseable — job export/adoption ships it)
            self._stmt_text = text
            if isinstance(stmt, ast.CreateFunction):
                result = self._create_function(stmt)
            else:
                result = self._execute_one(
                    inline_udfs(stmt, self.functions)
                )
            if isinstance(stmt, self._LOGGED_DDL):
                # DDL (or a planner-relevant SET) invalidates cached
                # serving pipelines
                self._serving_cache = {}
                if self.meta_store is not None and not self._replaying:
                    self.meta_store.append_ddl(text)
        return result

    def _definition_text(self, stmt) -> str:
        """The statement's original SQL (stashed by execute()) — the
        catalog entry's re-parseable definition, shipped verbatim when
        a job is exported/adopted across processes."""
        return getattr(self, "_stmt_text", None) or str(stmt)

    def _create_function(self, stmt: ast.CreateFunction):
        """Register a SQL UDF (ref: frontend SQL UDF inlining)."""
        if stmt.name in self.functions:
            if stmt.if_not_exists:
                return None
            raise ValueError(f"function {stmt.name!r} already exists")
        body = parse(stmt.body_sql)
        if len(body) != 1 or not isinstance(body[0], ast.Select) \
                or body[0].from_ is not None or len(body[0].items) != 1:
            raise ValueError(
                "SQL UDF body must be a single SELECT <expr>"
            )
        self.functions[stmt.name] = (
            tuple(stmt.params), body[0].items[0].expr
        )
        return None

    def query(self, sql: str):
        """Run statements; returns (column_names, rows) for wire clients."""
        self._last_columns = None
        rows = self.execute(sql)
        if rows is None:
            return [], []
        cols = self._last_columns
        if cols is None:
            cols = [f"col{i}" for i in range(len(rows[0]))] if rows else []
        return cols, rows

    def _execute_one(self, stmt):
        # column names are per-statement: a trailing non-SELECT must not
        # inherit an earlier SELECT's RowDescription
        self._last_columns = None
        #: bound Fields of the last SELECT's output (type-aware result
        #: rendering, e.g. timestamps in the slt runner); None when the
        #: serving path doesn't track them
        self._last_fields = None
        if isinstance(stmt, ast.CreateSource):
            return self._create_source(stmt)
        if isinstance(stmt, ast.CreateMaterializedView):
            return self._create_mview(stmt)
        if isinstance(stmt, ast.CreateIndex):
            return self._create_index(stmt)
        if isinstance(stmt, ast.CreateSink):
            return self._create_sink(stmt)
        if isinstance(stmt, ast.DropStatement):
            entry = self.catalog.get(stmt.name) \
                if stmt.name in self.catalog else None
            if entry is not None:
                want = {"source": "source", "table": "source",
                        "materialized view": "mview",
                        "sink": "sink", "index": "mview"}[stmt.kind]
                if entry.kind != want:
                    raise ValueError(
                        f"{stmt.name} is a {entry.kind}, not a {want}"
                    )
                if stmt.kind == "index" and entry.index_on is None:
                    raise ValueError(f"{stmt.name} is not an index")
                if entry.kind == "mview" and entry.index_on is None:
                    deps = [e.name for e in self.catalog.list("mview")
                            if e.index_on is not None
                            and e.index_on[0] == stmt.name]
                    if deps:
                        raise ValueError(
                            f"cannot drop {stmt.name!r}: indexes "
                            f"{deps} depend on it (DROP INDEX first)"
                        )
                if entry.kind == "mview":
                    # the shared serving keyspace forgets the MV too:
                    # tombstones for its exported rows + schema doc
                    # removed, so serving answers "does not exist"
                    # instead of stale rows
                    self._tombstone_dropped_mv(entry)
                if entry.job is not None:
                    job = entry.job
                    shared = isinstance(job, DagJob) and any(
                        e is not entry and e.job is job
                        for e in self.catalog.list()
                    )
                    if shared:
                        # removing only this MV's nodes; raises while
                        # dependent (cascaded) MVs still consume them
                        job.remove_nodes(entry.dag_nodes)
                        # this MV's private readers must stop being
                        # pulled once nothing consumes them
                        job.remove_sources(entry.dag_sources or [])
                        if not self._replaying:
                            job.reseed_checkpoint()
                    else:
                        self.jobs.remove(job)
                if entry.kind == "sink" and entry.mv_executor is not None:
                    entry.mv_executor.sink.close()
                if entry.dml is not None and self.meta_store is not None \
                        and not self._replaying:
                    # the durable history dies with the table; NOT at
                    # replay — there the log already holds only the
                    # final generation's rows
                    self.meta_store.truncate_dml(stmt.name)
                if entry.kind == "mview":
                    # DROP MV / DROP INDEX sweeps the scrape surface:
                    # the entry's own job-labeled series always; the
                    # underlying job's only when the job itself died
                    # (an index on a shared DAG leaves the host MV's
                    # series alone)
                    self._retire_job_series(entry.name)
                    if entry.job is not None \
                            and entry.job not in self.jobs:
                        self._retire_job_series(entry.job.name)
            self.catalog.drop(stmt.name, stmt.if_exists)
            return None
        if isinstance(stmt, ast.ShowStatement):
            kind = {"sources": "source", "tables": "source",
                    "materialized views": "mview",
                    "sinks": "sink"}.get(stmt.kind)
            return [(e.name,) for e in self.catalog.list(kind)]
        if isinstance(stmt, ast.FlushStatement):
            # ref FLUSH semantics (handler/flush.rs): block until all
            # DML issued so far is materialized and checkpointed — here:
            # drain every bounded source's pending rows, then commit one
            # barrier.  Unbounded sources (nexmark/datagen) have no
            # pending() and are excluded (they never drain).
            cpb = max(
                1, int(self.system_params.get("chunks_per_barrier"))
            )
            for _ in range(4096):
                pending, stuck = 0, []
                for job in self.jobs:
                    srcs = job.sources.values() \
                        if hasattr(job, "sources") else [job.source]
                    n = sum(s.pending() for s in srcs
                            if hasattr(s, "pending"))
                    pending += n
                    if n and job.ingest_hold is not None:
                        stuck.append(f"{job.name}: {job.ingest_hold}")
                if pending == 0:
                    break
                if stuck:
                    # these will not drain: say so now, not 4096
                    # empty ticks later
                    raise RuntimeError(
                        f"FLUSH cannot drain ({pending} rows pending): "
                        "ingest held at a view's high-water mark ("
                        + "; ".join(stuck) + ")")
                self.tick(barriers=1, chunks_per_barrier=cpb)
            else:
                raise RuntimeError(
                    "FLUSH did not drain in 4096 barriers "
                    f"({pending} rows still pending)"
                )
            self.tick(barriers=1, chunks_per_barrier=0)
            return None
        if isinstance(stmt, ast.SetStatement):
            if stmt.system:
                self.system_params.set(stmt.name, stmt.value)
            else:
                self.session_config.set(stmt.name, stmt.value)
            return None
        if isinstance(stmt, ast.DescribeStatement):
            entry = self.catalog.get(stmt.name)
            self._last_columns = ["name", "type"]
            return [(f.name, f.data_type.value) for f in entry.schema]
        if isinstance(stmt, ast.ShowParameters):
            return self.session_config.show_all() + [
                (k, str(v), "system")
                for k, v in sorted(self.system_params.to_dict().items())
            ]
        if isinstance(stmt, ast.Explain):
            return self._explain(stmt.statement)
        if isinstance(stmt, ast.AlterParallelism):
            return self._alter_parallelism(stmt)
        if isinstance(stmt, ast.Insert):
            return self._insert(stmt)
        if isinstance(stmt, ast.Delete):
            return self._delete(stmt)
        if isinstance(stmt, ast.Update):
            return self._update(stmt)
        if isinstance(stmt, ast.Select):
            return self._serve(stmt)
        raise ValueError(f"unhandled statement {stmt!r}")

    def _alter_parallelism(self, stmt: ast.AlterParallelism):
        """Online rescale of a running sharded MV at a barrier (ref
        ScaleController reschedule, scale.rs:224)."""
        from risingwave_tpu.stream.sharded import ShardedStreamingJob

        entry = self.catalog.get(stmt.name)
        if entry.kind != "mview" or not isinstance(
            entry.job, ShardedStreamingJob
        ):
            raise ValueError(
                f"{stmt.name} is not a sharded materialized view "
                "(linear jobs re-plan via DROP + CREATE with "
                "streaming_parallelism set)"
            )
        import jax as _jax
        n = stmt.parallelism
        if n < 2 or n > len(_jax.devices()):
            raise ValueError(
                f"parallelism {n} outside [2, {len(_jax.devices())}]"
            )
        entry.job.rescale(n)
        # retained checkpoints hold the OLD state-tree shape; re-seed
        # so recovery restores the new topology (recover() rebuilds the
        # mesh to the checkpoint's shard dim).  During bootstrap replay
        # the states are fresh — the real checkpoint must NOT be
        # overwritten; the trailing recover() will rescale-restore.
        if self.checkpoint_store is not None and not self._replaying:
            entry.job.reseed_checkpoint()
            entry.job.drain_uploads()
        return None

    def _dml_rows(self, stmt, entry, verb: str) -> list[tuple]:
        """Coerce INSERT/DELETE literal rows to the table schema."""
        schema = entry.schema
        if stmt.columns:
            order = [schema.index_of(c) for c in stmt.columns]
            if len(set(order)) != len(order):
                raise ValueError(f"{verb} lists a column twice")
            for i in set(range(len(schema))) - set(order):
                if not schema[i].nullable:
                    raise ValueError(
                        f"{verb} omits NOT NULL column {schema[i].name}"
                    )
        else:
            order = list(range(len(schema)))
        rows = []
        for r in stmt.rows:
            if len(r) != len(order):
                raise ValueError(f"{verb} arity mismatch")
            vals = [None] * len(schema)
            for pos, e in zip(order, r):
                vals[pos] = _coerce_const(
                    _const_value(e), schema[pos]
                )
            rows.append(tuple(vals))
        return rows

    def _insert(self, stmt: ast.Insert):
        entry = self.catalog.get(stmt.table)
        if entry.dml is None:
            raise ValueError(f"{stmt.table} is not an INSERT-able table")
        rows = self._dml_rows(stmt, entry, "INSERT")
        entry.dml.insert(rows)
        if self.meta_store is not None and not self._replaying:
            self.meta_store.append_dml(stmt.table, rows)
        return None

    def _delete(self, stmt: "ast.Delete"):
        """Exact-full-row retraction on a table created WITH
        (retract = 'true').  The marked rows (marker-tail encoding,
        connector/dml.py) are appended to the same history log, so the
        durable DML journal, exchange slicing, and replay all carry
        the op for free."""
        from risingwave_tpu.connector.dml import mark_deletes

        entry = self.catalog.get(stmt.table)
        if entry.dml is None:
            raise ValueError(f"{stmt.table} is not a DML table")
        if entry.append_only:
            raise ValueError(
                f"{stmt.table} is append-only; CREATE TABLE ... WITH "
                "(retract = 'true') to enable DELETE"
            )
        rows = self._dml_rows(stmt, entry, "DELETE")
        marked = mark_deletes(rows, len(entry.schema))
        entry.dml.insert(marked)
        if self.meta_store is not None and not self._replaying:
            self.meta_store.append_dml(stmt.table, marked)
        return None

    def _update(self, stmt: "ast.Update"):
        """``UPDATE t SET col = lit, ... WHERE <full-pk equality>`` —
        sugar over the exact-full-row retraction pair: resolve the
        live old row by pk from the table's own history log, then emit
        the SAME marked-delete + insert the workload generator would
        have shipped.  The pair lands in the durable DML journal as
        rows (not SQL), so cold-start replay reloads it like any other
        batch."""
        from risingwave_tpu.connector.dml import (
            mark_deletes,
            row_is_delete,
        )

        entry = self.catalog.get(stmt.table)
        if entry.dml is None:
            raise ValueError(f"{stmt.table} is not a DML table")
        if entry.append_only:
            raise ValueError(
                f"{stmt.table} is append-only; CREATE TABLE ... WITH "
                "(retract = 'true') to enable UPDATE"
            )
        if not entry.stream_key:
            raise ValueError(
                f"{stmt.table} has no PRIMARY KEY; UPDATE needs a "
                "full-pk WHERE"
            )
        schema = entry.schema
        width = len(schema)
        pk = set(entry.stream_key)

        def conjuncts(e):
            if isinstance(e, ast.BinaryOp) and e.op == "and":
                return conjuncts(e.left) + conjuncts(e.right)
            return [e]

        eq: dict[int, object] = {}
        for c in conjuncts(stmt.where):
            if not (isinstance(c, ast.BinaryOp) and c.op == "equal"):
                raise ValueError(
                    "UPDATE WHERE must be a conjunction of full-pk "
                    "equalities"
                )
            left, right = c.left, c.right
            if isinstance(left, ast.Literal) \
                    and isinstance(right, ast.ColumnRef):
                left, right = right, left
            if not isinstance(left, ast.ColumnRef):
                raise ValueError(
                    "UPDATE WHERE must compare columns to literals"
                )
            i = schema.index_of(left.name)
            if i is None:
                raise ValueError(
                    f"column {left.name!r} does not exist in "
                    f"{stmt.table!r}"
                )
            eq[i] = _coerce_const(_const_value(right), schema[i])
        if set(eq) != pk:
            raise ValueError(
                "UPDATE WHERE must pin exactly the full primary key"
            )

        sets: dict[int, object] = {}
        for col, expr in stmt.assignments:
            i = schema.index_of(col)
            if i is None:
                raise ValueError(
                    f"column {col!r} does not exist in {stmt.table!r}"
                )
            if i in pk:
                raise ValueError(
                    "UPDATE cannot assign a primary-key column "
                    "(retract + insert instead)"
                )
            if i in sets:
                raise ValueError(f"UPDATE assigns {col!r} twice")
            sets[i] = _coerce_const(_const_value(expr), schema[i])

        # fold the table's history as a multiset to find the live old
        # row under this pk (inserts +1, marked deletes −1) — the same
        # arithmetic every retraction-capable operator applies
        count: dict[tuple, int] = {}
        for row in entry.dml.history_slice(0):
            if row is None:
                continue  # shuffled-follower placeholder
            t = tuple(row)
            base = t[:width]
            if any(base[i] != eq[i] for i in pk):
                continue
            if row_is_delete(t, width):
                count[base] = count.get(base, 0) - 1
            else:
                count[base] = count.get(base, 0) + 1
        live = [b for b, n in count.items() if n > 0]
        if not live:
            raise ValueError(
                f"UPDATE matched no live row in {stmt.table!r}"
            )
        if len(live) > 1:
            raise ValueError(
                f"UPDATE pk matched {len(live)} live rows in "
                f"{stmt.table!r} (history is inconsistent)"
            )
        old = live[0]
        new_row = tuple(sets.get(i, old[i]) for i in range(width))
        rows = mark_deletes([old], width) + [new_row]
        entry.dml.insert(rows)
        if self.meta_store is not None and not self._replaying:
            self.meta_store.append_dml(stmt.table, rows)
        return None

    def _explain(self, stmt) -> list[tuple[str]]:
        """Plan description (ref handler/explain.rs, simplified)."""
        if isinstance(stmt, ast.CreateMaterializedView):
            query = stmt.query
        elif isinstance(stmt, ast.Select):
            query = stmt
        else:
            return [(f"DDL: {type(stmt).__name__}",)]
        plan = self.planner.plan(query)
        lines: list[tuple[str]] = []
        if isinstance(plan, UnaryPlan):
            lines.append(("StreamJob",))
            lines.append((f"  Source: {type(plan.reader).__name__}",))
            for ex in plan.fragment.executors:
                lines.append((f"  {ex!r}",))
        else:
            lines.append(("StreamJob (dataflow graph)",))
            for name, reader in plan.sources.items():
                kind = "MvTap" if isinstance(reader, MvTap) \
                    else type(reader).__name__
                lines.append((f"  source {name}: {kind}",))
            for i, node in enumerate(plan.nodes):
                if isinstance(node, JoinNode):
                    lines.append((
                        f"  node {i} <- {node.left}, {node.right}: "
                        f"HashJoin(keys={len(node.join.left_keys)})",
                    ))
                    continue
                lines.append((f"  node {i} <- {node.input}:",))
                for ex in node.fragment.executors:
                    lines.append((f"    {ex!r}",))
        return lines

    # -- DDL -------------------------------------------------------------
    def _create_source(self, stmt: ast.CreateSource):
        connector = stmt.with_options.get("connector")
        if connector is None and stmt.is_table:
            entry = self._dml_table(stmt)
        elif connector == "nexmark":
            entry = self._nexmark_source(stmt)
        elif connector == "datagen":
            entry = self._datagen_source(stmt)
        elif connector == "filetail":
            entry = self._filetail_source(stmt)
        else:
            raise ValueError(
                f"unsupported connector {connector!r} "
                "(nexmark, datagen, filetail available this round)"
            )
        self.catalog.create(entry, stmt.if_not_exists)
        return None

    def _nexmark_source(self, stmt: ast.CreateSource) -> CatalogEntry:
        opts = stmt.with_options
        table = opts.get("nexmark.table", stmt.name)
        base = {"bid": BID_SCHEMA, "auction": AUCTION_SCHEMA,
                "person": PERSON_SCHEMA}[table]
        # declared columns select/reorder the generator's columns
        if stmt.columns:
            idxs = []
            fields = []
            for c in stmt.columns:
                i = base.index_of(c.name)
                idxs.append(i)
                fields.append(base[i])
            schema = Schema(tuple(fields))
        else:
            idxs = list(range(len(base)))
            schema = base
        rate = int(opts.get("nexmark.event.rate", "100000"))
        inter_us = max(1_000_000 // max(rate, 1), 1)
        gen_config = NexmarkConfig(inter_event_us=inter_us)
        cap = self.config.chunk_capacity

        def factory(split_id: int = 0, num_splits: int = 1):
            reader = NexmarkSplitReader(
                table, NexmarkGenerator(gen_config), chunk_capacity=cap,
                split_id=split_id, num_splits=num_splits,
            )
            if idxs == list(range(len(base))):
                return reader
            return _ProjectingReader(reader, idxs, schema)

        wm = None
        if stmt.watermark is not None:
            wm = (schema.index_of(stmt.watermark.column),
                  stmt.watermark.delay.micros)
        return CatalogEntry(
            stmt.name, "source", schema, reader_factory=factory,
            watermark=wm, append_only=True, definition=self._definition_text(stmt),
        )

    @staticmethod
    def _declared_schema(stmt: ast.CreateSource):
        """(schema, watermark, auto-width cols) from CREATE SOURCE/TABLE.

        ``auto`` lists VARCHAR columns declared without a length: their
        device width starts at the default and is re-derived from
        observed data before each new plan (DML tables only — external
        sources size from their declared schema)."""
        from risingwave_tpu.common.types import parse_sql_type

        fields = []
        auto = []
        for i, c in enumerate(stmt.columns):
            t, width, scale = parse_sql_type(c.type_name)
            kw = {}
            if width is not None:
                kw["str_width"] = width
            elif t.is_string:
                auto.append(i)
            if scale is not None:
                kw["decimal_scale"] = scale
            fields.append(Field(c.name, t, nullable=c.nullable, **kw))
        schema = Schema(tuple(fields))
        wm = None
        if stmt.watermark is not None:
            wm = (schema.index_of(stmt.watermark.column),
                  stmt.watermark.delay.micros)
        return schema, wm, auto

    def _dml_table(self, stmt: ast.CreateSource) -> CatalogEntry:
        """CREATE TABLE without a connector: INSERT-fed (ref src/dml)."""
        from risingwave_tpu.connector.dml import TableDmlManager

        schema, wm, auto = self._declared_schema(stmt)
        dml = TableDmlManager(schema, auto_width_cols=auto)
        if self._replaying and self.meta_store is not None:
            # cold start: reload the table's durable history BEFORE any
            # MV replay plans against it — auto varchar widths and
            # recovered source cursors both index into this history
            hist = self.meta_store.dml_rows(stmt.name)
            if hist:
                dml.insert(hist)
        cap = self.config.chunk_capacity

        def factory(split_id: int = 0, num_splits: int = 1):
            return dml.new_reader(cap)

        pk = [schema.index_of(c) for c in stmt.primary_key] \
            if stmt.primary_key else None
        # WITH (retract = 'true'): the table accepts DELETE (exact
        # full-row retraction) and downstream plans must pick their
        # retraction-capable variants — exactly the append_only=False
        # path every changelog operator already implements
        retract = str(stmt.with_options.get(
            "retract", "false")).lower() in ("true", "1", "yes")
        return CatalogEntry(
            stmt.name, "source", schema, reader_factory=factory,
            watermark=wm, append_only=not retract,
            definition=self._definition_text(stmt),
            dml=dml, stream_key=pk,
        )

    def _filetail_source(self, stmt: ast.CreateSource) -> CatalogEntry:
        """External JSONL source tailed from disk (ref SplitReader +
        JSON parser, src/connector/src/source/base.rs:596)."""
        from risingwave_tpu.connector.file_source import FileTailSplitReader

        schema, wm, _ = self._declared_schema(stmt)
        opts = stmt.with_options
        path = opts.get("path")
        if not path:
            raise ValueError("filetail needs WITH (path = '...')")
        fmt = opts.get("format", "json")
        if fmt != "json":
            raise ValueError(f"filetail format {fmt!r} (json only)")
        cap = self.config.chunk_capacity
        rate = int(opts.get("rate.limit", cap))

        def factory(split_id: int = 0, num_splits: int = 1):
            return FileTailSplitReader(
                path, schema, chunk_capacity=cap,
                split_id=split_id, num_splits=num_splits,
                max_rows_per_chunk=rate,
            )

        return CatalogEntry(
            stmt.name, "source", schema, reader_factory=factory,
            watermark=wm, append_only=True, definition=self._definition_text(stmt),
        )

    def _datagen_source(self, stmt: ast.CreateSource) -> CatalogEntry:
        schema, wm, _ = self._declared_schema(stmt)
        cap = self.config.chunk_capacity

        def factory(split_id: int = 0, num_splits: int = 1):
            return _DatagenReader(schema, cap, split_id, num_splits)

        return CatalogEntry(
            stmt.name, "source", schema, reader_factory=factory,
            watermark=wm, append_only=True, definition=self._definition_text(stmt),
        )

    def _refresh_dml_widths(self) -> None:
        """Re-derive auto varchar widths for DML tables before planning.

        The reference's VARCHAR is unbounded (utf8_array.rs); a device
        column needs a static width before the job's programs compile,
        so width follows the observed max at plan time.  Running jobs
        keep their compiled widths; TableDmlManager.insert refuses data
        that would silently truncate in one of them."""
        for entry in self.catalog.list("source"):
            if entry.dml is not None and entry.dml.auto_width_cols:
                entry.schema = entry.dml.refresh_schema()

    def _build_job(self, plan, name: str):
        """Instantiate the runtime job for a plan (shared MV/sink path).

        When the session sets ``streaming_parallelism`` > 1, eligible
        aggregation plans run vnode-sharded over the device mesh
        (ref: adaptive parallelism, ADAPTIVE streaming jobs).

        Returns (job, terminal_executor, state_index, dag_node_ids,
        is_new_job)."""
        ckpt_freq = int(self.system_params.get("checkpoint_frequency"))
        par = int(self.session_config.get("streaming_parallelism"))
        if par == 0:  # adaptive: all devices (ref ADAPTIVE parallelism)
            import jax as _jax
            par = len(_jax.devices())
        if par > 1 and isinstance(plan, UnaryPlan):
            sharded = self._try_sharded_job(plan, name, par, ckpt_freq)
            if sharded is not None:
                job, terminal, state_index = sharded
                return job, terminal, state_index, None, True
        if par > 1 and isinstance(plan, DagPlan):
            sharded = self._try_sharded_dag_plan(plan, name, par, ckpt_freq)
            if sharded is not None:
                job, terminal, state_index, dag_meta = sharded
                return job, terminal, state_index, dag_meta, True
        if isinstance(plan, UnaryPlan):
            job = StreamingJob(
                plan.reader, plan.fragment, name,
                checkpoint_frequency=ckpt_freq,
                checkpoint_store=self.checkpoint_store,
            )
            terminal = plan.fragment.executors[plan.mv_index]
            return job, terminal, (plan.mv_index,), None, True
        return self._build_dag_job(plan, name, ckpt_freq)

    # -- DAG jobs: joins, cascades, shared upstreams ---------------------
    def _ensure_dag(self, entry: CatalogEntry) -> tuple[DagJob, int]:
        """Upgrade an MV's job to a DagJob in place (states preserved) so
        downstream MVs can attach; returns (job, materialize node id).

        Ref: the reference's jobs are always graph-shaped; here linear
        jobs use the leaner StreamingJob until something taps them."""
        job = entry.job
        if isinstance(job, DagJob):
            # sharded join jobs attach downstream nodes per-shard (the
            # whole DAG runs inside one shard_map; the caller validates
            # the new chain is per-key-safe before mutating anything)
            return job, entry.mv_state_index[0]
        if not isinstance(job, StreamingJob):
            raise PlanError(
                f"MV-on-MV over {type(job).__name__} (sharded upstream): "
                "next round"
            )
        src_name = f"_src_{entry.name}"
        dag = DagJob(
            {src_name: job.source},
            [FragNode(job.fragment, ("source", src_name))],
            name=job.name,
            checkpoint_frequency=job.checkpoint_frequency,
            checkpoint_store=job.checkpoint_store,
        )
        dag.states = (job.states,)
        dag.epoch = job.epoch
        dag.barriers_seen = job.barriers_seen
        dag.committed_epoch = job.committed_epoch
        dag.maintenance_interval = job.maintenance_interval
        dag.snapshot_interval = job.snapshot_interval
        # the checkpoint pipeline migrates with the job: the uploader
        # queue (FIFO, same job name) keeps in-flight epochs ordered
        # ahead of the reseed below; the shadow is dropped — the state
        # tree changed shape, the reseed re-bases it
        dag.sealed_epoch = job.sealed_epoch
        dag._uploader = job._uploader
        dag.upload_window = job.upload_window
        dag.metrics = job.metrics
        if hasattr(job, "vnode_gate_idx"):
            # a partitioned upstream keeps its scale-plane identity
            # through the upgrade: the gate now lives inside node 0's
            # fragment, the checkpoint lineage and vnode ownership
            # carry over, and future repartitions drive the DagJob
            # handover path
            dag.vnode_gates = [(0, job.vnode_gate_idx)]
            dag.n_vnodes = job.n_vnodes
            dag.vnodes = job.vnodes
            dag.ckpt_key = job.ckpt_key
            dag.shuffle_cols = dict(getattr(job, "shuffle_cols", {}))
            dag.edge_kinds = dict(getattr(job, "edge_kinds", {}))
        self.jobs[self.jobs.index(job)] = dag
        entry.job = dag
        entry.mv_state_index = (0,) + tuple(entry.mv_state_index)
        entry.dag_nodes = [0]
        entry.dag_sources = [src_name]
        # retained checkpoints hold the StreamingJob-shaped state tree;
        # re-snapshot so recover() sees the DagJob shape (not during
        # bootstrap replay: states are fresh, the durable checkpoint
        # already holds the final-topology state)
        if not self._replaying:
            dag.reseed_checkpoint()
        return dag, 0

    # -- batch serving over snapshots -----------------------------------
    def _serve_batch(self, select: ast.Select):
        """Serving reads through the SAME compiled executor pipeline as
        streaming — scan → filter → project → agg → join over one-shot
        bounded snapshot sources, jit-cached per query shape.

        Ref: the reference's batch engine (src/batch/src/executor/
        mod.rs:46) + local execution mode (scheduler/local.rs:60).  The
        TPU-first twist: batch IS streaming over bounded input — the
        planner's dataflow runs to completion on a snapshot, so serving
        semantics can never drift from the device kernels (the old
        interpreted `_serve_agg` path re-implemented SQL in host
        Python; it is gone)."""
        key = repr(select)
        if not hasattr(self, "_serving_cache"):
            self._serving_cache: dict = {}
        hit = self._serving_cache.get(key)
        if hit is None:
            stripped = dataclasses.replace(
                select, order_by=(), limit=None, offset=None
            )
            plan = self.planner.plan(stripped)
            if isinstance(plan, UnaryPlan):
                plan = DagPlan(
                    sources={"_in": plan.reader},
                    nodes=[FragNode(plan.fragment, ("source", "_in"))],
                    mv_node=0, mv_index=plan.mv_index,
                )
            readers: dict[str, Any] = {}
            for name, r in plan.sources.items():
                if isinstance(r, MvTap):
                    readers[name] = _SnapshotReader(
                        self, self.catalog.get(r.name)
                    )
                elif hasattr(r, "pending"):
                    readers[name] = r  # bounded (table-history cursor)
                else:
                    raise PlanError(
                        "serving reads over unbounded sources: create "
                        "a materialized view instead"
                    )
            job = DagJob(readers, plan.nodes, "_serve",
                         checkpoint_frequency=1, checkpoint_store=None)
            job.snapshot_interval = 1 << 30  # no commits: one-shot
            terminal = plan.nodes[plan.mv_node].fragment.executors[
                plan.mv_index
            ]
            hit = (job, plan, terminal, readers)
            self._serving_cache[key] = hit
        job, plan, terminal, readers = hit
        # fresh state + fresh snapshot every execution; the COMPILED
        # programs persist in the job (static shapes)
        job.states = job._init_states()
        for r in readers.values():
            if hasattr(r, "reset"):
                r.reset()
            else:
                r.offset = 0  # table cursor rewinds over shared history
        for _ in range(1 << 20):
            if not any(r.pending() for r in readers.values()):
                break
            job.chunk_round()
        job.inject_barrier()  # flush + drain emissions
        job.inject_barrier()  # residual drains (maintenance pass)
        st = job.states[plan.mv_node][plan.mv_index]
        rows = terminal.to_host(st)
        schema = terminal.in_schema
        keep = [i for i, f in enumerate(schema)
                if not f.name.startswith("_hidden_")]
        self._last_columns = [schema[i].name for i in keep]
        self._last_fields = [schema[i] for i in keep]
        rows = [tuple(r[i] for i in keep) for r in rows]
        out_schema = Schema(tuple(schema[i] for i in keep))
        return self._host_order_limit(rows, select, out_schema)

    def _host_order_limit(self, rows: list, select: ast.Select,
                          schema: Schema) -> list:
        """ORDER BY (output columns) / LIMIT / OFFSET on host rows."""
        if select.order_by:
            for oi in reversed(select.order_by):
                e = oi.expr
                if isinstance(e, ast.ColumnRef):
                    i = schema.index_of(e.name)
                elif isinstance(e, ast.Literal) and e.type_name == "int":
                    if not 1 <= e.value <= len(schema):
                        raise PlanError(
                            f"ORDER BY position {e.value} is not in "
                            f"the select list (1..{len(schema)})"
                        )
                    i = e.value - 1  # ORDER BY <position>
                else:
                    raise PlanError(
                        "serving ORDER BY supports output columns"
                    )
                rows.sort(
                    key=lambda r: (r[i] is None, r[i]),
                    reverse=oi.descending,
                )
        if select.offset:
            rows = rows[select.offset:]
        if select.limit is not None:
            rows = rows[:select.limit]
        return rows

    def _mv_snapshot_chunk(self, entry: CatalogEntry):
        """The upstream MV's current rows as ONE insert chunk (device-
        resident — backfill never leaves HBM).  Ref: arrangement
        backfill reads the upstream state table's snapshot."""
        import jax.numpy as jnp

        from risingwave_tpu.stream.materialize import (
            AppendOnlyMaterialize,
            MaterializeExecutor,
        )

        st = entry.job.states
        for i in entry.mv_state_index:
            st = st[i]
        ex = entry.mv_executor
        mesh = getattr(entry.job, "mesh", None)
        if mesh is not None:
            # sharded upstream: the snapshot is one STACKED chunk
            # ([shard, cap, ...] leaves) consumed by backfill_node's
            # shard_map program — each shard replays its own partition
            import jax as _jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            n_shards = entry.job.n_shards
            if isinstance(ex, MaterializeExecutor):
                valid = st.table.occupied
                cap = ex.table_size
            elif isinstance(ex, AppendOnlyMaterialize):
                valid = jnp.arange(ex.ring_size, dtype=jnp.int64)[
                    None, :] < st.cursor[:, None]
                cap = ex.ring_size
            else:
                raise PlanError("cannot backfill from a sink")
            chunk = Chunk(
                tuple(st.values),
                jnp.zeros((n_shards, cap), jnp.int8),
                valid,
                ex.in_schema,
            )
            return _jax.device_put(
                chunk, NamedSharding(mesh, P(entry.job.AXIS))
            )
        if isinstance(ex, MaterializeExecutor):
            valid = st.table.occupied
            cap = ex.table_size
            vn_set, n_vn = self._mv_vnode_set(entry)
            if vn_set is not None:
                valid = self._vnode_filtered_mv_state(
                    st, vn_set, n_vn
                ).table.occupied
        elif isinstance(ex, AppendOnlyMaterialize):
            valid = jnp.arange(ex.ring_size, dtype=jnp.int64) < st.cursor
            cap = ex.ring_size
        else:
            raise PlanError("cannot backfill from a sink")
        return Chunk(
            tuple(st.values),
            jnp.zeros((cap,), jnp.int8),  # all inserts
            valid,
            ex.in_schema,
        )

    def _build_dag_job(self, plan: DagPlan, name: str, ckpt_freq: int):
        taps = {n: r for n, r in plan.sources.items()
                if isinstance(r, MvTap)}
        if not taps:
            # deep multiway plans run STAGED: per-node dispatches with
            # host-driven join drains — fused drain loops embed each
            # join's downstream subgraph and XLA compile memory blows
            # up around 4+ chained joins (TPC-H q2/q8/q9)
            n_joins = sum(isinstance(n, JoinNode) for n in plan.nodes)
            job = DagJob(
                plan.sources, plan.nodes, name,
                checkpoint_frequency=ckpt_freq,
                checkpoint_store=self.checkpoint_store,
                staged=n_joins >= 4,
            )
            self._prime_temporal_builds(job, range(len(job.nodes)))
            terminal = plan.nodes[plan.mv_node].fragment.executors[
                plan.mv_index
            ]
            return job, terminal, (plan.mv_node, plan.mv_index), \
                (list(range(len(plan.nodes))), list(plan.sources)), True

        # validate every tap BEFORE mutating any live job: a failure
        # mid-attach would otherwise leave half-merged jobs behind
        for sname, tap in taps.items():
            entry = self.catalog.get(tap.name)
            if not isinstance(entry.job, (DagJob, StreamingJob)):
                raise PlanError(
                    f"MV-on-MV over {type(entry.job).__name__} (sharded "
                    "upstream): next round"
                )
        mesh_jobs = {
            self.catalog.get(tap.name).job
            for tap in taps.values()
            if getattr(self.catalog.get(tap.name).job, "mesh", None)
            is not None
        }
        exchange_specs: dict[int, list] = {}
        if mesh_jobs:
            exchange_specs = self._plan_mesh_attach(
                plan, taps, mesh_jobs
            )
        part_jobs = {
            self.catalog.get(tap.name).job
            for tap in taps.values()
            if getattr(self.catalog.get(tap.name).job,
                       "n_vnodes", None) is not None
        }
        if part_jobs:
            self._plan_partition_attach(plan, taps)

        # attach: resolve every tap to its upstream job's MV node
        tap_refs: dict[str, int] = {}
        tap_entries: dict[str, CatalogEntry] = {}
        target: DagJob | None = None
        for sname, tap in taps.items():
            entry = self.catalog.get(tap.name)
            ujob, unode = self._ensure_dag(entry)
            if target is None:
                target = ujob
            elif ujob is not target:
                target = self._merge_dag_jobs(target, ujob)
            tap_entries[sname] = entry
        # tap node ids read after all merges (merges remap them)
        for sname, tap in taps.items():
            tap_refs[sname] = self.catalog.get(tap.name).mv_state_index[0]

        base = len(target.nodes)
        src_rename: dict[str, str] = {}
        for sname, reader in plan.sources.items():
            if sname in taps:
                continue
            new_name = sname
            i = 1
            while new_name in target.sources:
                new_name = f"{sname}_{i}"
                i += 1
            src_rename[sname] = new_name
            target.add_source(new_name, reader)

        def remap(ref):
            kind, key = ref
            if kind == "node":
                return ("node", base + key)
            if key in tap_refs:
                return ("node", tap_refs[key])
            return ("source", src_rename[key])

        rewritten = []
        for n in plan.nodes:
            if isinstance(n, FragNode):
                rewritten.append(dataclasses.replace(
                    n, input=remap(n.input)
                ))
            else:
                rewritten.append(dataclasses.replace(
                    n, left=remap(n.left), right=remap(n.right)
                ))
        ids = target.add_nodes(rewritten)

        # sharded attach: mark the derived exchange edges BEFORE any
        # backfill/step program compiles — the snapshot replay and the
        # live changelog cross the same all_to_all (dag._exchange)
        for pi, specs in exchange_specs.items():
            for side, key_fn in specs:
                target.exchanges[(ids[pi], side)] = key_fn
        if exchange_specs:
            target._rebuild()

        # backfill: every NEW input slot that consumes a tapped MV
        # replays its current snapshot before going live (device-side,
        # one chunk).  Per input SLOT, not per tap — a self-join of one
        # MV taps it on both sides and each side backfills exactly once
        # (left before right: the right pass probes the filled left
        # side, producing the complete snapshot x snapshot join).
        tap_by_node = {tap_refs[s]: e for s, e in tap_entries.items()}
        snapshots: dict[int, Any] = {}

        def snap_for(tap_node: int):
            if tap_node not in snapshots:
                snapshots[tap_node] = self._mv_snapshot_chunk(
                    tap_by_node[tap_node]
                )
            return snapshots[tap_node]

        for nid in ids:
            node = target.nodes[nid]
            if isinstance(node, FragNode):
                slots = [(node.input, None)]
            else:
                slots = [(node.left, "left"), (node.right, "right")]
            for ref, side in slots:
                if ref[0] == "node" and ref[1] in tap_by_node:
                    target.backfill_node(
                        nid, [snap_for(ref[1])], side=side
                    )

        self._prime_temporal_builds(target, ids)
        if not self._replaying:
            target.reseed_checkpoint()
        terminal = rewritten[plan.mv_node].fragment.executors[plan.mv_index]
        return target, terminal, (ids[plan.mv_node], plan.mv_index), \
            (ids, list(src_rename.values())), False

    def _plan_mesh_attach(self, plan: DagPlan, taps: dict,
                          mesh_jobs: set) -> dict[int, list]:
        """MV-on-MV over SHARDED join jobs: derive the device hash
        exchange each attached node needs (ROADMAP multi-device item).

        The attached nodes run per-shard inside the upstream's
        shard_map.  A per-key-safe chain (project/filter/materialize)
        stays shard-local — a joined row's changelog always lands on
        the shard owning its join key.  Cross-shard shapes no longer
        raise; they get an ``all_to_all`` exchange on the attach edge
        (keyed by the same ``hash64``/crc32 vnode mix as every other
        exchange) so rows re-route to their new key owners:

        - HashAgg over REDUCED keys → exchange on its group-by keys
          (every group lands whole on one shard);
        - global agg / global TopN (no keys) → constant-key exchange
          to ONE owning shard (the reference's singleton fragment);
        - grouped TopN → exchange on its partition keys;
        - a new JoinNode (join of two sharded MVs; their mesh jobs
          merge first) → exchange per side on its equi keys.

        Still raising: un-sharded/new sources mixed in, temporal
        joins, shapes whose keys are not evaluable on the attach-edge
        chunk (a projection ahead of a keyed stateful op), and
        executors outside the gated set.

        Returns ``{plan_node_id: [(side, key_fn)]}`` (side None for a
        FragNode input edge)."""
        from risingwave_tpu.parallel.exchange import single_shard_keys
        from risingwave_tpu.stream.executor import (
            FilterExecutor as _F,
            ProjectExecutor as _P,
        )
        from risingwave_tpu.stream.hash_agg import (
            HashAggExecutor as _A,
        )
        from risingwave_tpu.stream.materialize import (
            AppendOnlyMaterialize as _AOM,
            MaterializeExecutor as _M,
        )
        from risingwave_tpu.stream.temporal_join import (
            TemporalJoinExecutor as _TJ,
        )
        from risingwave_tpu.stream.top_n import GroupTopNExecutor as _T

        if any(
            getattr(self.catalog.get(t.name).job, "mesh", None) is None
            for t in taps.values()
        ):
            raise PlanError(
                "MV-on-MV joining a sharded job with an un-sharded "
                "job: next round"
            )
        if len({j.n_shards for j in mesh_jobs}) > 1:
            raise PlanError(
                "MV-on-MV joining sharded jobs of different "
                "parallelism: next round"
            )
        if len(taps) != len(plan.sources):
            raise PlanError(
                "MV-on-MV over a sharded join job cannot add new "
                "sources: next round"
            )

        specs: dict[int, list] = {}
        for i, n in enumerate(plan.nodes):
            if isinstance(n, JoinNode):
                if isinstance(n.join, _TJ):
                    raise PlanError(
                        "temporal join over a sharded job (build side "
                        "replicates, not partitions): next round"
                    )
                specs[i] = [
                    ("left", lambda c, ks=n.join.left_keys:
                        _join_exchange_keys(ks, c)),
                    ("right", lambda c, ks=n.join.right_keys:
                        _join_exchange_keys(ks, c)),
                ]
                continue
            execs = n.fragment.executors
            stateful = [ex for ex in execs
                        if not isinstance(ex, (_F, _P, _M, _AOM))]
            if not stateful:
                continue  # per-key-safe chain: stays shard-local
            if len(stateful) > 1:
                raise PlanError(
                    "MV-on-MV over a sharded job with more than one "
                    "keyed operator per fragment: next round"
                )
            ex = stateful[0]
            pos = execs.index(ex)
            if isinstance(ex, _A):
                keyed = bool(ex.group_by)
                key_fn = (
                    (lambda c, a=ex: [e.eval(c) for _, e in a.group_by])
                    if keyed else single_shard_keys
                )
            elif isinstance(ex, _T):
                keyed = bool(ex.group_by)
                key_fn = (
                    (lambda c, t=ex: [k.eval(c) for k in t.group_by])
                    if keyed else single_shard_keys
                )
            else:
                raise PlanError(
                    "MV-on-MV over a sharded job supports project/"
                    "filter/materialize chains, aggs, TopN, and joins "
                    f"(got {type(ex).__name__}): next round"
                )
            # a KEYED op's keys evaluate on the attach-edge chunk:
            # only filters may precede it (they preserve the schema);
            # an unkeyed (constant-route) op tolerates any per-key-
            # safe prefix — the exchange does not read columns
            if keyed and any(not isinstance(p, _F)
                             for p in execs[:pos]):
                raise PlanError(
                    "MV-on-MV over a sharded job: a projection ahead "
                    "of a keyed agg/TopN (keys not evaluable on the "
                    "attach edge): next round"
                )
            specs[i] = [(None, key_fn)]
        return specs

    def _plan_partition_attach(self, plan: DagPlan,
                               taps: dict) -> None:
        """MV-on-MV over a vnode-PARTITIONED upstream: the worker-
        topology analog of ``_plan_mesh_attach``, compiled against the
        cluster exchange plane.  The attach edge's exchange must be
        the IDENTITY choreography (``ExchangeSpec.mode="local"``):
        every keyed state the new chain adds must key on the
        upstream's distribution value, so each partition's changelog
        already lives on its owner and no cross-worker row movement is
        needed — the cheapest exchange there is.  Concretely, every
        attached HashAgg's LEADING group-by key and every attached
        Materialize's LEADING pk column must trace (through plain
        InputRef hops, including through earlier attached aggs' group
        keys) back to the upstream MV's leading pk column.  Reduced-
        key aggs, TopN, joins of partitioned MVs, and new sources
        raise ``PlanError`` — a true cross-partition data exchange on
        the attach edge is the next round."""
        from risingwave_tpu.expr.node import InputRef
        from risingwave_tpu.stream.executor import (
            FilterExecutor as _F,
            ProjectExecutor as _P,
        )
        from risingwave_tpu.stream.hash_agg import HashAggExecutor
        from risingwave_tpu.stream.materialize import (
            MaterializeExecutor,
        )

        if len(taps) != 1 or len(plan.sources) != len(taps):
            raise PlanError(
                "MV-on-MV over a partitioned upstream supports "
                "exactly one upstream MV and no new sources: "
                "next round"
            )
        (tap_sname, tap), = taps.items()
        up_entry = self.catalog.get(tap.name)
        up_pk0 = up_entry.mv_executor.pk_indices[0]

        def trace_edge(ref, col) -> "int | None":
            """Trace a column on edge ``ref`` back to the tap source
            column (None = untraceable)."""
            while ref[0] == "node":
                node = plan.nodes[ref[1]]
                if isinstance(node, JoinNode):
                    return None
                idx = int(col)
                for ex in reversed(node.fragment.executors):
                    if isinstance(ex, (_F, MaterializeExecutor)):
                        continue
                    if isinstance(ex, _P):
                        if idx >= len(ex.exprs):
                            return None
                        e = ex.exprs[idx][1]
                        if not isinstance(e, InputRef):
                            return None
                        idx = e.index
                    elif isinstance(ex, HashAggExecutor):
                        # agg output = group keys ++ agg values; only
                        # a group-key column traces through
                        if idx >= len(ex.group_by):
                            return None
                        e = ex.group_by[idx][1]
                        if not isinstance(e, InputRef):
                            return None
                        idx = e.index
                    else:
                        return None
                col = idx
                ref = node.input
            return int(col) if ref == ("source", tap_sname) else None

        def trace_in_node(ni: int, pos: int, col) -> "int | None":
            """Trace ``col`` on the input edge of executor ``pos`` of
            node ``ni`` back to the tap source column."""
            node = plan.nodes[ni]
            idx = int(col)
            for ex in reversed(node.fragment.executors[:pos]):
                if isinstance(ex, (_F, MaterializeExecutor)):
                    continue
                if isinstance(ex, _P):
                    if idx >= len(ex.exprs):
                        return None
                    e = ex.exprs[idx][1]
                    if not isinstance(e, InputRef):
                        return None
                    idx = e.index
                elif isinstance(ex, HashAggExecutor):
                    if idx >= len(ex.group_by):
                        return None
                    e = ex.group_by[idx][1]
                    if not isinstance(e, InputRef):
                        return None
                    idx = e.index
                else:
                    return None
            return trace_edge(node.input, idx)

        for ni, node in enumerate(plan.nodes):
            if isinstance(node, JoinNode):
                raise PlanError(
                    "MV-on-MV joining a partitioned upstream: a "
                    "cross-partition join-key exchange on the attach "
                    "edge is the next round"
                )
            for pos, ex in enumerate(node.fragment.executors):
                if isinstance(ex, (_F, _P, MaterializeExecutor)):
                    if isinstance(ex, MaterializeExecutor):
                        k = ex.pk_indices[0]
                        e = trace_in_node(ni, pos, k)
                        if e is None or e != up_pk0:
                            raise PlanError(
                                "MV-on-MV over a partitioned "
                                "upstream: the new MV's leading pk "
                                "column must carry the upstream "
                                "distribution key: next round"
                            )
                    continue
                if isinstance(ex, HashAggExecutor):
                    if (ex.emit_on_window_close or ex._distinct_aggs
                            or ex._minput_aggs
                            or ex.watermark_group_idx is not None):
                        raise PlanError(
                            "MV-on-MV over a partitioned upstream: "
                            "DISTINCT/minput/EOWC/watermark "
                            "aggregations are not scale-eligible"
                        )
                    if not ex.group_by:
                        raise PlanError(
                            "MV-on-MV over a partitioned upstream: a "
                            "global aggregation reduces across "
                            "partitions (attach-edge exchange): "
                            "next round"
                        )
                    e0 = ex.group_by[0][1]
                    if not isinstance(e0, InputRef):
                        raise PlanError(
                            "MV-on-MV over a partitioned upstream: "
                            "the leading group-by key must be a "
                            "plain column: next round"
                        )
                    traced = trace_in_node(ni, pos, e0.index)
                    if traced is None or traced != up_pk0:
                        raise PlanError(
                            "MV-on-MV over a partitioned upstream "
                            "with REDUCED keys needs a cross-"
                            "partition exchange on the attach edge: "
                            "next round"
                        )
                    continue
                raise PlanError(
                    "MV-on-MV over a partitioned upstream supports "
                    "project/filter/materialize chains and same-key "
                    f"aggs (got {type(ex).__name__}): next round"
                )

    @staticmethod
    def _agg_shard_safe(agg, node, plan: DagPlan) -> bool:
        """True when every group of ``agg`` is guaranteed shard-local:
        its fragment directly consumes a join node, only filters
        precede it (positions preserved), and its GROUP BY InputRefs
        cover the join's probe-side equi-key InputRefs (rows route by
        join key ⇒ group determines shard)."""
        from risingwave_tpu.expr.node import InputRef as _IR
        from risingwave_tpu.stream.executor import (
            FilterExecutor as _F,
        )
        from risingwave_tpu.stream.hash_agg import (
            HashAggExecutor as _A,
        )

        kind, key = node.input
        if kind != "node" or not isinstance(plan.nodes[key], JoinNode):
            return False
        join = plan.nodes[key].join
        # INNER only: an outer join's NULL-padded rows live on the
        # UNMATCHED side's shard, not the shard of the (NULL) group
        # key — the NULL group would split across shards
        if getattr(join, "join_type", None) != "inner":
            return False
        for ex in node.fragment.executors:
            if ex is agg:
                break
            if not isinstance(ex, _F):
                return False
        if not all(isinstance(k, _IR) for k in join.left_keys):
            return False
        group_idx = {
            g.index for _, g in agg.group_by if isinstance(g, _IR)
        }
        jk = {k.index for k in join.left_keys}
        if not jk <= group_idx:
            return False
        # only ONE shard-safe agg per chain (a second agg over reduced
        # keys could merge groups across shards)
        return all(
            not isinstance(ex2, _A) or ex2 is agg
            for ex2 in node.fragment.executors
        )

    def _prime_temporal_builds(self, job: DagJob, node_ids) -> None:
        """Drain each temporal join's build-side source BEFORE any
        probe chunk flows: the build table must reflect the table's
        full current state at MV creation (ref temporal_join.rs reads
        the upstream table's storage directly; this local copy
        backfills instead)."""
        from risingwave_tpu.stream.temporal_join import (
            TemporalJoinExecutor,
        )

        for nid in node_ids:
            node = job.nodes[nid]
            if not (isinstance(node, JoinNode)
                    and isinstance(node.join, TemporalJoinExecutor)):
                continue
            ref = node.right
            while ref[0] == "node":
                n2 = job.nodes[ref[1]]
                if isinstance(n2, FragNode):
                    ref = n2.input
                else:
                    break  # joins feeding a temporal build: leave as-is
            if ref[0] != "source":
                continue
            r = job.sources.get(ref[1])
            for _ in range(1 << 16):
                if not (hasattr(r, "pending") and r.pending() > 0):
                    break
                job.run_chunk(ref[1])

    def _merge_dag_jobs(self, a: DagJob, b: DagJob) -> DagJob:
        """Fuse job ``b`` into ``a`` (a join of MVs living in different
        jobs): sources and nodes move over with remapped ids; catalog
        entries follow.  Two SHARDED jobs merge too (a join of two
        sharded MVs): equal-parallelism meshes span the same devices,
        so ``b``'s stacked states drop into ``a``'s mesh unchanged and
        its exchange edges remap with its node ids."""
        if (a.mesh is None) != (b.mesh is None):
            raise PlanError(
                "MV-on-MV joining a sharded job with an un-sharded "
                "job: next round"
            )
        if a.mesh is not None and a.n_shards != b.n_shards:
            raise PlanError(
                "MV-on-MV joining sharded jobs of different "
                "parallelism: next round"
            )
        offset = len(a.nodes)
        rename: dict[str, str] = {}
        for sname, reader in b.sources.items():
            new_name = sname
            i = 1
            while new_name in a.sources:
                new_name = f"{sname}_{i}"
                i += 1
            rename[sname] = new_name
            a.sources[new_name] = reader

        def remap(ref):
            kind, key = ref
            if kind == "node":
                return ("node", offset + key)
            return ("source", rename[key])

        moved = []
        for n in b.nodes:
            if n is None:
                moved.append(None)
            elif isinstance(n, FragNode):
                moved.append(dataclasses.replace(n, input=remap(n.input)))
            else:
                moved.append(dataclasses.replace(
                    n, left=remap(n.left), right=remap(n.right)
                ))
        a.nodes.extend(moved)
        a.states = tuple(list(a.states) + list(b.states))
        for (i, side), fn in b.exchanges.items():
            a.exchanges[(offset + i, side)] = fn
        a._rebuild()
        for entry in self.catalog.list():
            if entry.job is b:
                entry.job = a
                entry.mv_state_index = (
                    offset + entry.mv_state_index[0],
                ) + tuple(entry.mv_state_index[1:])
                if entry.dag_nodes is not None:
                    entry.dag_nodes = [offset + i for i in entry.dag_nodes]
        if b in self.jobs:
            self.jobs.remove(b)
        return a

    def _try_sharded_job(self, plan, name: str, par: int, ckpt_freq: int):
        import jax
        from risingwave_tpu.stream.executor import (
            FilterExecutor as _F,
            HopWindowExecutor as _H,
            ProjectExecutor as _P,
        )
        from risingwave_tpu.stream.hash_agg import HashAggExecutor as _A
        from risingwave_tpu.stream.sharded import (
            ShardedJob,
            ShardedStreamingJob,
            make_mesh,
        )

        reader = plan.reader
        if not (hasattr(reader, "impl") and hasattr(reader, "next_base")):
            return None
        from risingwave_tpu.stream.materialize import (
            AppendOnlyMaterialize as _AOM,
            MaterializeExecutor as _M,
        )

        from risingwave_tpu.stream.watermark import (
            WatermarkFilterExecutor as _W,
        )

        execs = plan.fragment.executors
        agg_idx = None
        for i, ex in enumerate(execs):
            if isinstance(ex, _A):
                if agg_idx is not None:
                    return None
                agg_idx = i
        if agg_idx is None:
            return None
        # prefix: stateless ops + watermark filters (each shard filters
        # its own substream; barrier-time pmin aligns the global
        # watermark — ShardedJob._wm_pass)
        prefix = execs[:agg_idx]
        if any(not isinstance(ex, (_F, _H, _P, _W)) for ex in prefix):
            return None
        # suffix after the agg: per-key-safe operators, plus a GLOBAL
        # TopN (group_by == []) — each shard keeps its own top-k band,
        # a guaranteed superset of the global top-k, and the serving
        # read applies the final order+limit over the merged shards
        # (ref: per-actor TopN + singleton merge, executor/top_n/; the
        # merge here rides the serving boundary instead of a singleton
        # fragment).  Sinks stay linear (host delivery ordering).
        from risingwave_tpu.stream.sink import SinkExecutor as _SK
        from risingwave_tpu.stream.top_n import GroupTopNExecutor as _T
        topn_spec = None
        has_sink = False
        for ex in execs[agg_idx + 1:]:
            if isinstance(ex, _T) and not ex.group_by \
                    and ex.rank_alias is None:
                topn_spec = (ex.order_by, ex.limit, ex.offset)
                continue
            if isinstance(ex, _SK):
                # per-shard ring cursors; host merge delivery at the
                # snapshot barrier (ShardedStreamingJob._deliver_all_sinks)
                has_sink = True
                continue
            if not isinstance(ex, (_F, _P, _M, _AOM)):
                return None
        if topn_spec is not None and has_sink:
            # a sink must see the GLOBAL band, not per-shard bands
            return None
        agg = execs[agg_idx]
        n = min(par, len(jax.devices()))
        if n < 2:
            return None
        mesh = make_mesh(n)
        # two-phase aggregation: a stateless in-chunk partial agg before
        # the exchange collapses duplicate keys, shrinking all_to_all
        # volume (ref §2.3 item 4 — local partial -> hash exchange ->
        # global combine)
        from risingwave_tpu.expr.node import InputRef as _IR
        from risingwave_tpu.stream.partial_agg import (
            TWO_PHASE_KINDS,
            PartialAggExecutor,
            translated_global_calls,
        )

        local_execs = list(prefix)
        keyed_execs = list(execs[agg_idx:])
        exchange_key_fn = lambda c: [e.eval(c) for _, e in agg.group_by]
        # two-phase is retraction-unsafe (partial min/max ignore signs;
        # global row_count counts partial rows) — append-only plans only
        if plan.append_only and all(
            a.kind in TWO_PHASE_KINDS and a.filter is None
            and not a.distinct
            for a in agg.aggs
        ):
            partial = PartialAggExecutor(
                agg.in_schema, agg.group_by, agg.aggs
            )
            n_keys = len(agg.group_by)
            global_agg = type(agg)(
                partial.out_schema,
                [(nm, _IR(i))
                 for i, (nm, _) in enumerate(agg.group_by)],
                translated_global_calls(agg.aggs, n_keys),
                table_size=agg.table_size,
                emit_capacity=agg.emit_capacity,
                # group-key positions are identical in the partial
                # output, so window cleaning/EOWC carry over directly
                watermark_group_idx=agg.watermark_group_idx,
                watermark_lag=agg.watermark_lag,
                watermark_src_col=agg.watermark_src_col,
                emit_on_window_close=agg.emit_on_window_close,
            )
            local_execs = local_execs + [partial]
            keyed_execs = [global_agg] + list(execs[agg_idx + 1:])
            exchange_key_fn = (
                lambda c, k=n_keys: [c.column(i) for i in range(k)]
            )
        # spill-to-host draining isn't wired for the sharded runtime
        # yet: overflow stays a loud error there (next round: per-shard
        # rings drained via a gathered readback)
        for ex in keyed_execs:
            if getattr(ex, "spill_ring", 0):
                ex.spill_ring = 0
        if topn_spec is not None:
            # per-shard band must cover GLOBAL rank offset+limit (a
            # globally rank-o row may rank 0 on its shard)
            order_by, limit, offset = topn_spec
            keyed_execs = [
                _T(ex.in_schema, group_by=[], order_by=ex.order_by,
                   limit=limit + offset, offset=0,
                   pool_size=ex.pool_size,
                   emit_capacity=ex.emit_capacity,
                   append_only=ex.append_only)
                if isinstance(ex, _T) and not ex.group_by else ex
                for ex in keyed_execs
            ]
        sharded = ShardedJob(
            mesh,
            source_fn=reader.impl,
            chunk_capacity=reader.cap,
            local_executors=local_execs,
            exchange_key_fn=exchange_key_fn,
            keyed_executors=keyed_execs,
        )
        job = ShardedStreamingJob(
            sharded, reader, name,
            checkpoint_frequency=ckpt_freq,
            checkpoint_store=self.checkpoint_store,
        )
        # index into the SHARDED executor list (the two-phase rewrite
        # inserts a partial agg, shifting positions vs the linear plan)
        terminal = keyed_execs[-1]
        if topn_spec is not None:
            # the serving read applies the GLOBAL order+limit over the
            # merged per-shard bands
            terminal.serving_topn = topn_spec
        return job, terminal, (len(local_execs) + len(keyed_execs) - 1,)

    def _try_sharded_dag_plan(self, plan: DagPlan, name: str, par: int,
                              ckpt_freq: int):
        """Shard a join-shaped DAG plan over the device mesh.

        Ref: every stateful fragment is vnode-parallel with hash
        exchanges on its inputs (src/meta/src/stream/stream_graph/
        actor.rs:435, dispatch.rs:949).  Here: the whole DAG runs
        per-shard inside one shard_map, with all_to_all exchanges on
        each join input edge routing rows by that side's equi keys.
        Join OUTPUT rows stay shard-local for the downstream
        materialize — a joined row's stream key contains its join key,
        so a given key's changelog always lands on the owning shard.

        Eligible: traceable sources (no MvTaps), stateless(+watermark)
        prefixes, joins, and a per-key-safe post chain (project/filter/
        materialize — no sinks/TopN, which need host delivery or global
        order)."""
        import jax
        from risingwave_tpu.stream.executor import (
            FilterExecutor as _F,
            HopWindowExecutor as _H,
            ProjectExecutor as _P,
        )
        from risingwave_tpu.stream.materialize import (
            AppendOnlyMaterialize as _AOM,
            MaterializeExecutor as _M,
        )
        from risingwave_tpu.stream.sharded import make_mesh
        from risingwave_tpu.stream.watermark import (
            WatermarkFilterExecutor as _W,
        )

        if any(isinstance(r, MvTap) for r in plan.sources.values()):
            return None
        # traceable sources generate per-shard inside the program;
        # host-chunk sources (DML tables) enter on shard 0 and re-route
        # at the first exchange edge — both shard
        joins = [i for i, n in enumerate(plan.nodes)
                 if isinstance(n, JoinNode)]
        if not joins:
            return None
        from risingwave_tpu.stream.temporal_join import (
            TemporalJoinExecutor as _TJ,
        )
        if any(isinstance(plan.nodes[i].join, _TJ) for i in joins):
            # temporal build tables replicate, not partition: meshless
            return None
        join_inputs: set = set()
        for i in joins:
            join_inputs.add(plan.nodes[i].left)
            join_inputs.add(plan.nodes[i].right)
        for i, n in enumerate(plan.nodes):
            if isinstance(n, JoinNode):
                continue
            if ("node", i) in join_inputs or n.input[0] == "source":
                # pre-join prefix: stateless + watermark filters
                if any(not isinstance(ex, (_F, _H, _P, _W))
                       for ex in n.fragment.executors):
                    return None
            else:
                # post-join chain: per-key-safe only.  A HashAgg is
                # per-key-safe when its GROUP BY keys cover the
                # upstream join's equi keys (rows are routed by join
                # key, so every such group lives on one shard)
                from risingwave_tpu.stream.hash_agg import (
                    HashAggExecutor as _A,
                )
                for ex in n.fragment.executors:
                    if isinstance(ex, (_F, _P, _M, _AOM)):
                        continue
                    if isinstance(ex, _A) and self._agg_shard_safe(
                            ex, n, plan):
                        continue
                    return None
        n = min(par, len(jax.devices()))
        if n < 2:
            return None
        exchanges = {}
        for i in joins:
            join = plan.nodes[i].join
            exchanges[(i, "left")] = (
                lambda c, ks=join.left_keys: _join_exchange_keys(ks, c)
            )
            exchanges[(i, "right")] = (
                lambda c, ks=join.right_keys: _join_exchange_keys(ks, c)
            )
        job = DagJob(
            plan.sources, plan.nodes, name,
            checkpoint_frequency=ckpt_freq,
            checkpoint_store=self.checkpoint_store,
            mesh=make_mesh(n),
            exchanges=exchanges,
        )
        terminal = plan.nodes[plan.mv_node].fragment.executors[
            plan.mv_index
        ]
        return job, terminal, (plan.mv_node, plan.mv_index), \
            (list(range(len(plan.nodes))), list(plan.sources))

    def _create_mview(self, stmt: ast.CreateMaterializedView):
        from risingwave_tpu.stream.materialize import AppendOnlyMaterialize

        if stmt.name in self.catalog:
            # checked BEFORE building: _build_job mutates live shared
            # jobs (attach/merge), which must not happen for a
            # doomed-to-fail duplicate
            if stmt.if_not_exists:
                return None
            raise ValueError(f"{stmt.name!r} already exists")
        self._refresh_dml_widths()
        self.planner.parallel_hint = int(
            self.session_config.get("streaming_parallelism")
        )
        plan = self.planner.plan(stmt.query,
                                 eowc=stmt.emit_on_window_close)
        job, mv_exec, state_index, dag_meta, is_new = self._build_job(
            plan, stmt.name
        )
        entry = CatalogEntry(
            stmt.name, "mview", mv_exec.in_schema,
            job=job, mv_executor=mv_exec, mv_state_index=state_index,
            append_only=isinstance(mv_exec, AppendOnlyMaterialize),
            dag_nodes=dag_meta[0] if dag_meta else None,
            dag_sources=dag_meta[1] if dag_meta else None,
            stream_key=list(getattr(mv_exec, "pk_indices", [])) or None,
            ttl=self._mv_ttl_option(stmt, mv_exec),
            definition=self._definition_text(stmt),
        )
        self.catalog.create(entry)
        if is_new:
            self.jobs.append(job)
        return None

    @staticmethod
    def _mv_ttl_option(stmt: ast.CreateMaterializedView, mv_exec):
        """Validate WITH (ttl = '<n>') at CREATE time: retention in
        units of the LEADING export-pk column, which must be an
        int-family NOT NULL column (the expiry horizon is one
        memcomparable byte bound — strings/floats/nullable keys have
        no sound integer horizon)."""
        opts = dict(stmt.with_options or {})
        ttl_raw = opts.pop("ttl", None)
        if opts:
            bad = sorted(opts)[0]
            raise ValueError(
                f"unknown materialized-view option {bad!r} "
                "(supported: ttl)"
            )
        if ttl_raw is None:
            return None
        try:
            ttl = int(str(ttl_raw))
        except ValueError:
            raise ValueError(
                f"ttl must be an integer, got {ttl_raw!r}"
            ) from None
        if ttl <= 0:
            raise ValueError("ttl must be positive")
        schema = mv_exec.in_schema
        pk = list(getattr(mv_exec, "pk_indices", ()))
        if not pk:
            raise ValueError(
                "WITH (ttl = ...) needs a materialized view with a "
                "primary key (the horizon tracks the leading pk "
                "column)"
            )
        f = schema[pk[0]]
        if f.data_type.is_string or f.data_type == DataType.DECIMAL \
                or f.data_type in (DataType.FLOAT32, DataType.FLOAT64) \
                or f.nullable:
            raise ValueError(
                f"WITH (ttl = ...) needs an int-family NOT NULL "
                f"leading pk column (got {f.name!r}: "
                f"{f.data_type.value})"
            )
        return (f.name, ttl)

    def _create_index(self, stmt: ast.CreateIndex):
        """``CREATE INDEX ix ON mv(col, ...)``: a small secondary-index
        MV — ``SELECT col..., <upstream pk>... FROM mv`` maintained
        through the ordinary MV-on-MV attach path — whose EXPORT key
        is ``(col..., upstream pk)``, so the shared serving keyspace
        sorts its rows by the indexed columns and a serving replica
        answers ``WHERE col = x`` with one contiguous index range scan
        plus pk point-gets instead of a full scan (ref: the frontend's
        index selection over index TableCatalogs)."""
        from risingwave_tpu.stream.materialize import AppendOnlyMaterialize

        if stmt.name in self.catalog:
            if stmt.if_not_exists:
                return None
            raise ValueError(f"{stmt.name!r} already exists")
        upstream = self.catalog.get(stmt.table)
        if upstream.kind != "mview":
            raise ValueError(
                f"{stmt.table!r} is not a materialized view"
            )
        if not upstream.stream_key:
            raise ValueError(
                f"CREATE INDEX on {stmt.table!r}: append-only MVs "
                "have no stream key to index"
            )
        by_name = {f.name: i for i, f in enumerate(upstream.schema)}
        for c in stmt.columns:
            if c not in by_name:
                raise ValueError(
                    f"column {c!r} does not exist in {stmt.table!r}"
                )
        ix_cols = [by_name[c] for c in stmt.columns]
        pk_cols = list(upstream.stream_key)
        items, used = [], set()
        for j, i in enumerate(ix_cols + pk_cols):
            base = upstream.schema[i].name
            alias = base if base not in used else f"_idx{j}_{base}"
            used.add(alias)
            items.append(
                ast.SelectItem(ast.ColumnRef(base), alias)
            )
        query = ast.Select(tuple(items), ast.TableRef(stmt.table))
        self._refresh_dml_widths()
        self.planner.parallel_hint = int(
            self.session_config.get("streaming_parallelism")
        )
        plan = self.planner.plan(query)
        job, mv_exec, state_index, dag_meta, is_new = self._build_job(
            plan, stmt.name
        )
        entry = CatalogEntry(
            stmt.name, "mview", mv_exec.in_schema,
            job=job, mv_executor=mv_exec, mv_state_index=state_index,
            append_only=isinstance(mv_exec, AppendOnlyMaterialize),
            dag_nodes=dag_meta[0] if dag_meta else None,
            dag_sources=dag_meta[1] if dag_meta else None,
            stream_key=list(getattr(mv_exec, "pk_indices", []))
            or None,
            index_on=(stmt.table, tuple(stmt.columns)),
            export_pk=tuple(range(len(ix_cols) + len(pk_cols))),
            definition=self._definition_text(stmt),
        )
        self.catalog.create(entry)
        if is_new:
            self.jobs.append(job)
        # the upstream's serve-schema doc must advertise the index
        self._schema_published.discard(stmt.table)
        return None

    def _create_sink(self, stmt: ast.CreateSink):
        from risingwave_tpu.connector.sinks import create_sink

        if stmt.name in self.catalog:
            if stmt.if_not_exists:
                return None
            raise ValueError(f"{stmt.name!r} already exists")
        if stmt.query is not None:
            query = stmt.query
        else:
            query = ast.Select(
                (ast.SelectItem(ast.Star(), None),),
                ast.TableRef(stmt.from_rel),
            )
        sink = create_sink(stmt.with_options)
        self._refresh_dml_widths()
        self.planner.parallel_hint = int(
            self.session_config.get("streaming_parallelism")
        )
        plan = self.planner.plan(query, sink=sink)
        job, sink_exec, _, dag_meta, is_new = self._build_job(
            plan, stmt.name
        )
        entry = CatalogEntry(
            stmt.name, "sink", sink_exec.in_schema,
            job=job, mv_executor=sink_exec,
            dag_nodes=dag_meta[0] if dag_meta else None,
            dag_sources=dag_meta[1] if dag_meta else None,
            definition=self._definition_text(stmt),
        )
        self.catalog.create(entry)
        if is_new:
            self.jobs.append(job)
        return None

    # -- the global barrier loop ----------------------------------------
    def tick(self, barriers: int = 1,
             chunks_per_barrier: int | None = None,
             ahead: Callable[[], bool] | None = None) -> None:
        """Advance every streaming job (meta's PeriodicBarriers analog).

        One span tree a call: the served node's ``_tick_loop`` opens the
        ``tick`` root (it also times its wait for the engine lock) and
        this attaches under it; called with no trace active (an
        in-process engine, ``FLUSH``, tests) it opens the root itself.

        ``ahead`` is the served ticker's, which alone knows another
        tick follows: once the last barrier has sealed its snapshot and
        the upload's device reads are queued, an eligible job's next
        window is dispatched (``window_ahead``) while ``ahead()`` says
        no statement waits for the lock, and only then is the epoch
        drained — the host's half of the checkpoint runs under the
        window, and the next tick starts at its barrier.  Any other
        caller first settles what is ahead (``settle``)."""
        if chunks_per_barrier is None:
            chunks_per_barrier = int(
                self.system_params.get("chunks_per_barrier")
            )
        if ahead is None:
            self.settle("tick")
        # runtime-mutable cadence (ref ALTER SYSTEM SET applies live)
        cadence = self._barrier_cadence()
        stall_hook = self._storage_stall_hook \
            if self.hummock is not None else None
        with GLOBAL_TRACE.root("tick", "tick", metrics=self.metrics,
                               barriers=barriers) as sp:
            rows = 0
            crossed = []
            for _ in range(barriers):
                crossed = []
                for job in self.jobs:
                    if job.window_ahead is None \
                            and self.ingest_waits(job, chunks_per_barrier):
                        continue
                    job.write_stall_hook = stall_hook
                    rows += self._job_barrier(job, chunks_per_barrier,
                                              cadence)
                    crossed.append(job)
            if ahead is not None:
                for job in crossed:
                    self._window_ahead(job, chunks_per_barrier, ahead)
            # batch boundary = durability point: uploads sealed inside
            # the window pipelined against the barrier loop; they must
            # land before tick() returns (tests/FLUSH/restart
            # determinism).  Cluster workers are driven via tick_job
            # instead — there the seal/ack split is the meta's global
            # protocol.
            for job in self.jobs:
                job.drain_uploads()
                self._export_checkpoint_gauges(job)
            sp.set(rows=rows, epoch=max(
                (j.sealed_epoch for j in self.jobs), default=0))

    def _window_ahead(self, job, chunks_per_barrier: int,
                      ahead: Callable[[], bool]) -> None:
        """Dispatch ``job``'s next window before its sealed epoch is
        drained, where all of this holds: the window is one
        asynchronous dispatch, the barrier just sealed a snapshot, the
        window brings chunks (``ingest_waits``), and no statement waits
        for the lock — a waiting read then sees the sealed epoch on a
        tree with nothing in flight, as it would without this.  The
        window waits for the upload's device reads to be queued: the
        chip runs programs in order, and a delta's gather behind the
        window would wait for all of it."""
        eligible = (chunks_per_barrier > 0 and not job.paused
                    and job.sealed_snapshot
                    and job.window_one_dispatch(chunks_per_barrier)
                    and not self.ingest_waits(job, chunks_per_barrier))
        went = False
        if eligible:
            up = job._uploader
            if up is not None:
                with GLOBAL_TRACE.span("wait_dispatched", job=job.name):
                    up.wait_dispatched()
            if ahead():
                t0 = time.perf_counter()
                with GLOBAL_TRACE.span("run_chunks", metrics=self.metrics,
                                       job=job.name, ahead=1) as sp:
                    rows = job.run_chunks(chunks_per_barrier)
                    sp.set(rows=rows)
                job.window_ahead = (rows, time.perf_counter() - t0)
                went = True
        # a series for every job the served ticker drives, so a share
        # of 0 reads as 0 and not as absent
        self.metrics.inc("barrier_windows_ahead_total", int(went),
                         job=job.name)

    def settle(self, by: str) -> None:
        """Complete every window the served ticker dispatched ahead:
        its barrier, seal and drain, as the next tick would have.  Any
        holder of the engine lock but the scrape calls this before it
        touches a job (``by``: ``statement``, ``ddl``, ``flush``,
        ``tick``, ``stop``, ``recover``), so a statement runs against a
        sealed, durable epoch with nothing in flight."""
        jobs = [job for job in self.jobs if job.window_ahead is not None]
        if not jobs:
            return
        cadence = self._barrier_cadence()
        with GLOBAL_TRACE.span("settle", metrics=self.metrics, by=by):
            for job in jobs:
                self._job_barrier(job, 0, cadence)
                job.drain_uploads()
                self._export_checkpoint_gauges(job)
                self.metrics.inc("barrier_windows_settled_total", 1,
                                 job=job.name, by=by)

    def ingest_waits(self, job, chunks_per_barrier: int | None = None
                     ) -> bool:
        """Back-pressure at a view's high-water mark
        (``BarrierLoop.ingest_hold``): a barrier that would bring the
        job chunks waits, so no key is dropped for want of a slot and
        the view stays what its source rows make it; a barrier that
        brings none (``chunks_per_barrier = 0``, the orderly stop)
        crosses, and its maintenance pass lifts the hold once the view
        has room."""
        if chunks_per_barrier is None:
            chunks_per_barrier = int(
                self.system_params.get("chunks_per_barrier"))
        return chunks_per_barrier > 0 and job.ingest_hold is not None

    def _barrier_cadence(self) -> tuple[int, int, int, int]:
        """(checkpoint_frequency, maintenance interval, snapshot
        interval, upload window), as the system parameters stand."""
        get = self.system_params.get
        return (int(get("checkpoint_frequency")),
                int(get("maintenance_interval_checkpoints")),
                int(get("snapshot_interval_checkpoints")),
                int(get("checkpoint_upload_window")))

    def _job_barrier(self, job, chunks_per_barrier: int, cadence: tuple,
                     fenced: bool = False) -> int:
        """ONE job across ONE barrier — the body ``tick`` and
        ``tick_job`` share: the window's chunks (``run_chunks``: one
        asynchronous dispatch where the source is traceable, so its
        span is host time), the fenced source drain of a partitioned
        job, then the seal (``inject_barrier``).  Feeds
        ``stream_rows_total``, ``barrier_latency_seconds`` and
        ``barrier_phase_seconds{phase}``; returns the rows pulled."""
        (job.checkpoint_frequency, job.maintenance_interval,
         job.snapshot_interval, job.upload_window) = cadence
        if job.metrics is None:
            job.metrics = self.metrics
        name = job.name
        t0 = time.perf_counter()
        if job.window_ahead is not None:
            # dispatched at the end of the last tick (``_window_ahead``):
            # its rows and dispatch time count at the barrier that seals
            # them
            (rows, dispatch), job.window_ahead = job.window_ahead, None
        else:
            with GLOBAL_TRACE.span("run_chunks", metrics=self.metrics,
                                   job=name) as sp:
                # traceable sources batch the whole inter-barrier
                # window into one dispatch (q1 host-overhead fix)
                rows = job.run_chunks(chunks_per_barrier)
                sp.set(rows=rows)
            dispatch = time.perf_counter() - t0
        t1 = time.perf_counter()
        if fenced:
            # Exchange-lite: a partitioned barrier consumes EXACTLY to
            # the round fence, however many chunks that takes — every
            # partition's cursor seals ON the fence, so handover
            # cursor checks hold even though shuffled partitions see
            # different owned-row densities.  (Bounded: pending() is
            # capped by min(local history, fence).)
            with GLOBAL_TRACE.span("source_drain", metrics=self.metrics,
                                   job=name):
                for _ in range(1 << 20):
                    if not self._fenced_pending(job):
                        break
                    rows += job.run_chunks(chunks_per_barrier)
        t2 = time.perf_counter()
        with GLOBAL_TRACE.span("inject_barrier", metrics=self.metrics,
                               job=name):
            job.inject_barrier()
        t3 = time.perf_counter()
        self.metrics.inc("stream_rows_total", rows, job=name)
        self._observe_barrier(
            name, dispatch + t3 - t1, dispatch=dispatch,
            source_drain=(t2 - t1) if fenced else None,
            seal=t3 - t2,
        )
        return rows

    def tick_job(self, name: str, chunks_per_barrier: int = 1,
                 source_limits: dict | None = None) -> int:
        """Advance ONE job a single barrier round (the cluster worker's
        barrier RPC — meta drives each job's rounds individually so a
        reassigned job can catch up while the rest hold).  Returns the
        job's committed epoch after the barrier.

        ``source_limits`` (cluster scale plane) fences DML-table
        consumption at a meta-chosen history position so every
        partition of the job consumes the identical prefix this round
        — source cursors stay aligned across workers, which is what
        makes checkpoint-slice handover exact."""
        job = self._job_by_name(name)
        if source_limits:
            self._apply_source_limits(job, source_limits)
        fenced = bool(source_limits) \
            and getattr(job, "n_vnodes", None) is not None
        self._job_barrier(job, chunks_per_barrier,
                          self._barrier_cadence(), fenced)
        self._export_checkpoint_gauges(job)
        # the SEAL, not the durable commit: the cluster's global epoch
        # advances only when every job's upload acks (meta polls
        # job_epochs) — the per-job barrier RPC never blocks on I/O
        return job.sealed_epoch

    #: rolling window feeding the spike-ratio gauge; ~128 barriers of
    #: history keeps the median stable while a 1-in-100 spike still
    #: lands in the p99 seat
    _SPIKE_WINDOW = 128
    #: below this many observations the ratio is noise, not signal
    _SPIKE_MIN_SAMPLES = 8

    def _observe_barrier(self, job_name: str, dt: float,
                         **phases) -> None:
        """Barrier-latency attribution: the total histogram, per-phase
        histograms (``barrier_phase_seconds{job,phase}``), and the
        rolling tail gauge ``barrier_spike_ratio{job}`` = p99/median
        over the last window — the number the tail-latency gates
        (``cluster_stress --assert`` / ``profile_q8 --assert``) bound.
        Quantiles here are exact over the window (sorted host floats),
        not histogram-bucket bounds: a spike ratio of 1.0 must mean
        a genuinely flat tail, not two latencies in one bucket."""
        self.metrics.observe("barrier_latency_seconds", dt,
                             job=job_name)
        for phase, secs in phases.items():
            if secs is None:
                continue
            self.metrics.observe("barrier_phase_seconds", secs,
                                 job=job_name, phase=phase)
        lat = self._barrier_lat.get(job_name)
        if lat is None:
            lat = self._barrier_lat[job_name] = deque(
                maxlen=self._SPIKE_WINDOW)
        lat.append(dt)
        if len(lat) >= self._SPIKE_MIN_SAMPLES:
            s = sorted(lat)
            med = s[len(s) // 2]
            p99 = s[min(len(s) - 1, int(0.99 * len(s)))]
            self.metrics.set_gauge(
                "barrier_spike_ratio", p99 / max(med, 1e-9),
                job=job_name,
            )

    def _retire_job_series(self, job_name: str) -> None:
        """DROP retires the job's whole scrape footprint: every series
        labeled ``job=<name>`` — barrier latency/phase histograms,
        spike ratio, join gauges, checkpoint gauges — the way the
        cluster meta retires a dead worker's per-worker series.
        Without this, a dropped MV's gauges linger forever."""
        self.metrics.remove_where(job=job_name)
        self._barrier_lat.pop(job_name, None)

    def _export_checkpoint_gauges(self, job) -> None:
        """Cheap (no device sync) checkpoint-pipeline gauges."""
        self.metrics.set_gauge("committed_epoch", job.committed_epoch,
                               job=job.name)
        self.metrics.set_gauge("sealed_epoch", job.sealed_epoch,
                               job=job.name)
        self.metrics.set_gauge(
            "checkpoint_seal_lag_epochs",
            max(0, job.sealed_epoch - job.committed_epoch),
            job=job.name,
        )
        self.metrics.set_gauge(
            "checkpoint_upload_queue_depth",
            job.upload_queue_depth(), job=job.name,
        )
        up = job._uploader
        if up is not None:
            self.metrics.set_gauge("checkpoint_uploads_total",
                                   up.uploads_total, job=job.name)
            self.metrics.set_gauge("checkpoint_upload_seconds_total",
                                   up.upload_seconds_total,
                                   job=job.name)
            self.metrics.set_gauge("checkpoint_upload_stall_seconds_total",
                                   up.stall_seconds_total, job=job.name)

    def job_epochs(self, name: str) -> dict:
        """Seal-vs-durable positions of one job (the cluster meta polls
        this to decide when a round's uploads have all acked).  Also
        services the job's pending acks — the worker's barrier loop
        only runs when meta drives it, so durable progress must be
        observable between rounds."""
        job = self._job_by_name(name)
        job._process_upload_acks()
        return {
            "sealed": job.sealed_epoch,
            "durable": job.committed_epoch,
            "upload_queue": job.upload_queue_depth(),
        }

    def drain_uploads(self) -> None:
        """Flush every job's checkpoint-upload queue (orderly stop)."""
        for job in self.jobs:
            job.drain_uploads()

    def collect_shard_metrics(self) -> None:
        """How a mesh job's rows and state spread over its shards — on
        demand, like collect_join_metrics (device readbacks).

        ``shard_rows{job,shard,op}``: rows in the groups each shard's
        aggregations hold (``agg`` — as received after the exchange, so
        partials where a local pre-aggregation ran), rows its joins
        emitted (``join_out``) and rows its MVs hold (``mv``).
        ``shard_state_bytes{job,device}``: bytes of the
        job's state on each device, from where the arrays actually
        live — a job that landed on one chip shows as one device."""
        import jax as _jax

        for job in self.jobs:
            n = getattr(getattr(job, "sharded", job), "n_shards", 1)
            if n <= 1:
                continue
            rows: dict[str, np.ndarray] = {}

            def add(op: str, per_shard) -> None:
                v = np.asarray(per_shard).reshape(n, -1).sum(axis=1)
                rows[op] = rows.get(op, 0) + v

            def walk(st) -> None:
                if hasattr(st, "row_count"):
                    add("agg", st.row_count)
                elif hasattr(st, "emit_rows"):
                    add("join_out", st.emit_rows)
                elif hasattr(st, "cursor") and hasattr(st, "values"):
                    add("mv", st.cursor)
                elif hasattr(st, "table") and hasattr(st, "values"):
                    add("mv", st.table.occupied)
                elif isinstance(st, (tuple, list)):
                    for x in st:
                        walk(x)

            walk(job.states)
            for op, per_shard in rows.items():
                for shard, v in enumerate(per_shard):
                    self.metrics.set_gauge(
                        "shard_rows", int(v), job=job.name,
                        shard=str(shard), op=op,
                    )
            by_device: dict[int, int] = {}
            for leaf in _jax.tree.leaves(job.states):
                for sh in leaf.addressable_shards:
                    by_device[sh.device.id] = \
                        by_device.get(sh.device.id, 0) + sh.data.nbytes
            for dev, nbytes in by_device.items():
                self.metrics.set_gauge(
                    "shard_state_bytes", nbytes, job=job.name,
                    device=str(dev),
                )

    def collect_checkpoint_metrics(self) -> None:
        """Snapshot-pipeline observability requiring a device readback
        (dirty-block ratio) — on-demand like collect_join_metrics; the
        steady loop never calls it."""
        for job in self.jobs:
            self._export_checkpoint_gauges(job)
            shadow = job._shadow
            if shadow is not None:
                self.metrics.set_gauge(
                    "snapshot_dirty_block_ratio",
                    shadow.dirty_ratio(), job=job.name,
                )
                self.metrics.set_gauge(
                    "snapshot_shadow_blocks", shadow.total_blocks,
                    job=job.name,
                )
            self.metrics.set_gauge(
                "checkpoint_stall_seconds_total",
                job.stall_seconds, job=job.name,
            )

    def _job_by_name(self, name: str):
        for job in self.jobs:
            if job.name == name:
                return job
        raise ValueError(f"unknown streaming job {name!r}")

    def recover(self) -> None:
        """Restore every job from its last committed checkpoint
        (ref §3.5: meta-driven recovery across all streaming jobs).  A
        window ahead is settled first; where its barrier fails, the
        rewind below is what resolves it."""
        try:
            self.settle("recover")
        except Exception as e:  # noqa: BLE001 — the rewind resolves it
            print(f"recover: the window ahead did not settle ({e!r}); "
                  "rewinding past it", file=sys.stderr)
        for job in self.jobs:
            job.recover()

    # -- cluster job export / adoption ----------------------------------
    def export_job_ddl(self, name: str) -> list[str]:
        """The DDL statements that recreate one MV/sink's job on a
        fresh engine: every source/table definition (in catalog order —
        cheap and closed over any FROM reference), then the entry's own
        definition.  The meta service ships exactly this shape when it
        places or reassigns a job."""
        entry = self.catalog.get(name)
        ddls = [e.definition for e in self.catalog.list("source")
                if e.definition]
        if entry.definition:
            ddls.append(entry.definition)
        return ddls

    def adopt_job(self, ddl: list[str], name: str,
                  recover: bool = True) -> int:
        """Replay a shipped job's DDL, skipping objects this engine
        already has (a survivor adopting its second job reuses its
        sources), then recover the job from the last durable
        checkpoint — state AND source cursors rewind to the same
        commit, so replay is exact.  Returns the recovered committed
        epoch (0 = fresh job, nothing durable yet)."""
        from risingwave_tpu.sql.parser import parse_with_text

        for sql in ddl:
            for text, stmt in parse_with_text(sql):
                nm = getattr(stmt, "name", None)
                if isinstance(stmt, (ast.CreateSource,
                                     ast.CreateMaterializedView,
                                     ast.CreateIndex,
                                     ast.CreateSink)) \
                        and nm in self.catalog:
                    continue
                if isinstance(stmt, ast.CreateFunction) \
                        and nm in self.functions:
                    continue
                if isinstance(stmt, ast.DropStatement) \
                        and nm not in self.catalog:
                    continue  # dropped before this worker ever saw it
                self.execute(text)
        entry = self.catalog.get(name)
        if entry.job is None:
            raise ValueError(f"{name!r} did not produce a streaming job")
        if recover:
            entry.job.recover()
        # adoption moves the MV export diff base: whatever this engine
        # exported in a previous ownership is stale against the shared
        # manifest — re-seed from storage on the next export
        self._exported.clear()
        return entry.job.committed_epoch

    # -- elastic scale plane (cluster/scale) -----------------------------
    def _job_sources(self, job) -> list:
        """Every source reader of a job (one for a linear StreamingJob,
        the sources dict for a DagJob)."""
        if hasattr(job, "sources"):
            return list(job.sources.values())
        src = getattr(job, "source", None)
        return [src] if src is not None else []

    def _table_of_reader(self, reader) -> str | None:
        rows = getattr(reader, "_rows", None)
        if rows is None:
            return None
        for e in self.catalog.list("source"):
            if e.dml is not None and rows is e.dml._history:
                return e.name
        return None

    def _dml_tables_of(self, job) -> list[str]:
        """Names of the DML tables this job's sources read (the tables
        the cluster exchanges worker↔worker so partitions see aligned
        streams)."""
        out: list[str] = []
        for src in self._job_sources(job):
            t = self._table_of_reader(src)
            if t is not None and t not in out:
                out.append(t)
        return out

    def _apply_source_limits(self, job, limits: dict) -> None:
        for src in self._job_sources(job):
            if not hasattr(src, "limit"):
                continue
            tbl = self._table_of_reader(src)
            if tbl is not None and tbl in limits:
                src.limit = int(limits[tbl])

    def _fenced_pending(self, job) -> int:
        """Unconsumed positions below the round fence across the job's
        fenced sources — a partitioned barrier drives this to ZERO so
        every partition's cursor lands exactly ON the fence (stronger
        than the PR-7 identical-consumption-math alignment, and the
        property that keeps shuffled cursors equal even though each
        partition's owned-row density differs)."""
        return sum(
            src.pending() for src in self._job_sources(job)
            if getattr(src, "limit", None) is not None
        )

    @staticmethod
    def _trace_input_col(prefix_execs, col: int) -> int | None:
        """Trace an output column of an executor chain back to an
        input column of the chain's first executor, or None when any
        hop is not a plain InputRef (the shuffle planner then degrades
        the edge to replicate mode — the gate still filters)."""
        from risingwave_tpu.expr.node import InputRef
        from risingwave_tpu.stream.executor import (
            FilterExecutor,
            HopWindowExecutor,
            ProjectExecutor,
        )

        idx = int(col)
        for ex in reversed(list(prefix_execs)):
            if isinstance(ex, FilterExecutor):
                continue
            if isinstance(ex, HopWindowExecutor):
                # row expansion appends window_start; input columns
                # keep their positions
                if idx >= len(ex.in_schema):
                    return None
                continue
            if isinstance(ex, ProjectExecutor):
                if idx >= len(ex.exprs):
                    return None
                e = ex.exprs[idx][1]
                if not isinstance(e, InputRef):
                    return None
                idx = e.index
                continue
            return None
        return idx

    def _trace_source_col(self, prefix_execs, dist_expr) -> int | None:
        """Raw source-column index of a distribution-key expression
        evaluated AFTER ``prefix_execs`` (the shuffle key the ingest
        leader hashes), or None when untraceable."""
        from risingwave_tpu.expr.node import InputRef

        if not isinstance(dist_expr, InputRef):
            return None
        return self._trace_input_col(prefix_execs, dist_expr.index)

    def partition_job(self, name: str, n_vnodes: int,
                      ckpt_key: str) -> dict:
        """Rebuild a freshly-adopted job as ONE partition of a
        vnode-partitioned cluster job (the scale plane's unit):
        ``VnodeGateExecutor``s land on the keyed edges and mask rows
        to the owned vnode set; the checkpoint lineage moves to
        ``ckpt_key`` so every partition checkpoints independently in
        the SHARED store.

        Exchange-lite shapes (raises ``PlanError`` otherwise — the
        worker falls back to whole-job placement):

        - a linear ``StreamingJob`` carrying exactly one MV:
          stateless prefix → one ``HashAggExecutor`` → Materialize
          (gate before the agg, routed by the leading GROUP BY key);
        - a two-source JOIN ``DagJob``: source → gate per side (routed
          by that side's FIRST equi key) → hash join (rebuilt with
          dense retractable sides — sliceable whole-key buckets) →
          project/filter → Materialize whose LEADING pk column is the
          preserved side's join key (one hash domain for routing,
          state slicing, serving filters, and export seeding);
        - no DISTINCT / minput / EOWC / watermark-driven cleaning /
          temporal joins, and every routing key a NOT NULL
          integer-family value.

        The returned spec carries ``shuffle_cols`` — the raw source
        column each DML table routes by when every hop back from the
        key is a plain InputRef — which the meta's ``ExchangePlanner``
        compiles into the sliced-ingest choreography (untraceable keys
        degrade that table's edge to replicate mode)."""
        from risingwave_tpu.cluster.scale.gate import VnodeGateExecutor
        from risingwave_tpu.stream.executor import (
            FilterExecutor,
            HopWindowExecutor,
            ProjectExecutor,
        )
        from risingwave_tpu.stream.fragment import Fragment
        from risingwave_tpu.stream.hash_agg import HashAggExecutor
        from risingwave_tpu.stream.materialize import MaterializeExecutor

        entry = self.catalog.get(name)
        job = entry.job
        if hasattr(job, "vnode_gate_idx") or hasattr(job, "vnode_gates"):
            # already a partition on this engine (a restarted meta
            # re-adopting lineages): re-point the checkpoint lineage —
            # the caller's recover() then loads it
            if job.n_vnodes != n_vnodes:
                raise PlanError(
                    f"{name!r}: vnode ring mismatch "
                    f"({job.n_vnodes} vs {n_vnodes})"
                )
            job.ckpt_key = ckpt_key
            return {
                "partitioned": True,
                "dml_tables": self._dml_tables_of(job),
                "shuffle_cols": getattr(job, "shuffle_cols", {}),
                "edge_kinds": getattr(job, "edge_kinds", {}),
            }
        if entry.kind != "mview":
            raise PlanError(
                f"{name!r} is not a streaming MV: not scale-eligible"
            )
        if isinstance(job, DagJob):
            return self._partition_dag_job(entry, n_vnodes, ckpt_key)
        if not isinstance(job, StreamingJob):
            raise PlanError(
                f"{name!r} is not a linear streaming MV: not "
                "scale-eligible"
            )
        riders = [e for e in self.catalog.list() if e.job is job]
        if riders != [entry]:
            raise PlanError(
                f"{name!r} shares its job with other MVs/sinks: not "
                "scale-eligible"
            )
        if job.barriers_seen:
            raise PlanError(
                f"{name!r} already ran unpartitioned barriers: "
                "partitioning happens at adoption"
            )
        execs = list(job.fragment.executors)
        aggs = [i for i, ex in enumerate(execs)
                if isinstance(ex, HashAggExecutor)]
        if len(aggs) != 1 or not isinstance(execs[-1],
                                            MaterializeExecutor):
            raise PlanError(
                f"{name!r}: scale-eligible jobs are "
                "source → agg → materialize"
            )
        agg_idx = aggs[0]
        agg = execs[agg_idx]
        for ex in execs[:agg_idx]:
            if not isinstance(ex, (FilterExecutor, ProjectExecutor,
                                   HopWindowExecutor)):
                raise PlanError(
                    f"{name!r}: stateful/watermark prefix executor "
                    f"{type(ex).__name__}: not scale-eligible"
                )
        for ex in execs[agg_idx + 1:-1]:
            if not isinstance(ex, (FilterExecutor, ProjectExecutor)):
                raise PlanError(
                    f"{name!r}: post-agg executor {type(ex).__name__}: "
                    "not scale-eligible"
                )
        if (agg.emit_on_window_close or agg._distinct_aggs
                or agg._minput_aggs
                or agg.watermark_group_idx is not None):
            raise PlanError(
                f"{name!r}: DISTINCT/minput/EOWC/watermark "
                "aggregations are not scale-eligible"
            )
        dist_expr = agg.group_by[0][1]
        f = dist_expr.return_field(agg.in_schema)
        if f.nullable or not np.issubdtype(
                np.dtype(f.data_type.physical_dtype), np.integer):
            raise PlanError(
                f"{name!r}: distribution key {agg.group_by[0][0]!r} "
                "must be a NOT NULL integer-family column"
            )
        # spill-to-host draining is not wired for partitioned state
        # handover: overflow stays a loud error (the sharded mesh path
        # makes the same call)
        for ex in execs:
            if getattr(ex, "spill_ring", 0):
                ex.spill_ring = 0
        gate = VnodeGateExecutor(agg.in_schema, dist_expr, n_vnodes)
        frag = Fragment(execs[:agg_idx] + [gate] + execs[agg_idx:],
                        name=f"{name}_part")
        part = StreamingJob(
            job.source, frag, name,
            checkpoint_frequency=job.checkpoint_frequency,
            checkpoint_store=job.checkpoint_store,
        )
        part.maintenance_interval = job.maintenance_interval
        part.snapshot_interval = job.snapshot_interval
        part.metrics = job.metrics
        part.ckpt_key = ckpt_key
        part.vnode_gate_idx = agg_idx
        part.n_vnodes = n_vnodes
        part.vnodes = frozenset(range(n_vnodes))
        self.jobs[self.jobs.index(job)] = part
        entry.job = part
        entry.mv_state_index = (entry.mv_state_index[0] + 1,) \
            + tuple(entry.mv_state_index[1:])
        self._serving_cache = {}
        # exchange plan input: which raw source column each DML table
        # routes by (None/absent = untraceable → replicate edge)
        tables = self._dml_tables_of(part)
        src_col = self._trace_source_col(execs[:agg_idx], dist_expr)
        part.shuffle_cols = {t: src_col for t in tables} \
            if src_col is not None else {}
        part.edge_kinds = {t: "source" for t in tables}
        self._apply_reader_filters(part)
        return {
            "partitioned": True,
            "dist": agg.group_by[0][0],
            "dml_tables": tables,
            "shuffle_cols": part.shuffle_cols,
            "edge_kinds": part.edge_kinds,
        }

    def _partition_dag_job(self, entry: CatalogEntry, n_vnodes: int,
                           ckpt_key: str) -> dict:
        """Partition a two-source JOIN DagJob: gate each source edge by
        that side's FIRST equi-key vnode (equal join keys share their
        first column, so rows that can ever match co-locate), rebuild
        the join with DENSE retractable sides (whole-key bucket
        entries — the sliceable layout ``handover`` moves), and
        require the MV's leading pk column to carry the preserved
        side's join key so every keyed state in the tree slices,
        serves, and exports in ONE vnode hash domain."""
        from risingwave_tpu.cluster.scale.gate import VnodeGateExecutor
        from risingwave_tpu.expr.node import InputRef
        from risingwave_tpu.stream.dag import FragNode, JoinNode
        from risingwave_tpu.stream.executor import (
            FilterExecutor,
            ProjectExecutor,
        )
        from risingwave_tpu.stream.fragment import Fragment
        from risingwave_tpu.stream.hash_join import HashJoinExecutor
        from risingwave_tpu.stream.materialize import MaterializeExecutor

        name = entry.name
        job = entry.job
        riders = [e for e in self.catalog.list() if e.job is job]
        if riders != [entry]:
            raise PlanError(
                f"{name!r} shares its job with other MVs/sinks: not "
                "scale-eligible"
            )
        if job.barriers_seen:
            raise PlanError(
                f"{name!r} already ran unpartitioned barriers: "
                "partitioning happens at adoption"
            )
        if getattr(job, "mesh", None) is not None or job.staged:
            raise PlanError(
                f"{name!r}: sharded/staged DAGs do not partition "
                "across workers yet (mesh×vnode composition is the "
                "next round)"
            )
        live = [(i, n) for i, n in enumerate(job.nodes)
                if n is not None]
        if len(live) != 2 or not isinstance(live[0][1], JoinNode) \
                or not isinstance(live[1][1], FragNode):
            raise PlanError(
                f"{name!r}: partitioned DAGs are source ⋈ source → "
                "materialize: not scale-eligible"
            )
        jn = live[0][1]
        frag_node = live[1][1]
        join = jn.join
        if not isinstance(join, HashJoinExecutor):
            raise PlanError(
                f"{name!r}: only hash equi-joins partition (got "
                f"{type(join).__name__}): not scale-eligible"
            )
        if join.join_type == "full_outer":
            raise PlanError(
                f"{name!r}: FULL OUTER join has no always-non-NULL "
                "routing column: not scale-eligible"
            )
        if join.left_clean is not None or join.right_clean is not None:
            raise PlanError(
                f"{name!r}: watermark-cleaned join state is not "
                "sliceable: not scale-eligible"
            )
        if jn.left[0] != "source" or jn.right[0] != "source" \
                or jn.left == jn.right:
            raise PlanError(
                f"{name!r}: join sides must read two distinct "
                "sources directly: not scale-eligible"
            )
        if frag_node.input != ("node", live[0][0]):
            raise PlanError(
                f"{name!r}: materialize must consume the join: not "
                "scale-eligible"
            )
        for ks, schema in ((join.left_keys, join.left_schema),
                           (join.right_keys, join.right_schema)):
            k0 = ks[0]
            if not isinstance(k0, InputRef):
                raise PlanError(
                    f"{name!r}: first join key must be a plain "
                    "column: not scale-eligible"
                )
            f = k0.return_field(schema)
            if f.nullable or not np.issubdtype(
                    np.dtype(f.data_type.physical_dtype), np.integer):
                raise PlanError(
                    f"{name!r}: routing key {f.name!r} must be a "
                    "NOT NULL integer-family column"
                )
        execs = list(frag_node.fragment.executors)
        mats = [i for i, ex in enumerate(execs)
                if isinstance(ex, MaterializeExecutor)]
        if len(mats) != 1 or mats[0] != len(execs) - 1 or any(
                not isinstance(ex, (FilterExecutor, ProjectExecutor))
                for ex in execs[:-1]):
            raise PlanError(
                f"{name!r}: post-join chain must be project/filter → "
                "materialize: not scale-eligible"
            )
        mv = execs[-1]
        # the MV's LEADING pk column must carry the preserved side's
        # join key — that one value is the row's vnode identity for
        # state slicing, serving filters, and export seeding
        left_pos = join.left_keys[0].index
        if join.emit_pairs:
            right_pos = len(join.left_schema) \
                + join.right_keys[0].index
        else:  # semi/anti: output is the preserved side alone
            right_pos = join.right_keys[0].index
        allowed = set()
        if join.join_type == "inner":
            allowed = {left_pos, right_pos}
        elif join.preserve_left:
            allowed = {left_pos}
        else:
            allowed = {right_pos}
        traced = self._trace_input_col(execs[:-1], mv.pk_indices[0])
        if traced is None or traced not in allowed:
            raise PlanError(
                f"{name!r}: the MV's leading pk column must be the "
                "preserved side's join key: not scale-eligible"
            )
        # rebuild the join with DENSE (sliceable) sides; pool sides
        # bump-allocate a shared row pool whose (hash, rank) tags do
        # not slice by key
        dense = HashJoinExecutor(
            join.left_schema, join.right_schema,
            join.left_keys, join.right_keys,
            table_size=join.table_size,
            left_bucket_cap=join.left_bucket_cap,
            right_bucket_cap=join.right_bucket_cap,
            left_table_size=join.left_table_size,
            right_table_size=join.right_table_size,
            out_capacity=join.out_capacity,
            join_type=join.join_type,
            left_storage="dense", right_storage="dense",
        )
        gate_l = VnodeGateExecutor(
            join.left_schema, list(join.left_keys), n_vnodes
        )
        gate_r = VnodeGateExecutor(
            join.right_schema, list(join.right_keys), n_vnodes
        )
        lname, rname = jn.left[1], jn.right[1]
        for ex in execs:
            if getattr(ex, "spill_ring", 0):
                ex.spill_ring = 0
        part = DagJob(
            dict(job.sources),
            [
                FragNode(Fragment([gate_l], name=f"{name}_gate_l"),
                         ("source", lname)),
                FragNode(Fragment([gate_r], name=f"{name}_gate_r"),
                         ("source", rname)),
                JoinNode(dense, ("node", 0), ("node", 1)),
                FragNode(Fragment(execs, name=f"{name}_part"),
                         ("node", 2)),
            ],
            name=job.name,
            checkpoint_frequency=job.checkpoint_frequency,
            checkpoint_store=job.checkpoint_store,
        )
        part.maintenance_interval = job.maintenance_interval
        part.snapshot_interval = job.snapshot_interval
        part.metrics = job.metrics
        part.ckpt_key = ckpt_key
        part.vnode_gates = [(0, 0), (1, 0)]
        part.n_vnodes = n_vnodes
        part.vnodes = frozenset(range(n_vnodes))
        self.jobs[self.jobs.index(job)] = part
        entry.job = part
        entry.mv_state_index = (3, len(execs) - 1)
        entry.dag_nodes = [0, 1, 2, 3]
        self._serving_cache = {}
        # shuffle plan: each side's table routes by its own key column
        part.shuffle_cols = {}
        for src_name, keys in ((lname, join.left_keys),
                               (rname, join.right_keys)):
            tbl = self._table_of_reader(part.sources[src_name])
            if tbl is not None:
                part.shuffle_cols[tbl] = keys[0].index
        part.edge_kinds = {t: "join" for t in part.shuffle_cols}
        self._apply_reader_filters(part)
        return {
            "partitioned": True,
            "dist": join.left_schema[left_pos].name,
            "dml_tables": self._dml_tables_of(part),
            "shuffle_cols": part.shuffle_cols,
            "edge_kinds": part.edge_kinds,
        }

    def set_job_vnodes(self, name: str, vnodes) -> None:
        """Swap the partition's owned-vnode mask (STATE, not code: the
        compiled fragment programs never retrace).  The gate's dropped
        counter rides along untouched — it audits the whole life of
        the partition, not one ownership."""
        import jax.numpy as jnp

        def _with_mask(gate, old_state):
            dropped = old_state[1] if isinstance(old_state, tuple) \
                else jnp.zeros((), jnp.int64)
            return (gate.make_mask(job.vnodes), dropped)

        entry = self.catalog.get(name)
        job = entry.job
        job.vnodes = frozenset(int(v) for v in vnodes)
        if hasattr(job, "vnode_gates"):
            states = list(job.states)
            for ni, ei in job.vnode_gates:
                gate = job.nodes[ni].fragment.executors[ei]
                node_states = list(states[ni])
                node_states[ei] = _with_mask(gate, node_states[ei])
                states[ni] = tuple(node_states)
            job.states = tuple(states)
        else:
            gi = job.vnode_gate_idx
            gate = job.fragment.executors[gi]
            states = list(job.states)
            states[gi] = _with_mask(gate, states[gi])
            job.states = tuple(states)
        self._apply_reader_filters(job)

    def apply_shuffle_plan(self, tables: dict) -> None:
        """Install the pushed choreography's per-table shuffle spec —
        ``{table: {"key_col", "n_vnodes", "mode"}}`` — and refresh
        every partitioned job's reader filters against it.  Called by
        the worker on every routing push."""
        self._shuffle_tables = {
            t: e for t, e in (tables or {}).items()
            if e.get("mode") == "shuffle"
            and e.get("key_col") is not None
        }
        for job in self.jobs:
            if getattr(job, "n_vnodes", None) is not None:
                self._apply_reader_filters(job)

    def _apply_reader_filters(self, job) -> None:
        """Point the job's DML readers at its owned vnode set on every
        shuffled table (the reader packs chunks with owned rows only —
        the gate downstream is the assert)."""
        plan = getattr(self, "_shuffle_tables", None) or {}
        own = getattr(job, "vnodes", None)
        for src in self._job_sources(job):
            if not hasattr(src, "vnode_filter"):
                continue
            tbl = self._table_of_reader(src)
            spec = plan.get(tbl)
            # the job's own traced key must agree with the pushed plan
            # (planner compiles from the same spec, but stay paranoid)
            mine = getattr(job, "shuffle_cols", {}).get(tbl)
            if spec is None or own is None or mine is None \
                    or int(spec["key_col"]) != int(mine):
                src.vnode_filter = None
                continue
            src.vnode_filter = (
                int(spec["key_col"]),
                frozenset(int(v) for v in own),
                int(spec["n_vnodes"]),
            )

    def table_consumption_floor(self, table: str) -> int:
        """Lowest unconsumed history position across this engine's
        readers of one DML table — positions below it are never read
        again, so the worker's fence completeness audit starts here
        instead of rescanning the whole history every round."""
        entry = self.catalog.get(table) if table in self.catalog \
            else None
        if entry is None or entry.dml is None:
            return 0
        floors = [
            src.offset
            for job in self.jobs
            for src in self._job_sources(job)
            if getattr(src, "_rows", None) is entry.dml._history
        ]
        return min(floors) if floors else 0

    def partition_stats(self) -> dict:
        """Per-partitioned-job observability: owned vnodes, the
        device gate-drop audit counters, and reader-side filtered-row
        counts (one device readback per gate — off the hot path)."""
        out: dict = {}
        for job in self.jobs:
            if getattr(job, "n_vnodes", None) is None:
                continue
            dropped = 0
            if hasattr(job, "vnode_gates"):
                for ni, ei in job.vnode_gates:
                    st = job.states[ni][ei]
                    if isinstance(st, tuple):
                        dropped += int(np.asarray(st[1]))
            elif hasattr(job, "vnode_gate_idx"):
                st = job.states[job.vnode_gate_idx]
                if isinstance(st, tuple):
                    dropped += int(np.asarray(st[1]))
            out[job.name] = {
                "vnodes": sorted(job.vnodes),
                "gate_dropped": dropped,
                "reader_filtered": sum(
                    getattr(s, "filtered_rows", 0)
                    for s in self._job_sources(job)
                ),
                "shuffle_cols": dict(getattr(job, "shuffle_cols", {})),
            }
        return out

    def repartition_job(self, name: str, vnodes, transfers: list,
                        rewind_epoch: int | None = None) -> dict:
        """Apply one handover step to this worker's partition: rewind
        to the handover epoch if the partition ran ahead (uncommitted
        round), evict stale entries in the gained vnodes, transplant
        each donor's checkpoint slice, then swap the owned mask.

        ``transfers``: ``[{"ckpt": donor_lineage, "epoch": e,
        "vnodes": [...]}]`` — the slices are read from the SHARED
        checkpoint store; only moved vnodes' entries leave disk."""
        from risingwave_tpu.cluster.scale.handover import (
            clear_job_vnodes,
            slice_job_states,
            transplant_job,
        )

        entry = self.catalog.get(name)
        job = entry.job
        if not hasattr(job, "vnode_gate_idx") \
                and not hasattr(job, "vnode_gates"):
            raise PlanError(f"{name!r} is not a partitioned job")
        is_dag = isinstance(job, DagJob)
        if rewind_epoch is not None and (
                job.committed_epoch != rewind_epoch
                or job.sealed_epoch != rewind_epoch):
            job.recover(rewind_epoch)

        def _check_cursor(ours, donor) -> None:
            if ("offset" in ours and "offset" in donor
                    and ours["offset"] != donor["offset"]):
                raise RuntimeError(
                    f"handover cursor mismatch for {name!r}: "
                    f"local {ours['offset']} vs donor "
                    f"{donor['offset']}"
                )

        stats = []
        cleared = 0
        if transfers:
            gained = sorted(
                set(int(v) for t in transfers for v in t["vnodes"])
            )
            job.states, cleared = clear_job_vnodes(
                job, job.states, gained, job.n_vnodes
            )
            fresh = job.barriers_seen == 0 and job.committed_epoch == 0
            for t in transfers:
                loaded = self.checkpoint_store.load(
                    t["ckpt"], int(t["epoch"])
                )
                if loaded is None:
                    raise RuntimeError(
                        f"donor checkpoint {t['ckpt']}@{t['epoch']} "
                        "not found in the shared store"
                    )
                _, d_states, d_src = loaded
                sl = slice_job_states(
                    job, d_states, t["vnodes"], job.n_vnodes
                )
                job.states, moved = transplant_job(
                    job, job.states, sl
                )
                if fresh:
                    # all donors sealed the same round at the same
                    # fence: any donor's cursor is THE cursor of the
                    # handover epoch
                    job._restore_sources(d_src)
                    fresh = False
                else:
                    ours = job._source_state()
                    if is_dag:
                        for sname in job.sources:
                            _check_cursor(ours.get(sname, {}),
                                          d_src.get(sname, {}))
                    else:
                        _check_cursor(ours, d_src)
                stats.append({
                    "ckpt": t["ckpt"],
                    "vnodes": len(t["vnodes"]),
                    "entries": moved,
                })
        self.set_job_vnodes(name, vnodes)
        durable = 0
        if transfers and self.checkpoint_store is not None:
            # durably seal the POST-TRANSPLANT state under this
            # partition's lineage at its committed epoch (0 for a
            # fresh recipient): a recipient killed between the
            # transplant and its first post-handover seal would
            # otherwise re-adopt a lineage MISSING the moved vnodes'
            # state — the crash-mid-scale hole the scale_kill chaos
            # schedule proves closed
            self.checkpoint_store.invalidate(job.ckpt_key)
            self.checkpoint_store.save(
                job.ckpt_key, job.committed_epoch, job.states,
                job._source_state(),
            )
            durable = job.committed_epoch
        # the export diff base is vnode-filtered: ownership changed, so
        # it re-seeds from the shared manifest on the next export
        self._exported.clear()
        return {"vnodes": len(job.vnodes), "cleared": cleared,
                "transfers": stats, "durable_epoch": durable}

    def _vnode_filtered_mv_state(self, st, vn_set, n_vn):
        """A materialize state narrowed to one vnode set: occupancy is
        masked by the stored leading-pk vnode, so stale slots (state a
        handover left behind) and co-owned rows never surface in reads
        or exports."""
        import jax.numpy as jnp

        from risingwave_tpu.cluster.scale.vnode import (
            vnode_member_mask,
            vnodes_of_ints,
        )
        from risingwave_tpu.state.hash_table import HashTable
        from risingwave_tpu.stream.materialize import MvState

        member = vnode_member_mask(vn_set, n_vn)
        key0 = st.table.key_cols[0]
        payload = key0.data if hasattr(key0, "null") else key0
        vn = vnodes_of_ints(payload, n_vn)
        occ = jnp.asarray(st.table.occupied) & member[vn]
        table = HashTable(st.table.key_cols, occ,
                          jnp.asarray(st.table.tombstone),
                          st.table.size)
        return MvState(table, st.values, st.overflow)

    def collect_join_metrics(self) -> None:
        """Export join-path observability into the Prometheus registry.

        ONE device readback per join node (gauges are snapshots, not
        stream counters), so this runs on demand — the scrape/ctl
        surface and tests call it; the steady-state loop never does
        (a sync readback stalls async dispatch; see bench.py).

        Gauges per join node:
        - ``join_probe_calls_per_chunk``: trace-time lookup_or_insert
          calls in the compiled update path (the fused (hash, rank)
          probe keeps this at 1 per append-only side);
        - ``join_probe_iters_per_chunk``: device probe-loop trips;
        - ``join_pool_occupancy``: fill of each pool side's row ring
          (live rows / capacity);
        - ``join_emit_window_fill_ratio``: staged emission rows over
          drained window capacity (small = oversized out_capacity);
        - ``join_drain_windows_per_chunk``: emission windows per probe
          chunk (1 = no amplification re-dispatch).

        Sharded jobs export the same gauges with counters SUMMED over
        the shard axis (chunks count per-shard pulls, so per-chunk
        ratios stay comparable to the linear job's).
        """
        import jax as _jax
        import numpy as _np

        from risingwave_tpu.stream.hash_join import PoolSideState

        for job in self.jobs:
            if not isinstance(job, DagJob):
                continue
            n_shards = job.n_shards
            for idx, node in enumerate(job.nodes):
                if not isinstance(node, JoinNode):
                    continue
                jstate = job.states[idx]
                if not hasattr(jstate, "chunks"):
                    continue  # non-HashJoin two-input node
                labels = {"job": job.name, "node": str(idx)}
                chunks = max(int(_np.asarray(jstate.chunks).sum()), 1)
                self.metrics.set_gauge(
                    "join_probe_iters_per_chunk",
                    float(_np.asarray(jstate.probe_iters).sum())
                    / chunks,
                    **labels,
                )
                out_cap = node.join.out_capacity
                windows = max(
                    int(_np.asarray(jstate.emit_windows).sum()), 1
                )
                self.metrics.set_gauge(
                    "join_emit_window_fill_ratio",
                    float(_np.asarray(jstate.emit_rows).sum())
                    / (windows * out_cap),
                    **labels,
                )
                self.metrics.set_gauge(
                    "join_drain_windows_per_chunk",
                    windows / chunks, **labels,
                )
                for side_name in ("left", "right"):
                    s = getattr(jstate, side_name)
                    if not isinstance(s, PoolSideState):
                        continue
                    from risingwave_tpu.stream.hash_join import (
                        _pool_capacity,
                    )
                    rows0 = s.rows if job.mesh is None else \
                        _jax.tree.map(lambda x: x[0], s.rows)
                    self.metrics.set_gauge(
                        "join_pool_occupancy",
                        float(_np.asarray(s.head - s.tail).sum())
                        / (_pool_capacity(rows0) * n_shards),
                        side=side_name, **labels,
                    )

    def audit_join_probe_counts(self) -> dict:
        """Trace each join's append-only update path and record how
        many table probes the compiled program performs per chunk —
        the regression guard behind the fused (hash, rank) design
        (exactly ONE lookup_or_insert per append-only side per chunk).

        Pure trace (jax.eval_shape — nothing executes, no state is
        touched).  Returns ``{(job, node_idx, side):
        {"lookup_or_insert": n, "lookup": m}}`` and exports each count
        as a ``join_probe_calls_per_chunk`` gauge."""
        import jax as _jax

        from risingwave_tpu.state.hash_table import (
            PROBE_STATS,
            reset_probe_stats,
        )

        out: dict = {}
        for job in self.jobs:
            if not isinstance(job, DagJob):
                continue
            for idx, node in enumerate(job.nodes):
                if not isinstance(node, JoinNode):
                    continue
                join = node.join
                if not hasattr(join, "storage_of"):
                    continue
                for side in ("left", "right"):
                    if join.storage_of(side) != "pool":
                        continue
                    schema = join.left_schema if side == "left" \
                        else join.right_schema
                    keys = join.left_keys if side == "left" \
                        else join.right_keys
                    clean = join.clean_rule(side)
                    proto = _empty_chunk(schema, 4)
                    sstate = getattr(job.states[idx], side)
                    if job.mesh is not None:
                        # audit the per-shard program (drop the shard
                        # axis — every shard compiles the same body)
                        sstate = _jax.tree.map(
                            lambda x: _jax.ShapeDtypeStruct(
                                x.shape[1:], x.dtype
                            ), sstate,
                        )
                    reset_probe_stats()
                    _jax.eval_shape(
                        lambda s, c, keys=keys, clean=clean:
                            join._update_side_pool(s, c, keys, clean),
                        sstate, proto,
                    )
                    stats = dict(PROBE_STATS)
                    out[(job.name, idx, side)] = stats
                    self.metrics.set_gauge(
                        "join_probe_calls_per_chunk",
                        stats["lookup_or_insert"],
                        job=job.name, node=str(idx), side=side,
                    )
        return out

    # -- storage service (Hummock-lite) ---------------------------------
    def start_storage_service(self) -> None:
        """Start the background compactor (the fourth node role);
        server.py calls this, embedded tests drive synchronously."""
        if self.compactor is not None:
            self.compactor.start()

    def stop_storage_service(self) -> None:
        if self.compactor is not None:
            self.compactor.stop()

    def _storage_stall_hook(self) -> float:
        """The barrier loop's write-stall gate: block while storage L0
        is over the stall threshold (compaction behind ingest)."""
        return self.hummock.wait_below_stall(timeout=5.0)

    @staticmethod
    def _mv_storage_range(name: str) -> tuple[bytes, bytes]:
        """Key range of one MV in the shared storage keyspace (the
        TableKey table-prefix scheme, hummock_sdk/src/key.rs)."""
        lo = b"m:" + name.encode() + b"\x00"
        return lo, lo[:-1] + b"\x01"

    def _mv_export_items(self, entry: CatalogEntry) -> dict:
        """(storage key → pickled row) of an MV's CURRENT rows in the
        shared ``m:<name>\\0<pk>`` keyspace — the export seam both the
        single-node ``storage_export_mv`` and the cluster worker's
        per-barrier delta export build on.

        TTL MVs export only rows AT/ABOVE the expiry cutoff: rows
        below the horizon neither upsert (a compaction that dropped
        them must never see them resurrected by the next diff) nor
        tombstone (expiry is the compactor's job — the policy rides
        the manifest, see ``_ttl_policy``)."""
        import pickle as _pickle

        schema = entry.mv_executor.in_schema
        pk = entry.export_pk \
            if entry.export_pk is not None \
            else getattr(entry.mv_executor, "pk_indices",
                         tuple(range(len(schema))))
        lo, _ = self._mv_storage_range(entry.name)
        new: dict[bytes, bytes] = {}
        for row in self._mv_rows(entry):
            key = lo + b"".join(
                _mc_encode_value(row[i], schema[i]) for i in pk
            )
            new[key] = _pickle.dumps(tuple(row), protocol=4)
        cut = self._ttl_cutoffs.get(entry.name)
        if cut:
            new = {k: v for k, v in new.items() if k >= cut}
        return new

    def _ttl_policy(self, entry: CatalogEntry, epoch: int):
        """Derive (and monotonically advance) one TTL MV's expiry
        policy at export time: horizon = max observed leading
        export-pk value − ttl.  The max-observed value is the
        watermark proxy at barrier commit — it never regresses, so the
        horizon (and the byte cutoff compiled from it) only moves
        forward.  Returns the ``ExpiryPolicy`` to publish, or None
        when no horizon exists yet (empty MV)."""
        from risingwave_tpu.storage.pushdown import (
            ExpiryPolicy,
            table_prefix,
        )

        if entry.ttl is None:
            return None
        col_name, ttl = entry.ttl
        schema = entry.mv_executor.in_schema
        idx = schema.index_of(col_name)
        mx = None
        for row in self._mv_rows(entry):
            v = row[idx]
            if v is not None and (mx is None or v > mx):
                mx = v
        if mx is not None:
            horizon = int(mx) - int(ttl)
            cur = self._ttl_horizons.get(entry.name)
            if cur is None or horizon > cur:
                self._ttl_horizons[entry.name] = horizon
        horizon = self._ttl_horizons.get(entry.name)
        if horizon is None:
            return None
        prefix = table_prefix(entry.name)
        enc = _mc_encode_value(horizon, schema[idx])
        pol = ExpiryPolicy(
            table=entry.name, prefix=prefix,
            expire_below=prefix + bytes(enc), horizon=horizon,
            ttl=int(ttl), column=col_name, epoch=int(epoch),
        )
        self._ttl_cutoffs[entry.name] = pol.expire_below
        return pol

    def _publish_mv_schema(self, store, entry: CatalogEntry,
                           since_epoch: int | None = None) -> None:
        """Publish the MV's shape next to its data so an engine-free
        serving replica can encode pk probes and project columns
        without the binder (serve/reader.MvSchema loads this).

        Index MVs carry ``index_of``/``index_width`` plus the epoch
        their FIRST export rides (``since_epoch``) — a replica pinned
        before that epoch must not trust the index range (the doc is
        an unversioned side-channel; the data is versioned).  The
        upstream's doc lists its indexes so ``plan_read`` can rewrite
        equality predicates without a catalog."""
        import json as _json

        from risingwave_tpu.serve.reader import schema_key

        schema = entry.mv_executor.in_schema
        pk = entry.export_pk \
            if entry.export_pk is not None \
            else getattr(entry.mv_executor, "pk_indices",
                         tuple(range(len(schema))))
        cols = []
        for f in schema:
            if f.data_type.is_string:
                kind = "string"
            elif f.data_type == DataType.DECIMAL:
                kind = "decimal"
            elif f.data_type in (DataType.FLOAT32, DataType.FLOAT64):
                kind = "float"
            else:
                kind = "int"
            cols.append({
                "name": f.name, "kind": kind,
                "scale": int(getattr(f, "decimal_scale", 0) or 0),
                "hidden": f.name.startswith("_hidden_"),
                "nullable": bool(f.nullable),
            })
        doc = {"mv": entry.name, "columns": cols, "pk": list(pk)}
        if entry.index_on is not None:
            doc["index_of"] = entry.index_on[0]
            doc["index_width"] = len(entry.index_on[1])
            if since_epoch is not None:
                doc["since_epoch"] = int(since_epoch)
        idxs = [
            {"name": e.name, "cols": list(e.index_on[1])}
            for e in self.catalog.list("mview")
            if e.index_on is not None
            and e.index_on[0] == entry.name
        ]
        if idxs:
            doc["indexes"] = idxs
        store.put(schema_key(entry.name),
                  _json.dumps(doc).encode())

    def storage_export_mv(self, name: str) -> dict:
        """Export an MV's current rows into the storage service as an
        epoch-stamped changelog batch (upserts + tombstones for rows
        gone since the last export) — ONE new L0 SST, no merge I/O;
        the compactor folds it down in the background."""
        if self.hummock is None:
            raise PlanError("storage export needs a durable data_dir")
        entry = self.catalog.get(name)
        if entry.kind != "mview" or entry.job is None:
            raise PlanError(f"{name!r} is not a materialized view")
        epoch = entry.job.committed_epoch
        lo, hi = self._mv_storage_range(name)
        pol = self._ttl_policy(entry, epoch)
        new = self._mv_export_items(entry)
        cut = self._ttl_cutoffs.get(name)
        # keys below the cutoff get NO tombstone — expiry is the
        # compaction filter's job (the policy committed below)
        stale = [k for k, _ in self.hummock.scan(lo, hi)
                 if k not in new and not (cut and k < cut)]
        from risingwave_tpu.storage.sst import TOMBSTONE
        batch = sorted(new.items()) + [(k, TOMBSTONE) for k in stale]
        self.hummock.write_batch(batch, epoch=epoch)
        if pol is not None:
            self.hummock.set_policy(name, pol.to_doc())
        self._publish_mv_schema(self.hummock.store, entry,
                                since_epoch=epoch)
        self._schema_published.add(entry.name)
        self.metrics.inc("storage_mv_export_rows_total", len(new),
                         job=name)
        return {"mv": name, "epoch": epoch, "rows": len(new),
                "deletes": len(stale)}

    def _seed_exported(self, store, name: str) -> dict:
        """Rebuild the export diff base of one MV from the SHARED
        manifest (a fresh/adopting worker has no export memory; the
        committed storage state IS the base the next delta must diff
        against)."""
        from risingwave_tpu.serve.reader import (
            ManifestFollower,
            mv_key_range,
        )
        from risingwave_tpu.storage.sst import SstReader, merge_scan

        v = ManifestFollower(store).refresh(None)
        readers = [SstReader(store=store, key=s.key)
                   for lv in v.levels for s in lv
                   if s.key not in self._seed_exclude]
        try:
            lo, hi = mv_key_range(name)
            base = dict(merge_scan(readers, lo, hi))
        finally:
            for r in readers:
                r.close()
        entry = self.catalog.get(name) if name in self.catalog else None
        if entry is None or getattr(entry.job, "n_vnodes", None) is None:
            return base
        # partitioned MV: the manifest holds EVERY partition's rows;
        # the diff base keeps only this partition's vnodes, so narrowed
        # ownership never emits tombstones for rows another partition
        # now owns (and gained rows never re-upload unchanged)
        import pickle as _pickle

        from risingwave_tpu.cluster.scale.vnode import vnodes_of_ints

        if not base:
            return base
        pk0 = entry.mv_executor.pk_indices[0]
        keys = list(base)
        vals = np.asarray(
            [int(_pickle.loads(base[k])[pk0]) for k in keys], np.int64
        )
        vn = np.asarray(vnodes_of_ints(vals, entry.job.n_vnodes))
        own = {int(v) for v in entry.job.vnodes}
        return {k: base[k] for k, v in zip(keys, vn) if int(v) in own}

    def export_mv_deltas(self, job_name: str, epoch: int) -> list:
        """Cluster-mode per-barrier MV export: diff every MV riding
        ``job_name`` against its last export, seal the changes
        (upserts + tombstones) as ONE new SST uploaded to the shared
        store, and return its descriptor(s) for the meta to commit
        into the shared manifest with the round's cluster epoch — the
        meta stays the manifest's single writer; workers only upload
        objects under meta-allocated (vacuum-protected) keys."""
        from risingwave_tpu.storage.sst import (
            TOMBSTONE,
            build_sst_bytes,
        )

        store = self.shared_store if self.shared_store is not None \
            else (self.hummock.store if self.hummock is not None
                  else None)
        if store is None or self.sst_key_allocator is None:
            return []
        batch: list[tuple[bytes, bytes]] = []
        staged: list[tuple[str, dict, int]] = []
        for entry in self.catalog.list("mview"):
            if entry.job is None or entry.job.name != job_name \
                    or entry.mv_executor is None:
                continue
            pol = self._ttl_policy(entry, epoch)
            if pol is not None:
                self.pending_policies[entry.name] = pol.to_doc()
            new = self._mv_export_items(entry)
            prev = self._exported.get(entry.name)
            if prev is None:
                prev = self._seed_exported(store, entry.name)
            cut = self._ttl_cutoffs.get(entry.name)
            if cut:
                # the diff base forgets expired keys too: no
                # tombstones for rows the compactor will drop, and a
                # drop that already happened cannot resurrect
                prev = {k: v for k, v in prev.items() if k >= cut}
            if entry.name not in self._schema_published:
                # first export this process, or a CREATE/DROP INDEX
                # dirtied the doc (the index list changed)
                self._publish_mv_schema(store, entry,
                                        since_epoch=epoch)
                self._schema_published.add(entry.name)
            ups = [(k, v) for k, v in new.items()
                   if prev.get(k) != v]
            dels = [(k, TOMBSTONE) for k in prev if k not in new]
            batch += ups + dels
            staged.append((entry.name, new, len(ups)))
        if not batch:
            for name, new, _ in staged:
                self._exported[name] = new
            return []
        batch.sort()
        key = self.sst_key_allocator()
        data, meta = build_sst_bytes(
            [k for k, _ in batch], [v for _, v in batch]
        )
        store.put(key, data)
        # the diff base moves ONLY after the object landed: an export
        # whose upload dies keeps its rows in the next attempt's diff
        # instead of silently dropping them from the serving tier
        for name, new, n_ups in staged:
            self._exported[name] = new
            if n_ups:
                self.metrics.inc("storage_mv_export_rows_total",
                                 n_ups, job=name)
        return [{
            "key": key,
            "first_key": meta.first_key.hex(),
            "last_key": meta.last_key.hex(),
            "n_records": meta.n_records,
            "size": meta.size,
            "epoch": epoch,
        }]

    def reexport_job_mvs(self, job_name: str, exclude=()) -> list:
        """Integrity repair export: drop the export diff bases of every
        MV riding ``job_name`` and re-seed them from the shared
        manifest EXCLUDING the quarantined keys — the resulting SST
        carries upserts for every row the corrupt object held and
        tombstones for rows it shadowed, so swapping it in for the
        corrupt SST is byte-exact.  Returns the SST descriptors for the
        meta's atomic replace commit."""
        job = self._job_by_name(job_name)
        if job is None:
            return []
        for entry in self.catalog.list("mview"):
            if entry.job is not None and entry.job.name == job_name:
                self._exported.pop(entry.name, None)
        self._seed_exclude = frozenset(exclude or ())
        try:
            return self.export_mv_deltas(job_name, job.committed_epoch)
        finally:
            self._seed_exclude = frozenset()

    def take_pending_policies(self) -> dict:
        """Drain the policy docs staged by this round's exports
        (table → doc, None = DROP) — the cluster worker ships them in
        its barrier response and the meta folds them into the SAME
        manifest delta that commits the round's export SSTs."""
        out, self.pending_policies = self.pending_policies, {}
        return out

    def _tombstone_dropped_mv(self, entry: CatalogEntry) -> None:
        """DROP MATERIALIZED VIEW / DROP INDEX removes the MV from the
        SHARED serving keyspace too: one tombstone batch for every
        exported row plus the serve-schema doc deleted, so a serving
        replica answers "does not exist" instead of stale rows.  Only
        the manifest OWNER writes (single node / meta-owned storage);
        a cluster compute worker just forgets its export diff base —
        the meta, which owns the manifest over the same store, writes
        the tombstones when it unplaces the MV."""
        from risingwave_tpu.storage.hummock.object_store import (
            ObjectError,
        )

        import json as _json

        self._exported.pop(entry.name, None)
        self._schema_published.discard(entry.name)
        if entry.ttl is not None:
            # retire the expiry policy with the MV (cluster workers
            # stage the removal; the manifest owner commits it below)
            self._ttl_horizons.pop(entry.name, None)
            self._ttl_cutoffs.pop(entry.name, None)
            self.pending_policies[entry.name] = None
        if entry.index_on is not None:
            # the upstream's doc must stop advertising this index
            self._schema_published.discard(entry.index_on[0])
        if self.hummock is None:
            return
        from risingwave_tpu.serve.reader import schema_key

        if entry.index_on is not None:
            # rewrite the upstream doc BEFORE the tombstone delta: a
            # reader refreshing past the tombstones must not plan
            # through the dead index (readers pinned earlier still see
            # consistent doc+data)
            try:
                doc = _json.loads(
                    self.hummock.store.get(schema_key(entry.index_on[0]))
                )
                doc["indexes"] = [
                    e for e in doc.get("indexes", [])
                    if e.get("name") != entry.name
                ]
                if not doc["indexes"]:
                    doc.pop("indexes")
                self.hummock.store.put(
                    schema_key(entry.index_on[0]),
                    _json.dumps(doc).encode(),
                )
            except ObjectError:
                pass  # upstream never exported
        lo, hi = self._mv_storage_range(entry.name)
        keys = [k for k, _ in self.hummock.scan(lo, hi)]
        if keys:
            self.hummock.delete_batch(
                keys, epoch=self.hummock.versions.max_committed_epoch
            )
        if entry.ttl is not None:
            self.hummock.set_policy(entry.name, None)
        try:
            self.hummock.store.delete(schema_key(entry.name))
        except ObjectError:
            pass  # never exported

    def storage_serve_mv(self, name: str) -> list:
        """Serve an exported MV from the storage service through a
        PINNED version — a consistent SST set even while the compactor
        rewrites levels and vacuum deletes their inputs (the
        BatchTable-over-Hummock read, SURVEY §3.4)."""
        import pickle as _pickle

        if self.hummock is None:
            raise PlanError("storage serving needs a durable data_dir")
        lo, hi = self._mv_storage_range(name)
        with self.hummock.pin() as pv:
            return [_pickle.loads(v) for _, v in pv.scan(lo, hi)]

    def storage_vacuum(self) -> dict:
        """GC pass: delete SST objects unreferenced by any pinned
        version (checkpoint exports live outside the sst/ prefix and
        are never touched)."""
        if self.hummock is None:
            raise PlanError("storage vacuum needs a durable data_dir")
        deleted = self.hummock.vacuum()
        return {"deleted_objects": deleted,
                **{"remaining_objects": self.hummock.stats()["objects"]}}

    # -- serving reads ---------------------------------------------------
    @staticmethod
    def _host_col(bound, chunk, vis):
        """Materialize a bound expr over visible rows as host values
        (strings decoded, decimals descaled)."""
        from risingwave_tpu.common.chunk import StrCol, decode_strings

        col = bound.eval(chunk)
        if isinstance(col, StrCol):
            return decode_strings(
                np.asarray(col.data)[vis], np.asarray(col.lens)[vis]
            ).tolist(), True
        f = bound.return_field(chunk.schema)
        vals = np.asarray(col)[vis]
        if f.data_type == DataType.DECIMAL:
            vals = vals.astype(np.float64) / 10**f.decimal_scale
        return vals.tolist(), False

    def _mv_vnode_set(self, entry: CatalogEntry):
        """(vnode_set, n_vnodes) a read of this MV must narrow to, or
        (None, None).  An explicit per-read override (the meta passes
        the map AT THE PINNED ROUND) wins over the partition's current
        ownership."""
        n_vn = getattr(entry.job, "n_vnodes", None)
        if n_vn is None:
            return None, None
        override = getattr(self, "_serve_vnodes", None)
        return (override if override is not None
                else entry.job.vnodes), n_vn

    def _mv_rows(self, entry: CatalogEntry):
        """Every row of a view, on the host (a device readback, under
        the engine lock: span ``_mv_rows``)."""
        with GLOBAL_TRACE.span("_mv_rows", mv=entry.name):
            return self._mv_rows_impl(entry)

    def _mv_rows_impl(self, entry: CatalogEntry):
        from risingwave_tpu.stream.sharded import ShardedStreamingJob

        vn_set, n_vn = self._mv_vnode_set(entry)
        # time travel: SET query_epoch reads a retained historical
        # checkpoint (ref FOR SYSTEM_TIME AS OF over Hummock versions,
        # time_travel_version_cache.rs)
        qe = int(self.session_config.get("query_epoch"))
        if qe:
            if self.checkpoint_store is None:
                raise PlanError(
                    "query_epoch needs a durable data_dir"
                )
            # checkpoints live under the JOB's lineage key — an MV
            # attached to a shared DagJob (MV-on-MV) reads its job's
            # snapshot; a partitioned job reads its own partition's
            ckpt_name = entry.job.ckpt_key
            epochs = self.checkpoint_store.epochs(ckpt_name)
            if qe not in epochs:
                raise PlanError(
                    f"epoch {qe} is not retained for {entry.name} "
                    f"(retained: {epochs})"
                )
            _, state, _ = self.checkpoint_store.load(ckpt_name, qe)
        else:
            state = entry.job.states
        for i in entry.mv_state_index:
            state = state[i]
        # a mesh job's state is stacked, a shard a leading row
        stacked = isinstance(entry.job, ShardedStreamingJob) \
            or getattr(entry.job, "mesh", None) is not None
        if vn_set is not None and not stacked:
            state = self._vnode_filtered_mv_state(state, vn_set, n_vn)
        with GLOBAL_TRACE.span("_mv_rows.to_host") as span:
            rows, moved = view_rows(entry.mv_executor, state, stacked)
            span.set(**moved)
        if moved.get("bytes"):
            self.metrics.inc("mv_read_bytes_total", moved["bytes"],
                             job=entry.name, path=moved["path"])
        return rows

    @staticmethod
    def _order_permutation(chunk, order_by, n_rows: int) -> list[int]:
        """Stable multi-key sort permutation over a host-built chunk.

        Keys evaluate in ORIGINAL row order (the permutation indexes
        original rows, so every pass stays aligned); NULLs sort last
        for ASC (pg default), first for DESC."""
        from risingwave_tpu.common.chunk import StrCol, decode_strings

        perm = list(range(n_rows))
        vis = np.asarray(chunk.valid)
        for e, desc in reversed(list(order_by)):
            vals, vals_null = split_col(e.eval(chunk))
            if isinstance(vals, StrCol):
                host = decode_strings(
                    np.asarray(vals.data)[vis], np.asarray(vals.lens)[vis]
                ).tolist()
            else:
                host = np.asarray(vals)[vis].tolist()
            if vals_null is not None:
                nulls = np.asarray(vals_null)[vis].tolist()
                z = type(host[0])() if host else 0
                host = [(True, z) if nul else (False, v)
                        for v, nul in zip(host, nulls)]
            perm.sort(key=lambda i: host[i], reverse=desc)
        return perm

    def _apply_serving_topn(self, entry: CatalogEntry, rows: list):
        """Global order+limit over a sharded TopN MV's merged bands.

        Each shard's band is a superset slice of the global top-k; the
        serving boundary is the singleton merge (ref top_n singleton
        fragments)."""
        spec = getattr(entry.mv_executor, "serving_topn", None)
        if spec is None or not rows:
            return rows
        order_by, limit, offset = spec
        schema = entry.mv_executor.in_schema
        arrays = [np.asarray([r[i] for r in rows])
                  for i in range(len(schema))]
        chunk = Chunk.from_numpy(schema, arrays, capacity=len(rows))
        perm = self._order_permutation(chunk, order_by, len(rows))
        rows = [rows[i] for i in perm]
        end = None if limit is None else offset + limit
        return rows[offset:end]

    def _needs_batch_exec(self, select: ast.Select) -> bool:
        """Fast path = plain projection/filter over one MV; everything
        else (aggs, GROUP BY, joins, derived tables, subqueries in
        WHERE, base-table scans) runs the batch executor pipeline."""
        if not isinstance(select.from_, ast.TableRef):
            return True
        if select.from_.name not in self.catalog:
            return False  # fast path raises the proper error
        if self.catalog.get(select.from_.name).kind != "mview":
            return True
        if select.group_by or select.having is not None \
                or self.planner._has_agg(select):
            return True

        def has_sub(e) -> bool:
            if isinstance(e, (ast.ScalarSubquery, ast.InSubquery,
                              ast.ExistsSubquery)):
                return True
            for a in ("left", "right", "operand"):
                v = getattr(e, a, None)
                if v is not None and has_sub(v):
                    return True
            return any(has_sub(x) for x in getattr(e, "args", ())
                       if not isinstance(x, ast.Star))

        return select.where is not None and has_sub(select.where)

    def _serve(self, select: ast.Select):
        """Batch read over a materialized view (local execution mode)."""
        if self._needs_batch_exec(select):
            return self._serve_batch(select)
        if not isinstance(select.from_, ast.TableRef):
            raise PlanError("serving reads support SELECT ... FROM <mv>")
        entry = self.catalog.get(select.from_.name)
        if entry.kind != "mview":
            raise PlanError("serving reads are over materialized views; "
                            "streaming queries use CREATE MATERIALIZED VIEW")
        rows = self._mv_rows(entry)
        rows = self._apply_serving_topn(entry, rows)
        schema = entry.schema
        # rebuild a host chunk and evaluate the residual query eagerly
        if rows:
            arrays = [np.asarray([r[i] for r in rows])
                      for i in range(len(schema))]
        else:
            arrays = [np.zeros((0,), np.int64) for _ in range(len(schema))]
        chunk = Chunk.from_numpy(schema, arrays, capacity=max(len(rows), 1))
        scope = Scope.of(schema, select.from_.alias or select.from_.name)
        if select.where is not None:
            keep = Binder(scope).bind(select.where).eval(chunk)
            chunk = chunk.mask(keep)
        # aggregates/GROUP BY route to _serve_batch before reaching
        # here (_needs_batch_exec); the interpreted host-agg path that
        # used to live at this dispatch is deleted — one SQL semantics,
        # one (compiled) implementation
        items = self.planner._expand_items(select.items, scope)
        b = Binder(scope)
        out_cols = []
        bound_fields = []
        for name, e in items:
            be = b.bind(e)
            out_cols.append(be.eval(chunk))
            f = be.return_field(schema)
            bound_fields.append(Field(
                name, f.data_type, str_width=f.str_width,
                decimal_scale=f.decimal_scale,
            ))
        self._last_columns = [f.name for f in bound_fields]
        self._last_fields = bound_fields
        out_chunk = chunk.with_columns(out_cols, Schema(tuple(bound_fields)))
        _, cols, _ = out_chunk.to_host()
        result = [tuple(c[i] for c in cols) for i in range(len(cols[0]))] \
            if cols else []
        # ORDER BY / LIMIT / OFFSET host-side (python sort: handles
        # strings and any comparable type, stable for multi-key)
        if select.order_by:
            out_scope = Scope.of(out_chunk.schema)
            ob = Binder(out_scope)
            order_by = [
                (self.planner._bind_order_key(
                    oi.expr, ob, out_chunk.schema
                ), oi.descending)
                for oi in select.order_by
            ]
            perm = self._order_permutation(
                out_chunk, order_by, len(result)
            )
            result = [result[i] for i in perm]
        if select.offset:
            result = result[select.offset:]
        if select.limit is not None:
            result = result[:select.limit]
        return result


class _SnapshotReader:
    """Bounded serving source: an MV's rows at read time, as one
    static-capacity all-inserts chunk (ref RowSeqScanExecutor reading a
    BatchTable at a pinned epoch, row_seq_scan.rs:44 — here the
    'table' is the MV's device state, snapshotted zero-copy)."""

    def __init__(self, engine, entry):
        self.engine = engine
        self.entry = entry
        self._chunks: list = []
        self._empty = None

    def reset(self) -> None:
        from risingwave_tpu.stream.sharded import ShardedStreamingJob
        import jax.numpy as jnp

        entry = self.entry
        if isinstance(entry.job, ShardedStreamingJob) \
                or getattr(entry.job, "mesh", None) is not None:
            # sharded upstream: host-gathered rows re-encoded at the
            # executor's static capacity
            rows = self.engine._mv_rows(entry)
            ex = entry.mv_executor
            cap = getattr(ex, "table_size", None) \
                or getattr(ex, "ring_size")
            schema = ex.in_schema
            if rows:
                arrays = [np.asarray([r[i] for r in rows])
                          for i in range(len(schema))]
            else:
                arrays = [np.zeros((0,), np.int64) for _ in schema]
            chunk = Chunk.from_numpy(schema, arrays, capacity=cap)
        else:
            chunk = self.engine._mv_snapshot_chunk(entry)
        self._chunks = [chunk]
        if self._empty is None:
            self._empty = Chunk(
                chunk.columns,
                jnp.zeros((chunk.capacity,), jnp.int8),
                jnp.zeros((chunk.capacity,), jnp.bool_),
                chunk.schema,
            )

    def pending(self) -> int:
        return len(self._chunks)

    def next_chunk(self):
        if self._chunks:
            return self._chunks.pop()
        return self._empty


def _const_value(e):
    """Evaluate a constant VALUES expression host-side."""
    if isinstance(e, ast.Literal):
        return e.value
    if isinstance(e, ast.IntervalLit):
        return e.micros
    if isinstance(e, ast.UnaryOp) and e.op == "neg":
        return -_const_value(e.operand)
    if isinstance(e, ast.Cast):
        v = _const_value(e.operand)
        t = DataType.from_sql(e.type_name)
        return _coerce_const(v, Field("?", t))
    raise ValueError(f"INSERT VALUES must be constants, got {e!r}")


def _coerce_const(v, field: Field):
    """Validate/convert one INSERT value to the column type at statement
    time — a bad constant must fail the INSERT, never poison the queue
    for every downstream job."""
    t = field.data_type
    if v is None:
        if not field.nullable:
            raise ValueError(
                f"NULL value for NOT NULL column {field.name} "
                "(declare the column `NULL` to allow NULLs)"
            )
        return None
    try:
        if t.is_string:
            return str(v)
        if t in (DataType.FLOAT32, DataType.FLOAT64, DataType.DECIMAL):
            return float(v)
        if t == DataType.BOOLEAN:
            if isinstance(v, str):
                raise ValueError(v)
            return bool(v)
        if isinstance(v, str) and t in (
            DataType.TIMESTAMP, DataType.TIMESTAMPTZ, DataType.DATE
        ):
            # '2015-07-15 00:00:00.005' literals (pg-style)
            from datetime import date, datetime, timezone

            if t == DataType.DATE:
                return (date.fromisoformat(v) - date(1970, 1, 1)).days
            dt = datetime.fromisoformat(v.replace("Z", "+00:00"))
            if dt.tzinfo is not None:
                dt = dt.astimezone(timezone.utc).replace(tzinfo=None)
            from datetime import timedelta
            # exact integer microseconds (float total_seconds() rounds)
            return (dt - datetime(1970, 1, 1)) // timedelta(microseconds=1)
        if isinstance(v, float):
            return int(round(v))  # SQL casts round, not truncate
        return int(v)
    except (TypeError, ValueError) as e:
        raise ValueError(
            f"invalid value {v!r} for column "
            f"{field.name} ({t.value})"
        ) from e


class _ProjectingReader:
    """Column-projecting wrapper over a source reader."""

    def __init__(self, inner, idxs: Sequence[int], schema: Schema):
        self.inner = inner
        self.idxs = list(idxs)
        self.schema = schema
        if hasattr(inner, "impl"):
            self.impl = lambda k0, cap: inner.impl(k0, cap).project(
                self.idxs
            )
            self.cap = inner.cap
            self.next_base = inner.next_base
        if hasattr(inner, "events_per_row"):
            self.events_per_row = inner.events_per_row

    def next_chunk(self) -> Chunk:
        return self.inner.next_chunk().project(self.idxs)

    @property
    def offset(self):
        return self.inner.offset

    @offset.setter
    def offset(self, v):
        self.inner.offset = v

    def state(self):
        return self.inner.state()


class _DatagenReader:
    """Deterministic generator for declared columns (ref datagen source)."""

    def __init__(self, schema: Schema, cap: int, split_id: int,
                 num_splits: int):
        self.schema = schema
        self.cap = cap
        self.split_id = split_id
        self.num_splits = num_splits
        self.offset = 0

    def next_chunk(self) -> Chunk:
        import jax.numpy as jnp

        base = self.offset * self.num_splits + self.split_id * self.cap
        k = base + np.arange(self.cap, dtype=np.int64)
        cols = []
        for f in self.schema:
            t = f.data_type
            if t.is_string:
                from risingwave_tpu.common.chunk import StrCol, encode_strings
                data, lens = encode_strings(
                    [f"{f.name}_{int(v) % 1000}" for v in k], f.str_width
                )
                cols.append(StrCol(jnp.asarray(data), jnp.asarray(lens)))
            elif t in (DataType.FLOAT32, DataType.FLOAT64):
                cols.append(jnp.asarray(
                    (k % 1000).astype(np.float64) / 10.0, t.physical_dtype
                ))
            else:
                cols.append(jnp.asarray(k, t.physical_dtype))
        self.offset += self.cap
        return Chunk(
            tuple(cols),
            jnp.zeros((self.cap,), jnp.int8),
            jnp.ones((self.cap,), jnp.bool_),
            self.schema,
        )

    def state(self):
        return {"offset": self.offset, "split_id": self.split_id}
