"""Admin/introspection surface (the risectl + dashboard analog).

Reference counterparts: ``src/ctl`` (risectl: cluster-info, pause/
resume-barrier, await-tree dump) and the meta dashboard's fragment
graph / ``EXPLAIN ANALYZE`` for streaming jobs
(``GetStreamingStats``, proto/monitor_service.proto:152).

``describe_job`` is the await-tree analog: instead of async stack
traces (there are no tasks to trace — fragments are jitted programs),
it reports the executor tree with live state-occupancy gauges, the
consistency counters, and the job's epoch/offset positions — what an
operator actually needs to see for a stuck or skewed job.
"""

from __future__ import annotations

from typing import Any

import jax.numpy as jnp


def _state_gauges(executor, state) -> dict:
    out: dict[str, Any] = {}
    table = getattr(state, "table", None)
    if table is not None and hasattr(table, "occupied"):
        out["groups"] = int(jnp.sum(table.occupied))
        out["tombstones"] = int(table.tombstone_count())
        out["table_size"] = table.size
    if hasattr(state, "valid") and getattr(state, "valid", None) is not None \
            and hasattr(state.valid, "dtype"):
        out["pool_rows"] = int(jnp.sum(state.valid))
    if hasattr(state, "cursor"):
        out["rows_written"] = int(state.cursor)
    if hasattr(state, "dirty"):
        out["dirty"] = int(jnp.sum(state.dirty))
    if hasattr(state, "wm"):
        out["watermark"] = int(state.wm)
    if hasattr(state, "max_ts"):
        out["max_event_time"] = int(state.max_ts)
    for counter in ("overflow", "inconsistency", "late_rows",
                    "emit_overflow"):
        if hasattr(state, counter):
            v = getattr(state, counter)
            out[counter] = int(jnp.sum(v))
    # join side states
    for side in ("left", "right"):
        if hasattr(state, side):
            s = getattr(state, side)
            out[side] = {
                "keys": int(jnp.sum(s.key_table.occupied)),
                "rows": int(jnp.sum(s.occupied)),
                "overflow": int(s.overflow),
                "inconsistency": int(s.inconsistency),
            }
    return out


def describe_job(job) -> dict:
    """Executor tree + state gauges for one streaming job."""
    from risingwave_tpu.stream.dag import DagJob, FragNode
    from risingwave_tpu.stream.runtime import StreamingJob
    from risingwave_tpu.stream.sharded import ShardedStreamingJob

    info: dict[str, Any] = {
        "name": job.name,
        "kind": type(job).__name__,
        "committed_epoch": job.committed_epoch,
        "barriers": job.barriers_seen,
        "paused": job.paused,
    }
    if isinstance(job, StreamingJob):
        info["source_offset"] = getattr(job.source, "offset", None)
        info["executors"] = [
            {"executor": repr(ex), **_state_gauges(ex, job.states[i])}
            for i, ex in enumerate(job.fragment.executors)
        ]
    elif isinstance(job, DagJob):
        info["sources"] = {
            name: getattr(src, "offset", None)
            for name, src in job.sources.items()
        }
        info["executors"] = []
        for idx, node in enumerate(job.nodes):
            if node is None:
                continue
            if isinstance(node, FragNode):
                for i, ex in enumerate(node.fragment.executors):
                    info["executors"].append({
                        "executor": f"[n{idx}<-{node.input}] {ex!r}",
                        **_state_gauges(ex, job.states[idx][i]),
                    })
            else:
                info["executors"].append({
                    "executor": f"[n{idx}<-{node.left},{node.right}] "
                                "HashJoinExecutor",
                    **_state_gauges(node.join, job.states[idx]),
                })
    elif isinstance(job, ShardedStreamingJob):
        info["n_shards"] = job.sharded.n_shards
        info["source_offset"] = getattr(job.source, "offset", None)
        info["executors"] = [
            {"executor": f"[sharded] {ex!r}",
             **_state_gauges(ex, job.states[i])}
            for i, ex in enumerate(job.sharded.executors)
        ]
    return info


def cluster_info(engine) -> dict:
    """risectl cluster-info analog."""
    import jax

    return {
        "devices": [str(d) for d in jax.devices()],
        "jobs": [describe_job(j) for j in engine.jobs],
        "catalog": [
            {"name": e.name, "kind": e.kind,
             "columns": [f"{f.name}:{f.data_type.name.lower()}"
                         for f in e.schema]}
            for e in engine.catalog.list()
        ],
        "system_params": engine.system_params.to_dict(),
        "storage": storage_info(engine) if engine.hummock is not None
        else None,
    }


# -- storage service (risectl hummock ... analog) -----------------------
def storage_info(engine) -> dict:
    """``storage version``: current version id/epoch, per-level file
    counts and bytes, pin count, stall state, compactor liveness."""
    if engine.hummock is None:
        return {"enabled": False}
    info = {"enabled": True, **engine.hummock.stats()}
    if engine.compactor is not None:
        info["compactor"] = {
            "running": engine.compactor.running,
            "tasks_run": engine.compactor.tasks_run,
            "errors": engine.compactor.errors,
        }
    return info


def storage_gc(engine) -> dict:
    """``storage gc``: run one vacuum pass (delete SST objects no
    pinned version references) and report the result."""
    return engine.storage_vacuum()


def _open_storage(data_dir: str):
    """Read-only-ish HummockStorage over an existing data_dir (for the
    offline CLI: inspect/GC without a running node)."""
    import os

    from risingwave_tpu.storage.hummock import (
        HummockStorage,
        LocalFsObjectStore,
    )

    return HummockStorage(
        LocalFsObjectStore(os.path.join(data_dir, "hummock"))
    )


def storage_scrub(data_dir: str) -> dict:
    """``ctl storage scrub <data_dir>`` — OFFLINE integrity scrub of a
    node's durable state: every SST in the version (footer crc, index,
    every block's crc32c trailer), the version log's hash chain, and
    every retained checkpoint epoch object vs its manifest-recorded
    crc.  Report-only (no node running, nothing to repair FROM): a
    corrupt object is listed, never silently read."""
    from risingwave_tpu.storage.hummock import LocalFsObjectStore
    from risingwave_tpu.storage.hummock.scrubber import ScrubberService
    from risingwave_tpu.storage.integrity import (
        ManifestCorruption,
        quarantine_list,
    )

    try:
        storage = _open_storage(data_dir)
    except ManifestCorruption as e:
        # the version log itself is damaged: report instead of crashing
        return {"ssts_verified": 0, "blocks_verified": 0,
                "checkpoints_verified": 0,
                "corrupt": [("manifest", e.key)], "ok": False}
    scrub = ScrubberService(
        storage,
        ckpt_object_store=LocalFsObjectStore(data_dir),
        pace_s=0.0,
    )
    report = scrub.run_once()
    report["quarantined"] = [
        n.get("key") for n in quarantine_list(storage.store)
    ]
    report["ok"] = not report["corrupt"]
    return report


def _storage_main(argv: list[str]) -> None:
    """``python -m risingwave_tpu.ctl storage
    {version|gc|scrub|compact|policy} <data_dir>`` — offline
    inspection/GC/integrity-scrub/compaction of a node's storage
    service state (risectl hummock list-version / trigger-full-gc
    analogs); ``policy`` prints the manifest-carried expiry policy
    docs the compaction filter enforces."""
    import json

    sub, data_dir = argv[0], argv[1]
    if sub == "scrub":
        report = storage_scrub(data_dir)
        print(json.dumps(report, indent=1))
        if not report["ok"]:
            raise SystemExit(1)
        return
    storage = _open_storage(data_dir)
    if sub == "version":
        print(json.dumps(storage.stats(), indent=1))
    elif sub == "gc":
        deleted = storage.vacuum()
        print(json.dumps({
            "deleted_objects": deleted,
            "remaining_objects": storage.stats()["objects"],
        }, indent=1))
    elif sub == "compact":
        n = 0
        while storage.compact_once():
            n += 1
        print(json.dumps({"tasks_run": n, **storage.stats()}, indent=1))
    elif sub == "policy":
        # the policy docs the manifest carries — exactly what an
        # offline ``storage compact`` run would enforce, so a live
        # compactor and this CLI can never disagree on a horizon
        print(json.dumps({
            "version_id": storage.stats()["version_id"],
            "policies": storage.versions.current.policy_docs(),
        }, indent=1))
    else:
        raise SystemExit(f"unknown storage subcommand: {sub}")


# -- cluster control plane (risectl cluster ... analog) -----------------
def _meta_state(meta_addr: str) -> dict:
    from risingwave_tpu.cluster.rpc import RpcClient, parse_addr

    host, port = parse_addr(meta_addr)
    client = RpcClient(host, port, timeout=30.0)
    try:
        return client.call("cluster_state")
    finally:
        client.close()


def cluster_workers(meta_addr: str) -> list[dict]:
    """``ctl cluster workers``: live/dead workers with heartbeat ages
    and their job assignments (risectl cluster-info's worker table)."""
    return _meta_state(meta_addr)["workers"]


def cluster_jobs(meta_addr: str) -> list[dict]:
    """``ctl cluster jobs``: placed streaming jobs — owner worker,
    sealed rounds, last committed and pinned epochs."""
    return _meta_state(meta_addr)["jobs"]


def cluster_serving(meta_addr: str) -> list[dict]:
    """``ctl cluster serving``: registered serving replicas — address,
    liveness, heartbeat age, the granted manifest vid, and the epoch
    pin lease (the vids vacuum keeps alive for each replica)."""
    return _meta_state(meta_addr).get("serving", [])


def cluster_faults(meta_addr: str) -> dict:
    """``ctl cluster faults``: the chaos observability surface — the
    meta's (and every live worker's/replica's) injected-fault
    counters, retry budget spend, and gave-up totals from the
    deterministic fault fabric (common/faults.py)."""
    from risingwave_tpu.cluster.rpc import RpcClient, parse_addr

    host, port = parse_addr(meta_addr)
    client = RpcClient(host, port, timeout=30.0)
    try:
        return client.call("cluster_faults")
    finally:
        client.close()


def cluster_pushdown(meta_addr: str) -> dict:
    """``ctl cluster pushdown <meta_addr>``: the pushdown-plane view —
    the manifest's per-table expiry policy docs (TTL horizons the
    compaction filter enforces), the meta-side compactor elision
    counters, and each live serving replica's negative-cache /
    warmup-replay numbers."""
    from risingwave_tpu.cluster.rpc import RpcClient, parse_addr

    host, port = parse_addr(meta_addr)
    client = RpcClient(host, port, timeout=120.0)
    try:
        return client.call("cluster_pushdown")
    finally:
        client.close()


def cluster_scale(meta_addr: str, n: int) -> dict:
    """``ctl cluster scale N <meta_addr>``: resize the active worker
    set online — the meta rebalances the vnode map minimally and
    hands the moved vnodes' state over through a checkpoint epoch
    (reads stay zero-error throughout)."""
    from risingwave_tpu.cluster.rpc import RpcClient, parse_addr

    host, port = parse_addr(meta_addr)
    client = RpcClient(host, port, timeout=600.0)
    try:
        return client.call("cluster_scale", n=int(n))
    finally:
        client.close()


def cluster_vnodes(meta_addr: str) -> dict:
    """``ctl cluster vnodes``: the scale plane's view — active worker
    set, per-worker vnode counts, and each partitioned job's
    partition layout."""
    s = _meta_state(meta_addr)
    return {
        "scale": s.get("scale"),
        "partitions": {
            j["name"]: j["partitions"]
            for j in s["jobs"] if j.get("partitions")
        },
    }


def cluster_exchange(meta_addr: str) -> dict:
    """``ctl cluster exchange``: the compiled Exchange-lite
    choreography — per-table shuffle mode, routing key column, ingest
    leader + standby, and the full edge-spec list (source / join /
    attach edges).  Compile once, execute forever: what this prints
    is exactly what every worker's per-chunk data path executes."""
    s = _meta_state(meta_addr)
    return s.get("exchange") or {}


def cluster_scrub(meta_addr: str) -> dict:
    """``ctl cluster scrub <meta_addr>``: drive ONE full ONLINE scrub
    cycle on the running meta — every pinned-version SST and retained
    checkpoint lineage verified, with quarantine + self-healing repair
    armed (corrupt MV exports re-export from live job state, corrupt
    checkpoint lineages rewind to the last verified epoch)."""
    from risingwave_tpu.cluster.rpc import RpcClient, parse_addr

    host, port = parse_addr(meta_addr)
    client = RpcClient(host, port, timeout=600.0)
    try:
        return client.call("cluster_scrub")
    finally:
        client.close()


def cluster_metrics(meta_addr: str) -> str:
    """``ctl cluster metrics <meta_addr>``: ONE aggregated Prometheus
    scrape for the whole cluster — the meta pulls every live worker's
    and serving replica's registry over RPC and merges them with
    ``role``/``worker``/``replica`` identity labels injected per
    sample (common/metrics.py merge_prometheus)."""
    from risingwave_tpu.cluster.rpc import RpcClient, parse_addr

    host, port = parse_addr(meta_addr)
    client = RpcClient(host, port, timeout=120.0)
    try:
        return client.call("cluster_metrics")["prometheus"]
    finally:
        client.close()


def cluster_trace(meta_addr: str, round: "int | None" = None,
                  chrome: str | None = None) -> dict:
    """``ctl cluster trace <meta_addr> [--round N] [--chrome out]``:
    assemble the merged cross-role span tree for one committed round
    (meta round span parenting worker barrier-phase spans, uploader
    prepare/commit spans, sampled serving reads).  ``--chrome`` also
    writes Chrome ``trace_event`` JSON loadable in chrome://tracing
    or Perfetto."""
    import json

    from risingwave_tpu.cluster.rpc import RpcClient, parse_addr
    from risingwave_tpu.common.trace import to_chrome_trace

    host, port = parse_addr(meta_addr)
    client = RpcClient(host, port, timeout=120.0)
    try:
        out = client.call("cluster_trace", round=round)
    finally:
        client.close()
    if chrome:
        with open(chrome, "w") as f:
            json.dump(to_chrome_trace(out["spans"]), f)
        out["chrome"] = chrome
    return out


def cluster_epochs(meta_addr: str) -> dict:
    """``ctl cluster epochs``: the global checkpoint positions — the
    committed cluster epoch (round), the manifest's epoch stamp, each
    job's serving pin, and the async-checkpoint split (sealed vs
    durable epoch + upload lag per job)."""
    s = _meta_state(meta_addr)
    return {
        "cluster_epoch": s["cluster_epoch"],
        "manifest_epoch": s["manifest_epoch"],
        "failovers": s["failovers"],
        "jobs": {
            j["name"]: {"pinned_epoch": j["pinned_epoch"],
                        "committed_epoch": j["committed_epoch"],
                        "sealed_epoch": j.get("sealed_epoch", 0),
                        "durable_epoch": j.get("durable_epoch", 0),
                        "upload_lag_epochs": max(
                            0, j.get("sealed_epoch", 0)
                            - j.get("durable_epoch", 0)),
                        "rounds": j["rounds"]}
            for j in s["jobs"]
        },
    }


def cluster_batch(meta_addr: str, sqls: list) -> dict:
    """``ctl cluster batch <meta_addr> <sql> [sql ...]``: N SELECTs
    through ONE serving-tier RPC frame (the batched multi-get
    protocol) — per-item owner fallback keeps the surface identical
    to single reads."""
    from risingwave_tpu.cluster.rpc import RpcClient, parse_addr

    host, port = parse_addr(meta_addr)
    client = RpcClient(host, port, timeout=120.0)
    try:
        return client.call("serve_batch", sqls=list(sqls))
    finally:
        client.close()


def cluster_multiget(meta_addr: str, mv: str, pks: list) -> dict:
    """``ctl cluster multiget <meta_addr> <mv> <pk> [pk ...]``:
    first-class multi-get — one MV + N pks in one frame, rows back in
    encoded-pk order (missing pks omitted).  Composite pks pass as
    comma-joined values (``3,foo``); bare integers coerce."""
    from risingwave_tpu.cluster.rpc import RpcClient, parse_addr

    def _coerce(s: str):
        try:
            return int(s)
        except ValueError:
            try:
                return float(s)
            except ValueError:
                return s

    keys = [[_coerce(part) for part in str(pk).split(",")]
            for pk in pks]
    host, port = parse_addr(meta_addr)
    client = RpcClient(host, port, timeout=120.0)
    try:
        return client.call("serve_multi_get", mv=mv, pks=keys)
    finally:
        client.close()


def _cluster_main(argv: list[str]) -> None:
    """``python -m risingwave_tpu.ctl cluster
    {workers|jobs|epochs|serving|faults} <meta_host:rpc_port>`` —
    online introspection of a running meta (mirrors the offline
    ``ctl storage`` pattern, but against the live control plane)."""
    import json

    sub = argv[0]
    if sub == "scale":
        # ctl cluster scale <N> <meta_addr>
        print(json.dumps(cluster_scale(argv[2], int(argv[1])),
                         indent=1))
        return
    if sub == "batch":
        # ctl cluster batch <meta_addr> <sql> [sql ...]
        print(json.dumps(cluster_batch(argv[1], argv[2:]), indent=1))
        return
    if sub == "multiget":
        # ctl cluster multiget <meta_addr> <mv> <pk> [pk ...]
        print(json.dumps(cluster_multiget(argv[1], argv[2], argv[3:]),
                         indent=1))
        return
    if sub == "metrics":
        # ctl cluster metrics <meta_addr> — raw exposition text
        print(cluster_metrics(argv[1]), end="")
        return
    if sub == "trace":
        # ctl cluster trace <meta_addr> [--round N] [--chrome out]
        addr, rnd, chrome = argv[1], None, None
        rest = argv[2:]
        while rest:
            flag = rest.pop(0)
            if flag == "--round":
                rnd = int(rest.pop(0))
            elif flag == "--chrome":
                chrome = rest.pop(0)
            else:
                raise SystemExit(f"unknown trace flag: {flag}")
        print(json.dumps(cluster_trace(addr, rnd, chrome), indent=1))
        return
    addr = argv[1]
    fn = {"workers": cluster_workers, "jobs": cluster_jobs,
          "epochs": cluster_epochs,
          "serving": cluster_serving,
          "vnodes": cluster_vnodes,
          "exchange": cluster_exchange,
          "scrub": cluster_scrub,
          "pushdown": cluster_pushdown,
          "faults": cluster_faults}.get(sub)
    if fn is None:
        raise SystemExit(f"unknown cluster subcommand: {sub}")
    print(json.dumps(fn(addr), indent=1))


def main() -> None:  # pragma: no cover - thin CLI
    """``python -m risingwave_tpu.ctl <host> <port> <sql>`` — send one
    statement to a running node over pgwire (risectl's transport is
    gRPC; ours is the SQL front door).  ``... ctl storage
    {version|gc|compact} <data_dir>`` operates on storage offline;
    ``... ctl cluster {workers|jobs|epochs} <meta_addr>`` talks to a
    running meta service."""
    import sys

    if len(sys.argv) > 1 and sys.argv[1] == "storage":
        _storage_main(sys.argv[2:])
        return
    if len(sys.argv) > 1 and sys.argv[1] == "cluster":
        _cluster_main(sys.argv[2:])
        return

    from risingwave_tpu.pgwire import SimpleClient

    host, port, sql = sys.argv[1], int(sys.argv[2]), sys.argv[3]
    c = SimpleClient(host, port)
    cols, rows = c.query(sql)
    if cols:
        print("\t".join(cols))
    for r in rows:
        print("\t".join("" if v is None else str(v) for v in r))
    c.close()


if __name__ == "__main__":
    main()
