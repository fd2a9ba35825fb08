"""Profile + regression-gate the incremental snapshot / async upload path.

The round-6 attribution named the every-8-checkpoints full-tree
``_snapshot_copy`` (~1.3 GB device copy, 6-8 s stalls ≈ half the q8
window) as the single biggest remaining lever.  Round 7 replaced it
with the ShadowSnapshot (digest-diff + dirty-block scatter, one async
dispatch) and moved durable persistence to a background uploader.
This script times the pieces and, with ``--assert``, turns the
structural guarantees into hard failures:

  - snapshot COPY traffic scales with dirty blocks, not state size
    (the copy component of a 0.5%-dirty update is a small fraction of
    the all-dirty update's);
  - a dirty-block update is not slower than the bare full copy it
    replaced (it also buys the digest the durable store reuses);
  - the steady barrier path — chunks, barriers, AND shadow-snapshot
    barriers — performs ZERO synchronous device→host transfers
    (enforced with jax's transfer guard, which raises on any d2h);
  - the upload queue is bounded under sustained load: the barrier loop
    write-stalls rather than queueing unacked epochs past the window;
  - recovery equivalence: restore from the shadow and from the
    async-uploaded durable chain are byte-identical to the live state
    at the sealed epoch.

Usage:
  JAX_PLATFORMS=cpu python scripts/profile_snapshot.py            # timings
  JAX_PLATFORMS=cpu python scripts/profile_snapshot.py --assert   # gate
  ... --assert --small    # reduced sizes (the CI/pytest wrapper)
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import risingwave_tpu  # noqa: F401,E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from risingwave_tpu.sql import Engine  # noqa: E402
from risingwave_tpu.sql.planner import PlannerConfig  # noqa: E402
from risingwave_tpu.stream.shadow import ShadowSnapshot  # noqa: E402


#: the baseline: a full device→device copy of the tree, one dispatch
@jax.jit
def _snapshot_copy(tree):
    return jax.tree.map(jnp.copy, tree)


def _median_time(fn, n=3) -> float:
    out = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        out.append(time.perf_counter() - t0)
    return sorted(out)[n // 2]


def make_tree(small: bool):
    # big enough that copy/digest dwarf per-dispatch noise on 1 core
    n = 1 << (23 if small else 24)
    leaves = tuple(
        jnp.arange(n, dtype=jnp.int64) * (i + 1) for i in range(4)
    )
    jax.block_until_ready(leaves)
    return leaves, n


def dirty_fraction(tree, n, frac):
    """Contiguous dirty prefix (the bump-allocator / ring-cursor write
    pattern the streaming state actually produces)."""
    k = max(1, int(n * frac))
    out = tuple(x.at[:k].add(1) for x in tree)
    jax.block_until_ready(out)
    return out


def q8_engine(small: bool) -> Engine:
    cap = 1024 if small else 8192
    eng = Engine(PlannerConfig(
        chunk_capacity=cap,
        agg_table_size=1 << 12, agg_emit_capacity=1024,
        join_left_table_size=1 << 14, join_right_table_size=1 << 14,
        join_pool_size=1 << 18, join_out_capacity=1 << 10,
        mv_table_size=1 << 12, mv_ring_size=1 << 16,
    ))
    eng.execute("""
    CREATE SOURCE person (
        id BIGINT, name VARCHAR, date_time TIMESTAMP,
        WATERMARK FOR date_time AS date_time - INTERVAL '4' SECOND
    ) WITH (connector = 'nexmark', nexmark.table = 'person',
            nexmark.event.rate = '1000000');
    CREATE SOURCE auction (
        id BIGINT, seller BIGINT, reserve BIGINT, expires TIMESTAMP,
        date_time TIMESTAMP,
        WATERMARK FOR date_time AS date_time - INTERVAL '4' SECOND
    ) WITH (connector = 'nexmark', nexmark.table = 'auction',
            nexmark.event.rate = '1000000');
    CREATE MATERIALIZED VIEW bench_mv AS
    SELECT p.id AS id, p.name AS name, a.reserve AS reserve
    FROM TUMBLE(person, date_time, INTERVAL '1' SECOND) p
    JOIN TUMBLE(auction, date_time, INTERVAL '1' SECOND) a
    ON p.id = a.seller AND p.window_start = a.window_start;
    """)
    return eng


def _states_host(job):
    return jax.device_get(job.states)


def _tree_equal(a, b) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    if len(la) != len(lb):
        return False
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        if x.shape != y.shape or x.dtype != y.dtype:
            return False
        if x.dtype.kind == "f":
            if not np.array_equal(x, y, equal_nan=True):
                return False
        elif not np.array_equal(x, y):
            return False
    return True


# ----------------------------------------------------------------------
def check_dirty_scaling(small: bool, failures: list[str]) -> dict:
    tree, n = make_tree(small)
    t_copy = _median_time(lambda: _snapshot_copy(tree))

    sh = ShadowSnapshot(tree)
    jax.block_until_ready(sh.leaves)
    cur = tree

    def upd(frac):
        nonlocal cur
        cur = dirty_fraction(cur, n, frac)
        t0 = time.perf_counter()
        sh.update(cur)
        jax.block_until_ready(sh.leaves)
        return time.perf_counter() - t0

    upd(0.001)  # compile every rung once
    upd(0.05)
    upd(1.0)
    t_clean = _median_time(lambda: (sh.update(cur), sh.leaves)[1])
    t_small = sorted(upd(0.005) for _ in range(3))[1]
    t_full = sorted(upd(1.0) for _ in range(3))[1]

    if not _tree_equal(sh.restore(), cur):
        failures.append("dirty-scaling: shadow restore != live tree")
    copy_small = max(t_small - t_clean, 0.0)
    copy_full = max(t_full - t_clean, 1e-9)
    # guard bands absorb 1-core scheduling noise on sub-second runs
    if copy_small > max(0.35 * copy_full, 0.025):
        failures.append(
            f"dirty-scaling: 0.5%-dirty copy component {copy_small:.3f}s"
            f" is not a small fraction of all-dirty {copy_full:.3f}s — "
            "snapshot copy traffic no longer scales with dirty blocks"
        )
    if t_small > 1.6 * t_copy + 0.05:
        failures.append(
            f"dirty-scaling: 0.5%-dirty update {t_small:.3f}s vs bare "
            f"full copy {t_copy:.3f}s — the incremental snapshot lost "
            "to the copy it replaced"
        )
    return {"full_copy": t_copy, "update_clean": t_clean,
            "update_0.5%": t_small, "update_all_dirty": t_full}


def check_no_sync_readback(small: bool, failures: list[str]) -> None:
    eng = q8_engine(small)
    eng.execute(
        "ALTER SYSTEM SET maintenance_interval_checkpoints = 1000000"
    )
    eng.execute("ALTER SYSTEM SET snapshot_interval_checkpoints = 4")
    # warm: compiles + the first shadow snapshot (build + re-base)
    eng.tick(barriers=9, chunks_per_barrier=2)
    try:
        with jax.transfer_guard_device_to_host("disallow"):
            # covers plain barriers AND one snapshot barrier
            eng.tick(barriers=4, chunks_per_barrier=2)
    except Exception as e:  # noqa: BLE001
        failures.append(
            "sync-readback: steady barrier path performed a "
            f"synchronous device→host transfer: {e!r:.300}"
        )


def check_bounded_queue(small: bool, tmp: str, failures: list[str],
                        ) -> dict:
    eng = q8_engine(True)  # small state: upload latency dominates
    import shutil
    os.makedirs(tmp, exist_ok=True)
    from risingwave_tpu.storage.checkpoint_store import CheckpointStore
    store = CheckpointStore(os.path.join(tmp, "ckpt"))
    real_put = store.store.put

    def slow_put(key, data):
        time.sleep(0.05)
        real_put(key, data)

    store.store.put = slow_put
    job = eng.jobs[0]
    job.checkpoint_store = store
    job.checkpoint_frequency = 1
    job.snapshot_interval = 1
    job.maintenance_interval = 1 << 30
    job.upload_window = 2
    max_depth = 0
    for _ in range(12):
        job.run_chunks(1)
        job.inject_barrier()
        max_depth = max(max_depth, job.upload_queue_depth())
    window_bound = job.upload_window + 1  # +1: the epoch just sealed
    if max_depth > window_bound:
        failures.append(
            f"bounded-queue: upload queue reached {max_depth} epochs "
            f"(window {job.upload_window}) — the write stall is not "
            "bounding in-flight checkpoints"
        )
    job.drain_uploads()
    if job.committed_epoch != job.sealed_epoch:
        failures.append(
            "bounded-queue: drain left committed "
            f"{job.committed_epoch} != sealed {job.sealed_epoch}"
        )
    if store.committed_epoch(job.name) != job.sealed_epoch:
        failures.append(
            "bounded-queue: durable manifest epoch "
            f"{store.committed_epoch(job.name)} != sealed "
            f"{job.sealed_epoch}"
        )
    shutil.rmtree(tmp, ignore_errors=True)
    return {"max_queue_depth": max_depth,
            "stall_seconds": round(job.stall_seconds, 3)}


def check_recovery_equivalence(small: bool, tmp: str,
                               failures: list[str]) -> None:
    import shutil

    # in-memory: shadow restore must be byte-identical to live state
    eng = q8_engine(True)
    eng.execute("ALTER SYSTEM SET snapshot_interval_checkpoints = 2")
    eng.tick(barriers=4, chunks_per_barrier=2)
    job = eng.jobs[0]
    live = _states_host(job)
    job.recover()
    if not _tree_equal(job.states, live):
        failures.append(
            "recovery: in-memory shadow restore != live state at the "
            "sealed epoch"
        )

    # durable: the async-uploaded chain must reconstruct byte-identical
    os.makedirs(tmp, exist_ok=True)
    eng2 = Engine(PlannerConfig(
        chunk_capacity=256, agg_table_size=1 << 10,
        agg_emit_capacity=256, mv_table_size=1 << 10,
        mv_ring_size=1 << 12,
    ), data_dir=os.path.join(tmp, "node"))
    eng2.execute("""
        CREATE SOURCE bid (
            auction BIGINT, bidder BIGINT, price BIGINT,
            channel VARCHAR, url VARCHAR, date_time TIMESTAMP
        ) WITH (connector = 'nexmark', nexmark.table = 'bid');
        CREATE MATERIALIZED VIEW q7 AS
        SELECT window_start, max(price) AS max_price, count(*) AS bids
        FROM TUMBLE(bid, date_time, INTERVAL '1' SECOND)
        GROUP BY window_start;
    """)
    eng2.tick(barriers=5, chunks_per_barrier=1)
    job2 = eng2.jobs[0]
    live2 = _states_host(job2)
    sealed = job2.sealed_epoch
    loaded = eng2.checkpoint_store.load(job2.name)
    if loaded is None or loaded[0] != sealed:
        failures.append(
            f"recovery: durable chain missing sealed epoch {sealed}"
        )
    elif not _tree_equal(loaded[1], live2):
        failures.append(
            "recovery: async-uploaded durable checkpoint != live state"
        )
    job2.recover()
    if not _tree_equal(job2.states, live2):
        failures.append(
            "recovery: recover() from durable chain != live state"
        )
    shutil.rmtree(tmp, ignore_errors=True)


def run_assert(small: bool) -> int:
    failures: list[str] = []
    scaling = check_dirty_scaling(small, failures)
    check_no_sync_readback(small, failures)
    queue = check_bounded_queue(
        small, "/tmp/_profile_snapshot_q", failures
    )
    check_recovery_equivalence(
        small, "/tmp/_profile_snapshot_r", failures
    )
    if failures:
        print("profile_snapshot --assert: FAIL", flush=True)
        for f in failures:
            print(f"  - {f}", flush=True)
        return 1
    print(
        "profile_snapshot --assert: OK — "
        f"copy {scaling['full_copy'] * 1e3:.0f}ms, "
        f"0.5%-dirty update {scaling['update_0.5%'] * 1e3:.0f}ms "
        f"(clean {scaling['update_clean'] * 1e3:.0f}ms, all-dirty "
        f"{scaling['update_all_dirty'] * 1e3:.0f}ms); zero sync d2h "
        f"on the steady path; max upload queue "
        f"{queue['max_queue_depth']} (stalled "
        f"{queue['stall_seconds']}s); recovery byte-identical",
        flush=True,
    )
    return 0


def main():
    small = "--small" in sys.argv
    if "--assert" in sys.argv:
        sys.exit(run_assert(small))
    failures: list[str] = []
    scaling = check_dirty_scaling(small, failures)
    for k, v in scaling.items():
        print(f"{k:20s} {v * 1e3:9.2f} ms")
    for f in failures:
        print(f"note: {f}")


if __name__ == "__main__":
    main()
