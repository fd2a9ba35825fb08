"""Benchmark driver: Nexmark streaming throughput via SQL, one process.

Runs the BASELINE.md configurations end-to-end through the SQL engine
(source generation on device → jitted fragment steps → device MV), at
the reference's default freshness envelope (checkpoint every barrier).

- q1: stateless project over the bid stream
- q5: sliding-window (hop) bid counts per auction  (windowed hash agg)
- q7: tumbling-window max price                    (windowed hash agg)
- q8: windowed person × auction join

Measures the asked query (``RWT_BENCH_QUERY=q1|q5|q7|q8|all``, default
q7) in THIS process — a chip belongs to one process — and prints one
json line per query with the device it ran on.  A run that finds no
TPU fails; ``JAX_PLATFORMS=cpu`` given explicitly measures the CPU, and
the line then says so and claims nothing per chip.
"""

from __future__ import annotations

import json
import os
import sys
import time

import risingwave_tpu  # noqa: F401  (x64 + compile cache before backend init)

from risingwave_tpu.sql import Engine
from risingwave_tpu.sql.planner import PlannerConfig

CHUNK_CAP = 8192
# warmup must cover one snapshot barrier (interval 8) so the snapshot
# copy's compile stays out of the measured window; the consistency
# audit compiles after the window (see measure())
WARMUP_BARRIERS = 9
BARRIERS = 32
CHUNKS_PER_BARRIER = 8

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

#: per-query event-rate overrides (none: the degree-adaptive pool join
#: runs q8 at the same full rate as every other query)
RATES: dict = {}

QUERIES = {
    "q1": """
        CREATE MATERIALIZED VIEW bench_mv AS
        SELECT auction, bidder, 0.908 * price AS price, date_time
        FROM bid;
    """,
    "q5": """
        CREATE MATERIALIZED VIEW bench_mv AS
        SELECT auction, window_start, count(*) AS bids
        FROM HOP(bid, date_time, INTERVAL '2' SECOND, INTERVAL '10' SECOND)
        GROUP BY auction, window_start;
    """,
    "q7": """
        CREATE MATERIALIZED VIEW bench_mv AS
        SELECT window_start, max(price) AS max_price, count(*) AS bids
        FROM TUMBLE(bid, date_time, INTERVAL '10' SECOND)
        GROUP BY window_start;
    """,
    "q8": """
        CREATE MATERIALIZED VIEW bench_mv AS
        SELECT p.id AS id, p.name AS name, a.reserve AS reserve
        FROM TUMBLE(person, date_time, INTERVAL '1' SECOND) p
        JOIN TUMBLE(auction, date_time, INTERVAL '1' SECOND) a
        ON p.id = a.seller AND p.window_start = a.window_start;
    """,
}


def measure(query: str) -> float:
    eng = Engine(PlannerConfig(
        chunk_capacity=CHUNK_CAP,
        agg_table_size=1 << 18,
        agg_emit_capacity=4096,
        # q8 state is rate x live-window-span rows per side (~2.7M in
        # the measured window before the watermark closes anything):
        # the shared pool holds them with NO per-key cap — hot sellers
        # need no hand-tuned bucket depths and no rate limiting
        join_left_table_size=1 << 22,
        join_right_table_size=1 << 18,
        join_pool_size=1 << 22,
        # out_capacity sizes every emission window chunk; oversizing
        # it taxes every chunk with dead rows (measured 3.6x on q8)
        join_out_capacity=1 << 12,
        mv_table_size=1 << 18,
        # q1/q8 materialize every output row; the ring must hold the
        # whole warmup+measured window (the lap counter voids lossy runs)
        mv_ring_size=1 << 23 if query in ("q1", "q8") else 1 << 21,
        topn_pool_size=1 << 14,
    ))
    eng.execute(SOURCES.format(rate=RATES.get(query, "1000000")))
    eng.execute(QUERIES[query])
    # snapshots (the durability/freshness envelope) stay at every 8
    # checkpoints — they are pure device-side copies.  The consistency
    # AUDIT does a device→host counter read, a sync in an otherwise
    # asynchronous window, so it runs once AFTER the measured window
    # instead of on a cadence.
    eng.execute(
        "ALTER SYSTEM SET maintenance_interval_checkpoints = 1000000"
    )
    eng.execute("ALTER SYSTEM SET snapshot_interval_checkpoints = 8")
    eng.tick(barriers=WARMUP_BARRIERS,
             chunks_per_barrier=CHUNKS_PER_BARRIER)  # compile + warm state
    import jax
    jax.block_until_ready(eng.jobs[0].states)
    warm_rows = eng.metrics.get("stream_rows_total", job="bench_mv")

    t0 = time.perf_counter()
    eng.tick(barriers=BARRIERS, chunks_per_barrier=CHUNKS_PER_BARRIER)
    jax.block_until_ready(eng.jobs[0].states)
    dt = time.perf_counter() - t0
    rows = eng.metrics.get("stream_rows_total", job="bench_mv") - warm_rows
    # post-window consistency audit: overflow/inconsistency in the
    # measured stream would raise here and void the result
    eng.execute("ALTER SYSTEM SET maintenance_interval_checkpoints = 1")
    eng.tick(barriers=1, chunks_per_barrier=0)
    return rows / dt


def main() -> None:
    import jax

    query = os.environ.get("RWT_BENCH_QUERY", "q7")
    dev = jax.devices()[0]
    on_cpu = dev.platform == "cpu"
    if dev.platform != "tpu" and not (
            on_cpu and os.environ.get("JAX_PLATFORMS") == "cpu"):
        sys.exit(f"bench.py: no TPU (found {dev.platform}); set "
                 "JAX_PLATFORMS=cpu to measure the CPU on purpose")
    for q in list(QUERIES) if query == "all" else [query]:
        print(json.dumps({
            "metric": f"nexmark_{q}_throughput",
            "value": round(measure(q), 1),
            "unit": "rows/s (cpu run)" if on_cpu else "rows/s/chip",
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())},
        }), flush=True)


if __name__ == "__main__":
    main()
