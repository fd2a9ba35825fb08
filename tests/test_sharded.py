"""Multi-shard (8 virtual devices) dataflow tests.

The reference tests multi-node behaviour in one process with madsim
(SURVEY.md §4.4); here the analog is a virtual 8-device CPU mesh with
the full shard_map + all_to_all path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from risingwave_tpu.common.chunk import Chunk
from risingwave_tpu.common.types import DataType, Schema
from risingwave_tpu.expr.agg import AggCall, count_star
from risingwave_tpu.expr.node import col
from risingwave_tpu.stream.hash_agg import HashAggExecutor
from risingwave_tpu.stream.sharded import ShardedJob, make_mesh


pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices"
)

SCHEMA = Schema.of(("g", DataType.INT64), ("v", DataType.INT64))


def _source(k0, cap):
    """Synthetic keyed stream: g cycles 0..15, v = ordinal."""
    k = k0 + jnp.arange(cap, dtype=jnp.int64)
    g = k % 16
    return Chunk(
        (g, k),
        jnp.zeros((cap,), jnp.int8),
        jnp.ones((cap,), jnp.bool_),
        SCHEMA,
    )


def test_sharded_count_sum_matches_single_shard():
    mesh = make_mesh(8)
    agg = HashAggExecutor(
        SCHEMA,
        group_by=[("g", col("g"))],
        aggs=[count_star("n"), AggCall("sum", col("v"), "s")],
        table_size=256,
        emit_capacity=64,
    )
    job = ShardedJob(
        mesh,
        source_fn=_source,
        chunk_capacity=32,
        local_executors=[],
        exchange_key_fn=lambda c: [c.column(0)],
        keyed_executors=[agg],
    )
    states = job.init_states()
    states, outs = job.run_epochs(states, barriers=2, chunks_per_barrier=2)

    # ground truth: 8 shards * 2 barriers * 2 chunks * 32 rows
    total = 8 * 2 * 2 * 32
    ks = np.arange(total, dtype=np.int64)
    want_n = {int(g): int((ks % 16 == g).sum()) for g in range(16)}
    want_s = {int(g): int(ks[ks % 16 == g].sum()) for g in range(16)}

    # fold the emitted changelog into a dict (ops applied in order)
    got = {}
    for flush_outs in outs:
        for out in flush_outs:  # each is a [8, cap]-stacked chunk pytree
            leaves = jax.tree.map(np.asarray, out)
            for shard in range(8):
                shard_chunk = jax.tree.map(lambda x: x[shard], leaves)
                ops, cols, _ = shard_chunk.to_host()
                for i in range(len(ops)):
                    g, n, s = int(cols[0][i]), int(cols[1][i]), int(cols[2][i])
                    if ops[i] in (0, 3):
                        got[g] = (n, s)
                    elif ops[i] == 1:
                        got.pop(g, None)
    assert {g: v[0] for g, v in got.items()} == want_n
    assert {g: v[1] for g, v in got.items()} == want_s


def test_each_group_lives_on_exactly_one_shard():
    mesh = make_mesh(8)
    agg = HashAggExecutor(
        SCHEMA, [("g", col("g"))], [count_star("n")],
        table_size=256, emit_capacity=64,
    )
    job = ShardedJob(
        mesh, _source, 32, [], lambda c: [c.column(0)], [agg],
    )
    states = job.init_states()
    states, _ = job.run_epochs(states, barriers=1, chunks_per_barrier=4)
    # inspect per-shard group tables: each group key on exactly one shard
    occupied = np.asarray(jax.device_get(states[0].table.occupied))
    keys = np.asarray(jax.device_get(states[0].table.key_cols[0]))
    owner: dict[int, int] = {}
    for shard in range(8):
        for slot in np.nonzero(occupied[shard])[0]:
            g = int(keys[shard, slot])
            assert g not in owner, f"group {g} on shards {owner[g]} and {shard}"
            owner[g] = shard
    assert len(owner) == 16


def test_shuffle_carries_string_columns():
    """Regression: StrCol columns survive the all_to_all exchange."""
    from jax.sharding import PartitionSpec as P
    from risingwave_tpu.parallel.exchange import shuffle_chunk

    from risingwave_tpu.parallel.exchange import shard_map_nocheck

    schema = Schema.of(("g", DataType.INT64), ("s", DataType.VARCHAR))
    mesh = make_mesh(8)
    cap = 16

    def make_local(shard_g):
        import risingwave_tpu.common.chunk as ck
        data, lens = ck.encode_strings(
            [f"str{i % 4}" for i in range(cap)], 64
        )
        return Chunk(
            (jnp.arange(cap, dtype=jnp.int64) % 4,
             ck.StrCol(jnp.asarray(data), jnp.asarray(lens))),
            jnp.zeros((cap,), jnp.int8),
            jnp.ones((cap,), jnp.bool_),
            schema,
        )

    def body(_):
        chunk = make_local(0)
        out = shuffle_chunk(chunk, [chunk.column(0)], "shard", 8)
        return jax.tree.map(lambda x: x[None], out)

    f = jax.jit(shard_map_nocheck(
        body, mesh=mesh, in_specs=(P("shard"),), out_specs=P("shard"),
    ))
    out = f(jnp.zeros((8,), jnp.int32))
    leaves = jax.tree.map(np.asarray, out)
    total = 0
    for shard in range(8):
        c = jax.tree.map(lambda x: x[shard], leaves)
        ops, cols, _ = c.to_host()
        for i in range(len(ops)):
            g, s = int(cols[0][i]), cols[1][i]
            assert s == f"str{g}"  # string stayed with its key
            total += 1
    assert total == 8 * cap  # nothing lost in the exchange


def test_sql_sharded_mv_matches_single_shard():
    """streaming_parallelism plans the same MV over the 8-device mesh."""
    from risingwave_tpu.sql import Engine
    from risingwave_tpu.sql.planner import PlannerConfig

    def build(par):
        eng = Engine(PlannerConfig(
            chunk_capacity=128, agg_table_size=512, agg_emit_capacity=128,
            mv_table_size=512, mv_ring_size=1024,
        ))
        eng.execute(
            "CREATE SOURCE bid (auction BIGINT, price BIGINT, "
            "date_time TIMESTAMP) WITH (connector='nexmark', "
            "nexmark.table='bid')"
        )
        if par:
            eng.execute(f"SET streaming_parallelism = {par}")
        eng.execute(
            "CREATE MATERIALIZED VIEW v AS SELECT auction, count(*) AS n, "
            "max(price) AS hi FROM bid GROUP BY auction"
        )
        return eng

    a = build(0)       # linear
    b = build(8)       # sharded over the virtual mesh
    from risingwave_tpu.stream.sharded import ShardedStreamingJob
    assert isinstance(b.jobs[0], ShardedStreamingJob)

    a.tick(barriers=2, chunks_per_barrier=2)
    # the sharded job consumes n_shards*cap rows per chunk call; align
    # total rows: linear 4*128 = 512 rows = sharded 4 chunk-units / 8
    b.jobs[0].run_chunk()  # 8*128 = 1024 rows in ONE sharded step...
    b.jobs[0].inject_barrier()

    rows_a = a.execute("SELECT auction, n, hi FROM v")
    # compare against ground truth for the rows each actually consumed
    import numpy as np
    from risingwave_tpu.connector.nexmark import NexmarkGenerator
    def want(total):
        g = NexmarkGenerator()
        _, cols, _ = g.gen_bids(0, total).to_host()
        out = {}
        for auc, pr in zip(cols[0], cols[2]):
            n, hi = out.get(int(auc), (0, 0))
            out[int(auc)] = (n + 1, max(hi, int(pr)))
        return out
    got_a = {int(r[0]): (int(r[1]), int(r[2])) for r in rows_a}
    assert got_a == want(512)
    rows_b = b.execute("SELECT auction, n, hi FROM v")
    got_b = {int(r[0]): (int(r[1]), int(r[2])) for r in rows_b}
    assert got_b == want(1024)
    assert b.jobs[0].committed_epoch > 0


def test_two_phase_partial_agg_unit():
    """PartialAgg collapses duplicate keys; global combine is exact."""
    import jax.numpy as jnp
    from collections import Counter
    from risingwave_tpu.common.chunk import Chunk
    from risingwave_tpu.expr.agg import AggCall, count_star
    from risingwave_tpu.expr.node import InputRef, col
    from risingwave_tpu.stream.fragment import Fragment
    from risingwave_tpu.stream.hash_agg import HashAggExecutor
    from risingwave_tpu.stream.partial_agg import (
        PartialAggExecutor,
        translated_global_calls,
    )

    schema = Schema.of(("g", DataType.INT64), ("v", DataType.INT64))
    group_by = [("g", col("g"))]
    aggs = [count_star("n"), AggCall("sum", col("v"), "s"),
            AggCall("max", col("v"), "hi")]
    partial = PartialAggExecutor(schema, group_by, aggs)
    st, out = Fragment([partial]).step(
        Fragment([partial]).init_states(),
        Chunk.from_pretty("""
            I I
            + 1 10
            + 1 5
            + 2 7
            + 1 1
            + 2 3
        """, names=["g", "v"]),
    )
    rows = sorted(out.to_rows())
    # 5 input rows collapse to 2 partial rows
    assert rows == [(0, 1, 3, 16, 10), (0, 2, 2, 10, 7)]

    glob = HashAggExecutor(
        partial.out_schema,
        [("g", InputRef(0))],
        translated_global_calls(aggs, 1),
        table_size=64, emit_capacity=16,
    )
    frag = Fragment([glob])
    gst = frag.init_states()
    gst, _ = frag.step(gst, out)
    gst, outs = frag.flush(gst, 1)
    mv = Counter()
    for op, *vals in outs[0].to_rows():
        mv[tuple(vals)] += 1 if op in (0, 3) else -1
    assert +mv == Counter({(1, 3, 16, 10): 1, (2, 2, 10, 7): 1})


NEXMARK_WM_SOURCES = """
CREATE SOURCE person (
    id BIGINT, name VARCHAR, date_time TIMESTAMP,
    WATERMARK FOR date_time AS date_time - INTERVAL '4' SECOND
) WITH (connector = 'nexmark', nexmark.table = 'person',
        nexmark.event.rate = '2000');
CREATE SOURCE auction (
    id BIGINT, seller BIGINT, reserve BIGINT, expires TIMESTAMP,
    date_time TIMESTAMP,
    WATERMARK FOR date_time AS date_time - INTERVAL '4' SECOND
) WITH (connector = 'nexmark', nexmark.table = 'auction',
        nexmark.event.rate = '2000');
"""

Q8_MV = """
CREATE MATERIALIZED VIEW v AS
SELECT p.id AS id, p.name AS name, a.reserve AS reserve
FROM TUMBLE(person, date_time, INTERVAL '1' SECOND) p
JOIN TUMBLE(auction, date_time, INTERVAL '1' SECOND) a
ON p.id = a.seller AND p.window_start = a.window_start;
"""


def _windowed_engine(par, rate="1000"):
    from risingwave_tpu.sql import Engine
    from risingwave_tpu.sql.planner import PlannerConfig

    eng = Engine(PlannerConfig(
        chunk_capacity=128, agg_table_size=512, agg_emit_capacity=128,
        mv_table_size=512, mv_ring_size=2048,
    ))
    eng.execute(
        "CREATE SOURCE bid (auction BIGINT, price BIGINT, "
        "date_time TIMESTAMP, "
        "WATERMARK FOR date_time AS date_time - INTERVAL '4' SECOND) "
        "WITH (connector='nexmark', nexmark.table='bid', "
        f"nexmark.event.rate='{rate}')"
    )
    if par:
        eng.execute(f"SET streaming_parallelism = {par}")
    eng.execute(
        "CREATE MATERIALIZED VIEW v AS SELECT window_start, "
        "max(price) AS hi, count(*) AS n "
        "FROM TUMBLE(bid, date_time, INTERVAL '2' SECOND) "
        "GROUP BY window_start"
    )
    return eng


def test_sharded_windowed_agg_matches_linear():
    """q7-shaped: TUMBLE + GROUP BY window_start runs vnode-sharded
    with watermark cleaning (round-2 verdict item 3a/3c)."""
    from risingwave_tpu.stream.sharded import ShardedStreamingJob

    b = _windowed_engine(8)
    assert isinstance(b.jobs[0], ShardedStreamingJob)
    for _ in range(6):
        b.jobs[0].run_chunk()
        b.jobs[0].inject_barrier()
    a = _windowed_engine(0)
    for _ in range(6 * 8):
        a.jobs[0].run_chunk()
        a.jobs[0].inject_barrier()
    rows_a = a.execute("SELECT window_start, hi, n FROM v ORDER BY window_start")
    rows_b = b.execute("SELECT window_start, hi, n FROM v ORDER BY window_start")
    assert rows_a == rows_b and len(rows_a) > 2


def test_sharded_windowed_agg_state_stays_bounded():
    """50+ barriers: the sharded agg's occupied groups must not grow
    (watermark cleaning evicts closed windows — sharded.py round-2 gap)."""
    eng = _windowed_engine(8, rate="4000")
    job = eng.jobs[0]
    occupied_counts = []
    for i in range(55):
        job.run_chunk()
        job.inject_barrier()
        if i % 10 == 9:
            for s in job.states:
                if hasattr(s, "table"):
                    occupied_counts.append(
                        int(np.asarray(jax.device_get(
                            s.table.occupied)).sum())
                    )
                    break
    # live windows = window_size + wm lag worth, NOT all history
    assert occupied_counts[-1] <= occupied_counts[0] + 4, occupied_counts
    assert max(occupied_counts) < 64, occupied_counts


def test_sharded_join_q8_matches_linear():
    """q8-shaped sharded DAG: join inputs exchange by equi keys inside
    shard_map; results must equal the linear run (verdict item 3d)."""
    from risingwave_tpu.sql import Engine
    from risingwave_tpu.sql.planner import PlannerConfig
    from risingwave_tpu.stream.dag import DagJob

    def build(par):
        eng = Engine(PlannerConfig(
            chunk_capacity=128,
            join_left_table_size=1 << 12, join_left_bucket_cap=4,
            join_right_table_size=1 << 10, join_right_bucket_cap=512,
            join_out_capacity=1 << 12,
            mv_table_size=4096, mv_ring_size=1 << 15,
        ))
        eng.execute(NEXMARK_WM_SOURCES)
        if par:
            eng.execute(f"SET streaming_parallelism = {par}")
        eng.execute(Q8_MV)
        return eng

    b = build(8)
    assert isinstance(b.jobs[0], DagJob) and b.jobs[0].mesh is not None
    for _ in range(6):
        b.jobs[0].chunk_round()
        b.jobs[0].inject_barrier()
    a = build(0)
    for _ in range(6 * 8):
        a.jobs[0].chunk_round()
        a.jobs[0].inject_barrier()
    rows_a = sorted(a.execute("SELECT id, name, reserve FROM v"))
    rows_b = sorted(b.execute("SELECT id, name, reserve FROM v"))
    assert rows_a == rows_b and len(rows_a) > 1000


def test_mv_on_mv_over_sharded_join_matches_linear():
    """ROADMAP carry from round 6 (ISSUE 5 satellite): MV-on-MV over a
    sharded join job no longer raises in ``_ensure_dag`` — a
    per-key-safe chain (project/filter/materialize) attaches PER-SHARD
    inside the upstream's shard_map, backfills the existing rows, and
    matches the linear run; shapes that would merge rows across shards
    still raise the explicit 'next round' error."""
    import pytest

    from risingwave_tpu.sql import Engine
    from risingwave_tpu.sql.planner import PlannerConfig
    from risingwave_tpu.stream.dag import DagJob

    def build(par):
        eng = Engine(PlannerConfig(
            chunk_capacity=128,
            join_left_table_size=1 << 12, join_left_bucket_cap=4,
            join_right_table_size=1 << 10, join_right_bucket_cap=512,
            join_out_capacity=1 << 12,
            mv_table_size=4096, mv_ring_size=1 << 15,
        ))
        eng.execute(NEXMARK_WM_SOURCES)
        if par:
            eng.execute(f"SET streaming_parallelism = {par}")
        eng.execute(Q8_MV)
        return eng

    b = build(8)
    assert isinstance(b.jobs[0], DagJob) and b.jobs[0].mesh is not None
    for _ in range(2):
        b.jobs[0].chunk_round()
        b.jobs[0].inject_barrier()
    # attach mid-stream: existing rows backfill, new rows stream in
    b.execute("CREATE MATERIALIZED VIEW v2 AS "
              "SELECT id, name FROM v WHERE id % 2 = 0")
    assert len(b.jobs) == 1  # attached to the mesh job, not a new one
    for _ in range(2):
        b.jobs[0].chunk_round()
        b.jobs[0].inject_barrier()
    rows_b = sorted(b.execute("SELECT id, name FROM v2"))

    a = build(0)
    for _ in range(2 * 8):
        a.jobs[0].chunk_round()
        a.jobs[0].inject_barrier()
    a.execute("CREATE MATERIALIZED VIEW v2 AS "
              "SELECT id, name FROM v WHERE id % 2 = 0")
    for _ in range(2 * 8):
        a.jobs[0].chunk_round()
        a.jobs[0].inject_barrier()
    rows_a = sorted(a.execute("SELECT id, name FROM v2"))
    assert rows_a == rows_b and len(rows_a) > 500

    # shapes that would pull a NEW un-sharded source into the mesh
    # job keep the explicit error (aggs/joins/TopN attach via the
    # device exchange now — see the cross-shard matrix tests below)
    from risingwave_tpu.sql.engine import PlanError
    with pytest.raises(PlanError, match="next round"):
        b.execute(
            "CREATE MATERIALIZED VIEW vx AS SELECT v.id AS id "
            "FROM v JOIN TUMBLE(person, date_time, INTERVAL '1' "
            "SECOND) p2 ON v.id = p2.id"
        )


def test_sharded_join_recovers_from_checkpoint(tmp_path):
    """Kill-and-recover a sharded join job from the durable store."""
    from risingwave_tpu.sql import Engine
    from risingwave_tpu.sql.planner import PlannerConfig

    def build():
        eng = Engine(PlannerConfig(
            chunk_capacity=128,
            join_left_table_size=1 << 12, join_left_bucket_cap=4,
            join_right_table_size=1 << 10, join_right_bucket_cap=512,
            join_out_capacity=1 << 12,
            mv_table_size=4096, mv_ring_size=1 << 15,
        ), data_dir=str(tmp_path))
        eng.execute(NEXMARK_WM_SOURCES)
        eng.execute("SET streaming_parallelism = 8")
        eng.execute(Q8_MV)
        return eng

    eng = build()
    job = eng.jobs[0]
    for _ in range(4):
        job.chunk_round()
        job.inject_barrier()
    # mesh jobs ride the async checkpoint pipeline now: committed
    # advances on uploader ack, so settle the queue before reading it
    job.drain_uploads()
    want = sorted(eng.execute("SELECT id, name, reserve FROM v"))
    committed = job.committed_epoch

    # per-shard shadow feeds the delta store: after the first full,
    # saves are dirty-fraction DELTAS, not tree-size full copies
    store = eng.checkpoint_store
    kinds = [store.checkpoint_kind("v", e) for e in store.epochs("v")]
    assert "delta" in kinds, kinds
    assert job._shadow is not None and job._shadow.shard_rows == 8

    # simulate mid-epoch crash: extra uncommitted work, then recover
    job.chunk_round()
    job.recover()
    assert job.committed_epoch == committed
    got = sorted(eng.execute("SELECT id, name, reserve FROM v"))
    assert got == want

    # continue after recovery: replay converges with an undisturbed run
    job.chunk_round()
    job.inject_barrier()
    after = sorted(eng.execute("SELECT id, name, reserve FROM v"))
    assert len(after) >= len(want)


def test_partial_agg_nullable_cols():
    """NCol group keys + args through the two-phase partial agg
    (round-2 verdict item 3b): NULL keys form one group; NULL args are
    skipped; an all-NULL segment yields a NULL partial."""
    from collections import Counter
    from risingwave_tpu.common.chunk import Chunk
    from risingwave_tpu.common.types import Field
    from risingwave_tpu.expr.agg import AggCall, count_star
    from risingwave_tpu.expr.node import InputRef, col
    from risingwave_tpu.stream.fragment import Fragment
    from risingwave_tpu.stream.hash_agg import HashAggExecutor
    from risingwave_tpu.stream.partial_agg import (
        PartialAggExecutor,
        translated_global_calls,
    )

    schema = Schema((
        Field("g", DataType.INT64, nullable=True),
        Field("v", DataType.INT64, nullable=True),
    ))
    group_by = [("g", col("g"))]
    aggs = [count_star("rows"), AggCall("count", col("v"), "n"),
            AggCall("sum", col("v"), "s"), AggCall("max", col("v"), "hi")]
    partial = PartialAggExecutor(schema, group_by, aggs)
    assert partial.out_schema[0].nullable          # key passthrough
    assert not partial.out_schema[1].nullable      # count_star
    assert partial.out_schema[3].nullable          # sum over nullable

    chunk = Chunk.from_pretty("""
        I I
        + 1 10
        + 1 .
        + . 7
        + . .
        + 2 .
    """, names=["g", "v"])
    frag = Fragment([partial])
    _, out = frag.step(frag.init_states(), chunk)

    glob = HashAggExecutor(
        partial.out_schema,
        [("g", InputRef(0))],
        translated_global_calls(aggs, 1),
        table_size=64, emit_capacity=16,
    )
    gfrag = Fragment([glob])
    gst = gfrag.init_states()
    gst, _ = gfrag.step(gst, out)
    gst, outs = gfrag.flush(gst, 1)
    mv = Counter()
    for op, *vals in outs[0].to_rows():
        mv[tuple(vals)] += 1 if op in (0, 3) else -1
    # group 1: 2 rows, count(v)=1, sum=10, max=10
    # group NULL: 2 rows, count(v)=1, sum=7, max=7
    # group 2: 1 row, count(v)=0, sum=NULL, max=NULL
    assert +mv == Counter({
        (1, 2, 1, 10, 10): 1,
        (None, 2, 1, 7, 7): 1,
        (2, 1, 0, None, None): 1,
    })


def test_sharded_exchange_carries_ncol():
    """NCol columns survive the all_to_all; NULL keys route to ONE
    shard (grouping-equality vnode routing)."""
    from jax.sharding import PartitionSpec as P
    from risingwave_tpu.common.chunk import NCol
    from risingwave_tpu.common.types import Field
    from risingwave_tpu.parallel.exchange import shuffle_chunk

    from risingwave_tpu.parallel.exchange import shard_map_nocheck

    schema = Schema((
        Field("g", DataType.INT64, nullable=True),
        Field("v", DataType.INT64),
    ))
    mesh = make_mesh(8)
    cap = 16

    def body(_):
        g = NCol(
            jnp.arange(cap, dtype=jnp.int64) % 4,
            jnp.arange(cap) % 4 == 3,  # every 4th row: NULL key
        )
        chunk = Chunk(
            (g, jnp.arange(cap, dtype=jnp.int64)),
            jnp.zeros((cap,), jnp.int8),
            jnp.ones((cap,), jnp.bool_),
            schema,
        )
        out = shuffle_chunk(chunk, [chunk.column(0)], "shard", 8)
        return jax.tree.map(lambda x: x[None], out)

    f = jax.jit(shard_map_nocheck(
        body, mesh=mesh, in_specs=(P("shard"),), out_specs=P("shard"),
    ))
    out = f(jnp.zeros((8,), jnp.int32))
    leaves = jax.tree.map(np.asarray, out)
    null_shards = set()
    total = 0
    for shard in range(8):
        c = jax.tree.map(lambda x: x[shard], leaves)
        _, cols, valid = c.to_host()
        for i in range(int(np.asarray(valid).sum())):
            if cols[0][i] is None:
                null_shards.add(shard)
            total += 1
    assert total == 8 * cap            # nothing lost
    assert len(null_shards) == 1       # NULL keys on exactly one shard


def test_sql_sharded_global_topn_matches_linear():
    """GROUP BY + ORDER BY/LIMIT plans sharded: per-shard bands hold a
    superset of the global top-k and the serving read applies the
    global order+limit (r3 verdict ask #8 — q4/q6-shaped plans stop
    falling back to linear)."""
    from risingwave_tpu.sql import Engine
    from risingwave_tpu.sql.planner import PlannerConfig

    SQL = ("CREATE MATERIALIZED VIEW v AS SELECT auction, count(*) AS n "
           "FROM bid GROUP BY auction ORDER BY n DESC, auction LIMIT 5")

    def build(par):
        eng = Engine(PlannerConfig(
            chunk_capacity=128, agg_table_size=512, agg_emit_capacity=128,
            mv_table_size=512, mv_ring_size=1024,
            topn_pool_size=512, topn_emit_capacity=128,
        ))
        eng.execute(
            "CREATE SOURCE bid (auction BIGINT, price BIGINT, "
            "date_time TIMESTAMP) WITH (connector='nexmark', "
            "nexmark.table='bid')"
        )
        if par:
            eng.execute(f"SET streaming_parallelism = {par}")
        eng.execute(SQL)
        return eng

    from risingwave_tpu.stream.sharded import ShardedStreamingJob
    a = build(0)
    b = build(8)
    assert isinstance(b.jobs[0], ShardedStreamingJob), \
        "global TopN should shard now"

    # equal row counts: linear 8 chunks of 128 = sharded 1 step of 8x128
    a.tick(barriers=1, chunks_per_barrier=8)
    b.jobs[0].run_chunk()
    b.jobs[0].inject_barrier()

    got_a = a.execute("SELECT auction, n FROM v")
    got_b = b.execute("SELECT auction, n FROM v")
    # band CONTENT matches (linear serving returns band rows unordered;
    # the sharded read merges + orders via serving_topn)
    assert sorted(tuple(map(int, r)) for r in got_a) == \
        sorted(tuple(map(int, r)) for r in got_b)
    assert len(got_b) == 5
    # and the band is the true top-5 (ground truth)
    from risingwave_tpu.connector.nexmark import NexmarkGenerator
    g = NexmarkGenerator()
    _, cols, _ = g.gen_bids(0, 1024).to_host()
    import collections
    cnt = collections.Counter(int(x) for x in cols[0])
    want = sorted(cnt.items(), key=lambda kv: (-kv[1], kv[0]))[:5]
    assert [tuple(map(int, r)) for r in got_b] == want


def test_online_rescale_2_to_4_converges():
    """ALTER MATERIALIZED VIEW ... SET PARALLELISM mid-stream: state
    moves to the new mesh at a barrier and results converge with an
    undisturbed run (r3 verdict ask #7; ref scale.rs reschedule)."""
    from risingwave_tpu.sql import Engine
    from risingwave_tpu.sql.planner import PlannerConfig

    def build(par):
        eng = Engine(PlannerConfig(
            chunk_capacity=128, agg_table_size=512, agg_emit_capacity=128,
            mv_table_size=512, mv_ring_size=1024,
        ))
        eng.execute(
            "CREATE SOURCE bid (auction BIGINT, price BIGINT, "
            "date_time TIMESTAMP) WITH (connector='nexmark', "
            "nexmark.table='bid')"
        )
        eng.execute(f"SET streaming_parallelism = {par}")
        eng.execute(
            "CREATE MATERIALIZED VIEW v AS SELECT auction, "
            "count(*) AS n, max(price) AS hi FROM bid GROUP BY auction"
        )
        return eng

    eng = build(2)
    from risingwave_tpu.stream.sharded import ShardedStreamingJob
    job = eng.jobs[0]
    assert isinstance(job, ShardedStreamingJob)
    assert job.sharded.n_shards == 2

    # phase 1 on 2 shards: 2 chunk-units = 2*2*128 = 512 rows
    job.run_chunk(); job.run_chunk(); job.inject_barrier()
    eng.execute("ALTER MATERIALIZED VIEW v SET PARALLELISM 4")
    assert job.sharded.n_shards == 4
    mid = {int(r[0]): (int(r[1]), int(r[2]))
           for r in eng.execute("SELECT auction, n, hi FROM v")}

    # phase 2 on 4 shards: 1 chunk-unit = 4*128 = 512 rows
    job.run_chunk(); job.inject_barrier()
    got = {int(r[0]): (int(r[1]), int(r[2]))
           for r in eng.execute("SELECT auction, n, hi FROM v")}

    from risingwave_tpu.connector.nexmark import NexmarkGenerator

    def want(total):
        g = NexmarkGenerator()
        _, cols, _ = g.gen_bids(0, total).to_host()
        out = {}
        for auc, pr in zip(cols[0], cols[2]):
            n, hi = out.get(int(auc), (0, 0))
            out[int(auc)] = (n + 1, max(hi, int(pr)))
        return out

    assert mid == want(512), "state lost/duplicated across rescale"
    assert got == want(1024), "post-rescale stream diverged"


def test_sharded_sink_delivers_exactly_once_across_recovery():
    """A sharded agg job with a file sink: per-shard ring cursors merge
    at the snapshot barrier; recovery neither duplicates nor drops
    (r3 verdict ask #8, sink half)."""
    import json as _json

    from risingwave_tpu.sql import Engine
    from risingwave_tpu.sql.planner import PlannerConfig

    import tempfile, os
    tmp = tempfile.mkdtemp()
    path = os.path.join(tmp, "out.jsonl")
    data_dir = os.path.join(tmp, "ckpt")

    def build():
        eng = Engine(PlannerConfig(
            chunk_capacity=128, agg_table_size=512, agg_emit_capacity=128,
            mv_table_size=512, mv_ring_size=2048,
        ), data_dir=data_dir)
        eng.execute(
            "CREATE SOURCE bid (auction BIGINT, price BIGINT, "
            "date_time TIMESTAMP) WITH (connector='nexmark', "
            "nexmark.table='bid')"
        )
        eng.execute("SET streaming_parallelism = 4")
        eng.execute(
            "CREATE SINK s AS SELECT auction, count(*) AS n FROM bid "
            f"GROUP BY auction WITH (connector='file', path='{path}')"
        )
        return eng

    eng = build()
    from risingwave_tpu.stream.sharded import ShardedStreamingJob
    job = eng.jobs[0]
    assert isinstance(job, ShardedStreamingJob), "sink job should shard"
    job.run_chunk()
    job.inject_barrier()
    job.drain_uploads()  # the epoch is on disk before the "crash"

    # fold the delivered changelog: per-key latest insert wins
    def fold():
        state = {}
        for line in open(path):
            r = _json.loads(line)
            if r["op"] in ("insert", "update_insert"):
                state[r["auction"]] = r["n"]
            elif r["op"] in ("delete", "update_delete"):
                state.pop(r["auction"], None)
        return state

    from risingwave_tpu.connector.nexmark import NexmarkGenerator
    import collections
    g = NexmarkGenerator()
    _, cols, _ = g.gen_bids(0, 512).to_host()
    want1 = dict(collections.Counter(int(x) for x in cols[0]))
    assert fold() == want1

    # crash + recover: the fresh engine cold-starts from data_dir
    # (DDL replay + checkpoint restore) and resumes delivery
    eng2 = Engine(PlannerConfig(
        chunk_capacity=128, agg_table_size=512, agg_emit_capacity=128,
        mv_table_size=512, mv_ring_size=2048,
    ), data_dir=data_dir)
    job2 = eng2.jobs[0]
    job2.run_chunk()
    job2.inject_barrier()
    _, cols, _ = g.gen_bids(0, 1024).to_host()
    want2 = dict(collections.Counter(int(x) for x in cols[0]))
    assert fold() == want2, "duplicated or lost sink rows after recovery"


def test_rescale_survives_recovery_with_stale_ddl_parallelism():
    """A rescaled job's checkpoint is authoritative: recovery rebuilds
    the mesh to the checkpoint's shard dim even when the replanned DDL
    asked for the old parallelism."""
    import tempfile
    from risingwave_tpu.sql import Engine
    from risingwave_tpu.sql.planner import PlannerConfig

    data_dir = tempfile.mkdtemp()

    def build():
        eng = Engine(PlannerConfig(
            chunk_capacity=128, agg_table_size=512, agg_emit_capacity=128,
            mv_table_size=512, mv_ring_size=1024,
        ), data_dir=data_dir)
        eng.execute(
            "CREATE SOURCE bid (auction BIGINT, price BIGINT, "
            "date_time TIMESTAMP) WITH (connector='nexmark', "
            "nexmark.table='bid')"
        )
        eng.execute("SET streaming_parallelism = 2")
        eng.execute(
            "CREATE MATERIALIZED VIEW v AS SELECT auction, "
            "count(*) AS n FROM bid GROUP BY auction"
        )
        return eng

    eng = build()
    job = eng.jobs[0]
    job.run_chunk()
    job.inject_barrier()
    eng.execute("ALTER MATERIALIZED VIEW v SET PARALLELISM 4")
    want = sorted(map(tuple, eng.execute("SELECT * FROM v")))

    # cold start: bootstrap replays the DDL log (including the ALTER
    # PARALLELISM) and restores the 4-shard checkpoint topology
    eng2 = Engine(PlannerConfig(
        chunk_capacity=128, agg_table_size=512, agg_emit_capacity=128,
        mv_table_size=512, mv_ring_size=1024,
    ), data_dir=data_dir)
    job2 = eng2.jobs[0]
    assert job2.sharded.n_shards == 4, "checkpoint topology not restored"
    assert sorted(map(tuple, eng2.execute("SELECT * FROM v"))) == want


def test_sharded_dag_spill_over_join():
    """Spill-to-host under the mesh (verdict r4 item 5): a sharded
    join→agg job whose group cardinality is ~4x the device table
    completes via PER-SHARD host tiers, matching the linear run."""
    from risingwave_tpu.sql import Engine
    from risingwave_tpu.sql.planner import PlannerConfig
    from risingwave_tpu.stream.dag import DagJob

    n_groups = 220  # >> agg_table_size(64)

    def build(par):
        eng = Engine(PlannerConfig(
            chunk_capacity=128,
            agg_table_size=64,
            agg_emit_capacity=256,
            join_table_size=1 << 10, join_bucket_cap=32,
            join_out_capacity=1 << 12,
            mv_table_size=1 << 10, mv_ring_size=1 << 12,
            agg_spill_ring=1 << 10,
        ))
        if par:
            eng.execute(f"SET streaming_parallelism = {par}")
        eng.execute("CREATE TABLE item (id BIGINT, grp BIGINT, "
                    "PRIMARY KEY (id))")
        eng.execute("CREATE TABLE hit (item BIGINT, w BIGINT)")
        for i in range(0, n_groups, 64):
            vals = ",".join(f"({k},{k % 7})"
                            for k in range(i, min(i + 64, n_groups)))
            eng.execute(f"INSERT INTO item VALUES {vals}")
        rows = [(i, 10 * i + r) for i in range(n_groups)
                for r in range(2)]
        for i in range(0, len(rows), 64):
            vals = ",".join(f"({a},{b})" for a, b in rows[i:i + 64])
            eng.execute(f"INSERT INTO hit VALUES {vals}")
        eng.execute(
            "CREATE MATERIALIZED VIEW mv AS SELECT h.item AS k, "
            "count(*) AS n, sum(h.w) AS s FROM hit h "
            "JOIN item i ON h.item = i.id GROUP BY h.item"
        )
        eng.execute("FLUSH")
        eng.tick(barriers=4)
        return eng

    lin = build(0)
    want = sorted(map(tuple, lin.execute("SELECT * FROM mv")))
    assert len(want) == n_groups

    sh = build(2)
    job = sh.jobs[0]
    assert isinstance(job, DagJob) and job.mesh is not None
    got = sorted(map(tuple, sh.execute("SELECT * FROM mv")))
    assert got == want
    # the device table really was too small: per-shard tiers absorbed
    tiers = getattr(job, "_spill_tiers", {})
    absorbed = sum(t.rows_absorbed for ts in tiers.values() for t in ts)
    assert tiers and absorbed > 0


def _q8_engine(par, extra=None):
    """Shared builder for the cross-shard MV-on-MV matrix tests."""
    from risingwave_tpu.sql import Engine
    from risingwave_tpu.sql.planner import PlannerConfig

    cfg = dict(
        chunk_capacity=128,
        join_left_table_size=1 << 12, join_left_bucket_cap=4,
        join_right_table_size=1 << 10, join_right_bucket_cap=512,
        join_out_capacity=1 << 12,
        mv_table_size=4096, mv_ring_size=1 << 15,
        topn_pool_size=1 << 12, topn_emit_capacity=256,
        agg_table_size=1 << 10, agg_emit_capacity=512,
    )
    cfg.update(extra or {})
    eng = Engine(PlannerConfig(**cfg))
    eng.execute(NEXMARK_WM_SOURCES)
    if par:
        eng.execute(f"SET streaming_parallelism = {par}")
    eng.execute(Q8_MV)
    return eng


def _drive(eng, rounds):
    for _ in range(rounds):
        for job in eng.jobs:
            job.chunk_round()
        for job in eng.jobs:
            job.inject_barrier()


def test_cross_shard_agg_and_topn_over_sharded_join_matches_linear():
    """ISSUE 9 tentpole: previously-rejected cross-shard MV-on-MV
    shapes attach via the device hash exchange and converge
    byte-identical to the linear run, including mid-stream attach +
    backfill:

    - ``vagg``: HashAgg over a REDUCED key (group ``id`` ⊂ the join's
      (id, window) distribution) — exchange keyed on the group-by;
    - ``vcnt``: GLOBAL agg (no keys) — constant-key exchange to one
      owning shard (the singleton-fragment analog);
    - ``vt``: global TopN over the sharded agg MV — constant-key
      exchange, band on one shard, merged read identical."""
    from risingwave_tpu.stream.dag import DagJob

    b = _q8_engine(8)
    assert isinstance(b.jobs[0], DagJob) and b.jobs[0].mesh is not None
    _drive(b, 2)
    b.execute("CREATE MATERIALIZED VIEW vagg AS SELECT id, "
              "count(*) AS n, sum(reserve) AS s FROM v GROUP BY id")
    b.execute("CREATE MATERIALIZED VIEW vcnt AS "
              "SELECT count(*) AS n FROM v")
    b.execute("CREATE MATERIALIZED VIEW vt AS SELECT id, n FROM vagg "
              "ORDER BY n DESC, id LIMIT 5")
    assert len(b.jobs) == 1  # all attached to the one mesh job
    _drive(b, 2)

    a = _q8_engine(0)
    _drive(a, 2 * 8)
    a.execute("CREATE MATERIALIZED VIEW vagg AS SELECT id, "
              "count(*) AS n, sum(reserve) AS s FROM v GROUP BY id")
    a.execute("CREATE MATERIALIZED VIEW vcnt AS "
              "SELECT count(*) AS n FROM v")
    a.execute("CREATE MATERIALIZED VIEW vt AS SELECT id, n FROM vagg "
              "ORDER BY n DESC, id LIMIT 5")
    _drive(a, 2 * 8)

    for mv in ("vagg", "vcnt", "vt"):
        ra = sorted(a.execute(f"SELECT * FROM {mv}"))
        rb = sorted(b.execute(f"SELECT * FROM {mv}"))
        assert ra == rb and len(ra) > 0, (mv, ra[:3], rb[:3])
    # the reduced-key agg really is cross-shard: groups live on more
    # than one shard of the attached agg node
    job = b.jobs[0]
    vagg_node = b.catalog.get("vagg").mv_state_index[0]
    occ = np.asarray(jax.device_get(
        job.states[vagg_node][0].table.occupied))
    shards_with_groups = int((occ.sum(axis=1) > 0).sum())
    assert shards_with_groups > 1, "agg groups all on one shard"


def test_cross_shard_join_of_two_sharded_mvs_matches_linear():
    """Join of two SHARDED MVs: their mesh jobs merge into one, the
    new JoinNode gets an all_to_all exchange per side keyed on its
    equi keys, both sides backfill through the exchange, and the
    result is byte-identical to the linear run."""
    from risingwave_tpu.stream.dag import DagJob

    W_MV = ("CREATE MATERIALIZED VIEW w AS "
            "SELECT a.reserve AS r, a.expires AS exp "
            "FROM TUMBLE(person, date_time, INTERVAL '1' SECOND) p "
            "JOIN TUMBLE(auction, date_time, INTERVAL '1' SECOND) a "
            "ON p.id = a.seller AND p.window_start = a.window_start")
    J_MV = ("CREATE MATERIALIZED VIEW j AS SELECT v.id AS id, "
            "v.reserve AS reserve, w.exp AS exp FROM v JOIN w "
            "ON v.reserve = w.r")

    b = _q8_engine(8, extra={"mv_ring_size": 1 << 16})
    b.execute(W_MV)
    assert all(isinstance(jb, DagJob) and jb.mesh is not None
               for jb in b.jobs)
    assert len(b.jobs) == 2
    _drive(b, 1)
    b.execute(J_MV)  # mid-stream: merges the two mesh jobs
    assert len(b.jobs) == 1
    _drive(b, 1)
    rb = sorted(b.execute("SELECT id, reserve, exp FROM j"))

    a = _q8_engine(0, extra={"mv_ring_size": 1 << 16})
    a.execute(W_MV)
    _drive(a, 1 * 8)
    a.execute(J_MV)
    _drive(a, 1 * 8)
    ra = sorted(a.execute("SELECT id, reserve, exp FROM j"))
    assert ra == rb and len(ra) > 100
