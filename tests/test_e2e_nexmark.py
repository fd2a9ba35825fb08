"""End-to-end Nexmark pipelines on the streaming runtime (CPU).

Mirrors the reference's e2e nexmark suite (e2e_test/nexmark/) at small
scale: the same queries run as maintained MVs and their contents are
cross-checked against a numpy reimplementation of the query.
"""

import jax.numpy as jnp
import numpy as np

from risingwave_tpu.common.types import DataType
from risingwave_tpu.connector.nexmark import (
    NexmarkGenerator,
    NexmarkSplitReader,
)
from risingwave_tpu.expr.agg import AggCall, count_star
from risingwave_tpu.expr.node import FuncCall, col, lit
from risingwave_tpu.stream.executor import ProjectExecutor
from risingwave_tpu.stream.fragment import Fragment
from risingwave_tpu.stream.hash_agg import HashAggExecutor
from risingwave_tpu.stream.hash_join import HashJoinExecutor
from risingwave_tpu.stream.materialize import (
    AppendOnlyMaterialize,
    MaterializeExecutor,
)
from risingwave_tpu.stream.dag import DagJob
from risingwave_tpu.stream.runtime import StreamingJob

WINDOW_US = 10_000_000


def test_q1_currency_conversion():
    """q1: SELECT auction, bidder, 0.908*price, date_time FROM bid."""
    src = NexmarkSplitReader("bid", chunk_capacity=256)
    proj = ProjectExecutor(src.schema, [
        ("auction", col("auction")),
        ("price_eur", col("price").cast(DataType.FLOAT64) * 0.908),
    ])
    mv = AppendOnlyMaterialize(proj.out_schema, ring_size=1024)
    job = StreamingJob(src, Fragment([proj, mv]))
    job.run(barriers=2, chunks_per_barrier=2)
    rows = mv.to_host(job.states[1])
    assert len(rows) == 1024

    want = NexmarkGenerator().gen_bids(0, 1024)
    _, cols, _ = want.to_host()
    np.testing.assert_allclose(
        [r[1] for r in rows], cols[2] * 0.908, rtol=1e-12
    )


def test_q7_style_windowed_max(accel_tuned):
    """q7-ish: max price + bid count per 10s tumbling window."""
    cap = 512
    src = NexmarkSplitReader("bid", chunk_capacity=cap)
    proj = ProjectExecutor(src.schema, [
        ("w", FuncCall("tumble_start",
                       (col("date_time"), lit(WINDOW_US, DataType.INTERVAL)))),
        ("price", col("price")),
    ])
    agg = HashAggExecutor(
        proj.out_schema, [("w", col("w"))],
        [AggCall("max", col("price"), "max_price"), count_star("bids")],
        table_size=256, emit_capacity=64,
    )
    mv = MaterializeExecutor(agg.out_schema, pk_indices=[0], table_size=256)
    job = StreamingJob(src, Fragment([proj, agg, mv]))
    n_chunks = 4
    job.run(barriers=2, chunks_per_barrier=2)
    got = {int(w): (int(mx), int(n)) for w, mx, n in mv.to_host(job.states[2])}

    bids = NexmarkGenerator().gen_bids(0, n_chunks * cap)
    _, cols, _ = bids.to_host()
    price, ts = cols[2], cols[5]
    w = ts - ts % WINDOW_US
    want = {}
    for wv in np.unique(w):
        m = w == wv
        want[int(wv)] = (int(price[m].max()), int(m.sum()))
    assert got == want


def test_q8_style_windowed_join(accel_tuned):
    """q8-ish: persons joined with auctions by seller in the same window."""
    cap = 256
    gen = NexmarkGenerator()
    persons = NexmarkSplitReader("person", gen, chunk_capacity=cap)
    auctions = NexmarkSplitReader("auction", gen, chunk_capacity=cap)

    p_proj = ProjectExecutor(persons.schema, [
        ("w", FuncCall("tumble_start",
                       (col("date_time"), lit(WINDOW_US, DataType.INTERVAL)))),
        ("id", col("id")),
        ("name", col("name")),
    ])
    a_proj = ProjectExecutor(auctions.schema, [
        ("w", FuncCall("tumble_start",
                       (col("date_time"), lit(WINDOW_US, DataType.INTERVAL)))),
        ("seller", col("seller")),
        ("reserve", col("reserve")),
    ])
    join = HashJoinExecutor(
        p_proj.out_schema, a_proj.out_schema,
        [col("w"), col("id")], [col("w"), col("seller")],
        table_size=1 << 12, out_capacity=1 << 15,
        left_bucket_cap=4,      # persons are unique per key
        right_bucket_cap=512,   # hot sellers concentrate auctions
    )
    mv = AppendOnlyMaterialize(join.out_schema, ring_size=1 << 15)
    job = DagJob.binary(persons, auctions, join, Fragment([mv]),
                    left_fragment=Fragment([p_proj]),
                    right_fragment=Fragment([a_proj]))
    job.run(barriers=2, chunks_per_barrier=1)
    rows = mv.to_host(job.states[3][0])

    # ground truth join in numpy (sides pace 1:3 by event time, so two
    # scheduling units pull 2 person chunks and 6 auction chunks)
    p = NexmarkGenerator().gen_persons(0, 2 * cap)
    a = NexmarkGenerator().gen_auctions(0, 6 * cap)
    _, pc, _ = p.to_host()
    _, ac, _ = a.to_host()
    p_w = pc[6] - pc[6] % WINDOW_US
    a_w = ac[5] - ac[5] % WINDOW_US
    want = set()
    from collections import Counter
    want = Counter()
    for i in range(len(pc[0])):
        for j in range(len(ac[0])):
            if pc[0][i] == ac[7][j] and p_w[i] == a_w[j]:
                want[(int(p_w[i]), int(pc[0][i]), int(ac[7][j]),
                      int(ac[4][j]))] += 1
    got = Counter(
        (int(r[0]), int(r[1]), int(r[4]), int(r[5])) for r in rows
    )
    assert got == want
    assert sum(want.values()) > 0  # the test actually joined something


def test_nexmark_splits_partition_the_stream():
    """N split readers cover the ordinal space disjointly (the
    reference's source split assignment, base.rs:222)."""
    gen = NexmarkGenerator()
    whole = NexmarkSplitReader("bid", gen, chunk_capacity=64)
    want = []
    for _ in range(4):
        _, cols, _ = whole.next_chunk().to_host()
        want.extend(zip(cols[0], cols[1], cols[5]))

    parts = [
        NexmarkSplitReader("bid", gen, chunk_capacity=64,
                           split_id=i, num_splits=2)
        for i in range(2)
    ]
    got = []
    for r in parts:
        for _ in range(2):
            _, cols, _ = r.next_chunk().to_host()
            got.extend(zip(cols[0], cols[1], cols[5]))
    assert sorted(got) == sorted(want)
    # offsets checkpoint per split
    assert parts[0].state() == {"table": "bid", "split_id": 0,
                                "offset": 128}


def test_price_is_integer_arithmetic_on_a_fixed_curve():
    """The generator's price must be the same on every backend (the
    chip emulates float64 and rounded ``10.0 ** x`` differently from a
    host in 5% of rows): integer arithmetic over knots that ``decimal``
    builds, checked here against plain Python integers."""
    from risingwave_tpu.connector import nexmark as nx

    knots = [int(k) for k in nx._PRICE_KNOTS]
    assert knots[0] == 100 and knots[-1] == 100_000_000
    assert all(a <= b for a, b in zip(knots, knots[1:]))
    ids = np.arange(5000, dtype=np.int64) * 7919
    got = np.asarray(nx._next_price(jnp.asarray(ids), 5))
    draws = np.asarray(nx._rand(jnp.asarray(ids), 5))
    for r, g in zip(draws.tolist(), got.tolist()):
        k, frac = r >> 54, (r >> 22) & 0xFFFFFFFF
        assert g == knots[k] + (((knots[k + 1] - knots[k]) * frac) >> 32)
    assert got.min() >= 100 and got.max() < 100_000_000
