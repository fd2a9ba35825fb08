"""Retractable MIN/MAX via materialized-input state (ref minput.rs).

Ground truth: python multisets replayed alongside the executor — every
flush's folded changelog must equal the brute-force min/max per group.
"""

from collections import Counter, defaultdict

import jax.numpy as jnp
import numpy as np
import pytest

import risingwave_tpu  # noqa: F401
from risingwave_tpu.common.chunk import Chunk
from risingwave_tpu.common.types import DataType, Field, Schema
from risingwave_tpu.expr.agg import AggCall
from risingwave_tpu.expr.node import InputRef
from risingwave_tpu.stream.hash_agg import HashAggExecutor

SCHEMA = Schema((Field("g", DataType.INT64), Field("v", DataType.INT64)))


def make_chunk(rows, ops):
    cols = tuple(
        jnp.asarray([r[i] for r in rows] or [0], jnp.int64)
        for i in range(2)
    )
    return Chunk(
        cols,
        jnp.asarray(ops or [0], jnp.int8),
        jnp.asarray([True] * len(rows) or [False], jnp.bool_),
        SCHEMA,
    )


def fold(acc: dict, out: Chunk):
    """Fold a (g, min, max) changelog into {g: (min, max)}."""
    vis = np.asarray(out.valid)
    ops = np.asarray(out.ops)[vis]
    cols = [np.asarray(c)[vis] for c in out.columns]
    for i in range(len(ops)):
        g = int(cols[0][i])
        row = (int(cols[1][i]), int(cols[2][i]))
        if ops[i] in (0, 3):
            acc[g] = row
        else:
            if acc.get(g) == row:
                del acc[g]
    return acc


SCRIPT = [
    ([(1, 5), (1, 9), (2, 7)], [0, 0, 0]),
    ([(1, 3)], [0]),               # new min
    ([(1, 3)], [1]),               # delete the min -> recompute to 5
    ([(1, 9), (1, 5)], [1, 1]),    # group 1 empties
    ([(2, 7), (2, 7)], [0, 1]),    # in-chunk annihilation (dup value)
    ([(3, 4), (3, 4), (3, 6)], [0, 0, 0]),  # duplicate values
    ([(3, 4)], [1]),               # one duplicate leaves; min stays 4
    ([(3, 4)], [1]),               # the other leaves; min becomes 6
]


def test_retractable_minmax_ground_truth():
    agg = HashAggExecutor(
        SCHEMA,
        [("g", InputRef(0))],
        [AggCall("min", InputRef(1), "mn"), AggCall("max", InputRef(1), "mx")],
        table_size=64, emit_capacity=64,
        retractable_input=True, minput_table_size=32,
    )
    st = agg.init_state()
    acc: dict = {}
    live = defaultdict(Counter)
    epoch = 0
    for rows, ops in SCRIPT:
        for (g, v), o in zip(rows, ops):
            if o == 0:
                live[g][v] += 1
            else:
                live[g][v] -= 1
        st, _ = agg.apply(st, make_chunk(rows, ops))
        epoch += 1
        st, out = agg.flush(st, epoch)
        fold(acc, out)
        want = {}
        for g, c in live.items():
            vals = list(c.elements())
            if vals:
                want[g] = (min(vals), max(vals))
        assert acc == want, f"after {rows} {ops}: {acc} != {want}"
    assert int(st.inconsistency) == 0
    assert int(st.overflow) == 0


def test_minput_table_overflow_is_loud():
    """A materialised-input table with no slot left counts the row it
    lost on its OWN counter, not the group table's."""
    agg = HashAggExecutor(
        SCHEMA, [("g", InputRef(0))],
        [AggCall("min", InputRef(1), "mn")],
        table_size=64, emit_capacity=64,
        retractable_input=True, minput_table_size=2,
    )
    st = agg.init_state()
    st, _ = agg.apply(st, make_chunk([(1, 1), (1, 2), (1, 3)], [0, 0, 0]))
    assert int(st.minput_overflow) == 1  # the third value found no slot
    assert int(st.overflow) == 0


def test_sql_min_over_retractable_cascade():
    """MIN over an agg MV's changelog (a retractable stream): deletes
    recompute exactly instead of crashing the job."""
    from tests.test_dag import small_engine

    eng = small_engine()
    eng.execute("CREATE TABLE t (k BIGINT, v BIGINT);")
    eng.execute("""
        CREATE MATERIALIZED VIEW counts AS
        SELECT k, count(*) AS n FROM t GROUP BY k;
    """)
    eng.execute("""
        CREATE MATERIALIZED VIEW extremes AS
        SELECT min(n) AS mn, max(n) AS mx FROM counts;
    """)
    eng.execute("INSERT INTO t VALUES (1, 0), (1, 0), (2, 0)")
    eng.tick(barriers=2, chunks_per_barrier=1)
    # counts: {1: 2, 2: 1}
    assert eng.execute("SELECT * FROM extremes") == [(1, 2)]
    eng.execute("INSERT INTO t VALUES (2, 0), (2, 0)")
    eng.tick(barriers=2, chunks_per_barrier=1)
    # counts: {1: 2, 2: 3} — the old max row (2,1) was RETRACTED
    assert eng.execute("SELECT * FROM extremes") == [(2, 3)]
    eng.execute("INSERT INTO t VALUES (3, 0)")
    eng.tick(barriers=2, chunks_per_barrier=1)
    # counts: {1: 2, 2: 3, 3: 1} — min drops back to 1
    assert eng.execute("SELECT * FROM extremes") == [(1, 3)]
