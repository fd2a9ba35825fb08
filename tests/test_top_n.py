"""TopN / dedup executor tests (changelog-diff semantics)."""

from collections import Counter

from risingwave_tpu.common.chunk import Chunk
from risingwave_tpu.common.types import DataType, Schema
from risingwave_tpu.expr.node import col
from risingwave_tpu.stream.fragment import Fragment
from risingwave_tpu.stream.top_n import (
    AppendOnlyDedupExecutor,
    GroupTopNExecutor,
)

S = Schema.of(("g", DataType.INT64), ("v", DataType.INT64))


def _chunk(text):
    return Chunk.from_pretty(text, names=["g", "v"])


def _fold(counter, out):
    for op, *vals in out.to_rows():
        if op in (0, 3):
            counter[tuple(vals)] += 1
        else:
            counter[tuple(vals)] -= 1
    return +counter


def test_plain_top2_asc():
    top = GroupTopNExecutor(
        S, group_by=[], order_by=[(col("v"), False)], limit=2,
        pool_size=16, emit_capacity=8,
    )
    frag = Fragment([top])
    st = frag.init_states()
    st, _ = frag.step(st, _chunk("""
        I I
        + 0 30
        + 0 10
        + 0 20
    """))
    st, outs = frag.flush(st, 1)
    mv = _fold(Counter(), outs[0])
    assert mv == Counter({(0, 10): 1, (0, 20): 1})

    # a smaller value displaces 20
    st, _ = frag.step(st, _chunk("""
        I I
        + 0 5
    """))
    st, outs = frag.flush(st, 2)
    mv = _fold(mv, outs[0])
    assert mv == Counter({(0, 5): 1, (0, 10): 1})

    # delete 5 -> 20 re-enters from the pool (retraction within pool)
    st, _ = frag.step(st, _chunk("""
        I I
        - 0 5
    """))
    st, outs = frag.flush(st, 3)
    mv = _fold(mv, outs[0])
    assert mv == Counter({(0, 10): 1, (0, 20): 1})


def test_group_top1_desc(accel_tuned):
    top = GroupTopNExecutor(
        S, group_by=[col("g")], order_by=[(col("v"), True)], limit=1,
        pool_size=16, emit_capacity=8,
    )
    frag = Fragment([top])
    st = frag.init_states()
    st, _ = frag.step(st, _chunk("""
        I I
        + 1 10
        + 1 30
        + 2 7
    """))
    st, outs = frag.flush(st, 1)
    mv = _fold(Counter(), outs[0])
    assert mv == Counter({(1, 30): 1, (2, 7): 1})

    st, _ = frag.step(st, _chunk("""
        I I
        + 2 9
        + 1 20
    """))
    st, outs = frag.flush(st, 2)
    mv = _fold(mv, outs[0])
    assert mv == Counter({(1, 30): 1, (2, 9): 1})


def test_topn_offset():
    top = GroupTopNExecutor(
        S, group_by=[], order_by=[(col("v"), False)], limit=2, offset=1,
        pool_size=16, emit_capacity=8,
    )
    frag = Fragment([top])
    st = frag.init_states()
    st, _ = frag.step(st, _chunk("""
        I I
        + 0 10
        + 0 20
        + 0 30
        + 0 40
    """))
    st, outs = frag.flush(st, 1)
    mv = _fold(Counter(), outs[0])
    assert mv == Counter({(0, 20): 1, (0, 30): 1})


def test_topn_duplicate_values(accel_tuned):
    top = GroupTopNExecutor(
        S, group_by=[], order_by=[(col("v"), False)], limit=3,
        pool_size=16, emit_capacity=8,
    )
    frag = Fragment([top])
    st = frag.init_states()
    st, _ = frag.step(st, _chunk("""
        I I
        + 0 10
        + 0 10
        + 0 20
        + 0 30
    """))
    st, outs = frag.flush(st, 1)
    mv = _fold(Counter(), outs[0])
    assert mv == Counter({(0, 10): 2, (0, 20): 1})

    # delete one duplicate: multiset diff emits exactly one delete
    st, _ = frag.step(st, _chunk("""
        I I
        - 0 10
    """))
    st, outs = frag.flush(st, 2)
    mv = _fold(mv, outs[0])
    assert mv == Counter({(0, 10): 1, (0, 20): 1, (0, 30): 1})


def test_append_only_dedup():
    dedup = AppendOnlyDedupExecutor(S, [col("g")], table_size=64)
    frag = Fragment([dedup])
    st = frag.init_states()
    st, out = frag.step(st, _chunk("""
        I I
        + 1 10
        + 1 11
        + 2 20
    """))
    assert sorted(out.to_rows()) == [(0, 1, 10), (0, 2, 20)]
    st, out = frag.step(st, _chunk("""
        I I
        + 1 12
        + 3 30
    """))
    assert sorted(out.to_rows()) == [(0, 3, 30)]
