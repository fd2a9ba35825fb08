"""Streaming executor tests, modeled on the reference's executor tests
(chunk DSL in, snapshot of emitted changelog out — SURVEY.md §4)."""

import jax.numpy as jnp
import numpy as np
import pytest

from risingwave_tpu.common.chunk import Chunk
from risingwave_tpu.common.types import DataType, Field, Schema
from risingwave_tpu.expr.node import col
from risingwave_tpu.expr.agg import AggCall, count_star
from risingwave_tpu.stream.executor import FilterExecutor, ProjectExecutor
from risingwave_tpu.stream.fragment import Fragment
from risingwave_tpu.stream.hash_agg import HashAggExecutor
from risingwave_tpu.stream.materialize import (
    AppendOnlyMaterialize,
    MaterializeExecutor,
)


def _rows(chunk):
    return sorted(chunk.to_rows())


def test_project_filter_fragment():
    schema = Schema.of(("a", DataType.INT64), ("b", DataType.INT64))
    proj = ProjectExecutor(schema, [("a", col("a")), ("c", col("b") * 2)])
    filt = FilterExecutor(proj.out_schema, col("c") > 10)
    frag = Fragment([proj, filt])
    states = frag.init_states()
    chunk = Chunk.from_pretty(
        """
        I I
        +  1 2
        +  2 6
        -  3 10
        """,
        names=["a", "b"],
    )
    states, out = frag.step(states, chunk)
    assert _rows(out) == [(0, 2, 12), (1, 3, 20)]


def test_filter_update_pair_degradation():
    # U- stays, U+ filtered out => U- becomes plain delete (ref filter.rs)
    schema = Schema.of(("a", DataType.INT64))
    filt = FilterExecutor(schema, col("a") < 10)
    frag = Fragment([filt])
    chunk = Chunk.from_pretty(
        """
        I
        U- 5
        U+ 15
        """,
        names=["a"],
    )
    _, out = frag.step(frag.init_states(), chunk)
    assert out.to_rows() == [(1, 5)]  # OP_DELETE

    chunk2 = Chunk.from_pretty(
        """
        I
        U- 15
        U+ 5
        """,
        names=["a"],
    )
    _, out2 = frag.step(frag.init_states(), chunk2)
    assert out2.to_rows() == [(0, 5)]  # OP_INSERT


def _agg_fragment(table_size=64, emit_capacity=8):
    schema = Schema.of(("g", DataType.INT64), ("v", DataType.INT64))
    agg = HashAggExecutor(
        schema,
        group_by=[("g", col("g"))],
        aggs=[count_star(), AggCall("sum", col("v"), "s")],
        table_size=table_size,
        emit_capacity=emit_capacity,
    )
    return Fragment([agg]), agg


def test_hash_agg_insert_then_update(accel_tuned):
    frag, agg = _agg_fragment()
    states = frag.init_states()
    states, _ = frag.step(states, Chunk.from_pretty(
        """
        I I
        + 1 10
        + 1 5
        + 2 7
        """,
    names=["g", "v"],
    ))
    states, outs = frag.flush(states, 1)
    assert len(outs) == 1
    assert _rows(outs[0]) == [(0, 1, 2, 15), (0, 2, 1, 7)]

    # second epoch: one more row for group 1 -> U-/U+ pair; group 2 silent
    states, _ = frag.step(states, Chunk.from_pretty(
        """
        I I
        + 1 1
        """,
    names=["g", "v"],
    ))
    states, outs = frag.flush(states, 2)
    rows = outs[0].to_rows()
    assert rows == [(2, 1, 2, 15), (3, 1, 3, 16)]  # U- old, U+ new


def test_hash_agg_retraction_to_empty(accel_tuned):
    frag, agg = _agg_fragment()
    states = frag.init_states()
    states, _ = frag.step(states, Chunk.from_pretty(
        """
        I I
        + 1 10
        """,
    names=["g", "v"],
    ))
    states, outs = frag.flush(states, 1)
    assert outs[0].to_rows() == [(0, 1, 1, 10)]
    states, _ = frag.step(states, Chunk.from_pretty(
        """
        I I
        - 1 10
        """,
    names=["g", "v"],
    ))
    states, outs = frag.flush(states, 2)
    assert outs[0].to_rows() == [(1, 1, 1, 10)]  # Delete of the old row

    # re-insert => plain Insert again (emitted flag was cleared)
    states, _ = frag.step(states, Chunk.from_pretty(
        """
        I I
        + 1 3
        """,
    names=["g", "v"],
    ))
    states, outs = frag.flush(states, 3)
    assert outs[0].to_rows() == [(0, 1, 1, 3)]


def test_hash_agg_emit_overflow_drains(accel_tuned):
    # 12 dirty groups, emit capacity 8 -> runtime drains in 2 flushes
    frag, agg = _agg_fragment(table_size=64, emit_capacity=8)
    states = frag.init_states()
    arrays = [np.arange(12, dtype=np.int64), np.ones(12, np.int64)]
    schema = Schema.of(("g", DataType.INT64), ("v", DataType.INT64))
    states, _ = frag.step(states, Chunk.from_numpy(schema, arrays))
    states, outs = frag.flush(states, 1)
    n1 = sum(len(o.to_rows()) for o in outs)
    assert n1 == 8
    assert int(agg.pending_dirty(states[0])) == 4
    states, outs2 = frag.flush(states, 1)
    assert sum(len(o.to_rows()) for o in outs2) == 4
    assert int(agg.pending_dirty(states[0])) == 0


def test_hash_agg_min_max_append_only(accel_tuned):
    schema = Schema.of(("g", DataType.INT64), ("v", DataType.INT64))
    agg = HashAggExecutor(
        schema,
        group_by=[("g", col("g"))],
        aggs=[AggCall("min", col("v"), "lo"), AggCall("max", col("v"), "hi")],
        table_size=64,
        emit_capacity=8,
    )
    frag = Fragment([agg])
    states = frag.init_states()
    states, _ = frag.step(states, Chunk.from_pretty(
        """
        I I
        + 1 5
        + 1 9
        + 1 2
        """,
    names=["g", "v"],
    ))
    states, outs = frag.flush(states, 1)
    assert outs[0].to_rows() == [(0, 1, 2, 9)]


def test_materialize_upsert():
    schema = Schema.of(("k", DataType.INT64), ("v", DataType.INT64))
    mv = MaterializeExecutor(schema, pk_indices=[0], table_size=64)
    frag = Fragment([mv])
    states = frag.init_states()
    states, _ = frag.step(states, Chunk.from_pretty(
        """
        I I
        + 1 10
        + 2 20
        """,
    names=["g", "v"],
    ))
    states, _ = frag.step(states, Chunk.from_pretty(
        """
        I I
        U- 1 10
        U+ 1 11
        -  2 20
        + 3 30
        """,
    names=["g", "v"],
    ))
    rows = sorted(mv.to_host(states[0]))
    assert rows == [(1, 11), (3, 30)]


def test_append_only_materialize_ring(accel_tuned):
    schema = Schema.of(("v", DataType.INT64))
    mv = AppendOnlyMaterialize(schema, ring_size=16)
    frag = Fragment([mv])
    states = frag.init_states()
    arrays = [np.arange(5, dtype=np.int64)]
    states, _ = frag.step(states, Chunk.from_numpy(schema, arrays, capacity=8))
    states, _ = frag.step(
        states, Chunk.from_numpy(schema, [np.arange(5, 10, dtype=np.int64)],
                                 capacity=8)
    )
    rows = mv.to_host(states[0])
    assert [r[0] for r in rows] == list(range(10))


def test_agg_into_materialize_chain(accel_tuned):
    """agg flush output flows through trailing materialize in one fragment."""
    schema = Schema.of(("g", DataType.INT64), ("v", DataType.INT64))
    agg = HashAggExecutor(
        schema, [("g", col("g"))], [count_star("n")],
        table_size=64, emit_capacity=8,
    )
    mv = MaterializeExecutor(agg.out_schema, pk_indices=[0], table_size=64)
    frag = Fragment([agg, mv])
    states = frag.init_states()
    states, _ = frag.step(states, Chunk.from_pretty(
        """
        I I
        + 1 0
        + 1 0
        + 2 0
        """,
    names=["g", "v"],
    ))
    states, _ = frag.flush(states, 1)
    assert sorted(mv.to_host(states[1])) == [(1, 2), (2, 1)]
    states, _ = frag.step(states, Chunk.from_pretty(
        """
        I I
        - 1 0
        """,
    names=["g", "v"],
    ))
    states, _ = frag.flush(states, 2)
    assert sorted(mv.to_host(states[1])) == [(1, 1), (2, 1)]


def test_changelog_executor():
    from risingwave_tpu.stream.executor import ChangelogExecutor

    schema = Schema.of(("v", DataType.INT64))
    frag = Fragment([ChangelogExecutor(schema)])
    _, out = frag.step(frag.init_states(), Chunk.from_pretty("""
        I
        + 1
        - 2
        U- 3
        U+ 4
    """, names=["v"]))
    # every row becomes an Insert carrying its original op
    assert out.to_rows() == [(0, 1, 0), (0, 2, 1), (0, 3, 2), (0, 4, 3)]


def test_row_id_gen_executor():
    from risingwave_tpu.stream.executor import RowIdGenExecutor

    schema = Schema.of(("v", DataType.INT64))
    gen = RowIdGenExecutor(schema)
    frag = Fragment([gen])
    st = frag.init_states()
    st, out = frag.step(st, Chunk.from_pretty("""
        I
        + 10
        + 11
    """, names=["v"]))
    assert out.to_rows() == [(0, 10, 0), (0, 11, 1)]
    st, out = frag.step(st, Chunk.from_pretty("""
        I
        + 12
    """, names=["v"]))
    assert out.to_rows() == [(0, 12, 2)]  # counter persists


def test_run_chunks_multi_dispatch_equivalence():
    """run_chunks(n) (one fused dispatch) must advance state and source
    cursor exactly like n run_chunk() calls (the q1 host-overhead
    amortization must not change semantics)."""
    import numpy as np

    from risingwave_tpu.sql import Engine
    from risingwave_tpu.sql.planner import PlannerConfig

    def build():
        eng = Engine(PlannerConfig(
            chunk_capacity=128, agg_table_size=512,
            agg_emit_capacity=256, mv_table_size=512, mv_ring_size=2048,
        ))
        eng.execute(
            "CREATE SOURCE bid (auction BIGINT, bidder BIGINT, "
            "price BIGINT, date_time TIMESTAMP) "
            "WITH (connector='nexmark', nexmark.table='bid')"
        )
        eng.execute(
            "CREATE MATERIALIZED VIEW m AS "
            "SELECT auction, count(*) AS n, sum(price) AS s "
            "FROM bid GROUP BY auction"
        )
        return eng

    a = build()
    job_a = a.jobs[0]
    assert job_a._fused is not None  # nexmark is traceable
    for _ in range(8):
        job_a.run_chunk()
    job_a.inject_barrier()
    rows_a = sorted(map(tuple, a.execute("SELECT * FROM m")))
    off_a = job_a.source.offset

    b = build()
    job_b = b.jobs[0]
    got = job_b.run_chunks(8)
    assert got == 8 * 128
    job_b.inject_barrier()
    rows_b = sorted(map(tuple, b.execute("SELECT * FROM m")))
    assert job_b.source.offset == off_a
    assert rows_b == rows_a and len(rows_a) > 0


@pytest.mark.parametrize("n", [1, 7, 8192, 40960])
def test_cumsum_int64_from_limbs_matches_numpy(n):
    """``compact._cumsum_int64`` (the chip's 64-bit scan, built from
    uint32 limb scans) is exact modulo 2^64, negatives included."""
    from risingwave_tpu.common.compact import _cumsum_int64

    rng = np.random.default_rng(n)
    x = rng.integers(-2**62, 2**62, n, dtype=np.int64)
    np.testing.assert_array_equal(
        np.asarray(_cumsum_int64(jnp.asarray(x))), np.cumsum(x))
    y = rng.integers(0, 2**64 - 1, n, dtype=np.uint64)
    np.testing.assert_array_equal(
        np.asarray(_cumsum_int64(jnp.asarray(y))), np.cumsum(y))


# -- the chip branch of HashAggExecutor.apply: representatives in tiles --

_GV = Schema.of(("g", DataType.INT64), ("v", DataType.INT64))


def _apply_on(accel_branch, chip, make_agg, chunks):
    """The state after ``chunks`` through the CPU branch (per-row
    probes) or the chip branch (sort, then representatives a tile at a
    time) of a fresh aggregate."""
    accel_branch(chip)
    frag = Fragment([make_agg()])
    states = frag.init_states()
    for c in chunks:
        states, _ = frag.step(states, c)
    return states[0]


def _groups(st):
    """State by group key: slots may differ between the branches."""
    occ = np.asarray(st.table.occupied)
    keys = np.asarray(st.table.key_cols[0])[occ]
    cols = [np.asarray(p)[occ] for p in st.prims]
    cols += [np.asarray(st.row_count)[occ], np.asarray(st.dirty)[occ]]
    # materialised input: the (value, multiplicity) pairs a group holds
    inputs = []
    for mt, cnt in zip(st.minput_tables, st.minput_counts):
        held: dict = {}
        live = np.asarray(mt.occupied) & (np.asarray(cnt) > 0)
        for g, v, n in zip(np.asarray(mt.key_cols[0])[live],
                           np.asarray(mt.key_cols[-1])[live],
                           np.asarray(cnt)[live]):
            held.setdefault(int(g), []).append((int(v), int(n)))
        inputs.append(held)
    out = {}
    for i, k in enumerate(keys):
        out[int(k)] = tuple(c[i].item() for c in cols) \
            + tuple(tuple(sorted(h.get(int(k), ()))) for h in inputs)
    assert len(out) == len(keys)
    return out


def _gv_chunk(g, v, cap, ops=None, valid=None):
    c = Chunk.from_numpy(_GV, [np.asarray(g, np.int64),
                               np.asarray(v, np.int64)],
                         ops=ops, capacity=cap)
    if valid is not None:
        full = np.zeros(cap, np.bool_)
        full[:len(valid)] = valid
        c = Chunk(c.columns, c.ops, jnp.asarray(full), c.schema)
    return c


def _four_aggs(table_size=4096, **kw):
    return lambda: HashAggExecutor(
        _GV, group_by=[("g", col("g"))],
        aggs=[count_star(), AggCall("sum", col("v"), "s"),
              AggCall("min", col("v"), "lo"), AggCall("max", col("v"), "hi")],
        table_size=table_size, emit_capacity=8, **kw)


def _tile_cases():
    from risingwave_tpu.stream.hash_agg import REP_TILE as K
    return K, 4 * K


@pytest.mark.parametrize("case", ["one", "k_minus_1", "k", "k_plus_1",
                                  "three_k_plus_7", "full_chunk"])
def test_hash_agg_rep_tiles_match_cpu_branch(accel_branch, case):
    """Same chunks, both branches, same state by group key; the chip
    branch's tallies say how many representatives and tiles it took."""
    K, cap = _tile_cases()
    n = {"one": 1, "k_minus_1": K - 1, "k": K, "k_plus_1": K + 1,
         "three_k_plus_7": 3 * K + 7, "full_chunk": cap}[case]
    rng = np.random.default_rng(n)
    # every one of n keys at least once, the rest of the chunk at random
    g1 = np.concatenate([np.arange(n), rng.integers(0, n, cap - n)])
    rng.shuffle(g1)
    # second chunk: old and new keys, some rows invalid
    g2 = rng.integers(n // 2, n // 2 + n, cap)
    valid2 = rng.random(cap) < 0.8
    chunks = [
        _gv_chunk(g1 * 7919, rng.integers(-50, 50, cap), cap),
        _gv_chunk(g2 * 7919, rng.integers(-50, 50, cap), cap, valid=valid2),
    ]
    cpu = _apply_on(accel_branch, False, _four_aggs(), chunks)
    chip = _apply_on(accel_branch, True, _four_aggs(), chunks)
    assert _groups(chip) == _groups(cpu)
    assert len(_groups(chip)) == len(set(g1) | set(g2[valid2]))
    assert int(chip.overflow) == int(cpu.overflow) == 0
    reps = [n, len(set(g2[valid2]))]
    assert int(chip.apply_chunks) == int(cpu.apply_chunks) == 2
    assert int(chip.rep_rows) == sum(reps)
    assert int(chip.rep_tiles) == sum(-(-r // K) for r in reps)
    assert int(cpu.rep_rows) == int(cpu.rep_tiles) == 0


def test_hash_agg_rep_tiles_all_invalid_chunk(accel_branch):
    """No representative, zero tiles: nothing touches the table."""
    _, cap = _tile_cases()
    chunk = _gv_chunk(np.arange(cap), np.ones(cap), cap,
                      valid=np.zeros(cap, np.bool_))
    for chip in (False, True):
        st = _apply_on(accel_branch, chip, _four_aggs(), [chunk])
        assert _groups(st) == {}
        assert (int(st.apply_chunks), int(st.rep_rows),
                int(st.rep_tiles)) == (1, 0, 0)


@pytest.mark.parametrize("interleaved", [False, True])
def test_hash_agg_rep_tiles_equal_hash_across_tile_edge(
        monkeypatch, accel_branch, interleaved):
    """Two distinct keys with one 64-bit hash, the last representative
    of one tile and the first of the next: they stay two groups, and the
    later tile probes past the earlier one's insert.  Interleaved rows
    split each key into several segments, so one key has representatives
    in both tiles."""
    from risingwave_tpu.stream import hash_agg

    K, cap = _tile_cases()
    monkeypatch.setattr(
        hash_agg, "hash64_columns",
        lambda cols, seed=0: (cols[0] // 2 + 1).astype(jnp.uint64))
    # keys 1..2K: key 1 alone, then pairs (2j, 2j+1) of equal hash at
    # sorted representative positions 2j-1 and 2j: (K, K+1) straddles
    keys = np.arange(1, 2 * K + 1)
    g = np.concatenate([keys, np.tile([K, K + 1], (cap - 2 * K) // 2)]
                       if interleaved else
                       [keys, np.repeat([K, K + 1], (cap - 2 * K) // 2)])
    v = np.arange(cap) % 97
    chunks = [_gv_chunk(g, v, cap), _gv_chunk(g[::-1], v, cap)]
    cpu = _apply_on(accel_branch, False, _four_aggs(), chunks)
    chip = _apply_on(accel_branch, True, _four_aggs(), chunks)
    got = _groups(chip)
    assert got == _groups(cpu) and len(got) == 2 * K
    assert got[K][0] == got[K + 1][0] == 2 * (1 + (cap - 2 * K) // 2)
    assert int(chip.rep_tiles) >= 4


@pytest.mark.parametrize("spill_ring", [0, 1024])
def test_hash_agg_rep_tiles_later_tile_overflows(accel_branch, spill_ring):
    """A table of 2K slots and 3K+7 one-row groups: the later tiles'
    representatives find it full.  Which groups lose differs between
    the branches; how many, and what becomes of their rows, does not."""
    K, cap = _tile_cases()
    n = 3 * K + 7
    g = np.arange(n) * 7919
    chunk = _gv_chunk(g, np.ones(n), cap)
    make = _four_aggs(table_size=2 * K, spill_ring=spill_ring)
    cpu = _apply_on(accel_branch, False, make, [chunk])
    chip = _apply_on(accel_branch, True, make, [chunk])
    lost = n - 2 * K
    for st in (cpu, chip):
        kept = set(_groups(st))
        assert len(kept) == 2 * K
        if spill_ring:
            assert int(st.overflow) == 0
            assert int(st.spill_count) == lost
            spilled = np.asarray(st.spill_rows[0])[:lost]
            assert kept.isdisjoint(spilled.tolist())
            assert kept | set(spilled.tolist()) == set(g.tolist())
        else:
            assert int(st.overflow) == lost
    assert int(chip.rep_tiles) == 4


def test_hash_agg_rep_tiles_retractable_minmax_and_filter(accel_branch):
    """Materialized-input min/max (a slot for every ROW, read from the
    row's representative) and a FILTERed sum, over 3K+7 groups with
    deletes in the second chunk."""
    K, cap = _tile_cases()
    n = 3 * K + 7
    rng = np.random.default_rng(27)
    g = np.concatenate([np.arange(n), rng.integers(0, n, cap - n)])
    v = rng.integers(0, 40, cap)
    # second chunk: retract a third of the first's rows, insert others
    gone = rng.random(cap) < 0.33
    g2 = np.where(gone, g, rng.integers(0, n + K, cap))
    v2 = np.where(gone, v, rng.integers(0, 40, cap))
    ops2 = np.where(gone, 1, 0).astype(np.int8)

    def make():
        return HashAggExecutor(
            _GV, group_by=[("g", col("g"))],
            aggs=[AggCall("min", col("v"), "lo"),
                  AggCall("max", col("v"), "hi"),
                  AggCall("sum", col("v"), "s", filter=col("v") > 20),
                  count_star()],
            table_size=4096, emit_capacity=8, retractable_input=True,
            minput_table_size=8192)

    chunks = [_gv_chunk(g * 7919, v, cap),
              _gv_chunk(g2 * 7919, v2, cap, ops=ops2)]
    cpu = _apply_on(accel_branch, False, make, chunks)
    chip = _apply_on(accel_branch, True, make, chunks)
    assert _groups(chip) == _groups(cpu)
    assert int(chip.overflow) == int(cpu.overflow) == 0
    assert int(chip.inconsistency) == int(cpu.inconsistency) == 0
    assert any(len(b[-1]) > 1 for b in _groups(chip).values())
