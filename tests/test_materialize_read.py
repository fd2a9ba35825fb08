"""The readback of a pk-keyed view (``MaterializeExecutor.fetch`` /
``to_host``): the blocks that hold a row are gathered on the device and
those cross, and the rows are the ones a plain numpy read of the whole
table gives, in the same order."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from risingwave_tpu.common.chunk import (
    Chunk,
    NCol,
    OP_DELETE,
    OP_INSERT,
    StrCol,
    apply_null_mask,
    decode_strings,
    encode_strings,
    split_col,
)
from risingwave_tpu.common.types import DataType, Field, Schema
from risingwave_tpu.state.hash_table import HashTable
from risingwave_tpu.stream import materialize
from risingwave_tpu.stream.materialize import (
    READ_BLOCK,
    MaterializeExecutor,
    MvState,
)

#: int64 pk, a nullable int64, a string, a numeric
SCHEMA = Schema((
    Field("k", DataType.INT64),
    Field("n", DataType.INT64, nullable=True),
    Field("s", DataType.VARCHAR, str_width=12),
    Field("d", DataType.DECIMAL, decimal_scale=2),
))
#: 256 blocks of 512 slots: the gather brings back up to 4
SIZE = 1 << 17
CAP = SIZE // READ_BLOCK // 64
#: for the cases that run ``apply``: 4 blocks, capacity 1
SMALL = 2048


def plain_rows(mv, state) -> list[tuple]:
    """Host copies of every column, masked by ``occupied``."""
    occ = np.asarray(state.table.occupied).reshape(-1)
    cols = []
    for f, store in zip(mv.in_schema, state.values):
        store, null = split_col(store)
        if isinstance(store, StrCol):
            data = np.asarray(store.data)
            out = decode_strings(
                data.reshape(-1, data.shape[-1])[occ],
                np.asarray(store.lens).reshape(-1)[occ])
        else:
            out = np.asarray(store).reshape(-1)[occ]
            if f.data_type.value == "numeric":
                out = out.astype(np.float64) / 10**f.decimal_scale
        if null is not None:
            out = apply_null_mask(out, np.asarray(null).reshape(-1)[occ])
        cols.append(out)
    return [tuple(c[i] for c in cols) for i in range(int(occ.sum()))]


def placed(mv, slots) -> MvState:
    """A state with one row in each of ``slots``, made from the slot."""
    state = mv.init_state()
    at = jnp.asarray(np.asarray(slots, np.int32))
    k = np.asarray(slots, np.int64)
    data, lens = encode_strings([f"row{i}" for i in slots], 12)
    n, s, d = state.values[1], state.values[2], state.values[3]
    values = (
        state.values[0].at[at].set(k),
        NCol(n.data.at[at].set(k * 3), n.null.at[at].set(k % 3 == 0)),
        StrCol(s.data.at[at].set(data), s.lens.at[at].set(lens)),
        d.at[at].set(k * 7 - 5),
    )
    table = HashTable(
        state.table.key_cols, state.table.occupied.at[at].set(True),
        state.table.tombstone, state.table.size)
    return MvState(table, values, state.overflow)


def applied(mv, steps) -> MvState:
    """``steps`` of ``(op, keys)`` through ``apply`` and ``maybe_rehash``."""
    step = jax.jit(lambda st, ch: mv.maybe_rehash(mv.apply(st, ch)[0]))
    state = mv.init_state()
    for op, keys in steps:
        k = np.asarray(keys, np.int64)
        n = np.asarray([None if x % 3 == 0 else int(x) * 3 for x in k],
                       object)
        state = step(state, Chunk.from_numpy(
            SCHEMA,
            [k, n, np.asarray([f"r{x}" for x in k], object), k / 4],
            ops=np.full(len(k), op, np.int8), capacity=1024))
    return state


def in_blocks(blocks, per_block=3):
    return [b * READ_BLOCK + j * 97 for b in blocks for j in range(per_block)]


CASES = {
    # name: (table size, state builder, path, rows)
    "empty": (SIZE, lambda mv: placed(mv, []), "gathered", 0),
    "one_row": (SIZE, lambda mv: placed(mv, [70_001]), "gathered", 1),
    "first_and_last_slot": (
        SIZE, lambda mv: placed(mv, [0, SIZE - 1]), "gathered", 2),
    "exactly_cap_blocks": (
        SIZE, lambda mv: placed(mv, in_blocks([3, 77, 200, 255])),
        "gathered", 12),
    "one_block_more": (
        SIZE, lambda mv: placed(mv, in_blocks([3, 77, 130, 200, 255])),
        "whole", 15),
    "full_block": (
        SIZE, lambda mv: placed(mv, range(5 * READ_BLOCK, 6 * READ_BLOCK)),
        "gathered", READ_BLOCK),
    "table_of_one_block": (
        64, lambda mv: placed(mv, [1, 5, 63]), "gathered", 3),
    "after_deletes": (
        SMALL, lambda mv: applied(mv, [
            (OP_INSERT, range(40)), (OP_DELETE, range(0, 40, 2)),
            (OP_INSERT, [4, 8])]), "whole", 22),
    "after_rehash": (
        SMALL, lambda mv: applied(mv, [
            (OP_INSERT, range(700)), (OP_DELETE, range(5, 700))]),
        "gathered", 5),
}


@pytest.mark.parametrize("case", CASES)
def test_to_host_equals_a_plain_read(case, accel_tuned):
    size, build, path, n_rows = CASES[case]
    mv = MaterializeExecutor(SCHEMA, pk_indices=[0], table_size=size)
    state = build(mv)
    if case == "after_rehash":
        assert int(state.table.tombstone_count()) == 0
    want = plain_rows(mv, state)
    assert len(want) == n_rows
    occ, values, moved = mv.fetch(state)
    assert mv.rows(occ, values) == want == mv.to_host(state)
    if case != "after_rehash":  # five keys may hash into two blocks
        assert moved["path"] == path
    table_bytes = sum(
        x.nbytes for x in jax.tree.leaves((state.table.occupied,
                                           state.values)))
    if moved["path"] == "gathered":
        assert moved["blocks"] <= max(1, size // READ_BLOCK // 64)
        if size == SIZE:
            assert 0 < moved["bytes"] < table_bytes / 32
    else:
        # and the windows that were fetched in vain
        assert table_bytes <= moved["bytes"]
        if size == SIZE:
            assert moved["bytes"] < table_bytes * (1 + 1 / 32)


def test_a_state_of_host_arrays_dispatches_nothing(monkeypatch):
    """A loaded checkpoint (time travel, ``export_mv_sst``) is numpy: it
    is cut where it is, and stacked by shard just the same."""
    mv = MaterializeExecutor(SCHEMA, pk_indices=[0], table_size=SIZE)
    state = placed(mv, in_blocks([1, 9]))
    want = plain_rows(mv, state)
    host = jax.device_get(state)

    def refuse(*a, **kw):
        raise AssertionError("a device program was asked for")

    monkeypatch.setattr(materialize, "_live_blocks_fn", refuse)
    occ, values, moved = mv.fetch(host)
    assert mv.rows(occ, values) == want
    assert moved == {"path": "host", "bytes": 0, "blocks": 0}
    two = jax.tree.map(lambda x: np.stack([x, x]), host)
    assert mv.to_host(two) == want + want


def test_stacked_state_reads_shard_after_shard():
    """A mesh view's leaves carry a leading shard axis: one program
    over it, and the whole path as soon as one shard is over capacity."""
    mv = MaterializeExecutor(SCHEMA, pk_indices=[0], table_size=SIZE)
    shards = [placed(mv, in_blocks([2, 250])), placed(mv, []),
              placed(mv, in_blocks([0, 1, 2, 3]))]
    want = [r for st in shards for r in plain_rows(mv, st)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *shards)
    occ, values, moved = mv.fetch(stacked)
    assert mv.rows(occ, values) == want
    assert (moved["path"], moved["blocks"]) == ("gathered", 6)
    shards[1] = placed(mv, in_blocks(range(10, 15)))
    want = [r for st in shards for r in plain_rows(mv, st)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *shards)
    occ, values, moved = mv.fetch(stacked)
    assert mv.rows(occ, values) == want
    assert moved["path"] == "whole"


def test_no_compile_after_the_first_read():
    """Which blocks are live is computed in the program, so a view's
    contents are no static bound: later reads, on either path, compile
    nothing."""
    import jax.monitoring

    mv = MaterializeExecutor(SCHEMA, pk_indices=[0], table_size=SIZE)
    mv.to_host(placed(mv, [9]))
    states = [placed(mv, slots) for slots in (
        [], [1, 2, 3], in_blocks([3, 77, 200, 255]),
        in_blocks(range(40)), [SIZE - 1])]
    compiled = []

    def on_duration(event, _secs, **kw):
        if event.endswith("backend_compile_duration"):
            compiled.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        paths = [mv.fetch(st)[2]["path"] for st in states]
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert paths == ["gathered"] * 3 + ["whole", "gathered"]
    assert compiled == []


def _moved(engine, job: str) -> dict:
    out = {"gathered": 0, "whole": 0}
    for (name, labels), c in engine.metrics._counters.items():
        if name == "mv_read_bytes_total" and ("job", job) in labels:
            out[dict(labels)["path"]] = c.value
    return out


def test_engine_counts_the_bytes_a_read_moved():
    """``mv_read_bytes_total{job,path}`` and the span's attrs: a
    few-row view moves under 1/32 of its table, a view over the
    program's capacity its table; both retire with the view."""
    from risingwave_tpu.common.trace import GLOBAL_TRACE
    from risingwave_tpu.sql.engine import Engine
    from risingwave_tpu.sql.planner import PlannerConfig

    eng = Engine(PlannerConfig(chunk_capacity=128, mv_table_size=SIZE))
    eng.execute("CREATE TABLE t (k BIGINT PRIMARY KEY, v BIGINT)")
    eng.execute("CREATE MATERIALIZED VIEW few AS "
                "SELECT k, sum(v) AS v FROM t GROUP BY k")
    eng.execute("INSERT INTO t VALUES (1, 10), (2, 20), (3, 30)")
    eng.execute("FLUSH")
    entry = eng.catalog.get("few")
    state = entry.job.states
    for i in entry.mv_state_index:
        state = state[i]
    table_bytes = sum(x.nbytes for x in jax.tree.leaves(
        (state.table.occupied, state.values)))

    def read():
        GLOBAL_TRACE.clear()
        with GLOBAL_TRACE.span("test", trace_id="test-1"):
            got = sorted(eng.execute("SELECT * FROM few"))
        span, = [s for s in GLOBAL_TRACE.dump()
                 if s["name"] == "_mv_rows.to_host"]
        return got, span["attrs"], _moved(eng, "few")

    got, attrs, moved = read()
    assert got == [(1, 10), (2, 20), (3, 30)]
    assert attrs["path"] == "gathered" and 1 <= attrs["blocks"] <= 3
    assert attrs["bytes"] == moved["gathered"]
    assert 0 < moved["gathered"] < table_bytes / 32
    assert moved["whole"] == 0
    # rows in more blocks than the program brings back: the table whole
    eng.execute("INSERT INTO t VALUES " + ", ".join(
        f"({k}, {k * 10})" for k in range(4, 60)))
    eng.execute("FLUSH")
    got, attrs, after = read()
    assert got == [(k, k * 10) for k in range(1, 60)]
    assert attrs["path"] == "whole" and attrs["blocks"] > CAP
    assert after["gathered"] == moved["gathered"]
    assert attrs["bytes"] == after["whole"] >= table_bytes
    eng.execute("DROP MATERIALIZED VIEW few")
    assert "mv_read_bytes_total" not in eng.metrics.render_prometheus()


def test_mesh_view_reads_the_same_rows():
    """``streaming_parallelism = 4`` on the CPU's virtual devices: the
    job's stacked state goes through one program, and the rows are those
    of a plain read of each shard's table, shard after shard."""
    from risingwave_tpu.sql.engine import Engine
    from risingwave_tpu.sql.planner import PlannerConfig
    from risingwave_tpu.stream.sharded import ShardedStreamingJob

    eng = Engine(PlannerConfig(
        chunk_capacity=128, agg_table_size=512, agg_emit_capacity=128,
        mv_table_size=1 << 15, mv_ring_size=1024,
    ))
    eng.execute(
        "CREATE SOURCE bid (auction BIGINT, price BIGINT, "
        "date_time TIMESTAMP) WITH (connector='nexmark', "
        "nexmark.table='bid')")
    eng.execute("SET streaming_parallelism = 4")
    eng.execute(
        "CREATE MATERIALIZED VIEW v AS SELECT auction, count(*) AS n, "
        "max(price) AS hi FROM bid GROUP BY auction")
    assert isinstance(eng.jobs[0], ShardedStreamingJob)
    eng.tick(barriers=2, chunks_per_barrier=2)
    entry = eng.catalog.get("v")
    state = eng.jobs[0].states
    for i in entry.mv_state_index:
        state = state[i]
    host = jax.device_get(state)
    want = [r for shard in range(4) for r in plain_rows(
        entry.mv_executor, jax.tree.map(lambda x: x[shard], host))]
    assert len(want) > 4
    assert eng._mv_rows(entry) == want
    assert sorted(eng.execute("SELECT auction, n, hi FROM v")) \
        == sorted(want)
    moved = _moved(eng, "v")
    assert moved["gathered"] + moved["whole"] > 0
