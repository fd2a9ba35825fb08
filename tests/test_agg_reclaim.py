"""The aggregate's reclaim (``HashTable.reclaimed`` under
``HashAggExecutor.maybe_rehash``): tombstones are given back every
maintenance barrier by reinserting only the groups whose probe chains
crossed one (PERF.md §6, PR 30).

- the reclaim alone against a dict, over rounds of inserts and retires;
- the q5-inner view (``HOP`` 2 s / 10 s by auction) through ``Engine``
  against the benchmark's plain reference on seeded data, in a table
  small enough that the reclaim runs every barrier: ``count(*)``, a
  retractable ``max`` over those counts, a ``DISTINCT`` count;
- a one-group-a-window table (q7-inner's shape), where it moves nothing.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from risingwave_tpu.common.config import RwConfig
from risingwave_tpu.sql import Engine
from risingwave_tpu.state.hash_table import HashTable

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                "benchmark", "reference"))
import nexmark_numpy as ref  # noqa: E402

W = 32  # keys handed to the table at a time


def _keys(ks):
    """Batches of ``W``: (the keys, two key columns, validity)."""
    for i in range(0, len(ks), W):
        part = np.asarray(ks[i:i + W], np.int64)
        pad = np.concatenate([part, np.zeros(W - len(part), np.int64)])
        yield (part, jnp.asarray(pad // 3), jnp.asarray(pad * 7919),
               jnp.asarray(np.arange(W) < len(part)))


@pytest.mark.parametrize("size,load", [(16, 0.9), (16, 1.0), (64, 0.9),
                                       (1024, 0.7), (1024, 0.9)])
def test_reclaim_against_a_dict(size, load, accel_tuned):
    """Random insert / retire rounds: after every reclaim each live key
    is found with its state, no retired key is found, no tombstone is
    left, fill is the live count and every empty slot holds its fill
    value.  ``load`` 1.0 fills the 16 slots to the last one, so a pass
    meets a table with no empty slot to count from."""
    rng = np.random.default_rng(size * 10 + int(load * 10))
    insert = jax.jit(lambda t, a, b, v: t.lookup_or_insert([a, b], v))
    lookup = jax.jit(lambda t, a, b, v: t.lookup([a, b], v))
    reclaim = jax.jit(
        lambda t, x, y, z: t.reclaimed((x, (y, z)), (0, 0, 7)))
    passes = 0
    for trial in range(4):
        t = HashTable.create([jnp.zeros((1,), jnp.int64)] * 2, size)
        val = jnp.zeros((size,), jnp.int64)
        wide = jnp.zeros((size, 3), jnp.int32)
        flag = jnp.full((size,), 7, jnp.int8)
        held: dict[int, int] = {}
        nxt = 1
        for rnd in range(6):
            room = int(size * load) - len(held)
            m = room if rnd == 0 else int(rng.integers(0, room + 1))
            fresh = np.arange(nxt, nxt + m)
            nxt += m
            for part, a, b, valid in _keys(fresh):
                t, slots, _, over = insert(t, a, b, valid)
                assert not bool((over & valid).any())
                slots = slots[:len(part)]
                val = val.at[slots].set(jnp.asarray(part + 100))
                wide = wide.at[slots].set(
                    jnp.stack([jnp.asarray(part, jnp.int32)] * 3, 1))
                flag = flag.at[slots].set(jnp.int8(1))
                held.update((int(k), int(k) + 100) for k in part)
            retired = [k for k in list(held) if rng.random() < 0.4]
            for part, a, b, valid in _keys(retired):
                slots, found = lookup(t, a, b, valid)
                assert bool(found[:len(part)].all())
                t = t.clear_slots(slots, found)
                for k in part:
                    del held[int(k)]
            t, (val, (wide, flag)), lost = reclaim(t, val, wide, flag)
            passes += 1
            assert int(lost) == 0
            assert int(t.tombstone_count()) == 0
            assert int(t.count()) == len(held)
            for part, a, b, valid in _keys(sorted(held)):
                slots, found = lookup(t, a, b, valid)
                slots = np.asarray(slots[:len(part)])
                assert bool(found[:len(part)].all()), (trial, rnd)
                assert (np.asarray(val)[slots] == part + 100).all()
                assert (np.asarray(wide)[slots, 1] == part).all()
                assert (np.asarray(flag)[slots] == 1).all()
            for part, a, b, valid in _keys(retired):
                assert not bool(lookup(t, a, b, valid)[1].any())
            free = ~np.asarray(t.occupied)
            assert (np.asarray(val)[free] == 0).all()
            assert (np.asarray(wide)[free] == 0).all()
            assert (np.asarray(flag)[free] == 7).all()
    assert passes == 24


# ---------------------------------------------------------------------------
# the q5-inner view through the engine, against the plain reference

RATE = 20_000          # events/s: 18,400 bids/s
CHUNK, CHUNKS, BARRIERS = 1024, 18, 24   # ~1 s of event time a barrier
SEED = 0               # NexmarkConfig's default

SOURCE = f"""
    CREATE SOURCE bid (auction BIGINT, bidder BIGINT, price BIGINT,
        channel VARCHAR, url VARCHAR, date_time TIMESTAMP,
        WATERMARK FOR date_time AS date_time - INTERVAL '4' SECOND)
    WITH (connector = 'nexmark', nexmark.table = 'bid',
          nexmark.event.rate = '{RATE}')"""
HOP = ("HOP(bid, date_time, INTERVAL '2' SECOND, INTERVAL '10' SECOND) "
       "GROUP BY auction, window_start")
VIEWS = {
    "count": ("SELECT auction, window_start, count(*) AS n FROM " + HOP),
    # the outer aggregate takes the inner one's retractions: max over a
    # retractable input, ~11 counts a group in its materialised input
    "retractable_max": (
        "SELECT window_start, lane, max(n) AS n FROM (SELECT auction % 128 "
        "AS lane, window_start, count(*) AS n FROM " + HOP + ") GROUP BY "
        "window_start, lane"),
    "distinct": ("SELECT auction, window_start, count(DISTINCT price % 5) "
                 "AS n FROM " + HOP),
}


def _want(kind: str, rows: int) -> dict:
    """The view's closed windows by the plain reference's generator."""
    cols = ref.gen_columns("bid", rows, ["auction", "price", "date_time"],
                           RATE, SEED)
    if kind == "distinct":
        # the distinct (auction, window, price % 5) triples, counted
        # window by window
        pane = cols["date_time"] // 2_000_000
        seen = set()
        for k in range(5):
            seen |= set(zip(cols["auction"].tolist(),
                            ((pane - k) * 2_000_000).tolist(),
                            (cols["price"] % 5).tolist()))
        out: dict = {}
        for a, w, _ in seen:
            out[(a, w)] = out.get((a, w), 0) + 1
    else:
        q5 = ref.q5_rows(cols["auction"], cols["date_time"])
        out = dict(zip(zip(q5["auction"].tolist(),
                           q5["window_start"].tolist()),
                       q5["bids"].tolist()))
        if kind == "retractable_max":
            top: dict = {}
            for (a, w), n in out.items():
                top[(a % 128, w)] = max(top.get((a % 128, w), 0), n)
            out = top
    closed = cols["date_time"].max() - 4_000_000 - 10_000_000
    return {k: n for k, n in out.items() if k[-1] <= closed}


def _agg_states(states) -> list:
    from risingwave_tpu.stream.hash_agg import AggState
    return [st for st in jax.tree.leaves(
        states, is_leaf=lambda x: isinstance(x, AggState))
        if isinstance(st, AggState)]


@pytest.mark.parametrize("kind", sorted(VIEWS))
def test_q5_inner_view_equals_reference_while_reclaiming(kind):
    """~7,100 groups live in 16,384 slots and ~900 retire a barrier, so
    the reclaim runs at every barrier past window + lag; the closed
    windows still equal the reference's, row for row."""
    eng = Engine(RwConfig.from_dict({
        "streaming": {"chunk_size": CHUNK},
        "state": {"agg_table_size": 16384, "agg_emit_capacity": 1024,
                  "mv_table_size": 65536, "distinct_table_size": 65536},
    }))
    eng.execute(SOURCE)
    eng.execute(f"CREATE MATERIALIZED VIEW v AS {VIEWS[kind]}")
    eng.tick(barriers=BARRIERS, chunks_per_barrier=CHUNKS)
    rows = eng.execute("SELECT * FROM v")
    want = _want(kind, CHUNK * CHUNKS * BARRIERS)
    closed = max(k[-1] for k in want)
    if kind == "retractable_max":  # (window_start, lane, n)
        rows = [(r[1], r[0], r[2]) for r in rows]
    got = {(int(k), int(w)): int(n) for k, w, n in rows if int(w) <= closed}
    assert len(want) > 1000
    assert got == want
    aggs = _agg_states(eng.jobs[0].states)
    passes = max(int(st.reclaim_passes) for st in aggs)
    assert passes >= 10, passes
    assert sum(int(st.reclaim_slots) for st in aggs) > 10 * 100
    assert all(int(st.table.tombstone_count()) == 0 for st in aggs)
    assert all(int(st.overflow) == 0 for st in aggs)
    if kind == "distinct":
        assert all(int(dt.tombstone_count()) == 0
                   for st in aggs for dt in st.distinct_tables)


def test_one_group_a_window_table_is_left_alone():
    """q7-inner's shape: a tumbling window is one group, one retires
    every ten seconds.  A barrier that retires nothing takes no pass,
    and the pass that frees the one tombstone moves no group."""
    eng = Engine(RwConfig.from_dict({
        "streaming": {"chunk_size": CHUNK},
        "state": {"agg_table_size": 4096, "mv_table_size": 4096},
    }))
    eng.execute(SOURCE)
    eng.execute(
        "CREATE MATERIALIZED VIEW v AS SELECT window_start, max(price) AS "
        "hi, count(*) AS n FROM TUMBLE(bid, date_time, INTERVAL '10' "
        "SECOND) GROUP BY window_start")
    job = eng.jobs[0]

    def agg():
        return _agg_states(job.states)[0]

    def slots():
        st = agg()
        occ = np.asarray(st.table.occupied)
        return dict(zip(np.asarray(st.table.key_cols[0])[occ].tolist(),
                        np.flatnonzero(occ).tolist()))

    seen = slots()
    passes = []
    for _ in range(36):
        eng.tick(barriers=1, chunks_per_barrier=CHUNKS)
        now = slots()
        # a group never changes its slot while it lives
        assert all(seen[k] == s for k, s in now.items() if k in seen)
        seen.update(now)
        passes.append(int(agg().reclaim_passes))
    retired = len(seen) - len(now)
    assert retired >= 2
    assert passes[-1] == int(agg().reclaim_slots) == retired
    assert sum(b > a for a, b in zip(passes, passes[1:])) == retired
    assert int(agg().table.tombstone_count()) == 0
