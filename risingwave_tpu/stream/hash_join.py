"""Streaming hash join — the full matrix: inner / left / right / full
outer / semi / anti — with device-resident two-sided state.

Reference counterpart: ``HashJoinExecutor`` (src/stream/src/executor/
hash_join.rs:158, 6 join types via const-generic ``JoinTypePrimitive``)
with ``JoinHashMap`` state+degree tables (join/hash_join.rs:169) and
the probe loop ``eq_join_oneside`` (hash_join.rs:949).

TPU-first design
----------------
Each side's state is a *bucketed multi-map* in HBM:

- ``key_table``: HashTable over the join key — one slot per distinct key;
- ``rows``:     per-column ``[size, bucket_cap]`` dense stores;
- ``occupied``: ``bool [size, bucket_cap]``;
- ``count``:    ``int32 [size]`` live rows per key.

A chunk applies as a handful of gathers/scatters over the whole chunk
(vs the reference's per-row HashMap + Vec walk): inserts claim free
bucket positions by rank-among-equal-keys, deletes match value-equal
entries by rank (row-hash disambiguated) and clear them.

**Degrees are per-KEY, not per-row** (unlike the reference's degree
table): a stored row's degree — its number of matches on the other
side — is fully determined by its join key, so the other side's
``count[slot]`` IS the degree.  Outer/semi/anti transitions fall out of
comparing a key's own-side count before/after a chunk: 0→n retracts the
NULL-padded (or emits the semi / retracts the anti) rows, n→0 restores
them.  No extra state.

**Emission is output-centric and windowed**: instead of materializing
the (probe-row × bucket-entry) grid and compacting it (O(cap×B) per
chunk), every output slot *gathers* its source via searchsorted over
per-row prefix sums — O(out_capacity) regardless of bucket depth.  One
logical emission space [pairs | self-rows | transition-rows] is cut
into fixed out_capacity windows; ``emit_window(pending, w)`` produces
window ``w``, so the runtime drains arbitrarily amplified joins without
dropping matches (``DagJob`` loops windows on device; the plain
``apply`` emits window 0 and counts the remainder as emit_overflow).

U-pair note: a key's transition emits UPDATE_DELETE/UPDATE_INSERT op
codes, but pads land in the transitions section rather than physically
adjacent to their replacement pair — every consumer in this codebase is
slot-keyed or sign-based, so only the op *codes* carry the pairing.

**Three stores for a side**, picked by the planner from what it sees of
the side's changelog (``sql/planner.py`` ``resolve_join``):

- *pool* (``PoolSideState``): an append-only side.  A ring of rows in
  arrival order behind one ``(key-hash, rank)`` tag table; no cap a key.
- *keyed* (``KeyedSideState``): a retractable side of an inner join whose
  join key is part of its stream key, so that many rows share a key
  (Nexmark q5: 110 k ``(auction, window)`` counts under seven window
  starts).  One row a slot of a ``HashTable`` over the stream key: a
  retraction finds its row in O(1) by what identifies it; nothing is
  ``[chunk, bucket]``.  The other side's chunk finds a key's rows in
  one of two ways.  With no residual, and ``SORTED_PROBE_MIN_ROWS``
  rows or more to probe (Nexmark q8's sellers), it sorts the slots once
  by join-key hash, O(size log size), and finds each probe row's run
  by a binary search, O(log size) a row; pair *j* of a row is the slot
  at its run's start + *j* (``_keyed_sorted_probe``; tally
  ``sorted_probe_chunks``).  A run groups the slots by 32 bits of the
  hash, so it may hold another key: the emission drops a pair whose
  keys differ.  Otherwise (a residual filters a key's run, or a chunk
  of few rows) it makes a masked pass over the slots a row (``key ==
  w``, then the residual), compacted into the emission windows: O(size)
  a row (Nexmark q5's max side).
- *dense* (``SideState``): every other retractable side, the
  ``[size, bucket_cap]`` buckets described above.

**A residual predicate** (a non-equality conjunct of an inner join's ON)
is applied where pairs are staged: a build row counts as a match only if
the predicate holds for (probe row, build row), so a change emits the
pairs that qualify before and after it and not the key's every row.
Dense and keyed build sides take it; behind a pool side it stays a
filter after the join.

State cleaning is per ROW: a side's ``clean`` rule names an event-time
expression of its rows, and a row retires once that value falls below
the watermark less a lag.  A window key that is part of the join key
(Nexmark q8) and a time band between the two sides (Nexmark q7:
``L.ts BETWEEN R.ts - c AND R.ts``) are both cases of it.  A pool side
is a RING in arrival order and retires the longest expired prefix of
it, a tile at a time, so the cost follows what was retired; a dense
side masks its buckets, a keyed side its slots.  ``reclaim`` then gives the tombstoned table
slots back (``HashTable.reclaimed`` / ``TagTable.reclaimed``).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import dataclasses

from risingwave_tpu.common.chunk import (
    Chunk,
    NCol,
    StrCol,
    conform_col,
    split_col,
)
from risingwave_tpu.common.hash import hash64_columns, key_words


def _null_stripped_keys(key_cols):
    """(bare key cols, any-key-null mask | None).

    SQL join equality: NULL matches nothing (unlike grouping equality),
    so rows with a NULL key are masked out of both updates and probes
    and the stored key columns stay bare arrays."""
    null_any = None
    bare = []
    for c in key_cols:
        d, n = split_col(c)
        bare.append(d)
        if n is not None:
            null_any = n if null_any is None else (null_any | n)
    return bare, null_any
from risingwave_tpu.common.compact import mask_indices
from risingwave_tpu.common.types import Field, Schema
from risingwave_tpu.expr.node import Expr, InputRef, NamedRef
from risingwave_tpu.state.hash_table import (
    TOMB_TAG,
    HashTable,
    TagTable,
    _scan_slots,
    _scatter_key,
    gather_key,
    keys_equal,
)


def _empty_store(f: Field, size: int, bucket: int):
    if f.data_type.is_string:
        col = StrCol(
            jnp.zeros((size, bucket, f.str_width), jnp.uint8),
            jnp.zeros((size, bucket), jnp.int32),
        )
    else:
        col = jnp.zeros((size, bucket), f.data_type.physical_dtype)
    if f.nullable:
        return NCol(col, jnp.zeros((size, bucket), jnp.bool_))
    return col


def _flat_store(f: Field, n: int):
    """Zeroed ``[n]`` storage for one column of a side's rows."""
    if f.data_type.is_string:
        col = StrCol(
            jnp.zeros((n, f.str_width), jnp.uint8),
            jnp.zeros((n,), jnp.int32),
        )
    else:
        col = jnp.zeros((n,), f.data_type.physical_dtype)
    if f.nullable:
        return NCol(col, jnp.zeros((n,), jnp.bool_))
    return col


def _input_refs(e) -> set:
    """Positions of the input columns an expression reads."""
    if isinstance(e, InputRef):
        return {e.index}
    out: set = set()
    if dataclasses.is_dataclass(e):
        for f in dataclasses.fields(e):
            v = getattr(e, f.name)
            for x in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(x, Expr):
                    out |= _input_refs(x)
    return out


def _pool_capacity(rows: tuple) -> int:
    """Row capacity of a pool side's flat stores (static shape)."""
    store = rows[0]
    while isinstance(store, NCol):
        store = store.data
    if isinstance(store, StrCol):
        return store.lens.shape[0]
    return store.shape[0]


def _gather_bucket(store, slots):
    """[size, B, ...] gathered at [cap] slots -> [cap, B, ...]."""
    if isinstance(store, NCol):
        return NCol(_gather_bucket(store.data, slots), store.null[slots])
    if isinstance(store, StrCol):
        return StrCol(store.data[slots], store.lens[slots])
    return store[slots]


def _scatter_rows(store, pos, col):
    """Write row values col[[cap]] at flat positions pos[[cap]] into the
    flattened [size*B, ...] view of the store."""
    if isinstance(store, NCol):
        null_flat = store.null.reshape(-1).at[pos].set(
            col.null, mode="drop"
        ).reshape(store.null.shape)
        return NCol(_scatter_rows(store.data, pos, col.data), null_flat)
    if isinstance(store, StrCol):
        flat_d = store.data.reshape((-1,) + store.data.shape[2:])
        flat_l = store.lens.reshape((-1,))
        flat_d = flat_d.at[pos].set(col.data, mode="drop")
        flat_l = flat_l.at[pos].set(col.lens, mode="drop")
        return StrCol(
            flat_d.reshape(store.data.shape), flat_l.reshape(store.lens.shape)
        )
    flat = store.reshape((-1,) + store.shape[2:])
    flat = flat.at[pos].set(col, mode="drop")
    return flat.reshape(store.shape)


def _rank_by(group: jnp.ndarray, active: jnp.ndarray) -> jnp.ndarray:
    """Stable rank of each active row among rows with equal ``group``."""
    rank, _, _ = _rank_by_sorted(group, active)
    return rank


def _rank_by_sorted(group: jnp.ndarray, active: jnp.ndarray):
    """``_rank_by`` that also returns its sort artifacts ``(rank,
    order, seg_id)`` so callers can derive further per-group reductions
    (``_totals_from_sort``) without paying a second argsort — the
    chunk-sized sort is a fixed per-chunk cost worth amortizing."""
    cap = group.shape[0]
    key = jnp.where(active, group, jnp.uint64(0xFFFFFFFFFFFFFFFF))
    order = jnp.argsort(key, stable=True)
    sorted_key = key[order]
    is_new = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), sorted_key[1:] != sorted_key[:-1]]
    )
    start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(is_new, jnp.arange(cap, dtype=jnp.int32), 0)
    )
    rank_sorted = jnp.arange(cap, dtype=jnp.int32) - start
    seg_id = jnp.cumsum(is_new) - 1
    rank = jnp.zeros((cap,), jnp.int32).at[order].set(rank_sorted)
    return rank, order, seg_id


def _totals_from_sort(order, seg_id, values) -> jnp.ndarray:
    """Per-row group total of ``values`` using a prior
    ``_rank_by_sorted`` decomposition (no second sort)."""
    cap = order.shape[0]
    sums = jax.ops.segment_sum(
        values[order].astype(jnp.int32), seg_id, num_segments=cap
    )
    totals_sorted = sums[seg_id]
    return jnp.zeros((cap,), jnp.int32).at[order].set(totals_sorted)


def _group_totals(group: jnp.ndarray, values: jnp.ndarray) -> jnp.ndarray:
    """Per-row sum of ``values`` over rows sharing the same ``group``."""
    cap = group.shape[0]
    order = jnp.argsort(group, stable=True)
    sorted_g = group[order]
    is_new = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), sorted_g[1:] != sorted_g[:-1]]
    )
    seg_id = jnp.cumsum(is_new) - 1
    sums = jax.ops.segment_sum(
        values[order].astype(jnp.int32), seg_id, num_segments=cap
    )
    totals_sorted = sums[seg_id]
    return jnp.zeros((cap,), jnp.int32).at[order].set(totals_sorted)


#: ring rows a pool side's ``clean`` looks at (and retires) at a time
CLEAN_TILE = 4096
#: probe rows from which a chunk sorts a keyed build side (no residual)
#: instead of passing over its slots once a row.  On the v5e at 2^20
#: slots the hash, sort and search of a chunk took 2.68 ms, the masked
#: pass 0.66 ms a matching row (its count, 44 us, and the emission's
#: search of it, 21 x 29.2 us) and 44 us a row that matches nothing:
#: even at 4.1 rows, rounded up for chunks whose rows do not all match
#: (PERF.md §6, the keyed side's sorted probe)
SORTED_PROBE_MIN_ROWS = 6
#: the sort key of an empty slot or a NULL key; a key's own is below it
_SORT_LAST = np.uint32(0xFFFFFFFF)


class JoinClean(NamedTuple):
    """A side's state-cleaning rule: a stored row is dead once
    ``expr(row) < watermark - lag_us``.  ``expr`` is an int64 event-time
    expression over the side's input schema.  The watermark is the minimum over the
    watermark filters found upstream of this side for ``src_col`` and of
    the other side for ``other_src_col`` (either may be None: not
    consulted)."""

    expr: Any
    lag_us: int
    src_col: "int | None"
    other_src_col: "int | None" = None


def _zero64():
    return jnp.zeros((), jnp.int64)


class SideState(NamedTuple):
    key_table: HashTable
    rows: tuple          # [size, B] stores, one per input column
    occupied: jnp.ndarray  # bool [size, B]
    count: jnp.ndarray     # int32 [size]
    overflow: jnp.ndarray  # int64 — rows that found no bucket space
    #: deletes with no matching stored row (ref consistency_error!)
    inconsistency: jnp.ndarray
    # -- tallies and levels (fragment.JOIN_TALLY_ATTRS / JOIN_GAUGE_ATTRS)
    insert_rows: jnp.ndarray
    delete_rows: jnp.ndarray
    probe_steps: jnp.ndarray
    emit_rows: jnp.ndarray
    cleaned_rows: jnp.ndarray
    reclaim_slots: jnp.ndarray
    live_rows: jnp.ndarray
    tombstones: jnp.ndarray
    table_slots: jnp.ndarray


class KeyedSideState(NamedTuple):
    """A retractable side stored by what identifies a row: ONE row a
    slot of a ``HashTable`` over the side's stream-key columns, the
    row's columns in ``[size]`` stores beside it.  A change finds its
    slot in one probe (the last change of a chunk to a slot decides what
    the slot holds); the other side finds a join key's rows by a masked
    pass over the slots (module docstring)."""

    table: HashTable       # over the stream-key columns
    rows: tuple            # [size] stores, one per input column
    overflow: jnp.ndarray  # int64 — rows the table was full for
    #: deletes of a row the side does not hold (ref consistency_error!)
    inconsistency: jnp.ndarray
    # -- tallies and levels (fragment.JOIN_TALLY_ATTRS / JOIN_GAUGE_ATTRS)
    insert_rows: jnp.ndarray
    delete_rows: jnp.ndarray
    probe_steps: jnp.ndarray
    emit_rows: jnp.ndarray
    cleaned_rows: jnp.ndarray
    reclaim_slots: jnp.ndarray
    live_rows: jnp.ndarray
    tombstones: jnp.ndarray
    table_slots: jnp.ndarray
    #: the update's probe rounds at the chunk's width and in straggler
    #: tiles (``fragment.PROBE_ROUND_ATTRS``)
    probe_wide_rounds: jnp.ndarray
    probe_tile_rounds: jnp.ndarray
    #: chunks of the other side that found their pairs here by a binary
    #: search of the slots sorted by join-key hash (``_keyed_sorted_probe``)
    sorted_probe_chunks: jnp.ndarray


class PoolSideState(NamedTuple):
    """Degree-adaptive side storage: ONE fused ``(key-hash, rank)``
    table over a shared row RING.

    The reference stores unbounded rows per key behind ``JoinHashMap``
    (src/stream/src/executor/join/hash_join.rs:169); dense
    ``[size, bucket_cap]`` buckets cap hot keys (nexmark's hot sellers)
    and waste HBM on cold ones.  TPU-first re-design: the rank-r row of
    key k owns the open-addressed entry for ``(hash(k), r)``, and the
    key's rank-0 entry doubles as its HEAD.  A key's live rows are the
    ranks ``[lo, hi)``: ``hi`` (ranks handed out) is ``count`` at the
    head slot, ``lo`` rides the head's ``pool_pos`` once the rank-0 row
    itself has retired (``pool + lo``; below ``pool`` it is that row's
    ring position and ``lo`` is 0).  Properties:

    - ONE ``lookup_or_insert`` per chunk: the fused two-phase probe
      (``TagTable.lookup_or_insert_ranked``) resolves head + target in
      a single loop;
    - no per-key cap: a hot key may fill the whole ring;
    - O(1) vectorized random access by (key, rank) — exactly what the
      output-centric windowed emission gathers — with no chain walks
      (pointer chasing is TPU-hostile);
    - rows take CONSECUTIVE ring positions in arrival order, so the
      row-store scatters hit a dense window, and the rows a watermark
      retires are a prefix of the ring (rows reach a side in event-time
      order up to the watermark's delay; a row is kept until every row
      before it has retired, which errs on the side of keeping).
      Within a key the prefix is its lowest live ranks, so ``lo``
      advances, the head survives its own row, and ranks stay
      contiguous; the ring's space is reused as its tail moves and
      nothing is ever compacted;
    - ``row_hash`` / ``row_rank`` / ``row_clean`` per ring row: what
      ``clean`` needs to find a retiring row's entries, and when.

    Append-only sides only (the bench/windowed-join shape): deletes
    would need value→rank search; a retractable side is a
    ``KeyedSideState`` or a dense ``SideState`` (module docstring).
    """

    table: TagTable        # packed (key-hash, rank) tags -> entry slot
    count: jnp.ndarray     # int32 [size] at a head: ranks handed out
    pool_pos: jnp.ndarray  # int32 [size] ring position | pool + lo
    rows: tuple            # [pool] ring stores, one per input column
    row_hash: jnp.ndarray  # uint64 [pool] join-key hash of a ring row
    row_rank: jnp.ndarray  # int32 [pool] its rank within its key
    row_clean: jnp.ndarray  # int64 [pool] its cleaning value
    head: jnp.ndarray      # int64 () rows ever appended
    tail: jnp.ndarray      # int64 () rows ever retired
    overflow: jnp.ndarray  # int64 — rows that found no table/ring space
    inconsistency: jnp.ndarray  # int64 — retractions on append-only side
    # -- tallies and levels (fragment.JOIN_TALLY_ATTRS / JOIN_GAUGE_ATTRS)
    insert_rows: jnp.ndarray
    probe_steps: jnp.ndarray
    emit_rows: jnp.ndarray
    cleaned_rows: jnp.ndarray
    reclaim_slots: jnp.ndarray
    live_rows: jnp.ndarray
    tombstones: jnp.ndarray
    table_slots: jnp.ndarray


def _pool_live(side: PoolSideState, slots):
    """(live rows, first live rank) of the keys whose HEAD entries sit
    at ``slots`` (clamped)."""
    pool = _pool_capacity(side.rows)
    lo = jnp.maximum(side.pool_pos[slots] - pool, 0)
    return side.count[slots] - lo, lo


class JoinState(NamedTuple):
    left: SideState
    right: SideState
    emit_overflow: jnp.ndarray  # int64 — matches dropped by out capacity
    # -- observability counters (device scalars; exported as Prometheus
    # -- gauges by Engine.collect_join_metrics, never read in the hot
    # -- loop) ---------------------------------------------------------
    chunks: jnp.ndarray        # int64 — probe chunks applied
    probe_iters: jnp.ndarray   # int64 — fused update-probe loop trips
    emit_rows: jnp.ndarray     # int64 — staged emission rows (all wins)
    emit_windows: jnp.ndarray  # int64 — emission windows drained


class JoinEmit(NamedTuple):
    """One chunk's staged emission space (all device arrays; light
    enough to ride a ``lax.while_loop`` carry).

    The logical emission array is ordered
    ``[up-transitions | pairs | self rows | down-transitions]`` —
    a key's first match retracts its pads BEFORE the replacement pairs
    land, and its last unmatch deletes the pairs BEFORE the pads
    return.  The order matters downstream: a projection may collapse a
    pad row and a pair row to identical values, and slot-keyed
    materialization resolves same-slot conflicts by LAST op in row
    order (the reference's U-pair adjacency contract, expressed as
    section order).  ``emit_window`` gathers any out_capacity-sized
    window of it.
    """

    probe_cols: tuple        # the probe chunk's columns
    signs: jnp.ndarray       # int32 [cap]
    slots: jnp.ndarray       # int32 [cap] clamped build-side key slots
    rank_to_idx: jnp.ndarray  # int32 [cap, B] k-th live row -> bucket idx
    #: probe rows' join-key hashes (pool build sides: the emission
    #: addresses build rows by (key-hash, rank) index lookups)
    probe_hash: jnp.ndarray  # uint64 [cap]
    m: jnp.ndarray           # int32 [cap] live build rows per probe row
    #: first live rank of each probe row's key on a pool build side (its
    #: lower ranks have retired); where its run starts in ``order`` on a
    #: keyed build side probed by sort; zeros otherwise
    base: jnp.ndarray        # int32 [cap]
    #: a keyed build side's slots sorted by join-key hash (``[size]``, or
    #: ``[1]`` where the plan has a residual), read where ``by_sort``
    order: jnp.ndarray       # int32
    by_sort: jnp.ndarray     # bool ()
    up_cnt: jnp.ndarray      # int32 [cap] up-transition rows per probe row
    up_end: jnp.ndarray      # int32 [cap] inclusive cumsum
    U: jnp.ndarray           # int32 total up-transition rows
    pair_end: jnp.ndarray    # int32 [cap] inclusive cumsum of pair counts
    P: jnp.ndarray           # int32 total pairs
    self_sel: jnp.ndarray    # int32 [cap] compacted self-row indices
    S: jnp.ndarray           # int32 total self rows
    down_cnt: jnp.ndarray    # int32 [cap] down-transition rows per row
    down_end: jnp.ndarray    # int32 [cap] inclusive cumsum
    total: jnp.ndarray       # int32 U + P + S + D


#: the join matrix (ref hash_join.rs JoinTypePrimitive + semi/anti)
JOIN_TYPES = (
    "inner", "left_outer", "right_outer", "full_outer",
    "left_semi", "left_anti", "right_semi", "right_anti",
)


class HashJoinExecutor:
    """Equi-join of two changelog streams (full join-type matrix).

    Not a linear-``Fragment`` executor: it has two inputs.  The DAG
    runtime calls ``apply(state, chunk, side)`` (single-window) or the
    windowed pair ``apply_begin`` / ``emit_window``.  Output schema is
    left ++ right columns (NULL-padded side nullable) for inner/outer,
    or the preserved side alone for semi/anti.
    """

    def __init__(
        self,
        left_schema: Schema,
        right_schema: Schema,
        left_keys: Sequence[Expr],
        right_keys: Sequence[Expr],
        table_size: int = 1 << 14,
        bucket_cap: int = 16,
        out_capacity: int = 16384,
        left_bucket_cap: int | None = None,
        right_bucket_cap: int | None = None,
        left_table_size: int | None = None,
        right_table_size: int | None = None,
        join_type: str = "inner",
        left_storage: str = "dense",
        right_storage: str = "dense",
        left_pool_size: int | None = None,
        right_pool_size: int | None = None,
        left_row_key: Sequence[int] | None = None,
        right_row_key: Sequence[int] | None = None,
    ):
        if join_type not in JOIN_TYPES:
            raise ValueError(f"unknown join type {join_type!r}")
        self.join_type = join_type
        self.left_schema = left_schema
        self.right_schema = right_schema
        self.left_keys = tuple(left_keys)
        self.right_keys = tuple(right_keys)
        self.table_size = table_size
        # per-side bucket depth: size for the max rows per join key on
        # that side (hot-key skew, e.g. nexmark's hot sellers, needs a
        # deep build side while a unique-keyed side stays shallow)
        self.left_bucket_cap = left_bucket_cap or bucket_cap
        self.right_bucket_cap = right_bucket_cap or bucket_cap
        # per-side key-table sizes: a unique-keyed side wants many slots
        # and shallow buckets; a hot-keyed side the opposite
        self.left_table_size = left_table_size or table_size
        self.right_table_size = right_table_size or table_size
        self.out_capacity = out_capacity
        #: per-side storage (module docstring): "dense" [size, B]
        #: buckets (general; caps hot keys), "pool" shared row ring
        #: (degree-adaptive; append-only sides) or "keyed" one row a
        #: slot by the side's stream key ``*_row_key`` (retractable
        #: sides with hot keys; inner joins)
        for storage, row_key in ((left_storage, left_row_key),
                                 (right_storage, right_row_key)):
            if storage not in ("dense", "pool", "keyed"):
                raise ValueError(
                    "storage must be 'dense', 'pool' or 'keyed'")
            if storage == "keyed" and (join_type != "inner"
                                       or not row_key):
                raise ValueError(
                    "a keyed side needs an inner join and the columns "
                    "that identify its rows")
        self.left_row_key = tuple(left_row_key or ())
        self.right_row_key = tuple(right_row_key or ())
        self.left_storage = left_storage
        self.right_storage = right_storage
        self.left_pool_size = left_pool_size or (
            self.left_table_size * self.left_bucket_cap
        )
        self.right_pool_size = right_pool_size or (
            self.right_table_size * self.right_bucket_cap
        )
        #: preserved sides: rows survive unmatched (as NULL-padded rows
        #: for outer, as the output itself for semi, inverted for anti)
        self.preserve_left = join_type in (
            "left_outer", "full_outer", "left_semi", "left_anti"
        )
        self.preserve_right = join_type in (
            "right_outer", "full_outer", "right_semi", "right_anti"
        )
        self.is_semi = join_type.endswith("_semi")
        self.is_anti = join_type.endswith("_anti")
        #: inner/outer emit (probe × build) pairs; semi/anti never do
        self.emit_pairs = not (self.is_semi or self.is_anti)
        if self.emit_pairs:
            left_out = left_schema if not self.preserve_right else Schema(
                tuple(f.with_nullable() for f in left_schema)
            )
            right_out = right_schema if not self.preserve_left else Schema(
                tuple(f.with_nullable() for f in right_schema)
            )
            self._out_schema = left_out.concat(right_out)
        else:
            self._out_schema = left_schema if self.preserve_left \
                else right_schema
        #: per-side watermark cleaning: at barriers the runtime retires the rows whose
        #: event-time expression < watermark - lag (a window join key,
        #: nexmark q8; a time band between the sides, nexmark q7)
        self.left_clean: JoinClean | None = None
        self.right_clean: JoinClean | None = None
        #: a predicate over the OUTPUT schema (left ++ right) a pair has
        #: to meet beside the key equality (inner joins; not behind a
        #: pool side): the planner sets it, ``apply_begin`` applies it
        #: where it stages pairs
        self.residual: Expr | None = None
        #: prefix of this join's ``jax.named_scope``s in a device
        #: profile (``/insert``, ``/probe``, ``/emit``, ``/clean``,
        #: ``/reclaim``); the DAG runtime appends the node index
        self.scope = "HashJoin"

    @property
    def out_schema(self) -> Schema:
        return self._out_schema

    def _preserved(self, side: str) -> bool:
        return self.preserve_left if side == "left" else self.preserve_right

    def clean_rule(self, side: str) -> "JoinClean | None":
        return self.left_clean if side == "left" else self.right_clean

    # ------------------------------------------------------------------
    def _key_protos(self, schema: Schema, keys: Sequence[Expr]):
        protos = []
        for e in keys:
            f = e.return_field(schema)
            if f.data_type.is_string:
                protos.append(StrCol(
                    jnp.zeros((1, f.str_width), jnp.uint8),
                    jnp.zeros((1,), jnp.int32),
                ))
            else:
                protos.append(jnp.zeros((1,), f.data_type.physical_dtype))
        return protos

    def _side_state(self, schema: Schema, keys: Sequence[Expr],
                    bucket: int, size: int) -> SideState:
        return SideState(
            key_table=HashTable.create(
                self._key_protos(schema, keys), size
            ),
            rows=tuple(_empty_store(f, size, bucket) for f in schema),
            occupied=jnp.zeros((size, bucket), jnp.bool_),
            count=jnp.zeros((size,), jnp.int32),
            overflow=_zero64(), inconsistency=_zero64(),
            insert_rows=_zero64(), delete_rows=_zero64(),
            probe_steps=_zero64(),
            emit_rows=_zero64(), cleaned_rows=_zero64(),
            reclaim_slots=_zero64(), live_rows=_zero64(),
            tombstones=_zero64(), table_slots=jnp.int64(size),
        )

    def _keyed_side_state(self, schema: Schema, row_key: Sequence[int],
                          size: int) -> KeyedSideState:
        return KeyedSideState(
            table=HashTable.create(
                [_flat_store(schema[i], 1) for i in row_key], size),
            rows=tuple(_flat_store(f, size) for f in schema),
            overflow=_zero64(), inconsistency=_zero64(),
            insert_rows=_zero64(), delete_rows=_zero64(),
            probe_steps=_zero64(),
            emit_rows=_zero64(), cleaned_rows=_zero64(),
            reclaim_slots=_zero64(), live_rows=_zero64(),
            tombstones=_zero64(), table_slots=jnp.int64(size),
            probe_wide_rounds=_zero64(), probe_tile_rounds=_zero64(),
            sorted_probe_chunks=_zero64(),
        )

    def _pool_side_state(self, schema: Schema, keys: Sequence[Expr],
                         size: int, pool: int) -> PoolSideState:
        # ONE fused tag table: live entries == live ring rows, plus the
        # heads that outlive their rank-0 row.  It has the ring's size
        # unless the side's table size asks for more slots (a ring that
        # runs nearly full wants a table at half its load)
        size = max(size, pool)
        return PoolSideState(
            table=TagTable.create(size),
            count=jnp.zeros((size,), jnp.int32),
            pool_pos=jnp.zeros((size,), jnp.int32),
            rows=tuple(_flat_store(f, pool) for f in schema),
            row_hash=jnp.zeros((pool,), jnp.uint64),
            row_rank=jnp.zeros((pool,), jnp.int32),
            row_clean=jnp.zeros((pool,), jnp.int64),
            head=_zero64(), tail=_zero64(),
            overflow=_zero64(), inconsistency=_zero64(),
            insert_rows=_zero64(), probe_steps=_zero64(),
            emit_rows=_zero64(), cleaned_rows=_zero64(),
            reclaim_slots=_zero64(), live_rows=_zero64(),
            tombstones=_zero64(), table_slots=jnp.int64(size),
        )

    def storage_of(self, side: str) -> str:
        return self.left_storage if side == "left" else self.right_storage

    def _init_side(self, side: str):
        left = side == "left"
        schema = self.left_schema if left else self.right_schema
        keys = self.left_keys if left else self.right_keys
        size = self.left_table_size if left else self.right_table_size
        storage = self.storage_of(side)
        if storage == "pool":
            return self._pool_side_state(
                schema, keys, size,
                self.left_pool_size if left else self.right_pool_size)
        if storage == "keyed":
            return self._keyed_side_state(
                schema, self.left_row_key if left else self.right_row_key,
                size)
        return self._side_state(
            schema, keys,
            self.left_bucket_cap if left else self.right_bucket_cap, size)

    def init_state(self) -> JoinState:
        left = self._init_side("left")
        right = self._init_side("right")
        return JoinState(
            left=left, right=right,
            emit_overflow=jnp.zeros((), jnp.int64),
            chunks=jnp.zeros((), jnp.int64),
            probe_iters=jnp.zeros((), jnp.int64),
            emit_rows=jnp.zeros((), jnp.int64),
            emit_windows=jnp.zeros((), jnp.int64),
        )

    # ------------------------------------------------------------------
    def _update_side(self, side: SideState, chunk: Chunk,
                     keys: Sequence[Expr]):
        """Apply the chunk's inserts/deletes to this side's multi-map.

        Returns the updated side.
        """
        B = side.occupied.shape[1]
        size = side.key_table.size
        key_cols, null_keys = _null_stripped_keys(
            [e.eval(chunk) for e in keys]
        )
        signs = chunk.signs()
        joinable = chunk.valid if null_keys is None \
            else chunk.valid & ~null_keys
        is_ins = joinable & (signs > 0)
        is_del = joinable & (signs < 0)

        # ---- in-chunk annihilation ------------------------------------
        # a +row and a -row of the same value inside one chunk cancel:
        # the delete pass below only sees *pre-chunk* state, so without
        # this a [-after-+] pair would ghost-insert.  Rows still take
        # part in probing (their +/- matches cancel downstream too).
        row_hash = hash64_columns(list(chunk.columns))
        ins_rank_h = _rank_by(row_hash, is_ins)
        del_rank_h = _rank_by(row_hash, is_del)
        n_ins_h = _group_totals(row_hash, is_ins)
        n_del_h = _group_totals(row_hash, is_del)
        cancelled_ins = is_ins & (ins_rank_h < n_del_h)
        cancelled_del = is_del & (del_rank_h < n_ins_h)
        is_ins = is_ins & ~cancelled_ins
        is_del = is_del & ~cancelled_del

        # ---- key slots: inserts may create, deletes only look up ------
        key_table, slots_ins, _, overflow = side.key_table.lookup_or_insert(
            key_cols, is_ins
        )
        is_ins = is_ins & ~overflow
        slots_del, found_del, probe_over = key_table.lookup_counted(
            key_cols, is_del
        )
        n_missing = jnp.sum((is_del & ~found_del).astype(jnp.int64))
        is_del = is_del & found_del
        safe_ins = jnp.minimum(slots_ins, size - 1)
        safe_del = jnp.minimum(slots_del, size - 1)

        # ---- deletes: clear the rank-th value-equal entry -------------
        # rank among value-equal delete rows: the full row hash is the
        # group key (equal rows share slot AND hash; unequal rows differ
        # in hash w.h.p., and a collision only reorders which duplicate
        # is cleared — harmless for multiset semantics)
        del_rank = _rank_by(row_hash, is_del)
        occ = side.occupied[safe_del]                     # [cap, B]
        bucket_hash = self._bucket_row_hash(side, safe_del)    # [cap, B]
        val_match = occ & (bucket_hash == row_hash[:, None])
        match_rank = jnp.cumsum(val_match, axis=1) - 1    # rank per entry
        clear_onehot = val_match & (match_rank == del_rank[:, None]) & \
            is_del[:, None]
        any_clear = jnp.any(clear_onehot, axis=1)
        n_missing = n_missing + jnp.sum(
            (is_del & ~any_clear).astype(jnp.int64)
        )
        j_clear = jnp.argmax(clear_onehot, axis=1).astype(jnp.int32)
        flat_clear = jnp.where(
            any_clear, safe_del * B + j_clear, jnp.int32(size * B)
        )
        occupied = side.occupied.reshape(-1).at[flat_clear].set(
            False, mode="drop"
        ).reshape(size, B)
        count = side.count.at[
            jnp.where(any_clear, safe_del, jnp.int32(size))
        ].add(-1, mode="drop")

        # ---- inserts: claim rank-th free position ---------------------
        ins_rank = _rank_by(slots_ins.astype(jnp.uint64), is_ins)
        free = ~occupied[safe_ins]                        # [cap, B]
        free_rank = jnp.cumsum(free, axis=1) - 1
        take_onehot = free & (free_rank == ins_rank[:, None]) & \
            is_ins[:, None]
        got = jnp.any(take_onehot, axis=1)
        j_take = jnp.argmax(take_onehot, axis=1).astype(jnp.int32)
        flat_take = jnp.where(
            got, safe_ins * B + j_take, jnp.int32(size * B)
        )
        occupied = occupied.reshape(-1).at[flat_take].set(
            True, mode="drop"
        ).reshape(size, B)
        rows = tuple(
            _scatter_rows(store, flat_take, col)
            for store, col in zip(side.rows, chunk.columns)
        )
        count = count.at[
            jnp.where(got, safe_ins, jnp.int32(size))
        ].add(1, mode="drop")
        n_over = jnp.sum((is_ins & ~got).astype(jnp.int64)) + \
            jnp.sum(overflow.astype(jnp.int64))

        return side._replace(
            key_table=key_table,
            rows=rows,
            occupied=occupied,
            count=count,
            overflow=side.overflow + n_over + probe_over,
            inconsistency=side.inconsistency + n_missing,
            insert_rows=side.insert_rows
            + jnp.sum(got.astype(jnp.int64)),
            delete_rows=side.delete_rows
            + jnp.sum(any_clear.astype(jnp.int64)),
        )

    def _update_side_keyed(self, side: KeyedSideState, chunk: Chunk,
                           keys: Sequence[Expr], schema: Schema,
                           row_key: Sequence[int]) -> KeyedSideState:
        """Apply the chunk's inserts/deletes to a keyed side: every row
        finds or claims the slot of its stream key in ONE probe, and the
        LAST row of the chunk at a slot decides what the slot holds (an
        insert its values, a delete nothing), as the view's upsert
        does.  A delete of a key the side did not hold is counted."""
        size = side.table.size
        cap = chunk.capacity
        _, null_keys = _null_stripped_keys([e.eval(chunk) for e in keys])
        signs = chunk.signs()
        touch = chunk.valid & (signs != 0)
        if null_keys is not None:
            touch = touch & ~null_keys  # a NULL join key matches nothing
        pk = [conform_col(chunk.columns[i], schema[i].nullable, cap)
              for i in row_key]
        table, slots, claimed, over, (wide, tile) = \
            side.table.lookup_or_insert_rounds(pk, touch)
        ok = touch & ~over
        with jax.named_scope(f"{self.scope}/delete"):
            # the chunk's rows by slot, in arrival order within a slot
            by_slot = jnp.where(ok, slots, jnp.int32(size))
            order = jnp.argsort(by_slot, stable=True)
            s_slot = by_slot[order]
            last = jnp.zeros((cap,), jnp.bool_).at[order].set(
                jnp.concatenate([s_slot[1:] != s_slot[:-1],
                                 jnp.ones((1,), jnp.bool_)]))
            put = ok & last & (signs > 0)
            drop = ok & last & (signs < 0)
            # the row that claimed a fresh slot is the first of its key
            # in the chunk: a delete there has nothing to delete
            missing = ok & claimed & (signs < 0)
            table = table.clear_slots(slots, drop)
        at = jnp.where(put, slots, jnp.int32(size))
        rows = tuple(
            _scatter_key(store, at, conform_col(col, f.nullable, cap), size)
            for store, col, f in zip(side.rows, chunk.columns, schema)
        )
        n_missing = jnp.sum(missing.astype(jnp.int64))
        return side._replace(
            table=table, rows=rows,
            overflow=side.overflow
            + jnp.sum((over & touch).astype(jnp.int64)),
            inconsistency=side.inconsistency + n_missing,
            insert_rows=side.insert_rows
            + jnp.sum((ok & (signs > 0)).astype(jnp.int64)),
            delete_rows=side.delete_rows
            + jnp.sum((ok & (signs < 0)).astype(jnp.int64)) - n_missing,
            probe_wide_rounds=side.probe_wide_rounds
            + wide.astype(jnp.int64),
            probe_tile_rounds=side.probe_tile_rounds
            + tile.astype(jnp.int64),
        )

    def _update_side_pool(self, side: PoolSideState, chunk: Chunk,
                          keys: Sequence[Expr], clean_spec,
                          key_cols=None, null_keys=None, h=None):
        """Apply an append-only chunk to a pool side with ONE fused
        (key-hash, rank) probe: each row resolves its key's head,
        learns the ranks handed out so far, and claims the entry for
        ``(hash, that + in-chunk rank)`` in a single loop; its row then
        takes the next ring position.

        Ranks stay contiguous per key (cleaning retires a key's lowest
        live ranks), so the emission's (key, lo + j) addressing always
        lands.

        ``key_cols``/``null_keys``/``h`` accept the caller's already-
        computed values (apply_begin hashes the same chunk for its
        probe pass); ``clean_spec`` is the side's ``JoinClean`` or None.

        Returns ``(new_side, probe_iters int32)``."""
        size = side.table.size
        pool = _pool_capacity(side.rows)
        if key_cols is None:
            key_cols, null_keys = _null_stripped_keys(
                [e.eval(chunk) for e in keys]
            )
        signs = chunk.signs()
        joinable = chunk.valid if null_keys is None \
            else chunk.valid & ~null_keys
        is_ins = joinable & (signs > 0)
        # append-only contract: retractions are a loud inconsistency
        n_bad = jnp.sum((joinable & (signs < 0)).astype(jnp.int64))

        if h is None:
            h = hash64_columns(key_cols)
        cr, sort_order, sort_seg = _rank_by_sorted(h, is_ins)
        (table, slots, rank, head_slot, inserted, existed, over,
         iters, steps) = side.table.lookup_or_insert_ranked(
            h, cr, side.count, is_ins
        )
        got = is_ins & ~over
        # a target entry that already existed means a prior overflow
        # stranded it while count stalled: this insert overwrites that
        # live pool row.  Count it so maintenance fails loudly instead
        # of silently losing a row.
        n_overwrite = jnp.sum((got & existed).astype(jnp.int64))

        # -- the ring: accepted rows take consecutive positions --------
        offs = jnp.cumsum(got, dtype=jnp.int32) - 1
        live = (side.head - side.tail).astype(jnp.int32)
        fits = live + offs < pool
        dropped = got & ~fits
        # un-claim entries whose row found no ring space (loud overflow)
        table = table.clear_slots(slots, dropped & inserted)
        got = got & fits
        pos = ((side.head + offs.astype(jnp.int64)) % pool).astype(
            jnp.int32)
        tgt = jnp.where(got, pos, jnp.int32(pool))
        rows = tuple(
            _scatter_key(store, tgt, col, pool)
            for store, col in zip(side.rows, chunk.columns)
        )
        row_hash = side.row_hash.at[tgt].set(h, mode="drop")
        row_rank = side.row_rank.at[tgt].set(rank, mode="drop")
        if clean_spec is not None:
            cval, _ = split_col(clean_spec.expr.eval(chunk))
            row_clean = side.row_clean.at[tgt].set(
                cval.astype(jnp.int64), mode="drop")
        else:
            row_clean = side.row_clean
        safe_slot = jnp.minimum(slots, size - 1)
        spos = jnp.where(got, safe_slot, jnp.int32(size))
        pool_pos = side.pool_pos.at[spos].set(tgt, mode="drop")
        # degree update: each key's rank-0 row (which always knows the
        # head slot) scatters the key's accepted-insert total — every
        # probe above saw the PRE-chunk degree.  Totals reuse the rank
        # sort's decomposition: no second argsort.
        rep = got & (cr == 0) & (head_slot < size)
        key_tot = _totals_from_sort(sort_order, sort_seg, got)
        count = side.count.at[
            jnp.where(rep, head_slot, jnp.int32(size))
        ].add(jnp.where(rep, key_tot, 0), mode="drop")
        n_got = jnp.sum(got.astype(jnp.int64))
        n_over = jnp.sum((is_ins & over).astype(jnp.int64)) + \
            jnp.sum(dropped.astype(jnp.int64)) + n_overwrite
        return side._replace(
            table=table,
            count=count,
            pool_pos=pool_pos,
            rows=rows,
            row_hash=row_hash,
            row_rank=row_rank,
            row_clean=row_clean,
            head=side.head + n_got,
            overflow=side.overflow + n_over,
            inconsistency=side.inconsistency + n_bad,
            insert_rows=side.insert_rows + n_got,
            probe_steps=side.probe_steps + steps.astype(jnp.int64),
        ), iters

    def _bucket_row_hash(self, side: SideState, safe_slots) -> jnp.ndarray:
        """Row hashes of a side's buckets gathered at [cap] slots."""

        def flat(g):
            if isinstance(g, NCol):
                return NCol(flat(g.data), g.null.reshape(-1))
            if isinstance(g, StrCol):
                cap, B, w = g.data.shape
                return StrCol(
                    g.data.reshape(cap * B, w), g.lens.reshape(cap * B)
                )
            return g.reshape(-1)

        cols = [flat(_gather_bucket(store, safe_slots))
                for store in side.rows]
        h = hash64_columns(cols)
        cap = safe_slots.shape[0]
        return h.reshape(cap, side.occupied.shape[1])

    # -- the residual predicate and the keyed probe ----------------------
    def _pair_chunk(self, probe_cols, build_cols, side: str, n: int):
        """A chunk over the OUTPUT schema for evaluating the residual:
        the arriving side's columns and the build side's, each ``[n]``;
        a column the predicate does not read is left out (None)."""
        cols = (tuple(probe_cols) + tuple(build_cols)) if side == "left" \
            else (tuple(build_cols) + tuple(probe_cols))
        return Chunk(cols, jnp.zeros((n,), jnp.int8),
                     jnp.ones((n,), jnp.bool_), self._out_schema)

    def _residual_holds(self, pair_chunk: Chunk) -> jnp.ndarray:
        v, null = split_col(self.residual.eval(pair_chunk))
        return v if null is None else v & ~null

    def _residual_split(self, side: str):
        """(positions the residual reads among the probe side's columns,
        among the build side's)."""
        n_left = len(self.left_schema)
        refs = _input_refs(self.residual)
        lrefs = {i for i in refs if i < n_left}
        rrefs = {i - n_left for i in refs if i >= n_left}
        return (lrefs, rrefs) if side == "left" else (rrefs, lrefs)

    def _residual_grid(self, probe_cols, build: SideState, safe,
                       side: str) -> jnp.ndarray:
        """bool [cap, B]: the residual for each (probe row, entry of
        the bucket its key found) of a dense build side."""
        cap = safe.shape[0]
        B = build.occupied.shape[1]
        prefs, brefs = self._residual_split(side)
        flat = lambda x: x.reshape((cap * B,) + x.shape[2:])
        pcols = [
            jax.tree.map(lambda x: jnp.repeat(x, B, axis=0), c)
            if i in prefs else None for i, c in enumerate(probe_cols)]
        bcols = [
            jax.tree.map(flat, _gather_bucket(store, safe))
            if i in brefs else None for i, store in enumerate(build.rows)]
        ok = self._residual_holds(
            self._pair_chunk(pcols, bcols, side, cap * B))
        return ok.reshape(cap, B)

    def _join_keys(self, cols, side: str):
        """(``side``'s join-key columns over its columns ``cols``, ``[n]``
        each, any-key-null mask | None)."""
        schema = self.left_schema if side == "left" else self.right_schema
        keys = self.left_keys if side == "left" else self.right_keys
        n = jax.tree.leaves(cols[0])[0].shape[0]
        rows = Chunk(cols, jnp.zeros((n,), jnp.int8),
                     jnp.ones((n,), jnp.bool_), schema)
        return _null_stripped_keys([e.eval(rows) for e in keys])

    def _keyed_matcher(self, probe_cols, key_cols, build: KeyedSideState,
                       side: str):
        """``hit_of(r) -> bool [size]``: the slots of a keyed build side
        that probe row ``r`` pairs with (join keys equal, the residual
        holds): one masked pass over the slots."""
        size = build.table.size
        bkey_cols, _ = self._join_keys(
            build.rows, "right" if side == "left" else "left")
        if self.residual is not None:
            prefs, brefs = self._residual_split(side)
            bcols = [c if i in brefs else None
                     for i, c in enumerate(build.rows)]

        def row_of(col, r):
            # row r of a column, shaped to broadcast against [size]
            return jax.tree.map(lambda x: x[r][None], col)

        def hit_of(r):
            hit = build.table.occupied
            for bk, pk in zip(bkey_cols, key_cols):
                hit = hit & keys_equal(bk, row_of(pk, r))
            if self.residual is not None:
                pcols = [
                    jax.tree.map(
                        lambda x: jnp.broadcast_to(
                            x[r], (size,) + x.shape[1:]), c)
                    if i in prefs else None
                    for i, c in enumerate(probe_cols)]
                hit = hit & self._residual_holds(
                    self._pair_chunk(pcols, bcols, side, size))
            return hit

        return hit_of

    def _keyed_sorted_probe(self, build: KeyedSideState, side: str,
                            probe_hash, joinable):
        """``(m, lo, order, by_sort)`` of a chunk probing a keyed build
        side with no residual: the slots sorted once by the top 32 bits
        of their join key's hash (``order``; empty slots and NULL keys
        last), then each probe row's run ``[lo, lo + m)`` in that order
        found by a binary search of both its ends.  A run may hold
        another key that shares those bits: ``m`` bounds the row's
        pairs, and the emission drops a pair whose keys differ.  Only
        the hash is a sort key (a sort compared on the key's four 64-bit
        words did not compile for the v5e in 200 s: PERF.md §6).
        ``by_sort`` is false, and nothing is sorted, where the chunk has
        fewer than ``SORTED_PROBE_MIN_ROWS`` rows to probe."""
        size = build.table.size
        cap = joinable.shape[0]
        bkey_cols, bnull = self._join_keys(
            build.rows, "right" if side == "left" else "left")
        live = build.table.occupied if bnull is None \
            else build.table.occupied & ~bnull

        def key32(h):
            return jnp.minimum((h >> 32).astype(jnp.uint32),
                               _SORT_LAST - 1)

        want = jnp.concatenate([key32(probe_hash)] * 2)
        # the first cap searches find where a run starts, the rest where
        # it ends (the first slot past every key <= the probe's)
        upper = jnp.arange(2 * cap) >= cap

        def probe(_):
            k = jnp.where(live, key32(hash64_columns(bkey_cols)),
                          _SORT_LAST)
            k, order = jax.lax.sort(
                (k, jnp.arange(size, dtype=jnp.int32)), num_keys=1)

            def halve(_, bounds):
                lo, hi = bounds
                go = lo < hi
                mid = (lo + hi) // 2
                v = k[jnp.minimum(mid, size - 1)]
                right = go & ((v < want) | ((v == want) & upper))
                return (jnp.where(right, mid + 1, lo),
                        jnp.where(go & ~right, mid, hi))

            bound, _ = jax.lax.fori_loop(
                0, size.bit_length(), halve,
                (jnp.zeros((2 * cap,), jnp.int32),
                 jnp.full((2 * cap,), size, jnp.int32)))
            lo = bound[:cap]
            return (jnp.where(joinable, bound[cap:] - lo, 0), lo, order,
                    jnp.bool_(True))

        def skip(_):
            return (jnp.zeros((cap,), jnp.int32),
                    jnp.zeros((cap,), jnp.int32),
                    jnp.zeros((size,), jnp.int32), jnp.bool_(False))

        n_probe = jnp.sum(joinable, dtype=jnp.int32)
        return jax.lax.cond(n_probe >= SORTED_PROBE_MIN_ROWS, probe, skip,
                            None)

    # -- output-centric windowed emission --------------------------------
    def apply_begin(self, state: JoinState, chunk: Chunk, side: str):
        """Update own-side state and stage the emission space.

        Returns (state, pending): ``pending`` describes one logical
        emission array [pairs | self rows | transition rows]; windows
        of it are produced by ``emit_window`` — O(out_capacity) gathers
        each, independent of bucket depth.
        """
        own = state.left if side == "left" else state.right
        other = state.right if side == "left" else state.left
        keys = self.left_keys if side == "left" else self.right_keys
        cap = chunk.capacity

        old = own  # own per-key row counts BEFORE the chunk
        own_clean = self.clean_rule(side)
        key_cols, null_keys = _null_stripped_keys(
            [e.eval(chunk) for e in keys]
        )
        probe_hash = hash64_columns(key_cols)
        upd_iters = jnp.zeros((), jnp.int32)
        with jax.named_scope(f"{self.scope}/insert"):
            if self.storage_of(side) == "pool":
                own2, upd_iters = self._update_side_pool(
                    own, chunk, keys, own_clean,
                    key_cols=key_cols, null_keys=null_keys, h=probe_hash,
                )
            elif self.storage_of(side) == "keyed":
                own2 = self._update_side_keyed(
                    own, chunk, keys,
                    self.left_schema if side == "left"
                    else self.right_schema,
                    self.left_row_key if side == "left"
                    else self.right_row_key,
                )
            else:
                own2 = self._update_side(own, chunk, keys)

        with jax.named_scope(f"{self.scope}/probe"):
            signs = chunk.signs()
            active = chunk.valid & (signs != 0)
            joinable = active if null_keys is None else active & ~null_keys

            # probe the build (other) side: per-row key slot + live rows
            build_storage = self.storage_of(
                "right" if side == "left" else "left")
            order = jnp.zeros((1,), jnp.int32)
            by_sort = jnp.bool_(False)
            base = jnp.zeros((cap,), jnp.int32)
            if build_storage == "keyed":
                bsize = other.table.size
                n_probe = jnp.sum(joinable, dtype=jnp.int32)
                m = jnp.zeros((cap,), jnp.int32)
                steps = jnp.zeros((), jnp.int64)
                if self.residual is None:
                    # one sort of the build side's slots, then a binary
                    # search a probe row
                    m, base, order, by_sort = self._keyed_sorted_probe(
                        other, side, probe_hash, joinable)
                    steps = by_sort * (bsize + 2 * bsize.bit_length()
                                       * n_probe.astype(jnp.int64))
                    other = other._replace(
                        sorted_probe_chunks=other.sorted_probe_chunks
                        + by_sort.astype(jnp.int64))
                # else (a residual filters a key's run, or a chunk of
                # few rows): one masked pass over the slots a row
                sel = mask_indices(joinable, cap, cap)
                hit_of = self._keyed_matcher(
                    chunk.columns, key_cols, other, side)

                def count_row(i, m):
                    r = sel[i]
                    return m.at[r].set(jnp.sum(hit_of(r), dtype=jnp.int32))

                m = jax.lax.cond(
                    by_sort, lambda m: m,
                    lambda m: jax.lax.fori_loop(0, n_probe, count_row, m), m)
                steps = steps + jnp.where(
                    by_sort, 0, n_probe.astype(jnp.int64) * bsize)
                safe = jnp.zeros((cap,), jnp.int32)
                probe_over = jnp.zeros((), jnp.int64)
                rank_to_idx = jnp.zeros((cap, 1), jnp.int32)
                own2 = own2._replace(probe_steps=own2.probe_steps + steps)
            elif build_storage == "pool":
                # pool build side: ONE fused-table probe of the key's HEAD
                # entry (hash, 0) yields its degree; rows are addressed at
                # emission time by (key-hash, rank)
                bsize = other.table.size
                slots, found, probe_over = other.table.lookup_pair_counted(
                    probe_hash, jnp.zeros((cap,), jnp.int32), joinable
                )
                safe = jnp.minimum(slots, bsize - 1)
                m, base = _pool_live(other, safe)
                m = jnp.where(found, m, 0).astype(jnp.int32)
                rank_to_idx = jnp.zeros((cap, 1), jnp.int32)
            else:
                bsize = other.key_table.size
                slots, found, probe_over = other.key_table.lookup_counted(
                    key_cols, joinable, hashes=probe_hash
                )
                safe = jnp.minimum(slots, bsize - 1)
                occ = other.occupied[safe] & found[:, None]        # [cap, B]
                if self.residual is not None:
                    occ = occ & self._residual_grid(
                        chunk.columns, other, safe, side)
                m = jnp.sum(occ, axis=1).astype(jnp.int32)
                # rank -> bucket index of the k-th live row (occupied
                # first, stable: bool sort of the occupancy bitmap only)
                rank_to_idx = jnp.argsort(~occ, axis=1, stable=True) \
                    .astype(jnp.int32)

            # section 1: (probe × build) pairs
            pair_cnt = m if self.emit_pairs else jnp.zeros_like(m)
            pair_end = jnp.cumsum(pair_cnt)
            P = pair_end[-1]

            # section 2: self rows (A preserved: pads for outer, the row
            # itself for semi/anti).  NULL-key rows match nothing, so they
            # count as zero-match rows here — SQL outer/anti semantics.
            if self._preserved(side):
                if self.is_semi:
                    self_mask = active & (m > 0)
                else:  # outer pad or anti
                    self_mask = active & (m == 0)
            else:
                self_mask = jnp.zeros((cap,), jnp.bool_)
            self_sel = mask_indices(self_mask, cap, cap)
            S = jnp.sum(self_mask).astype(jnp.int32)

            # section 3: transitions of the OTHER side's stored rows.  A
            # stored row's degree is its key's count on THIS side, so the
            # chunk flips other-side rows exactly when a key's own count
            # crosses 0 (ref: degree table 0<->1 transitions).
            other_pres = self._preserved(
                "right" if side == "left" else "left"
            )
            if other_pres:
                if self.storage_of(side) == "pool":
                    oslots, ofound, _ = own2.table.lookup_pair_counted(
                        probe_hash, jnp.zeros((cap,), jnp.int32), joinable
                    )
                    osafe = jnp.minimum(oslots, own2.table.size - 1)
                    oldc, _ = _pool_live(old, osafe)
                    newc, _ = _pool_live(own2, osafe)
                else:
                    oslots, ofound, _ = own2.key_table.lookup_counted(
                        key_cols, joinable
                    )
                    osafe = jnp.minimum(oslots, own2.key_table.size - 1)
                    oldc = old.count[osafe]
                    newc = own2.count[osafe]
                eligible = joinable & ofound
                up = eligible & (oldc == 0) & (newc > 0)
                down = eligible & (oldc > 0) & (newc == 0)
                first = _rank_by(oslots.astype(jnp.uint64), up | down) == 0
                up_cnt = jnp.where(up & first, m, 0)
                down_cnt = jnp.where(down & first, m, 0)
            else:
                up_cnt = jnp.zeros((cap,), jnp.int32)
                down_cnt = jnp.zeros((cap,), jnp.int32)
            up_end = jnp.cumsum(up_cnt)
            U = up_end[-1]
            down_end = jnp.cumsum(down_cnt)
            D = down_end[-1]

        pending = JoinEmit(
            probe_cols=chunk.columns,
            signs=signs,
            slots=safe,
            rank_to_idx=rank_to_idx,
            probe_hash=probe_hash,
            m=m,
            base=base,
            order=order,
            by_sort=by_sort,
            up_cnt=up_cnt,
            up_end=up_end,
            U=U,
            pair_end=pair_end,
            P=P,
            self_sel=self_sel,
            S=S,
            down_cnt=down_cnt,
            down_end=down_end,
            total=U + P + S + D,
        )
        total = U + P + S + D
        own2 = own2._replace(
            emit_rows=own2.emit_rows + total.astype(jnp.int64))
        new_state = JoinState(
            left=own2 if side == "left" else other,
            right=own2 if side == "right" else other,
            emit_overflow=state.emit_overflow
            + probe_over.astype(jnp.int64),
            chunks=state.chunks + 1,
            probe_iters=state.probe_iters + upd_iters.astype(jnp.int64),
            emit_rows=state.emit_rows + total.astype(jnp.int64),
            # window 0 always materializes; amplified chunks drain
            # ceil(total / out_capacity) windows
            emit_windows=state.emit_windows + jnp.maximum(
                (total + self.out_capacity - 1) // self.out_capacity, 1
            ).astype(jnp.int64),
        )
        return new_state, pending

    def emit_window(self, build_rows: tuple, p: JoinEmit, w,
                    side: str):
        with jax.named_scope(f"{self.scope}/emit"):
            return self._emit_window(build_rows, p, w, side)

    def _emit_window(self, build_rows: tuple, p: JoinEmit, w,
                     side: str):
        """Materialize window ``w`` of the pending emission space.

        ``build_rows`` is the build (non-arriving) side's row stores —
        taken from the CURRENT state so the while_loop carry holds the
        stores once, not per-window copies.

        Returns ``(chunk, probe_bound int64)``: the second value counts
        build-index probes that exhausted the probe-iteration bound —
        rows whose presence is then UNKNOWN and which are dropped from
        the output; callers must fold it into ``emit_overflow`` so
        maintenance fails loudly (hash_table.lookup_counted contract)."""
        out_cap = self.out_capacity
        cap = p.signs.shape[0]
        gpos = w * out_cap + jnp.arange(out_cap, dtype=jnp.int32)
        valid_out = gpos < p.total
        # section layout: [up-transitions | pairs | self | down-trans]
        in_up = valid_out & (gpos < p.U)
        ppos = gpos - p.U
        in_pairs = valid_out & (gpos >= p.U) & (ppos < p.P)
        spos = ppos - p.P
        in_self = valid_out & (ppos >= p.P) & (spos < p.S)
        dpos = spos - p.S
        in_down = valid_out & (spos >= p.S)
        in_trans = in_up | in_down

        def decode(end, cnt, pos):
            """row index + within-row offset for a cumsum section."""
            r_ = jnp.minimum(
                jnp.searchsorted(end, pos, side="right"), cap - 1
            ).astype(jnp.int32)
            return r_, pos - (end[r_] - cnt[r_])

        pair_cnt = p.m if self.emit_pairs else jnp.zeros_like(p.m)
        ur, uj = decode(p.up_end, p.up_cnt, gpos)
        pr, pj = decode(p.pair_end, pair_cnt, ppos)
        sr = p.self_sel[jnp.clip(spos, 0, cap - 1)]
        dr, dj = decode(p.down_end, p.down_cnt, dpos)

        r = jnp.where(in_up, ur,
                      jnp.where(in_pairs, pr,
                                jnp.where(in_self, sr, dr)))
        j = jnp.where(in_up, uj,
                      jnp.where(in_pairs, pj,
                                jnp.where(in_down, dj, 0)))
        slot = p.slots[r]

        def probe_val(col):
            return gather_key(col, r)

        build_rows, build_index = build_rows
        probe_bound = jnp.int64(0)
        if isinstance(build_index, KeyedSideState):
            bsize = build_index.table.size

            def by_order():
                # pair j of probe row r: position base + j of the order,
                # dropped where the slot's key only shares r's hash bits
                bslot = p.order[jnp.clip(p.base[r] + j, 0,
                                         p.order.shape[0] - 1)]
                bkeys, _ = self._join_keys(
                    jax.tree.map(lambda x: x[bslot], build_rows),
                    "right" if side == "left" else "left")
                pkeys, _ = self._join_keys(
                    jax.tree.map(lambda x: x[r], p.probe_cols), side)
                same = jnp.ones((out_cap,), jnp.bool_)
                for bw, pw in zip(key_words(bkeys), key_words(pkeys)):
                    same = same & (bw == pw)
                return bslot, same

            def by_pass():
                # pair j of probe row r is the j-th slot r's masked pass
                # finds.  The window's pairs belong to a few consecutive
                # probe rows (those with a match): one pass each, its
                # running count searched for the ranks wanted
                key_cols, _ = self._join_keys(p.probe_cols, side)
                hit_of = self._keyed_matcher(
                    p.probe_cols, key_cols, build_index, side)
                has = p.m > 0
                nz_rank = jnp.cumsum(has, dtype=jnp.int32) - 1
                nz_sel = mask_indices(has, cap, cap)
                n_in = jnp.sum(in_pairs, dtype=jnp.int32)
                first = jnp.argmax(in_pairs).astype(jnp.int32)
                k_lo = nz_rank[r[first]]
                k_hi = nz_rank[r[jnp.maximum(first + n_in - 1, 0)]]

                def locate(t, bslot):
                    rk = nz_sel[jnp.minimum(k_lo + t, cap - 1)]
                    seen = _scan_slots(
                        jax.lax.cumsum, jnp.add,
                        hit_of(rk).astype(jnp.int32), 0)
                    at = jnp.searchsorted(
                        seen, j + 1, side="left", method="scan")
                    return jnp.where(in_pairs & (r == rk),
                                     at.astype(jnp.int32), bslot)

                return jax.lax.fori_loop(
                    0, jnp.where(n_in > 0, k_hi - k_lo + 1, 0), locate,
                    jnp.zeros((out_cap,), jnp.int32)), \
                    jnp.ones((out_cap,), jnp.bool_)

            bslot, same = by_pass() if self.residual is not None \
                else jax.lax.cond(p.by_sort, by_order, by_pass)
            bslot = jnp.minimum(bslot, bsize - 1)
            valid_out = valid_out & (same | ~in_pairs)

            def build_val(store):
                return jax.tree.map(lambda x: x[bslot], store)
        elif build_index is not None:
            # pool build side: ONE vectorized (key-hash, rank) fused-
            # table lookup resolves every build row this window needs;
            # the entry's pool_pos value addresses the bump-allocated
            # row store
            btable, bpool_pos = build_index
            need = in_pairs | in_trans
            pool = _pool_capacity(build_rows)
            bslot, bfound, probe_bound = btable.lookup_pair_counted(
                p.probe_hash[r], (p.base[r] + j).astype(jnp.int32), need
            )
            bpos = jnp.clip(
                bpool_pos[jnp.minimum(bslot, btable.size - 1)],
                0, pool - 1,
            )
            # a needed-but-missing build row (pool overflow hole) is
            # dropped; the overflow counter already records the loss
            valid_out = valid_out & (~need | bfound)

            def build_val(store):
                if isinstance(store, NCol):
                    return NCol(build_val(store.data), store.null[bpos])
                if isinstance(store, StrCol):
                    return StrCol(store.data[bpos], store.lens[bpos])
                return store[bpos]
        else:
            bidx = p.rank_to_idx[
                r, jnp.clip(j, 0, p.rank_to_idx.shape[1] - 1)
            ]

            def build_val(store):
                if isinstance(store, NCol):
                    return NCol(
                        build_val(store.data), store.null[slot, bidx]
                    )
                if isinstance(store, StrCol):
                    return StrCol(
                        store.data[slot, bidx], store.lens[slot, bidx]
                    )
                return store[slot, bidx]

        def pad_null(col, is_pad):
            """Wrap/extend a column with pad-row null flags."""
            if isinstance(col, NCol):
                return NCol(col.data, col.null | is_pad)
            return NCol(col, is_pad)

        out_cols = []
        if self.emit_pairs:
            # left ++ right; probe side real except transitions, build
            # side real except self pads
            for src_side in ("left", "right"):
                schema = self.left_schema if src_side == "left" \
                    else self.right_schema
                from_probe = src_side == side
                for ci, f in enumerate(schema):
                    if from_probe:
                        col = probe_val(p.probe_cols[ci])
                        pad = in_trans
                    else:
                        col = build_val(build_rows[ci])
                        pad = in_self
                    nullable = (self.preserve_left
                                if src_side == "right"
                                else self.preserve_right)
                    out_cols.append(
                        pad_null(col, pad) if nullable else col
                    )
        else:
            # semi/anti: preserved side only — self rows come from the
            # probe chunk, transition rows from the build store
            pres = "left" if self.preserve_left else "right"
            schema = self.left_schema if pres == "left" \
                else self.right_schema
            for ci in range(len(schema)):
                if pres == side:
                    out_cols.append(probe_val(p.probe_cols[ci]))
                else:
                    out_cols.append(build_val(build_rows[ci]))

        from risingwave_tpu.common.chunk import (
            OP_DELETE,
            OP_INSERT,
            OP_UPDATE_DELETE,
            OP_UPDATE_INSERT,
        )

        sign_r = p.signs[r]
        base_op = jnp.where(
            sign_r > 0, jnp.int8(OP_INSERT), jnp.int8(OP_DELETE)
        )
        if self.is_semi:
            up_op, down_op = OP_INSERT, OP_DELETE
        elif self.is_anti:
            up_op, down_op = OP_DELETE, OP_INSERT
        else:  # outer pads retract on first match, return on last unmatch
            up_op, down_op = OP_UPDATE_DELETE, OP_UPDATE_INSERT
        ops = jnp.where(
            in_up, jnp.int8(up_op),
            jnp.where(in_down, jnp.int8(down_op), base_op),
        )
        return Chunk(out_cols, ops, valid_out, self._out_schema), \
            probe_bound

    def build_rows_of(self, state: JoinState, side: str) -> tuple:
        """(row stores, addressing-or-None) of the build side for
        emit_window — pool sides address rows via the fused
        (hash, rank) table + its pool_pos values."""
        build = state.right if side == "left" else state.left
        if isinstance(build, PoolSideState):
            return build.rows, (build.table, build.pool_pos)
        if isinstance(build, KeyedSideState):
            return build.rows, build
        return build.rows, None

    # ------------------------------------------------------------------
    def apply(self, state: JoinState, chunk: Chunk, side: str):
        """Process one chunk from ``side`` ("left"|"right"), emitting
        window 0 of the staged emissions; the remainder counts into
        ``emit_overflow`` (the windowed DAG path loses nothing —
        ``apply_begin``/``emit_window``).

        Order (matching the reference's update-then-probe for correct
        self-consistency): update own side, then probe the other side.
        """
        state, pending = self.apply_begin(state, chunk, side)
        out, probe_bound = self.emit_window(
            self.build_rows_of(state, side), pending, jnp.int32(0), side
        )
        dropped = jnp.maximum(pending.total - self.out_capacity, 0)
        return state._replace(
            emit_overflow=state.emit_overflow + dropped.astype(jnp.int64)
            + probe_bound
        ), out

    def max_windows(self, chunk_cap: int) -> int:
        """Static bound on emission windows for one chunk (the dynamic
        ``pending.total`` governs actual trips; pool sides' worst case
        is the whole pool joining one probe row)."""
        depth = {"pool": (self.left_pool_size, self.right_pool_size),
                 "keyed": (self.left_table_size, self.right_table_size),
                 "dense": (self.left_bucket_cap, self.right_bucket_cap)}
        depth_l = depth[self.left_storage][0]
        depth_r = depth[self.right_storage][1]
        worst = chunk_cap * max(depth_l, depth_r) * 2 + chunk_cap
        return -(-worst // self.out_capacity)

    # ------------------------------------------------------------------
    def maybe_rehash(self, state: JoinState) -> JoinState:
        """Give each side's tombstoned table slots back (runtime
        maintenance, after state cleaning), at a cost that follows what
        was retired: ``HashTable.reclaimed`` / ``TagTable.reclaimed``
        empty every tombstone and put back only the entries whose probe
        chain crossed one, the per-slot leaves moving with them.  A pool
        side's rows sit in a ring and never move.

        Traceable: per-side ``lax.cond`` on the device tombstone count;
        the levels (``fragment.JOIN_GAUGE_ATTRS``) are the tables as this
        pass found them, before it reclaimed."""

        def reclaim_dense(s: SideState) -> SideState:
            table, (rows, occupied, count), lost = s.key_table.reclaimed(
                (s.rows, s.occupied, s.count))
            return s._replace(key_table=table, rows=rows,
                              occupied=occupied, count=count,
                              overflow=s.overflow + lost)

        def reclaim_pool(s: PoolSideState) -> PoolSideState:
            table, (count, pool_pos), lost = s.table.reclaimed(
                (s.count, s.pool_pos))
            return s._replace(table=table, count=count, pool_pos=pool_pos,
                              overflow=s.overflow + lost)

        def reclaim_keyed(s: KeyedSideState) -> KeyedSideState:
            table, (rows,), lost = s.table.reclaimed((s.rows,))
            return s._replace(table=table, rows=rows,
                              overflow=s.overflow + lost)

        sides = {}
        with jax.named_scope(f"{self.scope}/reclaim"):
            for name in ("left", "right"):
                s = getattr(state, name)
                if isinstance(s, PoolSideState):
                    table, fn = s.table, reclaim_pool
                    live = s.head - s.tail
                elif isinstance(s, KeyedSideState):
                    table, fn = s.table, reclaim_keyed
                    live = table.count().astype(jnp.int64)
                else:
                    table, fn = s.key_table, reclaim_dense
                    live = jnp.sum(s.count, dtype=jnp.int64)
                tombs = table.tombstone_count()
                s = s._replace(
                    live_rows=live, tombstones=tombs.astype(jnp.int64),
                    reclaim_slots=s.reclaim_slots + tombs,
                )
                sides[name] = jax.lax.cond(tombs > 0, fn, lambda x: x, s)
        return state._replace(left=sides["left"], right=sides["right"])

    def clean_below(self, state: JoinState, side: str,
                    threshold) -> JoinState:
        """Retire ``side``'s rows whose cleaning value (``clean_rule``)
        lies below ``threshold`` — never a row a later change of the
        other side can still meet: the rule's lag sees to that."""
        s = getattr(state, side)
        rule = self.clean_rule(side)
        with jax.named_scope(f"{self.scope}/clean"):
            if isinstance(s, PoolSideState):
                cleaned = self._clean_pool(s, threshold)
            elif isinstance(s, KeyedSideState):
                cleaned = self._clean_keyed(s, rule.expr, side, threshold)
            else:
                cleaned = self._clean_dense(s, rule.expr, side, threshold)
        return state._replace(**{side: cleaned})

    def _clean_pool(self, s: PoolSideState, threshold) -> PoolSideState:
        """Retire the longest prefix of the ring whose rows lie below
        ``threshold``, ``CLEAN_TILE`` rows at a time: each retiring row
        finds its own ``(hash, rank)`` entry and its key's head in one
        probe, the head's ``lo`` moves past it, entries of rank > 0 are
        tombstoned at once and a head when its key has no live row left.
        The cost follows the rows retired; the row stores are not
        touched (the ring's tail moves)."""
        size = s.table.size
        pool = _pool_capacity(s.rows)
        K = min(CLEAN_TILE, pool)
        off = jnp.arange(K, dtype=jnp.int32)
        drop = jnp.int32(size)

        def cond(carry):
            return carry[-1]

        def body(carry):
            tags, count, pool_pos, tail, lost, _ = carry
            live = s.head - tail
            pos = ((tail + off.astype(jnp.int64)) % pool).astype(jnp.int32)
            ok = (off < live) & (s.row_clean[pos] < threshold)
            gone = jnp.cumsum(~ok) == 0      # the tile's expired prefix
            n = jnp.sum(gone, dtype=jnp.int32)
            h = s.row_hash[pos]
            r = s.row_rank[pos]
            # one probe for both: the rows' own entries, then the heads
            # of the rows that are not their key's head themselves
            slots, found, bound = TagTable(tags, size).lookup_pair_counted(
                jnp.concatenate([h, h]),
                jnp.concatenate([r, jnp.zeros((K,), jnp.int32)]),
                jnp.concatenate([gone, gone & (r > 0)]),
            )
            is_head = r == 0
            own_slot, own_found = slots[:K], found[:K]
            head_slot = jnp.where(is_head, own_slot, slots[K:])
            head_ok = gone & jnp.where(is_head, own_found, found[K:])
            at_head = jnp.where(head_ok, head_slot, drop)
            # lo moves past the retired rank (ranks retire in order)
            pool_pos = pool_pos.at[at_head].max(pool + r + 1, mode="drop")
            safe = jnp.minimum(head_slot, size - 1)
            dead = head_ok & (pool_pos[safe] - pool >= count[safe])
            at_dead = jnp.where(dead, head_slot, drop)
            kill = jnp.where(gone & own_found & ~is_head, own_slot, drop)
            tags = tags.at[kill].set(TOMB_TAG, mode="drop")
            tags = tags.at[at_dead].set(TOMB_TAG, mode="drop")
            count = count.at[at_dead].set(0, mode="drop")
            pool_pos = pool_pos.at[at_dead].set(0, mode="drop")
            more = (n == K) & (live > K)
            return (tags, count, pool_pos, tail + n.astype(jnp.int64),
                    lost + bound, more)

        tags, count, pool_pos, tail, lost, _ = jax.lax.while_loop(
            cond, body,
            (s.table.tags, s.count, s.pool_pos, s.tail, _zero64(),
             s.head > s.tail))
        return s._replace(
            table=TagTable(tags, size), count=count, pool_pos=pool_pos,
            tail=tail, overflow=s.overflow + lost,
            cleaned_rows=s.cleaned_rows + (tail - s.tail),
        )

    def _clean_keyed(self, s: KeyedSideState, expr, side: str,
                     threshold) -> KeyedSideState:
        """Give up a keyed side's slots whose row has expired."""
        schema = self.left_schema if side == "left" else self.right_schema
        size = s.table.size
        vals, null = split_col(expr.eval(Chunk(
            s.rows, jnp.zeros((size,), jnp.int8), s.table.occupied,
            schema)))
        stale = s.table.occupied & (vals < threshold)
        if null is not None:
            stale = stale & ~null
        return s._replace(
            table=s.table.clear_where(stale),
            cleaned_rows=s.cleaned_rows + jnp.sum(stale, dtype=jnp.int64),
        )

    def _clean_dense(self, s: SideState, expr, side: str,
                     threshold) -> SideState:
        """Mask a dense side's expired rows out of their buckets; a key
        whose bucket is left empty gives its slot up."""
        schema = self.left_schema if side == "left" else self.right_schema
        keys = self.left_keys if side == "left" else self.right_keys
        if isinstance(expr, NamedRef):
            expr = InputRef(schema.index_of(expr.name))
        if isinstance(expr, InputRef):
            vals, _ = split_col(s.rows[expr.index])       # [size, B]
        else:
            k = next((i for i, e in enumerate(keys) if e is expr), None)
            if k is None:
                raise ValueError(
                    "a dense join side cleans by a column or a join key")
            vals = s.key_table.key_cols[k][:, None]
        stale = s.occupied & (vals < threshold)
        occupied = s.occupied & ~stale
        n_stale = jnp.sum(stale, axis=1, dtype=jnp.int32)
        count = s.count - n_stale
        dead = s.key_table.occupied & (count == 0)
        return s._replace(
            key_table=s.key_table.clear_where(dead),
            occupied=occupied, count=count,
            cleaned_rows=s.cleaned_rows + jnp.sum(n_stale, dtype=jnp.int64),
        )
