"""Hash aggregation executor (device-resident groups, emit-on-barrier).

Reference counterpart: ``HashAggExecutor`` (src/stream/src/executor/
aggregate/hash_agg.rs:64) — LRU AggGroup cache keyed by HashKey, dirty
set, ``apply_chunk`` at :332, flush at :412.

TPU-first design
----------------
Groups live in a dense ``HashTable`` + per-aggregate state arrays in
HBM.  A chunk's worth of updates for thousands of groups lands as ONE
vectorized lookup_or_insert + one scatter per primitive state (vs the
reference's per-group HashMap walk):

    slots = table.lookup_or_insert(keys)
    state = state.at[slots].add(signs * value)     # retractable adds
    state = state.at[slots].min/max(value)         # append-only monoids

Changelog emission happens at barrier flush, exactly like the
reference's emit-on-barrier: dirty slots are compacted with a
fixed-size ``nonzero`` and emitted as an interleaved U-/U+ chunk, with
previous outputs reconstructed from a `prev` copy of the state arrays.
Retraction semantics (Insert if group appears, Update pair if it
changes, Delete if its row count reaches zero) mirror
``AggGroup::build_change``.

min/max over APPEND-ONLY inputs are monotone monoids (one scatter-min/
max per chunk).  Over RETRACTABLE inputs (``retractable_input=True``)
they switch to a **materialized-input state** — the reference's
``minput.rs`` (src/stream/src/executor/aggregate/minput.rs) as a counted
table: each such aggregate owns a ``HashTable`` keyed by *(group keys...,
value)* with a multiplicity a slot, the layout a DISTINCT call's dedup
table has (``_counted_update`` serves both).  An insert is a
find-or-claim and a +1, a retraction a lookup and a -1, and a value
whose multiplicity reaches zero gives its slot up: the work of a chunk
follows the chunk and the memory the distinct values held, whatever a
group holds (seven groups of 15,000 values or 100,000 groups of eleven).
The aggregate's ``[size]`` prim array is a flush-time CACHE: when a
chunk has changed the table, the next flush takes the extreme of every
group again in one masked pass over the table's slots (a scatter-min/max
into the group slots each value remembers), so any value may be
retracted, the current extreme included, and the prev-snapshot / U-pair
machinery is untouched.  The table is cleaned with its groups, reclaimed
like them, and a full one counts ``minput_overflow`` loudly (raise at
maintenance).
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from risingwave_tpu.common.chunk import (
    Chunk,
    NCol,
    OP_DELETE,
    OP_INSERT,
    OP_UPDATE_DELETE,
    OP_UPDATE_INSERT,
    StrCol,
    conform_col,
    split_col,
)
from risingwave_tpu.common.compact import (
    accel_tuned,
    mask_indices,
    segment_start_positions,
    segment_starts,
    segmented_minmax_at_ends,
    segmented_sum,
)
from risingwave_tpu.common.hash import hash64_columns
from risingwave_tpu.common.types import Field, Schema
from risingwave_tpu.expr.node import Expr, InputRef
from risingwave_tpu.expr.agg import AggCall
from risingwave_tpu.state.hash_table import HashTable, gather_key, keys_equal
from risingwave_tpu.stream.executor import Executor


#: representatives the chip branch of ``HashAggExecutor.apply`` hands to
#: the table at a time.  Every index of a scatter or gather against a
#: table-sized array costs the v5e a fixed time, live or dropped, so a
#: chunk of one group wants the tile narrow; a tile's own fixed cost is
#: a few µs, so a chunk of thousands loses little to it.  ``apply``
#: alone, ms a chunk at 64 / 128 / 256 / 512: one group in 8,192 rows
#: 0.85 / 0.90 / 1.04 / 1.18; 8,192 groups in 40,960 rows 18.0 / 17.8 /
#: 15.6 / 15.2 (PERF.md §6, PR 27).
REP_TILE = 128


class AggState(NamedTuple):
    table: HashTable
    #: flattened per-primitive state arrays, each [size]
    prims: tuple
    row_count: jnp.ndarray      # int64 [size]
    dirty: jnp.ndarray          # bool [size]
    prev_prims: tuple           # snapshot at last flush
    prev_row_count: jnp.ndarray
    emitted: jnp.ndarray        # bool [size] — group present downstream
    overflow: jnp.ndarray       # int64 scalar — rows lost to full table
    #: deletes that hit a non-retractable (min/max) state — the
    #: consistency_error! analog (ref src/stream/src/lib.rs:93); the
    #: runtime surfaces this at barrier time
    inconsistency: jnp.ndarray  # int64 scalar
    #: latest watermark received (EOWC emission; INT64_MIN = none)
    wm: jnp.ndarray             # int64 scalar
    #: materialized input of each retractable min/max agg (ref
    #: minput.rs): a table keyed (group keys..., value), the value's
    #: multiplicity int64 [M] and the group-table slot it belongs to
    #: int32 [M] (kept through ``maybe_rehash``); ``minput_stale`` says
    #: a chunk changed a table since the prim caches were last taken
    minput_tables: tuple = ()
    minput_counts: tuple = ()
    minput_gslot: tuple = ()
    minput_stale: jnp.ndarray = ()
    #: per-DISTINCT-call dedup state (ref distinct.rs dedup tables):
    #: a hash table keyed (group keys..., arg) and an int64 [size]
    #: row-count per key — 0↔nonzero transitions drive the agg update
    distinct_tables: tuple = ()
    distinct_counts: tuple = ()
    #: spill ring: INPUT rows whose group could not claim a device slot
    #: divert here instead of being dropped; the runtime drains the
    #: ring at snapshot barriers into the host-resident overflow tier
    #: (stream/spill.py — the state_table.rs "state beyond memory is
    #: the norm" analog)
    spill_rows: tuple = ()
    spill_ops: jnp.ndarray = ()
    spill_count: jnp.ndarray = ()
    #: tallies of ``apply`` (int64 scalars; ``fragment.TALLY_ATTRS``
    #: carries them to ``/metrics`` with the barrier's counters vector):
    #: chunks applied, and on the chip branch the group representatives
    #: that probed the table and the ``REP_TILE``-wide tiles they took
    apply_chunks: jnp.ndarray = ()
    rep_rows: jnp.ndarray = ()
    rep_tiles: jnp.ndarray = ()
    #: values put into or taken out of materialised input, and the
    #: flush calls that had a group to emit (a barrier's drain rounds)
    minput_changes: jnp.ndarray = ()
    flush_rounds: jnp.ndarray = ()
    #: groups the watermark retired (``clean_below``), and rows a flush
    #: handed downstream (a U-/U+ pair counts two)
    cleaned_groups: jnp.ndarray = ()
    emit_rows: jnp.ndarray = ()
    #: rows lost to a full materialised-input / distinct dedup table
    #: (``fragment.COUNTER_ATTRS``: the barrier raises, naming the store)
    minput_overflow: jnp.ndarray = ()
    distinct_overflow: jnp.ndarray = ()
    #: tallies of ``maybe_rehash``: passes that found a tombstone in the
    #: group table, and the tombstones they gave back
    reclaim_passes: jnp.ndarray = ()
    reclaim_slots: jnp.ndarray = ()
    #: the group table as the last ``maybe_rehash`` found it, before it
    #: reclaimed (``fragment.GAUGE_ATTRS``): its fullest in the barrier
    live_groups: jnp.ndarray = ()
    tombstones: jnp.ndarray = ()
    #: distinct (group, value) pairs the materialised-input tables held
    minput_live_values: jnp.ndarray = ()
    #: the group table's size, so that a job's levels (summed over its
    #: aggregates) read as a share of its tables
    table_slots: jnp.ndarray = ()


def _empty_input_col(f: Field, n: int):
    """Zeroed [n] storage for one input-schema column (NCol-aware)."""
    if f.data_type.is_string:
        base = StrCol(
            jnp.zeros((n, f.str_width), jnp.uint8),
            jnp.zeros((n,), jnp.int32),
        )
    else:
        base = jnp.zeros((n,), f.data_type.physical_dtype)
    if f.nullable:
        return NCol(base, jnp.zeros((n,), jnp.bool_))
    return base


def _scatter_input_col(store, pos, col):
    """Scatter a chunk column into [R] storage (NCol/StrCol-aware)."""
    if isinstance(store, NCol):
        return NCol(
            _scatter_input_col(store.data, pos,
                               col.data if isinstance(col, NCol)
                               else col),
            store.null.at[pos].set(
                col.null if isinstance(col, NCol)
                else jnp.zeros(pos.shape, jnp.bool_),
                mode="drop",
            ),
        )
    if isinstance(store, StrCol):
        return StrCol(
            store.data.at[pos].set(col.data, mode="drop"),
            store.lens.at[pos].set(col.lens, mode="drop"),
        )
    return store.at[pos].set(col, mode="drop")


def _interleave(old, new):
    """[n] + [n] -> [2n] with old at even, new at odd positions."""
    if isinstance(old, NCol):
        return NCol(
            _interleave(old.data, new.data), _interleave(old.null, new.null)
        )
    if isinstance(old, StrCol):
        return StrCol(
            _interleave(old.data, new.data), _interleave(old.lens, new.lens)
        )
    return jnp.stack([old, new], axis=1).reshape(
        (old.shape[0] * 2,) + old.shape[1:]
    )


def _counted_update(table: HashTable, counts, key_cols, eligible, signs):
    """Fold a chunk's +1/-1 rows into a counted table (key ->
    multiplicity): the dedup table of a DISTINCT call and the
    materialised input of a retractable min/max.  A row finds or claims
    its key's slot, the slot's count moves by the row's sign, and a key
    whose count is no longer positive gives the slot up (a tombstone
    until the next reclaim).  A key's net change decides, so the order
    of a chunk's rows does not matter.

    Returns ``(table, counts, slots, ok, n0, n1, n_over, n_bad)``:
    ``ok`` the eligible rows that found a slot, ``n0`` / ``n1`` their
    key's count before and after the chunk, ``n_over`` rows the table
    was full for, ``n_bad`` rows whose key went below zero (a delete of
    what was never inserted: the consistency_error! analog)."""
    table, slots, ins, over = table.lookup_or_insert(key_cols, eligible)
    size = table.size
    n_over = jnp.sum((over & eligible).astype(jnp.int64))
    ok = eligible & ~over
    safe = jnp.minimum(slots, size - 1)
    # a claimed slot may be a reclaimed one: its count is stale
    counts = counts.at[
        jnp.where(ins, slots, jnp.int32(size))].set(0, mode="drop")
    n0 = counts[safe]
    counts = counts.at[jnp.where(ok, safe, jnp.int32(size))].add(
        jnp.where(ok, signs.astype(jnp.int64), 0), mode="drop")
    n1 = counts[safe]
    n_bad = jnp.sum((ok & (n1 < 0)).astype(jnp.int64))
    table = table.clear_slots(slots, ok & (n1 <= 0))
    return table, counts, slots, ok, n0, n1, n_over, n_bad


class HashAggExecutor(Executor):
    """GROUP BY aggregation over a device hash table."""

    emits_on_apply = False
    emits_on_flush = True
    #: the scopes of ``maybe_rehash`` and ``on_watermark`` (the
    #: watermark's ``clean_below``) in a device profile
    maintain_phase = "reclaim"
    watermark_phase = "clean"

    def __init__(
        self,
        in_schema: Schema,
        group_by: Sequence[tuple[str, Expr]],
        aggs: Sequence[AggCall],
        table_size: int = 1 << 16,
        emit_capacity: int = 4096,
        watermark_group_idx: int | None = None,
        watermark_lag: int = 0,
        watermark_src_col: int | None = None,
        emit_on_window_close: bool = False,
        retractable_input: bool = False,
        minput_table_size: int | None = None,
        distinct_table_size: int | None = None,
        spill_ring: int = 0,
    ):
        super().__init__(in_schema)
        #: overflow-row ring capacity (0 = overflow is a hard error);
        #: the planner enables this for non-windowed aggregations whose
        #: key cardinality is unbounded
        self.spill_ring = spill_ring
        self._ctor_kwargs = dict(
            in_schema=in_schema, group_by=tuple(group_by),
            aggs=tuple(aggs), emit_capacity=emit_capacity,
            watermark_group_idx=watermark_group_idx,
            watermark_lag=watermark_lag,
            watermark_src_col=watermark_src_col,
            emit_on_window_close=emit_on_window_close,
            retractable_input=retractable_input,
        )
        #: EOWC (ref emit_on_window_close plan property): flush emits
        #: only CLOSED windows as final append-only rows and evicts them
        self.emit_on_window_close = emit_on_window_close
        if emit_on_window_close and watermark_group_idx is None:
            raise ValueError(
                "EMIT ON WINDOW CLOSE needs a watermarked window group key"
            )
        self.group_by = tuple(group_by)
        self.aggs = tuple(aggs)
        #: when set, watermarks clean groups whose key[idx] < wm - lag
        #: (lag = window size for tumble windows: a window closes when
        #: the watermark passes window_start + size)
        self.watermark_group_idx = watermark_group_idx
        self.watermark_lag = watermark_lag
        #: only react to Watermark messages with this source col_idx
        #: (None = any — single-watermark fragments)
        self.watermark_src_col = watermark_src_col
        self.table_size = table_size
        self.emit_capacity = emit_capacity
        key_fields = tuple(
            Field(name, e.return_field(in_schema).data_type,
                  str_width=e.return_field(in_schema).str_width,
                  decimal_scale=e.return_field(in_schema).decimal_scale,
                  nullable=e.return_field(in_schema).nullable)
            for name, e in self.group_by
        )
        agg_fields = tuple(a.out_field(in_schema) for a in self.aggs)
        self._out_schema = Schema(key_fields + agg_fields)
        # primitive-state layout: per agg, its PrimStates flattened
        self._prim_specs = []  # (agg_idx, PrimState)
        for ai, a in enumerate(self.aggs):
            for ps in a.spec().states:
                self._prim_specs.append((ai, ps))
        #: retractable min/max via materialized input (ref minput.rs);
        #: their prim arrays become flush-time caches.  The tables hold
        #: distinct (group, value) pairs: ``minput_table_size`` slots
        #: each (None = the group table's size)
        self.minput_table_size = minput_table_size or table_size
        self._minput_aggs: list[int] = [
            ai for ai, a in enumerate(self.aggs)
            if retractable_input and a.kind in ("min", "max")
        ]
        #: prim indices whose arrays are minput caches (no apply scatter)
        self._cache_prims = {
            pi for pi, (ai, _) in enumerate(self._prim_specs)
            if ai in self._minput_aggs
        }
        #: the cache prim of each materialised-input aggregate
        self._minput_prim = [
            next(pi for pi, (ai, _) in enumerate(self._prim_specs)
                 if ai == agg_idx)
            for agg_idx in self._minput_aggs
        ]
        #: DISTINCT calls with their own counted dedup tables (ref
        #: distinct.rs); min/max are distinct-insensitive and handled
        #: as plain calls
        self.distinct_table_size = distinct_table_size or table_size
        self._distinct_aggs: list[int] = [
            ai for ai, a in enumerate(self.aggs)
            if a.distinct and a.kind not in ("min", "max")
        ]
        # hidden non-null-count prims: an aggregate over a NULLABLE
        # argument yields SQL NULL when every argument row in the group
        # is NULL (ref AggregateFunction semantics); count() needs no
        # helper (its own state IS the non-null count)
        from risingwave_tpu.expr.agg import _ADD_COUNT
        self._nn_prim: dict[int, int] = {}
        for ai, a in enumerate(self.aggs):
            if a.arg is None or a.kind in ("count", "count_star"):
                continue
            # a FILTER clause makes any aggregate's input set possibly
            # empty even over a NOT NULL argument → same NULL-output
            # tracking as a nullable argument
            if a.arg.return_field(in_schema).nullable \
                    or a.filter is not None:
                self._nn_prim[ai] = len(self._prim_specs)
                self._prim_specs.append((ai, _ADD_COUNT))

    @property
    def out_schema(self) -> Schema:
        return self._out_schema

    # ------------------------------------------------------------------
    def _key_protos(self):
        """Zero-row prototypes of the key columns for table creation.

        Nullable group keys store as NCol (payload + null plane): the
        table's grouping equality treats NULL == NULL, so NULLs form
        one group like the reference's GROUP BY."""
        protos = []
        for _, e in self.group_by:
            f = e.return_field(self.in_schema)
            if f.data_type.is_string:
                p = StrCol(
                    jnp.zeros((1, f.str_width), jnp.uint8),
                    jnp.zeros((1,), jnp.int32),
                )
            else:
                p = jnp.zeros((1,), f.data_type.physical_dtype)
            if f.nullable:
                p = NCol(p, jnp.zeros((1,), jnp.bool_))
            protos.append(p)
        return protos

    def _input_dtype(self, agg_idx: int):
        a = self.aggs[agg_idx]
        if a.arg is None:
            return jnp.int64
        return a.arg.return_field(self.in_schema).data_type.physical_dtype

    def _distinct_protos(self, agg_idx: int) -> list:
        """Key prototypes of a distinct call's dedup table:
        (group keys..., arg)."""
        f = self.aggs[agg_idx].arg.return_field(self.in_schema)
        if f.data_type.is_string:
            p = StrCol(
                jnp.zeros((1, f.str_width), jnp.uint8),
                jnp.zeros((1,), jnp.int32),
            )
        else:
            p = jnp.zeros((1,), f.data_type.physical_dtype)
        if f.nullable:
            p = NCol(p, jnp.zeros((1,), jnp.bool_))
        return self._key_protos() + [p]

    def _minput_protos(self, agg_idx: int) -> list:
        """Key prototypes of a materialised-input table: (group
        keys..., value), the value bare (a NULL argument is no input)."""
        f = self.aggs[agg_idx].arg.return_field(self.in_schema)
        return self._key_protos() + [
            jnp.zeros((1,), f.data_type.physical_dtype)]

    def init_state(self) -> AggState:
        size = self.table_size
        table = HashTable.create(self._key_protos(), size)
        def make_prims():
            out = []
            for agg_idx, ps in self._prim_specs:
                in_dt = self._input_dtype(agg_idx)
                st_dt = ps.dtype(in_dt)
                out.append(jnp.full((size,), ps.init(st_dt), st_dt))
            return tuple(out)

        M = self.minput_table_size
        return AggState(
            table=table,
            # prev_prims must be INDEPENDENT buffers (donation forbids
            # the same buffer appearing twice in a donated pytree)
            prims=make_prims(),
            row_count=jnp.zeros((size,), jnp.int64),
            dirty=jnp.zeros((size,), jnp.bool_),
            prev_prims=make_prims(),
            prev_row_count=jnp.zeros((size,), jnp.int64),
            emitted=jnp.zeros((size,), jnp.bool_),
            overflow=jnp.zeros((), jnp.int64),
            inconsistency=jnp.zeros((), jnp.int64),
            wm=jnp.asarray(np.iinfo(np.int64).min, jnp.int64),
            minput_tables=tuple(
                HashTable.create(self._minput_protos(ai), M)
                for ai in self._minput_aggs
            ),
            minput_counts=tuple(
                jnp.zeros((M,), jnp.int64) for _ in self._minput_aggs
            ),
            minput_gslot=tuple(
                jnp.full((M,), size, jnp.int32) for _ in self._minput_aggs
            ),
            minput_stale=jnp.zeros((), jnp.bool_),
            distinct_tables=tuple(
                HashTable.create(self._distinct_protos(ai),
                                 self.distinct_table_size)
                for ai in self._distinct_aggs
            ),
            distinct_counts=tuple(
                jnp.zeros((self.distinct_table_size,), jnp.int64)
                for _ in self._distinct_aggs
            ),
            spill_rows=tuple(
                _empty_input_col(f, self.spill_ring)
                for f in self.in_schema
            ) if self.spill_ring else (),
            spill_ops=jnp.zeros((self.spill_ring,), jnp.int8)
            if self.spill_ring else (),
            spill_count=jnp.zeros((), jnp.int32)
            if self.spill_ring else (),
            apply_chunks=jnp.zeros((), jnp.int64),
            rep_rows=jnp.zeros((), jnp.int64),
            rep_tiles=jnp.zeros((), jnp.int64),
            minput_changes=jnp.zeros((), jnp.int64),
            flush_rounds=jnp.zeros((), jnp.int64),
            cleaned_groups=jnp.zeros((), jnp.int64),
            emit_rows=jnp.zeros((), jnp.int64),
            minput_overflow=jnp.zeros((), jnp.int64),
            distinct_overflow=jnp.zeros((), jnp.int64),
            reclaim_passes=jnp.zeros((), jnp.int64),
            reclaim_slots=jnp.zeros((), jnp.int64),
            live_groups=jnp.zeros((), jnp.int64),
            tombstones=jnp.zeros((), jnp.int64),
            minput_live_values=jnp.zeros((), jnp.int64),
            table_slots=jnp.asarray(size, jnp.int64),
        )

    # ------------------------------------------------------------------
    def apply(self, state: AggState, chunk: Chunk):
        """Apply one chunk of updates; backend-adaptive strategy.

        TPU: chunk-local pre-aggregation.  A scatter or gather against a
        table-sized array costs the chip a fixed time for every index it
        is handed, dropped or not (PERF.md §5), while a sort or a
        segmented scan of the whole chunk costs less than a tenth of one
        such scatter.  So the chunk is sorted by key hash, adjacent
        equal keys form segments, each primitive contribution is
        segment-reduced, and only each segment's END row (its
        "representative") probes the table and scatters — compacted to
        the front and taken ``REP_TILE`` at a time, so the table-sized
        arrays see O(distinct keys) indices instead of O(chunk).

        CPU: scatters are cheap and sorts are not, so the chunk probes
        and scatters per-row with no sort at all (the round-1 shape; the
        round-2 always-sort version was the "4x q7 regression")."""
        signs = chunk.signs()
        valid = chunk.valid
        cap = valid.shape[0]
        key_cols = [
            conform_col(e.eval(chunk),
                        e.return_field(self.in_schema).nullable, cap)
            for _, e in self.group_by
        ]

        h = hash64_columns(key_cols)
        preagg = accel_tuned()
        if preagg:
            # invalid rows sort to the very end under the all-ones
            # sentinel (hash64_columns never returns ~0, so no valid
            # row lands there, and the sorted key says which rows are)
            sort_key = jnp.where(valid, h, ~jnp.uint64(0))
            s_h, perm = jax.lax.sort_key_val(
                sort_key, jnp.arange(cap, dtype=jnp.int32)
            )
            s_valid = s_h != ~jnp.uint64(0)
            s_signs = signs[perm]
            s_keys = [gather_key(c, perm) for c in key_cols]
            # segment boundary: hash differs OR any key column differs
            # (hash collisions between distinct keys stay distinct)
            neq = s_h[1:] != s_h[:-1]
            for c in s_keys:
                # every leaf of a key column has the rows first: slices,
                # where a gather by ``arange`` stays a chunk-wide gather
                neq = neq | ~keys_equal(
                    jax.tree.map(lambda x: x[1:], c),
                    jax.tree.map(lambda x: x[:-1], c))
            starts = segment_starts(neq)
            ends = jnp.concatenate([neq, jnp.ones((1,), jnp.bool_)])
            rep = ends & s_valid
            start_pos = segment_start_positions(starts)
            # unique, monotone segment id (hash-collision-split
            # segments of equal s_h must not merge in the min/max
            # secondary sort)
            seg_id = jnp.cumsum(starts.astype(jnp.int32))

            # representatives' sorted positions compacted to the front
            # (ascending), padded to whole tiles; a tile is REP_TILE of
            # them, and how many tiles run follows the chunk
            K = min(REP_TILE, cap)
            n_rep = jnp.sum(rep.astype(jnp.int32))
            n_tiles = (n_rep + (K - 1)) // K
            row = jnp.arange(cap, dtype=jnp.int32)
            rep_pos = jnp.concatenate([
                jax.lax.sort(jnp.where(rep, row, cap)),
                jnp.full((-cap % K,), cap, jnp.int32),
            ])
            padded = rep_pos.shape[0]

            def tile(t):
                """(sorted positions, liveness) of tile ``t``'s reps."""
                pos = jax.lax.dynamic_slice(rep_pos, (t * K,), (K,))
                return jnp.minimum(pos, cap - 1), pos < cap

            def probe_tile(t, carry):
                table, rep_slot, rep_ins, rep_over, n_over = carry
                pos, live = tile(t)
                table, slots, ins, over = table.lookup_or_insert(
                    [gather_key(c, pos) for c in s_keys], live,
                    hashes=s_h[pos],
                )
                # an overflowed representative drops its whole segment
                # (all of it valid, and ending at ``pos``)
                n_over = n_over + jnp.sum(jnp.where(
                    over, pos - start_pos[pos] + 1, 0
                ).astype(jnp.int64))
                at = (t * K,)
                return (
                    table,
                    jax.lax.dynamic_update_slice(rep_slot, slots, at),
                    jax.lax.dynamic_update_slice(rep_ins, ins, at),
                    jax.lax.dynamic_update_slice(rep_over, over, at),
                    n_over,
                )

            # tiles run in order, so a later tile finds the keys an
            # earlier one inserted, as the losers of a claim race find
            # the winner's within one wide probe
            table, rep_slot, rep_ins, rep_over, n_over = jax.lax.fori_loop(
                0, n_tiles, probe_tile, (
                    state.table,
                    jnp.full((padded,), self.table_size, jnp.int32),
                    jnp.zeros((padded,), jnp.bool_),
                    jnp.zeros((padded,), jnp.bool_),
                    jnp.zeros((), jnp.int64),
                ),
            )
            if self.spill_ring or self._minput_aggs:
                # the paths that need a slot for every ROW: a row's
                # representative is the first at or after it, so as many
                # lie before the row as before its representative
                row_rep = jnp.cumsum(rep.astype(jnp.int32)) \
                    - rep.astype(jnp.int32)
            if self.spill_ring:
                # back to the chunk's order: sorting by ``perm`` is the
                # inverse permutation without a chunk-wide scatter
                _, spilled = jax.lax.sort_key_val(
                    perm, (s_valid & rep_over[row_rep]).astype(jnp.int32)
                )
                spill_mask = spilled > 0
        else:
            perm = None
            n_rep = n_tiles = 0
            s_signs = signs
            table, slots, inserted, overflow = state.table.lookup_or_insert(
                key_cols, valid, hashes=h
            )
            n_over = jnp.sum((overflow & valid).astype(jnp.int64))
            if self.spill_ring:
                spill_mask = valid & overflow
        spill_rows = state.spill_rows
        spill_ops = state.spill_ops
        spill_count = state.spill_count
        if self.spill_ring:
            # divert overflow rows into the ring (original chunk order);
            # only rows the ring itself cannot hold stay in n_over.
            # The capture runs under lax.cond so the CLEAN path (no
            # overflow — the steady state) skips the ring scatters.
            R = self.spill_ring

            def capture(args):
                spill_rows, spill_ops, spill_count = args
                rank = jnp.cumsum(spill_mask.astype(jnp.int32)) - \
                    spill_mask.astype(jnp.int32)
                pos = spill_count + rank
                ok = spill_mask & (pos < R)
                tgt = jnp.where(ok, pos, jnp.int32(R))
                rows = tuple(
                    _scatter_input_col(store, tgt, col)
                    for store, col in zip(spill_rows, chunk.columns)
                )
                ops2 = spill_ops.at[tgt].set(chunk.ops, mode="drop")
                cnt = jnp.minimum(
                    spill_count + jnp.sum(spill_mask.astype(jnp.int32)),
                    jnp.int32(R),
                ).astype(jnp.int32)
                dropped = jnp.sum((spill_mask & ~ok).astype(jnp.int64))
                return rows, ops2, cnt, dropped

            def skip(args):
                rows, ops2, cnt = args
                return rows, ops2, cnt, jnp.zeros((), jnp.int64)

            spill_rows, spill_ops, spill_count, n_over = jax.lax.cond(
                jnp.any(spill_mask), capture, skip,
                (spill_rows, spill_ops, spill_count),
            )
        #: prim index -> the update each row (CPU) or each segment's END
        #: row (chip) brings to its group
        segs: dict[int, jnp.ndarray] = {}
        arg_cache: dict[int, jnp.ndarray] = {}
        filt_cache: dict[int, jnp.ndarray] = {}

        def filter_mask(a, agg_idx):
            """bool [cap] FILTER (WHERE ...) mask; NULL = excluded."""
            if a.filter is None:
                return None
            if agg_idx not in filt_cache:
                fcol, fnull = split_col(a.filter.eval(chunk))
                filt_cache[agg_idx] = fcol if fnull is None \
                    else fcol & ~fnull
            return filt_cache[agg_idx]

        # DISTINCT dedup (ref distinct.rs): per call, count rows per
        # (group, value) key; only 0↔nonzero transitions reach the
        # aggregate — emitted as a ±1 "transition sign" at one
        # representative row per key, zero elsewhere.  The transition
        # depends only on the key's net delta, so in-chunk ordering is
        # irrelevant.
        d_tables = list(state.distinct_tables)
        d_counts = list(state.distinct_counts)
        d_signs: dict[int, jnp.ndarray] = {}
        n_over_d = jnp.zeros((), jnp.int64)
        n_bad_d = jnp.zeros((), jnp.int64)
        if self._distinct_aggs:
            from risingwave_tpu.stream.hash_join import _rank_by
            for di, agg_idx in enumerate(self._distinct_aggs):
                a = self.aggs[agg_idx]
                if agg_idx not in arg_cache:
                    arg_cache[agg_idx] = a.arg.eval(chunk)
                acol = arg_cache[agg_idx]
                _, anull = split_col(acol)
                eligible = valid & (signs != 0)
                if self.spill_ring:
                    # diverted rows replay in the tier's own dedup state
                    eligible = eligible & ~spill_mask
                if anull is not None:
                    eligible = eligible & ~anull
                fm = filter_mask(a, agg_idx)
                if fm is not None:
                    eligible = eligible & fm
                (d_tables[di], d_counts[di], dslots, eligible, n0, n1,
                 over_d, bad_d) = _counted_update(
                    d_tables[di], d_counts[di], key_cols + [acol],
                    eligible, signs)
                n_over_d = n_over_d + over_d
                n_bad_d = n_bad_d + bad_d
                first = eligible & (
                    _rank_by(dslots.astype(jnp.uint64), eligible) == 0
                )
                d_signs[agg_idx] = jnp.where(
                    first,
                    (n1 > 0).astype(jnp.int64)
                    - (n0 > 0).astype(jnp.int64),
                    0,
                )
        for pi, (agg_idx, ps) in enumerate(self._prim_specs):
            a = self.aggs[agg_idx]
            if pi in self._cache_prims:
                continue  # minput cache: recomputed at flush
            if a.arg is None:
                col = jnp.ones_like(signs, jnp.int64)
            else:
                if agg_idx not in arg_cache:
                    arg_cache[agg_idx] = a.arg.eval(chunk)
                col = arg_cache[agg_idx]
            # NULL arguments contribute nothing (SQL: aggregates skip
            # NULLs): zero the sign, which every lift mode maps to its
            # identity element.  The payload is zeroed too — a NULL
            # row's payload is unspecified (e.g. inf from x/NULL) and
            # inf * 0 would poison additive states with NaN.
            col, col_null = split_col(col)
            if col_null is not None and not isinstance(col, StrCol):
                col = jnp.where(col_null, jnp.zeros((), col.dtype), col)
            fm = filter_mask(a, agg_idx)
            if perm is None:
                if agg_idx in d_signs:
                    # DISTINCT: the dedup pass already folded filter/
                    # NULL/duplicate semantics into ±1 transition signs
                    prim_signs = d_signs[agg_idx]
                else:
                    prim_signs = signs if col_null is None else jnp.where(
                        col_null, 0, signs
                    )
                    if fm is not None:
                        prim_signs = jnp.where(fm, prim_signs, 0)
                # per-row update scattered directly (invalid rows carry
                # sign 0 ⇒ identity, and sentinel slots drop)
                seg = ps.lift(col, prim_signs)
            else:
                if agg_idx in d_signs:
                    prim_signs = d_signs[agg_idx][perm]
                else:
                    prim_signs = s_signs if col_null is None \
                        else jnp.where(col_null[perm], 0, s_signs)
                    if fm is not None:
                        prim_signs = jnp.where(fm[perm], prim_signs, 0)
                # per-row lift in sorted order, then segment-reduce:
                # the value at each segment END is the segment's update
                contrib = ps.lift(gather_key(col, perm), prim_signs)
                if ps.mode == "add":
                    seg = segmented_sum(contrib, start_pos)
                else:
                    seg = segmented_minmax_at_ends(
                        seg_id, contrib, start_pos, ps.mode
                    )
            segs[pi] = seg
        if perm is None:
            prims, row_count, dirty = self._scatter_groups(
                state.prims, state.row_count, state.dirty,
                slots, inserted, segs, signs.astype(jnp.int64),
            )
        else:
            seg_signs = segmented_sum(s_signs.astype(jnp.int64), start_pos)

            def scatter_tile(t, carry):
                pos, _ = tile(t)
                at = (t * K,)
                return self._scatter_groups(
                    *carry,
                    jax.lax.dynamic_slice(rep_slot, at, (K,)),
                    jax.lax.dynamic_slice(rep_ins, at, (K,)),
                    {pi: seg[pos] for pi, seg in segs.items()},
                    seg_signs[pos],
                )

            prims, row_count, dirty = jax.lax.fori_loop(
                0, n_tiles, scatter_tile,
                (state.prims, state.row_count, state.dirty),
            )

        # materialized-input updates (retractable min/max): every row
        # moves the count of its (group, value) pair and leaves the
        # pair its group's slot — per-row slots come from the per-row
        # probe (CPU) or from each row's representative (TPU)
        minput_tables = list(state.minput_tables)
        minput_counts = list(state.minput_counts)
        minput_gslot = list(state.minput_gslot)
        n_over_mi = jnp.zeros((), jnp.int64)
        n_miss_mi = jnp.zeros((), jnp.int64)
        n_changes = jnp.zeros((), jnp.int64)
        if self._minput_aggs:
            if perm is None:
                row_slots = slots
                row_keys = key_cols
                row_ok = valid & (row_slots < self.table_size)
            else:
                # segments whose representative overflowed keep the
                # `size` sentinel and their rows are skipped (already
                # counted in n_over)
                row_slots = jnp.where(
                    s_valid, rep_slot[row_rep], self.table_size
                )
                row_keys = s_keys
                row_ok = row_slots < self.table_size
            for mi, agg_idx in enumerate(self._minput_aggs):
                a = self.aggs[agg_idx]
                if agg_idx not in arg_cache:
                    arg_cache[agg_idx] = a.arg.eval(chunk)
                vcol, vnull = split_col(arg_cache[agg_idx])
                v_rows = vcol if perm is None else gather_key(vcol, perm)
                active = row_ok & (s_signs != 0)
                if vnull is not None:
                    active = active & ~(
                        vnull if perm is None else vnull[perm]
                    )
                fm = filter_mask(a, agg_idx)
                if fm is not None:
                    active = active & (fm if perm is None else fm[perm])
                (minput_tables[mi], minput_counts[mi], mslots, ok, _, _,
                 over, miss) = _counted_update(
                    minput_tables[mi], minput_counts[mi],
                    list(row_keys) + [v_rows], active, s_signs)
                M = self.minput_table_size
                minput_gslot[mi] = minput_gslot[mi].at[
                    jnp.where(ok, mslots, jnp.int32(M))
                ].set(row_slots, mode="drop")
                n_over_mi = n_over_mi + over
                n_miss_mi = n_miss_mi + miss
                n_changes = n_changes + jnp.sum(ok.astype(jnp.int64))

        n_bad = jnp.zeros((), jnp.int64)
        if any(not a.spec().retractable and ai not in self._minput_aggs
               for ai, a in enumerate(self.aggs)):
            n_bad = jnp.sum((valid & (signs < 0)).astype(jnp.int64))
        return state._replace(
            table=table,
            prims=prims,
            row_count=row_count,
            dirty=dirty,
            overflow=state.overflow + n_over,
            minput_overflow=state.minput_overflow + n_over_mi,
            distinct_overflow=state.distinct_overflow + n_over_d,
            inconsistency=state.inconsistency + n_bad + n_miss_mi
            + n_bad_d,
            minput_tables=tuple(minput_tables),
            minput_counts=tuple(minput_counts),
            minput_gslot=tuple(minput_gslot),
            minput_stale=state.minput_stale | (n_changes > 0),
            minput_changes=state.minput_changes + n_changes,
            distinct_tables=tuple(d_tables),
            distinct_counts=tuple(d_counts),
            spill_rows=spill_rows,
            spill_ops=spill_ops,
            spill_count=spill_count,
            apply_chunks=state.apply_chunks + 1,
            rep_rows=state.rep_rows + n_rep,
            rep_tiles=state.rep_tiles + n_tiles,
        ), None

    def _scatter_groups(self, prims, row_count, dirty, slots, inserted,
                        segs, seg_signs):
        """Fold one update per entry of ``slots`` into the per-slot
        state arrays (``size`` = dropped), at whatever width ``slots``
        has: the chunk's on the CPU, one tile's on the chip."""
        # freshly claimed slots may be reclaimed after state cleaning —
        # reset their (stale) state before applying updates
        ins_pos = jnp.where(inserted, slots, jnp.int32(self.table_size))
        prims = list(prims)
        for pi, seg in segs.items():
            ps = self._prim_specs[pi][1]
            p = prims[pi]
            p = p.at[ins_pos].set(ps.init(p.dtype), mode="drop")
            if ps.mode == "add":
                p = p.at[slots].add(seg, mode="drop")
            elif ps.mode == "min":
                p = p.at[slots].min(seg, mode="drop")
            else:
                p = p.at[slots].max(seg, mode="drop")
            prims[pi] = p
        row_count = row_count.at[ins_pos].set(0, mode="drop")
        row_count = row_count.at[slots].add(seg_signs, mode="drop")
        dirty = dirty.at[slots].set(True, mode="drop")
        return tuple(prims), row_count, dirty

    def reconstructible_from_rows(self) -> bool:
        """True when the agg's full state round-trips through its own
        input rows: plain InputRef keys in order and one sum/sum0/min/
        max call per trailing input column — exactly the GLOBAL half of
        a two-phase pair (translated_global_calls).  Such an agg can be
        rebuilt on a different mesh by re-applying extracted rows (the
        online-rescale path, ref scale.rs: state follows vnodes)."""
        n_keys = len(self.group_by)
        for ki, (_, e) in enumerate(self.group_by):
            if not (isinstance(e, InputRef) and e.index == ki):
                return False
        if self._minput_aggs or self._distinct_aggs:
            return False
        for ai, a in enumerate(self.aggs):
            if a.kind not in ("sum", "sum0", "min", "max") \
                    or a.distinct or a.filter is not None:
                return False
            if not (isinstance(a.arg, InputRef)
                    and a.arg.index == n_keys + ai):
                return False
            if self.in_schema[n_keys + ai].data_type.is_string:
                # string min/max state is a PACKED int64 (_pack_str8);
                # extract_chunk cannot emit it as the string input col
                return False
        return len(self.in_schema) == n_keys + len(self.aggs)

    def extract_chunk(self, state_host) -> Chunk:
        """One INPUT-schema chunk holding every live group's state
        (host arrays; capacity = table_size).  Re-applying it to a
        fresh state reconstructs the aggregation exactly — valid only
        when ``reconstructible_from_rows()``."""
        n_keys = len(self.group_by)
        cols = list(state_host.table.key_cols)
        pi = 0
        for ai, a in enumerate(self.aggs):
            spec = a.spec()
            val = state_host.prims[pi]
            pi += len(spec.states)
            f = self.in_schema[n_keys + ai]
            if f.nullable and ai in self._nn_prim:
                nn = state_host.prims[self._nn_prim[ai]]
                val = NCol(jnp.asarray(val), jnp.asarray(nn == 0))
            cols.append(val)
        occ = jnp.asarray(state_host.table.occupied)
        return Chunk(
            tuple(jnp.asarray(c) if not isinstance(c, (NCol, StrCol))
                  else c for c in cols),
            jnp.zeros((self.table_size,), jnp.int8),
            occ, self.in_schema,
        )

    def drain_spill(self, state: AggState):
        """(state with an empty ring, Chunk of the diverted rows).

        Jitted by the runtime at snapshot barriers; the chunk feeds the
        host overflow tier (stream/spill.py)."""
        R = self.spill_ring
        valid = jnp.arange(R, dtype=jnp.int32) < state.spill_count
        chunk = Chunk(state.spill_rows, state.spill_ops, valid,
                      self.in_schema)
        return state._replace(
            spill_count=jnp.zeros((), jnp.int32)
        ), chunk

    def make_spill_tier(self, table_size: int) -> "HashAggExecutor":
        """A same-shaped aggregation for the host (CPU) overflow tier."""
        return HashAggExecutor(
            table_size=table_size,
            distinct_table_size=max(table_size,
                                    self.distinct_table_size),
            **self._ctor_kwargs,
        )

    # ------------------------------------------------------------------
    def _outputs(self, prims: tuple, row_count, slots):
        """Per-emitted-slot output columns from the state arrays."""
        size = self.table_size
        safe = jnp.minimum(slots, size - 1)
        cols = []
        pi = 0
        for ai, a in enumerate(self.aggs):
            spec = a.spec()
            n = len(spec.states)
            st = tuple(prims[pi + k][safe] for k in range(n))
            pi += n
            out_f = self._out_schema[len(self.group_by) + ai]
            out = spec.output(st, row_count[safe], out_f)
            if ai in self._nn_prim:
                # all argument rows NULL -> SQL NULL result
                nn = prims[self._nn_prim[ai]][safe]
                out = NCol(out, nn == 0)
            cols.append(out)
        return cols

    def _refresh_minput_caches(self, state: AggState) -> AggState:
        """Take the retractable min/max of every group again from the
        materialised input, if a chunk changed it since the last time:
        one masked pass over each table's slots, a live value folded
        into the group slot it remembers (the prim array is just a cache
        of this reduction)."""
        if not self._minput_aggs:
            return state
        size = self.table_size

        def refresh(prims):
            prims = list(prims)
            for mi, pi in enumerate(self._minput_prim):
                mt = state.minput_tables[mi]
                p = prims[pi]
                live = mt.occupied & (state.minput_counts[mi] > 0)
                at = jnp.where(live, state.minput_gslot[mi],
                               jnp.int32(size))
                ps = self._prim_specs[pi][1]
                base = jnp.full((size,), ps.init(p.dtype), p.dtype)
                vals = mt.key_cols[-1].astype(p.dtype)
                if self.aggs[self._minput_aggs[mi]].kind == "min":
                    prims[pi] = base.at[at].min(vals, mode="drop")
                else:
                    prims[pi] = base.at[at].max(vals, mode="drop")
            return tuple(prims)

        with jax.named_scope("extreme"):
            prims = jax.lax.cond(
                state.minput_stale, refresh, lambda p: p, state.prims)
        return state._replace(
            prims=prims, minput_stale=jnp.zeros((), jnp.bool_))

    def flush(self, state: AggState, epoch):
        if self.emit_on_window_close:
            return self._flush_eowc(state)
        cap = self.emit_capacity
        size = self.table_size
        slots = mask_indices(state.dirty, cap, size)
        slot_live = slots < size
        safe = jnp.minimum(slots, size - 1)
        state = self._refresh_minput_caches(state)

        old_nonempty = state.prev_row_count[safe] > 0
        new_nonempty = state.row_count[safe] > 0
        del_side = slot_live & state.emitted[safe] & old_nonempty
        ins_side = slot_live & new_nonempty

        key_vals = state.table.gather_keys(slots)
        old_cols = self._outputs(state.prev_prims, state.prev_row_count, slots)
        new_cols = self._outputs(state.prims, state.row_count, slots)

        out_cols = []
        for k in key_vals:
            out_cols.append(_interleave(k, k))
        for o, n in zip(old_cols, new_cols):
            out_cols.append(_interleave(o, n))

        both = del_side & ins_side
        op_even = jnp.where(both, OP_UPDATE_DELETE, OP_DELETE).astype(jnp.int8)
        op_odd = jnp.where(both, OP_UPDATE_INSERT, OP_INSERT).astype(jnp.int8)
        ops = _interleave(op_even, op_odd)
        valid = _interleave(del_side, ins_side)

        out = Chunk(out_cols, ops, valid, self._out_schema)

        # persist current as prev for emitted slots; clear their dirty bit.
        # un-emitted dirty slots (overflow beyond emit_capacity) stay dirty
        # and are drained by the runtime calling flush() again.
        prev_prims = tuple(
            p.at[slots].set(c[safe], mode="drop")
            for p, c in zip(state.prev_prims, state.prims)
        )
        prev_row_count = state.prev_row_count.at[slots].set(
            state.row_count[safe], mode="drop"
        )
        emitted = state.emitted.at[slots].set(new_nonempty, mode="drop")
        dirty = state.dirty.at[slots].set(False, mode="drop")
        return state._replace(
            dirty=dirty,
            prev_prims=prev_prims,
            prev_row_count=prev_row_count,
            emitted=emitted,
            flush_rounds=state.flush_rounds
            + jnp.any(slot_live).astype(jnp.int64),
            emit_rows=state.emit_rows + jnp.sum(valid, dtype=jnp.int64),
        ), out

    def _closed_mask(self, state: AggState) -> jnp.ndarray:
        key, key_null = split_col(
            state.table.key_cols[self.watermark_group_idx]
        )
        no_wm = state.wm == np.iinfo(np.int64).min
        closed = state.table.occupied & (
            key + self.watermark_lag <= state.wm
        )
        if key_null is not None:
            closed = closed & ~key_null  # a NULL window never closes
        return closed & ~no_wm

    def _flush_eowc(self, state: AggState):
        """Emit final rows for closed windows; evict them (ref EOWC)."""
        cap = self.emit_capacity
        size = self.table_size
        state = self._refresh_minput_caches(state)
        closed = self._closed_mask(state)
        slots = mask_indices(closed, cap, size)
        slot_live = slots < size
        safe = jnp.minimum(slots, size - 1)
        live = slot_live & (state.row_count[safe] > 0)

        key_vals = state.table.gather_keys(slots)
        out_cols = list(key_vals) + self._outputs(
            state.prims, state.row_count, slots
        )
        out = Chunk(
            tuple(out_cols),
            jnp.full((cap,), OP_INSERT, jnp.int8),
            live,
            self._out_schema,
        )
        emitted_mask = jnp.zeros((size,), jnp.bool_).at[slots].set(
            slot_live, mode="drop"
        )
        table = state.table.clear_where(emitted_mask)
        # an evicted group's materialised input goes with it
        gone = jnp.concatenate([emitted_mask, jnp.zeros((1,), jnp.bool_)])
        return state._replace(
            table=table,
            row_count=jnp.where(emitted_mask, 0, state.row_count),
            dirty=state.dirty & ~emitted_mask,
            minput_tables=tuple(
                mt.clear_where(gone[g])
                for mt, g in zip(state.minput_tables, state.minput_gslot)
            ),
            flush_rounds=state.flush_rounds
            + jnp.any(slot_live).astype(jnp.int64),
            emit_rows=state.emit_rows + jnp.sum(live, dtype=jnp.int64),
        ), out

    def pending_dirty(self, state: AggState) -> jnp.ndarray:
        return jnp.sum(state.dirty.astype(jnp.int32))

    # runtime drain protocol
    def pending_flush(self, state: AggState) -> jnp.ndarray:
        if self.emit_on_window_close:
            return jnp.sum(self._closed_mask(state).astype(jnp.int32))
        return self.pending_dirty(state)

    def on_watermark(self, state: AggState, watermark):
        if self.watermark_group_idx is None:
            return state
        if (self.watermark_src_col is not None
                and watermark.col_idx != self.watermark_src_col):
            return state
        state = state._replace(
            wm=jnp.maximum(state.wm, jnp.int64(watermark.value))
        )
        if self.emit_on_window_close:
            return state  # emission evicts; no pre-cleaning
        return self.clean_below(
            state, self.watermark_group_idx,
            watermark.value - self.watermark_lag,
        )

    def maybe_rehash(self, state: AggState) -> AggState:
        """Give the retired groups' slots back (called by the runtime at
        checkpoint barriers, after state cleaning): whenever the group
        table holds a tombstone, ``HashTable.reclaimed`` empties them
        all and reinserts the groups whose probe chains crossed one,
        every per-slot leaf moving with its group.  The cost follows
        what the barrier retired, so q5-inner's barriers are alike and a
        table that retires a group now and then (q7-inner) pays the
        ``cond`` alone.

        Traceable: the decision is a ``lax.cond`` on the device-resident
        tombstone count, so maintenance never reads back to the host."""
        tombs = state.table.tombstone_count()
        size = self.table_size
        state = state._replace(
            live_groups=state.table.count().astype(jnp.int64),
            tombstones=tombs.astype(jnp.int64),
            minput_live_values=sum(
                (mt.count().astype(jnp.int64)
                 for mt in state.minput_tables), jnp.zeros((), jnp.int64)),
        )

        def reclaim(state: AggState) -> AggState:
            inits = [ps.init(p.dtype)
                     for (_, ps), p in zip(self._prim_specs, state.prims)]
            dense = (state.prims, state.row_count, state.dirty,
                     state.prev_prims, state.prev_row_count, state.emitted)
            fills = inits + [0, False] + inits + [0, False]
            if self._minput_aggs:
                # where each group came from, so that the materialised
                # input's values can follow their group
                dense += (jnp.arange(size, dtype=jnp.int32),)
                fills += [size]
            table, dense, lost = state.table.reclaimed(dense, fills)
            (prims, row_count, dirty, prev_prims, prev_row_count,
             emitted) = dense[:6]
            gslot = state.minput_gslot
            if self._minput_aggs:
                now_at = jnp.full((size + 1,), size, jnp.int32).at[
                    dense[6]].set(jnp.arange(size, dtype=jnp.int32),
                                  mode="drop")
                gslot = tuple(now_at[g] for g in gslot)
            return state._replace(
                table=table, prims=prims, row_count=row_count, dirty=dirty,
                prev_prims=prev_prims, prev_row_count=prev_row_count,
                emitted=emitted, minput_gslot=gslot,
                overflow=state.overflow + lost,
                reclaim_passes=state.reclaim_passes + 1,
                reclaim_slots=state.reclaim_slots + tombs,
            )

        state = jax.lax.cond(tombs > 0, reclaim, lambda s: s, state)

        # the counted tables (distinct dedup, materialised input)
        # reclaim independently: their own keys, their own tombstones
        def reclaim_counted(args):
            t, dense = args
            # a count is 0 in a slot never used, a group slot ``size``
            return t.reclaimed(dense, [0] + [size] * (len(dense) - 1))

        def reclaim_all(tables, denses):
            out_t, out_d = [], []
            lost = jnp.zeros((), jnp.int64)
            for t, dense in zip(tables, denses):
                t, dense, n = jax.lax.cond(
                    t.tombstone_count() > 0, reclaim_counted,
                    lambda a: (a[0], a[1], jnp.zeros((), jnp.int64)),
                    (t, dense),
                )
                out_t.append(t)
                out_d.append(dense)
                lost = lost + n
            return out_t, out_d, lost

        d_tables, d_dense, lost_d = reclaim_all(
            state.distinct_tables, [(c,) for c in state.distinct_counts])
        m_tables, m_dense, lost_m = reclaim_all(
            state.minput_tables,
            list(zip(state.minput_counts, state.minput_gslot)))
        return state._replace(
            distinct_tables=tuple(d_tables),
            distinct_counts=tuple(d[0] for d in d_dense),
            distinct_overflow=state.distinct_overflow + lost_d,
            minput_tables=tuple(m_tables),
            minput_counts=tuple(d[0] for d in m_dense),
            minput_gslot=tuple(d[1] for d in m_dense),
            minput_overflow=state.minput_overflow + lost_m,
        )

    # ------------------------------------------------------------------
    def clean_below(self, state: AggState, key_col_idx: int, threshold):
        """Drop groups whose ``key_col_idx`` group-key < threshold.

        Watermark-driven state cleaning (ref state_table.rs:223): used by
        windowed aggregations once a window can no longer change.
        """
        key, key_null = split_col(state.table.key_cols[key_col_idx])
        stale = state.table.occupied & (key < threshold)
        if key_null is not None:
            stale = stale & ~key_null  # NULL keys are never below a wm
        table = state.table.clear_where(stale)
        # the counted tables' keys carry the same group-key prefix:
        # evict their (group, value) rows with the window too
        def clean_counted(tables, counts):
            out_t, out_c = [], []
            for t, cnt in zip(tables, counts):
                k, kn = split_col(t.key_cols[key_col_idx])
                stale_t = t.occupied & (k < threshold)
                if kn is not None:
                    stale_t = stale_t & ~kn
                out_t.append(t.clear_where(stale_t))
                out_c.append(jnp.where(stale_t, 0, cnt))
            return tuple(out_t), tuple(out_c)

        d_tables, d_counts = clean_counted(
            state.distinct_tables, state.distinct_counts)
        m_tables, m_counts = clean_counted(
            state.minput_tables, state.minput_counts)
        return state._replace(
            table=table,
            row_count=jnp.where(stale, 0, state.row_count),
            dirty=state.dirty & ~stale,
            prev_row_count=jnp.where(stale, 0, state.prev_row_count),
            emitted=state.emitted & ~stale,
            distinct_tables=d_tables,
            distinct_counts=d_counts,
            minput_tables=m_tables,
            minput_counts=m_counts,
            cleaned_groups=state.cleaned_groups
            + jnp.sum(stale, dtype=jnp.int64),
        )
