"""MaterializeExecutor: maintain the MV's table from its changelog.

Reference counterpart: ``MaterializeExecutor`` (src/stream/src/executor/
mview/materialize.rs:70) — applies the changelog to the MV's StateTable
with primary-key conflict handling.

TPU-first design
----------------
Two device-resident variants, chosen by the plan:

- ``MaterializeExecutor`` (pk-keyed): a ``HashTable`` on the pk plus
  dense value arrays.  A whole changelog chunk applies as one
  lookup_or_insert + two scatters (delete-side tombstones, insert-side
  writes) — the reference's per-row conflict handling becomes a
  vectorized upsert.
- ``AppendOnlyMaterialize``: a ring buffer + cursor for pk-less /
  append-only MVs (e.g. Nexmark q1) — one dynamic-slice write per chunk.

Snapshot serving reads (`fetch` / `to_host`) gather the pk table's live
blocks on the device at barrier time and move those — the batch-side
`BatchTable` scan of SURVEY §3.4, collapsed to a gather.
"""

from __future__ import annotations

import functools
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
    apply_null_mask,
    decode_strings,
    split_col,
)
from risingwave_tpu.common import compact
from risingwave_tpu.common.compact import mask_indices
from risingwave_tpu.common.types import Schema
from risingwave_tpu.state.hash_table import HashTable
from risingwave_tpu.stream.executor import Executor


def _empty_value_col(f, size: int):
    if f.data_type.is_string:
        col = StrCol(
            jnp.zeros((size, f.str_width), jnp.uint8),
            jnp.zeros((size,), jnp.int32),
        )
    else:
        col = jnp.zeros((size,), f.data_type.physical_dtype)
    if getattr(f, "nullable", False):
        return NCol(col, jnp.zeros((size,), jnp.bool_))
    return col


def _scatter_col(store, pos, values):
    if isinstance(store, NCol):
        return NCol(
            _scatter_col(store.data, pos, values.data),
            store.null.at[pos].set(values.null, mode="drop"),
        )
    if isinstance(store, StrCol):
        return StrCol(
            store.data.at[pos].set(values.data, mode="drop"),
            store.lens.at[pos].set(values.lens, mode="drop"),
        )
    return store.at[pos].set(values, mode="drop")


class MvState(NamedTuple):
    table: HashTable
    values: tuple  # dense [size] column stores (all output columns)
    overflow: jnp.ndarray


class MaterializeExecutor(Executor):
    """Upsert the changelog into a pk-keyed device table."""

    emits_on_apply = False
    emits_on_flush = False

    def __init__(
        self,
        in_schema: Schema,
        pk_indices: Sequence[int],
        table_size: int = 1 << 16,
    ):
        super().__init__(in_schema)
        self.pk_indices = tuple(pk_indices)
        self.table_size = table_size

    def init_state(self) -> MvState:
        protos = []
        for i in self.pk_indices:
            protos.append(_empty_value_col(self.in_schema[i], 1))
        table = HashTable.create(protos, self.table_size)
        values = tuple(
            _empty_value_col(f, self.table_size) for f in self.in_schema
        )
        return MvState(table, values, jnp.zeros((), jnp.int64))

    def apply(self, state: MvState, chunk: Chunk):
        pk_cols = [chunk.column(i) for i in self.pk_indices]
        is_del = (chunk.ops == OP_DELETE) | (chunk.ops == OP_UPDATE_DELETE)
        is_ins = (chunk.ops == OP_INSERT) | (chunk.ops == OP_UPDATE_INSERT)
        del_rows = chunk.valid & is_del
        ins_rows = chunk.valid & is_ins

        table, slots, _, overflow = state.table.lookup_or_insert(
            pk_cols, chunk.valid
        )
        n_over = jnp.sum((overflow & chunk.valid).astype(jnp.int64))
        # per-slot conflict resolution honors INTRA-CHUNK ROW ORDER (the
        # reference applies conflicts row by row, materialize.rs): the
        # last op in row order wins — a [+pk, -pk] chunk ends absent, a
        # [-pk, +pk] chunk ends present.  XLA scatter order for duplicate
        # indices is unspecified, so the winner is chosen by scatter-max
        # of the row index per side.
        row_idx = jnp.arange(slots.shape[0], dtype=jnp.int32)
        last_del = jnp.full((self.table_size,), -1, jnp.int32).at[
            jnp.where(del_rows, slots, jnp.int32(self.table_size))
        ].max(jnp.where(del_rows, row_idx, -1), mode="drop")
        last_ins = jnp.full((self.table_size,), -1, jnp.int32).at[
            jnp.where(ins_rows, slots, jnp.int32(self.table_size))
        ].max(jnp.where(ins_rows, row_idx, -1), mode="drop")
        safe = jnp.minimum(slots, self.table_size - 1)
        # delete wins where its last row index beats the last insert's
        del_wins = del_rows & (last_del[safe] > last_ins[safe])
        table = table.clear_slots(slots, del_wins)
        is_last = ins_rows & (last_ins[safe] == row_idx) & (
            last_ins[safe] > last_del[safe]
        )
        ins_pos = jnp.where(is_last, slots, jnp.int32(self.table_size))
        table = HashTable(
            table.key_cols,
            table.occupied.at[ins_pos].set(True, mode="drop"),
            table.tombstone.at[ins_pos].set(False, mode="drop"),
            table.size,
        )
        values = tuple(
            _scatter_col(store, ins_pos, col)
            for store, col in zip(state.values, chunk.columns)
        )
        # pass the changelog through: downstream (cascaded) MVs consume
        # this MV's change stream, exactly as the reference's dispatcher
        # forwards the materialize fragment's output to dependent jobs
        return MvState(table, values, state.overflow + n_over), chunk

    # -- maintenance ----------------------------------------------------
    def levels(self, state: MvState):
        """``fragment.VIEW_GAUGE_ATTRS`` on the barrier's counters
        vector: the slots no new key can claim (a row's, or a
        tombstone's until the next rehash) and the table's slots."""
        used = state.table.occupied | state.table.tombstone
        return (jnp.sum(used.astype(jnp.int32)),
                jnp.asarray(self.table_size, jnp.int32))

    def maybe_rehash(self, state: MvState) -> MvState:
        """Rebuild the pk table once tombstones dominate (traceable:
        lax.cond on the device tombstone count, no host readback)."""

        def do_rehash(state: MvState) -> MvState:
            fresh, moved = state.table.rehashed()
            from risingwave_tpu.state.hash_table import permute_dense

            values = tuple(permute_dense(v, moved) for v in state.values)
            return MvState(fresh, values, state.overflow)

        return jax.lax.cond(
            state.table.tombstone_count() > self.table_size // 4,
            do_rehash, lambda s: s, state,
        )

    # -- serving (snapshot read) ----------------------------------------
    def fetch(self, state: MvState):
        """The view's slots on the host: ``(occupied, values, moved)``,
        ``occupied`` a flat bool vector and every leaf of ``values`` as
        many leading rows, slots ascending.  A mesh view's stacked state
        (one leading shard axis a leaf) comes shard after shard.  One
        algorithm, its path chosen by what it observes:

        - ``gathered``: the blocks that hold a row fit the program's
          capacity (``_live_blocks``): those blocks alone cross, with
          their count, in one ``device_get``;
        - ``whole``: more live blocks than that: every leaf whole, in
          one ``device_get`` more;
        - ``host``: a state that holds a host array (a loaded
          checkpoint) is cut where it is; no program is dispatched, and
          a leaf that is on the device all the same crosses ``whole``.

        ``moved`` is ``{"path", "bytes", "blocks"}``: the bytes that
        crossed from the device, and the live blocks the program found."""
        occ = state.table.occupied
        leaves, treedef = jax.tree.flatten(state.values)
        rests = [x.shape[occ.ndim:] for x in leaves]
        lanes = occ.size // occ.shape[-1]
        on_device = all(isinstance(x, jax.Array) for x in (occ, *leaves))
        moved = {"path": "host", "bytes": 0, "blocks": 0}
        host = None
        # the program's window starts are int32 element offsets
        if on_device and max(x.size for x in leaves) // lanes < 2 ** 31:
            fn = _live_blocks_fn(occ.ndim == 2, compact.accel_tuned())
            live, *windows = jax.device_get(fn(occ, leaves))
            live = np.atleast_1d(live)
            cap = windows[0].shape[-2]
            moved = {"path": "gathered", "blocks": int(live.sum()),
                     "bytes": live.nbytes + sum(w.nbytes for w in windows)}
            if live.max() <= cap:
                host = [
                    np.concatenate([
                        lane[:n] for lane, n in
                        zip(w.reshape(lanes, cap, -1), live)
                    ]) for w in windows
                ]
        if host is None:
            host = jax.device_get([occ, *leaves])
            crossed = sum(h.nbytes for x, h in zip((occ, *leaves), host)
                          if isinstance(x, jax.Array))
            if crossed:
                moved["path"] = "whole"
                moved["bytes"] += crossed
        values = jax.tree.unflatten(treedef, [
            h.reshape(-1, *rest) for h, rest in zip(host[1:], rests)
        ])
        return host[0].reshape(-1), values, moved

    def rows(self, occ: np.ndarray, values) -> list[tuple]:
        """The occupied slots of fetched columns as python rows."""
        cols = []
        for f, store in zip(self.in_schema, values):
            store, null = split_col(store)
            if isinstance(store, StrCol):
                out = decode_strings(store.data[occ], store.lens[occ])
            else:
                arr = store[occ]
                if f.data_type.value == "numeric":
                    arr = arr.astype(np.float64) / 10**f.decimal_scale
                out = arr
            if null is not None:
                out = apply_null_mask(out, null[occ])
            cols.append(out)
        n = int(occ.sum())
        return [tuple(c[i] for c in cols) for i in range(n)]

    def to_host(self, state: MvState) -> list[tuple]:
        """Read the MV as python rows (batch serving path)."""
        occ, values, _ = self.fetch(state)
        return self.rows(occ, values)


#: slots in a block of the read's gather
READ_BLOCK = 512

_WINDOWS = jax.lax.GatherDimensionNumbers(
    offset_dims=(1,), collapsed_slice_dims=(), start_index_map=(0,),
)


def _live_blocks(occ, leaves):
    """The blocks of one table that hold a row: their count, and the
    first ``cap = max(1, blocks // 64)`` of them, ascending, as ``(cap,
    block)`` windows of ``occ`` and of every leaf (a leaf's trailing
    axes folded into its window).  Past the count the windows repeat
    block 0.  Which blocks are live is computed here, from the table:
    nothing about a view's contents is a static bound, so a view
    compiles this once, at its first read."""
    size = occ.shape[0]
    block = min(READ_BLOCK, size)
    live = jnp.any(occ.reshape(size // block, block), axis=1)
    ids = mask_indices(live, max(1, live.shape[0] // 64), 0)

    def windows(x):
        w = block * (x.size // size)
        return jax.lax.gather(
            x.reshape(-1), (ids * w)[:, None], _WINDOWS, slice_sizes=(w,),
            mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS,
        )

    return (jnp.sum(live, dtype=jnp.int32), windows(occ),
            *map(windows, leaves))


@functools.cache
def _live_blocks_fn(stacked: bool, chip: bool):
    """``_live_blocks`` jitted, over the shard axis of a stacked state
    where there is one.  ``chip`` is ``accel_tuned()``, a trace-time
    branch of ``mask_indices``: a key, so that each has its trace."""
    return jax.jit(jax.vmap(_live_blocks) if stacked else _live_blocks)


class RingState(NamedTuple):
    values: tuple          # [ring_size] column stores
    cursor: jnp.ndarray    # int64 total rows written (mod ring for slot)
    overflow: jnp.ndarray  # rows evicted before being read


class AppendOnlyMaterialize(Executor):
    """Ring-buffer MV for append-only changelogs (no pk conflicts).

    The reference appends via row-id pks; here an on-device ring buffer
    absorbs inserts with one compaction + dynamic write per chunk.
    """

    emits_on_apply = False
    emits_on_flush = False

    def __init__(self, in_schema: Schema, ring_size: int = 1 << 20):
        super().__init__(in_schema)
        if ring_size & (ring_size - 1):
            raise ValueError("ring_size must be a power of two")
        self.ring_size = ring_size

    def init_state(self) -> RingState:
        return RingState(
            tuple(_empty_value_col(f, self.ring_size) for f in self.in_schema),
            jnp.zeros((), jnp.int64),
            jnp.zeros((), jnp.int64),
        )

    def apply(self, state: RingState, chunk: Chunk):
        cap = chunk.capacity
        # compact visible rows to the front (fixed-size nonzero)
        idx = mask_indices(chunk.valid, cap, cap)
        n = chunk.cardinality().astype(jnp.int64)
        k = jnp.arange(cap, dtype=jnp.int64)
        pos = ((state.cursor + k) % self.ring_size).astype(jnp.int32)
        pos = jnp.where(k < n, pos, jnp.int32(self.ring_size))
        safe_idx = jnp.minimum(idx, cap - 1)
        from risingwave_tpu.state.hash_table import gather_key
        values = []
        for store, col in zip(state.values, chunk.columns):
            values.append(_scatter_col(store, pos, gather_key(col, safe_idx)))
        # ring laps silently overwrite the oldest MV rows — count them as
        # overflow so maintenance fails loudly instead of serving a
        # truncated MV (history beyond ring_size needs the SST spill path)
        lost_before = jnp.maximum(state.cursor - self.ring_size, 0)
        lost_after = jnp.maximum(state.cursor + n - self.ring_size, 0)
        return RingState(
            tuple(values), state.cursor + n,
            state.overflow + (lost_after - lost_before),
        ), chunk  # pass-through: cascaded MVs tap this changelog

    def to_host(self, state: RingState, limit: int | None = None) -> list[tuple]:
        total = int(state.cursor)
        n = min(total, self.ring_size if limit is None else limit)
        start = max(total - n, 0)
        sel = (np.arange(start, start + n) % self.ring_size).astype(np.int64)
        cols = []
        for f, store in zip(self.in_schema, state.values):
            store, null = split_col(store)
            if isinstance(store, StrCol):
                out = decode_strings(
                    np.asarray(store.data)[sel], np.asarray(store.lens)[sel]
                )
            else:
                out = np.asarray(store)[sel]
                if f.data_type.value == "numeric":
                    out = out.astype(np.float64) / 10**f.decimal_scale
            if null is not None:
                out = apply_null_mask(out, np.asarray(null)[sel])
            cols.append(out)
        return [tuple(c[i] for c in cols) for i in range(n)]


def view_rows(mv_executor, state, stacked: bool):
    """A view's rows and what reading them moved (``fetch``'s
    ``moved``).  ``stacked``: a mesh job's state, whose leaves carry one
    leading shard axis; the rows come shard after shard.  The ring
    reads as it always did and reports nothing."""
    if isinstance(mv_executor, MaterializeExecutor):
        occ, values, moved = mv_executor.fetch(state)
        return mv_executor.rows(occ, values), moved
    moved: dict = {}
    if not stacked:
        return mv_executor.to_host(state), moved
    host = jax.device_get(state)  # one transfer
    rows = []
    for shard in range(jax.tree.leaves(host)[0].shape[0]):
        rows.extend(mv_executor.to_host(
            jax.tree.map(lambda x: x[shard], host)
        ))
    return rows, moved
