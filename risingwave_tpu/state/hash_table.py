"""Open-addressing device hash table, fully vectorized.

Reference counterpart: the per-key state maps inside stateful executors —
``AggGroup`` cache (src/stream/src/executor/aggregate/hash_agg.rs:64) and
``JoinHashMap`` (src/stream/src/executor/join/hash_join.rs:169) — which
on CPU are per-row HashMap probes behind an LRU.

TPU-first design
----------------
State is a *dense, preallocated* table in HBM:

- ``key_cols``: one ``[size]`` array per key column (``StrCol`` for
  strings) — the slot's group key;
- ``occupied``: ``bool [size]``.

``lookup_or_insert`` resolves a whole chunk of keys in one pass of a
``lax.while_loop``: every pending row probes its candidate slot
simultaneously; rows hitting an empty slot *claim* it with a
scatter-min of their row index (first-writer-wins, deterministic), and
losers simply re-check the slot on the next iteration (where they will
either match the winner's key or move on with linear probing).  The loop
runs until all rows resolve — worst case bounded, typical case 2-4
iterations — and every iteration is a handful of gathers/scatters over
the chunk, so a 4k-row chunk against a 256k-slot table is a few fused
XLA kernels rather than 4k pointer chases.

Deletion uses tombstones: a cleared slot stops matching but keeps the
probe chain intact (``~occupied & tombstone`` ⇒ keep probing, never
claim).  Bulk eviction is a vectorized mask sweep (``clear_where``) —
this is how watermark state-cleaning works (the reference cleans per-key
on commit, state_table.rs:223) — and ``needs_rehash``/``rehashed``
rebuild the table once tombstones accumulate.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from risingwave_tpu.common.chunk import NCol, StrCol
from risingwave_tpu.common.compact import mask_indices
from risingwave_tpu.common.hash import (
    hash64_columns,
    hash64_extend,
    hash64_finish,
    hash64_partial,
)

#: movers ``HashTable.reclaimed`` reinserts at a time: one probe and one
#: scatter a leaf of this width, like ``hash_agg.REP_TILE`` (PERF.md §6)
RECLAIM_TILE = 128
#: movers it lists at a time (one binary search over the table each)
RECLAIM_BATCH = 4096
#: movers ``TagTable.reclaimed`` reinserts at a time: its probe loop
#: narrows by itself (``_probe_loop``), so a wide tile shares the rounds'
#: fixed cost (a join retires ten times an aggregate's slots a barrier:
#: PERF.md §6, PR 35)
TAG_RECLAIM_TILE = 4096
#: widths a probe loop narrows to as a chunk's rows resolve
#: (``_probe_loop``, for ``HashTable`` and ``TagTable``): the open rows go
#: on in a tile of the next width once they fit it
STRAGGLER_TILES = (1024, 128)

#: trace-time probe accounting: how many table-probe loops a compiled
#: program contains.  Incremented while TRACING (each jitted program
#: traces once), so wrapping a trace of an update function between
#: ``reset_probe_stats()`` and a read yields exactly the per-dispatch
#: probe-call count of the compiled artifact — the regression guard for
#: "one lookup_or_insert per side per chunk" (scripts/profile_q8.py
#: --assert and tests/test_join_pool_fused.py).
PROBE_STATS = {"lookup": 0, "lookup_or_insert": 0}


def reset_probe_stats() -> None:
    for k in PROBE_STATS:
        PROBE_STATS[k] = 0


def _gather_key(col, idx):
    if isinstance(col, NCol):
        return NCol(_gather_key(col.data, idx), col.null[idx])
    if isinstance(col, StrCol):
        return StrCol(col.data[idx], col.lens[idx])
    return col[idx]


def _scatter_key(col, pos, values, size):
    """Write values at pos (mode=drop for sentinel positions)."""
    if isinstance(col, NCol):
        return NCol(
            _scatter_key(col.data, pos, values.data, size),
            col.null.at[pos].set(values.null, mode="drop"),
        )
    if isinstance(col, StrCol):
        return StrCol(
            col.data.at[pos].set(values.data, mode="drop"),
            col.lens.at[pos].set(values.lens, mode="drop"),
        )
    return col.at[pos].set(values, mode="drop")


def _keys_equal(a, b) -> jnp.ndarray:
    """Rowwise *grouping* equality of two key column values.

    NULL == NULL here (GROUP BY/DISTINCT semantics, matching the
    reference's HashKey serde); join executors mask null keys out
    BEFORE key lookup, so join equality never reaches this."""
    if isinstance(a, NCol) or isinstance(b, NCol):
        ad, an = (a.data, a.null) if isinstance(a, NCol) else (a, None)
        bd, bn = (b.data, b.null) if isinstance(b, NCol) else (b, None)
        data_eq = _keys_equal(ad, bd)
        if an is None:
            an = jnp.zeros_like(bn)
        if bn is None:
            bn = jnp.zeros_like(an)
        return (an & bn) | (~an & ~bn & data_eq)
    if isinstance(a, StrCol):
        return jnp.all(a.data == b.data, axis=-1) & (a.lens == b.lens)
    return a == b


# public aliases for executors that pre-sort/compare key columns
# (chunk pre-aggregation in hash_agg, join bucket paths)
gather_key = _gather_key
keys_equal = _keys_equal


def _scan_slots(op, combine, x: jnp.ndarray, identity) -> jnp.ndarray:
    """``op`` (``lax.cummax`` / ``lax.cumsum``, with its ``combine``)
    along a table-sized int32 vector, as a scan within rows of 512 and
    one over the rows' totals:
    flat, the chip's compiler takes 37 s over 2^20 elements and 6 s over
    2^18; so, a second (deviceless compiles, PERF.md §6, PR 30)."""
    n = x.shape[0]
    if n <= 512:
        return op(x, axis=0)
    inner = op(x.reshape(n // 512, 512), axis=1)
    carry = jnp.concatenate([
        jnp.full((1,), identity, x.dtype), op(inner[:, -1], axis=0)[:-1]])
    return combine(inner, carry[:, None]).reshape(n)


def permute_dense(arr, moved: jnp.ndarray, init=None):
    """Move dense per-slot values ``arr[[old]] -> out[[moved[old]]]``.

    ``moved`` comes from ``HashTable.rehashed``; dead slots carry the
    drop sentinel.  ``init`` fills untouched slots (monoid identity for
    min/max states; zero otherwise).
    """
    if isinstance(arr, NCol):
        return NCol(
            permute_dense(arr.data, moved), permute_dense(arr.null, moved)
        )
    if isinstance(arr, StrCol):
        return StrCol(
            permute_dense(arr.data, moved), permute_dense(arr.lens, moved)
        )
    if init is None:
        out = jnp.zeros_like(arr)
    else:
        out = jnp.full_like(arr, init)
    return out.at[moved].set(arr, mode="drop")


def _probe_loop(step, shared, rows: tuple, inputs: tuple, max_iters: int):
    """Run a chunk's probe rounds until every row has resolved.

    ``step(shared, rows, inputs) -> (shared, rows)`` is one round over
    rows of any width: ``shared`` is what all rows probe (the table's
    arrays), ``rows`` per-row state with ``rows[0]`` the ``done`` mask,
    ``inputs`` per-row values that do not change.  A round costs the
    same for a resolved row as for an open one, and the longest chain of
    a chunk is ten times its mean (at load 0.41, 8,192 rows: 2.6 slots a
    row, 30-45 rounds): so once no more rows are open than the next of
    ``STRAGGLER_TILES`` holds they are gathered into a tile of that
    width, go on there, and are put back.  A resolved row does nothing
    in a round, so the rounds probe the same slots in the same order at
    any width.  The first round is unrolled into the enclosing program:
    at sane load factors most rows resolve at once, and the common case
    should not pay a loop iteration's fixed overhead (PERF.md §6).
    Returns ``(shared, rows, rounds, wide)``: ``rounds`` all of them,
    ``wide`` those at the chunk's full width (the first included);
    ``max_iters`` bounds ``rounds``."""
    shared, rows = step(shared, rows, inputs)
    return _narrowing_rounds(step, shared, rows, inputs, jnp.int32(1),
                             max_iters, STRAGGLER_TILES)


def _narrowing_rounds(step, shared, rows, inputs, iters, max_iters, tiles):
    """``_probe_loop``'s rounds from ``iters`` on at the width of
    ``rows``, then in the narrower ``tiles``.  Returns ``(shared, rows,
    iters, wide)``, ``wide`` the count when this width's rounds ended."""
    width = rows[0].shape[0]
    tiles = [t for t in tiles if t < width]
    more_than = tiles[0] if tiles else 0

    def cond(carry):
        _, rows, iters = carry
        still = jnp.sum(~rows[0], dtype=jnp.int32)
        return (still > more_than) & (iters < max_iters)

    def body(carry):
        shared, rows, iters = carry
        shared, rows = step(shared, rows, inputs)
        return shared, rows, iters + 1

    shared, rows, iters = jax.lax.while_loop(
        cond, body, (shared, rows, iters))
    wide = iters
    if not tiles:
        return shared, rows, iters, wide
    at = mask_indices(~rows[0], tiles[0], width)
    picked = at < width
    safe = jnp.minimum(at, width - 1)
    few = tuple(x[safe] for x in rows)
    few = (few[0] | ~picked,) + few[1:]
    shared, few, iters, _ = _narrowing_rounds(
        step, shared, few, jax.tree.map(lambda x: x[safe], inputs), iters,
        max_iters, tiles[1:])
    to = jnp.where(picked, at, width)
    rows = tuple(x.at[to].set(y, mode="drop") for x, y in zip(rows, few))
    return shared, rows, iters, wide


def _reclaim(size: int, live, tomb, home, emptied, reinsert, occupied_of,
             dense, inits, tile: int = RECLAIM_TILE):
    """Give an open-addressing table's tombstones back at a cost that
    follows what was retired: the live entries whose probe chain crosses
    a tombstone are taken out and inserted again, ``tile`` at a time,
    and nothing else moves.  One implementation for ``HashTable``
    (key columns) and ``TagTable`` (packed tags).

    Every index of a table-wide scatter or gather costs the v5e ~66 ns,
    live or dropped (PERF.md §6), and a rebuild of the whole table with
    a permute of every per-slot leaf hands it the table once a leaf.
    Here the table-wide work is elementwise and two scans.  A live
    entry is a *mover* if it is off its home slot and a tombstone lies
    before it in its run (the stretch of non-empty slots it sits in): a
    superset of the entries a freed slot can bring nearer home, and
    closed, because an entry ahead of its run's first tombstone has no
    freed slot on its chain.  All tombstones and movers become empty at
    once; then the movers go back in, in the order of their old slots
    counted from an empty one (so no run is cut), which keeps each
    reinserted entry at or before the last old slot of its tile: the
    old slots' contents are read a tile ahead of anything that could
    overwrite them, and no staging copy is needed.

    ``live`` / ``tomb`` / ``home`` are ``[size]``: occupancy, tombstones
    and each live entry's home slot.  ``emptied(keep)`` is the table
    with exactly the ``keep`` slots occupied and no tombstone;
    ``reinsert(table, old_slots, valid)`` puts the entries that sat at
    ``old_slots`` back and returns ``(table, new_slots, overflow)``;
    ``occupied_of(table)`` its occupancy.  ``dense`` / ``inits`` as in
    ``HashTable.reclaimed``.  Returns ``(table, dense', lost)``.
    """
    K = min(tile, size)
    idx = jnp.arange(size, dtype=jnp.int32)
    empty = ~live & ~tomb

    def last_at_or_before(mask):
        """Slot of the nearest ``mask`` slot at or before each slot,
        around the table's end (then negative); none: below all."""
        none = -2 * size - 2
        at = _scan_slots(jax.lax.cummax, jnp.maximum,
                         jnp.where(mask, idx, none), none)
        return jnp.maximum(at, at[-1] - size)

    mover = live & (home != idx) & (
        last_at_or_before(tomb) > last_at_or_before(empty))
    # slots are counted from an empty one, or, in a table without,
    # from a tombstone (then every displaced entry is a mover)
    origin = jnp.where(jnp.any(empty), jnp.argmax(empty),
                       jnp.argmax(tomb)).astype(jnp.int32)
    table = emptied(live & ~mover)
    leaves, treedef = jax.tree.flatten(tuple(dense))
    fills = list(inits) or [0] * len(leaves)
    if len(fills) != len(leaves):
        raise ValueError("one fill value for each leaf of `dense`")

    # the movers' old slots, ascending from ``origin``: the k-th is
    # where the running count of movers first reaches k (a binary
    # search a batch: a sort or a ``top_k`` over the table would cost
    # the chip's compiler half a minute, PERF.md §6)
    B = min(RECLAIM_BATCH, size)
    count = _scan_slots(
        jax.lax.cumsum, jnp.add,
        jnp.roll(mover, -origin).astype(jnp.int32), 0)
    n_movers = count[-1]

    def batch(b, carry):
        at = jnp.searchsorted(
            count, b * B + jnp.arange(1, B + 1, dtype=jnp.int32),
            side="left", method="scan").astype(jnp.int32)
        old = jnp.concatenate([
            jnp.where(at < size, (at + origin) % size, size),
            jnp.full((-B % K,), size, jnp.int32)])

        def put_back(t, carry):
            table, leaves, lost = carry
            pos = jax.lax.dynamic_slice(old, (t * K,), (K,))
            valid = pos < size
            safe = jnp.minimum(pos, size - 1)
            rows = [x[safe] for x in leaves]
            table, slots, over = reinsert(table, safe, valid)
            to = jnp.where(valid & ~over, slots, jnp.int32(size))
            leaves = [x.at[to].set(r, mode="drop")
                      for x, r in zip(leaves, rows)]
            lost = lost + jnp.sum((valid & over).astype(jnp.int64))
            return table, leaves, lost

        n = jnp.minimum(n_movers - b * B, B)
        return jax.lax.fori_loop(0, (n + K - 1) // K, put_back, carry)

    table, leaves, lost = jax.lax.fori_loop(
        0, (n_movers + B - 1) // B, batch,
        (table, leaves, jnp.zeros((), jnp.int64)))
    occ = occupied_of(table)
    leaves = [
        jnp.where(occ.reshape((size,) + (1,) * (x.ndim - 1)),
                  x, jnp.asarray(f, x.dtype))
        for x, f in zip(leaves, fills)]
    return table, jax.tree.unflatten(treedef, leaves), lost


def _empty_key_col(col_proto, size: int):
    if isinstance(col_proto, NCol):
        return NCol(
            _empty_key_col(col_proto.data, size),
            jnp.zeros((size,), jnp.bool_),
        )
    if isinstance(col_proto, StrCol):
        return StrCol(
            jnp.zeros((size, col_proto.data.shape[1]), jnp.uint8),
            jnp.zeros((size,), jnp.int32),
        )
    return jnp.zeros((size,), col_proto.dtype)


@jax.tree_util.register_pytree_node_class
class HashTable:
    """Keys + occupancy; value arrays live beside it in the executor state."""

    __slots__ = ("key_cols", "occupied", "tombstone", "size")

    def __init__(
        self,
        key_cols: tuple,
        occupied: jnp.ndarray,
        tombstone: jnp.ndarray,
        size: int,
    ):
        self.key_cols = tuple(key_cols)
        self.occupied = occupied
        self.tombstone = tombstone
        self.size = size

    def tree_flatten(self):
        return (self.key_cols, self.occupied, self.tombstone), self.size

    @classmethod
    def tree_unflatten(cls, size, children):
        key_cols, occupied, tombstone = children
        return cls(key_cols, occupied, tombstone, size)

    # ------------------------------------------------------------------
    @staticmethod
    def create(key_protos: Sequence, size: int) -> "HashTable":
        """Empty table; ``key_protos`` supply per-column dtype/width."""
        if size & (size - 1):
            raise ValueError(f"size {size} must be a power of two")
        cols = tuple(_empty_key_col(p, size) for p in key_protos)
        return HashTable(
            cols,
            jnp.zeros((size,), jnp.bool_),
            jnp.zeros((size,), jnp.bool_),
            size,
        )

    def count(self) -> jnp.ndarray:
        return jnp.sum(self.occupied.astype(jnp.int32))

    # ------------------------------------------------------------------
    def lookup(self, key_cols: Sequence, valid: jnp.ndarray,
               hashes: jnp.ndarray | None = None):
        """Find slots without inserting.

        Returns ``(slots int32 [cap], found bool [cap])``; unfound/invalid
        rows get slot == size (a drop sentinel for downstream gathers).
        """
        slots, found, _ = self.lookup_counted(key_cols, valid, hashes)
        return slots, found

    def lookup_counted(self, key_cols: Sequence, valid: jnp.ndarray,
                       hashes: jnp.ndarray | None = None):
        """``lookup`` that also returns the probe-bound overflow count.

        A probe chain exhausting the iteration bound reports found=False
        for a key that may be present; callers on correctness-critical
        paths (join probes) must accumulate the count into an error
        counter so maintenance fails loudly instead of silently
        dropping matches."""
        _, slots, found, overflow, _ = self._probe(
            key_cols, valid, insert=False, hashes=hashes
        )
        return slots, found, jnp.sum((overflow & valid).astype(jnp.int64))

    def lookup_or_insert(self, key_cols: Sequence, valid: jnp.ndarray,
                         hashes: jnp.ndarray | None = None):
        """Find-or-claim slots for a chunk of keys.

        ``hashes`` optionally supplies precomputed ``hash64_columns``
        values (callers that already hashed for a pre-aggregation sort
        avoid a second full-chunk hash pass).

        Returns ``(table', slots, inserted, overflow)``:
        - ``slots int32 [cap]`` — resolved slot per row (size if overflow
          or invalid);
        - ``inserted bool [cap]`` — row claimed a fresh slot;
        - ``overflow bool [cap]`` — table was full for this row.
        """
        return self._probe(key_cols, valid, insert=True, hashes=hashes)[:4]

    def lookup_or_insert_rounds(self, key_cols: Sequence,
                                valid: jnp.ndarray,
                                hashes: jnp.ndarray | None = None):
        """``lookup_or_insert`` and its probe rounds: ``(table', slots,
        inserted, overflow, (wide, tile))``, int32 counts of the rounds
        run at the chunk's full width and in straggler tiles (an
        executor's device tallies, ``fragment.PROBE_ROUND_ATTRS``)."""
        return self._probe(key_cols, valid, insert=True, hashes=hashes)

    # ------------------------------------------------------------------
    def _probe(self, key_cols: Sequence, valid: jnp.ndarray, insert: bool,
               hashes: jnp.ndarray | None = None):
        """Every row of the chunk probes its chain in lockstep rounds
        (``_probe_loop``, which narrows to the stragglers).  Returns
        ``(table', slots, inserted | found, overflow, rounds)``, with
        ``rounds`` = ``(wide, tile)`` int32: rounds at the chunk's full
        width, the first included, and rounds in a straggler tile."""
        PROBE_STATS["lookup_or_insert" if insert else "lookup"] += 1
        size = self.size
        cap = valid.shape[0]
        if hashes is None:
            hashes = hash64_columns(key_cols)
        h = (hashes % np.uint64(size)).astype(jnp.int32)
        sentinel = jnp.int32(size)
        tombstone = self.tombstone

        # probe-length bound: at sane load factors chains are a handful
        # of slots; a pathological (near-full) table must degrade to
        # overflow counters, not O(size) loop iterations
        max_iters = min(size + 2, 1024)

        def step(shared, rows, inputs):
            occupied, key_store = shared
            done, slots, inserted, off = rows
            h, keys = inputs
            width = done.shape[0]
            cand = (h + off) % size
            occ = occupied[cand]
            tomb = tombstone[cand] & ~occ
            match = occ
            for c, k in zip(key_store, keys):
                match = match & _keys_equal(_gather_key(c, cand), k)
            hit = ~done & match
            slots = jnp.where(hit, cand, slots)
            done = done | hit
            if insert:
                # only a *true-empty* slot (no tombstone) is claimable:
                # claiming a tombstone could shadow the same key further
                # along a probe chain.  Intra-chunk claim races resolve
                # by scatter-min of the row index into a scratch of four
                # times the round's width (hashed by candidate slot):
                # exact for same-slot contenders; cross-slot scratch
                # collisions only delay a row to the next round.  Never
                # a table-sized array.
                want = ~done & ~occ & ~tomb
                row_idx = jnp.arange(width, dtype=jnp.int32)
                m = 4 * width
                scratch_idx = cand % m
                claim = jnp.full((m,), width, jnp.int32).at[
                    jnp.where(want, scratch_idx, m)
                ].min(jnp.where(want, row_idx, width), mode="drop")
                won = want & (claim[scratch_idx] == row_idx)
                pos = jnp.where(won, cand, sentinel)
                occupied = occupied.at[pos].set(True, mode="drop")
                key_store = tuple(
                    _scatter_key(c, pos, k, size)
                    for c, k in zip(key_store, keys)
                )
                slots = jnp.where(won, cand, slots)
                inserted = inserted | won
                done = done | won
                # losers of the claim re-check cand next round (it is
                # now occupied — match if same key, else advance)
            else:
                # probe-only: true-empty slot ⇒ key absent ⇒ miss
                done = done | (~done & ~occ & ~tomb)
            # tombstones are skipped, keeping probe chains intact
            advance = ~done & ((occ & ~match) | tomb)
            off = jnp.where(advance, off + 1, off)
            return (occupied, key_store), (done, slots, inserted, off)

        rows = (
            ~valid,
            jnp.full((cap,), sentinel, jnp.int32),
            jnp.zeros((cap,), jnp.bool_),
            jnp.zeros((cap,), jnp.int32),
        )
        (occupied, key_store), (done, slots, inserted, _), n, wide = \
            _probe_loop(step, (self.occupied, self.key_cols), rows,
                        (h, tuple(key_cols)), max_iters)
        overflow = ~done
        rounds = (wide, n - wide)
        if insert:
            table = HashTable(key_store, occupied, self.tombstone, size)
            return table, slots, inserted, overflow, rounds
        found = valid & done & (slots < size)
        return self, slots, found, overflow, rounds

    # ------------------------------------------------------------------
    def clear_where(self, pred: jnp.ndarray) -> "HashTable":
        """Bulk-evict slots where ``pred [size]`` is True (state cleaning).

        Cleared slots become tombstones so probe chains stay intact;
        call ``rehashed()`` periodically to reclaim them.
        """
        dead = pred & self.occupied
        return HashTable(
            self.key_cols,
            self.occupied & ~dead,
            self.tombstone | dead,
            self.size,
        )

    def clear_slots(self, slots: jnp.ndarray, mask: jnp.ndarray) -> "HashTable":
        """Tombstone specific slots (per-row deletes, e.g. MV conflict ops)."""
        pos = jnp.where(mask, slots, jnp.int32(self.size))
        return HashTable(
            self.key_cols,
            self.occupied.at[pos].set(False, mode="drop"),
            self.tombstone.at[pos].set(True, mode="drop"),
            self.size,
        )

    def tombstone_count(self) -> jnp.ndarray:
        return jnp.sum((self.tombstone & ~self.occupied).astype(jnp.int32))

    def rehashed(self) -> tuple["HashTable", jnp.ndarray]:
        """Rebuild without tombstones.

        Returns ``(fresh_table, moved)`` where ``moved int32 [size]`` maps
        old slot -> new slot (size for dead slots), so callers can
        permute their value arrays alongside.
        """
        fresh = HashTable.create(
            tuple(_gather_key(c, jnp.arange(1)) for c in self.key_cols),
            self.size,
        )
        live = self.occupied
        fresh, new_slots, _, _ = fresh.lookup_or_insert(self.key_cols, live)
        return fresh, new_slots

    def reclaimed(self, dense=(), inits=()):
        """Give every tombstone back, at a cost that follows what was
        retired (``_reclaim``, shared with ``TagTable``).

        ``dense`` is a tuple of pytrees of ``[size, ...]`` per-slot
        arrays that move with their keys; ``inits`` one fill value (what
        a never-used slot holds) per leaf of ``dense`` in
        ``jax.tree.leaves`` order, or empty for zeros: every slot left
        empty is reset to it.  Returns ``(table, dense', lost)``;
        ``lost`` counts movers whose reinsertion ran into the probe
        bound (none, below the table's load limit).
        """
        size = self.size
        live = self.occupied
        home = (hash64_columns(self.key_cols) % np.uint64(size)).astype(
            jnp.int32)

        def emptied(keep):
            return HashTable(self.key_cols, keep,
                             jnp.zeros((size,), jnp.bool_), size)

        def reinsert(table, safe, valid):
            # a cleared slot still holds its key columns
            table, slots, _, over = table.lookup_or_insert(
                [_gather_key(c, safe) for c in table.key_cols], valid)
            return table, slots, over

        return _reclaim(size, live, self.tombstone & ~live, home,
                        emptied, reinsert, lambda t: t.occupied,
                        dense, inits)

    def gather_keys(self, slots: jnp.ndarray) -> tuple:
        """Key column values at ``slots`` (drop-sentinel aware gathers)."""
        return tuple(_gather_key(c, jnp.minimum(slots, self.size - 1))
                     for c in self.key_cols)


# ---------------------------------------------------------------------------
# TagTable: the fused (key-hash, rank) table behind pool join sides.
# ---------------------------------------------------------------------------

#: reserved tag values (the tag hash remaps into [2, 2^64))
EMPTY_TAG = np.uint64(0)
TOMB_TAG = np.uint64(1)


def pair_tag(hashes: jnp.ndarray, rank: jnp.ndarray) -> jnp.ndarray:
    """The 64-bit identity tag of a ``(key-hash, rank)`` pair.

    ``hash64_columns([h, rank])`` remapped off the EMPTY/TOMB
    sentinels.  The tag doubles as the slot hash (``tag % size``), so a
    probe costs ONE random gather per iteration."""
    return finish_tag(hash64_extend(hash64_partial([hashes]), rank))


def tag_home(tags: jnp.ndarray, size: int) -> jnp.ndarray:
    """Each tag's home slot in a table of ``size`` slots (a power of
    two): its low bits."""
    return (tags % np.uint64(size)).astype(jnp.int32)


def finish_tag(state: jnp.ndarray) -> jnp.ndarray:
    raw = hash64_finish(state)
    return jnp.where(raw < np.uint64(2), raw + np.uint64(2), raw)


@jax.tree_util.register_pytree_node_class
class TagTable:
    """Open-addressing table over ONE packed uint64 tag array.

    The generic ``HashTable`` gathers occupied + tombstone + every key
    column per probe iteration — ~5 random DRAM reads per row per
    round, which IS the probe cost at multi-M-entry sizes.  Pool join
    sides only ever key by ``(key-hash, rank)``, whose identity
    compresses into a single 64-bit tag with reserved values for
    empty/tombstone: a probe iteration is ONE gather, a claim ONE
    scatter.  Tag collisions merge two (hash, rank) pairs with
    probability ~n²/2⁶⁴ — the same order as the key-hash collisions
    the pool design already accepts.

    Value arrays (pool position, degree, clean key) live beside the
    table in the executor state, addressed by slot.
    """

    __slots__ = ("tags", "size")

    def __init__(self, tags: jnp.ndarray, size: int):
        self.tags = tags
        self.size = size

    def tree_flatten(self):
        return (self.tags,), self.size

    @classmethod
    def tree_unflatten(cls, size, children):
        return cls(children[0], size)

    @staticmethod
    def create(size: int) -> "TagTable":
        if size & (size - 1):
            raise ValueError(f"size {size} must be a power of two")
        return TagTable(jnp.zeros((size,), jnp.uint64), size)

    # -- occupancy ------------------------------------------------------
    @property
    def occupied(self) -> jnp.ndarray:
        return self.tags >= np.uint64(2)

    def count(self) -> jnp.ndarray:
        return jnp.sum((self.tags >= np.uint64(2)).astype(jnp.int32))

    def tombstone_count(self) -> jnp.ndarray:
        return jnp.sum((self.tags == TOMB_TAG).astype(jnp.int32))

    # -- probes ---------------------------------------------------------
    def _probe_tags(self, tag_vals: jnp.ndarray, valid: jnp.ndarray,
                    insert: bool):
        """Generic one-gather probe over precomputed tags.

        Returns ``(tags', slots, found, overflow, inserted)``."""
        PROBE_STATS["lookup_or_insert" if insert else "lookup"] += 1
        size = self.size
        cap = valid.shape[0]
        sentinel = jnp.int32(size)
        max_iters = min(size + 2, 1024)

        def step(tags, rows, inputs):
            done, slots, inserted, off = rows
            tag_vals, home = inputs
            width = done.shape[0]
            row_idx = jnp.arange(width, dtype=jnp.int32)
            cand = (home + off) % size
            t = tags[cand]  # THE one random gather
            tomb = t == TOMB_TAG
            empty = t == EMPTY_TAG
            match = t == tag_vals
            hit = ~done & match
            slots = jnp.where(hit, cand, slots)
            done = done | hit
            if insert:
                want = ~done & empty
                m = 4 * width
                scratch_idx = cand % m
                claim = jnp.full((m,), width, jnp.int32).at[
                    jnp.where(want, scratch_idx, m)
                ].min(jnp.where(want, row_idx, width), mode="drop")
                won = want & (claim[scratch_idx] == row_idx)
                pos = jnp.where(won, cand, sentinel)
                tags = tags.at[pos].set(tag_vals, mode="drop")
                slots = jnp.where(won, cand, slots)
                inserted = inserted | won
                done = done | won
            else:
                done = done | (~done & empty)
            advance = ~done & ((~empty & ~match) | tomb)
            off = jnp.where(advance, off + 1, off)
            return tags, (done, slots, inserted, off)

        rows = (
            ~valid,
            jnp.full((cap,), sentinel, jnp.int32),
            jnp.zeros((cap,), jnp.bool_),
            jnp.zeros((cap,), jnp.int32),
        )
        home = tag_home(tag_vals, size)
        tags, (done, slots, inserted, _), _, _ = _probe_loop(
            step, self.tags, rows, (tag_vals, home), max_iters)
        overflow = ~done
        found = valid & done & ~inserted & (slots < size)
        return tags, slots, found, overflow, inserted

    def lookup_pair_counted(self, hashes: jnp.ndarray, rank: jnp.ndarray,
                            valid: jnp.ndarray):
        """Find (hash, rank) entries; ``(slots, found, bound_count)``
        with the probe-bound overflow folded to a loud counter (the
        lookup_counted contract)."""
        _, slots, found, overflow, _ = self._probe_tags(
            pair_tag(hashes, rank), valid, insert=False
        )
        return slots, found, jnp.sum((overflow & valid).astype(jnp.int64))

    # -- the fused two-phase ranked insert ------------------------------
    def lookup_or_insert_ranked(self, hashes: jnp.ndarray,
                                chunk_rank: jnp.ndarray,
                                degree: jnp.ndarray,
                                valid: jnp.ndarray):
        """Fused two-phase find-or-claim of ``(hash, degree[head] +
        chunk_rank)`` — ONE probe loop replacing the former key-table +
        rank-index pair of ``lookup_or_insert`` passes (the q8
        join-update cost halver).

        Each valid row resolves its key's HEAD entry ``(hash, 0)``,
        reads the key's pre-chunk degree at the head slot, switches its
        target to ``(hash, degree + chunk_rank)``, and find-or-claims
        it, all in the same loop.  A row whose head chain hits
        true-empty knows its key is absent (degree 0) and jumps
        straight to phase 2; its ``chunk_rank == 0`` sibling claims the
        head in the same loop.

        ``degree`` is only read, never written — callers scatter the
        per-key insert totals at the returned head slots afterwards, so
        every row sees the PRE-chunk degree regardless of loop order.

        Returns ``(table', slots, rank, head_slot, inserted, existed,
        overflow, iters)``:

        - ``slots int32 [cap]`` — resolved (hash, rank) slot (size
          sentinel on overflow/invalid);
        - ``rank int32 [cap]`` — resolved target rank;
        - ``head_slot int32 [cap]`` — the key's (hash, 0) slot where
          this row learned it (rows that claimed or matched the head;
          size sentinel otherwise — every key's chunk_rank==0 row
          always knows it);
        - ``inserted bool [cap]`` — row claimed a fresh slot;
        - ``existed bool [cap]`` — target entry was already present (a
          stranded entry from an earlier overflow; callers overwrite
          its payload and count the loss loudly);
        - ``overflow bool [cap]`` — probe bound exhausted;
        - ``iters int32 ()`` — loop trips (device probe-effort counter);
        - ``steps int32 ()`` — slots looked at, summed over the rows
          (a row that resolves at its first candidate counts one).
        """
        PROBE_STATS["lookup_or_insert"] += 1
        size = self.size
        cap = valid.shape[0]
        sentinel = jnp.int32(size)

        def tag_of(base, r):
            return finish_tag(hash64_extend(base, r))

        max_iters = min(2 * size + 4, 1024)

        def step(shared, rows, inputs):
            tags, steps = shared
            (done, slots, inserted, existed, phase2, target, target_tag,
             head_slot, off) = rows
            chunk_rank, base = inputs
            width = done.shape[0]
            row_idx = jnp.arange(width, dtype=jnp.int32)
            steps = steps + jnp.sum(~done, dtype=jnp.int32)
            cand = (tag_home(target_tag, size) + off) % size
            t = tags[cand]  # THE one random gather
            tomb = t == TOMB_TAG
            empty = t == EMPTY_TAG
            match = t == target_tag

            # -- phase 1: resolve the head (hash, 0) -------------------
            p1 = ~done & ~phase2
            head_hit = p1 & match
            # gather degree only at head hits; other rows read slot 0
            # (one hot cache line) instead of a random miss
            d = degree[jnp.where(head_hit, cand, 0)]
            new_rank = d + chunk_rank
            head_slot = jnp.where(head_hit, cand, head_slot)
            # degree-0 head hit with chunk_rank 0: the target IS the
            # head entry, already present (stranded) — take it
            done_h = head_hit & (new_rank == 0)
            slots = jnp.where(done_h, cand, slots)
            existed = existed | done_h
            done = done | done_h
            sw_hit = head_hit & (new_rank > 0)
            # head absent (true empty terminates its chain): degree 0;
            # rows with chunk_rank > 0 move on — their rank-0 sibling
            # claims the head
            sw_empty = p1 & empty & (chunk_rank > 0)
            switched = sw_hit | sw_empty
            phase2 = phase2 | switched
            new_target = jnp.where(sw_hit, new_rank, chunk_rank)
            target = jnp.where(switched, new_target, target)
            target_tag = jnp.where(
                switched, tag_of(base, new_target), target_tag
            )
            off = jnp.where(switched, 0, off)

            # -- phase 2: find-or-claim (hash, target) -----------------
            hit2 = ~done & phase2 & ~switched & match
            slots = jnp.where(hit2, cand, slots)
            existed = existed | hit2
            done = done | hit2

            # claims (same scratch-race as _probe): phase-1 rank-0 rows
            # claim the head; phase-2 rows claim their target entry
            want = ~done & ~switched & empty & (phase2 | (chunk_rank == 0))
            m = 4 * width
            scratch_idx = cand % m
            claim = jnp.full((m,), width, jnp.int32).at[
                jnp.where(want, scratch_idx, m)
            ].min(jnp.where(want, row_idx, width), mode="drop")
            won = want & (claim[scratch_idx] == row_idx)
            pos = jnp.where(won, cand, sentinel)
            tags = tags.at[pos].set(target_tag, mode="drop")
            slots = jnp.where(won, cand, slots)
            head_slot = jnp.where(won & (target == 0), cand, head_slot)
            inserted = inserted | won
            done = done | won
            advance = ~done & ~switched & ((~empty & ~match) | tomb)
            off = jnp.where(advance, off + 1, off)
            return (tags, steps), (done, slots, inserted, existed, phase2,
                                   target, target_tag, head_slot, off)

        # split hash: fold the 64-bit key hash once; re-finalize with
        # the (varying) rank on phase switches only
        base = hash64_partial([hashes])
        zeros = jnp.zeros((cap,), jnp.int32)
        no = jnp.zeros((cap,), jnp.bool_)
        rows = (
            ~valid,
            jnp.full((cap,), sentinel, jnp.int32),
            no, no, no,
            zeros,
            tag_of(base, zeros),
            jnp.full((cap,), sentinel, jnp.int32),
            zeros,
        )
        (tags, steps), rows, iters, _ = _probe_loop(
            step, (self.tags, jnp.int32(0)), rows, (chunk_rank, base),
            max_iters)
        done, slots, inserted, existed, _, target, _, head_slot, _ = rows
        overflow = ~done
        table = TagTable(tags, size)
        return (table, slots, target, head_slot, inserted,
                existed & valid, overflow, iters, steps)

    # -- maintenance ----------------------------------------------------
    def clear_slots(self, slots: jnp.ndarray,
                    mask: jnp.ndarray) -> "TagTable":
        """Tombstone specific slots (e.g. un-claim on pool overflow)."""
        pos = jnp.where(mask, slots, jnp.int32(self.size))
        return TagTable(
            self.tags.at[pos].set(TOMB_TAG, mode="drop"), self.size
        )

    def reclaimed(self, dense=(), inits=()):
        """``HashTable.reclaimed`` for packed tags: every tombstone
        becomes empty and only the entries whose probe chain crossed one
        are put back (``_reclaim``); ``dense`` per-slot leaves move with
        them.  Returns ``(table, dense', lost)``."""
        size = self.size
        old = self.tags

        def emptied(keep):
            return TagTable(jnp.where(keep, old, EMPTY_TAG), size)

        def reinsert(table, safe, valid):
            tags, slots, _, over, _ = table._probe_tags(
                old[safe], valid, insert=True)
            return TagTable(tags, size), slots, over

        return _reclaim(size, old >= np.uint64(2), old == TOMB_TAG,
                        tag_home(old, size),
                        emptied, reinsert, lambda t: t.occupied,
                        dense, inits, TAG_RECLAIM_TILE)
