"""ShadowSnapshot: incremental device-side in-memory snapshots.

Reference counterpart: Hummock never re-uploads a full state snapshot
per epoch — ``commit_epoch`` persists only each epoch's dirty deltas
(docs/dev/src/design/checkpoint.md).  The old in-memory snapshot here
was the opposite: a full device tree copy every snapshot barrier, a
periodic multi-second stall that PERF_ATTRIBUTION round 6 measured at
roughly HALF the q8 window.

TPU-first incremental design: the snapshot is a persistent device-side
SHADOW of the state tree plus its block-digest vector.  One jitted
program per state shape, dispatched once per snapshot barrier:

1. digest every live leaf in fixed-size blocks (storage/digest.py —
   the SAME scheme the durable store diffs with, so the digest pass
   runs ONCE and is shared);
2. diff against the shadow's digest vector → per-block dirty mask;
3. copy only the dirty blocks live→shadow, through a budget ladder
   (1/64 → 1/8 → full per leaf, selected on device by ``lax.switch``
   on the dirty count) — gather/scatter traffic is O(dirty blocks),
   never O(state), and the shadow buffers are donated so no new
   allocation happens on the steady path.

The program is dispatched asynchronously — zero synchronous
device→host transfers; the dirty count stays a device scalar until an
observability surface explicitly asks for it.

Invariant: ``self.digests`` always equals the digest of the shadow's
CONTENTS.  The update diffs live digests against shadow digests, so
the shadow self-heals toward whatever the live tree is — recovery may
restore live state older than the shadow (durable rewind) and the next
update still converges, because every differing block is by definition
dirty under the diff.

Programs are cached process-wide by (state signature, block size) —
tests and restarted jobs with identical tree shapes reuse compiles.

Collision caveat: a 64-bit block digest collision would silently skip
a changed block.  The durable delta store has always accepted this
(2^-64-ish per block); the shadow inherits the same odds.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from risingwave_tpu.storage.digest import (
    DEFAULT_BLOCK_ELEMS,
    lane_block_count,
    leaf_block_count,
    leaf_digest,
    leaf_digest_lanes,
)

#: leaves at/below this many blocks skip the ladder and copy whole
#: (scalars/counters — a gather program costs more than the copy)
_SMALL_NB = 8

#: compiled (init, update, restore) per (sig, block) — bounded
_PROG_CACHE: dict = {}
_PROG_CACHE_MAX = 16


def _copy_leaf(flat, sh, dirty, nb: int, n: int, block: int):
    """Dirty-budget ladder for one leaf: windowed gather/scatter of K
    whole blocks when K bounds the dirty count, else the next rung,
    else a full leaf copy.  All rungs run on device — no host
    readback.  The windowed ops move contiguous ``block``-element runs
    (near-memcpy per block), not per-element indices."""
    nb_full = n // block
    if nb <= _SMALL_NB or nb_full < 2:
        return flat, jnp.int64(0)
    nd = jnp.sum(dirty)
    # dirty FULL-block ids first, ascending (stable argsort of ~dirty);
    # the ragged tail block is copied unconditionally below
    order = jnp.argsort(jnp.logical_not(dirty[:nb_full]), stable=True)

    gdims = jax.lax.GatherDimensionNumbers(
        offset_dims=(1,), collapsed_slice_dims=(),
        start_index_map=(0,),
    )
    sdims = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1,), inserted_window_dims=(),
        scatter_dims_to_operand_dims=(0,),
    )

    def rung(k: int):
        def body(operand):
            flat, sh = operand
            starts = (order[:k] * block).astype(jnp.int32)[:, None]
            vals = jax.lax.gather(
                flat, starts, gdims, slice_sizes=(block,),
                unique_indices=True,
                mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS,
            )
            return jax.lax.scatter(
                sh, starts, vals, sdims, unique_indices=True,
                mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS,
            )

        return body

    k0 = max(1, nb_full // 64)
    k1 = max(1, nb_full // 8)
    level = (nd > k0).astype(jnp.int32) + (nd > k1).astype(jnp.int32)
    new_sh = jax.lax.switch(
        level,
        [rung(k0), rung(k1), lambda operand: operand[0]],
        (flat, sh),
    )
    tail = n - nb_full * block
    if tail:
        new_sh = jax.lax.dynamic_update_slice(
            new_sh, flat[nb_full * block:], (nb_full * block,)
        )
    return new_sh, nd.astype(jnp.int64)


def _copy_leaf_rows(flat, sh, dirty, rows: int, m: int, block: int):
    """Lane-aware dirty-budget ladder (mesh-stacked leaves): like
    ``_copy_leaf``, but block starts are computed per (lane, block)
    pair — ``start = lane*m + b*block`` — so the windowed gather/
    scatter never crosses a shard row's boundary, and each lane's
    ragged tail copies unconditionally as ONE static slice update
    over the shard axis."""
    nb_row = max(1, -(-m // block))
    nbf = m // block  # full blocks per lane
    if rows * nb_row <= _SMALL_NB or rows * nbf < 2:
        return flat, jnp.int64(0)
    nd = jnp.sum(dirty)
    dirty_full = dirty.reshape(rows, nb_row)[:, :nbf].reshape(-1)
    order = jnp.argsort(jnp.logical_not(dirty_full), stable=True)

    gdims = jax.lax.GatherDimensionNumbers(
        offset_dims=(1,), collapsed_slice_dims=(),
        start_index_map=(0,),
    )
    sdims = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1,), inserted_window_dims=(),
        scatter_dims_to_operand_dims=(0,),
    )

    def rung(k: int):
        def body(operand):
            flat, sh = operand
            ids = order[:k]
            starts = ((ids // nbf) * m + (ids % nbf) * block) \
                .astype(jnp.int32)[:, None]
            vals = jax.lax.gather(
                flat, starts, gdims, slice_sizes=(block,),
                unique_indices=True,
                mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS,
            )
            return jax.lax.scatter(
                sh, starts, vals, sdims, unique_indices=True,
                mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS,
            )

        return body

    k0 = max(1, rows * nbf // 64)
    k1 = max(1, rows * nbf // 8)
    level = (nd > k0).astype(jnp.int32) + (nd > k1).astype(jnp.int32)
    new_sh = jax.lax.switch(
        level,
        [rung(k0), rung(k1), lambda operand: operand[0]],
        (flat, sh),
    )
    tail = m - nbf * block
    if tail:
        new_sh = new_sh.reshape(rows, m).at[:, nbf * block:].set(
            flat.reshape(rows, m)[:, nbf * block:]
        ).reshape(-1)
    return new_sh, nd.astype(jnp.int64)


def leaf_lanes(shape, shard_rows) -> tuple | None:
    """Lane structure of one leaf under a per-shard digest scheme:
    ``(rows, row_elems)`` when the leaf carries the mesh-stacked
    leading axis, else None (flat digesting)."""
    if not shard_rows or not shape or shape[0] != shard_rows:
        return None
    n = int(np.prod(shape)) if shape else 1
    return (shard_rows, n // shard_rows)


def _build_programs(sig, block: int, digest: bool, shard_rows):
    shapes = [s for _, s in sig]
    lanes = [leaf_lanes(s, shard_rows) for s in shapes]
    nblocks = [
        lane_block_count(s, ln[0], block) if ln
        else leaf_block_count(s, block)
        for s, ln in zip(shapes, lanes)
    ]
    sizes = [int(np.prod(s)) if s else 1 for s in shapes]
    total = sum(nblocks)

    def digest_one(flat, nb, ln):
        return leaf_digest_lanes(flat, ln[0], block) if ln \
            else leaf_digest(flat, nb, block)

    def init(leaves):
        flat = tuple(jnp.copy(jnp.asarray(x).reshape(-1))
                     for x in leaves)
        d = jnp.concatenate([
            digest_one(x, nb, ln)
            for x, nb, ln in zip(flat, nblocks, lanes)
        ]) if digest else jnp.zeros((0,), jnp.uint64)
        return flat, d

    def update(live_leaves, shadow_leaves, old_digests):
        with jax.named_scope("ShadowSnapshot/update"):
            return _update(live_leaves, shadow_leaves, old_digests)

    def _update(live_leaves, shadow_leaves, old_digests):
        if not digest:
            # store-less mode: no durable delta wants the digest, so
            # the cheapest correct snapshot is a straight copy INTO
            # the donated shadow buffers (no allocation churn — the
            # part of the old full-copy path that actually hurt)
            new_shadow = tuple(
                jnp.copy(jnp.asarray(x).reshape(-1))
                for x in live_leaves
            )
            return (new_shadow, old_digests, jnp.int64(total))
        new_shadow = []
        new_digests = []
        dirty_total = jnp.zeros((), jnp.int64)
        off = 0
        for x, sh, nb, n, ln in zip(live_leaves, shadow_leaves,
                                    nblocks, sizes, lanes):
            flat = jnp.asarray(x).reshape(-1)
            d = digest_one(flat, nb, ln)
            dirty = d != jax.lax.dynamic_slice(
                old_digests, (off,), (nb,)
            )
            off += nb
            if ln:
                new_sh, nd = _copy_leaf_rows(
                    flat, sh, dirty, ln[0], ln[1], block
                )
            else:
                new_sh, nd = _copy_leaf(flat, sh, dirty, nb, n, block)
            new_shadow.append(new_sh)
            new_digests.append(d)
            dirty_total = dirty_total + nd
        return (tuple(new_shadow), jnp.concatenate(new_digests),
                dirty_total)

    def restore(shadow_leaves):
        return tuple(
            jnp.copy(f).reshape(s)
            for f, s in zip(shadow_leaves, shapes)
        )

    return (
        jax.jit(init),
        jax.jit(update, donate_argnums=(1, 2)),
        jax.jit(restore),
    )


def _programs(sig, block: int, digest: bool, shard_rows):
    key = (sig, block, digest, shard_rows)
    hit = _PROG_CACHE.get(key)
    if hit is None:
        if len(_PROG_CACHE) >= _PROG_CACHE_MAX:
            _PROG_CACHE.pop(next(iter(_PROG_CACHE)))
        hit = _build_programs(sig, block, digest, shard_rows)
        _PROG_CACHE[key] = hit
    return hit


class ShadowSnapshot:
    """A device-resident shadow of one job's state tree.

    ``digest=True`` (the durable mode): block-digest diff + dirty-run
    scatter; the digest vector feeds the checkpoint store's delta
    upload.  ``digest=False`` (store-less jobs): nothing consumes the
    digest, so the update is a straight copy into the persistent
    (donated) shadow buffers — no digest pass, no allocation churn.

    ``shard_rows=N`` (mesh-stacked trees): every leaf whose leading
    axis is the shard axis digests in N per-shard LANES — the block
    grid restarts at each shard row, so no digest block (and no
    dirty-run copy) ever spans two shards.  ``lanes`` records the
    per-leaf structure for the checkpoint store's delta extraction."""

    def __init__(self, states, block_elems: int = DEFAULT_BLOCK_ELEMS,
                 digest: bool = True, shard_rows: int | None = None):
        leaves, self.treedef = jax.tree.flatten(states)
        self.block = block_elems
        self.digest_mode = digest
        self.shard_rows = shard_rows
        self.shapes = [np.shape(x) for x in leaves]
        self.sig = tuple(
            (str(x.dtype), np.shape(x)) for x in leaves
        )
        #: per-leaf (rows, row_elems) lane structure, None = flat —
        #: shipped with every UploadTask so the store's dirty-run
        #: extraction uses the same block grid as the digest
        self.lanes = [leaf_lanes(s, shard_rows) for s in self.shapes]
        self.nblocks = [
            lane_block_count(s, ln[0], block_elems) if ln
            else leaf_block_count(s, block_elems)
            for s, ln in zip(self.shapes, self.lanes)
        ]
        self.total_blocks = int(sum(self.nblocks))
        self._init_prog, self._update_prog, self._restore_prog = \
            _programs(self.sig, block_elems, digest, shard_rows)
        #: flat device copies of every leaf (the shadow contents)
        self.leaves, self.digests = self._init_prog(tuple(leaves))
        #: dirty blocks of the LAST update (device scalar; read only by
        #: observability surfaces — never on the barrier path)
        self.dirty_blocks = jnp.zeros((), jnp.int64)
        #: epoch the shadow currently reflects (host bookkeeping)
        self.epoch = 0
        # warm the update program NOW (a clean no-op diff): the first
        # shadow build lands in a warmup/compile window — the second
        # snapshot must not pay the XLA compile inside the measured
        # steady state
        self.update(states)

    # ------------------------------------------------------------------
    def matches(self, states) -> bool:
        leaves = jax.tree.leaves(states)
        if len(leaves) != len(self.sig):
            return False
        return all(
            (str(x.dtype), np.shape(x)) == s
            for x, s in zip(leaves, self.sig)
        )

    def update(self, states, epoch: int = 0):
        """One async dispatch: diff live vs shadow, copy dirty blocks
        into the (donated) shadow, refresh the digest vector.  Returns
        the new digest vector (device array) for the durable store."""
        leaves = jax.tree.leaves(states)
        self.leaves, self.digests, self.dirty_blocks = self._update_prog(
            tuple(leaves), self.leaves, self.digests
        )
        self.epoch = epoch
        return self.digests

    # ------------------------------------------------------------------
    def restore(self):
        """A fresh device tree equal to the shadow contents (one
        dispatch).  The copies are independent buffers, safe to donate
        into step programs without touching the shadow."""
        leaves = self._restore_prog(self.leaves)
        return jax.tree.unflatten(self.treedef, list(leaves))

    # ------------------------------------------------------------------
    def dirty_ratio(self) -> float:
        """Dirty fraction of the LAST update (host readback — for
        metrics/ctl surfaces only, never the barrier path)."""
        return float(np.asarray(self.dirty_blocks)) / max(
            1, self.total_blocks
        )
