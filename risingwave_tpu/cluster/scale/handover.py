"""Online state handover: per-vnode checkpoint slices + transplant.

Reference counterpart: the reschedule plan of ``scale.rs`` — when a
vnode moves, the state *behind* it (agg groups, MV rows keyed in that
vnode) moves with it, anchored at a checkpoint epoch so the transfer
is exact.

Mechanics here (cluster/meta_service drives the protocol):

1. the meta seals a round whose checkpoints are DURABLE on every
   partition (the handover epoch);
2. the recipient loads each donor partition's checkpoint *at that
   epoch* from the SHARED checkpoint store — state never crosses an
   RPC, only the moved keys' slices leave disk;
3. ``slice_partition_states`` extracts exactly the moved vnodes'
   entries (group keys + every per-slot state array) — the "only
   moved vnodes transfer" contract is structural, not best-effort;
4. ``clear_vnodes`` tombstones any stale entries the recipient still
   holds for the gained vnodes (a worker regaining vnodes it donated
   earlier refreshes, never resurrects);
5. ``transplant`` bulk find-or-claims the moved keys in the live
   tables (``HashTable.lookup_or_insert`` over the whole slice) and
   scatters the donor's per-slot arrays at the claimed slots.

Eligible state shapes: ``HashAggExecutor`` (prims / row_count / prev
snapshot / emitted / dirty — everything slot-aligned) and
``MaterializeExecutor`` (pk table + dense value columns).  The
engine's eligibility gate guarantees no DISTINCT dedup or materialised-
input tables and an empty spill ring; asserted loudly here anyway.

Exchange-lite (round 14) extends the same contract to partitioned
JOIN jobs and MV-on-MV DAGs: ``partition_sites`` walks a ``DagJob``'s
node tree and yields every sliceable state (aggs, materializes, and
dense hash-join *sides* — key table + [size, B] row buckets + per-key
degree counters, moved as whole key entries so the bucket layout, and
therefore the emission order, is preserved bit-for-bit).  Every keyed
state's LEADING key lives in the same ``hash64`` vnode domain as the
routing key — the engine's partition eligibility enforces that at
adoption, which is what lets one vnode set slice the whole tree.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from risingwave_tpu.cluster.scale.vnode import (
    vnode_member_mask,
    vnodes_of_ints,
)
from risingwave_tpu.common.chunk import NCol, StrCol
from risingwave_tpu.state.hash_table import gather_key
from risingwave_tpu.stream.hash_agg import HashAggExecutor
from risingwave_tpu.stream.materialize import (
    MaterializeExecutor,
    _scatter_col,
)


def _to_dev(col):
    """Host slice column → device (NCol/StrCol aware)."""
    if isinstance(col, NCol):
        return NCol(_to_dev(col.data), jnp.asarray(col.null))
    if isinstance(col, StrCol):
        return StrCol(jnp.asarray(col.data), jnp.asarray(col.lens))
    return jnp.asarray(col)


def _dist_payload(col):
    """Raw integer payload of the distribution key column (the
    eligibility gate guarantees NOT NULL integer family)."""
    if isinstance(col, NCol):
        return col.data
    return col


def _entry_mask(table, vnodes, n_vnodes) -> np.ndarray:
    """Host ``bool [size]``: occupied slots whose key falls in the
    vnode set."""
    occ = np.asarray(table.occupied)
    vn = np.asarray(vnodes_of_ints(
        _dist_payload(table.key_cols[0]), n_vnodes
    ))
    member = np.zeros((n_vnodes,), bool)
    member[[int(v) for v in vnodes]] = True
    return occ & member[vn]


def _assert_plain_agg(ex: HashAggExecutor, state) -> None:
    if state.distinct_tables:
        raise RuntimeError(
            "vnode handover over a DISTINCT aggregation (dedup tables "
            "are not sliceable): not scale-eligible"
        )
    spill = getattr(state, "spill_count", ())
    if not isinstance(spill, tuple) and int(np.asarray(spill)) != 0:
        raise RuntimeError(
            "vnode handover with rows in the spill ring — drain first"
        )


# -- slice (donor checkpoint → moved entries) ---------------------------
def slice_partition_states(executors, states, vnodes,
                           n_vnodes: int) -> dict[int, dict]:
    """Extract the moved vnodes' entries from a (host) checkpoint
    state tree: ``{executor_idx: slice}`` for every keyed executor.

    Works on the numpy trees ``CheckpointStore.load`` returns (and on
    device trees — gathers go through numpy either way)."""
    out: dict[int, dict] = {}
    for i, ex in enumerate(executors):
        st = states[i]
        if isinstance(ex, HashAggExecutor):
            _assert_plain_agg(ex, st)
            take = _entry_mask(st.table, vnodes, n_vnodes)
            idx = np.nonzero(take)[0]
            out[i] = {
                "kind": "agg",
                "n": int(idx.shape[0]),
                "keys": [gather_key(np.asarray(c) if not isinstance(
                    c, (NCol, StrCol)) else c, idx)
                    for c in st.table.key_cols],
                "prims": [np.asarray(p)[idx] for p in st.prims],
                "prev_prims": [np.asarray(p)[idx]
                               for p in st.prev_prims],
                "row_count": np.asarray(st.row_count)[idx],
                "prev_row_count": np.asarray(st.prev_row_count)[idx],
                "dirty": np.asarray(st.dirty)[idx],
                "emitted": np.asarray(st.emitted)[idx],
            }
        elif isinstance(ex, MaterializeExecutor):
            take = _entry_mask(st.table, vnodes, n_vnodes)
            idx = np.nonzero(take)[0]
            out[i] = {
                "kind": "mv",
                "n": int(idx.shape[0]),
                "keys": [gather_key(np.asarray(c) if not isinstance(
                    c, (NCol, StrCol)) else c, idx)
                    for c in st.table.key_cols],
                "values": [gather_key(v if isinstance(v, (NCol, StrCol))
                                      else np.asarray(v), idx)
                           for v in st.values],
            }
    return out


# -- clear (recipient live state: evict stale entries in gained set) ----
def clear_vnodes(executors, states, vnodes, n_vnodes: int):
    """Tombstone every live entry in the given vnode set (stale state
    from an earlier ownership must never shadow the donor's current
    slice).  Returns (states', cleared_entries)."""
    new_states = list(states)
    cleared = 0
    member = vnode_member_mask(vnodes, n_vnodes)
    for i, ex in enumerate(executors):
        st = states[i]
        if isinstance(ex, HashAggExecutor):
            vn = vnodes_of_ints(
                _dist_payload(st.table.key_cols[0]), n_vnodes
            )
            stale = st.table.occupied & member[vn]
            cleared += int(jnp.sum(stale))
            new_states[i] = st._replace(
                table=st.table.clear_where(stale),
                row_count=jnp.where(stale, 0, st.row_count),
                prev_row_count=jnp.where(stale, 0, st.prev_row_count),
                dirty=st.dirty & ~stale,
                emitted=st.emitted & ~stale,
            )
        elif isinstance(ex, MaterializeExecutor):
            vn = vnodes_of_ints(
                _dist_payload(st.table.key_cols[0]), n_vnodes
            )
            stale = st.table.occupied & member[vn]
            cleared += int(jnp.sum(stale))
            new_states[i] = st._replace(table=st.table.clear_where(stale))
    return tuple(new_states), cleared


# -- DagJob partitions: joins + MV-on-MV trees --------------------------
def partition_sites(job) -> list[tuple]:
    """Every sliceable keyed state of a partitioned job as
    ``(path, kind, executor)``: path indexes the (possibly nested)
    state tree — ``(i,)`` for a linear StreamingJob executor,
    ``(node, exec)`` for a DagJob fragment executor, ``(node,)`` for a
    JoinNode."""
    from risingwave_tpu.stream.dag import DagJob, JoinNode

    sites: list[tuple] = []
    if not isinstance(job, DagJob):
        for i, ex in enumerate(job.fragment.executors):
            if isinstance(ex, (HashAggExecutor, MaterializeExecutor)):
                sites.append(((i,), "agg" if isinstance(
                    ex, HashAggExecutor) else "mv", ex))
        return sites
    for ni, node in enumerate(job.nodes):
        if node is None:
            continue
        if isinstance(node, JoinNode):
            sites.append(((ni,), "join", node.join))
            continue
        for ei, ex in enumerate(node.fragment.executors):
            if isinstance(ex, HashAggExecutor):
                sites.append(((ni, ei), "agg", ex))
            elif isinstance(ex, MaterializeExecutor):
                sites.append(((ni, ei), "mv", ex))
    return sites


def _tree_get(states, path):
    st = states
    for i in path:
        st = st[i]
    return st


def _tree_set(states, path, value):
    if not path:
        return value
    lst = list(states)
    lst[path[0]] = _tree_set(states[path[0]], path[1:], value)
    return tuple(lst)


def _scatter_bucket(store, slots, vals):
    """Write whole [n, B] bucket rows at entry ``slots`` (NCol/StrCol
    aware — the inverse of ``hash_join._gather_bucket``)."""
    if isinstance(vals, NCol):
        return NCol(_scatter_bucket(store.data, slots, vals.data),
                    store.null.at[slots].set(vals.null, mode="drop"))
    if isinstance(vals, StrCol):
        return StrCol(store.data.at[slots].set(vals.data, mode="drop"),
                      store.lens.at[slots].set(vals.lens, mode="drop"))
    return store.at[slots].set(jnp.asarray(vals), mode="drop")


def _gather_host_bucket(store, idx):
    """[size, B, ...] host-gathered at idx -> [n, B, ...]."""
    if isinstance(store, NCol):
        return NCol(_gather_host_bucket(store.data, idx),
                    np.asarray(store.null)[idx])
    if isinstance(store, StrCol):
        return StrCol(np.asarray(store.data)[idx],
                      np.asarray(store.lens)[idx])
    return np.asarray(store)[idx]


def _assert_dense_join(join, st) -> None:
    from risingwave_tpu.stream.hash_join import SideState

    for side_name in ("left", "right"):
        side = getattr(st, side_name)
        if not isinstance(side, SideState):
            raise RuntimeError(
                "vnode handover over a pool-storage join side "
                "(append-only pools are not sliceable): not "
                "scale-eligible"
            )


def _slice_join_side(side, vnodes, n_vnodes: int) -> dict:
    """Extract whole key entries (key + bucket rows + degree) whose
    FIRST join-key column's vnode moved."""
    take = _entry_mask(side.key_table, vnodes, n_vnodes)
    idx = np.nonzero(take)[0]
    return {
        "n": int(idx.shape[0]),
        "keys": [gather_key(c if isinstance(c, (NCol, StrCol))
                            else np.asarray(c), idx)
                 for c in side.key_table.key_cols],
        "rows": [_gather_host_bucket(r, idx) for r in side.rows],
        "occupied": np.asarray(side.occupied)[idx],
        "count": np.asarray(side.count)[idx],
    }


def _clear_join_side(side, member, n_vnodes: int):
    vn = vnodes_of_ints(_dist_payload(side.key_table.key_cols[0]),
                        n_vnodes)
    stale = side.key_table.occupied & member[vn]
    cleared = int(jnp.sum(stale))
    return side._replace(
        key_table=side.key_table.clear_where(stale),
        occupied=side.occupied & ~stale[:, None],
        count=jnp.where(stale, 0, side.count),
    ), cleared


def _transplant_join_side(side, sl: dict):
    n = sl["n"]
    if n == 0:
        return side, 0
    keys = [_to_dev(c) for c in sl["keys"]]
    valid = jnp.ones((n,), jnp.bool_)
    table, slots, _, overflow = side.key_table.lookup_or_insert(
        keys, valid
    )
    if bool(jnp.any(overflow & valid)):
        raise RuntimeError(
            f"vnode transplant overflowed a join key table ({n} "
            "entries) — increase table capacity"
        )
    return side._replace(
        key_table=table,
        rows=tuple(
            _scatter_bucket(store, slots, _to_dev(col))
            for store, col in zip(side.rows, sl["rows"])
        ),
        occupied=side.occupied.at[slots].set(
            _to_dev(sl["occupied"]), mode="drop"),
        count=side.count.at[slots].set(
            _to_dev(sl["count"]), mode="drop"),
    ), n


def slice_job_states(job, states, vnodes, n_vnodes: int) -> dict:
    """``slice_partition_states`` generalized over a partitioned job's
    (possibly nested) state tree; keys are state PATHS."""
    out: dict[tuple, dict] = {}
    for path, kind, ex in partition_sites(job):
        st = _tree_get(states, path)
        if kind == "join":
            _assert_dense_join(ex, st)
            left = _slice_join_side(st.left, vnodes, n_vnodes)
            right = _slice_join_side(st.right, vnodes, n_vnodes)
            out[path] = {"kind": "join", "left": left, "right": right,
                         "n": left["n"] + right["n"]}
        else:
            sl = slice_partition_states([ex], (st,), vnodes, n_vnodes)
            out[path] = sl[0]
    return out


def clear_job_vnodes(job, states, vnodes, n_vnodes: int):
    """``clear_vnodes`` over a partitioned job's state tree."""
    member = vnode_member_mask(vnodes, n_vnodes)
    cleared = 0
    for path, kind, ex in partition_sites(job):
        st = _tree_get(states, path)
        if kind == "join":
            _assert_dense_join(ex, st)
            left, c1 = _clear_join_side(st.left, member, n_vnodes)
            right, c2 = _clear_join_side(st.right, member, n_vnodes)
            states = _tree_set(states, path,
                               st._replace(left=left, right=right))
            cleared += c1 + c2
        else:
            new, c = clear_vnodes([ex], (st,), vnodes, n_vnodes)
            states = _tree_set(states, path, new[0])
            cleared += c
    return states, cleared


def transplant_job(job, states, slices: dict):
    """``transplant`` over a partitioned job's state tree (slices
    keyed by state path, as produced by ``slice_job_states``)."""
    sites = {path: (kind, ex) for path, kind, ex in
             partition_sites(job)}
    moved = 0
    for path, sl in slices.items():
        path = tuple(path)
        kind, ex = sites[path]
        st = _tree_get(states, path)
        if sl.get("kind") == "join":
            left, n1 = _transplant_join_side(st.left, sl["left"])
            right, n2 = _transplant_join_side(st.right, sl["right"])
            states = _tree_set(states, path,
                               st._replace(left=left, right=right))
            moved += n1 + n2
        else:
            new, n = transplant([ex], (st,), {0: sl})
            states = _tree_set(states, path, new[0])
            moved += n
    return states, moved


# -- transplant (moved entries → recipient live state) ------------------
def transplant(executors, states, slices: dict[int, dict]):
    """Merge donor slices into the live state tree; returns
    ``(states', entries_moved)``.  Raises loudly when the recipient
    table cannot claim a slot (undersized table — the overflow analog
    of the streaming path's loud counters)."""
    new_states = list(states)
    moved = 0
    for i, sl in slices.items():
        st = states[i]
        n = sl["n"]
        if n == 0:
            continue
        keys = [_to_dev(c) for c in sl["keys"]]
        valid = jnp.ones((n,), jnp.bool_)
        table, slots, _, overflow = st.table.lookup_or_insert(
            keys, valid
        )
        if bool(jnp.any(overflow & valid)):
            raise RuntimeError(
                f"vnode transplant overflowed executor {i}'s table "
                f"({n} entries) — increase table capacity"
            )
        if sl["kind"] == "agg":
            new_states[i] = st._replace(
                table=table,
                prims=tuple(
                    p.at[slots].set(_to_dev(v), mode="drop")
                    for p, v in zip(st.prims, sl["prims"])
                ),
                prev_prims=tuple(
                    p.at[slots].set(_to_dev(v), mode="drop")
                    for p, v in zip(st.prev_prims, sl["prev_prims"])
                ),
                row_count=st.row_count.at[slots].set(
                    _to_dev(sl["row_count"]), mode="drop"),
                prev_row_count=st.prev_row_count.at[slots].set(
                    _to_dev(sl["prev_row_count"]), mode="drop"),
                dirty=st.dirty.at[slots].set(
                    _to_dev(sl["dirty"]), mode="drop"),
                emitted=st.emitted.at[slots].set(
                    _to_dev(sl["emitted"]), mode="drop"),
            )
        else:
            values = tuple(
                _scatter_col(store, slots, _to_dev(col))
                for store, col in zip(st.values, sl["values"])
            )
            new_states[i] = st._replace(table=table, values=values)
        moved += n
    return tuple(new_states), moved
