"""Hash exchange: vnode partitioning + all_to_all shuffle.

Reference counterparts:
- ``HashDataDispatcher::dispatch_data`` — src/stream/src/executor/
  dispatch.rs:949 (vectorized vnode computation + per-output visibility
  bitmaps)
- ``StreamExchangeService.GetStream`` — proto/task_service.proto:156
  (credit-based chunk exchange)
- ``MergeExecutor`` alignment — src/stream/src/executor/merge.rs:161

TPU-first design
----------------
Inside a ``shard_map``-ed fragment step, each shard partitions its
output chunk into ``n_shards`` fixed-capacity buckets (scatter by
destination, visibility-masked) and one ``lax.all_to_all`` swaps bucket
``i→j`` over ICI.  The received buckets concatenate into a single
``n_shards*cap`` chunk — merge alignment is implicit because SPMD runs
every shard in lockstep per step (credits/permits are unnecessary:
backpressure is the synchronous dataflow itself).

Like the reference, routing is vnode-based (vnode = crc32(keys) %
VNODE_COUNT, then vnode→shard by contiguous ranges), so elastic rescale
= remapping vnode ranges at a barrier, and state follows vnodes.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from risingwave_tpu.common.chunk import Chunk, NCol, StrCol
from risingwave_tpu.common.hash import VNODE_COUNT, compute_vnodes


def shard_map_nocheck(body, *, mesh, in_specs, out_specs):
    """``shard_map`` with the varying-manual-axes check disabled — the
    per-shard streaming bodies intentionally mix replicated and varying
    values."""
    return jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def axis_min(x, axis: str):
    """Minimum of a per-shard scalar over a mesh axis.  Not
    ``lax.pmin``: the TPU compiler lowers a 64-bit all-reduce for sums
    only ("Supported lowering only of Sum all reduce"), and watermarks
    and pending counts are int64 — gather the scalars and reduce here."""
    return jnp.min(jax.lax.all_gather(x, axis), axis=0)


def axis_max(x, axis: str):
    """Maximum over a mesh axis (see ``axis_min``)."""
    return jnp.max(jax.lax.all_gather(x, axis), axis=0)


#: trace-time exchange audit (profile_q8 --assert --sharded): each
#: ``shuffle_chunk`` TRACE bumps ``calls`` and adds the per-shard
#: all_to_all payload bytes.  Programs compile once, so after a warm
#: run this reflects exactly what the compiled graphs contain — a
#: per-row or per-window exchange regression shows up as extra traced
#: calls/bytes, with zero steady-state cost (nothing runs on device).
EXCHANGE_TRACE = {"calls": 0, "bytes": 0}


def reset_exchange_trace() -> None:
    EXCHANGE_TRACE["calls"] = 0
    EXCHANGE_TRACE["bytes"] = 0


def _trace_bytes(x) -> int:
    return int(np.prod(x.shape)) * x.dtype.itemsize


def single_shard_keys(chunk) -> list:
    """Constant routing key: every row hashes to ONE owning shard.

    The device analog of the reference's singleton fragments (global
    aggs / global TopN need a total view): an all_to_all keyed on a
    constant routes the whole stream to whichever shard owns
    vnode(hash(0)), and the other shards run the same programs over
    empty chunks — byte-identical to the linear run at that shard."""
    return [jnp.zeros((chunk.capacity,), jnp.int64)]


def shard_of_vnode(vnodes: jnp.ndarray, n_shards: int,
                   vnode_count: int = VNODE_COUNT) -> jnp.ndarray:
    """Contiguous-range vnode→shard mapping (ref WorkerSlotMapping)."""
    if n_shards > vnode_count:
        raise ValueError(
            f"n_shards={n_shards} exceeds vnode_count={vnode_count}; raise "
            "the job's vnode count (ref: max 2^15 vnodes, vnode.rs:30)"
        )
    per = vnode_count // n_shards
    return jnp.minimum(vnodes // per, n_shards - 1).astype(jnp.int32)


def _bucketize(col, dest_slot: jnp.ndarray, n_shards: int, cap: int):
    """Scatter a [cap] column into [n_shards*cap] bucket-major layout."""
    if isinstance(col, NCol):
        return NCol(
            _bucketize(col.data, dest_slot, n_shards, cap),
            # unfilled bucket slots read as NULL (their validity is
            # False anyway, but NULL is the safe default payload)
            jnp.ones((n_shards * cap,), jnp.bool_).at[dest_slot].set(
                col.null, mode="drop"
            ),
        )
    if isinstance(col, StrCol):
        return StrCol(
            _bucketize(col.data, dest_slot, n_shards, cap),
            _bucketize(col.lens, dest_slot, n_shards, cap),
        )
    out = jnp.zeros((n_shards * cap,) + col.shape[1:], col.dtype)
    return out.at[dest_slot].set(col, mode="drop")


def shuffle_chunk(
    chunk: Chunk,
    key_cols: Sequence,
    axis_name: str,
    n_shards: int,
    vnode_count: int = VNODE_COUNT,
) -> Chunk:
    """Exchange a chunk's rows to their key-owning shards.

    Must be called inside ``shard_map``.  Returns the received chunk of
    capacity ``n_shards * cap`` (worst-case skew-safe: each sender may
    route its whole chunk to one shard).
    """
    cap = chunk.capacity
    vnodes = compute_vnodes(key_cols, vnode_count)
    dest = shard_of_vnode(vnodes, n_shards, vnode_count)
    dest = jnp.where(chunk.valid, dest, n_shards)  # invalid rows dropped

    # position within the destination bucket: stable rank among rows
    # with the same destination (argsort-of-argsort trick, shape-static)
    order = jnp.argsort(dest, stable=True)         # rows grouped by dest
    rank_in_sorted = jnp.zeros((cap,), jnp.int32)
    sorted_dest = dest[order]
    is_new_group = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), sorted_dest[1:] != sorted_dest[:-1]]
    )
    group_start = jax.lax.associative_scan(
        jnp.maximum,
        jnp.where(is_new_group, jnp.arange(cap, dtype=jnp.int32), 0),
    )
    rank_sorted = jnp.arange(cap, dtype=jnp.int32) - group_start
    rank_in_sorted = rank_in_sorted.at[order].set(rank_sorted)

    dest_slot = jnp.where(
        dest < n_shards, dest * cap + rank_in_sorted,
        jnp.int32(n_shards * cap),
    )

    cols = tuple(
        _bucketize(c, dest_slot, n_shards, cap) for c in chunk.columns
    )
    ops = _bucketize(chunk.ops, dest_slot, n_shards, cap)
    valid = jnp.zeros((n_shards * cap,), jnp.bool_).at[dest_slot].set(
        chunk.valid, mode="drop"
    )

    # swap bucket i of shard j to shard i (bucket-major leading axis)
    def a2a(x):
        r = x.reshape((n_shards, cap) + x.shape[1:])
        r = jax.lax.all_to_all(
            r, axis_name, split_axis=0, concat_axis=0, tiled=False
        )
        return r.reshape((n_shards * cap,) + x.shape[1:])

    def a2a_col(c):
        if isinstance(c, NCol):
            return NCol(a2a_col(c.data), a2a(c.null))
        if isinstance(c, StrCol):
            return StrCol(a2a(c.data), a2a(c.lens))
        return a2a(c)

    cols = tuple(a2a_col(c) for c in cols)
    ops = a2a(ops)
    valid = a2a(valid)
    EXCHANGE_TRACE["calls"] += 1
    EXCHANGE_TRACE["bytes"] += sum(
        _trace_bytes(x)
        for x in jax.tree.leaves((cols, ops, valid))
    )
    return Chunk(cols, ops, valid, chunk.schema)
