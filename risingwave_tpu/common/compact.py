"""Mask compaction primitives, backend-adaptive.

The same logical op has opposite cost profiles per backend:

- ``jnp.nonzero(mask, size=k)`` lowers to a cumsum + a scatter as wide
  as the mask.  The chip pays for a scatter by the index (a fixed time
  for each, live or dropped: 0.54 ms for 8,192 into a 2^20-slot array,
  PERF.md §5), so this is the shape to keep off it; the CPU does it
  cheaply.
- ``lax.top_k`` is a sort-class primitive, and the sorts of a chunk
  cost the chip less than a tenth of one such scatter (PERF.md §5); on
  the CPU it lowers to a full variadic sort per call and is the slow
  one.

Round 2 switched everything to top_k and silently made the CPU path
several times slower (the round-2 q7 "4x regression"); the strategy is
now selected once per process from ``jax.default_backend()`` — a
trace-time Python branch, so each backend compiles only its fast op.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.cache
def accel_tuned() -> bool:
    """True when compiling for an accelerator (TPU tunings apply)."""
    return jax.default_backend() != "cpu"


def mask_indices(mask: jnp.ndarray, k: int, fill) -> jnp.ndarray:
    """Indices of up to ``k`` set bits of ``mask`` (ascending), ``fill``
    for the rest.

    TPU: ``lax.top_k`` (tie-break = ascending index, a drop-in for
    nonzero's order).  CPU: ``jnp.nonzero`` (top_k is the slow one
    there)."""
    if accel_tuned():
        vals, idx = jax.lax.top_k(mask.astype(jnp.int32), k)
        return jnp.where(vals > 0, idx, jnp.asarray(fill, idx.dtype))
    (idx,) = jnp.nonzero(mask, size=k, fill_value=fill)
    return idx.astype(jnp.int32)


def segment_starts(sorted_neq: jnp.ndarray) -> jnp.ndarray:
    """[n-1] adjacent-inequality -> [n] is-segment-start mask."""
    return jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), sorted_neq]
    )


def segment_start_positions(starts: jnp.ndarray) -> jnp.ndarray:
    """Running index of each row's segment start (int32 [n]).

    One ``cummax`` — the building block for the cheap segmented
    reductions below.  (``associative_scan`` would unroll to ~8 ops per
    level × log2(n) levels; at TPU's per-op launch floor that costs
    milliseconds, while cumsum/cummax lower to single reduce-window
    ops.)"""
    idx = jnp.arange(starts.shape[0], dtype=jnp.int32)
    return jax.lax.cummax(jnp.where(starts, idx, 0))


def _cumsum_int64(values: jnp.ndarray) -> jnp.ndarray:
    """Inclusive cumsum of 64-bit integers built from 32-bit scans.

    A 64-bit ``reduce-window`` inside a loop body is refused by the
    TPU compiler (libtpu 0.0.34: scoped vmem exhausted for lengths
    2^14..2^16, e.g. q5's 40,960-row pane chunks in the barrier drain
    loop).  The two's-complement sum is exact modulo 2^64 from limb-wise
    uint32 scans, the limbs narrow enough that ``n`` of them cannot
    overflow 32 bits."""
    n = values.shape[0]
    bits = 32 - max(n - 1, 1).bit_length()
    u = values.astype(jnp.uint64)
    mask = np.uint64((1 << bits) - 1)
    acc = jnp.zeros_like(u)
    for shift in range(0, 64, bits):
        limb = ((u >> np.uint64(shift)) & mask).astype(jnp.uint32)
        acc = acc + (
            jnp.cumsum(limb, dtype=jnp.uint32).astype(jnp.uint64)
            << np.uint64(shift)
        )
    return acc.astype(values.dtype)


def segmented_sum(values: jnp.ndarray, start_pos: jnp.ndarray) -> jnp.ndarray:
    """Inclusive segmented running sum; the value at each segment's END
    is the segment total.  cumsum + gather-of-prefix."""
    if values.dtype in (jnp.int64, jnp.uint64):
        c = _cumsum_int64(values)
    else:
        c = jnp.cumsum(values, axis=0, dtype=values.dtype)
    prev = jnp.maximum(start_pos - 1, 0)
    base = jnp.where(start_pos > 0, c[prev], jnp.zeros((), values.dtype))
    return c - base


def segmented_minmax_at_ends(seg_id: jnp.ndarray, values: jnp.ndarray,
                             start_pos: jnp.ndarray, mode: str):
    """Per-segment min or max of ``values``, available at every row of
    the segment (in particular its END, where the representative row
    lives).

    One secondary sort by (segment id, value): the segment's min lands
    on its start row and its max on its end row.  ``mode`` selects
    which to return ("min" | "max")."""
    _, sorted_v = jax.lax.sort((seg_id, values), num_keys=2)
    if mode == "min":
        return sorted_v[start_pos]    # value at segment start = min
    if mode == "max":
        return sorted_v               # value at own row; at END = max
    raise ValueError(mode)
