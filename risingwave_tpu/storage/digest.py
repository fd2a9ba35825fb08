"""Shared block-digest scheme for incremental state snapshots.

One digest definition, two consumers:

- the device-side shadow snapshot (stream/shadow.py) diffs live state
  against the shadow copy and scatters only the dirty block runs;
- the durable checkpoint store (storage/checkpoint_store.py) diffs an
  epoch against the last persisted digests and uploads only the dirty
  runs as a delta file.

Because both sides hash the SAME flat element stream with the SAME
block size, the digest vector computed once per snapshot (on the
barrier path, as part of the shadow-update program) can be handed to
the durable store verbatim — the store never re-reads the full state.

The digest of one block is a position-mixed splitmix sum: every element
is xored with its golden-ratio-scaled flat index before mixing, so
swapped or shifted values cannot cancel, and the per-block sum keeps
the reduction associative (XLA fuses the elementwise mix straight into
the block reduction — no materialized temp).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from risingwave_tpu.common.hash import _MIX_K1 as _GOLD, _mix64

#: default block size in ELEMENTS (not bytes) — matches the checkpoint
#: store's historical default so shadow digests and store digests agree
DEFAULT_BLOCK_ELEMS = 1 << 9


def normalize_u64(x):
    """Change-faithful view of any leaf as flat uint64 (1:1 elements).

    float64 avoids 64-bit float bitcasts (unimplemented by the TPU x64
    rewrite — see common/hash._key_words): frexp decomposes exactly
    into a 53-bit integer mantissa + exponent, with inf/nan pinned to
    sentinels so value flips never alias zero."""
    if x.dtype == jnp.bool_:
        v = x.astype(jnp.uint64)
    elif x.dtype == jnp.float64:
        m, e = jnp.frexp(x)
        m2 = (m * (2.0 ** 53)).astype(jnp.int64)
        m2 = jnp.where(jnp.isnan(x), jnp.int64(-(2 ** 62)), m2)
        m2 = jnp.where(jnp.isposinf(x), jnp.int64(2 ** 62), m2)
        m2 = jnp.where(jnp.isneginf(x), jnp.int64(-(2 ** 62) + 1), m2)
        v = m2.astype(jnp.uint64) ^ (e.astype(jnp.uint64)
                                     << np.uint64(53))
    elif x.dtype == jnp.float32:
        v = jax.lax.bitcast_convert_type(x, jnp.uint32).astype(jnp.uint64)
    elif x.dtype.itemsize == 8:
        v = jax.lax.bitcast_convert_type(x, jnp.uint64)
    else:
        u = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
        v = jax.lax.bitcast_convert_type(x, u).astype(jnp.uint64)
    return v.reshape(-1)


def leaf_block_count(shape, block: int) -> int:
    n = int(np.prod(shape)) if shape else 1
    return max(1, -(-n // block))


def _pack_words(x, nb: int, block: int) -> jnp.ndarray | None:
    """Narrow dtypes packed 8-bytes-per-u64 word, ``[nb * block/k]``.

    The splitmix mix is a scalar 64-bit multiply chain on this CPU ISA
    (no AVX2 vpmullq) — mixing per BYTE makes string columns ~8x more
    expensive per stored byte than int64 columns.  Packing k narrow
    lanes into one word before mixing restores byte-rate parity.
    Returns None for dtypes that already occupy a full word (the
    caller mixes elements directly)."""
    if x.dtype == jnp.bool_:
        u, bits = x.astype(jnp.uint8), 8
    elif x.dtype == jnp.float32:
        u, bits = jax.lax.bitcast_convert_type(x, jnp.uint32), 32
    elif x.dtype.itemsize == 8:
        return None
    else:
        t = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
        u, bits = jax.lax.bitcast_convert_type(x, t), 8 * x.dtype.itemsize
    k = 64 // bits
    flat = u.reshape(-1)
    pad = nb * block - flat.shape[0]
    if pad:  # trace-time: aligned leaves never materialize a pad copy
        flat = jnp.pad(flat, (0, pad))
    # word j of a block packs element j of each of its k contiguous
    # ``block/k``-element runs.  The minor axis stays ``block/k`` wide:
    # a ``[n/k, k]`` view pads k up to 128 lanes in the TPU's tiled
    # layout (16x for bytes — q8's 2^23-row string ring asked for 12 GiB)
    runs = flat.reshape(nb, k, block // k).astype(jnp.uint64)
    words = runs[:, 0]
    for j in range(1, k):
        words = words | (runs[:, j] << np.uint64(j * bits))
    return words.reshape(-1)


def leaf_digest(x, nb: int, block: int) -> jnp.ndarray:
    """Per-block digests of one leaf, ``uint64 [nb]`` (traceable).

    ``block`` counts ELEMENTS; narrow dtypes are packed into u64 words
    first (block must keep whole words per block — any power of two
    ≥ 8 does)."""
    x = jnp.asarray(x)
    words = _pack_words(x, nb, block)
    if words is None:
        words = normalize_u64(x)
        pad = nb * block - words.shape[0]
        if pad:
            words = jnp.pad(words, (0, pad))
    wpb = words.shape[0] // nb
    idx = jnp.arange(words.shape[0], dtype=jnp.uint64)
    h = _mix64(words ^ (idx * _GOLD) ^ _GOLD)
    return jnp.sum(h.reshape(nb, wpb), axis=1)


def digest_leaves(leaves, nblocks, block: int) -> jnp.ndarray:
    """Concatenated per-block digests of a leaf list (traceable)."""
    return jnp.concatenate([
        leaf_digest(x, nb, block) for x, nb in zip(leaves, nblocks)
    ])


def lane_block_count(shape, rows: int, block: int) -> int:
    """Block count of a leaf digested as ``rows`` independent lanes
    (``rows * ceil(row_elems / block)``)."""
    n = int(np.prod(shape)) if shape else 1
    m = n // rows
    return rows * max(1, -(-m // block))


def leaf_digest_lanes(x, rows: int, block: int) -> jnp.ndarray:
    """Per-block digests of one leaf in ``rows`` lanes, ``uint64
    [lane_block_count]`` (traceable).

    A mesh-stacked leaf (``[n_shards, ...]``) digested flat would let
    blocks straddle shard rows: two shards writing different halves of
    one straddling block keep it eternally dirty, and the delta
    extraction cannot attribute it to either shard.  Lanes restart the
    block grid at every row — no digest block spans a lane boundary,
    so the dirty mask (and the dirty-run upload) is exact per shard.
    Position mixing is row-local, which is fine: a digest is only ever
    compared against the SAME block's previous digest."""
    x = jnp.asarray(x).reshape(rows, -1)
    nb_row = max(1, -(-x.shape[1] // block))
    return jax.vmap(
        lambda r: leaf_digest(r, nb_row, block)
    )(x).reshape(-1)
