"""Vectorized hashing: vnode assignment and hash-table key hashing.

Reference counterparts:
- ``VirtualNode::compute_chunk`` — src/common/src/hash/consistent_hash/vnode.rs:151
  (vnode = crc32(dist-key bytes) % vnode_count, vectorized over a chunk)
- ``HashKey`` vectorized build  — src/common/src/hash/key_v2.rs:221
- crc32 hasher                  — src/common/src/util/hash_util.rs:25

TPU-first design
----------------
The crc32 inner loop is a table lookup per byte.  On device this is a
``[256]`` u32 gather per byte position, unrolled over the (static) key
byte width — entirely vectorized over the chunk's row dimension, so a
whole chunk's vnodes are computed in one fused XLA program (the
reference's `compute_chunk` is the same idea on CPU SIMD).

For open-addressing state tables we also provide a 64-bit mix hash
(`hash64_columns`) — cheaper than crc for wide probes and with better
avalanche for slot distribution.
"""

from __future__ import annotations

from functools import lru_cache
from typing import Sequence

import jax.numpy as jnp
import numpy as np

from risingwave_tpu.common.chunk import NCol, StrCol

def normalize_null_col(col) -> list:
    """Flatten a possibly-nullable column into hashable plain columns.

    An ``NCol`` becomes [payload-with-nulls-zeroed, null-flag]: equal
    values (including NULL==NULL, the *grouping* equality the reference
    uses for GROUP BY/DISTINCT keys) produce equal words, regardless of
    whatever garbage the payload held at null rows."""
    if not isinstance(col, NCol):
        return [col]
    data, null = col.data, col.null
    if isinstance(data, StrCol):
        zeroed = StrCol(
            jnp.where(null[:, None], jnp.uint8(0), data.data),
            jnp.where(null, 0, data.lens),
        )
    else:
        zeroed = jnp.where(null, jnp.zeros((), data.dtype), data)
    return [zeroed, null]

#: Default number of virtual nodes (ref vnode.rs:62 COUNT_FOR_COMPAT).
VNODE_COUNT = 256


@lru_cache(maxsize=1)
def _crc32_table() -> np.ndarray:
    poly = np.uint32(0xEDB88320)
    table = np.zeros(256, np.uint32)
    for i in range(256):
        c = np.uint32(i)
        for _ in range(8):
            c = np.where(c & 1, poly ^ (c >> np.uint32(1)), c >> np.uint32(1))
        table[i] = c
    return table


def _crc_step(state: jnp.ndarray, byte: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    idx = (state ^ byte.astype(jnp.uint32)) & jnp.uint32(0xFF)
    return (state >> jnp.uint32(8)) ^ table[idx]


def _key_words(col) -> list[jnp.ndarray]:
    """Decompose one fixed-width key column into unsigned integer words.

    SQL-equal values must produce equal words: floats are canonicalized
    (-0.0 → +0.0, all NaNs → one NaN) before bit extraction.  float64 is
    split double-double style into two float32 words because the TPU x64
    rewrite does not implement 64-bit bitcasts from floats.
    """
    if col.dtype == jnp.bool_:
        col = col.astype(jnp.int64)
    if jnp.issubdtype(col.dtype, jnp.floating):
        zero = jnp.zeros((), col.dtype)
        col = jnp.where(col == 0, zero, col)           # -0.0 == 0.0 in SQL
        col = jnp.where(jnp.isnan(col), jnp.full((), jnp.nan, col.dtype), col)
        if col.dtype == jnp.float64:
            hi = col.astype(jnp.float32)
            lo = (col - hi.astype(jnp.float64)).astype(jnp.float32)
            return [hi.view(jnp.uint32), lo.view(jnp.uint32)]
        return [col.view(jnp.uint32)]
    return [col.view(_unsigned_view(col.dtype))]


def _str_words(col: StrCol) -> list[jnp.ndarray]:
    """A string column as ``uint64`` words: its bytes, eight to a word,
    then its length.  Bytes at and after a row's length count as zero, so
    a slot reused with stale padding can never split equal strings."""
    cap, width = col.data.shape
    words = width // 8 + (1 if width % 8 else 0)
    padded = jnp.pad(col.data, ((0, 0), (0, words * 8 - width)))
    byte_idx = jnp.arange(words * 8, dtype=jnp.int32)
    masked = jnp.where(byte_idx[None, :] < col.lens[:, None], padded, 0)
    w64 = masked.reshape(cap, words, 8).astype(jnp.uint64)
    shifts = (np.arange(8, dtype=np.uint64) * 8)
    folded = jnp.sum(w64 << shifts[None, None, :], axis=-1, dtype=jnp.uint64)
    return [folded[:, k] for k in range(words)] + [col.lens.astype(jnp.uint64)]


def key_words(columns: Sequence) -> list[jnp.ndarray]:
    """What ``hash64_columns`` folds, unhashed: ``[cap]`` unsigned words
    that are equal for two rows exactly when their key columns are equal
    as the hash sees them (a string by its bytes and length, a float
    canonicalized, a NULL by its flag): what tells apart two keys that
    share a hash."""
    out: list[jnp.ndarray] = []
    for raw in columns:
        for col in normalize_null_col(raw):
            out.extend(_str_words(col) if isinstance(col, StrCol)
                       else _key_words(col))
    return out


def crc32_columns(columns: Sequence, init: int = 0xFFFFFFFF) -> jnp.ndarray:
    """crc32 over the little-endian bytes of each row's key columns.

    ``columns`` are ``[cap]`` integer arrays and/or ``StrCol``s; returns
    ``uint32 [cap]``.  String padding bytes beyond ``lens`` are skipped so
    equal strings hash equally regardless of column width.
    """
    table = jnp.asarray(_crc32_table())
    state = None
    for col in columns:
        if isinstance(col, StrCol):
            cap, width = col.data.shape
            if state is None:
                state = jnp.full((cap,), init, jnp.uint32)
            for k in range(width):
                b = col.data[:, k]
                stepped = _crc_step(state, b, table)
                state = jnp.where(k < col.lens, stepped, state)
        else:
            for u in _key_words(col):
                nbytes = np.dtype(u.dtype).itemsize
                if state is None:
                    state = jnp.full(u.shape, init, jnp.uint32)
                for k in range(nbytes):
                    b = ((u >> (8 * k)) & 0xFF).astype(jnp.uint32)
                    state = _crc_step(state, b, table)
    if state is None:
        raise ValueError("no key columns")
    return ~state  # final xor, standard crc32


def _unsigned_view(dtype) -> jnp.dtype:
    return {
        jnp.dtype(jnp.int16): jnp.uint16,
        jnp.dtype(jnp.int32): jnp.uint32,
        jnp.dtype(jnp.int64): jnp.uint64,
        jnp.dtype(jnp.uint8): jnp.uint8,
        jnp.dtype(jnp.uint16): jnp.uint16,
        jnp.dtype(jnp.uint32): jnp.uint32,
        jnp.dtype(jnp.uint64): jnp.uint64,
    }[jnp.dtype(dtype)]


def compute_vnodes(
    key_columns: Sequence, vnode_count: int = VNODE_COUNT
) -> jnp.ndarray:
    """Vectorized vnode assignment for a chunk (ref vnode.rs:151).

    vnode = crc32(dist key) % vnode_count, returned as ``int32 [cap]``.

    Nullable (``NCol``) keys route by grouping equality: NULLs hash as
    a zeroed payload + null flag, so all NULL keys land on one vnode —
    exactly the reference's NULL-is-one-group GROUP BY routing.
    """
    flat: list = []
    for c in key_columns:
        flat.extend(normalize_null_col(c))
    h = crc32_columns(flat)
    return (h % jnp.uint32(vnode_count)).astype(jnp.int32)


_MIX_K1 = np.uint64(0x9E3779B97F4A7C15)  # golden-ratio multiplier
_MIX_K2 = np.uint64(0xBF58476D1CE4E5B9)  # splitmix64 constants
_MIX_K3 = np.uint64(0x94D049BB133111EB)


def _mix64(x: jnp.ndarray) -> jnp.ndarray:
    x = (x ^ (x >> np.uint64(30))) * _MIX_K2
    x = (x ^ (x >> np.uint64(27))) * _MIX_K3
    return x ^ (x >> np.uint64(31))


def hash64_i64_host(vals) -> np.ndarray:
    """Pure-numpy ``hash64_columns([int64 col])`` — bit-identical to
    the device path for a single NOT NULL int64 column (asserted by
    tests/test_exchange.py).  The Exchange-lite host paths (ingest
    leader batch slicing, reader-side vnode filters) hash thousands of
    tiny batches; eager jnp dispatch per batch costs more than the
    hash itself, so the host plane runs this numpy twin instead."""
    with np.errstate(over="ignore"):
        u = np.asarray(vals, np.int64).view(np.uint64)
        state = np.full(u.shape, _MIX_K1, np.uint64)  # seed 0 ^ K1
        x = state ^ (u * _MIX_K1)
        x = (x ^ (x >> np.uint64(30))) * _MIX_K2
        x = (x ^ (x >> np.uint64(27))) * _MIX_K3
        x = x ^ (x >> np.uint64(31))
    return np.where(x == ~np.uint64(0), ~np.uint64(1), x)


def hash64_columns(columns: Sequence, seed: int = 0) -> jnp.ndarray:
    """64-bit mix hash of key columns, ``uint64 [cap]``.

    Used for open-addressing state-table slot selection (the analog of
    the reference's ``HashKey`` + hasher in hash_join/hash_agg).

    The all-ones value is never returned (remapped to ~1): callers use
    ~0 as an "invalid row" sort sentinel, and the remap here keeps that
    convention consistent between chunk pre-aggregation sorts and the
    hash table's own probe hashing.
    """
    state = None
    for raw in columns:
        for col in normalize_null_col(raw):
            state = _hash64_one(col, state, seed)
    if state is None:
        raise ValueError("no key columns")
    return jnp.where(state == ~np.uint64(0), ~np.uint64(1), state)


def hash64_partial(columns: Sequence, seed: int = 0) -> jnp.ndarray:
    """Unfinalized mix state after folding ``columns``.

    Split-hash support for probes that re-derive a compound hash per
    iteration (the fused (hash, rank) join probe): fold the expensive
    prefix once, then ``hash64_extend`` the varying suffix column and
    ``hash64_finish`` per probe round.  The composition
    ``hash64_finish(hash64_extend(hash64_partial([a]), b))`` is EXACTLY
    ``hash64_columns([a, b])`` — entries placed by one are found by the
    other."""
    state = None
    for raw in columns:
        for col in normalize_null_col(raw):
            state = _hash64_one(col, state, seed)
    if state is None:
        raise ValueError("no key columns")
    return state


def hash64_extend(state: jnp.ndarray, col) -> jnp.ndarray:
    """Fold one more column into a ``hash64_partial`` state."""
    out = state
    for c in normalize_null_col(col):
        out = _hash64_one(c, out, 0)
    return out


def hash64_finish(state: jnp.ndarray) -> jnp.ndarray:
    """Finalize a partial state (the sentinel remap of hash64_columns)."""
    return jnp.where(state == ~np.uint64(0), ~np.uint64(1), state)


def _hash64_one(col, state, seed):
    if isinstance(col, StrCol):
        cap = col.data.shape[0]
        if state is None:
            state = jnp.full((cap,), np.uint64(seed) ^ _MIX_K1, jnp.uint64)
        *folded, lens = _str_words(col)
        for w in folded:
            state = _mix64(state ^ w * _MIX_K1)
        state = _mix64(state ^ lens)
    else:
        for w in _key_words(col):
            u = w.astype(jnp.uint64)
            if state is None:
                state = jnp.full(u.shape, np.uint64(seed) ^ _MIX_K1, jnp.uint64)
            state = _mix64(state ^ u * _MIX_K1)
    return state
