"""Epoch-versioned checkpoint persistence + manifest (incremental).

Reference counterpart: the Hummock commit path — shared-buffer upload on
checkpoint (uploader/mod.rs:1478), ``commit_epoch`` version bump
(src/meta/src/hummock/manager/commit_epoch.rs:73), and meta-backed
recovery (SURVEY.md §3.5).  The reference uploads per-epoch DELTAS (the
epoch's dirty key-value batches become SSTs); a full snapshot never
crosses the wire.

TPU-first incremental design
----------------------------
Executor state here is a pytree of dense device arrays, not a KV map —
so the natural delta is *dirty blocks of those arrays*:

1. A jitted digest program hashes every state leaf in fixed-size blocks
   ON DEVICE (storage/digest.py — the SAME scheme the in-memory shadow
   snapshot uses, so on the async path the digest vector is computed
   once per snapshot and handed in; the store never re-reads state).
2. Blocks whose digest changed since the last checkpoint are gathered
   ON DEVICE by one cached program a job (the dirty blocks' starts are
   an argument, a leaf's capacity is static: 1/64 of its blocks) and
   only those blocks cross to the host, all leaves in one transfer;
   adjacent blocks coalesce into runs there and are written as a delta
   file.  A leaf with more dirty blocks than its capacity crosses
   whole, and its runs at most ``RUN_GAP_BYTES`` apart are written as
   one, clean blocks and all: a member costs more than those bytes.
   Device→host traffic and disk bytes scale with the epoch's actual
   write set, not the state size.  A device slice with static
   bounds is never used: it is a program per run, compiled at the
   barrier that first needs it.
3. Every ``full_interval`` checkpoints (or when >50% of blocks are
   dirty) a full snapshot re-bases the chain, bounding restore length
   and letting GC reclaim old chains.

Persistence is split into two phases so a background uploader can
pipeline it (stream/checkpoint.py):

- ``prepare()`` — the device→host fetch: stages the epoch's payload as
  host arrays and decides full-vs-delta.  After it returns, the caller
  may mutate/donate the device buffers.
- ``commit()`` — npz/meta encode, object-store writes, manifest bump,
  GC, digest-cache advance.

The epoch object.  ``commit`` writes two objects under two keys:
``<job>/epoch_<n>.npz``, a ZIP of stored (uncompressed) ``.npy``
members (``leaf_<i>`` for a full, ``r_<leaf>_<first element>`` a dirty
run of a delta) that ``np.load`` reads and ``testzip()`` passes, and
``epoch_<n>.meta``, a pickle.  The manifest records the crc32c of both,
computed over the bytes before the put (``storage/codec.py``: the CPU's
instruction where it has one), and every read verifies them.  The
object is laid out once (``encode_npz``) in a buffer the committing
thread keeps (``_Arena``; ``CheckpointStore._encode`` owns one a
thread) and ``put`` is handed a view of it: one copy of the payload,
one CRC-32 pass (the ZIP's, a member), one crc32c pass, no allocation
that scales with the object after the buffer's first growth.

``save()`` remains the synchronous composition of both.  A manifest
lock serializes commits across jobs (one engine hosts several jobs,
each with its own uploader thread, over ONE manifest file).

Restore = nearest full ≤ target epoch + deltas replayed forward —
exactly the reference's version + version-delta reconstruction.  MV
contents can additionally be exported as SSTs for engine-free serving
(``export_mv_sst``).
"""

from __future__ import annotations

import io
import json
import os
import pickle
import threading
import zipfile
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from risingwave_tpu.common.trace import GLOBAL_TRACE
from risingwave_tpu.storage.codec import crc32c_impl
from risingwave_tpu.storage.digest import (
    DEFAULT_BLOCK_ELEMS,
    digest_leaves,
    leaf_block_count,
    normalize_u64,
)
from risingwave_tpu.storage.integrity import (
    CheckpointCorruption,
    crc32c,
    quarantine,
    record_integrity_error,
)

# back-compat aliases (pre-round-7 internal names)
_normalize_u64 = normalize_u64


#: clean bytes between two dirty runs of a leaf that crossed whole at
#: or below which the runs are written as one member.  A member costs
#: ``encode_npz`` ~40 us on a v5e host, what ~40 KB of bytes cost the
#: encode and the write together; a leaf whose dirty blocks are strewn
#: over it (a string column's 512-byte blocks under 16 k new rows) was
#: ~10 k members of a few blocks each
RUN_GAP_BYTES = 1 << 15


def _leaf_block_count(shape, dtype, block: int) -> int:
    return leaf_block_count(shape, block)


def _dirty_runs(leaf_dirty: np.ndarray, rows: int, m: int, block: int,
                gap: int = 0):
    """Adjacent dirty blocks of one leaf coalesced into runs, as
    ``(first element, end element, blocks)``; runs at most ``gap``
    clean blocks apart are one run, the clean blocks' bytes with it.  A
    lane leaf (``rows`` > 1) is walked a shard row at a time, so no run
    crosses a shard boundary; a run that reaches a row's ragged tail
    ends with it."""
    nb_row = leaf_dirty.shape[0] // rows
    for r in range(rows):
        row_dirty = leaf_dirty[r * nb_row:(r + 1) * nb_row]
        edges = np.flatnonzero(np.diff(np.r_[False, row_dirty, False]))
        starts, ends = edges[::2], edges[1::2]
        if not starts.size:
            continue
        split = starts[1:] - ends[:-1] > gap
        starts = starts[np.r_[True, split]]
        ends = ends[np.r_[split, True]]
        for b, e in zip(starts, ends):
            yield (r * m + int(b) * block,
                   r * m + min(int(e) * block, m), int(e - b))


class _Arena:
    """The seekable file ``zipfile`` writes an epoch object into: one
    byte buffer that is KEPT between objects, so an object's bytes are
    copied once, into memory that is already mapped.  (``np.savez``
    into a fresh ``BytesIO`` copied every array twice more — numpy's
    ``tobytes()`` chunks, ``getvalue()`` — into buffers grown by
    reallocation: 70 MB of page faults a commit.)"""

    def __init__(self):
        self._buf = np.empty(0, np.uint8)
        self._pos = self._end = 0

    def start(self, capacity: int) -> None:
        """Begin a new object; ``capacity`` is a guess at its size (a
        wrong one costs a copy, once)."""
        self._pos = self._end = 0
        self._reserve(capacity)

    def _reserve(self, n: int) -> None:
        if n > self._buf.size:
            grown = np.empty(max(n, self._buf.size * 3 // 2), np.uint8)
            grown[:self._end] = self._buf[:self._end]
            self._buf = grown

    def write(self, data) -> int:
        src = np.frombuffer(data, np.uint8)
        end = self._pos + src.size
        self._reserve(end)
        self._buf[self._pos:end] = src
        self._pos = end
        self._end = max(self._end, end)
        return src.size

    def seek(self, offset: int) -> int:
        """From the start: all ``zipfile`` asks for when it writes."""
        self._pos = offset
        return offset

    def tell(self) -> int:
        return self._pos

    def flush(self) -> None:
        pass

    def view(self) -> memoryview:
        """The object written since ``start``: a view, valid until the
        next ``start`` on this arena."""
        return memoryview(self._buf)[:self._end]


def encode_npz(arena: _Arena, payload: dict[str, np.ndarray]) -> memoryview:
    """``payload`` as ``np.savez`` would write it — a ZIP of stored
    ``<name>.npy`` members, each with its CRC-32, that ``np.load``
    reads and ``testzip()`` passes — laid out in ``arena``: every
    array's bytes are copied once, to their place in the object, and
    read once more by zlib's CRC-32 as the format demands."""
    arena.start(sum(a.nbytes for a in payload.values())
                + sum(2 * len(k) + 512 for k in payload) + 256)
    with zipfile.ZipFile(arena, "w", zipfile.ZIP_STORED,
                         allowZip64=True) as zf:
        for name, a in payload.items():
            if a.dtype.hasobject:  # its bytes are pointers
                raise TypeError(f"{name}: object arrays have no place "
                                "in a checkpoint")
            with zf.open(name + ".npy", "w", force_zip64=True) as member:
                np.lib.format.write_array_header_1_0(member, {
                    "descr": np.lib.format.dtype_to_descr(a.dtype),
                    "fortran_order": False, "shape": a.shape,
                })
                # the one copy (a strided or 0-d array is made
                # contiguous first: one more, of that array alone)
                member.write(
                    np.ascontiguousarray(a).reshape(-1).view(np.uint8))
    return arena.view()


class CheckpointStore:
    """All durable I/O goes through an ``ObjectStore``
    (storage/hummock/object_store.py) — the same seam the SST layer
    uses, so chaos tests can swap an in-memory or fault-injecting
    backend under the whole durability path."""

    _MANIFEST = "MANIFEST.json"

    def __init__(self, root: str, keep_epochs: int = 2,
                 full_interval: int = 16,
                 block_elems: int = DEFAULT_BLOCK_ELEMS,
                 object_store=None, metrics=None):
        from risingwave_tpu.storage.hummock.object_store import (
            LocalFsObjectStore,
        )
        self.root = root
        self.keep_epochs = keep_epochs
        #: integrity counters (integrity_errors_total, repairs)
        self.metrics = metrics
        #: checkpoints between forced fulls (chain-length bound)
        self.full_interval = full_interval
        self.block_elems = block_elems
        self.store = object_store if object_store is not None \
            else LocalFsObjectStore(root)
        #: per-job digest program + last digests (in-memory fast path;
        #: a restarted process re-bases with a full snapshot)
        self._digest_fns: dict[str, Any] = {}
        #: per-job dirty-block gather program (``_gather_fn``)
        self._gather_fns: dict[str, Any] = {}
        self._last_digests: dict[str, tuple[int, np.ndarray]] = {}
        self._since_full: dict[str, int] = {}
        #: serializes manifest read-modify-write + digest-cache updates
        #: across uploader threads (several jobs share one manifest)
        self._lock = threading.RLock()
        #: ``.arena``: the buffer a committing thread lays its epoch
        #: objects out in (``encode_npz``).  One a thread, because one
        #: thread encodes and puts one object at a time and the store
        #: is shared by several jobs' uploaders; it grows to the
        #: largest object that thread has written and goes with the
        #: thread, or with the store
        self._encode = threading.local()

    def _abs(self, key: str) -> str:
        """Filesystem path for a key when the backend is local (the
        legacy return-a-path surfaces, e.g. ``export_mv_sst``)."""
        root = getattr(self.store, "root", None)
        return os.path.join(root, key) if root is not None else key

    def _manifest_txn(self):
        """Cross-PROCESS manifest transaction: ``self._lock`` excludes
        this store's uploader threads; an OS-level flock on the shared
        directory excludes OTHER worker processes (and other store
        instances in one process).  Exchange-lite's parallel barrier
        dispatch lets several workers' uploaders commit different
        lineages concurrently over ONE shared manifest — without this
        the read-modify-write cycles interleave and lose each other's
        epoch records (observed as broken delta chains).  In-memory
        stores (single-process by construction) skip the file lock."""
        import contextlib

        root = getattr(self.store, "root", None)

        @contextlib.contextmanager
        def txn():
            with self._lock:
                if root is None:
                    yield
                    return
                import fcntl

                os.makedirs(root, exist_ok=True)
                with open(os.path.join(root, "MANIFEST.lock"),
                          "a+b") as f:
                    fcntl.flock(f, fcntl.LOCK_EX)
                    try:
                        yield
                    finally:
                        fcntl.flock(f, fcntl.LOCK_UN)

        return txn()

    # -- manifest -------------------------------------------------------
    def _load_manifest(self) -> dict:
        if not self.store.exists(self._MANIFEST):
            return {"jobs": {}}
        return json.loads(self.store.get(self._MANIFEST))

    def _store_manifest(self, m: dict) -> None:
        self.store.put(self._MANIFEST, json.dumps(m, indent=1).encode())

    # -- digests --------------------------------------------------------
    def _digest_fn(self, job_name: str, leaves):
        """Cached jitted digest program, keyed by the state SHAPE: a
        dropped-and-recreated job with a different plan (different leaf
        list) must rebuild — and its first save re-bases with a full
        (stale digests are discarded with the program)."""
        sig = tuple((str(np.asarray(x).dtype) if not hasattr(x, "dtype")
                     else str(x.dtype), np.shape(x)) for x in leaves)
        with self._lock:
            cached = self._digest_fns.get(job_name)
            if cached is not None and cached[2] == sig:
                return cached[0], cached[1]
            if cached is not None:
                self._last_digests.pop(job_name, None)
                self._since_full.pop(job_name, None)
                self._gather_fns.pop(job_name, None)
            block = self.block_elems
            nblocks = [
                leaf_block_count(np.shape(x), block) for x in leaves
            ]

            def digest(leaves):
                return digest_leaves(
                    [jnp.asarray(x) for x in leaves], nblocks, block
                )

            self._digest_fns[job_name] = (jax.jit(digest), nblocks, sig)
            return self._digest_fns[job_name][0], nblocks

    # -- the delta fetch ------------------------------------------------
    def _gather_fn(self, job_name: str, leaves, nblocks):
        """Cached jitted gather of dirty blocks, one per job-state
        signature (as ``_digest_fn``, and dropped with it).  Returns
        ``(fn, caps)``: ``caps[i]`` is how many blocks of leaf ``i`` one
        call brings back — ``shadow._copy_leaf``'s first rung, 1/64 of
        the leaf's blocks — and 0 for a leaf that never takes part (a
        host array, or fewer elements than one block).  ``fn(leaves,
        starts)`` takes the leaves that take part and, for each, a
        ``caps[i]``-long int32 vector of window starts; it returns a
        ``(caps[i], block)`` array a leaf.  The starts are an ARGUMENT:
        a static slice bound is a program per dirty set, which on the
        chip cost q8 18 s a barrier in compiles and round trips (PR
        22); this one is compiled once, at the job's first delta."""
        block = self.block_elems
        sig = tuple(
            (str(x.dtype), np.shape(x), nb)
            if isinstance(x, jax.Array) else None
            for x, nb in zip(leaves, nblocks)
        )
        with self._lock:
            cached = self._gather_fns.get(job_name)
            if cached is not None and cached[2] == sig:
                return cached[0], cached[1]
            caps = [
                max(1, nb // 64)
                if s is not None and block <= int(np.prod(s[1])) < 2 ** 31
                else 0
                for s, nb in zip(sig, nblocks)
            ]
            dnums = jax.lax.GatherDimensionNumbers(
                offset_dims=(1,), collapsed_slice_dims=(),
                start_index_map=(0,),
            )

            def gather(leaves, starts):
                return tuple(
                    jax.lax.gather(
                        x.reshape(-1), s[:, None], dnums,
                        slice_sizes=(block,),
                        mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS,
                    )
                    for x, s in zip(leaves, starts)
                )

            fn = jax.jit(gather) if any(caps) else None
            self._gather_fns[job_name] = (fn, caps, sig)
            return fn, caps

    def _fetch_delta(self, job_name: str, leaves, nblocks, lanes, dirty,
                     payload: dict, dispatched) -> tuple[dict, dict]:
        """Fill ``payload`` with the dirty runs of a delta checkpoint,
        keyed ``r_<leaf>_<first element>`` in leaf then element order.
        One algorithm, three sources a leaf: a host array is cut where
        it is; a device leaf with at most its capacity of dirty blocks
        has those gathered on the device; any other dirty device leaf
        crosses whole.  Everything that crosses does so in ONE
        ``device_get``, after ``dispatched()``.  Returns the bytes that
        crossed, by path, and the fetch span's ``blocks`` /
        ``whole_leaves``."""
        block = self.block_elems
        fn, caps = self._gather_fn(job_name, leaves, nblocks)
        starts = [np.zeros(c, np.int32) for c in caps]
        runs: dict[int, list] = {}     # every leaf with a dirty block
        windows: dict[int, tuple] = {}  # gathered leaf -> where each
        #                                 block lies in its window
        whole: list[int] = []          # device leaves that cross whole
        off = 0
        for i, (x, nb, ln) in enumerate(zip(leaves, nblocks, lanes)):
            leaf_dirty = dirty[off:off + nb]
            off += nb
            ids = np.flatnonzero(leaf_dirty)
            if not ids.size:
                continue
            n = int(np.prod(np.shape(x)))
            rows, m = ln if ln else (1, n)
            gathered_leaf = ids.size <= caps[i]
            runs[i] = list(_dirty_runs(
                leaf_dirty, rows, m, block,
                0 if gathered_leaf else RUN_GAP_BYTES // (
                    block * np.dtype(x.dtype).itemsize)))
            if gathered_leaf:
                # lane leaves restart their blocks at every shard row;
                # a window that would run past the leaf's end (its
                # ragged tail) starts earlier instead
                b = ids % (nb // rows)
                first = (ids // (nb // rows)) * m + b * block
                start = np.minimum(first, n - block)
                starts[i][:ids.size] = start
                windows[i] = (first - start,
                              np.minimum(block, m - b * block))
            elif isinstance(x, jax.Array):
                whole.append(i)
        gathered: dict[int, Any] = {}
        if fn is not None:
            # dispatched at every delta, so the program is compiled at
            # a job's first and never at a later one's first need
            part = [i for i, c in enumerate(caps) if c]
            gathered = dict(zip(part, fn(
                tuple(leaves[i] for i in part),
                tuple(starts[i] for i in part),
            )))
        dispatched()
        fetched = jax.device_get(
            [gathered[i] for i in windows] + [leaves[i] for i in whole]
        )
        blocks = dict(zip(windows, fetched))
        flats = dict(zip(whole, fetched[len(windows):]))
        for i, leaf_runs in runs.items():
            if i in blocks:
                got, (offs, lens), k = blocks[i], windows[i], 0
                for s_el, _, nblk in leaf_runs:
                    # only a run's last block can be short, or start
                    # late in its window
                    last = k + nblk - 1
                    payload[f"r_{i}_{s_el}"] = np.concatenate([
                        got[k:last].reshape(-1),
                        got[last, offs[last]:offs[last] + lens[last]],
                    ])
                    k += nblk
            else:
                flat = np.asarray(flats.get(i, leaves[i])).reshape(-1)
                for s_el, e_el, _ in leaf_runs:
                    payload[f"r_{i}_{s_el}"] = flat[s_el:e_el].copy()
        return ({"gathered": sum(h.nbytes for h in blocks.values()),
                 "whole": sum(h.nbytes for h in flats.values())},
                {"blocks": sum(len(w[0]) for w in windows.values()),
                 "whole_leaves": len(whole)})

    # -- checkpoint save: prepare (fetch) / commit (write) --------------
    def prepare(self, job_name: str, epoch: int, leaves, shapes,
                treedef, source_state: dict, digests=None,
                lanes=None, dispatched=lambda: None) -> dict:
        """Stage one epoch's payload on the host.

        ``leaves`` may be device arrays of any shape (they are read as
        flat element streams); ``digests`` (uint64 vector from the
        shadow snapshot's update program) skips the digest pass.
        ``lanes`` (per-leaf ``(rows, row_elems)`` or None, from a
        per-shard shadow) describes the digest's block grid: lane
        leaves restart their blocks at every row, so the dirty-run
        extraction below walks rows and never emits a run crossing a
        shard boundary.  After this returns, the caller may freely
        mutate or donate the device buffers — everything needed by
        ``commit`` is host-resident.  ``dispatched`` is called once
        every device program of the fetch is queued, before the host
        waits on the transfer."""
        from risingwave_tpu.storage.digest import lane_block_count

        block = self.block_elems
        if lanes is None:
            lanes = [None] * len(shapes)
        nblocks = [
            lane_block_count(s, ln[0], block) if ln
            else leaf_block_count(s, block)
            for s, ln in zip(shapes, lanes)
        ]
        if digests is None:
            # the store-side digest pass is flat-only; a lane grid is
            # meaningful only for shadow-computed digest vectors
            lanes = [None] * len(shapes)
            digest_jit, nblocks = self._digest_fn(job_name, leaves)
            digests = np.asarray(digest_jit(leaves))
        else:
            digests = np.asarray(digests).astype(np.uint64, copy=False)

        with self._lock:
            prev = self._last_digests.get(job_name)
            since_full = self._since_full.get(job_name, 0)
            # a re-save of an epoch already in the manifest
            # (post-rescale re-base, re-seal after a crashed commit)
            # must be FULL: a delta would overwrite a chain entry with
            # a wrong-base delta
            resave = epoch in self._load_manifest()["jobs"].get(
                job_name, {}).get("epochs", [])

        dirty = None
        if prev is not None and prev[1].shape == digests.shape:
            dirty = digests != prev[1]
        kind = "delta"
        if (dirty is None or since_full >= self.full_interval - 1
                or int(dirty.sum()) * 2 > digests.shape[0] or resave):
            kind = "full"

        payload: dict[str, np.ndarray] = {}
        # the device→host transfer (and the cut into runs), apart from
        # the diff above
        with GLOBAL_TRACE.span("ckpt_prepare.fetch", job=job_name,
                               kind=kind) as span:
            if kind == "full":
                flat = [jnp.asarray(x).reshape(-1) for x in leaves]
                dispatched()
                host = jax.device_get(flat)
                for i, (h, s) in enumerate(zip(host, shapes)):
                    payload[f"leaf_{i}"] = np.asarray(h).reshape(s)
                moved = {"whole": sum(
                    h.nbytes for x, h in zip(leaves, host)
                    if isinstance(x, jax.Array))}
                counts = {"blocks": 0, "whole_leaves": len(host)}
            else:
                moved, counts = self._fetch_delta(
                    job_name, leaves, nblocks, lanes, dirty, payload,
                    dispatched,
                )
            span.set(bytes=sum(moved.values()), **counts)
        if self.metrics is not None:
            for path, nbytes in moved.items():
                if nbytes:
                    self.metrics.inc("checkpoint_fetch_bytes_total",
                                     nbytes, job=job_name, path=path)
        return {
            "job": job_name, "epoch": epoch, "kind": kind,
            "payload": payload, "treedef": treedef,
            "source_state": source_state, "digests": digests,
        }

    def commit(self, prep: dict) -> None:
        """Write a prepared epoch: objects, manifest bump, GC, digest
        cache — the durable commit point the uploader acks."""
        job_name, epoch, kind = prep["job"], prep["epoch"], prep["kind"]
        key = f"{job_name}/epoch_{epoch}"
        with GLOBAL_TRACE.span("ckpt_commit.encode",
                               job=job_name) as span:
            arena = getattr(self._encode, "arena", None)
            if arena is None:
                arena = self._encode.arena = _Arena()
            # a view of this thread's arena: good until this thread's
            # next commit, and the puts below are done by then
            npz_bytes = encode_npz(arena, prep["payload"])
            meta_bytes = pickle.dumps({
                "treedef": prep["treedef"],
                "source_state": prep["source_state"],
                "epoch": epoch, "kind": kind,
            })
            # crc32c trailer per epoch object, recorded in the
            # manifest (computed over the bytes BEFORE the put, so a
            # put corrupted in flight — or on disk later — mismatches
            # on read and the typed CheckpointCorruption fires)
            crc = {"npz": crc32c(npz_bytes), "meta": crc32c(meta_bytes)}
            span.set(bytes=len(npz_bytes) + len(meta_bytes),
                     impl=crc32c_impl())
        with self._manifest_txn():
            with GLOBAL_TRACE.span("ckpt_commit.put", job=job_name,
                                   bytes=len(npz_bytes) + len(meta_bytes)):
                self.store.put(key + ".npz", npz_bytes)
                self.store.put(key + ".meta", meta_bytes)
            with GLOBAL_TRACE.span("ckpt_commit.manifest", job=job_name):
                self._commit_manifest(job_name, epoch, kind, crc,
                                      prep["digests"])

    def _commit_manifest(self, job_name: str, epoch: int, kind: str,
                         crc: dict, digests) -> None:
        """The manifest read-modify-write of one commit (inside the
        manifest transaction): record the epoch, GC beyond
        ``keep_epochs``, store, advance the digest cache."""
        m = self._load_manifest()
        job = m["jobs"].setdefault(job_name, {"epochs": []})
        job.setdefault("crc", {})[str(epoch)] = crc
        # idempotent per epoch: a re-save of an already-committed
        # epoch (e.g. ALTER PARALLELISM re-basing state at the
        # current epoch) REPLACES the entry — appending would leave
        # duplicate epochs in GC/load bookkeeping (advisor r4)
        if epoch not in job["epochs"]:
            job["epochs"].append(epoch)
        job.setdefault("kind", {})[str(epoch)] = kind
        job["committed"] = epoch
        # GC beyond keep_epochs — but never break a delta chain:
        # keep everything back to the BASE FULL of the oldest epoch
        # that must stay readable (ref: hummock version GC keeps
        # deltas reachable from a checkpointed version)
        kinds = job["kind"]
        epochs_l = job["epochs"]
        if len(epochs_l) > self.keep_epochs:
            idx = len(epochs_l) - self.keep_epochs
            while idx > 0 and \
                    kinds.get(str(epochs_l[idx]), "full") != "full":
                idx -= 1
            for old in epochs_l[:idx]:
                kinds.pop(str(old), None)
                job.get("crc", {}).pop(str(old), None)
                for suffix in (".npz", ".meta"):
                    self.store.delete(
                        f"{job_name}/epoch_{old}{suffix}"
                    )
            job["epochs"] = epochs_l[idx:]
        self._store_manifest(m)
        # only after the manifest commit: a save that dies earlier
        # must not leave the digest cache pointing at an orphan file
        self._last_digests[job_name] = (epoch, digests)
        self._since_full[job_name] = 0 if kind == "full" \
            else self._since_full.get(job_name, 0) + 1

    def save(self, job_name: str, epoch: int, states: Any,
             source_state: dict, digests=None, lanes=None) -> None:
        """Persist one committed epoch synchronously (prepare+commit —
        the 'SST upload' + commit in one call).

        ``states`` may be a DEVICE pytree — only dirty blocks are
        fetched for delta checkpoints."""
        leaves, treedef = jax.tree.flatten(states)
        shapes = [np.shape(x) for x in leaves]
        self.commit(self.prepare(
            job_name, epoch, leaves, shapes, treedef, source_state,
            digests=digests, lanes=lanes,
        ))

    def invalidate(self, job_name: str) -> None:
        """Drop the in-memory digest cache for a job (called on any
        recovery rewind): the next save re-bases with a full snapshot
        instead of a delta computed against post-rewind live state.
        Also vacuums orphan epoch files a crashed upload left behind
        (object written, manifest never bumped)."""
        with self._lock:
            self._last_digests.pop(job_name, None)
            self._since_full.pop(job_name, None)
        self.vacuum_orphans(job_name)

    def vacuum_orphans(self, job_name: str) -> int:
        """Delete ``epoch_N.{npz,meta}`` objects whose epoch the
        manifest does not reference — the residue of a crash between
        the object write and the manifest commit.  Called on recovery
        rewinds, when no upload can be in flight for the job."""
        removed = 0
        with self._lock:
            m = self._load_manifest()
            known = {str(e) for e in m["jobs"].get(
                job_name, {}).get("epochs", [])}
            for key in self.store.list(job_name + "/"):
                name = key.rsplit("/", 1)[-1]
                if not name.startswith("epoch_"):
                    continue  # mv_epoch_*.sst exports etc.
                stem = name[len("epoch_"):]
                for suffix in (".npz", ".meta"):
                    if stem.endswith(suffix):
                        stem = stem[:-len(suffix)]
                        break
                else:
                    continue
                if stem.isdigit() and stem not in known:
                    self.store.delete(key)
                    removed += 1
        return removed

    def committed_epoch(self, job_name: str) -> int | None:
        m = self._load_manifest()
        job = m["jobs"].get(job_name)
        return None if job is None else job.get("committed")

    def epochs(self, job_name: str) -> list[int]:
        """Retained (time-travel-readable) epochs, oldest first."""
        m = self._load_manifest()
        job = m["jobs"].get(job_name)
        return list(job.get("epochs", [])) if job else []

    def checkpoint_bytes(self, job_name: str, epoch: int) -> int:
        """Stored payload size of one epoch (soak-test observability)."""
        key = f"{job_name}/epoch_{epoch}.npz"
        return self.store.size(key) if self.store.exists(key) else 0

    def checkpoint_kind(self, job_name: str, epoch: int) -> str | None:
        m = self._load_manifest()
        job = m["jobs"].get(job_name)
        if job is None:
            return None
        return job.get("kind", {}).get(str(epoch), "full")

    def load(self, job_name: str, epoch: int | None = None):
        """Load (epoch, states_host, source_state); latest if epoch None.

        Reconstructs delta checkpoints from the nearest full plus the
        delta chain (the reference's version + version-deltas).  Every
        object fetched is verified against the crc the manifest
        recorded at commit.  A latest-epoch load (``epoch=None`` — the
        recovery path) SELF-HEALS: a corrupt object quarantines its
        lineage tail (``quarantine_epoch``) and the load rewinds to
        the last epoch whose whole chain verifies — the round-credit
        rewind upstream then replays the gap.  An explicit-epoch load
        (time travel, scale-handover slices) must be exact, so
        corruption there raises ``CheckpointCorruption``.

        Holds the manifest lock so a concurrent uploader commit's GC
        cannot delete a chain file between the manifest read and the
        fetch."""
        with self._lock:
            if epoch is not None:
                return self._load_locked(job_name, epoch)
            while True:
                target = self.committed_epoch(job_name)
                if target is None:
                    return None
                try:
                    return self._load_locked(job_name, target)
                except CheckpointCorruption as e:
                    record_integrity_error(self.metrics, e)
                    dropped = self.quarantine_epoch(
                        job_name, getattr(e, "epoch", target),
                        reason=str(e),
                    )
                    if not dropped:
                        raise  # nothing left to rewind past
                    if self.metrics is not None:
                        self.metrics.inc("integrity_repairs_total",
                                         kind="checkpoint_rewind")

    def _get_verified(self, job: dict, job_name: str, epoch: int,
                      suffix: str) -> bytes:
        key = f"{job_name}/epoch_{epoch}.{suffix}"
        data = self.store.get(key)
        rec = job.get("crc", {}).get(str(epoch))
        if rec is not None and crc32c(data) != int(rec[suffix]):
            err = CheckpointCorruption(
                f"{key}: checkpoint object checksum mismatch", key=key
            )
            err.epoch = epoch
            raise err
        return data

    def _load_locked(self, job_name: str, epoch: int | None):
        if epoch is None:
            epoch = self.committed_epoch(job_name)
            if epoch is None:
                return None
        m = self._load_manifest()
        job = m["jobs"].get(job_name, {})
        kinds = job.get("kind", {})
        retained = [e for e in job.get("epochs", []) if e <= epoch]
        if not retained or retained[-1] != epoch:
            retained = retained + [epoch]  # legacy manifests
        # walk back to the base full
        chain: list[int] = []
        for e in reversed(retained):
            chain.append(e)
            if kinds.get(str(e), "full") == "full":
                break
        chain.reverse()
        base = chain[0]
        meta = pickle.loads(
            self._get_verified(job, job_name, base, "meta")
        )
        with np.load(io.BytesIO(
                self._get_verified(job, job_name, base, "npz"))) as z:
            leaves = [np.array(z[f"leaf_{i}"])
                      for i in range(len(z.files))]
        for e in chain[1:]:
            meta = pickle.loads(
                self._get_verified(job, job_name, e, "meta")
            )
            with np.load(io.BytesIO(
                    self._get_verified(job, job_name, e, "npz"))) as z:
                for key in z.files:
                    _, li, s_el = key.split("_")
                    li, s_el = int(li), int(s_el)
                    data = z[key]
                    flat = leaves[li].reshape(-1)
                    flat[s_el:s_el + data.shape[0]] = data
        states = jax.tree.unflatten(meta["treedef"], leaves)
        return epoch, states, meta["source_state"]

    # -- integrity: quarantine + lineage repair --------------------------
    def quarantine_epoch(self, job_name: str, epoch: int,
                         reason: str = "checksum mismatch") -> list[int]:
        """Quarantine one corrupt epoch and drop it — plus every later
        DELTA chained through it (a full re-bases the chain, so epochs
        from the next full onward stay) — from the manifest.  Dropped
        objects become vacuumable orphans; a durable quarantine note
        records each.  Returns the dropped epochs."""
        with self._manifest_txn():
            m = self._load_manifest()
            job = m["jobs"].get(job_name)
            if job is None or epoch not in job.get("epochs", []):
                return []
            epochs = job["epochs"]
            kinds = job.setdefault("kind", {})
            i = epochs.index(epoch)
            j = i + 1
            while j < len(epochs) \
                    and kinds.get(str(epochs[j]), "full") != "full":
                j += 1
            dropped = epochs[i:j]
            for e in dropped:
                quarantine(self.store, f"{job_name}/epoch_{e}.npz",
                           reason=reason, by="checkpoint_store",
                           metrics=self.metrics)
                kinds.pop(str(e), None)
                job.get("crc", {}).pop(str(e), None)
            job["epochs"] = epochs[:i] + epochs[j:]
            job["committed"] = max(job["epochs"]) if job["epochs"] \
                else 0
            self._store_manifest(m)
            # stale digest cache could delta against a dropped base
            self._last_digests.pop(job_name, None)
            self._since_full.pop(job_name, None)
        return dropped

    def verify_job(self, job_name: str) -> dict:
        """Scrub one job's retained lineage: every epoch object's
        bytes against the manifest-recorded crc (no decode).  Returns
        ``{"verified": n, "corrupt": [(epoch, key)]}``."""
        from risingwave_tpu.storage.integrity import (
            verify_checkpoint_store,
        )

        with self._lock:
            rep = verify_checkpoint_store(self.store, self._MANIFEST,
                                          jobs=[job_name])
        return {"verified": rep["verified"],
                "corrupt": [(e, k) for _, e, k in rep["corrupt"]]}

    def repair_lineage(self, job_name: str) -> dict:
        """Verify + self-heal one lineage in place: corrupt epochs are
        quarantined and the chain truncates to verified state (the
        corrupt-checkpoint repair the scrubber triggers through the
        owning worker).  The next save after a repair re-bases with a
        full snapshot (digest cache dropped by ``quarantine_epoch``)."""
        rep = self.verify_job(job_name)
        dropped: list[int] = []
        for e, key in rep["corrupt"]:
            record_integrity_error(
                self.metrics,
                CheckpointCorruption(f"{key}: scrub mismatch", key=key),
            )
            dropped += self.quarantine_epoch(
                job_name, e, reason="scrub checksum mismatch"
            )
        if dropped and self.metrics is not None:
            self.metrics.inc("integrity_repairs_total",
                             kind="checkpoint_rewind")
        return {"verified": rep["verified"],
                "corrupt": [k for _, k in rep["corrupt"]],
                "dropped_epochs": sorted(set(dropped))}

    # -- MV export to SSTs ----------------------------------------------
    def export_mv_sst(self, job_name: str, epoch: int, mv_executor,
                      mv_state) -> str:
        """Write an MV's rows as an SST keyed by memcomparable pk.

        The serving path (or another process) can then read the MV at
        this epoch without the job's device state — the reference's
        batch-scan-from-Hummock pattern (SURVEY.md §3.4).
        """
        from risingwave_tpu.storage.sst import build_sst_bytes

        rows = mv_executor.to_host(mv_state)
        schema = mv_executor.in_schema
        pk = getattr(mv_executor, "pk_indices", tuple(range(len(schema))))
        encoded: list[tuple[bytes, bytes]] = []
        for row in rows:
            key = b"".join(
                _mc_encode_value(row[i], schema[i]) for i in pk
            )
            val = pickle.dumps(row, protocol=4)
            encoded.append((key, val))
        encoded.sort(key=lambda kv: kv[0])
        key = f"{job_name}/mv_epoch_{epoch}.sst"
        data, _ = build_sst_bytes(
            [k for k, _ in encoded], [v for _, v in encoded])
        self.store.put(key, data)
        return self._abs(key)


def _mc_encode_value(v, field) -> bytes:
    from risingwave_tpu.common.types import DataType
    from risingwave_tpu.storage import codec as C

    t = field.data_type
    if field.nullable:
        # NULLABLE pk components (outer-join MV keys) carry a
        # presence prefix: \x00 + enc for present values, \x01 for
        # NULL — present values keep their relative byte order, NULLs
        # sort LAST (the pg default the serving ORDER BY pushdown
        # mirrors).  Non-nullable fields stay prefix-free, so every
        # pre-existing key encoding is unchanged.
        if v is None:
            return b"\x01"
        from dataclasses import replace as _replace

        return b"\x00" + _mc_encode_value(
            v, _replace(field, nullable=False)
        )
    if t.is_string:
        # terminated string encoding keeps prefix ordering correct
        return str(v).encode() + b"\x00"
    if t == DataType.DECIMAL:
        # to_host returns logical floats; re-scale to the exact integer
        # representation so fractional pks don't collide
        scaled = int(round(float(v) * 10**field.decimal_scale))
        return C.mc_encode_i64(np.asarray([scaled])).tobytes()
    if t in (DataType.FLOAT32, DataType.FLOAT64):
        return C.mc_encode_f64(np.asarray([float(v)])).tobytes()
    return C.mc_encode_i64(np.asarray([int(v)])).tobytes()
