"""Object-store seam under the storage service (the S3 boundary).

Reference counterpart: ``src/object_store`` — one trait
(``ObjectStore``: upload/read/delete/list) with S3/GCS/filesystem/
in-memory implementations, plus the deterministic *simulated* store
madsim uses to kill uploads mid-flight
(``src/object_store/src/object/sim/``).  Everything above this seam
(SSTs, version manifest, checkpoints) speaks keys and bytes only, so
chaos tests swap the backend without touching the LSM.

Fault injection is **deterministic** (counter-addressed, no RNG): a
``StoreFaults`` rule fires on the Nth matching operation, either
*before* the object is stored (upload lost with the process) or
*after* (the object is durable but the caller dies before committing
its manifest — the orphan-SST case vacuum must reap).
"""

from __future__ import annotations

import io
import os
import threading
import time
from dataclasses import dataclass, field


class ObjectError(IOError):
    """An object-store operation failed (injected or real)."""


@dataclass
class _FaultRule:
    op: str               # "put" | "get" | "delete"
    substr: str           # only keys containing this match
    after: int            # skip this many matching ops first
    mode: str             # "before" (op lost) | "after" (op durable)
    times: int            # how many firings before the rule retires
    hits: int = 0
    seen: int = 0


@dataclass
class StoreFaults:
    """Injectable latency + error schedule shared by both stores."""

    put_latency_s: float = 0.0
    get_latency_s: float = 0.0
    rules: list[_FaultRule] = field(default_factory=list)
    #: totals for test assertions
    injected_errors: int = 0
    injected_corruptions: int = 0
    #: deterministic-corruption seed (splitmix64 bit choice)
    seed: int = 0

    def fail(self, op: str, substr: str = "", after: int = 0,
             mode: str = "before", times: int = 1) -> None:
        """Arm one deterministic failure: the ``after``-th matching op
        (0-based) raises ``ObjectError``; with ``mode='after'`` the
        store mutation still lands first (crash-after-upload); with
        ``mode='bit_flip'``/``'truncate'`` the op succeeds but its
        PAYLOAD is deterministically damaged (the corruption probe the
        integrity layer must catch)."""
        from risingwave_tpu.common.faults import CORRUPT_MODES

        assert op in ("put", "get", "delete") \
            and mode in ("before", "after") + CORRUPT_MODES
        assert not (mode in CORRUPT_MODES and op == "delete")
        self.rules.append(_FaultRule(op, substr, after, mode, times))

    # -- hooks called by the stores -------------------------------------
    def _match(self, op: str, key: str) -> "_FaultRule | None":
        for r in self.rules:
            if r.op != op or r.substr not in key or r.hits >= r.times:
                continue
            r.seen += 1
            if r.seen > r.after:
                r.hits += 1
                return r
        return None

    def before(self, op: str, key: str) -> "_FaultRule | None":
        lat = self.put_latency_s if op == "put" else self.get_latency_s
        if lat:
            time.sleep(lat)
        r = self._match(op, key)
        if r is not None and r.mode == "before":
            self.injected_errors += 1
            raise ObjectError(f"injected {op} fault (lost): {key}")
        return r

    def after(self, rule: "_FaultRule | None", op: str,
              key: str) -> None:
        if rule is not None and rule.mode == "after":
            self.injected_errors += 1
            raise ObjectError(f"injected {op} fault (durable): {key}")

    def corrupt(self, rule: "_FaultRule | None", key: str,
                data: bytes) -> bytes:
        from risingwave_tpu.common.faults import (
            CORRUPT_MODES,
            corrupt_payload,
        )

        if rule is None or rule.mode not in CORRUPT_MODES:
            return data
        self.injected_corruptions += 1
        return corrupt_payload(data, rule.mode, self.seed, rule.hits)


class ObjectStore:
    """Key → immutable bytes.  ``put`` is atomic (no torn reads)."""

    faults: StoreFaults | None = None

    # -- interface ------------------------------------------------------
    def put(self, key: str, data) -> None:
        """Store ``data`` under ``key``.  ``data`` is ``bytes`` or a
        view (``memoryview``) of a buffer its owner overwrites once
        this returns — ``CheckpointStore.commit`` hands in a view of
        the epoch buffer it keeps.  So an implementation reads all of
        it before it returns and keeps no reference: one that queues,
        uploads in parts or retries later takes ``bytes(data)`` first,
        or it lands bytes the manifest's crc32c no longer matches."""
        raise NotImplementedError

    def get(self, key: str) -> bytes:
        raise NotImplementedError

    def open(self, key: str):
        """Seekable binary reader (SSTs read footer-first)."""
        raise NotImplementedError

    def delete(self, key: str) -> None:
        raise NotImplementedError

    def exists(self, key: str) -> bool:
        raise NotImplementedError

    def list(self, prefix: str = "") -> list[str]:
        raise NotImplementedError

    def size(self, key: str) -> int:
        raise NotImplementedError

    # -- shared fault plumbing ------------------------------------------
    # Two layers consult here: the store's OWN StoreFaults (armed by
    # unit tests against one store instance) and the process-global
    # FaultFabric (common/faults.py — armed by chaos schedules, also
    # via the RWT_FAULTS env in spawned workers).  Either may raise.
    def _pre(self, op: str, key: str):
        local = self.faults.before(op, key) if self.faults else None
        from risingwave_tpu.common.faults import get_fabric

        fabric = get_fabric()
        global_rule = None
        if fabric is not None:
            global_rule = fabric.store_before(op, key)
        return local, global_rule

    def _post(self, rule, op: str, key: str) -> None:
        local, global_rule = rule if isinstance(rule, tuple) \
            else (rule, None)
        if self.faults:
            self.faults.after(local, op, key)
        if global_rule is not None:
            from risingwave_tpu.common.faults import get_fabric

            fabric = get_fabric()
            if fabric is not None:
                fabric.store_after(global_rule, op, key)

    def _xform(self, rule, key: str, data: bytes) -> bytes:
        """Apply matched corrupt-mode rules (local + global fabric) to
        one payload — put corruption lands DURABLY damaged bytes, get
        corruption models a bad read of an intact object."""
        local, global_rule = rule if isinstance(rule, tuple) \
            else (rule, None)
        if self.faults:
            data = self.faults.corrupt(local, key, data)
        if global_rule is not None:
            from risingwave_tpu.common.faults import get_fabric

            fabric = get_fabric()
            if fabric is not None:
                data = fabric.store_corrupt(global_rule, key, data)
        return data


class InMemObjectStore(ObjectStore):
    """Dict-backed store for tests/chaos (the sim object store)."""

    def __init__(self, faults: StoreFaults | None = None):
        self._d: dict[str, bytes] = {}
        self._lock = threading.Lock()
        self.faults = faults

    def put(self, key: str, data: bytes) -> None:
        rule = self._pre("put", key)
        with self._lock:
            self._d[key] = bytes(self._xform(rule, key, data))
        self._post(rule, "put", key)

    def get(self, key: str) -> bytes:
        rule = self._pre("get", key)
        with self._lock:
            if key not in self._d:
                raise ObjectError(f"no such object: {key}")
            data = self._d[key]
        self._post(rule, "get", key)
        return self._xform(rule, key, data)

    def open(self, key: str):
        return io.BytesIO(self.get(key))

    def delete(self, key: str) -> None:
        rule = self._pre("delete", key)
        with self._lock:
            self._d.pop(key, None)
        self._post(rule, "delete", key)

    def exists(self, key: str) -> bool:
        with self._lock:
            return key in self._d

    def list(self, prefix: str = "") -> list[str]:
        with self._lock:
            return sorted(k for k in self._d if k.startswith(prefix))

    def size(self, key: str) -> int:
        with self._lock:
            if key not in self._d:
                raise ObjectError(f"no such object: {key}")
            return len(self._d[key])


class LocalFsObjectStore(ObjectStore):
    """Filesystem-backed store; atomic put via tmp + rename."""

    def __init__(self, root: str, faults: StoreFaults | None = None):
        self.root = root
        self.faults = faults
        os.makedirs(root, exist_ok=True)

    def _path(self, key: str) -> str:
        assert ".." not in key.split("/"), key
        return os.path.join(self.root, key)

    def put(self, key: str, data: bytes) -> None:
        rule = self._pre("put", key)
        data = self._xform(rule, key, data)
        path = self._path(key)
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(data)
        os.replace(tmp, path)
        self._post(rule, "put", key)

    def get(self, key: str) -> bytes:
        rule = self._pre("get", key)
        try:
            with open(self._path(key), "rb") as f:
                data = f.read()
        except FileNotFoundError as e:
            raise ObjectError(f"no such object: {key}") from e
        self._post(rule, "get", key)
        return self._xform(rule, key, data)

    def open(self, key: str):
        rule = self._pre("get", key)
        try:
            f = open(self._path(key), "rb")
        except FileNotFoundError as e:
            raise ObjectError(f"no such object: {key}") from e
        self._post(rule, "get", key)
        local, global_rule = rule
        if (local is not None and local.mode in ("bit_flip", "truncate")) \
                or (global_rule is not None
                    and global_rule.mode in ("bit_flip", "truncate")):
            # a corrupted READ of a seekable object: materialize the
            # damaged bytes once (footer-first SST reads then see them)
            data = f.read()
            f.close()
            return io.BytesIO(self._xform(rule, key, data))
        return f

    def delete(self, key: str) -> None:
        rule = self._pre("delete", key)
        try:
            os.remove(self._path(key))
        except FileNotFoundError:
            pass
        self._post(rule, "delete", key)

    def exists(self, key: str) -> bool:
        return os.path.exists(self._path(key))

    def list(self, prefix: str = "") -> list[str]:
        out = []
        for dirpath, _, files in os.walk(self.root):
            rel = os.path.relpath(dirpath, self.root)
            rel = "" if rel == "." else rel + "/"
            for name in files:
                if name.endswith(".tmp"):
                    continue  # torn put, never visible
                key = rel + name
                if key.startswith(prefix):
                    out.append(key)
        return sorted(out)

    def size(self, key: str) -> int:
        try:
            return os.path.getsize(self._path(key))
        except FileNotFoundError as e:
            raise ObjectError(f"no such object: {key}") from e
