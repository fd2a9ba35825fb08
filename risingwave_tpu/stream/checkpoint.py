"""Pipelined asynchronous checkpoint persistence.

Reference counterpart: Hummock's uploader (uploader/mod.rs:1478) —
sealed epochs' dirty batches are uploaded OFF the barrier path and the
committed epoch advances only when the upload acks; the barrier loop
never blocks on object-store I/O unless the uploader falls behind
(the write-limit stall).

Shape here: one daemon thread per job.  A snapshot barrier SEALS an
epoch — shadow update dispatched, (epoch, digest vector, shadow leaf
refs, source/spill state) enqueued — and returns immediately.  The
uploader thread then:

1. reads the epoch's digests and queues the device programs of the
   fetch (a delta's gather), then marks the task DISPATCHED — the
   chip runs programs in order, so a window the served ticker sends
   ahead (``Engine.tick``) waits for this point, not to stand before
   the gather; then fetches the payload device→host (the digest diff
   picks the dirty runs; ``CheckpointStore.prepare``) and marks the
   task FETCHED — the next shadow update donates the shadow buffers,
   so it must wait for this point and no further;
2. encodes + writes the npz/meta objects and commits the manifest
   (``CheckpointStore.commit``), then ACKS the epoch.

The barrier loop polls acks (cheap, lock-free-ish deque) to advance
``committed_epoch`` and deferred sink delivery; ``wait_window`` is the
bounded in-flight contract — sealing stalls when more than N epochs
are unacked, mirroring the storage service's L0-depth write stall.
Recovery and orderly-stop paths call ``drain()`` first, so nothing
sealed is silently dropped by a clean exit.

A failed upload retries FIRST (the unified ``RetryPolicy`` — capped
exponential backoff, deterministic jitter; store blips and injected
chaos faults are transient by construction), and only after the
budget is exhausted turns LOUD: the partial objects are vacuumed and
the error is re-raised on the barrier loop at the next window wait /
drain — a job cannot keep sealing epochs that will never become
durable.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any

from risingwave_tpu.common.faults import RetryPolicy
from risingwave_tpu.common.trace import GLOBAL_TRACE


@dataclass
class UploadTask:
    """One sealed epoch queued for durable persistence."""

    epoch: int
    #: flat device leaves of the shadow AT SEAL TIME (the next shadow
    #: update donates these buffers — fetch must complete first)
    leaves: tuple
    #: device uint64 digest vector (computed by the shadow update; the
    #: store diffs it against its last persisted digests)
    digests: Any
    shapes: list
    treedef: Any
    source_state: dict
    #: per-leaf (rows, row_elems) digest-lane structure from a
    #: per-shard shadow (mesh-stacked trees), None entries = flat —
    #: prepare() must extract dirty runs on the same block grid
    lanes: Any = None
    #: [(store_key, host_state)] spill-tier saves, persisted FIRST (a
    #: crash between tier and job save leaves the tier ahead, which
    #: recovery rewinds; the reverse order loses absorbed groups)
    spill: list = field(default_factory=list)
    dispatched: threading.Event = field(default_factory=threading.Event)
    fetched: threading.Event = field(default_factory=threading.Event)
    done: threading.Event = field(default_factory=threading.Event)
    error: Exception | None = None
    #: (trace_id, span_id) captured AT SEAL TIME — the uploader thread
    #: has no thread-local trace context of its own, so the prepare/
    #: commit spans parent under the seal that enqueued this epoch
    trace_ctx: tuple | None = None


class CheckpointUploader:
    """Background uploader for one job's checkpoint chain."""

    def __init__(self, store, job_name: str, metrics=None,
                 retry: "RetryPolicy | None" = None):
        self.store = store
        self.job_name = job_name
        self.metrics = metrics
        #: transient store failures (incl. injected chaos faults)
        #: retry here, OFF the barrier loop, before anything surfaces
        self.retry = retry if retry is not None else RetryPolicy(
            max_attempts=4, base_delay_s=0.05, max_delay_s=1.0,
            metrics=metrics, op="upload",
        )
        self._q: deque[UploadTask] = deque()
        self._cv = threading.Condition()
        self._pending: list[UploadTask] = []
        self._acked: deque[int] = deque()
        self._thread: threading.Thread | None = None
        self._stop = False
        self.error: Exception | None = None
        #: observability (host counters; exported as gauges on demand)
        self.uploads_total = 0
        self.upload_seconds_total = 0.0
        self.stall_seconds_total = 0.0
        self.max_queue_depth = 0

    @property
    def retries_total(self) -> int:
        return self.retry.retries

    # -- producer side (the barrier loop) --------------------------------
    def enqueue(self, task: UploadTask) -> None:
        with self._cv:
            self._raise_if_failed()
            self._q.append(task)
            self._pending.append(task)
            self.max_queue_depth = max(self.max_queue_depth,
                                       len(self._pending))
            self._cv.notify_all()
        # AFTER the append: an idle thread only exits while the queue
        # is empty (under the cv), so a non-empty queue pins it alive
        # and a dead one is restarted here
        self._ensure_thread()

    def pending(self) -> int:
        with self._cv:
            return len(self._pending)

    def take_acked(self) -> list[int]:
        """Drain acked epochs (ascending — uploads are FIFO)."""
        with self._cv:
            out = list(self._acked)
            self._acked.clear()
            return out

    def wait_fetched(self, timeout: float = 600.0) -> None:
        """Block until every queued task's device→host fetch completed
        — the shadow buffers are about to be donated."""
        self._wait_tasks("fetched", timeout)

    def wait_dispatched(self, timeout: float = 600.0) -> None:
        """Block until every queued task's fetch programs are in the
        device's queue — the next window program is about to join it."""
        self._wait_tasks("dispatched", timeout)

    def _wait_tasks(self, point: str, timeout: float) -> None:
        with self._cv:
            tasks = list(self._pending)
        deadline = time.monotonic() + timeout
        for t in tasks:
            if not getattr(t, point).wait(
                    max(0.0, deadline - time.monotonic())):
                raise TimeoutError(
                    f"{self.job_name}: upload of epoch {t.epoch} not "
                    f"{point} within {timeout}s"
                )
        self._raise_if_failed()

    def wait_window(self, window: int, timeout: float = 600.0) -> float:
        """The bounded in-flight contract: block while more than
        ``window`` sealed epochs are unacked.  Returns seconds stalled
        (the job's write-stall meter, like the L0-depth stall)."""
        with self._cv:
            self._raise_if_failed()
            if len(self._pending) <= window:
                return 0.0
            t0 = time.monotonic()
            deadline = t0 + timeout
            while len(self._pending) > window:
                if self.error is not None:
                    self._raise_if_failed()
                left = deadline - time.monotonic()
                if left <= 0:
                    raise TimeoutError(
                        f"{self.job_name}: checkpoint uploader still "
                        f"{len(self._pending)} epochs behind after "
                        f"{timeout}s"
                    )
                self._cv.wait(min(left, 0.5))
            stalled = time.monotonic() - t0
            self.stall_seconds_total += stalled
            return stalled

    def drain(self, raise_error: bool = True, timeout: float = 600.0,
              ) -> None:
        """Block until the queue is empty (recovery/stop/tick-boundary
        paths: nothing sealed may be dropped)."""
        with self._cv:
            deadline = time.monotonic() + timeout
            while self._pending:
                left = deadline - time.monotonic()
                if left <= 0:
                    raise TimeoutError(
                        f"{self.job_name}: upload queue did not drain "
                        f"within {timeout}s"
                    )
                self._cv.wait(min(left, 0.5))
            if raise_error:
                self._raise_if_failed()

    def clear_error(self) -> None:
        """Recovery acknowledged the failure; the next save re-bases."""
        with self._cv:
            self.error = None

    def close(self) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify_all()

    def _raise_if_failed(self) -> None:
        if self.error is not None:
            raise RuntimeError(
                f"{self.job_name}: checkpoint upload failed — durable "
                "progress is stuck; recover() to rewind to the last "
                "committed epoch"
            ) from self.error

    # -- the uploader thread ---------------------------------------------
    def _ensure_thread(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._stop = False
            self._thread = threading.Thread(
                target=self._run, name=f"ckpt-upload-{self.job_name}",
                daemon=True,
            )
            self._thread.start()

    #: idle uploader threads exit after this long with an empty queue
    #: (test suites build many engines; re-enqueue restarts the thread)
    _IDLE_EXIT_S = 10.0

    def _run(self) -> None:
        import numpy as np

        idle_since = time.monotonic()
        while True:
            with self._cv:
                while not self._q and not self._stop:
                    if time.monotonic() - idle_since > self._IDLE_EXIT_S:
                        return
                    self._cv.wait(0.5)
                if self._stop and not self._q:
                    return
                task = self._q.popleft()
            idle_since = time.monotonic()
            t0 = time.perf_counter()
            try:
                # tier saves FIRST (see UploadTask.spill).  Every
                # store write retries through the policy: re-putting
                # the same key is idempotent (atomic object replace),
                # so a commit that died between the npz and the
                # manifest just rewrites both.
                for key, host_state in task.spill:
                    self.retry.run(
                        lambda k=key, hs=host_state: self.store.save(
                            k, task.epoch, hs, {}),
                        retry_on=(OSError,), label="spill_save",
                    )
                with GLOBAL_TRACE.span("ckpt_prepare",
                                       ctx=task.trace_ctx,
                                       metrics=self.metrics,
                                       job=self.job_name,
                                       epoch=task.epoch):
                    # waits for the shadow update program, then reads
                    # its digest vector
                    with GLOBAL_TRACE.span("ckpt_prepare.digests",
                                           job=self.job_name):
                        digests = np.asarray(task.digests) \
                            if task.digests is not None else None
                    prep = self.store.prepare(
                        self.job_name, task.epoch, task.leaves,
                        task.shapes, task.treedef, task.source_state,
                        digests=digests, lanes=task.lanes,
                        dispatched=task.dispatched.set,
                    )
                # host payload materialized: the shadow may be donated
                task.fetched.set()
                with GLOBAL_TRACE.span("ckpt_commit",
                                       ctx=task.trace_ctx,
                                       metrics=self.metrics,
                                       job=self.job_name,
                                       epoch=task.epoch):
                    self.retry.run(lambda: self.store.commit(prep),
                                   retry_on=(OSError,), label="commit")
                dt = time.perf_counter() - t0
                with self._cv:
                    self._acked.append(task.epoch)
                    self._pending.remove(task)
                    self.uploads_total += 1
                    self.upload_seconds_total += dt
                    self._cv.notify_all()
                if self.metrics is not None:
                    self.metrics.observe(
                        "checkpoint_upload_seconds", dt,
                        job=self.job_name,
                    )
                task.done.set()
            except Exception as e:  # noqa: BLE001 — surfaced on the loop
                # retry budget exhausted (or a non-transient failure):
                # reap the partial epoch objects so nothing un-durable
                # lingers in the store, then go loud on the loop
                try:
                    self.store.vacuum_orphans(self.job_name)
                except Exception:  # noqa: BLE001 — best-effort reap
                    pass
                task.error = e
                task.dispatched.set()
                task.fetched.set()
                task.done.set()
                with self._cv:
                    self.error = e
                    self._pending.remove(task)
                    self._cv.notify_all()
