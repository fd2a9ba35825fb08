"""Background compactor service: merges off the write path.

Reference counterpart: the compactor node role
(src/storage/src/hummock/compactor/compactor_runner.rs:70,
src/storage/compactor) — RisingWave's fourth binary, which this repo
lacked: the seed ``LsmTree`` merged inline on the ingest path.  Here a
daemon thread polls ``HummockStorage.pick_compaction`` (level budgets
→ tasks), executes the k-way merge, and commits version deltas; the
ingest path's only coupling is the L0-depth write stall.  Decoupling
compaction from ingest is the latency-tail discipline of Hazelcast
Jet's 99.99th-percentile argument and Taurus' near-data storage
service split (PAPERS.md).
"""

from __future__ import annotations

import threading
import time

from risingwave_tpu.common.trace import GLOBAL_TRACE


class CompactorService:
    """Thread-based compactor over one ``HummockStorage``.

    ``start()``/``stop()`` bound the thread's life; ``run_once()`` is
    the synchronous single-task step (shared with ctl and tests).  An
    optional ``vacuum_interval_tasks`` runs the orphan GC pass every N
    committed tasks — the embedded vacuum mode; deployments can also
    call ``storage.vacuum()`` on their own cadence (ctl ``storage
    gc``).
    """

    def __init__(self, storage, poll_interval_s: float = 0.01,
                 metrics=None, vacuum_interval_tasks: int = 0):
        self.storage = storage
        self.poll_interval_s = poll_interval_s
        self.metrics = metrics if metrics is not None \
            else storage.metrics
        self.vacuum_interval_tasks = vacuum_interval_tasks
        self._thread: threading.Thread | None = None
        self._stop = threading.Event()
        self.tasks_run = 0
        self.errors = 0
        #: last exception seen by the loop (surfaced to ctl/tests)
        self.last_error: BaseException | None = None

    # -- lifecycle ------------------------------------------------------
    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def start(self) -> "CompactorService":
        if self.running:
            return self
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, name="hummock-compactor", daemon=True)
        self._thread.start()
        return self

    def stop(self, timeout: float = 10.0) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout)
        self._thread = None

    # -- work -----------------------------------------------------------
    def run_once(self) -> bool:
        """Pick + execute + commit one compaction task; False when the
        policy is at quiescence."""
        t0 = time.perf_counter()
        with GLOBAL_TRACE.sampled_span("compact_cycle") as tsp:
            did = self.storage.compact_once()
            if not did:
                # an idle poll (every ``poll_interval_s``) would push
                # the barriers' and reads' trees out of the ring
                tsp.drop()
        if did:
            self.tasks_run += 1
            if self.metrics is not None:
                self.metrics.observe("storage_compact_seconds",
                                     time.perf_counter() - t0)
            if self.vacuum_interval_tasks \
                    and self.tasks_run % self.vacuum_interval_tasks == 0:
                self.storage.vacuum()
        return did

    def drain(self, max_tasks: int = 1_000_000) -> int:
        """Run tasks to quiescence on the CALLER's thread (tests,
        shutdown flush)."""
        n = 0
        while n < max_tasks and self.run_once():
            n += 1
        return n

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                if not self.run_once():
                    # idle: nothing due — sleep one poll interval
                    # (woken early only by the next due poll; ingest
                    # commits are frequent enough at stall depths)
                    self._stop.wait(self.poll_interval_s)
            except BaseException as e:  # keep the service alive
                self.errors += 1
                self.last_error = e
                if self.metrics is not None:
                    self.metrics.inc("storage_compactor_errors_total")
                self._stop.wait(self.poll_interval_s)
