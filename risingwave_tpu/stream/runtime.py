"""Streaming job runtime: the host-side barrier/epoch control loop.

Reference counterparts:
- meta's ``PeriodicBarriers`` + ``GlobalBarrierWorker::run`` loop
  (src/meta/src/barrier/{schedule.rs:508,worker.rs:378})
- CN's ``LocalBarrierWorker`` + actor event loop
  (src/stream/src/task/barrier_worker/mod.rs:303)

TPU-first design (SURVEY.md §7.1): barriers are host control flow, but
the barrier CROSSING is one asynchronously dispatched XLA program.  The
steady-state loop — K chunk steps, then a barrier — performs ZERO
synchronous host↔device round trips:

- emit-capacity drain loops run on device (``lax.while_loop`` inside
  the barrier program) instead of host readback loops;
- watermarks propagate as device scalars inside the same program;
- error counters (overflow/inconsistency) are collected into ONE device
  vector per barrier and read back once per maintenance interval;
- rehash decisions are ``lax.cond`` on device tombstone counts;
- snapshots are incremental device→device shadow updates.

``BarrierLoop`` is that protocol, once; the runtimes (``StreamingJob``
here, ``DagJob``, ``ShardedStreamingJob``) are device programs + hooks.
"""

from __future__ import annotations

import sys
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from risingwave_tpu.common.epoch import EpochPair
from risingwave_tpu.common.trace import GLOBAL_TRACE
from risingwave_tpu.stream.fragment import (
    Fragment,
    GAUGE_ATTRS,
    JOIN_GAUGE_ATTRS,
    PROBE_ROUND_ATTRS,
    TALLY_ATTRS,
    VIEW_GAUGE_ATTRS,
)
from risingwave_tpu.stream.message import Barrier, BarrierKind

#: a pk-keyed view holds its job's ingest (``BarrierLoop.ingest_hold``)
#: once its used slots, and what they have grown by between two
#: maintenance passes at most, pass this share of its table: before a
#: key finds no slot and the barrier raises with rows dropped
VIEW_HIGH_WATER = 7 / 8


@dataclass
class CheckpointSnapshot:
    """A committed epoch's host-side record: the state itself lives in
    the job's incremental ``ShadowSnapshot`` (stream/shadow.py), which
    ``recover()`` restores from when there is no durable store.

    ref: Hummock ``commit_epoch`` (src/meta/src/hummock/manager/
    commit_epoch.rs:73) — the in-memory snapshot stays device-resident;
    only the durable store pays a device→host transfer.
    """

    epoch: int
    source_state: dict
    #: host copies of spill-tier states at this epoch (key → pytree);
    #: None/missing key = the tier had absorbed nothing yet
    spill: dict | None = None


class BarrierLoop:
    """The host side of a barrier, once, for every runtime: cadence,
    maintain, commit, recover.  A runtime (``StreamingJob``, ``DagJob``,
    ``ShardedStreamingJob``) is its device programs plus the hooks this
    class calls; it sets ``self.states`` itself once its programs exist.

    Checkpoints are incremental shadow snapshots with pipelined async
    durable uploads (stream/shadow.py, stream/checkpoint.py): a
    snapshot barrier SEALS the epoch (``sealed_epoch``) in one async
    device dispatch and enqueues persistence to a background uploader;
    ``committed_epoch`` (the recovery/serving pin) advances only when
    the upload ACKS.  Without a durable store, seal and commit coincide
    (the shadow IS the commit).  The loop stalls only when the uploader
    falls more than ``upload_window`` epochs behind — the checkpoint
    analog of the L0-depth write stall.  A pk-keyed view that nears the
    end of its table sets ``ingest_hold`` at the maintenance pass that
    sees it (``_hold_at_high_water``); whoever drives the loop lets the
    barriers that would bring chunks wait (``Engine.ingest_waits``).

    Hooks a runtime provides: ``_cross_barrier``, ``_run_maintain``,
    ``counter_labels``, ``_init_states``, ``run_chunk``; where it
    differs from one plain source on one device and no spill tier:
    ``_place``, ``_source_state`` / ``_restore_sources`` /
    ``_reset_sources``, ``_drain_spill_tiers`` / ``_iter_spill_tiers``,
    ``_deliver_all_sinks``, ``_shadow_shard_rows``.
    """

    #: max sealed-but-unacked epochs before the barrier loop stalls
    upload_window: int = 4
    #: optional MetricsRegistry (the engine attaches its own)
    metrics = None
    #: index in the counters vector -> side, for a join side's tallies
    #: and levels (a runtime with joins sets it beside its labels)
    counter_sides: dict[int, str] | None = None
    _ckpt_key = None

    def __init__(self, name: str, checkpoint_frequency: int = 1,
                 checkpoint_store=None):
        self.name = name
        self.checkpoint_frequency = checkpoint_frequency
        #: optional durable store (storage.CheckpointStore); when set,
        #: commits persist across process restarts
        self.checkpoint_store = checkpoint_store
        #: checkpoints between maintenance passes (amortizes the ONE
        #: counters readback + rehash program)
        self.maintenance_interval = 1
        self._ckpts_since_maintain = 0
        #: checkpoints between snapshot commits
        self.snapshot_interval = 1
        self._ckpts_since_snapshot = 0
        #: storage-service backpressure (the Hummock write-limit
        #: contract): when set, every barrier crossing first calls
        #: this hook, which blocks while the storage L0 is deeper than
        #: its stall threshold — ingest yields to the compactor
        #: instead of burying it.  Returns seconds stalled.
        self.write_stall_hook = None
        #: cumulative seconds stalled (write stall + upload window)
        self.stall_seconds = 0.0
        self.epoch = EpochPair.first()
        self.barriers_seen = 0
        self.checkpoints: list[CheckpointSnapshot] = []
        #: committed epoch visible to batch reads (ref pinned snapshots)
        self.committed_epoch: int = 0
        self.sealed_epoch = 0
        self.paused = False
        #: why this job takes no chunk (a view at its high-water mark,
        #: by the last maintenance pass), else None.  The driver of the
        #: loop (``Engine.tick``) lets a barrier that brings chunks
        #: wait; one that brings none crosses, and the next pass looks
        #: again
        self.ingest_hold: str | None = None
        #: (rows, dispatch seconds) of a window the served ticker
        #: dispatched ahead of its barrier (``Engine.tick``), else None:
        #: the next barrier seals it, whoever crosses it
        self.window_ahead: tuple[int, float] | None = None
        #: source name -> rows pulled since ``take_source_rows`` last
        #: ran (a runtime of several sources keeps it; ``Engine`` counts
        #: it as ``source_rows_total{job,table}`` beside the job's rows)
        self.source_rows: dict[str, int] = {}
        #: whether the last barrier sealed a snapshot the shadow holds
        self.sealed_snapshot = False
        #: view label -> (used slots at the last pass, most they grew
        #: by between two passes)
        self._view_levels: dict[str, tuple[int, int]] = {}
        #: counters vector from the last barrier program (device array;
        #: read back once per maintenance interval)
        self._counters = None
        self._shadow = None
        self._uploader = None
        self._sinks_due = False

    @property
    def ckpt_key(self) -> str:
        """Durable-store key of this job's checkpoint lineage.  A
        partitioned job (cluster scale plane) runs one replica per
        worker over ONE shared store — each partition checkpoints
        under its own lineage key instead of the job name."""
        return self._ckpt_key or self.name

    @ckpt_key.setter
    def ckpt_key(self, value: str) -> None:
        self._ckpt_key = value

    # -- hooks ----------------------------------------------------------
    def _cross_barrier(self, epoch_val):
        """Dispatch the barrier crossing at ``epoch_val`` (async),
        leaving ``self.states`` and ``self._counters`` set; may return
        the first flush pass's emissions."""
        raise NotImplementedError

    def _run_maintain(self) -> None:
        """Dispatch the maintain program over ``self.states`` (a
        runtime without one keeps this)."""

    def window_one_dispatch(self, n: int) -> bool:
        """Whether ``run_chunks(n)`` is one asynchronous device
        dispatch (what a window sent ahead of its barrier must be)."""
        return False

    def _init_states(self):
        """A fresh state tree, placed where the programs expect it."""
        raise NotImplementedError

    def _place(self, states):
        """Put a loaded (host) or shadow-restored tree where the
        programs expect it."""
        return jax.device_put(states)

    def _source_state(self) -> dict:
        return self.source.state() if hasattr(self.source, "state") \
            else {}

    def _restore_sources(self, state: dict) -> None:
        restore_source(self.source, state)

    def _reset_sources(self) -> None:
        if hasattr(self.source, "offset"):
            self.source.offset = 0

    def _drain_spill_tiers(self, epoch_val) -> None:
        """Snapshot-barrier hook: divert ring rows to the host tiers
        and inject their changelog downstream."""

    def _iter_spill_tiers(self):
        """(snapshot key, durable-store key, tier) of every host spill
        tier."""
        return ()

    def _deliver_all_sinks(self, epoch_val) -> None:
        """Drain sink ring buffers at ``epoch_val``."""

    def _shadow_shard_rows(self) -> int | None:
        """Leading per-shard axis length of every state leaf
        (mesh-stacked trees digest in per-shard lanes), None for
        linear trees."""
        return None

    # -- driving --------------------------------------------------------
    def take_source_rows(self) -> dict[str, int]:
        """Rows pulled from each source since the last call."""
        taken, self.source_rows = self.source_rows, {}
        return taken

    def _pulled(self, source: str, rows: int) -> None:
        self.source_rows[source] = self.source_rows.get(source, 0) + rows

    def chunk_round(self) -> int:
        """One scheduling round (one chunk for a single-source job)."""
        return self.run_chunk()

    def run_chunks(self, n: int) -> int:
        """n scheduling rounds; runtimes with a fused window program
        make them one dispatch."""
        if self.paused:
            return 0
        return sum(self.chunk_round() for _ in range(n))

    def run(self, barriers: int, chunks_per_barrier: int) -> None:
        """The steady-state loop (ref §3.3).  Uploads pipeline within
        the batch; the batch boundary drains them (durability point)."""
        for _ in range(barriers):
            for _ in range(chunks_per_barrier):
                self.chunk_round()
            self.inject_barrier()
        self.drain_uploads()

    # -- the barrier ----------------------------------------------------
    def inject_barrier(self, barrier: Barrier | None = None):
        """Cross a barrier: one async dispatch (flush + drain +
        watermarks + counters), then maintenance / checkpoint on their
        cadences.

        Returns the chunks emitted by the first flush pass where the
        runtime has them (they have already flowed through the
        downstream executors — e.g. into a trailing Materialize — so
        callers usually ignore them).
        """
        if barrier is None:
            self.barriers_seen += 1
            kind = (
                BarrierKind.CHECKPOINT
                if self.barriers_seen % self.checkpoint_frequency == 0
                else BarrierKind.BARRIER
            )
            # the barrier SEALS the epoch data has been flowing in
            # (epoch.curr) and opens the next one (ref EpochPair)
            barrier = Barrier(self.epoch.bump(), kind)
        if barrier.mutation is not None:
            self._apply_mutation(barrier.mutation)
        if self.write_stall_hook is not None:
            # the barrier loop is the ingest clock: stalling HERE (not
            # per chunk) applies backpressure at epoch granularity
            # without touching the fused steady-state dispatch
            self.stall_seconds += self.write_stall_hook()

        epoch_val = barrier.epoch.prev.value
        self.sealed_snapshot = False
        with GLOBAL_TRACE.span("inject_barrier.dispatch", job=self.name):
            outs = self._cross_barrier(epoch_val)
        if barrier.is_checkpoint:
            self._ckpts_since_maintain += 1
            if self._ckpts_since_maintain >= self.maintenance_interval:
                self._maintain(epoch_val)
                self._ckpts_since_maintain = 0
            self._ckpts_since_snapshot += 1
            if self._ckpts_since_snapshot >= self.snapshot_interval:
                self._ckpts_since_snapshot = 0
                self._commit_checkpoint(epoch_val)
                self.sealed_snapshot = True
        # cheap ack poll keeps committed_epoch (and deferred sink
        # delivery) advancing while uploads complete in the background
        self._process_upload_acks()
        self.epoch = barrier.epoch
        return outs

    def _apply_mutation(self, mutation) -> None:
        if mutation.kind == "pause":
            self.paused = True
        elif mutation.kind == "resume":
            self.paused = False
        elif mutation.kind == "stop":
            self.paused = True

    def _maintain(self, epoch_val) -> None:
        """Rehash (on device) + the single counters readback."""
        with GLOBAL_TRACE.span("_maintain", job=self.name):
            self._run_maintain()
            if self._counters is None:
                return
            # THE one device sync: the host blocked on the chip until
            # the window, barrier and maintain programs have run
            with GLOBAL_TRACE.span("_maintain.device_wait",
                                   job=self.name):
                values = np.asarray(self._counters)
            residual = check_counter_values(
                self.name, self.counter_labels, values, self.metrics,
                self.counter_sides,
            )
            self._hold_at_high_water(values)
            # residual pending beyond MAX_DRAIN_ROUNDS×emit_capacity
            # per barrier: pathological; finish draining with host loops
            for _ in range(64):
                if not residual:
                    break
                self._cross_barrier(epoch_val)
                residual = check_counter_values(
                    self.name, self.counter_labels,
                    np.asarray(self._counters), self.metrics,
                    self.counter_sides,
                )

    def _hold_at_high_water(self, values: np.ndarray) -> None:
        """Set or lift ``ingest_hold`` from the views' levels on the
        counters vector (``fragment.VIEW_GAUGE_ATTRS``)."""
        at = dict(zip(self.counter_labels, values))
        hold = None
        for label, used in at.items():
            view, _, attr = label.rpartition(".")
            if attr != VIEW_GAUGE_ATTRS[0]:
                continue
            used = int(used)
            slots = int(at[f"{view}.{VIEW_GAUGE_ATTRS[1]}"])
            # a first look (a new or recovered job) knows no growth
            before, grew = self._view_levels.get(view, (used, 0))
            grew = max(grew, used - before)
            self._view_levels[view] = (used, grew)
            if used + grew > VIEW_HIGH_WATER * slots:
                hold = (f"{view}: {used} of {slots} slots used, "
                        f"{grew} more a maintenance pass")
        if hold is not None and self.ingest_hold is None:
            print(f"{self.name}: ingest held at the view's high-water "
                  f"mark ({hold}); barriers that bring chunks wait — "
                  "increase table/bucket capacity",
                  file=sys.stderr, flush=True)
        self.ingest_hold = hold
        if self.metrics is not None:
            self.metrics.set_gauge("stream_ingest_held",
                                   int(hold is not None), job=self.name)

    def _commit_checkpoint(self, epoch_val) -> None:
        """Seal one snapshot epoch: spill drain + sink delivery + the
        incremental shadow update, then hand durable persistence to the
        background uploader.  Recovery rewinds to the last DURABLE
        epoch, so ``committed_epoch`` (and deferred sink delivery)
        advance only on uploader ack; without a store, seal == commit
        (the shadow is the recovery point)."""
        with GLOBAL_TRACE.span("_commit_checkpoint", job=self.name,
                               epoch=epoch_val):
            self._drain_spill_tiers(epoch_val)
            up = self._ensure_uploader()
            if up is None or up.pending() == 0:
                # at-least-once delivery (rows delivered before their
                # epoch is durable ride THIS epoch's snapshot via the
                # advanced read_cursor)
                with GLOBAL_TRACE.span("_commit_checkpoint.sinks",
                                       job=self.name):
                    self._deliver_all_sinks(epoch_val)
            else:
                # uploader behind: defer delivery to the ack poll
                self._sinks_due = True
            self._snapshot_commit(epoch_val)

    # -- uploader plumbing ----------------------------------------------
    def _ensure_uploader(self):
        if self._uploader is None and self.checkpoint_store is not None:
            from risingwave_tpu.stream.checkpoint import (
                CheckpointUploader,
            )
            self._uploader = CheckpointUploader(
                self.checkpoint_store, self.ckpt_key,
                metrics=self.metrics,
            )
        return self._uploader

    def _process_upload_acks(self) -> None:
        """Cheap ack poll (no device work): advances committed_epoch
        and runs deferred sink delivery once the queue is empty."""
        up = self._uploader
        if up is None:
            return
        acked = up.take_acked()
        if acked:
            self.committed_epoch = max(self.committed_epoch, acked[-1])
        if self._sinks_due and up.pending() == 0 \
                and self.committed_epoch > 0:
            self._sinks_due = False
            self._deliver_all_sinks(self.committed_epoch)

    def upload_queue_depth(self) -> int:
        return 0 if self._uploader is None else self._uploader.pending()

    def drain_uploads(self, raise_error: bool = True) -> None:
        """Block until every sealed epoch is durable (tick-batch
        boundaries, orderly stop, recovery).  Within a batch the
        uploads pipeline; the batch boundary is the freshness point."""
        if self._uploader is not None:
            # the barrier loop standing still for its own upload
            with GLOBAL_TRACE.span("drain_uploads", job=self.name):
                self._uploader.drain(raise_error=raise_error)
                self._process_upload_acks()

    def _drop_shadow(self) -> None:
        """Forget the shadow — and the store's digest chain — of a
        state tree that changed shape (or of a job that gained/lost a
        durable store): in-flight uploads drain first, the next
        snapshot re-bases from scratch."""
        if self._shadow is None:
            return
        if self._uploader is not None:
            self._uploader.drain()
            self._process_upload_acks()
        if self.checkpoint_store is not None:
            self.checkpoint_store.invalidate(self.ckpt_key)
        self._shadow = None

    def reseed_checkpoint(self) -> None:
        """Re-snapshot after a change of the state tree's shape
        (topology edit, rescale): retained checkpoints hold the OLD
        shape, so a recover() between the change and the next commit
        would restore a structurally incompatible tree.  Callers invoke
        this once the change (and any backfill) is complete."""
        self._snapshot_commit(self.committed_epoch)

    # -- the snapshot-commit tail ----------------------------------------
    def _snapshot_commit(self, epoch_val: int) -> None:
        """Seal one epoch: shadow update (one async dispatch) +
        uploader enqueue (or, with no store, the in-memory commit)."""
        from risingwave_tpu.storage.digest import DEFAULT_BLOCK_ELEMS
        from risingwave_tpu.stream.shadow import ShadowSnapshot

        src_state = self._source_state()
        # ONE host materialization per tier, shared by the in-memory
        # snapshot and the durable save
        spill_host, spill_items = {}, []
        for key, store_key, tier in self._iter_spill_tiers():
            if tier.rows_absorbed:
                spill_host[key] = tier.snapshot()
                spill_items.append((store_key, spill_host[key]))
        store = self.checkpoint_store
        up = self._ensure_uploader()
        if up is not None:
            # bounded in-flight window (mirrors the L0-depth stall)
            with GLOBAL_TRACE.span("_commit_checkpoint.wait_window",
                                   job=self.name):
                self.stall_seconds += up.wait_window(self.upload_window)
            self._process_upload_acks()
        if self._shadow is not None and (
                not self._shadow.matches(self.states)
                or self._shadow.digest_mode != (store is not None)):
            self._drop_shadow()
        if self._shadow is not None and up is not None:
            # the update donates the shadow buffers in-flight fetches
            # still read — wait for the fetch point only
            with GLOBAL_TRACE.span("_commit_checkpoint.wait_fetched",
                                   job=self.name):
                up.wait_fetched()
        with GLOBAL_TRACE.span("snapshot", job=self.name,
                               epoch=epoch_val):
            if self._shadow is None:
                self._shadow = ShadowSnapshot(
                    self.states,
                    block_elems=store.block_elems if store is not None
                    else DEFAULT_BLOCK_ELEMS,
                    digest=store is not None,
                    shard_rows=self._shadow_shard_rows(),
                )
                digests = self._shadow.digests
            else:
                digests = self._shadow.update(self.states, epoch_val)
        self.sealed_epoch = epoch_val
        self.checkpoints = [CheckpointSnapshot(
            epoch=epoch_val, source_state=src_state, spill=spill_host,
        )]
        if store is not None:
            from risingwave_tpu.stream.checkpoint import UploadTask
            up.enqueue(UploadTask(
                epoch=epoch_val, leaves=self._shadow.leaves,
                digests=digests, shapes=self._shadow.shapes,
                treedef=self._shadow.treedef, source_state=src_state,
                spill=spill_items, lanes=self._shadow.lanes,
                trace_ctx=GLOBAL_TRACE.current(),
            ))
            self._process_upload_acks()
        else:
            self.committed_epoch = epoch_val

    # -- recovery -------------------------------------------------------
    def recover(self, epoch: int | None = None) -> None:
        """Reset to the last committed checkpoint (ref §3.5 recovery:
        rebuild actors + resume from last committed epoch).  Drains the
        upload queue first (sealed epochs finish becoming durable, a
        failed upload is swallowed — the rewind IS its resolution),
        then prefers the durable store (survives process restarts) over
        the in-memory shadow.  ``epoch`` pins the rewind to a specific
        retained checkpoint (the scale plane rewinds survivors to the
        handover round before transplanting moved-vnode slices);
        checkpoints live under ``ckpt_key`` — a partition's lineage,
        not the job name."""
        self._counters = None
        # a window still ahead of its barrier is rewound with the rest
        self.window_ahead = None
        # the rewound view's levels are the next maintenance pass's to
        # read: a hold of the state that is gone must not outlive it
        self.ingest_hold = None
        self._view_levels.clear()
        if self._uploader is not None:
            self._uploader.drain(raise_error=False)
            self._process_upload_acks()
            self._uploader.clear_error()
            self._sinks_due = False
        store = self.checkpoint_store
        if store is not None:
            # any rewind invalidates the store's in-memory digest
            # cache: the next save must re-base with a full snapshot,
            # or a delta computed against post-rewind live state could
            # overwrite a valid chain entry with a wrong-base delta
            # (invalidate also vacuums orphan files a crashed upload
            # left between object write and manifest commit)
            store.invalidate(self.ckpt_key)
            loaded = store.load(self.ckpt_key, epoch)
            if loaded is not None:
                epoch_v, states, src_state = loaded
                self.states = self._place(states)
                self.committed_epoch = epoch_v
                self.sealed_epoch = epoch_v
                self._restore_sources(src_state)
                for _, store_key, tier in self._iter_spill_tiers():
                    store.invalidate(store_key)
                    rewind_spill_tier(store, store_key, epoch_v, tier)
                return
        if not self.checkpoints:
            self.states = self._init_states()
            self._reset_sources()
            for _, _, tier in self._iter_spill_tiers():
                tier.reset()
            return
        snap = self.checkpoints[-1]
        # the shadow's restore is a copy: the next step donates its
        # input buffers, which must not invalidate the shadow itself
        self.states = self._place(self._shadow.restore())
        self._restore_sources(snap.source_state)
        for key, _, tier in self._iter_spill_tiers():
            if snap.spill and key in snap.spill:
                tier.restore(snap.spill[key])
            else:
                tier.reset()


def check_counter_values(name: str, labels: list[str],
                         values: np.ndarray, metrics=None,
                         sides: dict[int, str] | None = None) -> list[str]:
    """Raise on error counters; return labels with residual pending.

    ``values`` is the host copy of a barrier program's counters vector;
    with a registry, its per-kind sums are left behind as
    ``maintenance_counter_rows{job,kind}`` gauges (what the LAST
    maintenance barrier read) before anything raises.  The tallies of
    ``fragment.TALLY_ATTRS`` count engagement, not lost rows: they go
    out as ``hash_agg_<kind>_total{job}``, the levels of
    ``fragment.GAUGE_ATTRS`` as gauges ``hash_agg_<kind>{job}``, a join
    side's (``sides``: index in the vector -> side, from the runtime
    that collected them) as ``hash_join_<kind>[_total]{job,side}``, the
    probe rounds of ``fragment.PROBE_ROUND_ATTRS`` as
    ``hash_table_probe_rounds_total{job,width}``, and all are otherwise
    skipped, as ``.pending`` is.
    """
    kinds = [label.rsplit(".", 1)[-1] for label in labels]
    join_side = [(sides or {}).get(i) for i in range(len(labels))]
    if metrics is not None:
        sums: dict[str, int] = {}
        tallies: dict[str, int] = {}
        joins: dict[tuple, int] = {}
        views: dict[str, int] = {}
        rounds: dict[str, int] = {}
        for kind, side, v in zip(kinds, join_side, values):
            if kind in PROBE_ROUND_ATTRS:
                width = PROBE_ROUND_ATTRS[kind]
                rounds[width] = rounds.get(width, 0) + int(v)
            elif side is not None:
                joins[kind, side] = joins.get((kind, side), 0) + int(v)
            elif kind in TALLY_ATTRS + GAUGE_ATTRS:
                tallies[kind] = tallies.get(kind, 0) + int(v)
            elif kind in VIEW_GAUGE_ATTRS:
                views[kind] = views.get(kind, 0) + int(v)
            elif kind != "pending":
                sums[kind] = sums.get(kind, 0) + int(v)
        for (kind, side), v in joins.items():
            if kind in JOIN_GAUGE_ATTRS:
                metrics.set_gauge(f"hash_join_{kind}", v, job=name,
                                  side=side)
            else:
                metrics.set_counter(f"hash_join_{kind}_total", v,
                                    job=name, side=side)
        for kind, v in sums.items():
            metrics.set_gauge("maintenance_counter_rows", v,
                              job=name, kind=kind)
        for kind, v in views.items():
            metrics.set_gauge(f"materialize_{kind}", v, job=name)
        for width, v in rounds.items():
            metrics.set_counter("hash_table_probe_rounds_total", v,
                                job=name, width=width)
        for kind, v in tallies.items():
            if kind in GAUGE_ATTRS:
                metrics.set_gauge(f"hash_agg_{kind}", v, job=name)
            else:
                metrics.set_counter(f"hash_agg_{kind}_total", v, job=name)
    residual = []
    for label, kind, side, v in zip(labels, kinds, join_side, values):
        if side is not None or kind in PROBE_ROUND_ATTRS \
                or kind in TALLY_ATTRS + GAUGE_ATTRS + VIEW_GAUGE_ATTRS:
            continue
        if kind == "pending":
            if v > 0:
                residual.append(label)
        elif v > 0:
            if kind == "inconsistency":
                raise RuntimeError(
                    f"{name}/{label}: {v} inconsistent changelog rows "
                    "(deletes with no matching state)"
                )
            if kind == "emit_overflow":
                raise RuntimeError(
                    f"{name}/{label}: emit overflow ({v} output rows "
                    "dropped) — increase out_capacity"
                )
            if kind == "minput_overflow":
                hint = ("the materialised input of a min/max over a "
                        "retractable input (its table's size)")
            elif kind == "distinct_overflow":
                hint = "the DISTINCT dedup table (distinct_table_size)"
            elif "HashAgg" in label:
                hint = "the group table (agg_table_size)"
            elif "Ring" in label or "AppendOnly" in label:
                hint = "ring_size"
            else:
                hint = "table/bucket capacity"
            raise RuntimeError(
                f"{name}/{label}: state overflow ({v} rows dropped) — "
                f"increase {hint}"
            )
    return residual


def restore_source(source, state: dict) -> None:
    """Restore a source from its checkpointed state() dict.

    Sources may implement ``restore(state)`` for full-fidelity recovery;
    the fallback covers plain offset-cursor sources."""
    if hasattr(source, "restore"):
        source.restore(state)
    elif hasattr(source, "offset") and "offset" in state:
        source.offset = state["offset"]


def rewind_spill_tier(store, key: str, epoch: int, tier) -> None:
    """Rewind a host spill tier after job recovery: restore the nearest
    tier epoch <= the job's recovered epoch (a crash between the tier
    save and the job save leaves the tier one epoch ahead); when no
    eligible checkpoint exists the tier postdates every commit and must
    RESET — keeping its live state would double-count the replayed
    rows."""
    cands = [e for e in store.epochs(key) if e <= epoch]
    loaded = store.load(key, cands[-1]) if cands else None
    if loaded is not None:
        tier.restore(loaded[1])
    else:
        tier.reset()


def deliver_sinks(fragment: Fragment, states, epoch_val):
    """Drain sink ring buffers to their connectors (host barrier hook).

    Inherently a device→host read — runs on the snapshot cadence only."""
    states = list(states)
    for i, ex in enumerate(fragment.executors):
        if hasattr(ex, "deliver"):
            states[i] = ex.deliver(states[i], epoch_val)
    return tuple(states)


class StreamingJob(BarrierLoop):
    """A linear source → fragment pipeline driven by the barrier loop.

    The fragment typically ends in a Materialize executor (the MV).
    ``source.next_chunk()`` must return a device ``Chunk``.
    """

    def __init__(
        self,
        source,
        fragment: Fragment,
        name: str = "job",
        checkpoint_frequency: int = 1,
        checkpoint_store=None,
    ):
        super().__init__(name, checkpoint_frequency, checkpoint_store)
        self.source = source
        self.fragment = fragment
        self.states = self._init_states()
        #: spill-to-host tiers (stream/spill.py) per spill-enabled agg:
        #: [(exec_idx, drain_jit, inject_jit, tier)]
        self._spill: list = []
        for i, ex in enumerate(fragment.executors):
            if not getattr(ex, "spill_ring", 0):
                continue
            from risingwave_tpu.stream.spill import AggSpillTier
            drain = jax.jit(
                lambda states, i=i, ex=ex: self._drain_impl(states, i, ex),
                donate_argnums=(0,),
            )
            inject = jax.jit(
                lambda states, chunk, i=i: self._inject_impl(
                    states, chunk, i
                ),
                donate_argnums=(0,),
            )
            tier = AggSpillTier(
                ex, getattr(ex, "spill_table_size", ex.table_size * 8)
            )
            self._spill.append((i, drain, inject, tier))
        # fuse generation into the step when the source is traceable:
        # the source chunk never materializes standalone — XLA fuses
        # generator arithmetic straight into the executor kernels
        self._fused = None
        #: n-chunk fused programs (one dispatch per n chunks; host
        #: dispatch overhead amortized n-fold), keyed by n
        self._fused_multi: dict[int, Any] = {}
        if hasattr(source, "impl") and hasattr(source, "next_base"):

            def _fused(states, k0):
                with jax.named_scope("gen"):
                    chunk = source.impl(k0, source.cap)
                return fragment._step_impl(states, chunk)

            self._fused = jax.jit(_fused, donate_argnums=(0,))

    # ------------------------------------------------------------------
    def run_chunk(self) -> int:
        """Pull one chunk from the source through the fragment.

        Returns the chunk capacity processed (0 when paused) so callers
        can meter throughput without a device sync."""
        if self.paused:
            return 0
        if self._fused is not None:
            self.states, _ = self._fused(
                self.states, jnp.int64(self.source.next_base())
            )
            return self.source.cap
        chunk = self.source.next_chunk()
        self.states, _ = self.fragment.step(self.states, chunk)
        return chunk.capacity

    def run_chunks(self, n: int) -> int:
        """n chunk steps in ONE dispatch when the source is traceable.

        The stateless-query floor is per-dispatch host work (~hundreds
        of µs of Python per XLA call), not device compute — a
        ``fori_loop`` over n generator+step iterations inside one
        program amortizes it n-fold (the q1 attribution fix)."""
        if self.paused or n <= 0:
            return 0
        if self._fused is None or n == 1:
            return super().run_chunks(n)
        prog = self._multi_prog(n)
        k0 = jnp.int64(self.source.next_base())
        # the cursor already advanced one block; skip the other n-1
        self.source.offset += self.source.cap * (n - 1)
        self.states = prog(self.states, k0)
        return self.source.cap * n

    def window_one_dispatch(self, n: int) -> bool:
        return self._fused is not None

    def _multi_prog(self, n: int):
        """The jitted n-chunk window program (generator + step under
        one ``fori_loop``), cached by n."""
        prog = self._fused_multi.get(n)
        if prog is None:
            cap = self.source.cap
            stride = cap * getattr(self.source, "num_splits", 1)

            def _multi(states, k0):
                def body(i, st):
                    with jax.named_scope("gen"):
                        chunk = self.source.impl(k0 + i * stride, cap)
                    st2, _ = self.fragment._step_impl(st, chunk)
                    return st2

                return jax.lax.fori_loop(0, n, body, states)

            prog = jax.jit(_multi, donate_argnums=(0,))
            # bounded: chunks_per_barrier is runtime-mutable; distinct
            # values each compile a program — keep only the newest few
            if len(self._fused_multi) >= 4:
                self._fused_multi.pop(next(iter(self._fused_multi)))
            self._fused_multi[n] = prog
        return prog

    # -- BarrierLoop hooks ------------------------------------------------
    def _init_states(self):
        return self.fragment.init_states()

    @property
    def counter_labels(self) -> list[str]:
        return self.fragment.counter_labels

    def _cross_barrier(self, epoch_val):
        self.states, outs, self._counters = self.fragment.barrier(
            self.states, epoch_val
        )
        return outs

    def _run_maintain(self) -> None:
        self.states = self.fragment.maintain(self.states)

    def _drain_impl(self, states, i, ex):
        new_states = list(states)
        new_states[i], chunk = ex.drain_spill(states[i])
        return tuple(new_states), chunk

    def _inject_impl(self, states, chunk, i):
        """Feed a tier changelog through the executors AFTER the agg."""
        new_states = list(states)
        cur = chunk
        for j in range(i + 1, len(self.fragment.executors)):
            if cur is None:
                break
            new_states[j], cur = self.fragment.executors[j].apply(
                new_states[j], cur
            )
        return tuple(new_states)

    def _drain_spill_tiers(self, epoch_val) -> None:
        """Snapshot-barrier hook: divert ring rows to the host tier and
        inject its changelog downstream (ref: state beyond memory via
        the state-store tier, state_table.rs:187)."""
        for i, drain, inject, tier in self._spill:
            if int(np.asarray(self.states[i].spill_count)) == 0:
                continue
            self.states, chunk = drain(self.states)
            host_chunk = jax.device_get(chunk)
            out = tier.process(host_chunk, epoch_val)
            if out is not None:
                self.states = inject(self.states, out)

    def _deliver_all_sinks(self, epoch_val) -> None:
        self.states = deliver_sinks(self.fragment, self.states, epoch_val)

    def _iter_spill_tiers(self):
        for i, _, _, tier in self._spill:
            yield i, f"{self.ckpt_key}@spill{i}", tier
