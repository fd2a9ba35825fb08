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
- in-memory snapshots are jit-compiled device→device tree copies.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from risingwave_tpu.common.epoch import EpochPair
from risingwave_tpu.common.trace import GLOBAL_TRACE
from risingwave_tpu.stream.fragment import (
    COUNTER_ATTRS,
    Fragment,
    GAUGE_ATTRS,
    TALLY_ATTRS,
    WM_NONE,
    WM_SAFE_FLOOR,
    collect_counters,
)
from risingwave_tpu.stream.message import Barrier, BarrierKind


@dataclass
class CheckpointSnapshot:
    """A committed epoch: device snapshot of all state + source offsets.

    ref: Hummock ``commit_epoch`` (src/meta/src/hummock/manager/
    commit_epoch.rs:73) — the in-memory snapshot stays device-resident;
    only the durable store pays a device→host transfer.

    ``states is None`` marks a SHADOW-BACKED snapshot: the state lives
    in the job's incremental ``ShadowSnapshot`` (stream/shadow.py) and
    ``recover()`` restores from there — the full-copy tree is only
    retained on paths that still take it (sharded meshes).
    """

    epoch: int
    states: Any
    source_state: dict
    #: host copies of spill-tier states at this epoch (key → pytree);
    #: None/missing key = the tier had absorbed nothing yet
    spill: dict | None = None


#: jitted device→device snapshot copy (one dispatch for the whole tree)
@jax.jit
def _snapshot_copy(tree):
    return jax.tree.map(jnp.copy, tree)


class CheckpointPipelineMixin:
    """Incremental shadow snapshots + pipelined async durable uploads,
    shared by StreamingJob and DagJob (see stream/shadow.py and
    stream/checkpoint.py).

    Contract: a snapshot barrier SEALS the epoch (``sealed_epoch``) in
    one async device dispatch and enqueues persistence to a background
    uploader; ``committed_epoch`` (the recovery/serving pin) advances
    only when the upload ACKS.  Without a durable store, seal and
    commit coincide (the shadow IS the commit).  The barrier loop
    stalls only when the uploader falls more than ``upload_window``
    epochs behind — the checkpoint analog of the L0-depth write stall.
    """

    #: max sealed-but-unacked epochs before the barrier loop stalls
    upload_window: int = 4
    #: optional MetricsRegistry (the engine attaches its own)
    metrics = None
    _shadow = None
    _uploader = None
    _sinks_due = False

    def _init_pipeline(self) -> None:
        self.sealed_epoch = 0
        self._shadow = None
        self._uploader = None
        self._sinks_due = False

    @property
    def ckpt_key(self) -> str:
        """Durable-store key of this job's checkpoint lineage.  A
        partitioned job (cluster scale plane) runs one replica per
        worker over ONE shared store — each partition checkpoints
        under its own lineage key instead of the job name."""
        return getattr(self, "_ckpt_key", None) or self.name

    @ckpt_key.setter
    def ckpt_key(self, value: str) -> None:
        self._ckpt_key = value

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

    def _deliver_all_sinks(self, epoch_val) -> None:
        """Subclass hook: drain sink ring buffers at ``epoch_val``."""

    def _shadow_shard_rows(self) -> int | None:
        """Subclass hook: leading per-shard axis length of every state
        leaf (mesh-stacked trees digest in per-shard lanes), None for
        linear trees."""
        return None

    # -- the shared snapshot-commit tail ---------------------------------
    def _snapshot_commit(self, epoch_val: int, src_state: dict,
                         spill_host: dict, spill_items: list) -> None:
        """Seal one epoch: shadow update (one async dispatch) +
        uploader enqueue (or, with no store, the in-memory commit)."""
        from risingwave_tpu.storage.digest import DEFAULT_BLOCK_ELEMS
        from risingwave_tpu.stream.shadow import ShadowSnapshot

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
            # topology changed (or the job gained/lost a durable
            # store): the shadow — and the store's digest chain —
            # describe the OLD configuration; drain in-flight uploads,
            # then rebuild from scratch (full re-base)
            if up is not None:
                up.drain()
                self._process_upload_acks()
            if store is not None:
                store.invalidate(self.ckpt_key)
            self._shadow = None
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
            epoch=epoch_val, states=None, source_state=src_state,
            spill=spill_host,
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

    def _restore_in_memory(self, snap: CheckpointSnapshot):
        """States tree for an in-memory recover: from the shadow when
        the snapshot is shadow-backed, else the retained full copy."""
        if snap.states is None:
            return self._shadow.restore()
        return _snapshot_copy(snap.states)


def check_counter_values(name: str, labels: list[str],
                         values: np.ndarray, metrics=None) -> list[str]:
    """Raise on error counters; return labels with residual pending.

    ``values`` is the host copy of a barrier program's counters vector;
    with a registry, its per-kind sums are left behind as
    ``maintenance_counter_rows{job,kind}`` gauges (what the LAST
    maintenance barrier read) before anything raises.  The tallies of
    ``fragment.TALLY_ATTRS`` count engagement, not lost rows: they go
    out as ``hash_agg_<kind>_total{job}``, the levels of
    ``fragment.GAUGE_ATTRS`` as gauges ``hash_agg_<kind>{job}``, and
    both are otherwise skipped, as ``.pending`` is.
    """
    kinds = [label.rsplit(".", 1)[-1] for label in labels]
    if metrics is not None:
        sums: dict[str, int] = {}
        tallies: dict[str, int] = {}
        for kind, v in zip(kinds, values):
            if kind in TALLY_ATTRS + GAUGE_ATTRS:
                tallies[kind] = tallies.get(kind, 0) + int(v)
            elif kind != "pending":
                sums[kind] = sums.get(kind, 0) + int(v)
        for kind, v in sums.items():
            metrics.set_gauge("maintenance_counter_rows", v,
                              job=name, kind=kind)
        for kind, v in tallies.items():
            if kind in GAUGE_ATTRS:
                metrics.set_gauge(f"hash_agg_{kind}", v, job=name)
            else:
                metrics.set_counter(f"hash_agg_{kind}_total", v, job=name)
    residual = []
    for label, kind, v in zip(labels, kinds, values):
        if kind in TALLY_ATTRS + GAUGE_ATTRS:
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
            hint = "ring_size" if "Ring" in label or "AppendOnly" in label \
                else "table/bucket capacity"
            raise RuntimeError(
                f"{name}/{label}: state overflow ({v} rows dropped) — "
                f"increase {hint}"
            )
    return residual


def check_state_counters(name: str, st) -> None:
    """Eager single-state check (test/debug surface; one readback per
    counter — not for the steady-state loop)."""
    for attr in ("inconsistency", "overflow"):
        if hasattr(st, attr) and int(getattr(st, attr)) > 0:
            check_counter_values(
                name, [f"state.{attr}"],
                np.asarray([int(getattr(st, attr))]),
            )


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
    rows.  Shared by StreamingJob and DagJob."""
    cands = [e for e in store.epochs(key) if e <= epoch] \
        if store is not None else []
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


class StreamingJob(CheckpointPipelineMixin):
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
        self.source = source
        self.fragment = fragment
        self.name = name
        self.checkpoint_frequency = checkpoint_frequency
        #: optional durable store (storage.CheckpointStore); when set,
        #: commits persist across process restarts
        self.checkpoint_store = checkpoint_store
        #: checkpoints between maintenance passes (amortizes the ONE
        #: counters readback + rehash program)
        self.maintenance_interval = 1
        self._ckpts_since_maintain = 0
        #: checkpoints between in-memory snapshot copies
        self.snapshot_interval = 1
        self._ckpts_since_snapshot = 0
        #: storage-service backpressure (the Hummock write-limit
        #: contract): when set, every barrier crossing first calls
        #: this hook, which blocks while the storage L0 is deeper than
        #: its stall threshold — ingest yields to the compactor
        #: instead of burying it.  Returns seconds stalled.
        self.write_stall_hook = None
        #: cumulative seconds this job spent write-stalled
        self.stall_seconds = 0.0
        self.states = fragment.init_states()
        self.epoch = EpochPair.first()
        self.barriers_seen = 0
        self.checkpoints: list[CheckpointSnapshot] = []
        #: committed epoch visible to batch reads (ref pinned snapshots)
        self.committed_epoch: int = 0
        self._init_pipeline()
        self.paused = False
        #: counters vector from the last barrier program (device array;
        #: read back once per maintenance interval)
        self._counters = None
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
            rows = 0
            for _ in range(n):
                rows += self.run_chunk()
            return rows
        prog = self._multi_prog(n)
        k0 = jnp.int64(self.source.next_base())
        # the cursor already advanced one block; skip the other n-1
        self.source.offset += self.source.cap * (n - 1)
        self.states = prog(self.states, k0)
        return self.source.cap * n

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

    def inject_barrier(self, barrier: Barrier | None = None) -> list:
        """Cross a barrier: one async dispatch (flush + drain +
        watermarks + counters), then maintenance / checkpoint on their
        cadences.

        Returns the chunks emitted by the first flush pass (they have
        already flowed through the downstream executors inside the
        fragment — e.g. into a trailing Materialize — so callers
        usually ignore them).
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
            barrier = Barrier(
                EpochPair(self.epoch.curr.next(), self.epoch.curr), kind
            )
        if barrier.mutation is not None:
            self._apply_mutation(barrier.mutation)
        if self.write_stall_hook is not None:
            # the barrier loop is the ingest clock: stalling HERE (not
            # per chunk) applies backpressure at epoch granularity
            # without touching the fused steady-state dispatch
            self.stall_seconds += self.write_stall_hook()

        epoch_val = barrier.epoch.prev.value
        with GLOBAL_TRACE.span("inject_barrier.dispatch", job=self.name):
            self.states, outs, self._counters = self.fragment.barrier(
                self.states, epoch_val
            )
        if barrier.is_checkpoint:
            self._ckpts_since_maintain += 1
            if self._ckpts_since_maintain >= self.maintenance_interval:
                self._maintain(epoch_val)
                self._ckpts_since_maintain = 0
            self._commit_checkpoint(barrier)
        # cheap ack poll keeps committed_epoch (and deferred sink
        # delivery) advancing while uploads complete in the background
        self._process_upload_acks()
        self.epoch = barrier.epoch
        return outs

    def _maintain(self, epoch_val) -> None:
        """Rehash (on device) + the single counters readback."""
        with GLOBAL_TRACE.span("_maintain", job=self.name):
            self.states = self.fragment.maintain(self.states)
            if self._counters is None:
                return
            # THE one device sync: the host blocked on the chip until
            # the window, barrier and maintain programs have run
            with GLOBAL_TRACE.span("_maintain.device_wait",
                                   job=self.name):
                values = np.asarray(self._counters)
            residual = check_counter_values(
                self.name, self.fragment.counter_labels, values,
                self.metrics,
            )
            # residual pending beyond MAX_DRAIN_ROUNDS×emit_capacity
            # per barrier: pathological; finish draining with host loops
            for _ in range(64):
                if not residual:
                    break
                self.states, _, self._counters = self.fragment.barrier(
                    self.states, epoch_val
                )
                residual = check_counter_values(
                    self.name, self.fragment.counter_labels,
                    np.asarray(self._counters), self.metrics,
                )

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
        import numpy as _np
        for i, drain, inject, tier in self._spill:
            cnt = int(_np.asarray(self.states[i].spill_count))
            if cnt == 0:
                continue
            self.states, chunk = drain(self.states)
            host_chunk = jax.device_get(chunk)
            out = tier.process(host_chunk, epoch_val)
            if out is not None:
                self.states = inject(self.states, out)

    def _deliver_all_sinks(self, epoch_val) -> None:
        self.states = deliver_sinks(self.fragment, self.states, epoch_val)

    def _commit_checkpoint(self, barrier: Barrier) -> None:
        """Seal one snapshot epoch: spill drain + sink delivery + the
        incremental shadow update, then hand durable persistence to the
        background uploader.  Recovery rewinds to the last DURABLE
        epoch, so ``committed_epoch`` (and deferred sink delivery)
        advance only on uploader ack; without a store, seal == commit
        (the shadow is the recovery point)."""
        epoch_val = barrier.epoch.prev.value
        self._ckpts_since_snapshot += 1
        if self._ckpts_since_snapshot < self.snapshot_interval:
            return
        self._ckpts_since_snapshot = 0
        with GLOBAL_TRACE.span("_commit_checkpoint", job=self.name,
                               epoch=epoch_val):
            self._drain_spill_tiers(epoch_val)
            up = self._ensure_uploader()
            if up is None or up.pending() == 0:
                # at-least-once delivery, same window as the
                # synchronous path (rows delivered before their epoch
                # is durable ride THIS epoch's snapshot via the
                # advanced read_cursor)
                with GLOBAL_TRACE.span("_commit_checkpoint.sinks",
                                       job=self.name):
                    self.states = deliver_sinks(
                        self.fragment, self.states, epoch_val
                    )
            else:
                # uploader behind: defer delivery to the ack poll
                self._sinks_due = True
            src_state = self.source.state() \
                if hasattr(self.source, "state") else {}
            # ONE host materialization per tier, shared by the
            # in-memory snapshot and the durable save
            spill_host = {i: tier.snapshot()
                          for i, _, _, tier in self._spill
                          if tier.rows_absorbed}
            spill_items = [(f"{self.ckpt_key}@spill{i}", spill_host[i])
                           for i in spill_host]
            self._snapshot_commit(epoch_val, src_state, spill_host,
                                  spill_items)

    def _apply_mutation(self, mutation) -> None:
        if mutation.kind == "pause":
            self.paused = True
        elif mutation.kind == "resume":
            self.paused = False
        elif mutation.kind == "stop":
            self.paused = True

    # -- recovery -------------------------------------------------------
    def recover(self, epoch: int | None = None) -> None:
        """Reset to the last committed checkpoint (ref §3.5 recovery:
        rebuild actors + resume from last committed epoch).  Drains the
        upload queue first (sealed epochs finish becoming durable, a
        failed upload is swallowed — the rewind IS its resolution),
        then prefers the durable store (survives process restarts) over
        the in-memory shadow.  ``epoch`` pins the rewind to a specific
        retained checkpoint (the scale plane rewinds survivors to the
        handover round before transplanting moved-vnode slices)."""
        self._counters = None
        if self._uploader is not None:
            self._uploader.drain(raise_error=False)
            self._process_upload_acks()
            self._uploader.clear_error()
            self._sinks_due = False
        if self.checkpoint_store is not None:
            # any rewind invalidates the store's in-memory digest
            # cache: the next save must re-base with a full snapshot,
            # or a delta computed against post-rewind live state could
            # overwrite a valid chain entry with a wrong-base delta
            # (invalidate also vacuums orphan files a crashed upload
            # left between object write and manifest commit)
            self.checkpoint_store.invalidate(self.ckpt_key)
            loaded = self.checkpoint_store.load(self.ckpt_key, epoch)
            if loaded is not None:
                epoch_v, states, src_state = loaded
                self.states = jax.device_put(states)
                self.committed_epoch = epoch_v
                self.sealed_epoch = epoch_v
                restore_source(self.source, src_state)
                for i, _, _, tier in self._spill:
                    key = f"{self.ckpt_key}@spill{i}"
                    self.checkpoint_store.invalidate(key)
                    rewind_spill_tier(
                        self.checkpoint_store, key, epoch_v, tier
                    )
                return
        if not self.checkpoints:
            self.states = self.fragment.init_states()
            if hasattr(self.source, "offset"):
                self.source.offset = 0
            for _, _, _, tier in self._spill:
                tier.reset()
            return
        snap = self.checkpoints[-1]
        # copy: the next step donates its input buffers, which must not
        # invalidate the retained snapshot (shadow-backed snapshots
        # restore from the shadow tree — the shadow itself survives)
        self.states = self._restore_in_memory(snap)
        restore_source(self.source, snap.source_state)
        for i, _, _, tier in self._spill:
            if snap.spill and i in snap.spill:
                tier.restore(snap.spill[i])
            else:
                tier.reset()

    # ------------------------------------------------------------------
    def chunk_round(self) -> int:
        """Uniform driving interface shared with DagJob (one scheduling
        round = one chunk for a single-source linear job)."""
        return self.run_chunk()

    def run(self, barriers: int, chunks_per_barrier: int) -> None:
        """The steady-state loop (ref §3.3).  Uploads pipeline within
        the batch; the batch boundary drains them (durability point)."""
        for _ in range(barriers):
            for _ in range(chunks_per_barrier):
                self.run_chunk()
            self.inject_barrier()
        self.drain_uploads()

    def executor_state(self, idx: int):
        return self.states[idx]
