"""Fragment: a chain of executors compiled to one jitted step.

Reference counterpart: a plan *fragment* (cut at exchange boundaries,
src/frontend/src/stream_fragmenter/mod.rs:388) whose actors each run an
executor chain.  Here the chain is composed into a single pure function
``step(states, chunk) -> (states, out_chunk)`` and jitted once — XLA
fuses the per-executor kernels (SURVEY.md §7.1).

Barrier-time flushing (``flush``) is a second jitted function: executors
that emit on barrier (aggs) produce their changelog, and that changelog
flows through the *remaining* executors in the chain.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from risingwave_tpu.common.chunk import Chunk
from risingwave_tpu.common.types import Schema
from risingwave_tpu.stream.executor import Executor

#: sentinel for "no watermark yet" (matches WmState.max_ts init)
WM_NONE = np.iinfo(np.int64).min
#: safe stand-in threshold when no watermark exists: far enough below
#: any real event time that cleaning predicates match nothing, far
#: enough above INT64_MIN that `value - lag` cannot wrap
WM_SAFE_FLOOR = -(1 << 62)

#: per-executor-state scalar counters surfaced to maintenance checks; an
#: aggregate counts its three stores apart (``overflow`` the group
#: table), so that the barrier's error names the one that was full
COUNTER_ATTRS = ("inconsistency", "overflow", "emit_overflow",
                 "minput_overflow", "distinct_overflow")
#: running tallies that ride the same vector (``AggState``): how often a
#: mechanism engaged, not rows lost — maintenance exports them as
#: ``hash_agg_<attr>_total`` and neither sums nor raises on them
TALLY_ATTRS = ("apply_chunks", "rep_rows", "rep_tiles",
               "reclaim_passes", "reclaim_slots",
               "minput_changes", "flush_rounds",
               "cleaned_groups", "emit_rows")
#: levels on the same vector, as the last maintenance pass found them:
#: exported as gauges ``hash_agg_<attr>{job}``, skipped like the tallies
GAUGE_ATTRS = ("live_groups", "tombstones", "table_slots",
               "minput_live_values")
#: a join side's tallies and levels, under ``n<i>.join.<side>.<attr>`` on
#: the DAG runtime's vector: ``hash_join_<attr>_total{job,side}`` and
#: gauges ``hash_join_<attr>{job,side}``; skipped like the aggregate's
JOIN_TALLY_ATTRS = ("insert_rows", "delete_rows", "probe_steps",
                    "emit_rows", "cleaned_rows", "reclaim_slots",
                    "sorted_probe_chunks")
JOIN_GAUGE_ATTRS = ("live_rows", "tombstones", "table_slots")
#: tallies of a ``HashTable`` probe that runs at a chunk's width on the
#: barrier path (a view's upsert, a keyed join side): its rounds at that
#: width and in straggler tiles (``hash_table._probe_loop``), summed over
#: a job as ``hash_table_probe_rounds_total{job,width}``, ``width`` the
#: value here; skipped like the tallies above
PROBE_ROUND_ATTRS = {"probe_wide_rounds": "chunk",
                     "probe_tile_rounds": "tile"}
#: a pk-keyed view's levels, from ``MaterializeExecutor.levels`` (no
#: field of its state: a checkpoint keeps its leaves): gauges
#: ``materialize_<attr>{job}``, and what ``BarrierLoop`` holds a job's
#: ingest by before the view overflows
VIEW_GAUGE_ATTRS = ("used_slots", "view_slots")


def executor_scope(i: int, ex, phase: str):
    """``jax.named_scope("<ExecutorClass>.<index>/<phase>")`` —
    ``HashAgg.1/apply``, ``Materialize.2/flush``: the name a device
    profile puts an executor's operations under.  Metadata only: the
    compiled code is the same with or without it."""
    cls = type(ex).__name__.removesuffix("Executor")
    return jax.named_scope(f"{cls}.{i}/{phase}")


def watermark_scope(i: int, ex):
    """The scope of an executor's ``on_watermark``: ``<Executor>.<i>/
    watermark``, or what the executor calls the pass
    (``HashAgg.<i>/clean``: the groups a window close retires)."""
    return executor_scope(i, ex, getattr(ex, "watermark_phase", "watermark"))


def collect_counters(executors, states):
    """Gather every executor's error counters + residual pending-flush
    into ONE device vector (labels, int64 [n]).

    The host reads this vector once per maintenance interval — a single
    device sync — instead of one sync per counter per barrier."""
    labels: list[str] = []
    vals: list[jnp.ndarray] = []
    for i, ex in enumerate(executors):
        st = states[i]
        with executor_scope(i, ex, "counters"):
            for attr in COUNTER_ATTRS + TALLY_ATTRS + GAUGE_ATTRS \
                    + tuple(PROBE_ROUND_ATTRS):
                if hasattr(st, attr):
                    labels.append(f"{ex}.{attr}")
                    vals.append(getattr(st, attr).astype(jnp.int64))
            if hasattr(ex, "pending_flush"):
                labels.append(f"{ex}.pending")
                vals.append(ex.pending_flush(st).astype(jnp.int64))
            if hasattr(ex, "levels"):
                for attr, v in zip(VIEW_GAUGE_ATTRS, ex.levels(st)):
                    labels.append(f"{ex}.{attr}")
                    vals.append(v.astype(jnp.int64))
    vec = jnp.stack(vals) if vals else jnp.zeros((0,), jnp.int64)
    return labels, vec


class Fragment:
    """An executor chain with jit-compiled chunk/barrier paths."""

    #: bound on device-side flush re-drain rounds per barrier (each
    #: round emits one emit_capacity chunk per flushing executor)
    MAX_DRAIN_ROUNDS = 64

    def __init__(self, executors: Sequence[Executor], name: str = "fragment"):
        if not executors:
            raise ValueError("fragment needs at least one executor")
        self.executors = list(executors)
        self.name = name
        # donate the state buffers: XLA then mutates HBM in place
        # instead of copying every state array per chunk (the single
        # biggest throughput lever for large state tables).  Snapshot
        # holders copy explicitly before the next step (runtime).
        self._step = jax.jit(self._step_impl, donate_argnums=(0,))
        # epoch is passed as a traced scalar so barriers never retrace
        self._flush = jax.jit(self._flush_impl, donate_argnums=(0,))
        # the whole barrier crossing (flush + drain + watermarks +
        # counter collection) as ONE async dispatch — the steady-state
        # loop never synchronizes with the device
        self._barrier = jax.jit(self._barrier_impl, donate_argnums=(0,))
        self._maintain = jax.jit(self._maintain_impl, donate_argnums=(0,))
        #: counter labels aligned with the barrier counters vector;
        #: populated on first barrier trace
        self.counter_labels: list[str] = []

    # ------------------------------------------------------------------
    @property
    def out_schema(self) -> Schema:
        return self.executors[-1].out_schema

    def init_states(self) -> tuple:
        return tuple(e.init_state() for e in self.executors)

    # -- chunk path -----------------------------------------------------
    def _step_impl(self, states: tuple, chunk: Chunk):
        new_states = list(states)
        cur = chunk
        for i, ex in enumerate(self.executors):
            if cur is None:
                break
            with executor_scope(i, ex, "apply"):
                new_states[i], cur = ex.apply(states[i], cur)
        return tuple(new_states), cur

    def step(self, states: tuple, chunk: Chunk):
        """Process one chunk; returns (states, out_chunk_or_None)."""
        return self._step(states, chunk)

    # -- barrier path ---------------------------------------------------
    def _flush_impl(self, states: tuple, epoch):
        new_states = list(states)
        outs: list[Chunk] = []
        for i, ex in enumerate(self.executors):
            with executor_scope(i, ex, "flush"):
                new_states[i], emitted = ex.flush(new_states[i], epoch)
            if not ex.emits_on_flush or emitted is None:
                continue
            # emitted changelog flows through the rest of the chain
            cur = emitted
            for j in range(i + 1, len(self.executors)):
                if cur is None:
                    break
                ex2 = self.executors[j]
                with executor_scope(j, ex2, "apply"):
                    new_states[j], cur = ex2.apply(new_states[j], cur)
            if cur is not None:
                outs.append(cur)
        return tuple(new_states), outs

    def flush(self, states: tuple, epoch: int):
        """Barrier crossing: flush executors; returns (states, [chunks])."""
        return self._flush(states, epoch)

    def on_watermark(self, states: tuple, watermark):
        new_states = list(states)
        for i, ex in enumerate(self.executors):
            new_states[i] = ex.on_watermark(states[i], watermark)
        return tuple(new_states)

    # -- async barrier machinery (traceable; composed by the runtimes) --
    def has_pending_protocol(self) -> bool:
        return any(hasattr(ex, "pending_flush") for ex in self.executors)

    def pending_total(self, states) -> jnp.ndarray:
        """Total rows awaiting a further flush round (device scalar)."""
        tot = jnp.zeros((), jnp.int64)
        for i, ex in enumerate(self.executors):
            if hasattr(ex, "pending_flush"):
                tot = tot + ex.pending_flush(states[i]).astype(jnp.int64)
        return tot

    def _flush_states_only(self, states, epoch):
        s, _ = self._flush_impl(states, epoch)
        return s

    def _drain_impl(self, states, epoch):
        """Device-side emit-capacity drain: repeat flush passes until no
        executor reports pending output (the reference's re-drain loop
        in the runtime, moved into the program so the host never reads
        the pending count).  Only valid for terminal chains — drained
        emissions feed the rest of the chain and are then discarded."""
        if not self.has_pending_protocol():
            return states

        def cond(carry):
            sts, it = carry
            return (self.pending_total(sts) > 0) & (
                it < self.MAX_DRAIN_ROUNDS
            )

        def body(carry):
            sts, it = carry
            return self._flush_states_only(sts, epoch), it + 1

        states, _ = jax.lax.while_loop(cond, body, (states, jnp.int32(0)))
        return states

    def _wm_impl(self, states, axis: str | None = None):
        """Propagate watermarks from generator executors through the
        chain, entirely on device (no scalar readback).  The "no
        watermark yet" sentinel maps to WM_SAFE_FLOOR so downstream
        cleaning predicates match nothing.  Under a sharded runtime
        (``axis``) the watermark is the minimum across shards — one ICI
        collective, the reference's min-of-upstream-actors rule."""
        from risingwave_tpu.parallel.exchange import axis_min
        from risingwave_tpu.stream.message import Watermark
        from risingwave_tpu.stream.watermark import WatermarkFilterExecutor

        new_states = list(states)
        for i, ex in enumerate(self.executors):
            if not isinstance(ex, WatermarkFilterExecutor):
                continue
            raw = new_states[i].max_ts
            if axis is not None:
                raw = axis_min(raw, axis)
            val = jnp.where(
                raw == WM_NONE,
                jnp.int64(WM_SAFE_FLOOR),
                raw - ex.delay_us,
            )
            wm = Watermark(ex.ts_col, val)
            for j, ex2 in enumerate(self.executors):
                with watermark_scope(j, ex2):
                    new_states[j] = ex2.on_watermark(new_states[j], wm)
        return tuple(new_states)

    def _barrier_impl(self, states, epoch):
        """One-dispatch barrier crossing: flush, drain, watermarks,
        post-watermark drain (EOWC rows closed by THIS barrier emit at
        this barrier), then counter collection."""
        states, outs = self._flush_impl(states, epoch)
        states = self._drain_impl(states, epoch)
        states = self._wm_impl(states)
        states = self._drain_impl(states, epoch)
        labels, counters = collect_counters(self.executors, states)
        self.counter_labels = labels
        return states, outs, counters

    def barrier(self, states, epoch):
        """Cross a barrier asynchronously.

        Returns (states, first-pass emissions, counters int64 vector).
        The counters stay on device; the runtime reads them once per
        maintenance interval."""
        return self._barrier(states, epoch)

    def _maintain_impl(self, states):
        """Checkpoint-time housekeeping, all on device: executors whose
        tombstones dominate rebuild their tables (lax.cond inside
        maybe_rehash — no host readback of tombstone counts).  The scope
        is ``<Executor>.<i>/rehash``, or what the executor calls its
        pass (``HashAgg.<i>/reclaim``)."""
        new_states = list(states)
        for i, ex in enumerate(self.executors):
            if hasattr(ex, "maybe_rehash"):
                with executor_scope(
                        i, ex, getattr(ex, "maintain_phase", "rehash")):
                    new_states[i] = ex.maybe_rehash(new_states[i])
        return tuple(new_states)

    def maintain(self, states):
        return self._maintain(states)

    def __repr__(self) -> str:
        chain = " -> ".join(map(repr, self.executors))
        return f"Fragment({self.name}: {chain})"
