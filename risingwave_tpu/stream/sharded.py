"""Sharded streaming jobs: one fragment chain SPMD over a vnode mesh.

Reference counterpart: fragment data parallelism — N parallel actors per
fragment, each owning a disjoint vnode bitmap, connected by hash
dispatchers (SURVEY.md §2.3 parallelism items 1-2).

TPU restructuring: the N actors of the reference become ONE
``shard_map``-ed step function over a mesh axis (``"shard"``).  Each
shard holds its own executor states (leading mesh-sharded axis); the
hash exchange between the stateless prefix and the keyed suffix is an
``all_to_all`` inside the same jitted program, riding ICI.  The barrier
loop drives all shards in lockstep, so merge alignment is structural.
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from risingwave_tpu.parallel.exchange import axis_min, shard_map_nocheck

from risingwave_tpu.common.chunk import Chunk
from risingwave_tpu.parallel.exchange import shuffle_chunk
from risingwave_tpu.stream.executor import Executor
from risingwave_tpu.stream.fragment import (
    WM_NONE,
    WM_SAFE_FLOOR,
    Fragment,
    collect_counters,
)
from risingwave_tpu.stream.runtime import BarrierLoop


def make_mesh(n_devices: int | None = None, axis: str = "shard") -> Mesh:
    devs = jax.devices()
    n = n_devices or len(devs)
    return Mesh(devs[:n], (axis,))


class ShardedJob:
    """source → [local executors] → hash exchange → [keyed executors].

    ``source_fn(k0, cap) -> Chunk`` must be traceable (e.g. the nexmark
    generator impl): each shard generates/reads its own ordinal range, so
    ingestion is embarrassingly parallel like the reference's source
    splits.  ``exchange_keys(chunk) -> [key cols]`` routes rows to the
    shard owning their vnode.
    """

    AXIS = "shard"

    def __init__(
        self,
        mesh: Mesh,
        source_fn: Callable,
        chunk_capacity: int,
        local_executors: Sequence[Executor],
        exchange_key_fn: Callable,
        keyed_executors: Sequence[Executor],
    ):
        self.mesh = mesh
        self.n_shards = mesh.devices.size
        self.source_fn = source_fn
        self.cap = chunk_capacity
        # the two halves of the chain are real Fragments, so chain
        # semantics (None-break, flush cascade) stay single-sourced
        self.local_frag = (
            Fragment(local_executors, "local") if local_executors else None
        )
        self.keyed_frag = Fragment(keyed_executors, "keyed")
        self.exchange_key_fn = exchange_key_fn
        self.executors = list(local_executors) + list(keyed_executors)

        spec = P(self.AXIS)
        self._step = jax.jit(
            shard_map_nocheck(
                self._local_step,
                mesh=self.mesh,
                in_specs=(spec, spec),
                out_specs=spec,
            )
        )
        self._flush = jax.jit(
            shard_map_nocheck(
                self._local_flush,
                mesh=self.mesh,
                in_specs=(spec, spec),
                out_specs=(spec, spec),
            )
        )
        self._counters_prog = jax.jit(self._shard_summed_counters)
        #: labels aligned with the counters vector; set on first trace
        self.counter_labels: list[str] = []

    def on_mesh(self, mesh: Mesh) -> "ShardedJob":
        """The same executor descriptors over another mesh."""
        return ShardedJob(
            mesh,
            source_fn=self.source_fn,
            chunk_capacity=self.cap,
            local_executors=list(
                self.local_frag.executors if self.local_frag else []
            ),
            exchange_key_fn=self.exchange_key_fn,
            keyed_executors=list(self.keyed_frag.executors),
        )

    # ------------------------------------------------------------------
    def init_states(self):
        """Per-shard states stacked on a leading mesh-sharded axis."""
        def one_shard(_):
            return tuple(ex.init_state() for ex in self.executors)

        return self.place(jax.vmap(one_shard)(jnp.arange(self.n_shards)))

    def place(self, stacked):
        """A ``[n_shards, ...]`` tree, one leading row on each shard."""
        return jax.device_put(
            stacked, jax.NamedSharding(self.mesh, P(self.AXIS))
        )

    # -- traced per-shard bodies ----------------------------------------
    def _split(self, states):
        n_local = len(self.local_frag.executors) if self.local_frag else 0
        return tuple(states[:n_local]), tuple(states[n_local:])

    def _local_step(self, states, k0):
        states = jax.tree.map(lambda x: x[0], states)
        local_states, keyed_states = self._split(states)
        chunk = self.source_fn(k0[0], self.cap)
        if self.local_frag is not None:
            local_states, chunk = self.local_frag._step_impl(
                local_states, chunk
            )
        if chunk is not None:
            chunk = shuffle_chunk(
                chunk, self.exchange_key_fn(chunk), self.AXIS, self.n_shards
            )
            keyed_states, _ = self.keyed_frag._step_impl(keyed_states, chunk)
        return jax.tree.map(
            lambda x: x[None], tuple(local_states) + tuple(keyed_states)
        )

    def _feed_exchange(self, keyed_states, emitted):
        """Route a local-half emission across the vnode exchange into
        the keyed half (inside the shard_map body — rides ICI)."""
        shuffled = shuffle_chunk(
            emitted, self.exchange_key_fn(emitted), self.AXIS, self.n_shards
        )
        keyed_states, out = self.keyed_frag._step_impl(
            keyed_states, shuffled
        )
        return keyed_states, out

    def _local_flush(self, states, epoch):
        states = jax.tree.map(lambda x: x[0], states)
        local_states, keyed_states = self._split(states)
        outs = []
        if self.local_frag is not None:
            local_states, local_outs = self.local_frag._flush_impl(
                local_states, epoch[0]
            )
            # barrier emissions from the local half cross the exchange
            for emitted in local_outs:
                keyed_states, out = self._feed_exchange(
                    keyed_states, emitted
                )
                if out is not None:
                    outs.append(out)
            if self.local_frag.has_pending_protocol():
                # device-side drain of the local half, feeding each
                # round across the exchange (no host pending readbacks)
                def cond(carry):
                    ls, ks, it = carry
                    return (self.local_frag.pending_total(ls) > 0) & (
                        it < self.local_frag.MAX_DRAIN_ROUNDS
                    )

                def body(carry):
                    ls, ks, it = carry
                    ls, more = self.local_frag._flush_impl(ls, epoch[0])
                    for emitted in more:
                        ks, _ = self._feed_exchange(ks, emitted)
                    return ls, ks, it + 1

                local_states, keyed_states, _ = jax.lax.while_loop(
                    cond, body,
                    (local_states, keyed_states, jnp.int32(0)),
                )
        keyed_states, keyed_outs = self.keyed_frag._flush_impl(
            keyed_states, epoch[0]
        )
        outs.extend(keyed_outs)
        # keyed half is terminal — drain it on device too
        keyed_states = self.keyed_frag._drain_impl(keyed_states, epoch[0])
        # watermark alignment + state cleaning (mirrors the linear
        # barrier's flush → drain → wm → drain order)
        local_states, keyed_states = self._wm_pass(
            local_states, keyed_states
        )
        keyed_states = self.keyed_frag._drain_impl(keyed_states, epoch[0])
        out_tree = jax.tree.map(lambda x: x[None], tuple(outs))
        new_states = tuple(local_states) + tuple(keyed_states)
        return jax.tree.map(lambda x: x[None], new_states), out_tree

    def _wm_pass(self, local_states, keyed_states):
        """Cross-shard watermark alignment, entirely on device.

        The reference aligns watermarks by flowing them through
        exchange dispatchers and taking the min across upstream actors
        (src/stream/src/executor/merge.rs watermark alignment).  Here
        each shard's WatermarkFilter holds a local max_ts; the global
        watermark is the minimum over the mesh axis — one ICI
        collective per barrier — then every executor in both halves
        applies its cleaning/EOWC hook.  A shard that has seen no data
        pins the global watermark at the WM_NONE sentinel, so cleaning
        never outruns a lagging shard (exactly the reference's
        min-of-upstreams rule)."""
        from risingwave_tpu.stream.message import Watermark
        from risingwave_tpu.stream.watermark import WatermarkFilterExecutor

        local_execs = list(self.local_frag.executors) \
            if self.local_frag else []
        keyed_execs = list(self.keyed_frag.executors)
        locs, keys = list(local_states), list(keyed_states)
        for i, ex in enumerate(local_execs):
            if not isinstance(ex, WatermarkFilterExecutor):
                continue
            graw = axis_min(locs[i].max_ts, self.AXIS)
            val = jnp.where(
                graw == WM_NONE,
                jnp.int64(WM_SAFE_FLOOR),
                graw - ex.delay_us,
            )
            wm = Watermark(ex.ts_col, val)
            for j, ex2 in enumerate(local_execs):
                locs[j] = ex2.on_watermark(locs[j], wm)
            for j, ex2 in enumerate(keyed_execs):
                keys[j] = ex2.on_watermark(keys[j], wm)
        return tuple(locs), tuple(keys)

    def _shard_summed_counters(self, states):
        """``fragment.collect_counters`` of every shard, summed over
        the shard axis: ONE device vector, read back once per
        maintenance interval."""
        def one_shard(shard_states):
            self.counter_labels, vec = collect_counters(
                self.executors, shard_states
            )
            return vec

        return jnp.sum(jax.vmap(one_shard)(states), axis=0)

    # -- host API --------------------------------------------------------
    def counters(self, states):
        return self._counters_prog(states)

    def step(self, states, k0_per_shard: jnp.ndarray):
        """One chunk per shard; ``k0_per_shard`` int64 [n_shards]."""
        return self._step(states, k0_per_shard)

    def flush(self, states, epoch: int):
        epochs = jnp.full((self.n_shards,), epoch, jnp.int64)
        return self._flush(states, epochs)

    def shard_states(self, states, shard: int):
        """Host view of one shard's states (for serving/inspection)."""
        return jax.tree.map(lambda x: x[shard], jax.device_get(states))

    def run_epochs(
        self,
        states,
        barriers: int,
        chunks_per_barrier: int,
        start_ordinal: int = 0,
    ):
        """Drive the barrier loop; returns (states, emitted-per-flush)."""
        ordinal = start_ordinal
        all_outs = []
        for _ in range(barriers):
            for _ in range(chunks_per_barrier):
                k0 = ordinal + jnp.arange(self.n_shards, dtype=jnp.int64) \
                    * self.cap
                states = self.step(states, k0)
                ordinal += self.n_shards * self.cap
            states, outs = self.flush(states, 0)
            all_outs.append(outs)
        return states, all_outs


class ShardedStreamingJob(BarrierLoop):
    """A ShardedJob's programs under the barrier loop.

    Lets the engine drive vnode-sharded MVs like linear jobs (ref: the
    reference's adaptive parallelism — N actors per fragment — behind
    one scheduling surface).  Checkpoints ride the shadow + uploader
    pipeline with one digest lane per shard.

    Round-1 scope: traceable sources, no watermark-driven cleaning in
    the sharded path (planner gates eligibility).
    """

    def __init__(self, sharded: ShardedJob, source, name: str,
                 checkpoint_frequency: int = 1, checkpoint_store=None):
        super().__init__(name, checkpoint_frequency, checkpoint_store)
        self.sharded = sharded
        self.source = source
        self.states = self._init_states()

    def run_chunk(self) -> int:
        if self.paused:
            return 0
        n, cap = self.sharded.n_shards, self.sharded.cap
        # next_base() owns split→global ordinal mapping; one cap-stride
        # block per shard
        k0 = jnp.asarray(
            [self.source.next_base() for _ in range(n)], jnp.int64
        )
        self.states = self.sharded.step(self.states, k0)
        return n * cap

    # -- BarrierLoop hooks ------------------------------------------------
    def _init_states(self):
        return self.sharded.init_states()

    @property
    def counter_labels(self) -> list[str]:
        return self.sharded.counter_labels

    def _cross_barrier(self, epoch_val) -> None:
        # flush drains on device inside the shard_map body — the host
        # never reads pending counts
        self.states, _ = self.sharded.flush(self.states, epoch_val)
        self._counters = self.sharded.counters(self.states)

    def _shadow_shard_rows(self) -> int:
        return self.sharded.n_shards

    def _place(self, states):
        # an online rescale may have committed a DIFFERENT parallelism
        # than the DDL replanned: rebuild the mesh to the tree's shard
        # dim (state is authoritative — silently truncating shards
        # would drop groups)
        n = jax.tree.leaves(states)[0].shape[0]
        if n != self.sharded.n_shards:
            if n > len(jax.devices()):
                raise RuntimeError(
                    f"checkpoint has {n} shards but only "
                    f"{len(jax.devices())} devices are visible"
                )
            self.sharded = self.sharded.on_mesh(make_mesh(n))
        return self.sharded.place(states)

    def _deliver_all_sinks(self, sealed: int) -> None:
        """Per-shard sink cursors, merged host-side at the snapshot
        barrier (ref sink.rs delivery; cross-shard row order is
        unspecified, matching the reference's per-parallelism sinks).
        The cursors live in the sharded state tree and share the
        checkpoint cadence, but delivery runs BEFORE the durable save:
        a crash between the two rewinds the cursors and re-delivers the
        epoch's rows — at-least-once, like the linear runtime.
        Downstream readers get exactly-once by honoring the per-epoch
        commit marker (the closed-epoch reader protocol, sinks.py):
        rows of an epoch delivered twice carry the same epoch tag, and
        only one commit marker is ever emitted per epoch."""
        states = list(self.states)
        for i, ex in enumerate(self.sharded.executors):
            if not hasattr(ex, "deliver"):
                continue
            host_shards = []
            for s in range(self.sharded.n_shards):
                st = jax.tree.map(lambda x: x[s], states[i])
                # every shard's rows first; ONE commit marker per epoch
                # (the closed-epoch reader protocol, sinks.py)
                host_shards.append(ex.deliver(st, sealed, commit=False))
            ex.sink.commit(sealed)
            states[i] = self.sharded.place(
                jax.tree.map(lambda *xs: jnp.stack(xs), *host_shards)
            )
        self.states = tuple(states)

    # -- online rescale --------------------------------------------------
    def rescale(self, new_n: int) -> None:
        """Re-parallelize at a barrier: N → new_n shards.

        Ref: ``ScaleController`` reschedules by reassigning vnode
        ownership at a barrier and letting state follow vnodes through
        shared storage (src/meta/src/stream/scale.rs:224,336).  Here
        state is device-resident, so it MOVES: the keyed aggregation's
        live groups are extracted as input-schema rows, re-routed by
        the same vnode map onto the new mesh, and re-applied; every
        downstream state (TopN bands, the MV) rebuilds from the agg's
        first post-rescale flush, which re-emits all groups against
        fresh prev-state.  Watermarks carry over conservatively (the
        old global min seeds every new shard)."""
        from risingwave_tpu.parallel.exchange import (
            compute_vnodes, shard_of_vnode,
        )
        from risingwave_tpu.stream.hash_agg import (
            HashAggExecutor as _A,
        )
        from risingwave_tpu.stream.watermark import (
            WatermarkFilterExecutor as _W,
        )

        old = self.sharded
        if new_n == old.n_shards:
            return
        keyed = old.keyed_frag.executors
        if not (keyed and isinstance(keyed[0], _A)
                and keyed[0].reconstructible_from_rows()):
            raise ValueError(
                "online rescale needs a two-phase keyed aggregation "
                "(partial -> exchange -> global); this job's keyed "
                "stage cannot be re-keyed (minput/distinct state or a "
                "non-agg head): next round"
            )
        if any(hasattr(ex, "deliver") for ex in keyed):
            # downstream rebuild re-emits every group — a sink would
            # re-deliver them as duplicates
            raise ValueError("online rescale of sink jobs: next round")
        agg = keyed[0]
        # 1. seal in-flight state at a barrier
        sealed = self.epoch.curr.value
        self.states, _ = old.flush(self.states, sealed)
        host = jax.device_get(self.states)
        n_local = len(old.local_frag.executors) if old.local_frag else 0

        # 2. extract live groups per OLD shard + the global watermark
        chunks = []
        for s in range(old.n_shards):
            st = jax.tree.map(lambda x: x[s], host)
            chunks.append(agg.extract_chunk(st[n_local]))
        wm_mins: dict[int, int] = {}
        for i, ex in enumerate(
            old.local_frag.executors if old.local_frag else []
        ):
            if isinstance(ex, _W):
                wm_mins[i] = min(
                    int(host[i].max_ts[s]) for s in range(old.n_shards)
                )

        # 3. fresh job on the new mesh (same executor descriptors)
        new = old.on_mesh(make_mesh(new_n))
        states = jax.device_get(new.init_states())

        # 4. route extracted rows by the SAME vnode map onto new shards
        import numpy as np

        @jax.jit
        def dest_of(chunk):
            keys = old.exchange_key_fn(chunk)
            return shard_of_vnode(compute_vnodes(keys), new_n)

        @jax.jit
        def apply_keyed(keyed_states, chunk):
            out, _ = new.keyed_frag._step_impl(keyed_states, chunk)
            return out

        per_shard = [jax.tree.map(lambda x: x[t], states)
                     for t in range(new_n)]
        for chunk in chunks:
            chunk = jax.tree.map(jnp.asarray, chunk)
            dests = np.asarray(dest_of(chunk))
            for t in range(new_n):
                keep = jnp.asarray((dests == t)) & chunk.valid
                if not bool(np.asarray(keep).any()):
                    continue
                sub = chunk.mask(keep)
                ks = tuple(per_shard[t][n_local:])
                ks = apply_keyed(ks, sub)
                per_shard[t] = tuple(per_shard[t][:n_local]) + tuple(ks)
        # watermark seeds
        for i, wm in wm_mins.items():
            for t in range(new_n):
                lst = list(per_shard[t])
                lst[i] = lst[i]._replace(
                    max_ts=jnp.asarray(wm, jnp.int64)
                )
                per_shard[t] = tuple(lst)

        restacked = jax.tree.map(
            lambda *xs: jnp.stack(xs), *per_shard
        )
        self.sharded = new
        self.states = new.place(restacked)
        # 5. first flush re-emits every group into the fresh downstream
        # states (TopN bands, MV) before anything is served
        self.states, _ = self.sharded.flush(self.states, sealed)
        # old-shape snapshots are invalid
        self._drop_shadow()
        self.checkpoints = []
