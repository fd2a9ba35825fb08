"""DAG streaming runtime: arbitrary fragment graphs under one barrier loop.

Reference counterparts:
- the stream fragmenter cuts any plan into a *graph* of fragments
  (src/frontend/src/stream_fragmenter/mod.rs:388), instantiated as
  actors wired by dispatch/exchange edges
  (src/stream/src/executor/dispatch.rs:62);
- merges align barriers at every fan-in
  (src/stream/src/executor/merge.rs:161, barrier_align.rs:44);
- MV-on-MV: a downstream job consumes the upstream MaterializeExecutor's
  output changelog.

TPU-first design (SURVEY.md §7.1): the DAG is *compiled*, not threaded.
Instead of one actor task per fragment connected by channels, the whole
reachable subgraph of a source becomes ONE jitted step program (XLA
fuses across fragment boundaries — a cascade of MVs costs the same as
one fused chain), and the whole graph's barrier crossing is ONE jitted
program.  Barrier alignment at fan-in is implicit: barriers are host
control flow between dispatches, so every node sees the same epoch
boundary by construction — the alignment buffers of ``merge.rs`` have
no analog because there is nothing to align.

Node inputs always reference earlier nodes (list order = topological
order), so in-order traversal is dataflow-correct.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from fractions import Fraction
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from risingwave_tpu.stream.fragment import (
    COUNTER_ATTRS,
    Fragment,
    JOIN_GAUGE_ATTRS,
    JOIN_TALLY_ATTRS,
    PROBE_ROUND_ATTRS,
    WM_NONE,
    WM_SAFE_FLOOR,
    collect_counters,
    watermark_scope,
)
from risingwave_tpu.stream.runtime import (
    BarrierLoop,
    deliver_sinks,
    restore_source,
)

from risingwave_tpu.parallel.exchange import (
    axis_max,
    axis_min,
    shard_map_nocheck,
)

#: a dataflow edge endpoint: ("source", name) or ("node", node_id)
Ref = tuple


@dataclass
class FragNode:
    """A fragment (executor chain) with one upstream input."""

    fragment: Fragment
    input: Ref

    def init_state(self):
        return self.fragment.init_states()


@dataclass
class JoinNode:
    """A two-input hash join (ref hash_join.rs:158 as a DAG vertex)."""

    join: Any
    left: Ref
    right: Ref

    def init_state(self):
        return self.join.init_state()


class DagJob(BarrierLoop):
    """A streaming job over an arbitrary DAG of fragments and joins.

    ``sources`` maps names to chunk readers; ``nodes`` is a topological
    list (a node's inputs only reference sources or earlier nodes).
    Dropped nodes become ``None`` tombstones so node ids stay stable for
    catalog references.
    """

    #: mesh axis name for sharded DAGs
    AXIS = "shard"

    def __init__(
        self,
        sources: dict[str, Any],
        nodes: list,
        name: str = "dag_job",
        checkpoint_frequency: int = 1,
        checkpoint_store=None,
        mesh=None,
        exchanges: dict | None = None,
        staged: bool = False,
    ):
        super().__init__(name, checkpoint_frequency, checkpoint_store)
        self.sources = dict(sources)
        self.nodes: list = list(nodes)
        #: sharded execution (ref: every stateful op is vnode-parallel,
        #: src/meta/src/stream/stream_graph/actor.rs:435): the whole
        #: reachable subgraph runs per-shard inside shard_map, with
        #: ``exchanges[(node_id, side)] -> key_fn`` marking the edges
        #: where chunks re-route to their key-owning shard via
        #: all_to_all (the reference's hash dispatchers)
        self.mesh = mesh
        self.exchanges = dict(exchanges or {})
        self.n_shards = int(mesh.devices.size) if mesh is not None else 1
        #: staged execution (meshless): chunks hop between PER-NODE
        #: jitted programs and join emission windows drain in HOST
        #: loops (one pending readback per probed chunk) instead of
        #: device while_loops.  The fused mode embeds each join's
        #: downstream subgraph inside its drain loop body — on deep
        #: multiway plans (TPC-H q2/q8/q9: 8-9 base tables) that
        #: nesting blows up XLA:CPU compile memory (observed LLVM
        #: OOM).  Staging is the reference's actor/exchange boundary:
        #: compile size is linear in plan size, at the cost of host
        #: hops — the right trade for wide analytic MVs.
        self._staged_hint = staged
        self.staged = False  # derived per-topology in _rebuild
        self._staged_progs: dict = {}
        #: n-round fused programs (one dispatch per n scheduling rounds;
        #: per-dispatch host overhead amortized n-fold), keyed by n
        self._fused_multi: dict[int, Any] = {}
        #: windows that could NOT run as one fused dispatch, by reason
        #: (observability: a silent degradation to per-chunk host
        #: dispatches is a throughput cliff — exported as
        #: ``dag_fused_fallback_total{reason}`` as it is counted)
        self.fused_fallbacks: dict[str, int] = {}
        #: host spill tiers, one list (a tier per shard) per spill-
        #: enabled agg site (node_idx, exec_idx), and their programs
        self._spill_tiers: dict = {}
        self._spill_progs: dict = {}
        self.states = self._init_states()
        self.counter_labels: list[str] = []
        self.counter_sides: dict[int, str] = {}
        self._rebuild()

    def _init_states(self):
        def one_shard(_=None):
            return tuple(
                n.init_state() if n is not None else None
                for n in self.nodes
            )

        if self.mesh is None:
            return one_shard()
        return self._place(jax.vmap(one_shard)(jnp.arange(self.n_shards)))

    def _sharding_spec(self):
        from jax.sharding import PartitionSpec as P
        return P(self.AXIS)

    # -- topology -------------------------------------------------------
    def _rebuild(self) -> None:
        """Recompute consumer maps + drop compiled programs (called after
        any topology change; programs re-jit lazily on next use)."""
        self._consumers: dict[Ref, list[int]] = {}
        for idx, node in enumerate(self.nodes):
            if node is None:
                continue
            refs = [node.input] if isinstance(node, FragNode) \
                else [node.left, node.right]
            for ref in refs:
                self._validate_ref(ref, idx)
                lst = self._consumers.setdefault(ref, [])
                # once per node even when both join sides share the ref
                # (a self-join): enqueue() already fans out per side
                if idx not in lst:
                    lst.append(idx)
            if hasattr(getattr(node, "join", None), "scope"):
                node.join.scope = f"HashJoin.{idx}"
        self._step_programs: dict[str, Any] = {}
        self._barrier_prog = None
        self._maintain_prog = None
        self._staged_progs = {}
        self._fused_multi = {}
        # staging is a property of the CURRENT topology: attach/merge
        # can grow a fused job past the depth where fused drain loops
        # blow up the compile — re-derive on every rebuild
        n_joins = sum(
            isinstance(n, JoinNode) for n in self.nodes if n is not None
        )
        self.staged = self.mesh is None and (
            getattr(self, "_staged_hint", False) or n_joins >= 4
        )
        self._pulls = self._compute_pulls()

    def _validate_ref(self, ref: Ref, at: int) -> None:
        kind, key = ref
        if kind == "source":
            if key not in self.sources:
                raise ValueError(f"node {at} references unknown source {key!r}")
        elif kind == "node":
            if not (0 <= key < at) or self.nodes[key] is None:
                raise ValueError(
                    f"node {at} must reference an earlier live node, got {key}"
                )
        else:
            raise ValueError(f"bad ref {ref!r}")

    def add_source(self, name: str, reader) -> None:
        if name in self.sources:
            raise ValueError(f"source {name!r} already attached")
        self.sources[name] = reader
        self._rebuild()

    def remove_sources(self, names: list[str]) -> None:
        """Detach sources (a dropped MV's private readers).  Refuses
        while any live node still consumes one."""
        for name in names:
            if self._consumers.get(("source", name)):
                raise ValueError(f"source {name!r} still has consumers")
            self.sources.pop(name, None)
        self._rebuild()

    def add_nodes(self, nodes: list) -> list[int]:
        """Attach new nodes (e.g. a cascaded MV's fragment); returns their
        ids.  Existing states are preserved; new nodes start empty —
        callers backfill upstream history explicitly (see
        ``backfill_node``)."""
        ids = []
        states = list(self.states)
        for n in nodes:
            self.nodes.append(n)
            if self.mesh is None:
                states.append(n.init_state())
            else:
                # sharded job: the new node's state gets the same
                # stacked-and-sharded layout as _init_states
                states.append(self._place(
                    jax.vmap(lambda _: n.init_state())(
                        jnp.arange(self.n_shards)
                    )
                ))
            ids.append(len(self.nodes) - 1)
        self.states = tuple(states)
        self._rebuild()
        return ids

    def remove_nodes(self, ids: list[int]) -> None:
        """Tombstone nodes (a dropped MV).  Refuses while live consumers
        remain — the reference likewise rejects dropping an MV with
        dependents."""
        drop = set(ids)
        for idx, node in enumerate(self.nodes):
            if node is None or idx in drop:
                continue
            refs = [node.input] if isinstance(node, FragNode) \
                else [node.left, node.right]
            for kind, key in refs:
                if kind == "node" and key in drop:
                    raise ValueError(
                        f"node {key} still feeds node {idx} (drop dependents "
                        "first)"
                    )
        states = list(self.states)
        for i in drop:
            self.nodes[i] = None
            states[i] = None
        self.states = tuple(states)
        for key in [k for k in self.exchanges if k[0] in drop]:
            del self.exchanges[key]
        self._rebuild()

    def _shadow_shard_rows(self) -> int | None:
        return self.n_shards if self.mesh is not None else None

    def downstream_closure(self, ref: Ref,
                           through_joins: bool = True) -> list[int]:
        """All node ids transitively consuming ``ref`` (topo order).

        With ``through_joins=False`` the traversal includes a JoinNode
        consumer but does not continue past it (a join's downstream sees
        the MIN of both inputs' watermarks, not either one alone)."""
        seen = set()
        frontier = [ref]
        while frontier:
            r = frontier.pop()
            for idx in self._consumers.get(r, ()):
                if idx in seen:
                    continue
                seen.add(idx)
                if through_joins or isinstance(self.nodes[idx], FragNode):
                    frontier.append(("node", idx))
        return sorted(seen)

    # -- chunk path -----------------------------------------------------
    def _propagate(self, new_states: list, injections) -> None:
        """Push chunks through the DAG in topological order.

        ``injections`` is a list of (ref, chunk).  Mutates new_states.
        A source feeding both sides of a join (self-join) delivers to
        the left side first, then the right — one deterministic order,
        like the reference's dispatcher duplicating a chunk."""
        inbox: dict[int, list] = {}

        def enqueue(ref, chunk):
            for idx in self._consumers.get(ref, ()):
                node = self.nodes[idx]
                if isinstance(node, FragNode):
                    inbox.setdefault(idx, []).append(
                        (self._exchange(idx, None, chunk), None)
                    )
                else:
                    if node.left == ref:
                        inbox.setdefault(idx, []).append(
                            (self._exchange(idx, "left", chunk), "left")
                        )
                    if node.right == ref:
                        inbox.setdefault(idx, []).append(
                            (self._exchange(idx, "right", chunk), "right")
                        )

        for ref, chunk in injections:
            enqueue(ref, chunk)
        for idx in range(len(self.nodes)):
            node = self.nodes[idx]
            if node is None or idx not in inbox:
                continue
            for chunk, side in inbox[idx]:
                if isinstance(node, FragNode):
                    new_states[idx], out = node.fragment._step_impl(
                        new_states[idx], chunk
                    )
                    if out is not None:
                        enqueue(("node", idx), out)
                else:
                    self._apply_join_windowed(new_states, idx, chunk,
                                              side, enqueue)

    def _exchange(self, idx: int, side, chunk):
        """Route a chunk across the vnode exchange on a marked edge
        (sharded DAGs only; linear DAGs deliver in place)."""
        fn = self.exchanges.get((idx, side))
        if fn is None or self.mesh is None:
            return chunk
        from risingwave_tpu.parallel.exchange import shuffle_chunk
        return shuffle_chunk(chunk, fn(chunk), self.AXIS, self.n_shards)

    def _apply_join_windowed(self, new_states: list, idx: int, chunk,
                             side: str, enqueue) -> None:
        """Drive a join with WINDOWED emission: the chunk's emission
        windows (one, or many under a high-amplification probe) drain
        through the downstream subgraph inside a device ``while_loop``
        — matches dropped by a fixed out buffer in the old design now
        always reach downstream (ref hash_join.rs chunk-sized yielding
        under amplification)."""
        node = self.nodes[idx]
        join = node.join
        if not hasattr(join, "apply_begin"):
            new_states[idx], out = join.apply(new_states[idx], chunk, side)
            if out is not None:
                enqueue(("node", idx), out)
            return
        new_states[idx], pending = join.apply_begin(
            new_states[idx], chunk, side
        )
        if not self._consumers.get(("node", idx)):
            return  # terminal join: emissions have no consumers
        build_rows = join.build_rows_of(new_states[idx], side)
        max_w = join.max_windows(chunk.capacity)
        if max_w <= 1:
            # one window holds whatever a chunk can stage: it propagates
            # directly (NOT via the inbox), in emission order
            first, probe_bound = join.emit_window(
                build_rows, pending, jnp.int32(0), side
            )
            new_states[idx] = new_states[idx]._replace(
                emit_overflow=new_states[idx].emit_overflow + probe_bound
            )
            self._propagate(new_states, [(("node", idx), first)])
            return

        # windows leave in emission order — a +pair in window 0 must
        # land before its -pair in window 1 — from ONE loop whose first
        # round is window 0, so the program holds the join's downstream
        # subgraph once.  Sharded: the loop body may contain collectives
        # (downstream exchanges), so every shard must run the same trip
        # count — bound by the max pending across shards (extra windows
        # emit empty chunks, which are harmless)
        total = pending.total
        if self.mesh is not None:
            total = axis_max(total, self.AXIS)

        def cond(carry):
            sts, w = carry
            return (w == 0) | ((w * join.out_capacity < total)
                               & (w < max_w))

        def body(carry):
            sts, w = carry
            window, probe_bound = join.emit_window(
                build_rows, pending, w, side
            )
            lst = list(sts)
            lst[idx] = lst[idx]._replace(
                emit_overflow=lst[idx].emit_overflow + probe_bound
            )
            self._propagate(lst, [(("node", idx), window)])
            return tuple(lst), w + 1

        sts, _ = jax.lax.while_loop(
            cond, body, (tuple(new_states), jnp.int32(0))
        )
        new_states[:] = list(sts)

    def _make_step(self, src_name: str):
        reader = self.sources[src_name]
        fused = hasattr(reader, "impl") and hasattr(reader, "next_base")
        if self.mesh is not None:
            spec = self._sharding_spec()
            if fused:
                def body(states, k0):
                    local = jax.tree.map(lambda x: x[0], states)
                    new_states = list(local)
                    with jax.named_scope("gen"):
                        chunk = reader.impl(k0[0], reader.cap)
                    self._propagate(
                        new_states, [(("source", src_name), chunk)]
                    )
                    return jax.tree.map(
                        lambda x: x[None], tuple(new_states)
                    )
            else:
                # host-chunk source (DML tables): the chunk arrives
                # stacked [n_shards, ...] with rows on shard 0 only;
                # the first exchange edge (join input) re-routes them
                # to their key owners via all_to_all — the reference's
                # dispatcher on a singleton source fragment
                def body(states, chunk):
                    local = jax.tree.map(lambda x: x[0], states)
                    lchunk = jax.tree.map(lambda x: x[0], chunk)
                    new_states = list(local)
                    self._propagate(
                        new_states, [(("source", src_name), lchunk)]
                    )
                    return jax.tree.map(
                        lambda x: x[None], tuple(new_states)
                    )

            # donated like the linear path: the mesh-stacked state
            # updates in place, no per-step allocation churn
            prog = jax.jit(shard_map_nocheck(
                body, mesh=self.mesh, in_specs=(spec, spec),
                out_specs=spec,
            ), donate_argnums=(0,))
            return prog, fused
        if fused:
            # traceable source: generation fuses into the step program
            def fn(states, k0):
                with jax.named_scope("gen"):
                    chunk = reader.impl(k0, reader.cap)
                new_states = list(states)
                self._propagate(new_states, [(("source", src_name), chunk)])
                return tuple(new_states)
        else:
            def fn(states, chunk):
                new_states = list(states)
                self._propagate(new_states, [(("source", src_name), chunk)])
                return tuple(new_states)
        return jax.jit(fn, donate_argnums=(0,)), fused

    # -- staged execution (host-hop scheduling) -------------------------
    def _staged_prog(self, key, builder, donate: bool = True):
        """Per-node jitted program cache.  ``donate`` donates arg 0
        (the state, reassigned immediately after every call) — emit
        programs must NOT donate (the same state feeds every window)."""
        prog = self._staged_progs.get(key)
        if prog is None:
            prog = jax.jit(
                builder(), donate_argnums=(0,) if donate else ()
            )
            self._staged_progs[key] = prog
        return prog

    def _staged_deliver(self, injections: list) -> None:
        """Host-level chunk propagation, DEPTH-FIRST: each chunk flows
        all the way downstream before the next emission window is even
        gathered — breadth-first queuing held every cascaded window in
        memory at once (a 7-join chain OOM'd the host).  Per-node
        dispatches; join windows drain in host loops with ONE pending
        readback per probed chunk."""
        for ref, chunk in injections:
            for idx in self._consumers.get(ref, ()):
                node = self.nodes[idx]
                if node is None:
                    continue
                if isinstance(node, FragNode):
                    prog = self._staged_prog(
                        ("frag", idx),
                        lambda node=node: node.fragment._step_impl,
                    )
                    st, out = prog(self.states[idx], chunk)
                    self._set_state(idx, st)
                    if out is not None:
                        self._staged_deliver([(("node", idx), out)])
                else:
                    if node.left == ref:
                        self._staged_join(idx, chunk, "left")
                    if node.right == ref:
                        self._staged_join(idx, chunk, "right")

    def _set_state(self, idx: int, st) -> None:
        lst = list(self.states)
        lst[idx] = st
        self.states = tuple(lst)

    def _staged_join(self, idx: int, chunk, side: str) -> None:
        node = self.nodes[idx]
        join = node.join
        if not hasattr(join, "apply_begin"):
            prog = self._staged_prog(
                ("japply", idx, side),
                lambda join=join, side=side:
                    lambda st, c: join.apply(st, c, side),
            )
            st, out = prog(self.states[idx], chunk)
            self._set_state(idx, st)
            if out is not None:
                self._staged_deliver([(("node", idx), out)])
            return
        begin = self._staged_prog(
            ("jbegin", idx, side),
            lambda join=join, side=side:
                lambda st, c: join.apply_begin(st, c, side),
        )
        st, pending = begin(self.states[idx], chunk)
        self._set_state(idx, st)
        if not self._consumers.get(("node", idx)):
            return
        emit = self._staged_prog(
            ("jemit", idx, side),
            lambda join=join, side=side:
                lambda st, pend, w: join.emit_window(
                    join.build_rows_of(st, side), pend, w, side
                ),
            donate=False,
        )
        total = int(pending.total)  # the one host readback
        n_w = max(1, -(-total // join.out_capacity))
        n_w = min(n_w, join.max_windows(chunk.capacity))
        for w in range(n_w):
            out, probe_bound = emit(
                self.states[idx], pending, jnp.int32(w)
            )
            self._set_state(idx, self.states[idx]._replace(
                emit_overflow=self.states[idx].emit_overflow
                + probe_bound
            ))
            # window w flows ALL the way down before w+1 is gathered
            self._staged_deliver([(("node", idx), out)])

    def _staged_flush_all(self, sealed) -> None:
        for idx, node in enumerate(self.nodes):
            if not isinstance(node, FragNode):
                continue
            frag = node.fragment
            flush = self._staged_prog(
                ("flush", idx),
                lambda frag=frag: frag._flush_impl,
            )
            rounds = frag.MAX_DRAIN_ROUNDS + 64
            for _ in range(rounds):
                st, outs = flush(self.states[idx], sealed)
                self._set_state(idx, st)
                for out in outs:
                    self._staged_deliver([(("node", idx), out)])
                if not frag.has_pending_protocol():
                    break
                pend = self._staged_prog(
                    ("pending", idx),
                    lambda frag=frag: frag.pending_total,
                )
                if int(pend(self.states[idx])) == 0:
                    break

    def _staged_barrier(self, sealed):
        """The barrier crossing, staged: flush → watermarks → EOWC
        flush → clean + counters (same order as _barrier_impl)."""
        self._staged_flush_all(sealed)

        def wm_tail(states):
            new_states = list(states)
            self._wm_all(new_states)
            return tuple(new_states)

        prog_wm = self._staged_prog(("wm_tail",), lambda: wm_tail)
        self.states = prog_wm(self.states)
        self._staged_flush_all(sealed)

        def clean_tail(states):
            new_states = list(states)
            self._clean_joins(new_states)
            return tuple(new_states), self._collect_counters(new_states)

        prog_cl = self._staged_prog(("clean_tail",), lambda: clean_tail)
        self.states, counters = prog_cl(self.states)
        return counters

    def run_chunk(self, src_name: str) -> int:
        """Pull one chunk from one source through its reachable subgraph."""
        if self.paused:
            return 0
        if self.staged:
            reader = self.sources[src_name]
            chunk = reader.next_chunk()
            self._staged_deliver([(("source", src_name), chunk)])
            return chunk.capacity
        if src_name not in self._step_programs:
            self._step_programs[src_name] = self._make_step(src_name)
        prog, fused = self._step_programs[src_name]
        reader = self.sources[src_name]
        if self.mesh is not None:
            if not fused:
                chunk = reader.next_chunk()
                host = jax.device_get(chunk)
                empty = jax.tree.map(np.zeros_like, host)
                stacked = jax.tree.map(
                    lambda *xs: np.stack(xs),
                    *([host] + [empty] * (self.n_shards - 1)),
                )
                self.states = prog(self.states, self._place(stacked))
                return chunk.capacity
            # one cap-stride ordinal block per shard (split readers own
            # disjoint ordinal ranges, like the reference's source
            # splits)
            k0 = jnp.asarray(
                [reader.next_base() for _ in range(self.n_shards)],
                jnp.int64,
            )
            self.states = prog(self.states, k0)
            return reader.cap * self.n_shards
        if fused:
            self.states = prog(self.states, jnp.int64(reader.next_base()))
            return reader.cap
        chunk = reader.next_chunk()
        self.states = prog(self.states, chunk)
        return chunk.capacity

    def _compute_pulls(self) -> list[tuple[str, int]]:
        """Chunks pulled per scheduling round per source: sources whose
        rows sweep event time faster pull proportionally fewer chunks so
        no watermark runs unboundedly ahead (ref: per-source rate
        limits; BinaryJob.chunk_ratio generalized to N sources)."""
        names = list(self.sources)
        eprs = []
        for n in names:
            epr = getattr(self.sources[n], "events_per_row", None)
            if epr is None:
                return [(n, 1) for n in names]
            eprs.append(Fraction(epr))
        inv = [1 / e for e in eprs]
        lo = min(inv)
        pulls = []
        for n, f in zip(names, inv):
            ratio = f / lo
            if ratio.denominator != 1 or ratio.numerator > 16:
                return [(n, 1) for n in names]
            pulls.append((n, int(ratio)))
        return pulls

    def chunk_round(self) -> int:
        """One scheduling round: pull each source by its pacing ratio."""
        rows = 0
        for name, k in self._pulls:
            for _ in range(k):
                n = self.run_chunk(name)
                self._pulled(name, n)
                rows += n
        return rows

    def run_chunks(self, n: int) -> int:
        """n scheduling rounds in ONE dispatch when every source is
        traceable.

        The linear runtime's multi-chunk fusion (StreamingJob.
        run_chunks, the q1 attribution fix) extended to DAGs: a
        ``fori_loop`` over n rounds — each round generating and
        propagating every source's chunks through the whole reachable
        subgraph, join emission windows draining in the loop body's
        device ``while_loop`` — amortizes the per-dispatch host cost
        n-fold.  For q8's binary-join DAG that cost was 2n dispatches
        per barrier (one per source chunk); now it is one.

        Sharded meshes fuse too (``_run_chunks_mesh``): the whole
        barrier-to-barrier window runs as ONE ``shard_map`` program,
        exchanges (all_to_all) inside the loop body, mesh-stacked
        state donated.  Falls back to per-chunk dispatch only for
        host-chunk sources and staged plans (whose compile size must
        stay linear) — each fallback is counted by reason
        (``fused_fallbacks``) so the degradation is observable."""
        if self.paused or n <= 0:
            return 0
        reason = self._unfused_reason()
        if reason is not None or n == 1:
            if reason is not None and n > 1:
                count = self.fused_fallbacks.get(reason, 0) + 1
                self.fused_fallbacks[reason] = count
                if self.metrics is not None:
                    self.metrics.set_gauge(
                        "dag_fused_fallback_total", count,
                        job=self.name, reason=reason,
                    )
            rows = 0
            for _ in range(n):
                rows += self.chunk_round()
            return rows
        if self.mesh is not None:
            return self._run_chunks_mesh(n)
        prog = self._multi_prog(n)
        k0s = {}
        rows = 0
        for nm, k in self._pulls:
            reader = self.sources[nm]
            # next_base() consumed one cap block; skip the other n*k-1
            k0s[nm] = jnp.int64(reader.next_base())
            reader.offset += reader.cap * (n * k - 1)
            self._pulled(nm, reader.cap * n * k)
            rows += reader.cap * n * k
        self.states = prog(self.states, k0s)
        return rows

    def _unfused_reason(self) -> str | None:
        """Why a window cannot be one fused dispatch, else None."""
        if not self.sources:
            return "no_sources"
        if self.staged:
            return "staged"
        if not all(hasattr(src, "impl") and hasattr(src, "next_base")
                   for src in self.sources.values()):
            return "host_chunk_source"
        return None

    def window_one_dispatch(self, n: int) -> bool:
        return n > 1 and self._unfused_reason() is None

    def _multi_prog(self, n: int):
        """The jitted n-round window program (linear or mesh), cached
        by n."""
        prog = self._fused_multi.get(n)
        if prog is not None:
            return prog
        pulls = list(self._pulls)
        readers = dict(self.sources)
        if self.mesh is None:
            strides = {
                nm: readers[nm].cap * getattr(readers[nm], "num_splits", 1)
                for nm, _ in pulls
            }

            def _multi(states, k0s):
                def body(i, st):
                    new_states = list(st)
                    for nm, k in pulls:
                        for rep in range(k):
                            base = k0s[nm] + (i * k + rep) * strides[nm]
                            with jax.named_scope("gen"):
                                chunk = readers[nm].impl(
                                    base, readers[nm].cap)
                            self._propagate(
                                new_states, [(("source", nm), chunk)]
                            )
                    return tuple(new_states)

                return jax.lax.fori_loop(0, n, body, states)

            prog = jax.jit(_multi, donate_argnums=(0,))
        else:
            spec = self._sharding_spec()

            def body(states, *base_cols):
                local = jax.tree.map(lambda x: x[0], states)

                def round_body(i, st):
                    new_states = list(st)
                    for si, (nm, k) in enumerate(pulls):
                        for rep in range(k):
                            b0 = base_cols[si][0, i * k + rep]
                            with jax.named_scope("gen"):
                                chunk = readers[nm].impl(
                                    b0, readers[nm].cap)
                            self._propagate(
                                new_states, [(("source", nm), chunk)]
                            )
                    return tuple(new_states)

                out = jax.lax.fori_loop(0, n, round_body, tuple(local))
                return jax.tree.map(lambda x: x[None], out)

            prog = jax.jit(shard_map_nocheck(
                body, mesh=self.mesh,
                in_specs=(spec,) + (spec,) * len(pulls),
                out_specs=spec,
            ), donate_argnums=(0,))
        # bounded cache: chunks_per_barrier is runtime-mutable and
        # each distinct n compiles a program — keep the newest few
        if len(self._fused_multi) >= 4:
            self._fused_multi.pop(next(iter(self._fused_multi)))
        self._fused_multi[n] = prog
        return prog

    def _run_chunks_mesh(self, n: int) -> int:
        """The sharded fused window: n scheduling rounds — per-shard
        source generation, every exchange collective, join emission
        drains — as ONE ``shard_map``-ed ``fori_loop`` program between
        barriers, with the mesh-stacked state donated.

        Per-shard base ordinals come in as one ``[n_shards, n*k]``
        int64 column per source, computed host-side by the SAME
        ``next_base()`` sequence the per-chunk path consumes — the
        generated streams are ordinal-identical to n per-chunk rounds,
        so fused and unfused runs stay byte-identical."""
        prog = self._multi_prog(n)
        rows = 0
        base_cols = []
        for nm, k in self._pulls:
            reader = self.sources[nm]
            arr = np.empty((n * k, self.n_shards), np.int64)
            for i in range(n * k):
                for s in range(self.n_shards):
                    arr[i, s] = reader.next_base()
            base_cols.append(self._place(jnp.asarray(arr.T)))
            self._pulled(nm, reader.cap * n * k * self.n_shards)
            rows += reader.cap * n * k * self.n_shards
        self.states = prog(self.states, *base_cols)
        return rows

    # -- barrier program ------------------------------------------------
    def _flush_node(self, new_states: list, idx: int, epoch,
                    after_watermarks: bool = False) -> None:
        """Flush one fragment node; emissions cross downstream nodes.
        Drains on device while the node reports pending output, in ONE
        loop whose first round is the flush itself: the program holds
        the node's downstream subgraph once, not once for the flush and
        once more for its drain.

        ``after_watermarks`` is the barrier's second pass, for what the
        new watermark closed (EOWC).  A node whose flushing executors
        all say what they have pending (``pending_flush``) runs it only
        while something is pending, as ``Fragment._drain_impl`` does;
        where none of them emits on window close, nothing can be, so
        the first pass takes both passes' rounds and the second is not
        compiled at all."""
        node = self.nodes[idx]
        frag = node.fragment
        flushing = [ex for ex in frag.executors if ex.emits_on_flush]
        says_pending = all(hasattr(ex, "pending_flush") for ex in flushing)
        eowc = any(getattr(ex, "emit_on_window_close", False)
                   for ex in flushing)
        one_pass = says_pending and not eowc
        if after_watermarks and (one_pass or not flushing):
            return
        if not frag.has_pending_protocol():
            st, outs = frag._flush_impl(new_states[idx], epoch)
            new_states[idx] = st
            for out in outs:
                self._propagate(new_states, [(("node", idx), out)])
            return
        rounds = frag.MAX_DRAIN_ROUNDS + 1
        if one_pass:
            rounds *= 2

        def _more(states_idx):
            # sharded: the drain body may cross exchanges (collectives),
            # so shards must agree on the trip count — any shard with
            # pending keeps every shard in the loop (idle shards flush
            # empty, which is harmless)
            p = frag.pending_total(states_idx)
            if self.mesh is not None:
                p = axis_max(p, self.AXIS)
            return p > 0

        def cond(carry):
            sts, it, more = carry
            return more & (it < rounds)

        def body(carry):
            sts, it, _ = carry
            lst = list(sts)
            st2, outs2 = frag._flush_impl(lst[idx], epoch)
            lst[idx] = st2
            for out in outs2:
                self._propagate(lst, [(("node", idx), out)])
            return tuple(lst), it + 1, _more(lst[idx])

        # the first pass always flushes (a flush is more than its
        # pending rows: it takes the extremes again, moves ``prev``);
        # the pass after the watermarks only what is pending
        first = _more(new_states[idx]) if after_watermarks and says_pending \
            else jnp.asarray(True)
        sts, _, _ = jax.lax.while_loop(
            cond, body, (tuple(new_states), jnp.int32(0), first),
        )
        new_states[:] = list(sts)

    def _flush_all(self, new_states: list, epoch,
                   after_watermarks: bool = False) -> None:
        for idx, node in enumerate(self.nodes):
            if isinstance(node, FragNode):
                self._flush_node(new_states, idx, epoch, after_watermarks)

    def _node_watermarks(self, new_states: list, idx: int):
        """(Watermark, has) pairs produced by a fragment node's wm
        filters (device scalars)."""
        from risingwave_tpu.stream.message import Watermark
        from risingwave_tpu.stream.watermark import WatermarkFilterExecutor

        node = self.nodes[idx]
        out = []
        for i, ex in enumerate(node.fragment.executors):
            if not isinstance(ex, WatermarkFilterExecutor):
                continue
            raw = new_states[idx][i].max_ts
            if self.mesh is not None:
                # global watermark = min over shards (the reference's
                # min-of-upstream-actors alignment, as ONE ICI gather)
                raw = axis_min(raw, self.AXIS)
            has = raw != WM_NONE
            val = jnp.where(has, raw - ex.delay_us, jnp.int64(WM_SAFE_FLOOR))
            out.append((Watermark(ex.ts_col, val), has))
        return out

    def _wm_all(self, new_states: list) -> None:
        """Propagate watermarks: within each fragment, then across node
        boundaries to downstream FRAGMENT nodes (cascaded MVs).  Joins
        block propagation — their two-sided min semantics are handled by
        ``_clean_joins``."""
        for idx, node in enumerate(self.nodes):
            if not isinstance(node, FragNode):
                continue
            new_states[idx] = node.fragment._wm_impl(
                new_states[idx],
                axis=self.AXIS if self.mesh is not None else None,
            )
            for wm, _ in self._node_watermarks(new_states, idx):
                for j in self.downstream_closure(("node", idx),
                                                 through_joins=False):
                    dn = self.nodes[j]
                    if not isinstance(dn, FragNode):
                        continue
                    lst = list(new_states[j])
                    for k, ex2 in enumerate(dn.fragment.executors):
                        with watermark_scope(k, ex2):
                            lst[k] = ex2.on_watermark(lst[k], wm)
                    new_states[j] = tuple(lst)

    def _upstream_wm(self, new_states: list, ref: Ref, src_col: int):
        """Walk a join input upstream to its wm filter for ``src_col``;
        (value, has) device scalars or None when absent."""
        from risingwave_tpu.stream.watermark import WatermarkFilterExecutor

        while True:
            kind, key = ref
            if kind == "source":
                return None
            node = self.nodes[key]
            if not isinstance(node, FragNode):
                return None  # joins don't forward watermarks (yet)
            for i, ex in enumerate(node.fragment.executors):
                if isinstance(ex, WatermarkFilterExecutor) \
                        and ex.ts_col == src_col:
                    raw = new_states[key][i].max_ts
                    if self.mesh is not None:
                        raw = axis_min(raw, self.AXIS)
                    has = raw != WM_NONE
                    val = jnp.where(
                        has, raw - ex.delay_us, jnp.int64(WM_SAFE_FLOOR)
                    )
                    return val, has
            ref = node.input

    def _clean_joins(self, new_states: list) -> None:
        """Watermark-driven join state cleaning: each side with a rule
        (``HashJoinExecutor.clean_rule``: a window join key, a time band
        between the sides) retires its rows below the MIN watermark
        across the inputs its rules name, less the rule's lag — a build
        row serves the other side's future probes.  The tombstones it
        leaves are given back by the maintenance pass
        (``_maintain_impl``), as the aggregates' are."""
        for idx, node in enumerate(self.nodes):
            if not isinstance(node, JoinNode) \
                    or not hasattr(node.join, "clean_rule"):
                continue
            join = node.join
            refs = {"left": node.left, "right": node.right}
            rules = {side: join.clean_rule(side)
                     for side in ("left", "right")}
            wanted = set()
            for side, rule in rules.items():
                if rule is None:
                    continue
                other = "right" if side == "left" else "left"
                if rule.src_col is not None:
                    wanted.add((side, rule.src_col))
                if rule.other_src_col is not None:
                    wanted.add((other, rule.other_src_col))
            wms = [self._upstream_wm(new_states, refs[side], col)
                   for side, col in sorted(wanted)]
            if not wms or any(wm is None for wm in wms):
                continue
            has_all = wms[0][1]
            min_wm = wms[0][0]
            for val, has in wms[1:]:
                has_all = has_all & has
                min_wm = jnp.minimum(min_wm, val)

            def do_clean(jstate, join=join, min_wm=min_wm, rules=rules):
                for side, rule in rules.items():
                    if rule is not None:
                        jstate = join.clean_below(
                            jstate, side, min_wm - rule.lag_us
                        )
                return jstate

            new_states[idx] = jax.lax.cond(
                has_all, do_clean, lambda j: j, new_states[idx]
            )

    def _collect_counters(self, new_states: list):
        """The barrier's counters vector; its labels, and for a join
        side's tallies and levels the side, are left on the job
        (``counter_labels``, ``counter_sides``) as the program is
        traced."""
        labels: list[str] = []
        sides: dict[int, str] = {}
        vals: list[jnp.ndarray] = []
        for idx, node in enumerate(self.nodes):
            if node is None:
                continue
            if isinstance(node, FragNode):
                sub_labels, sub = collect_counters(
                    node.fragment.executors, new_states[idx]
                )
                labels.extend(f"n{idx}.{x}" for x in sub_labels)
                if sub.shape[0]:
                    vals.append(sub)
                continue
            jstate = new_states[idx]
            if not hasattr(jstate, "left"):
                # two-input non-join node (dynamic filter): counters
                # live flat on the state itself
                for attr in COUNTER_ATTRS:
                    if hasattr(jstate, attr):
                        labels.append(f"n{idx}.dynfilter.{attr}")
                        vals.append(
                            getattr(jstate, attr).astype(jnp.int64)[None]
                        )
                continue
            for side_name in ("left", "right"):
                s = getattr(jstate, side_name)
                for attr in COUNTER_ATTRS + JOIN_TALLY_ATTRS \
                        + JOIN_GAUGE_ATTRS + tuple(PROBE_ROUND_ATTRS):
                    if hasattr(s, attr):
                        if attr not in COUNTER_ATTRS:
                            sides[len(labels)] = side_name
                        labels.append(f"n{idx}.join.{side_name}.{attr}")
                        vals.append(getattr(s, attr).astype(jnp.int64)[None])
            labels.append(f"n{idx}.join.emit_overflow")
            vals.append(jstate.emit_overflow.astype(jnp.int64)[None])
        self.counter_labels = labels
        self.counter_sides = sides
        return jnp.concatenate(vals) if vals \
            else jnp.zeros((0,), jnp.int64)

    def _barrier_impl(self, states, epoch):
        new_states = list(states)
        self._flush_all(new_states, epoch)
        # watermarks advance, then a second flush pass emits rows the
        # new watermark closed (EOWC) at THIS barrier
        self._wm_all(new_states)
        self._flush_all(new_states, epoch, after_watermarks=True)
        self._clean_joins(new_states)
        return tuple(new_states), self._collect_counters(new_states)

    def _make_barrier_prog(self):
        if self.mesh is None:
            return jax.jit(self._barrier_impl, donate_argnums=(0,))
        from jax.sharding import PartitionSpec as P
        spec = self._sharding_spec()

        def body(states, epoch):
            local = jax.tree.map(lambda x: x[0], states)
            new_states, counters = self._barrier_impl(
                tuple(local), epoch[0]
            )
            # shard-summed counters, replicated (ONE host readback later)
            counters = jax.lax.psum(counters, self.AXIS)
            return jax.tree.map(lambda x: x[None], new_states), counters

        return jax.jit(shard_map_nocheck(
            body, mesh=self.mesh, in_specs=(spec, spec),
            out_specs=(spec, P()),
        ), donate_argnums=(0,))

    def _cross_barrier(self, epoch_val) -> None:
        if self.staged:
            self._counters = self._staged_barrier(epoch_val)
            return
        if self._barrier_prog is None:
            self._barrier_prog = self._make_barrier_prog()
        if self.mesh is not None:
            epoch_val = jnp.full((self.n_shards,), epoch_val, jnp.int64)
        self.states, self._counters = self._barrier_prog(
            self.states, epoch_val
        )

    # -- maintenance ----------------------------------------------------
    def _maintain_impl(self, states):
        new_states = list(states)
        for idx, node in enumerate(self.nodes):
            if isinstance(node, FragNode):
                new_states[idx] = node.fragment._maintain_impl(
                    new_states[idx]
                )
            elif isinstance(node, JoinNode) \
                    and hasattr(node.join, "maybe_rehash"):
                new_states[idx] = node.join.maybe_rehash(new_states[idx])
        return tuple(new_states)

    def _make_maintain_prog(self):
        if self.mesh is None:
            return jax.jit(self._maintain_impl, donate_argnums=(0,))
        spec = self._sharding_spec()

        def body(states):
            local = jax.tree.map(lambda x: x[0], states)
            out = self._maintain_impl(tuple(local))
            return jax.tree.map(lambda x: x[None], out)

        return jax.jit(shard_map_nocheck(
            body, mesh=self.mesh, in_specs=(spec,), out_specs=spec,
        ), donate_argnums=(0,))

    def _run_maintain(self) -> None:
        if self._maintain_prog is None:
            self._maintain_prog = self._make_maintain_prog()
        self.states = self._maintain_prog(self.states)

    # -- checkpoint / recovery ------------------------------------------
    def _deliver_all_sinks(self, epoch_val) -> None:
        new_states = list(self.states)
        for idx, node in enumerate(self.nodes):
            if isinstance(node, FragNode):
                new_states[idx] = deliver_sinks(
                    node.fragment, new_states[idx], epoch_val
                )
        self.states = tuple(new_states)

    def _place(self, tree):
        """A stacked ``[n_shards, ...]`` tree (states, chunks) pinned
        to the mesh layout: loaded trees are host arrays and shadow
        restores land on the default device."""
        if self.mesh is None:
            return super()._place(tree)
        from jax.sharding import NamedSharding
        return jax.device_put(
            tree, NamedSharding(self.mesh, self._sharding_spec())
        )

    def _source_state(self) -> dict:
        return {
            name: (src.state() if hasattr(src, "state") else {})
            for name, src in self.sources.items()
        }

    def _restore_sources(self, state: dict) -> None:
        for name, src in self.sources.items():
            restore_source(src, state.get(name, {}))

    def _reset_sources(self) -> None:
        for src in self.sources.values():
            if hasattr(src, "offset"):
                src.offset = 0

    # -- spill-to-host (stream/spill.py) --------------------------------
    def _iter_spill_tiers(self):
        """One tier per shard per site; snapshot keys carry the shard
        index."""
        for idx, j, ex in self._spill_sites():
            self._ensure_spill_tier(idx, j, ex)
            for s, tier in enumerate(self._spill_tiers[(idx, j)]):
                yield (idx, j, s), self._spill_key(idx, j, s), tier

    def _spill_sites(self):
        """[(node_idx, exec_idx, executor)] of spill-enabled aggs."""
        out = []
        for idx, node in enumerate(self.nodes):
            if not isinstance(node, FragNode):
                continue
            for j, ex in enumerate(node.fragment.executors):
                if getattr(ex, "spill_ring", 0):
                    out.append((idx, j, ex))
        return out

    def _spill_key(self, idx: int, j: int, s: int) -> str:
        # keyed by the checkpoint LINEAGE (== name for whole jobs;
        # a partitioned DagJob's spill follows its partition lineage)
        base = f"{self.ckpt_key}@spill{idx}_{j}"
        return base if self.n_shards == 1 else f"{base}_s{s}"

    def _ensure_spill_tier(self, idx: int, j: int, ex) -> None:
        key = (idx, j)
        if key in self._spill_tiers:
            return
        from risingwave_tpu.stream.spill import AggSpillTier
        # one host tier PER SHARD: the exchange partitions keys by
        # vnode, so a shard's overflow groups live in that shard's
        # tier and the structural-ownership invariant holds per shard
        self._spill_tiers[key] = [
            AggSpillTier(
                ex, getattr(ex, "spill_table_size", ex.table_size * 8)
            )
            for _ in range(self.n_shards)
        ]

        def drain_local(states, idx=idx, j=j, ex=ex):
            new_states = list(states)
            node_states = list(new_states[idx])
            node_states[j], chunk = ex.drain_spill(node_states[j])
            new_states[idx] = tuple(node_states)
            return tuple(new_states), chunk

        def inject_local(states, chunk, idx=idx, j=j):
            new_states = list(states)
            node = self.nodes[idx]
            node_states = list(new_states[idx])
            cur = chunk
            for k in range(j + 1, len(node.fragment.executors)):
                if cur is None:
                    break
                node_states[k], cur = \
                    node.fragment.executors[k].apply(
                        node_states[k], cur
                    )
            new_states[idx] = tuple(node_states)
            if cur is not None:
                self._propagate(new_states, [(("node", idx), cur)])
            return tuple(new_states)

        if self.mesh is None:
            self._spill_progs[key] = (
                jax.jit(drain_local, donate_argnums=(0,)),
                jax.jit(inject_local, donate_argnums=(0,)),
            )
            return

        # mesh: the SAME per-shard bodies run inside shard_map — the
        # inject path may cross exchanges (all_to_all), which is valid
        # only in the sharded program (mirrors _maintain's pattern)
        spec = self._sharding_spec()

        def drain_body(states):
            local = jax.tree.map(lambda x: x[0], states)
            out_states, chunk = drain_local(tuple(local))
            return (
                jax.tree.map(lambda x: x[None], tuple(out_states)),
                jax.tree.map(lambda x: x[None], chunk),
            )

        def inject_body(states, chunk):
            local = jax.tree.map(lambda x: x[0], states)
            lchunk = jax.tree.map(lambda x: x[0], chunk)
            out_states = inject_local(tuple(local), lchunk)
            return jax.tree.map(lambda x: x[None], tuple(out_states))

        self._spill_progs[key] = (
            jax.jit(shard_map_nocheck(
                drain_body, mesh=self.mesh, in_specs=(spec,),
                out_specs=(spec, spec),
            ), donate_argnums=(0,)),
            jax.jit(shard_map_nocheck(
                inject_body, mesh=self.mesh, in_specs=(spec, spec),
                out_specs=spec,
            ), donate_argnums=(0,)),
        )

    def _drain_spill_tiers(self, sealed) -> None:
        """Snapshot-barrier hook: divert ring rows to host tiers and
        inject their changelog downstream of each agg node.  Under the
        mesh every shard drains into its own tier; the merged
        changelogs inject back shard-aligned through the sharded
        program (exchanges included)."""
        for idx, j, ex in self._spill_sites():
            self._ensure_spill_tier(idx, j, ex)
            key = (idx, j)
            counts = np.asarray(self.states[idx][j].spill_count)
            if int(counts.sum()) == 0:
                continue
            drain_p, inject_p = self._spill_progs[key]
            tiers = self._spill_tiers[key]
            if self.mesh is None:
                self.states, chunk = drain_p(self.states)
                out = tiers[0].process(jax.device_get(chunk), sealed)
                if out is not None:
                    self.states = inject_p(self.states, out)
                continue
            self.states, chunks = drain_p(self.states)
            host = jax.device_get(chunks)  # stacked [n_shards, ...]
            outs = []
            for s in range(self.n_shards):
                shard_chunk = jax.tree.map(lambda x: x[s], host)
                outs.append(tiers[s].process(shard_chunk, sealed))
            if all(o is None for o in outs):
                continue
            proto = next(o for o in outs if o is not None)
            empty = jax.tree.map(
                lambda x: np.zeros_like(np.asarray(x)), proto
            )
            stacked = jax.tree.map(
                lambda *xs: np.stack([np.asarray(x) for x in xs]),
                *[o if o is not None else empty for o in outs],
            )
            self.states = inject_p(self.states, self._place(stacked))

    # -- backfill -------------------------------------------------------
    def backfill_node(self, node_id: int, chunks, side: str | None = None,
                      ) -> None:
        """Feed snapshot chunks through ONE node's subtree (a freshly
        attached cascade MV consuming the upstream MV's existing rows —
        ref arrangement_backfill.rs, collapsed to snapshot replay since
        the upstream MV is device-resident).  ``side`` targets a join
        node's build/probe side.

        NOT donated: the snapshot chunk aliases the upstream MV's state
        buffers (it is built zero-copy from them), so donating the state
        tree would donate the chunk's own storage."""
        if self.mesh is None:
            prog = jax.jit(
                lambda states, chunk: self._backfill_impl(
                    states, chunk, node_id, side
                ),
            )
        else:
            # sharded job: the snapshot chunk arrives stacked
            # [n_shards, ...]; each shard replays its own MV partition
            # through the attached subtree inside shard_map (same
            # calling convention as _make_step's per-shard body)
            spec = self._sharding_spec()

            def body(states, chunk):
                local_s = jax.tree.map(lambda x: x[0], states)
                local_c = jax.tree.map(lambda x: x[0], chunk)
                out = self._backfill_impl(
                    tuple(local_s), local_c, node_id, side
                )
                return jax.tree.map(lambda x: x[None], out)

            prog = jax.jit(shard_map_nocheck(
                body, mesh=self.mesh, in_specs=(spec, spec),
                out_specs=spec,
            ))
        for chunk in chunks:
            self.states = prog(self.states, chunk)

    def _backfill_impl(self, states, chunk, node_id: int,
                       side: str | None):
        new_states = list(states)
        node = self.nodes[node_id]
        # a marked attach edge routes the snapshot replay through the
        # SAME exchange live chunks cross (agg-over-reduced-key / join
        # attach edges): each shard's partition re-routes to its new
        # key owners before the first executor sees it
        chunk = self._exchange(
            node_id, side if isinstance(node, JoinNode) else None, chunk
        )
        if isinstance(node, FragNode):
            new_states[node_id], out = node.fragment._step_impl(
                new_states[node_id], chunk
            )
            if out is not None:
                self._propagate(new_states, [(("node", node_id), out)])
        else:
            # joins drain with WINDOWED emission: an MV snapshot is one
            # big chunk, its self-join easily exceeds out_capacity
            def direct(ref, out):
                self._propagate(new_states, [(ref, out)])

            self._apply_join_windowed(
                new_states, node_id, chunk, side, direct
            )
        return tuple(new_states)

    @classmethod
    def binary(
        cls,
        left_source,
        right_source,
        join,
        post_fragment: Fragment,
        left_fragment: Fragment | None = None,
        right_fragment: Fragment | None = None,
        checkpoint_frequency: int = 1,
        name: str = "join_job",
        checkpoint_store=None,
    ) -> "DagJob":
        """Two sources → per-side prep → join → post chain (the former
        BinaryJob shape as a DAG)."""
        nodes: list = []
        lref: Ref = ("source", "left")
        rref: Ref = ("source", "right")
        if left_fragment is not None:
            nodes.append(FragNode(left_fragment, lref))
            lref = ("node", len(nodes) - 1)
        if right_fragment is not None:
            nodes.append(FragNode(right_fragment, rref))
            rref = ("node", len(nodes) - 1)
        nodes.append(JoinNode(join, lref, rref))
        nodes.append(FragNode(post_fragment, ("node", len(nodes) - 1)))
        return cls(
            {"left": left_source, "right": right_source}, nodes,
            name=name, checkpoint_frequency=checkpoint_frequency,
            checkpoint_store=checkpoint_store,
        )
