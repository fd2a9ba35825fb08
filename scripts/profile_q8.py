"""Attribute q8's cost: stage-by-stage timings of the windowed join.

Round-3 verdict ask #4: q8 runs 16x below q7 on CPU with no in-repo
attribution.  This times each pipeline stage as its own jitted program
over identical inputs:

  1. source generation + tumble windowing (both sides)
  2. join apply_begin (state update + emission staging)
  3. emission window 0 materialization (emit_window)
  4. the full per-chunk step (everything incl. extra windows + MV)

Usage:
  JAX_PLATFORMS=cpu python scripts/profile_q8.py            # timings
  JAX_PLATFORMS=cpu python scripts/profile_q8.py --assert   # regression
  ... --assert --small    # reduced state sizes (the CI/pytest wrapper)
  ... --assert --sharded  # 8 host-emulated devices: the SHARDED gate
                          # (1 fused dispatch per window, 0 per-chunk
                          # host dispatches, exchange-bytes budget,
                          # per-shard delta snapshots, probe audit)

``--assert`` turns the structural q8 invariants into hard failures so
probe-count and dispatch-count regressions fail loudly instead of
silently re-widening the join gap (exit 1 + named violation):

  - exactly ONE lookup_or_insert per append-only join side per chunk
    (trace-time probe audit of the fused (hash, rank) pool update);
  - the whole inter-barrier window dispatches as ONE fused program
    (DagJob.run_chunks) — zero per-chunk host dispatches;
  - steady-state probe effort stays bounded (device probe_iters per
    chunk within budget — load-factor / tombstone regressions show up
    here);
  - steady-state emission drains in ONE window per chunk (out_capacity
    sizing regressions show up as extra drain-loop trips);
  - join state error counters (overflow/inconsistency/emit_overflow)
    all zero.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import risingwave_tpu  # noqa: F401,E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from risingwave_tpu.sql import Engine  # noqa: E402
from risingwave_tpu.sql.planner import PlannerConfig  # noqa: E402

CAP = 8192

#: steady-state per-chunk budget on fused-probe loop trips: the ranked
#: probe resolves in ~4 rounds at bench load factors; tombstone pileup
#: or an overfull table shows up as a climb well past this
PROBE_ITERS_BUDGET = 24
#: steady-state emission windows per probe chunk (q8 emits a few
#: hundred matches per 8k chunk — one out_capacity window covers it)
DRAIN_WINDOWS_BUDGET = 1.25


def timeit(name, fn, n=20):
    jax.block_until_ready(fn())  # compile
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn()
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / n
    print(f"{name:42s} {dt * 1e3:9.2f} ms  "
          f"({CAP / dt / 1e6:7.2f}M rows/s/side)", flush=True)
    return dt


#: per-traced-exchange payload budget, BYTES PER ROW-SLOT: the
#: all_to_all moves n_shards*cap bucket slots of the q8 prep schema
#: (~170 B/row with the string column); a schema/bucketing regression
#: (extra columns, per-window exchanges) blows through this
EXCHANGE_BYTES_PER_SLOT_BUDGET = 512
#: traced exchange sites across ALL compiled sharded q8 programs (the
#: fused window traces 2 — one per join side, fori_loop traces its
#: body once; barrier/backfill/spill programs add a handful).  A
#: per-round or per-window exchange regression multiplies this.
EXCHANGE_CALLS_BUDGET = 24
#: steady-state per-shard dirty fraction bound: q8's tag-table scatter
#: dirties 10-40% of blocks per window at bench rate; 1.0 = the
#: full-copy path came back
DIRTY_RATIO_BOUND = 0.9


def build_engine(small: bool, cap: int) -> Engine:
    if small:
        cfg = PlannerConfig(
            chunk_capacity=cap,
            agg_table_size=1 << 12, agg_emit_capacity=1024,
            join_left_table_size=1 << 14, join_right_table_size=1 << 14,
            join_pool_size=1 << 18, join_out_capacity=1 << 10,
            mv_table_size=1 << 12, mv_ring_size=1 << 16,
        )
    else:
        cfg = PlannerConfig(
            chunk_capacity=cap,
            agg_table_size=1 << 18, agg_emit_capacity=4096,
            join_left_table_size=1 << 22, join_right_table_size=1 << 18,
            join_pool_size=1 << 22, join_out_capacity=1 << 15,
            mv_table_size=1 << 18, mv_ring_size=1 << 23,
        )
    eng = Engine(cfg)
    eng.execute("""
    CREATE SOURCE person (
        id BIGINT, name VARCHAR, date_time TIMESTAMP,
        WATERMARK FOR date_time AS date_time - INTERVAL '4' SECOND
    ) WITH (connector = 'nexmark', nexmark.table = 'person',
            nexmark.event.rate = '1000000');
    CREATE SOURCE auction (
        id BIGINT, seller BIGINT, reserve BIGINT, expires TIMESTAMP,
        date_time TIMESTAMP,
        WATERMARK FOR date_time AS date_time - INTERVAL '4' SECOND
    ) WITH (connector = 'nexmark', nexmark.table = 'auction',
            nexmark.event.rate = '1000000');
    CREATE MATERIALIZED VIEW bench_mv AS
    SELECT p.id AS id, p.name AS name, a.reserve AS reserve
    FROM TUMBLE(person, date_time, INTERVAL '1' SECOND) p
    JOIN TUMBLE(auction, date_time, INTERVAL '1' SECOND) a
    ON p.id = a.seller AND p.window_start = a.window_start;
    """)
    return eng


def run_assert(small: bool) -> int:
    """The regression-assertion mode (per-stage budget check)."""
    cap = 1024 if small else CAP
    eng = build_engine(small, cap)
    failures: list[str] = []

    # dispatch count: the inter-barrier window must be ONE fused
    # dispatch — count per-chunk host dispatches under the fused path
    from risingwave_tpu.stream.dag import DagJob
    per_chunk_calls = {"n": 0}
    orig_run_chunk = DagJob.run_chunk

    def counting_run_chunk(self, src):
        per_chunk_calls["n"] += 1
        return orig_run_chunk(self, src)

    DagJob.run_chunk = counting_run_chunk
    try:
        eng.tick(barriers=2, chunks_per_barrier=8)
    finally:
        DagJob.run_chunk = orig_run_chunk
    if per_chunk_calls["n"] != 0:
        failures.append(
            f"dispatch-count: {per_chunk_calls['n']} per-chunk host "
            "dispatches — the inter-barrier window no longer runs as "
            "one fused DagJob.run_chunks program"
        )

    # probe count: exactly one lookup_or_insert per pool side per chunk
    audit = eng.audit_join_probe_counts()
    if not audit:
        failures.append("probe-count: no pool join sides found to audit")
    for (jname, node, jside), stats in audit.items():
        if stats["lookup_or_insert"] != 1 or stats["lookup"] != 0:
            failures.append(
                f"probe-count: {jname} node {node} {jside} update "
                f"compiles {stats['lookup_or_insert']} lookup_or_insert"
                f" + {stats['lookup']} lookup calls (want exactly 1+0)"
            )

    # device-counter budgets (one readback, post-run)
    eng.collect_join_metrics()
    m = eng.metrics
    job = eng.jobs[0]
    from risingwave_tpu.stream.dag import JoinNode
    jidx = next(i for i, n in enumerate(job.nodes)
                if isinstance(n, JoinNode))
    labels = dict(job=job.name, node=str(jidx))
    iters = m.get("join_probe_iters_per_chunk", **labels)
    if iters > PROBE_ITERS_BUDGET:
        failures.append(
            f"probe-effort: {iters:.1f} fused-probe loop trips per "
            f"chunk (budget {PROBE_ITERS_BUDGET}) — table load factor "
            "or tombstone pileup regressed"
        )
    windows = m.get("join_drain_windows_per_chunk", **labels)
    if windows > DRAIN_WINDOWS_BUDGET:
        failures.append(
            f"drain-loop: {windows:.2f} emission windows per chunk "
            f"(budget {DRAIN_WINDOWS_BUDGET}) — out_capacity sizing "
            "or emission staging regressed"
        )

    # observability gate (trace-lite): the engine must attribute
    # barrier time per phase on its scrape surface for the bench job
    for phase in ("dispatch", "seal"):
        try:
            m.quantile("barrier_phase_seconds", 0.5,
                       job=job.name, phase=phase)
        except KeyError:
            failures.append(
                "observability: no barrier_phase_seconds"
                f"{{job={job.name},phase={phase}}} histogram — "
                "barrier-phase attribution regressed"
            )

    # error counters must be clean (the audit barrier would raise, but
    # assert explicitly so this mode stands alone)
    import numpy as np
    st = job.states[jidx]
    for sname in ("left", "right"):
        s = getattr(st, sname)
        for attr in ("overflow", "inconsistency"):
            v = int(np.asarray(getattr(s, attr)))
            if v:
                failures.append(f"counters: {sname}.{attr} = {v}")
    if int(np.asarray(st.emit_overflow)):
        failures.append(
            f"counters: emit_overflow = {int(np.asarray(st.emit_overflow))}"
        )

    if failures:
        print("profile_q8 --assert: FAIL", flush=True)
        for f in failures:
            print(f"  - {f}", flush=True)
        return 1
    print(
        "profile_q8 --assert: OK — 1 probe/side/chunk, fused dispatch, "
        f"probe iters/chunk {iters:.1f} <= {PROBE_ITERS_BUDGET}, "
        f"windows/chunk {windows:.2f} <= {DRAIN_WINDOWS_BUDGET}",
        flush=True,
    )
    return 0


def run_assert_sharded() -> int:
    """The SHARDED regression gate (ISSUE 9): q8 over an 8-device mesh
    must run each barrier-to-barrier window as ONE fused shard_map
    dispatch — zero per-chunk host dispatches — with bounded exchange
    traffic and per-shard DELTA snapshots (dirty-fraction cost, not
    full-copy).  Structural invariants only: this 1-core box cannot
    show wall-clock scaling on host-emulated devices."""
    import tempfile

    import numpy as np

    import jax

    if len(jax.devices()) < 8:
        print(f"profile_q8 --sharded: {len(jax.devices())} devices "
              "visible (need 8 host-emulated); re-exec with "
              "--xla_force_host_platform_device_count", flush=True)
        if os.environ.get("RWT_SHARDED_REEXEC"):
            return 1
        env = dict(os.environ)
        env["RWT_SHARDED_REEXEC"] = "1"
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        ).strip()
        os.execve(sys.executable, [sys.executable] + sys.argv, env)

    from risingwave_tpu.parallel.exchange import (
        EXCHANGE_TRACE,
        reset_exchange_trace,
    )
    from risingwave_tpu.stream.dag import DagJob, JoinNode

    cap = 1024
    rounds = 8
    failures: list[str] = []
    data_dir = tempfile.mkdtemp(prefix="rwt_profile_q8_sharded_")

    # the --small shapes plus a durable store (the delta-snapshot gate
    # needs the digest-mode shadow) and mesh parallelism
    eng = Engine(PlannerConfig(
        chunk_capacity=cap,
        agg_table_size=1 << 12, agg_emit_capacity=1024,
        join_left_table_size=1 << 14, join_right_table_size=1 << 14,
        join_pool_size=1 << 18, join_out_capacity=1 << 10,
        mv_table_size=1 << 12, mv_ring_size=1 << 18,
    ), data_dir=data_dir)
    eng.execute("SET streaming_parallelism = 8")
    eng.execute("""
    CREATE SOURCE person (
        id BIGINT, name VARCHAR, date_time TIMESTAMP,
        WATERMARK FOR date_time AS date_time - INTERVAL '4' SECOND
    ) WITH (connector = 'nexmark', nexmark.table = 'person',
            nexmark.event.rate = '1000000');
    CREATE SOURCE auction (
        id BIGINT, seller BIGINT, reserve BIGINT, expires TIMESTAMP,
        date_time TIMESTAMP,
        WATERMARK FOR date_time AS date_time - INTERVAL '4' SECOND
    ) WITH (connector = 'nexmark', nexmark.table = 'auction',
            nexmark.event.rate = '1000000');
    """)
    reset_exchange_trace()
    eng.execute("""
    CREATE MATERIALIZED VIEW bench_mv AS
    SELECT p.id AS id, p.name AS name, a.reserve AS reserve
    FROM TUMBLE(person, date_time, INTERVAL '1' SECOND) p
    JOIN TUMBLE(auction, date_time, INTERVAL '1' SECOND) a
    ON p.id = a.seller AND p.window_start = a.window_start;
    """)
    job = eng.jobs[0]
    if not (isinstance(job, DagJob) and job.mesh is not None
            and job.n_shards == 8):
        failures.append(
            f"plan: q8 did not shard over the mesh (mesh="
            f"{getattr(job, 'mesh', None)}, type {type(job).__name__})"
        )
        _report(failures)
        return 1

    # dispatch count: the whole inter-barrier window must be ONE fused
    # shard_map program — zero per-chunk host dispatches
    per_chunk_calls = {"n": 0}
    orig_run_chunk = DagJob.run_chunk

    def counting_run_chunk(self, src):
        per_chunk_calls["n"] += 1
        return orig_run_chunk(self, src)

    DagJob.run_chunk = counting_run_chunk
    try:
        eng.tick(barriers=3, chunks_per_barrier=rounds)
    finally:
        DagJob.run_chunk = orig_run_chunk
    if per_chunk_calls["n"] != 0:
        failures.append(
            f"dispatch-count: {per_chunk_calls['n']} per-chunk host "
            "dispatches — the sharded window no longer runs as one "
            "fused shard_map program"
        )
    if rounds not in job._fused_multi:
        failures.append(
            f"dispatch-count: no fused {rounds}-round program cached "
            f"(have {sorted(job._fused_multi)})"
        )
    if job.fused_fallbacks:
        failures.append(
            f"dispatch-count: fused fallbacks {job.fused_fallbacks}"
        )

    # exchange budget: traced sites + per-slot payload bytes
    calls = EXCHANGE_TRACE["calls"]
    if calls == 0:
        failures.append("exchange: no all_to_all traced in the "
                        "sharded programs")
    elif calls > EXCHANGE_CALLS_BUDGET:
        failures.append(
            f"exchange: {calls} traced exchange sites (budget "
            f"{EXCHANGE_CALLS_BUDGET}) — a per-round/per-window "
            "exchange crept in"
        )
    if calls:
        slots = calls * job.n_shards * cap
        per_slot = EXCHANGE_TRACE["bytes"] / slots
        if per_slot > EXCHANGE_BYTES_PER_SLOT_BUDGET:
            failures.append(
                f"exchange: {per_slot:.0f} B per bucket slot (budget "
                f"{EXCHANGE_BYTES_PER_SLOT_BUDGET}) — exchange payload "
                "schema regressed"
            )

    # per-shard shadow snapshots: delta kind + bounded dirty fraction
    kinds = [eng.checkpoint_store.checkpoint_kind(job.name, e)
             for e in eng.checkpoint_store.epochs(job.name)]
    if "delta" not in kinds:
        failures.append(
            f"snapshot: no delta checkpoint in the window (kinds "
            f"{kinds}) — the per-shard shadow is not feeding the "
            "delta store"
        )
    shadow = job._shadow
    if shadow is None:
        failures.append("snapshot: no shadow snapshot on the mesh job")
    else:
        if shadow.shard_rows != 8:
            failures.append(
                f"snapshot: shadow digests flat (shard_rows="
                f"{shadow.shard_rows}) — per-shard lanes lost"
            )
        ratio = shadow.dirty_ratio()
        if not (0.0 < ratio <= DIRTY_RATIO_BOUND):
            failures.append(
                f"snapshot: dirty-block ratio {ratio:.3f} outside "
                f"(0, {DIRTY_RATIO_BOUND}] — full-copy behaviour "
                "(or a dead digest diff)"
            )

    # probe count: the per-shard update body still compiles exactly
    # ONE lookup_or_insert per append-only pool side
    audit = eng.audit_join_probe_counts()
    if not audit:
        failures.append("probe-count: no pool join sides found")
    for (jname, node, jside), stats in audit.items():
        if stats["lookup_or_insert"] != 1 or stats["lookup"] != 0:
            failures.append(
                f"probe-count: {jname} node {node} {jside} compiles "
                f"{stats['lookup_or_insert']}+{stats['lookup']} probe "
                "calls (want exactly 1+0)"
            )

    # error counters clean, summed over the shard axis
    jidx = next(i for i, n in enumerate(job.nodes)
                if isinstance(n, JoinNode))
    st = job.states[jidx]
    for sname in ("left", "right"):
        s = getattr(st, sname)
        for attr in ("overflow", "inconsistency"):
            v = int(np.asarray(getattr(s, attr)).sum())
            if v:
                failures.append(f"counters: {sname}.{attr} = {v}")
    if int(np.asarray(st.emit_overflow).sum()):
        failures.append(
            f"counters: emit_overflow = "
            f"{int(np.asarray(st.emit_overflow).sum())}"
        )

    if failures:
        _report(failures)
        return 1
    print(
        "profile_q8 --assert --sharded: OK — 1 fused dispatch per "
        f"{rounds}-round window on 8 shards, 0 per-chunk host "
        f"dispatches, {calls} traced exchange sites, dirty ratio "
        f"{shadow.dirty_ratio():.3f} <= {DIRTY_RATIO_BOUND}, delta "
        "snapshots, 1 probe/side/chunk",
        flush=True,
    )
    return 0


def _report(failures: list) -> None:
    print("profile_q8 --assert --sharded: FAIL", flush=True)
    for f in failures:
        print(f"  - {f}", flush=True)


def main():
    if "--assert" in sys.argv:
        if "--sharded" in sys.argv:
            sys.exit(run_assert_sharded())
        sys.exit(run_assert(small="--small" in sys.argv))
    eng = build_engine(False, CAP)
    eng.tick(barriers=2, chunks_per_barrier=2)  # warm state + compile
    job = eng.jobs[0]
    from risingwave_tpu.stream.dag import JoinNode

    jidx = next(i for i, n in enumerate(job.nodes)
                if isinstance(n, JoinNode))
    join = job.nodes[jidx].join
    # prep fragments feeding the join (wm filter + tumble per side)
    src = "p"
    reader = job.sources[src]

    prep_idx = next(
        i for i, n in enumerate(job.nodes)
        if not isinstance(n, JoinNode) and n.input == ("source", src)
    )
    prep = job.nodes[prep_idx].fragment

    @jax.jit
    def gen_only(k0):
        return reader.impl(k0, reader.cap)

    @jax.jit
    def gen_prep(states, k0):
        chunk = reader.impl(k0, reader.cap)
        return prep._step_impl(states, chunk)

    # donated, as the real step program runs it: the state updates in
    # place; an un-donated trace would copy the multi-hundred-MB side
    # state every call and time the memcpy, not the join
    join_begin = jax.jit(
        lambda jstate, chunk: join.apply_begin(jstate, chunk, "left"),
        donate_argnums=(0,),
    )

    @jax.jit
    def emit0(jstate, pending):
        build = join.build_rows_of(jstate, "left")
        return join.emit_window(build, pending, jnp.int32(0), "left")

    k0 = jnp.int64(10_000_000)
    timeit("source gen only", lambda: gen_only(k0))
    st_prep = job.states[prep_idx]
    _, chunk = gen_prep(st_prep, k0)
    timeit("gen + wm + tumble", lambda: gen_prep(st_prep, k0)[1])
    jstate = jax.tree.map(jnp.copy, job.states[jidx])
    st2, pending = join_begin(jstate, chunk)

    def begin_threaded():
        # thread the donated state: measures the steady-state in-place
        # update cost
        nonlocal_state = begin_threaded.state
        st, pend = join_begin(nonlocal_state, chunk)
        begin_threaded.state = st
        return pend

    begin_threaded.state = st2
    timeit("join apply_begin (donated)", begin_threaded)
    st3 = begin_threaded.state
    _, pending = jax.jit(
        lambda jstate, chunk: join.apply_begin(jstate, chunk, "left")
    )(st3, chunk)
    timeit("emit window 0", lambda: emit0(st3, pending)[0])
    print("max_windows:", join.max_windows(CAP),
          "out_capacity:", join.out_capacity)
    print("pending total (this chunk):", int(pending.total))

    # whole-step reference (the real per-chunk cost)
    prog, fused = job._step_programs.get(src, (None, None))
    if prog is None:
        job._step_programs[src] = job._make_step(src)
        prog, fused = job._step_programs[src]
    job.states = prog(job.states, k0)
    jax.block_until_ready(job.states)

    def full():
        return prog(job.states, jnp.int64(reader.next_base()))

    t0 = time.perf_counter()
    N = 20
    for _ in range(N):
        job.states = full()
    jax.block_until_ready(job.states)
    dt = (time.perf_counter() - t0) / N
    print(f"{'FULL step (person side)':42s} {dt * 1e3:9.2f} ms  "
          f"({CAP / dt / 1e6:7.2f}M rows/s/side)")


if __name__ == "__main__":
    main()
