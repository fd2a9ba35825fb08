"""A pk-keyed view at its high-water mark holds its job's ingest
(``BarrierLoop.ingest_hold``, ``Engine.ingest_waits``): no key is dropped
for want of a slot, the view stays exactly what the rows it took make
it, and the barrier loop does not raise.  The last test is the run that
ended PRs 33 and 36 at the driver: the benchmark's window loop blocked
in ``trace_stop`` while the served node ticks on into a full view."""

import json
import os
import shutil
import sys
import time
import urllib.request

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SLOTS = 64
#: keys a barrier: the hold sets in at 50 (50 + 10 > 7/8 of 64)
STEP = 10


def _engine():
    from risingwave_tpu.sql.engine import Engine
    from risingwave_tpu.sql.planner import PlannerConfig

    eng = Engine(PlannerConfig(
        chunk_capacity=128, agg_table_size=512, agg_emit_capacity=128,
        mv_table_size=SLOTS))
    for name in "tu":
        eng.execute(f"CREATE TABLE {name} (k BIGINT PRIMARY KEY, v BIGINT)")
    return eng


VIEWS = {
    "agg": "CREATE MATERIALIZED VIEW w AS "
           "SELECT k, sum(v) AS v FROM t GROUP BY k",
    # a DagJob: the view behind a join node
    "join_agg": "CREATE MATERIALIZED VIEW w AS SELECT t.k AS k, "
                "sum(u.v) AS v FROM t JOIN u ON t.k = u.k GROUP BY t.k",
}


def _insert(eng, lo: int, hi: int) -> None:
    for name in "tu":
        eng.execute(f"INSERT INTO {name} VALUES " + ", ".join(
            f"({k}, {k})" for k in range(lo, hi)))


def _fill_to_the_mark(eng) -> object:
    job = eng.jobs[-1]
    for r in range(5):
        assert job.ingest_hold is None
        _insert(eng, r * STEP, (r + 1) * STEP)
        eng.execute("FLUSH")
    return job


def _series(text: str, name: str, job: str) -> float:
    for line in text.splitlines():
        if line.startswith(name + "{") and f'job="{job}"' in line:
            return float(line.rsplit(" ", 1)[1])
    raise AssertionError(f"no {name}{{job={job}}} in the scrape")


@pytest.mark.parametrize("view", sorted(VIEWS))
def test_a_view_at_its_mark_holds_ingest_and_stays_exact(view, capfd):
    eng = _engine()
    eng.execute(VIEWS[view])
    job = _fill_to_the_mark(eng)
    assert job.ingest_hold is not None and "50 of 64" in job.ingest_hold
    assert "ingest held at the view's high-water mark" in \
        capfd.readouterr().err
    want = [(k, k) for k in range(5 * STEP)]
    assert sorted(eng.execute("SELECT * FROM w")) == want

    # rows that would overflow the 64 slots: nothing is taken, nothing
    # raises, no barrier is crossed for them
    _insert(eng, 50, 150)
    seen = job.barriers_seen
    eng.tick(barriers=3, chunks_per_barrier=1)
    assert job.barriers_seen == seen
    assert sorted(eng.execute("SELECT * FROM w")) == want
    with pytest.raises(RuntimeError, match="high-water mark"):
        eng.execute("FLUSH")
    # a barrier that brings no chunk crosses (the orderly stop's, and
    # the one an operator's chunks_per_barrier = 0 makes)
    eng.tick(barriers=1, chunks_per_barrier=0)
    assert job.barriers_seen == seen + 1
    assert job.ingest_hold is not None
    assert sorted(eng.execute("SELECT * FROM w")) == want

    text = eng.metrics.render_prometheus()
    assert _series(text, "stream_ingest_held", "w") == 1
    assert _series(text, "materialize_used_slots", "w") == 50
    assert _series(text, "materialize_view_slots", "w") == SLOTS
    # levels, not lost rows: the benchmark's counter_rows check sums
    # this family and wants 0
    assert not [ln for ln in text.splitlines()
                if ln.startswith("maintenance_counter_rows")
                and not ln.endswith(" 0")]


def test_the_hold_lifts_when_the_view_has_room_and_a_first_look_knows_no_growth():
    import numpy as np

    eng = _engine()
    eng.execute(VIEWS["agg"])
    job = _fill_to_the_mark(eng)
    labels = job.counter_labels
    used = next(i for i, x in enumerate(labels) if x.endswith(".used_slots"))
    values = np.zeros(len(labels), np.int64)
    values[[i for i, x in enumerate(labels)
            if x.endswith(".view_slots")]] = SLOTS
    values[used] = 20
    job._hold_at_high_water(values)
    assert job.ingest_hold is None
    assert _series(eng.metrics.render_prometheus(),
                   "stream_ingest_held", "w") == 0
    # a recovered job's first pass finds a view more than half full:
    # that is its level, not its growth
    job._view_levels.clear()
    values[used] = 40
    job._hold_at_high_water(values)
    assert job.ingest_hold is None
    values[used] = 48
    job._hold_at_high_water(values)   # 48 + 8 is the mark, not past it
    assert job.ingest_hold is None
    values[used] = 49
    job._hold_at_high_water(values)
    assert job.ingest_hold is not None
    # a rewind takes the hold of the state that is gone with it
    job.recover()
    assert job.ingest_hold is None and not job._view_levels


def test_a_view_with_room_is_never_held():
    eng = _engine()
    eng.execute(VIEWS["agg"])
    job = eng.jobs[-1]
    for r in range(4):
        _insert(eng, r * STEP, (r + 1) * STEP)
        eng.execute("FLUSH")
    # changes to keys that are there claim no slot, however many
    for _ in range(6):
        _insert(eng, 0, 4 * STEP)
        eng.execute("FLUSH")
    assert job.ingest_hold is None
    assert len(eng.execute("SELECT * FROM w")) == 4 * STEP


def test_the_served_node_makes_no_tick_while_every_job_waits(tmp_path):
    from risingwave_tpu.common.config import RwConfig
    from risingwave_tpu.server import SingleNode

    node = SingleNode(RwConfig.from_dict({
        "streaming": {"chunk_size": 128},
        "state": {"agg_table_size": 512, "agg_emit_capacity": 128,
                  "mv_table_size": SLOTS, "mv_ring_size": 1024},
    }), data_dir=str(tmp_path))
    try:
        eng = node.engine
        for name in "tu":
            eng.execute(
                f"CREATE TABLE {name} (k BIGINT PRIMARY KEY, v BIGINT)")
        eng.execute(VIEWS["agg"])
        job = _fill_to_the_mark(eng)
        assert job.ingest_hold is not None
        _insert(eng, 50, 90)

        def ticks() -> float:
            for line in node.render_metrics().splitlines():
                if line.startswith("trace_span_total{") \
                        and 'span="tick"' in line:
                    return float(line.rsplit(" ", 1)[1])
            return 0.0

        n, seen = ticks(), job.barriers_seen
        for _ in range(5):
            node._tick_once()
        assert ticks() == n and job.barriers_seen == seen
        # the operator's way out of the wait, and the harness's hold
        eng.execute("ALTER SYSTEM SET chunks_per_barrier = 0")
        node._tick_once()
        assert ticks() == n + 1 and job.barriers_seen == seen + 1
    finally:
        node.stop()   # the final barrier brings no chunk: it crosses
    assert job.committed_epoch > 0
    assert len(node.engine.execute("SELECT * FROM w")) == 5 * STEP


def test_a_traced_run_whose_trace_stop_blocks_ends_correct(tmp_path):
    """``benchmark/run.py`` stops the profiler inside its window loop and
    is held there (42-64 s on the chip) while the sources run on; a node
    faster than ~0.55 s a tick then filled q5's view and the run ended
    with exit code 1 (ledger, PR 33; PR 36's rehearsal).  Here the stop
    blocks until the view is at its mark: the run ends ``correct``, the
    view is the reference's for the rows taken, and the window holds
    only barriers that brought rows."""
    for p in ("benchmark", "benchmark/reference"):
        sys.path.insert(0, os.path.join(ROOT, p))
    try:
        import run
        import trace_reduce
    finally:
        del sys.path[:2]
    preset = os.path.join(ROOT, "benchmark", "tests", "preset")
    home = tmp_path / "preset"
    shutil.copytree(os.path.join(preset, "workloads"), home / "workloads")
    os.makedirs(home / "configs")
    cfg = json.load(open(os.path.join(preset, "configs", "tiny_q5.json")))
    cfg["server"]["config_json"]["state"]["mv_table_size"] = 1 << 15
    cfg["horizon_rows"] *= 40
    json.dump(cfg, open(home / "configs" / "tiny_q5.json", "w"))
    wl = home / "workloads" / "tiny_q5_backlog.json"
    traffic = json.load(open(wl))
    traffic["trace"]["seconds"] = 0.2
    json.dump(traffic, open(wl, "w"))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench["configs"] = [{
        "name": "tiny_q5", "source": "tests only", "reduced": [],
        "file": os.path.relpath(home / "configs" / "tiny_q5.json", ROOT),
        "why": "tests only"}]
    bench["workloads"] = [{
        "name": "tiny_q5_backlog", "config": "tiny_q5",
        "traffic": "tiny_q5_backlog", "chips": 1, "why": "tests only"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = []
    bench_path = str(tmp_path / "BENCHMARK.json")
    json.dump(bench, open(bench_path, "w"))

    held_at_stop = []
    plain_ctl = run.Child.ctl
    plain_reduce = trace_reduce.reduce_dir

    def ctl(self, line, **kw):
        if line.startswith("trace_stop"):
            mport = self.proc.args[self.proc.args.index("--metrics-port") + 1]
            url = f"http://127.0.0.1:{mport}/metrics"
            t_end = time.monotonic() + 240
            while time.monotonic() < t_end:
                with urllib.request.urlopen(url, timeout=60) as r:
                    text = r.read().decode()
                if _series(text, "stream_ingest_held", "q5") == 1:
                    held_at_stop.append(
                        _series(text, "materialize_used_slots", "q5"))
                    break
                time.sleep(0.2)
            time.sleep(1.0)   # and some empty turns of the ticker
        return plain_ctl(self, line, **kw)

    run.Child.ctl = ctl
    trace_reduce.reduce_dir = lambda *_: {
        "busy_s": 1.0, "window_s": 2.0, "device_ops": [], "idle_gaps": [],
        "modules": {}, "window_program": {"name": "jit__multi", "runs": 0,
                                          "device_s": 0.0},
        "summary": "no device plane on the CPU: not reduced"}
    try:
        result, window = run.run_cell(
            "tiny_q5_backlog", 2**31 + 77, 3.0, True, bench_path=bench_path,
            require_tpu=False, out_root=str(tmp_path))
    finally:
        run.Child.ctl = plain_ctl
        trace_reduce.reduce_dir = plain_reduce
    assert held_at_stop and held_at_stop[0] <= 7 / 8 * (1 << 15)
    assert result["correct"] is True, result["checks"]
    assert result["checks"]["view_rows_differ"][0] == 0
    assert result["checks"]["barrier_errors"][0] == 0
    # every barrier of the window brought its rows: the held turns of
    # the ticker are no barriers, so no per-layer mean is thinned
    took = run.arith.rows(window["scrape_end"], "q5") \
        - run.arith.rows(window["scrape_start"], "q5")
    assert took == window["barriers"] * 32 * 1024
