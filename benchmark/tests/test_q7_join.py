"""The whole of q7: the new reference's generator against the program's
(``bidder`` included), its join against a slower writing of the same,
the five join layer readers, and ``run.py`` end to end on the CPU over
the join cell at a tiny preset (``preset/cells_q7_join.json``)."""

import json
import os

import numpy as np
import pytest

import compare
import nexmark_q7_numpy as ref
import run

HERE = os.path.dirname(os.path.abspath(__file__))
REAL = os.path.join(run.ROOT, "BENCHMARK.json")
JOIN_LAYERS = ["join_live_rows", "join_cleaned_rows_per_barrier",
               "join_probe_steps_per_row", "join_emit_rows_per_barrier",
               "join_reclaim_slots_per_barrier"]
N = 200_000


@pytest.mark.parametrize("seed", [1, 77, 1 << 20])
def test_seeded_reference_equals_the_seeded_generator(seed):
    import jax

    import risingwave_tpu  # noqa: F401  (x64)
    from risingwave_tpu.connector.nexmark import (
        NexmarkConfig, NexmarkGenerator,
    )

    gen = NexmarkGenerator(NexmarkConfig(inter_event_us=4, seed=seed))
    chunk = gen._bids_impl(jax.numpy.int64(0), N)
    mine = ref.gen_columns("bid", N, ref.COLUMNS, 250_000, seed)
    for n in ref.COLUMNS:
        theirs = np.asarray(chunk.columns[chunk.schema.index_of(n)])
        assert np.array_equal(mine[n], theirs), n


def test_join_against_a_slower_writing():
    b = ref.gen_columns("bid", 60_000, ref.COLUMNS, 2_000, 3)
    # a price held by many rows, some in a window where it is the
    # maximum: every one of them within the band comes out
    b["price"][::7] = 10**9
    got = sorted(zip(*(ref.q7_rows(b)[n].tolist() for n in ref.COLUMNS)))
    rows = list(zip(*(b[n].tolist() for n in ref.COLUMNS)))
    want = []
    ends = sorted({t // ref.WINDOW_US * ref.WINDOW_US + ref.WINDOW_US
                   for *_, t in rows})
    for end in ends:
        top = max(p for _, p, _, t in rows if end - ref.WINDOW_US <= t < end)
        want += [r for r in rows
                 if r[1] == top and end - ref.WINDOW_US <= r[3] <= end]
    assert got == sorted(want)
    assert len(got) > 60_000 // 7  # the held price is every window's


def test_event_time_at_and_closed_rows():
    out = ref.reference_rows("q7", 600_000, 20_000, 3, [1, 600_000])
    assert out["event_time_at"][0] == ref.BASE_TIME_US + 4 * 50
    windows = {"column": "date_time", "size_us": 10_000_000,
               "lag_us": 4_000_000}
    cols = {n: out[n] for n in ref.COLUMNS}
    closed = compare.closed(cols, windows, int(out["event_time_at"][1]))
    # 32.6 s of event time less the lag and the band: the bids up to
    # 18.6 s, so the maxima of the first window and of no later one
    assert 1 <= closed["price"].shape[0] < out["price"].shape[0]
    assert closed["date_time"].max() + 14_000_000 <= out["event_time_max"]


def window_of(first: dict, last: dict) -> dict:
    def sample(m):
        return {"t_req": 0.0, "t_resp": 0.0, "m": {
            (k, tuple(sorted(lb.items()))): v for k, lb, v in m}}
    return {"job": "q7", "scrape_start": sample(first),
            "scrape_end": sample(last)}


def test_join_layer_readers():
    j = {"job": "q7"}
    left, right = dict(j, side="left"), dict(j, side="right")
    first = [("barrier_latency_seconds_count", j, 20.0),
             ("hash_join_cleaned_rows_total", left, 1000.0),
             ("hash_join_cleaned_rows_total", right, 1.0),
             ("hash_join_emit_rows_total", left, 10.0),
             ("hash_join_emit_rows_total", right, 30.0),
             ("hash_join_reclaim_slots_total", left, 900.0),
             ("hash_join_insert_rows_total", left, 5000.0),
             ("hash_join_probe_steps_total", left, 12000.0)]
    last = [("barrier_latency_seconds_count", j, 30.0),
            ("hash_join_cleaned_rows_total", left, 3000.0),
            ("hash_join_cleaned_rows_total", right, 11.0),
            ("hash_join_emit_rows_total", left, 20.0),
            ("hash_join_emit_rows_total", right, 70.0),
            ("hash_join_reclaim_slots_total", left, 2900.0),
            ("hash_join_reclaim_slots_total", right, 10.0),
            ("hash_join_insert_rows_total", left, 9000.0),
            ("hash_join_probe_steps_total", left, 22000.0),
            ("hash_join_live_rows", left, 3210.0),
            ("hash_join_live_rows", right, 2.0)]
    w = window_of(first, last)
    got = {n: run.load_module(run.reader_path("per_layer", n)).read(w)
           for n in JOIN_LAYERS}
    assert got == {"join_live_rows": 3210.0,
                   "join_cleaned_rows_per_barrier": 201.0,
                   "join_probe_steps_per_row": 2.5,
                   "join_emit_rows_per_barrier": 5.0,
                   "join_reclaim_slots_per_barrier": 201.0}
    # a program without the join's counters (the parent commit): nothing
    # to read, and no reader raises
    bare = window_of(first[:1], last[:1])
    for n in JOIN_LAYERS:
        assert run.load_module(
            run.reader_path("per_layer", n)).read(bare) is None


def test_benchmark_json_lists_the_join_metrics_for_the_join_cell_only():
    bench = json.load(open(REAL))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for n in JOIN_LAYERS:
        assert by_name[n]["workloads"] == ["q7_join_backlog"]
        assert by_name[n]["moves"] == "rows_per_s"
    cell = run.load_cell(REAL, "q7_join_backlog")
    assert cell["config"]["reduced"] == [
        "rate_events_per_s", "horizon_rows", "bid_extra_column"]
    assert cell["traffic"]["readers"] == []
    assert {m["name"] for m in cell["end_to_end"]} == {
        "rows_per_s", "setup_s"}


def preset(tmp_path) -> str:
    bench = json.load(open(REAL))
    cells = json.load(open(
        os.path.join(HERE, "preset", "cells_q7_join.json")))
    bench.update(configs=cells["configs"], workloads=cells["workloads"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny_q7_join_backlog"] \
                if "q7_join_backlog" in m["workloads"] else []
    path = os.path.join(str(tmp_path), "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(bench, f)
    return path


def test_rehearsal_of_the_join_cell_is_correct(tmp_path):
    result, window = run.run_cell(
        "tiny_q7_join_backlog", 2**31 + 54321, 3.0, False,
        bench_path=preset(tmp_path), require_tpu=False,
        out_root=str(tmp_path))
    assert result["device"]["platform"] == "cpu"
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0
    assert result["checks"]["closed_windows"][0] >= 1
    assert result["checks"]["view_rows_differ"][0] == 0
    assert result["checks"]["counter_rows"][0] == 0
    assert result["checks"]["fused_fallbacks"][0] == 0
    assert len(window["epochs_on_disk"]) >= window["barriers"] >= 2
    got = {n: run.load_module(run.reader_path("per_layer", n)).read(window)
           for n in JOIN_LAYERS}
    # 18,400 bids/s x 14 s, give or take the barrier in flight
    assert 0.9 * 257_600 <= got["join_live_rows"] <= 1.15 * 257_600
    assert 0.9 * 32_768 <= got["join_cleaned_rows_per_barrier"] \
        <= 1.1 * 32_768
    assert got["join_reclaim_slots_per_barrier"] > 0
    assert 1.0 <= got["join_probe_steps_per_row"] < 8.0
    assert got["join_emit_rows_per_barrier"] > 0
