"""The five layer metrics of ``q5_inner_agg_backlog`` (PR 30): the
arithmetic on a recorded window (a canned pair of scrapes, a reduced
trace's module table), silence where the program has no such counter
(the parent commit), and the real server on the CPU."""

import pytest

import arith
import run

from test_rehearsal import rehearse

JOB = "q5"
#: counter -> its value at the window's first and last barrier
COUNTERS = {
    "hash_agg_reclaim_passes_total": (20.0, 50.0),
    "hash_agg_reclaim_slots_total": (231_000.0, 579_000.0),
    "hash_agg_live_groups": (112_000.0, 110_574.0),
    "hash_agg_tombstones": (11_500.0, 11_625.0),
    "hash_agg_table_slots": (262_144.0, 262_144.0),
}
#: a reduced trace: module -> [runs, device seconds]
MODULES = {"jit__multi": [4, 0.060], "jit__barrier_impl": [4, 0.820],
           "jit__maintain_impl": [4, 0.050]}
WANT = {
    "reclaim_passes_per_barrier": 1.0,
    "reclaim_slots_per_barrier": 11_600.0,
    "agg_table_fill_pct": 100.0 * (110_574 + 11_625) / 262_144,
    "barrier_prog_dev_ms": 205.0,
    "maintain_prog_dev_ms": 12.5,
}

def scrape(which: int, counters: bool = True) -> dict:
    lines = ['barrier_latency_seconds_count{job="%s"} %d'
             % (JOB, (20, 50)[which])]
    for name, ends in COUNTERS.items() if counters else ():
        lines.append('%s{job="%s"} %r' % (name, JOB, ends[which]))
    return {"t_req": which, "t_resp": which + 0.5,
            "m": arith.parse_scrape("\n".join(lines) + "\n")}


def window(counters: bool = True, modules: dict | None = MODULES,
           still: bool = False) -> dict:
    return {"job": JOB,
            "scrape_start": scrape(1 if still else 0, counters),
            "scrape_end": scrape(1, counters),
            "trace": None if modules is None else {"modules": modules}}


def reader(name: str):
    return run.load_module(run.reader_path("per_layer", name)).read


@pytest.mark.parametrize("name", sorted(WANT))
def test_q5_layer_on_a_recorded_window(name):
    assert reader(name)(window()) == pytest.approx(WANT[name])
    # the parent commit: no such counter, no such run in the trace (or
    # no trace at all): nothing to read, nothing raised
    assert reader(name)(window(counters=False, modules={})) is None
    assert reader(name)(window(counters=False, modules=None)) is None
    if name.endswith("_per_barrier"):
        # no barrier between the two scrapes: no mean
        assert reader(name)(window(still=True)) is None


def test_q5_layers_are_this_cells_only():
    """Each is listed for the one cell, under the layer's accepted
    name, and moves the metric the cell reports."""
    bench = run.load_json(run.os.path.join(run.ROOT, "BENCHMARK.json"))
    mine = {m["name"]: m for m in bench["per_layer"] if m["name"] in WANT}
    assert set(mine) == set(WANT)
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] == "device_wait_ms"}
    for m in mine.values():
        assert m["workloads"] == ["q5_inner_agg_backlog"]
        assert m["moves"] == "rows_per_s" and m["layer"] in layers
    cell = run.load_cell(run.os.path.join(run.ROOT, "BENCHMARK.json"),
                         "q5_inner_agg_backlog")
    assert {m["name"] for m in cell["end_to_end"]} == {"rows_per_s",
                                                       "setup_s"}
    assert set(WANT) <= {m["name"] for m in cell["per_layer"]}
    assert cell["traffic"]["readers"] == []
    rows_per_barrier = cell["config"]["system_params"][
        "chunks_per_barrier"] * cell["config"]["server"]["config_json"][
        "streaming"]["chunk_size"]
    assert cell["traffic"]["window"]["rows"] == 30 * rows_per_barrier
    assert cell["traffic"]["warmup"][0]["until"]["rows"] \
        == 20 * rows_per_barrier


def test_q5_counters_read_the_real_server(tmp_path):
    """The served node on the CPU, the preset's q5: the three counter
    readers find their series; the reclaim ran at every barrier of the
    window and gave back what the watermark retired."""
    _, w = rehearse(tmp_path, "tiny_q5_backlog", seed=11)
    passes = reader("reclaim_passes_per_barrier")(w)
    slots = reader("reclaim_slots_per_barrier")(w)
    fill = reader("agg_table_fill_pct")(w)
    # the view plans two aggregates (panes, then windows): the windows'
    # table retires at every barrier, the panes' at every 2 s slide
    assert 1.0 <= passes <= 2.0
    assert slots > 10
    assert 0 < fill < 90
