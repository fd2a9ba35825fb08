"""``probe_wide_rounds_per_barrier``: the probe rounds a view's upsert
and a keyed join side ran at a chunk's full width, a barrier.  The
arithmetic on a pair of canned scrapes, silence where the program has
no such counter (the parent commit), and the real server on the CPU."""

import pytest

import arith
import run

from test_rehearsal import rehearse

NAME = "probe_wide_rounds_per_barrier"


def scrape(barriers: int, chunk: float | None, tile: float = 0.0) -> dict:
    lines = ['barrier_latency_seconds_count{job="q5"} %d' % barriers]
    if chunk is not None:
        lines += ['hash_table_probe_rounds_total{job="q5",width="chunk"} %r'
                  % chunk,
                  'hash_table_probe_rounds_total{job="q5",width="tile"} %r'
                  % tile]
    return {"t_req": 0, "t_resp": 0.5,
            "m": arith.parse_scrape("\n".join(lines) + "\n")}


def reader():
    return run.load_module(run.reader_path("per_layer", NAME)).read


def test_on_canned_scrapes():
    window = {"job": "q5", "scrape_start": scrape(20, 400.0, 9_000.0),
              "scrape_end": scrape(50, 760.0, 21_000.0)}
    # the tile's rounds are not the chunk's
    assert reader()(window) == pytest.approx(12.0)
    # the parent commit: no such counter, nothing raised
    assert reader()({"job": "q5", "scrape_start": scrape(20, None),
                     "scrape_end": scrape(50, None)}) is None
    # no barrier between the scrapes: no mean
    assert reader()({"job": "q5", "scrape_start": scrape(50, 760.0),
                     "scrape_end": scrape(50, 760.0)}) is None


def test_listed_for_the_two_q5_cells():
    bench = run.load_json(run.os.path.join(run.ROOT, "BENCHMARK.json"))
    m = {x["name"]: x for x in bench["per_layer"]}[NAME]
    layer = {x["name"]: x for x in bench["per_layer"]}["device_wait_ms"]
    assert m["workloads"] == ["q5_inner_agg_backlog", "q5_join_backlog"]
    assert (m["moves"], m["layer"]) == ("rows_per_s", layer["layer"])


def test_reads_the_real_server(tmp_path):
    """The served node on the CPU, the preset's q5: the view's upsert
    counts its rounds at every barrier, a round or more each."""
    _, w = rehearse(tmp_path, "tiny_q5_backlog", seed=11)
    assert reader()(w) >= 1.0
    tile = arith.delta(w["scrape_start"], w["scrape_end"],
                       "hash_table_probe_rounds_total", job=w["job"],
                       width="tile")
    assert tile is not None and tile >= 0
