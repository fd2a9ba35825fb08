"""``read_fetch_bytes_per_read``: the arithmetic on a canned pair of
scrapes, and silence where the program has no such counter (the parent
commit)."""

import pytest

import arith
import run

#: path -> bytes at the window's first and last barrier; 200 statements
#: between them (199 gathered reads of 819,204 and one whole table of
#: 54,525,952 behind its windows)
MOVED = {"gathered": (8_192_040, 172_032_840),
         "whole": (0, 54_525_952)}


def scrape(which: int, paths=("gathered", "whole")) -> dict:
    lines = ['barrier_latency_seconds_count{job="q7"} %d' % (10, 96)[which],
             'trace_span_total{span="read"} %d.0' % (10, 210)[which],
             'mv_read_bytes_total{job="other",path="whole"} 7.0']
    lines += ['mv_read_bytes_total{job="q7",path="%s"} %d.0'
              % (p, MOVED[p][which]) for p in paths]
    return {"t_req": which, "t_resp": which + 0.5,
            "m": arith.parse_scrape("\n".join(lines) + "\n")}


def reader():
    return run.load_module(
        run.reader_path("per_layer", "read_fetch_bytes_per_read")).read


def window(first, last):
    return {"job": "q7", "scrape_start": first, "scrape_end": last}


def test_bytes_of_both_paths_over_the_statements_of_the_window():
    assert reader()(window(scrape(0), scrape(1))) == pytest.approx(
        (163_840_800 + 54_525_952) / 200)
    # a few-row view never takes the whole path: one series
    only = window(scrape(0, ("gathered",)), scrape(1, ("gathered",)))
    assert reader()(only) == pytest.approx(819_204)
    # a path first taken inside the window counts from nothing
    late = window(scrape(0, ("gathered",)), scrape(1))
    assert reader()(late) == pytest.approx(
        (163_840_800 + 54_525_952) / 200)


def test_silent_without_the_counter_or_without_a_read():
    # the parent commit: nothing to read, nothing raised, and the
    # result line leaves the metric out
    assert reader()(window(scrape(0, ()), scrape(1, ()))) is None
    assert reader()(window(scrape(1), scrape(1))) is None


def test_listed_for_the_cell_with_a_reader_under_its_layer():
    import json
    import os

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"]
              if m["name"] == "read_fetch_bytes_per_read"]
    assert entry == {
        "name": "read_fetch_bytes_per_read", "unit": "bytes",
        "better": "lower", "source": "program_counter",
        "layer": "front door, engine lock, read path",
        "moves": "read_p50_ms", "workloads": ["q7_inner_agg_backlog"],
    }
    assert bench["per_layer"][-1] == entry


def test_reads_the_real_server(tmp_path):
    """The served node on the CPU: the counter is there under the
    view's name, every read of the few-row view gathered, and each
    moved less than the table."""
    from test_rehearsal import rehearse

    _, win = rehearse(tmp_path, "tiny_q7_backlog", seed=12)
    got = reader()(win)
    assert got is not None and got > 0, got
    series = arith.family(win["scrape_end"]["m"], "mv_read_bytes_total",
                          job=win["job"])
    paths = {dict(labels)["path"] for labels in series}
    assert paths == {"gathered"}, paths
