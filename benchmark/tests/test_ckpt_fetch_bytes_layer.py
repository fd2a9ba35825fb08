"""``ckpt_fetch_bytes_per_barrier``: the arithmetic on a canned pair of
scrapes, and silence where the program has no such counter (the parent
commit)."""

import pytest

import arith
import run

#: path -> bytes at the window's first and last barrier; ten uploads
#: between them (one full of 134,217,728 and nine deltas of 2,097,152)
MOVED = {"gathered": (4_194_304, 23_068_672),
         "whole": (134_217_728, 268_435_456)}


def scrape(which: int, paths=("gathered", "whole")) -> dict:
    lines = ['barrier_latency_seconds_count{job="q7"} %d' % (10, 20)[which],
             'trace_span_total{job="q7",span="ckpt_prepare"} %d.0'
             % (10, 20)[which],
             'checkpoint_fetch_bytes_total{job="other",path="whole"} 7.0']
    lines += ['checkpoint_fetch_bytes_total{job="q7",path="%s"} %d.0'
              % (p, MOVED[p][which]) for p in paths]
    return {"t_req": which, "t_resp": which + 0.5,
            "m": arith.parse_scrape("\n".join(lines) + "\n")}


def reader():
    return run.load_module(
        run.reader_path("per_layer", "ckpt_fetch_bytes_per_barrier")).read


def window(first, last):
    return {"job": "q7", "scrape_start": first, "scrape_end": last}


def test_bytes_of_both_paths_over_the_uploads_of_the_window():
    assert reader()(window(scrape(0), scrape(1))) == pytest.approx(
        (18_874_368 + 134_217_728) / 10)
    # a job whose every checkpoint is full never gathers: one path
    only_whole = window(scrape(0, ("whole",)), scrape(1, ("whole",)))
    assert reader()(only_whole) == pytest.approx(13_421_772.8)
    # a path first taken inside the window counts from nothing
    late = window(scrape(0, ("whole",)), scrape(1))
    assert reader()(late) == pytest.approx(
        (23_068_672 + 134_217_728) / 10)


def test_silent_without_the_counter_or_without_an_upload():
    # the parent commit: nothing to read, nothing raised, and the
    # result line leaves the metric out
    assert reader()(window(scrape(0, ()), scrape(1, ()))) is None
    assert reader()(window(scrape(1), scrape(1))) is None


def test_listed_for_both_cells_under_its_layer():
    import json
    import os

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"]
              if m["name"] == "ckpt_fetch_bytes_per_barrier"]
    assert entry == {
        "name": "ckpt_fetch_bytes_per_barrier", "unit": "bytes",
        "better": "lower", "source": "program_counter",
        "layer": "checkpoint upload", "moves": "rows_per_s",
        "workloads": ["q7_inner_agg_backlog", "q5_inner_agg_backlog"],
    }


def test_reads_the_real_server(tmp_path):
    """The served node on the CPU: the counter is there under the
    view's job name, and a barrier's checkpoint moved something."""
    from test_rehearsal import rehearse

    _, win = rehearse(tmp_path, "tiny_q7_backlog", seed=11)
    got = reader()(win)
    assert got is not None and got > 0, got
    series = arith.family(win["scrape_end"]["m"],
                          "checkpoint_fetch_bytes_total", job=win["job"])
    paths = {dict(labels)["path"] for labels in series}
    assert paths <= {"gathered", "whole"} and "whole" in paths, paths
