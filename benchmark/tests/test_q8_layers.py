"""The two layer metrics of ``q8_backlog`` on a recorded pair of scrapes,
and silence where the program has no such counter (the parent commit);
the plain reference of q8 against its own program's generator and
against a slower writing of the same query."""

import numpy as np
import pytest

import arith
import compare
import nexmark_q8_numpy as ref
import run

JOB = "nexmark_q8"
#: counter -> its value at the window's first and last barrier
COUNTERS = {
    "hash_agg_cleaned_groups_total": (400_000.0, 1_416_000.0),
    "hash_agg_emit_rows_total": (336_000.0, 1_353_000.0),
}
WANT = {"agg_cleaned_groups_per_barrier": 1_016_000.0 / 61,
        "agg_emit_rows_per_barrier": 1_017_000.0 / 61}


def scrape(which: int, counters: bool = True) -> dict:
    lines = ['barrier_latency_seconds_count{job="%s"} %d'
             % (JOB, (20, 81)[which])]
    for name, ends in COUNTERS.items() if counters else ():
        lines.append('%s{job="%s"} %r' % (name, JOB, ends[which]))
    return {"t_req": which, "t_resp": which + 0.5,
            "m": arith.parse_scrape("\n".join(lines) + "\n")}


def read(name: str, window: dict):
    return run.load_module(run.reader_path("per_layer", name)).read(window)


@pytest.mark.parametrize("name", sorted(WANT))
def test_on_recorded_scrapes(name):
    window = {"job": JOB, "scrape_start": scrape(0), "scrape_end": scrape(1)}
    assert read(name, window) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_silent_without_the_counter_or_a_barrier(name):
    # the parent commit: no such counter, nothing raised
    bare = {"job": JOB, "scrape_start": scrape(0, False),
            "scrape_end": scrape(1, False)}
    assert read(name, bare) is None
    still = {"job": JOB, "scrape_start": scrape(1), "scrape_end": scrape(1)}
    assert read(name, still) is None


def test_listed_for_the_q8_cell_only():
    bench = run.load_json(run.os.path.join(run.ROOT, "BENCHMARK.json"))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in WANT:
        m = by_name[name]
        assert m["workloads"] == ["q8_backlog"]
        assert (m["moves"], m["layer"], m["source"]) == (
            "rows_per_s", by_name["device_wait_ms"]["layer"],
            "program_counter")


# -- the reference

def test_reference_equals_the_seeded_generator():
    import jax

    import risingwave_tpu  # noqa: F401  (x64)
    from risingwave_tpu.connector.nexmark import (
        NexmarkConfig, NexmarkGenerator,
    )

    n = 30_000
    for seed in (1, 1 << 20):
        gen = NexmarkGenerator(NexmarkConfig(inter_event_us=1, seed=seed))
        a = gen._auctions_impl(jax.numpy.int64(0), n)
        p = gen._persons_impl(jax.numpy.int64(0), n)
        mine_a = ref.auction_columns(0, n, 1_000_000, seed)
        mine_p = ref.person_columns(0, n, 1_000_000, seed)
        for col, name in ((a.columns[7], "seller"),
                          (a.columns[5], "date_time")):
            assert np.array_equal(np.asarray(col), mine_a[name]), name
        assert np.array_equal(np.asarray(p.columns[0]), mine_p["id"])
        assert np.array_equal(np.asarray(p.columns[6]), mine_p["date_time"])
        names = p.columns[1]
        got = [bytes(d[:k]).decode() for d, k in zip(
            np.asarray(names.data), np.asarray(names.lens))]
        assert got == mine_p["name"].tolist()


def test_reference_against_sets():
    rows = 4 * 20_000
    got = ref.reference_rows("q8", rows, 5_000, 9, [rows // 2, rows])
    pers = ref._blocks(ref.person_columns, rows // 4, 5_000, 9)
    auct = ref._blocks(ref.auction_columns, 3 * rows // 4, 5_000, 9)
    w = ref.WINDOW_US
    sellers = {(s, t // w * w) for s, t in zip(
        auct["seller"].tolist(), auct["date_time"].tolist())}
    want = sorted({(i, n, t // w * w) for i, n, t in zip(
        pers["id"].tolist(), pers["name"].tolist(),
        pers["date_time"].tolist()) if (i, t // w * w) in sellers})
    assert sorted(zip(*(got[c].tolist() for c in ref.COLUMNS))) == want
    assert len(want) > 400
    # the watermark's event time at each count is the newest auction's
    assert got["event_time_at"].tolist() == [
        int(auct["date_time"][: 3 * n // 4].max()) for n in (rows // 2,
                                                             rows)]
    # the harness's comparison reads the integer columns and finds them
    # equal to themselves, and one row away from a row less
    cols = {c: got[c] for c in ("id", "starttime")}
    windows = {"column": "starttime", "size_us": w, "lag_us": 4_000_000}
    closed = compare.closed(cols, windows, int(got["event_time_at"][-1]))
    assert compare.rows_differ(closed, closed, ["id", "starttime"])[0] == 0
    fewer = {c: v[1:] for c, v in closed.items()}
    assert compare.rows_differ(fewer, closed, ["id", "starttime"])[0] == 1
