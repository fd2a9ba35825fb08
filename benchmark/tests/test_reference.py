"""The plain reference: its own bid generator against the program's, its
queries against a slower writing of the same, and the comparison."""

import numpy as np
import pytest

import compare
import nexmark_numpy as ref

N = 200_000


def program_columns(seed: int, names: list[str]) -> dict:
    import jax

    import risingwave_tpu  # noqa: F401  (x64)
    from risingwave_tpu.connector.nexmark import (
        NexmarkConfig, NexmarkGenerator,
    )

    gen = NexmarkGenerator(NexmarkConfig(inter_event_us=1, seed=seed))
    chunk = gen._bids_impl(jax.numpy.int64(0), N)
    return {n: np.asarray(chunk.columns[chunk.schema.index_of(n)])
            for n in names}


@pytest.mark.parametrize("seed", [1, 77, 1 << 20])
def test_seeded_reference_equals_the_seeded_generator(seed):
    names = ["auction", "price", "date_time"]
    mine = ref.gen_columns("bid", N, names, 1_000_000, seed)
    theirs = program_columns(seed, names)
    for n in names:
        assert np.array_equal(mine[n], theirs[n]), n


def test_seeds_differ():
    a = ref.gen_columns("bid", N, ["auction", "price"], 1_000_000, 1)
    b = ref.gen_columns("bid", N, ["auction", "price"], 1_000_000, 2)
    assert not np.array_equal(a["price"], b["price"])
    assert not np.array_equal(a["auction"], b["auction"])


def q5_slow(auction, ts):
    """Five counts over the raw rows (as scripts/baseline_numpy.py)."""
    out: dict[tuple, int] = {}
    pane = ts // 2_000_000
    for a, p in zip(auction.tolist(), pane.tolist()):
        for k in range(5):
            key = (a, (p - k) * 2_000_000)
            out[key] = out.get(key, 0) + 1
    return out


def test_queries_against_a_slower_writing():
    c = ref.gen_columns("bid", 30_000, ["auction", "price", "date_time"],
                        20_000, 3)
    r5 = ref.q5_rows(c["auction"], c["date_time"])
    slow = q5_slow(c["auction"], c["date_time"])
    got = {(a, w): b for a, w, b in zip(r5["auction"].tolist(),
                                        r5["window_start"].tolist(),
                                        r5["bids"].tolist())}
    assert got == slow
    r7 = ref.q7_rows(c["price"], c["date_time"])
    win = c["date_time"] // 10_000_000 * 10_000_000
    for w, m, n in zip(r7["window_start"], r7["max_price"], r7["bids"]):
        assert m == c["price"][win == w].max()
        assert n == (win == w).sum()
    assert r7["bids"].sum() == 30_000


def test_event_time_at_and_closed_windows():
    out = ref.reference_rows("q7", 600_000, 20_000, 3,
                             [1, 10_000, 600_000])
    assert out["event_time_at"][0] == ref.BASE_TIME_US + 4 * 50
    assert list(out["at"]) == [1, 10_000, 600_000]
    windows = {"column": "window_start", "size_us": 10_000_000,
               "lag_us": 4_000_000}
    cols = {n: out[n] for n in ("window_start", "max_price", "bids")}
    closed = compare.closed(cols, windows, int(out["event_time_at"][2]))
    # 600,000 bids at 20,000 events/s: 32.6 s of event time, less the
    # lag: the windows that end at or before 28.6 s are closed: two
    assert compare.distinct_windows(closed, windows) == 2


def test_rows_differ_counts_unmatched_rows():
    a = {"w": np.array([0, 10, 10]), "x": np.array([1, 2, 3])}
    b = {"w": np.array([10, 0, 10]), "x": np.array([3, 1, 2])}
    assert compare.rows_differ(a, b, ["w", "x"])[0] == 0
    b["x"][0] = 4
    n, detail = compare.rows_differ(a, b, ["w", "x"])
    assert n == 2 and "unmatched" in detail
    short = {k: v[:2] for k, v in a.items()}
    assert compare.rows_differ(a, short, ["w", "x"])[0] == 1
    empty = {k: v[:0] for k, v in a.items()}
    assert compare.rows_differ(empty, empty, ["w", "x"])[0] == 0
