#!/usr/bin/env python3
"""Plain reference: the whole of Nexmark q5 ("hot items") over the
seeded bid generator, in numpy.

    SELECT AuctionBids.auction, AuctionBids.num, AuctionBids.starttime
    FROM (SELECT bid.auction, count(*) AS num, window_start AS starttime
          FROM HOP(bid, date_time, INTERVAL '2' SECOND, INTERVAL '10' SECOND)
          GROUP BY window_start, bid.auction) AS AuctionBids
    JOIN (SELECT max(CountBids.num) AS maxn, CountBids.starttime_c
          FROM (SELECT count(*) AS num, window_start AS starttime_c
                FROM HOP(bid, date_time, INTERVAL '2' SECOND,
                         INTERVAL '10' SECOND)
                GROUP BY bid.auction, window_start) AS CountBids
          GROUP BY CountBids.starttime_c) AS MaxBids
      ON AuctionBids.starttime = MaxBids.starttime_c
     AND AuctionBids.num >= MaxBids.maxn

With nothing imported from the program.  The bid source is the one
``nexmark_numpy.py`` beside this file writes out (loaded from that
file, not written out again).  The counts are taken here by the five
windows a bid falls in, one ``np.unique`` a window offset, which is
another road than that file's pane sums; the maximum by window is one
``np.maximum.at``; the join keeps every ``(auction, num, starttime)``
whose count is at least its window's maximum, ties kept.  ``rows``
counts bids produced, each once.

``run.py`` loads this into its own process (numpy only, no JAX) once the
measured window has closed and the server has stopped, calls
``reference_rows`` and compares what the server answered over pgwire with
what is written here, exactly (``compare.py``).  ``compare.closed``
keeps a row once ``starttime`` + 10 s is at or below the watermark.

Usage: python benchmark/reference/nexmark_q5_numpy.py q5 \\
           --rows 11468800 --seed 7 [--rate 250000] --out q5.npz \\
           [--at 229376,458752]
"""

from __future__ import annotations

import argparse
import importlib.util
import os
import time

import numpy as np

S = 1_000_000  # us per second
SLIDE_US = 2 * S
WINDOW_US = 10 * S
COLUMNS = ["auction", "num", "starttime"]


def _beside(name: str):
    """The module in the file of that name beside this one."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        name + ".py")
    spec = importlib.util.spec_from_file_location("bench_ref_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


gen_columns = _beside("nexmark_numpy").gen_columns


def window_counts(auction: np.ndarray, ts: np.ndarray
                  ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(auction, window start, bids) of every (auction, window) a bid
    falls in: a bid at ``t`` is in the windows starting at the five
    multiples of 2 s in ``(t - 10 s, t]``."""
    newest = (ts // SLIDE_US) * SLIDE_US
    lo = int(newest.min()) - WINDOW_US
    span = (int(newest.max()) - lo) // SLIDE_US + 1
    parts = []
    for k in range(WINDOW_US // SLIDE_US):
        w = (newest - k * SLIDE_US - lo) // SLIDE_US
        parts.append(np.unique(auction * span + w, return_counts=True))
    key, inv = np.unique(np.concatenate([u for u, _ in parts]),
                         return_inverse=True)
    num = np.zeros(key.shape[0], np.int64)
    np.add.at(num, inv, np.concatenate([c for _, c in parts]))
    return key // span, (key % span) * SLIDE_US + lo, num


def q5_rows(auction: np.ndarray, ts: np.ndarray) -> dict[str, np.ndarray]:
    """The hot items: every (auction, window) whose count reaches the
    window's maximum."""
    a, start, num = window_counts(auction, ts)
    windows, at = np.unique(start, return_inverse=True)
    top = np.zeros(windows.shape[0], np.int64)
    np.maximum.at(top, at, num)
    hot = num >= top[at]
    return {"auction": a[hot], "num": num[hot], "starttime": start[hot]}


def reference_rows(query: str, rows: int, rate: int, seed: int,
                   at: list[int]) -> dict[str, np.ndarray]:
    """The view's columns, the event time of the newest row after each
    row count of ``at``, and the event-time span covered."""
    if query != "q5":
        raise SystemExit(f"unknown query {query!r}")
    b = gen_columns("bid", rows, ["auction", "date_time"], rate, seed)
    ts = b["date_time"]
    bad = [n for n in at if not 0 < n <= rows]
    if bad:
        raise SystemExit(f"--at outside 1..{rows}: {bad}")
    # event time is not decreasing in the ordinal, but take the running
    # maximum anyway: the watermark follows the newest row seen
    newest = np.maximum.accumulate(ts)
    return {**q5_rows(b["auction"], ts),
            "at": np.asarray(at, np.int64),
            "event_time_at": newest[np.asarray(at, np.int64) - 1]
            if at else np.zeros(0, np.int64),
            "event_time_min": np.int64(ts.min()),
            "event_time_max": np.int64(ts.max())}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("query", choices=["q5"])
    ap.add_argument("--rows", type=int, required=True)
    ap.add_argument("--rate", type=int, default=250_000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--at", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    t0 = time.perf_counter()
    at = [int(x) for x in args.at.split(",") if x]
    out = reference_rows(args.query, args.rows, args.rate, args.seed, at)
    np.savez(args.out, **out)
    print(f"NUMPY {args.query} seed={args.seed} rows_in={args.rows} "
          f"rows_out={out['num'].shape[0]} "
          f"seconds={time.perf_counter() - t0:.1f}")


if __name__ == "__main__":
    main()
