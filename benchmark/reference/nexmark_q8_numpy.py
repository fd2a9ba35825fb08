#!/usr/bin/env python3
"""Plain reference: the whole of Nexmark q8 ("new users") over the seeded
person and auction generators, in numpy.

    SELECT P.id, P.name, P.starttime
    FROM (SELECT id, name, window_start AS starttime, window_end AS endtime
          FROM TUMBLE(person, date_time, INTERVAL '10' SECOND)
          GROUP BY id, name, window_start, window_end) P
    JOIN (SELECT seller, window_start AS starttime, window_end AS endtime
          FROM TUMBLE(auction, date_time, INTERVAL '10' SECOND)
          GROUP BY seller, window_start, window_end) A
    ON P.id = A.seller AND P.starttime = A.starttime
       AND P.endtime = A.endtime

With nothing imported from the program.  The two sources are written out
again here in numpy: a counter-based generator, every field a splitmix64
hash of the global event number, 1 person : 3 auctions : 46 bids in every
50 events (NEXmark generator; ``connector/nexmark.py`` is the program's
own, on the device).  The hash is the one ``nexmark_numpy.py`` writes for
bids, copied here with the name lists, so that this file reads alone.
The two GROUP BYs are ``np.unique`` over their keys, the join an
``np.intersect1d`` of (id, window start) against (seller, window start);
the window end is the start plus 10 s on both sides.

A job that reads both sources pulls one person chunk for every three
auction chunks of the same size (each sweeps the same event time), so
``rows`` (``stream_rows_total``: persons + auctions) splits into
``rows / 4`` persons and ``3 rows / 4`` auctions; ``rows`` must be a
whole number of such rounds.

``run.py`` loads this into its own process (numpy only, no JAX) once the
measured window has closed and the server has stopped, calls
``reference_rows`` and compares what the server answered over pgwire with
what is written here, exactly (``compare.py``).  ``compare.closed`` keeps
a row once ``starttime`` + 10 s is at or below the watermark.

Usage: python benchmark/reference/nexmark_q8_numpy.py q8 \\
           --rows 3997696 --seed 7 [--rate 1000000] --out q8.npz \\
           [--at 65536,131072]
"""

from __future__ import annotations

import argparse
import time

import numpy as np

S = 1_000_000  # us per second
WINDOW_US = 10 * S
COLUMNS = ["id", "name", "starttime"]

TOTAL, PERSONS, AUCTIONS = 50, 1, 3
FIRST_PERSON_ID = 1000
HOT_SELLER_RATIO = 100
ACTIVE_PEOPLE = 1000
BASE_TIME_US = 1_436_918_400_000_000
_K1 = np.uint64(0x9E3779B97F4A7C15)
_K2 = np.uint64(0xBF58476D1CE4E5B9)
_K3 = np.uint64(0x94D049BB133111EB)

FIRST_NAMES = ["Peter", "Paul", "Luke", "John", "Saul", "Vicky", "Kate",
               "Julie", "Sarah", "Deiter", "Walter"]
LAST_NAMES = ["Shultz", "Abrams", "Spencer", "White", "Bartels", "Walton",
              "Smith", "Jones", "Noris"]
NAMES = np.asarray([f"{f} {l}" for f in FIRST_NAMES for l in LAST_NAMES])

#: rows generated per block
BLOCK = 1 << 20


def _rand(event_id: np.ndarray, stream: int) -> np.ndarray:
    key = np.uint64((stream * int(_K3)) & 0xFFFFFFFFFFFFFFFF)
    x = event_id.astype(np.uint64) * _K1 ^ key
    x = (x ^ (x >> np.uint64(30))) * _K2
    x = (x ^ (x >> np.uint64(27))) * _K3
    return x ^ (x >> np.uint64(31))


def _draw(event_id: np.ndarray, stream: int, bound: int) -> np.ndarray:
    return (_rand(event_id, stream) % np.uint64(bound)).astype(np.int64)


def _keyed(n: np.ndarray, seed: int) -> np.ndarray:
    """The seed folds into the hash's key, not into the id chain."""
    return n + np.int64(seed) * np.int64(2**40)


def person_columns(k0: int, k1: int, rate: int, seed: int
                   ) -> dict[str, np.ndarray]:
    """Persons number ``k0..k1``: id, name, date_time."""
    k = np.arange(k0, k1, dtype=np.int64)
    n = k * TOTAL
    eid = _keyed(n, seed)
    return {"id": n // TOTAL + FIRST_PERSON_ID,
            "name": NAMES[_draw(eid, 20, NAMES.shape[0])],
            "date_time": np.int64(BASE_TIME_US)
            + n * np.int64(max(S // max(rate, 1), 1))}


def auction_columns(k0: int, k1: int, rate: int, seed: int
                    ) -> dict[str, np.ndarray]:
    """Auctions number ``k0..k1``: seller, date_time.  99 in 100 sellers
    are the newest person's hundred; the rest one of the last 1,000
    people registered by the auction's event."""
    k = np.arange(k0, k1, dtype=np.int64)
    n = (k // AUCTIONS) * TOTAL + PERSONS + k % AUCTIONS
    eid = _keyed(n, seed)
    people = n // TOTAL + 1
    active = np.minimum(people, ACTIVE_PEOPLE)
    cold = people - active + np.minimum(
        _draw(eid, 13, ACTIVE_PEOPLE + 1), active)
    hot = (people - 1) // HOT_SELLER_RATIO * HOT_SELLER_RATIO
    seller = np.where(_draw(eid, 12, HOT_SELLER_RATIO) > 0, hot, cold)
    return {"seller": seller + FIRST_PERSON_ID,
            "date_time": np.int64(BASE_TIME_US)
            + n * np.int64(max(S // max(rate, 1), 1))}


def _blocks(fn, n_rows: int, rate: int, seed: int) -> dict[str, np.ndarray]:
    parts = [fn(k0, min(k0 + BLOCK, n_rows), rate, seed)
             for k0 in range(0, n_rows, BLOCK)] or [fn(0, 0, rate, seed)]
    return {c: np.concatenate([p[c] for p in parts]) for c in parts[0]}


def q8_rows(person: dict[str, np.ndarray], auction: dict[str, np.ndarray]
            ) -> dict[str, np.ndarray]:
    """The people who opened an auction in the window they registered
    in: distinct (id, name, window) joined to distinct (seller, window)."""
    p_win = person["date_time"] // WINDOW_US
    a_win = auction["date_time"] // WINDOW_US
    w0 = min(int(p_win.min()), int(a_win.min()))
    # one key a (person, window): ids are far below 2^40, and windows
    # are counted from the first
    span = np.int64(1 << 40)
    p_key, first = np.unique((p_win - w0) * span + person["id"],
                             return_index=True)
    a_key = np.unique((a_win - w0) * span + auction["seller"])
    both, at, _ = np.intersect1d(p_key, a_key, assume_unique=True,
                                 return_indices=True)
    return {"id": both % span, "name": person["name"][first[at]],
            "starttime": (both // span + w0) * WINDOW_US}


def reference_rows(query: str, rows: int, rate: int, seed: int,
                   at: list[int]) -> dict[str, np.ndarray]:
    """The view's columns, the event time of the newest row of either
    source after each row count of ``at``, and the event-time span
    covered."""
    if query != "q8":
        raise SystemExit(f"unknown query {query!r}")
    bad = [n for n in [rows, *at] if n <= 0 or n % 4]
    if bad or any(n > rows for n in at):
        raise SystemExit(f"rows and --at must be whole rounds (multiples "
                         f"of 4) in 1..{rows}: {bad or at}")
    person = _blocks(person_columns, rows // 4, rate, seed)
    auction = _blocks(auction_columns, 3 * rows // 4, rate, seed)

    def newest(n: int) -> int:
        return int(max(person["date_time"][: n // 4].max(),
                       auction["date_time"][: 3 * n // 4].max()))

    ts = np.concatenate([person["date_time"], auction["date_time"]])
    return {**q8_rows(person, auction),
            "at": np.asarray(at, np.int64),
            "event_time_at": np.asarray([newest(n) for n in at], np.int64),
            "event_time_min": np.int64(ts.min()),
            "event_time_max": np.int64(ts.max())}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("query", choices=["q8"])
    ap.add_argument("--rows", type=int, required=True)
    ap.add_argument("--rate", type=int, default=1_000_000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--at", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    t0 = time.perf_counter()
    at = [int(x) for x in args.at.split(",") if x]
    out = reference_rows(args.query, args.rows, args.rate, args.seed, at)
    np.savez(args.out, **out)
    print(f"NUMPY {args.query} seed={args.seed} rows_in={args.rows} "
          f"rows_out={out['id'].shape[0]} "
          f"seconds={time.perf_counter() - t0:.1f}")


if __name__ == "__main__":
    main()
