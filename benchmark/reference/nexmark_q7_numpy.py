#!/usr/bin/env python3
"""Plain reference: the whole of Nexmark q7 ("highest bid") over the
seeded bid generator, in numpy.

    SELECT B.auction, B.price, B.bidder, B.date_time
    FROM bid B
    JOIN (SELECT MAX(price) AS maxprice, window_end AS date_time
          FROM TUMBLE(bid, date_time, INTERVAL '10' SECOND)
          GROUP BY window_end) B1
      ON B.price = B1.maxprice
    WHERE B.date_time BETWEEN B1.date_time - INTERVAL '10' SECOND
                          AND B1.date_time

By brute force and with nothing imported from the program: the bid source
is written out again here (``auction``, ``bidder``, ``price``,
``date_time``; a generator on the device that differs from it shows as a
mismatch), the per-window maximum is one pass, and the join is a scan of
every bid for every window: all bids with the window's maximum price and
a time in ``[window_end - 10 s, window_end]``, both ends included,
duplicates kept.  ``rows`` counts bids produced, each once.

``run.py`` loads this into its own process (numpy only, no JAX) once the
measured window has closed and the server has stopped, calls
``reference_rows`` and compares what the server answered over pgwire with
what is written here, exactly (``compare.py``).  ``compare.closed`` keeps
a result row once its bid's time + 10 s is at or below the watermark:
every window that bid can pair with has then closed.

Usage: python benchmark/reference/nexmark_q7_numpy.py q7 \\
           --rows 11468800 --seed 7 [--rate 250000] --out q7.npz \\
           [--at 229376,458752]
"""

from __future__ import annotations

import argparse
import time

import numpy as np

S = 1_000_000  # us per second
WINDOW_US = 10 * S

#: rows generated per block
BLOCK = 1 << 20

# ---------------------------------------------------------------------------
# the bid source, written out again in numpy: a counter-based generator,
# every field a splitmix64 hash of the global event number, proportions
# 1 person : 3 auctions : 46 bids in every 50 events (NEXmark generator;
# ``connector/nexmark.py`` is the program's own, on the device).

TOTAL, PERSONS, AUCTIONS, BIDS = 50, 1, 3, 46
FIRST_AUCTION_ID = 1000
FIRST_PERSON_ID = 1000
HOT_AUCTION_RATIO = 100
HOT_BIDDER_RATIO = 100
IN_FLIGHT_AUCTIONS = 100
ACTIVE_PEOPLE = 1000
BASE_TIME_US = 1_436_918_400_000_000
_K1 = np.uint64(0x9E3779B97F4A7C15)
_K2 = np.uint64(0xBF58476D1CE4E5B9)
_K3 = np.uint64(0x94D049BB133111EB)
_KNOT_BITS = 10


def price_knots() -> np.ndarray:
    """``round(100 * 10^(6 i / 1024))`` at 1,025 knots, in software
    decimal arithmetic (the same digits on every host)."""
    import decimal

    with decimal.localcontext() as ctx:
        ctx.prec = 50
        ln10 = decimal.Decimal(10).ln()
        n = 1 << _KNOT_BITS
        return np.asarray([
            int((ln10 * (2 + decimal.Decimal(6 * i) / n)).exp()
                .to_integral_value(decimal.ROUND_HALF_EVEN))
            for i in range(n + 1)
        ], np.int64)


def _rand(event_id: np.ndarray, stream: int) -> np.ndarray:
    key = np.uint64((stream * int(_K3)) & 0xFFFFFFFFFFFFFFFF)
    x = event_id.astype(np.uint64) * _K1 ^ key
    x = (x ^ (x >> np.uint64(30))) * _K2
    x = (x ^ (x >> np.uint64(27))) * _K3
    return x ^ (x >> np.uint64(31))


def _last_person(n: np.ndarray) -> np.ndarray:
    """Base-0 id of the newest person at event number ``n`` (one person
    opens every 50 events)."""
    return n // TOTAL


def _last_auction(n: np.ndarray) -> np.ndarray:
    """Base-0 id of the newest auction at event number ``n``."""
    epoch, offset = n // TOTAL, n % TOTAL
    before = offset < PERSONS
    epoch = np.where(before, epoch - 1, epoch)
    offset = np.where(before, AUCTIONS - 1,
                      np.minimum(offset - PERSONS, AUCTIONS - 1))
    return epoch * AUCTIONS + offset


def bid_columns(k0: int, k1: int, rate: int, seed: int,
                knots: np.ndarray) -> dict[str, np.ndarray]:
    """Bids number ``k0..k1`` (bid ordinals) as host columns."""
    k = np.arange(k0, k1, dtype=np.int64)
    n = (k // BIDS) * TOTAL + PERSONS + AUCTIONS + k % BIDS
    # the seed folds into the key of the hash; the hot chains follow the
    # event number, the cold draws the keyed id (as the program's
    # generator has it)
    eid = n + np.int64(seed) * np.int64(2**40)
    date_time = np.int64(BASE_TIME_US) + n * np.int64(
        max(S // max(rate, 1), 1))

    hot = (_rand(eid, 1) % np.uint64(HOT_AUCTION_RATIO)).astype(np.int64) > 0
    hot_auction = _last_auction(n) // HOT_AUCTION_RATIO * HOT_AUCTION_RATIO
    newest = _last_auction(eid)
    oldest = np.maximum(newest - IN_FLIGHT_AUCTIONS, 0)
    cold = oldest + (_rand(eid, 2) % (newest - oldest + 1).astype(
        np.uint64)).astype(np.int64)
    auction = np.where(hot, hot_auction, cold) + FIRST_AUCTION_ID

    hot_b = (_rand(eid, 3) % np.uint64(HOT_BIDDER_RATIO)).astype(np.int64) > 0
    hot_bidder = _last_person(n) // HOT_BIDDER_RATIO * HOT_BIDDER_RATIO + 1
    people = _last_person(eid) + 1
    active = np.minimum(people, ACTIVE_PEOPLE)
    cold_b = people - active + np.minimum(
        (_rand(eid, 4) % np.uint64(ACTIVE_PEOPLE + 1)).astype(np.int64),
        active)
    bidder = np.where(hot_b, hot_bidder, cold_b) + FIRST_PERSON_ID

    r = _rand(eid, 5)
    knot = (r >> np.uint64(64 - _KNOT_BITS)).astype(np.int64)
    frac = (r >> np.uint64(32 - _KNOT_BITS)) & np.uint64(0xFFFFFFFF)
    lo, hi = knots[knot], knots[knot + 1]
    price = lo + (((hi - lo).astype(np.uint64) * frac)
                  >> np.uint64(32)).astype(np.int64)
    return {"auction": auction, "bidder": bidder, "price": price,
            "date_time": date_time}


COLUMNS = ["auction", "price", "bidder", "date_time"]


def gen_columns(table: str, n_rows: int, names: list[str] | None = None,
                rate: int = 1_000_000, seed: int = 0
                ) -> dict[str, np.ndarray]:
    """The first ``n_rows`` bids, as host columns."""
    if table != "bid":
        raise SystemExit(f"no plain generator for table {table!r}")
    names = names or COLUMNS
    knots = price_knots()
    parts: dict[str, list[np.ndarray]] = {n: [] for n in names}
    for k0 in range(0, n_rows, BLOCK):
        cols = bid_columns(k0, min(k0 + BLOCK, n_rows), rate, seed, knots)
        for n in names:
            parts[n].append(cols[n])
    return {n: np.concatenate(p) if p else np.zeros(0, np.int64)
            for n, p in parts.items()}


def window_max(price: np.ndarray, ts: np.ndarray
               ) -> tuple[np.ndarray, np.ndarray]:
    """(window_end, max price) of every 10 s tumbling window with a bid."""
    end = (ts // WINDOW_US) * WINDOW_US + WINDOW_US
    ends = np.unique(end)
    return ends, np.asarray([price[end == e].max() for e in ends], np.int64)


def q7_rows(b: dict[str, np.ndarray]) -> dict[str, np.ndarray]:
    """Every (bid, window) pair of the join, the bid's columns out."""
    price, ts = b["price"], b["date_time"]
    picks = []
    for end, top in zip(*window_max(price, ts)):
        picks.append(np.flatnonzero(
            (price == top) & (ts >= end - WINDOW_US) & (ts <= end)))
    idx = np.concatenate(picks) if picks else np.zeros(0, np.int64)
    return {n: b[n][idx] for n in COLUMNS}


def reference_rows(query: str, rows: int, rate: int, seed: int,
                   at: list[int]) -> dict[str, np.ndarray]:
    """The view's columns, the event time of the newest row after each
    row count of ``at``, and the event-time span covered."""
    if query != "q7":
        raise SystemExit(f"unknown query {query!r}")
    b = gen_columns("bid", rows, COLUMNS, rate, seed)
    ts = b["date_time"]
    bad = [n for n in at if not 0 < n <= rows]
    if bad:
        raise SystemExit(f"--at outside 1..{rows}: {bad}")
    # event time is not decreasing in the ordinal, but take the running
    # maximum anyway: the watermark follows the newest row seen
    newest = np.maximum.accumulate(ts)
    return {**q7_rows(b),
            "at": np.asarray(at, np.int64),
            "event_time_at": newest[np.asarray(at, np.int64) - 1]
            if at else np.zeros(0, np.int64),
            "event_time_min": np.int64(ts.min()),
            "event_time_max": np.int64(ts.max())}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("query", choices=["q7"])
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
          f"rows_out={out['price'].shape[0]} "
          f"seconds={time.perf_counter() - t0:.1f}")


if __name__ == "__main__":
    main()
