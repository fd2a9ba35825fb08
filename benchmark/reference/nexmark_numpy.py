#!/usr/bin/env python3
"""Plain reference: Nexmark views over the seeded generator, in numpy.

The queries are those of ``scripts/baseline_numpy.py`` (PR 22), kept here
so that no later PR can change the yardstick, with a seed.  The rows a view must hold
after its source has produced ``--rows`` rows, computed without any of
the engine's operators and with nothing imported from the program: the
bid source is written out again here in numpy (a generator on the device
that differs from it shows as a mismatch), the queries are plain numpy.

- q5: bids per (auction, window_start), HOP 2 s slide / 10 s size: the
  inner aggregate of Nexmark q5, without its join to the per-window
  maximum
- q7: (max price, bids) per 10 s tumbling window: the inner aggregate
  of Nexmark q7, without its join back to bid

``run.py`` loads this into its own process (numpy only, no JAX) once the
measured window has closed and the server has stopped, calls
``reference_rows``, and compares what the server answered over pgwire
with what is written here, exactly (``compare.py``).

Usage: python benchmark/reference/nexmark_numpy.py q7 \\
           --rows 23592960 --seed 7 [--rate 1000000] --out q7.npz \\
           [--at 1048576,2097152]
``--at`` lists row counts; the npz then holds ``event_time_at``, the
event time (us) of the newest row once the source had produced each.
"""

from __future__ import annotations

import argparse
import time

import numpy as np

S = 1_000_000  # us per second

#: rows generated per block
BLOCK = 1 << 20


# ---------------------------------------------------------------------------
# the bid source, written out again in numpy (nothing imported from the
# program): a counter-based generator, every field a splitmix64 hash of
# the global event number, proportions 1 person : 3 auctions : 46 bids
# in every 50 events (NEXmark generator; ``connector/nexmark.py`` is the
# program's own, on the device).

TOTAL, PERSONS, AUCTIONS, BIDS = 50, 1, 3, 46
FIRST_AUCTION_ID = 1000
HOT_AUCTION_RATIO = 100
IN_FLIGHT_AUCTIONS = 100
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


def _last_auction(n: np.ndarray) -> np.ndarray:
    """Base-0 id of the newest auction at event number ``n``."""
    epoch, offset = n // TOTAL, n % TOTAL
    before = offset < PERSONS
    epoch = np.where(before, epoch - 1, epoch)
    offset = np.where(before, AUCTIONS - 1,
                      np.minimum(offset - PERSONS, AUCTIONS - 1))
    return epoch * AUCTIONS + offset


def bid_columns(k0: int, k1: int, names: list[str], rate: int, seed: int,
                knots: np.ndarray) -> dict[str, np.ndarray]:
    """Bids number ``k0..k1`` (bid ordinals) as host columns."""
    k = np.arange(k0, k1, dtype=np.int64)
    n = (k // BIDS) * TOTAL + PERSONS + AUCTIONS + k % BIDS
    # the seed folds into the key of the hash; the hot-auction chain
    # follows the event number, the cold draw the keyed id (as the
    # program's generator has it)
    eid = n + np.int64(seed) * np.int64(2**40)
    out = {}
    if "date_time" in names:
        out["date_time"] = np.int64(BASE_TIME_US) + n * np.int64(
            max(S // max(rate, 1), 1))
    if "auction" in names:
        hot = (_rand(eid, 1) % np.uint64(HOT_AUCTION_RATIO)
               ).astype(np.int64) > 0
        hot_auction = _last_auction(n) // HOT_AUCTION_RATIO \
            * HOT_AUCTION_RATIO
        newest = _last_auction(eid)
        oldest = np.maximum(newest - IN_FLIGHT_AUCTIONS, 0)
        cold = oldest + (_rand(eid, 2) % (newest - oldest + 1).astype(
            np.uint64)).astype(np.int64)
        out["auction"] = np.where(hot, hot_auction, cold) \
            + FIRST_AUCTION_ID
    if "price" in names:
        r = _rand(eid, 5)
        knot = (r >> np.uint64(64 - _KNOT_BITS)).astype(np.int64)
        frac = (r >> np.uint64(32 - _KNOT_BITS)) & np.uint64(0xFFFFFFFF)
        lo, hi = knots[knot], knots[knot + 1]
        out["price"] = lo + (((hi - lo).astype(np.uint64) * frac)
                             >> np.uint64(32)).astype(np.int64)
    return out


def gen_columns(table: str, n_rows: int, names: list[str],
                rate: int = 1_000_000, seed: int = 0
                ) -> dict[str, np.ndarray]:
    """The first ``n_rows`` rows of one Nexmark table, as host columns."""
    if table != "bid":
        raise SystemExit(f"no plain generator for table {table!r} yet")
    knots = price_knots()
    parts: dict[str, list[np.ndarray]] = {n: [] for n in names}
    for k0 in range(0, n_rows, BLOCK):
        cols = bid_columns(k0, min(k0 + BLOCK, n_rows), names, rate, seed,
                           knots)
        for n in names:
            parts[n].append(cols[n])
    return {n: np.concatenate(p) if p else np.zeros(0, np.int64)
            for n, p in parts.items()}


def q5_rows(auction: np.ndarray, ts: np.ndarray) -> dict[str, np.ndarray]:
    slide, n_win = 2 * S, 5
    pane = ts // slide
    lo = pane.min() - (n_win - 1)
    span = int(pane.max() - lo) + 1
    # bids per (auction, pane), then each pane counted into the five
    # windows that hold it
    u, c = np.unique(auction * span + (pane - lo), return_counts=True)
    keys = np.concatenate([u - k for k in range(n_win)])
    key, inv = np.unique(keys, return_inverse=True)
    bids = np.bincount(inv, weights=np.tile(c, n_win),
                       minlength=key.shape[0]).astype(np.int64)
    return {"auction": key // span,
            "window_start": (key % span + lo) * slide, "bids": bids}


def q7_rows(price: np.ndarray, ts: np.ndarray) -> dict[str, np.ndarray]:
    win = (ts // (10 * S)) * (10 * S)
    if not np.all(win[1:] >= win[:-1]):
        order = np.argsort(win, kind="stable")
        win, price = win[order], price[order]
    starts = np.flatnonzero(np.r_[True, win[1:] != win[:-1]])
    return {"window_start": win[starts],
            "max_price": np.maximum.reduceat(price, starts),
            "bids": np.diff(np.r_[starts, win.shape[0]])}


QUERIES = {
    "q5": (["auction", "date_time"], q5_rows),
    "q7": (["price", "date_time"], q7_rows),
}


def reference_rows(query: str, rows: int, rate: int, seed: int,
                   at: list[int]) -> dict[str, np.ndarray]:
    """The view's columns, the event time of the newest row after each
    row count of ``at``, and the event-time span covered."""
    if query not in QUERIES:
        raise SystemExit(f"unknown query {query!r}")
    cols, fn = QUERIES[query]
    b = gen_columns("bid", rows, cols, rate, seed)
    ts = b["date_time"]
    out = fn(b[cols[0]], ts)
    bad = [n for n in at if not 0 < n <= rows]
    if bad:
        raise SystemExit(f"--at outside 1..{rows}: {bad}")
    # event time is not decreasing in the ordinal, but take the
    # running maximum anyway: the watermark follows the newest row seen
    newest = np.maximum.accumulate(ts)
    return {**out,
            "at": np.asarray(at, np.int64),
            "event_time_at": newest[np.asarray(at, np.int64) - 1]
            if at else np.zeros(0, np.int64),
            "event_time_min": np.int64(ts.min()),
            "event_time_max": np.int64(ts.max())}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("query", choices=sorted(QUERIES))
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
    n = next(iter(out.values())).shape[0]
    print(f"NUMPY {args.query} seed={args.seed} rows_in={args.rows} "
          f"rows_out={n} seconds={time.perf_counter() - t0:.1f}")


if __name__ == "__main__":
    main()
