"""Plain reference: the bench queries over the same ordinals in numpy.

The rows a view must hold after its sources have produced a given
number of rows, computed without any of the engine's operators: events
come from the repo's Nexmark generator run on the CPU backend in bulk
blocks (the one piece shared with the system under test — a generator
that differs by backend shows as a mismatch), the queries are plain
numpy (sort, unique, reduceat, searchsorted).

- q5: bids per (auction, window_start), HOP 2 s slide / 10 s size
- q7: (max price, bids) per 10 s tumbling window
- q8: persons x auctions ON id = seller AND same 1 s tumbling window

``chip_smoke.py`` runs this as a CPU child and compares what the server
answers over pgwire with the rows written here, exactly.

Usage: JAX_PLATFORMS=cpu python scripts/baseline_numpy.py q7 \\
           --rows 23592960 [--rate 1000000] --out q7.npz
``--rows`` counts bid rows (q5/q7) or person rows (q8, which reads
three auctions for every person, as the engine's pacing does).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

S = 1_000_000  # us per second

#: rows generated per jitted call
BLOCK = 1 << 20


def gen_columns(table: str, n_rows: int, names: list[str],
                rate: int = 1_000_000) -> dict[str, np.ndarray]:
    """The first ``n_rows`` rows of one Nexmark table, as host columns
    (strings as fixed-width ``S`` arrays)."""
    import jax

    import risingwave_tpu  # noqa: F401  (x64)
    from risingwave_tpu.common.chunk import StrCol
    from risingwave_tpu.connector.nexmark import (
        NexmarkConfig, NexmarkGenerator,
    )

    gen = NexmarkGenerator(
        NexmarkConfig(inter_event_us=max(S // max(rate, 1), 1)))
    impl = {"bid": gen._bids_impl, "auction": gen._auctions_impl,
            "person": gen._persons_impl}[table]
    block = min(BLOCK, max(n_rows, 1))

    @jax.jit
    def pick(k0):
        chunk = impl(k0, block)
        return [chunk.columns[chunk.schema.index_of(n)] for n in names]

    parts: list[list[np.ndarray]] = [[] for _ in names]
    for k0 in range(0, n_rows, block):
        for i, col in enumerate(pick(jax.numpy.int64(k0))):
            if isinstance(col, StrCol):
                data = np.asarray(col.data)
                lens = np.asarray(col.lens)
                data = np.where(
                    np.arange(data.shape[1])[None, :] < lens[:, None],
                    data, 0).astype(np.uint8)
                col = np.ascontiguousarray(data).view(
                    f"S{data.shape[1]}")[:, 0]
            parts[i].append(np.asarray(col))
    return {n: np.concatenate(p)[:n_rows] for n, p in zip(names, parts)}


def q5_rows(auction: np.ndarray, ts: np.ndarray) -> dict[str, np.ndarray]:
    slide, n_win = 2 * S, 5
    pane = ts // slide
    lo = pane.min() - (n_win - 1)
    span = int(pane.max() - lo) + 1
    keys, counts = [], []
    for k in range(n_win):
        # the window that starts k slides before the row's own pane
        u, c = np.unique(auction * span + (pane - k - lo),
                         return_counts=True)
        keys.append(u)
        counts.append(c)
    key, inv = np.unique(np.concatenate(keys), return_inverse=True)
    bids = np.bincount(inv, weights=np.concatenate(counts),
                       minlength=key.shape[0]).astype(np.int64)
    return {"auction": key // span,
            "window_start": (key % span + lo) * slide, "bids": bids}


def q7_rows(price: np.ndarray, ts: np.ndarray) -> dict[str, np.ndarray]:
    win = (ts // (10 * S)) * (10 * S)
    order = np.argsort(win, kind="stable")
    win, price = win[order], price[order]
    starts = np.flatnonzero(np.r_[True, win[1:] != win[:-1]])
    return {"window_start": win[starts],
            "max_price": np.maximum.reduceat(price, starts),
            "bids": np.diff(np.r_[starts, win.shape[0]])}


def q8_rows(pid, pname, pts, seller, reserve, ats) -> dict[str, np.ndarray]:
    order = np.argsort(pid, kind="stable")
    pid, pname, pw = pid[order], pname[order], (pts // S)[order]
    # every person (ids may repeat in principle) x every auction of theirs
    lo = np.searchsorted(pid, seller, side="left")
    hi = np.searchsorted(pid, seller, side="right")
    a_idx = np.repeat(np.arange(seller.shape[0]), hi - lo)
    p_idx = np.arange(a_idx.shape[0]) - np.repeat(
        np.cumsum(hi - lo) - (hi - lo), hi - lo) + lo[a_idx]
    same = pw[p_idx] == (ats // S)[a_idx]
    p_idx, a_idx = p_idx[same], a_idx[same]
    return {"id": pid[p_idx], "name": pname[p_idx],
            "reserve": reserve[a_idx]}


def reference_rows(query: str, rows: int, rate: int) -> dict[str, np.ndarray]:
    """The view's columns, plus the event-time span its sources have
    covered (``event_time_min``/``_max``, us: the watermark is the
    latter less the sources' lag)."""
    if query in ("q5", "q7"):
        cols = ["auction", "date_time"] if query == "q5" \
            else ["price", "date_time"]
        b = gen_columns("bid", rows, cols, rate)
        fn = q5_rows if query == "q5" else q7_rows
        out = fn(b[cols[0]], b["date_time"])
        ts_min, ts_max = b["date_time"].min(), b["date_time"].max()
    elif query == "q8":
        p = gen_columns("person", rows, ["id", "name", "date_time"], rate)
        a = gen_columns("auction", 3 * rows,
                        ["seller", "reserve", "date_time"], rate)
        out = q8_rows(p["id"], p["name"], p["date_time"],
                      a["seller"], a["reserve"], a["date_time"])
        ts_min = min(p["date_time"].min(), a["date_time"].min())
        ts_max = min(p["date_time"].max(), a["date_time"].max())
    else:
        raise SystemExit(f"unknown query {query!r}")
    return {**out, "event_time_min": np.int64(ts_min),
            "event_time_max": np.int64(ts_max)}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("query", choices=["q5", "q7", "q8"])
    ap.add_argument("--rows", type=int, required=True)
    ap.add_argument("--rate", type=int, default=1_000_000)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    t0 = time.perf_counter()
    out = reference_rows(args.query, args.rows, args.rate)
    np.savez(args.out, **out)
    n = next(iter(out.values())).shape[0]
    print(f"NUMPY {args.query} rows_in={args.rows} rows_out={n} "
          f"seconds={time.perf_counter() - t0:.1f}")


if __name__ == "__main__":
    main()
