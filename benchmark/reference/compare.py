"""The comparison that decides ``correct`` for a view's rows.

Exact, order-free equality of two row sets held as integer columns,
restricted to the windows the watermark has closed: a window is closed
once its end is at or below the newest event time seen less the source's
lag, and what the view holds for it can no longer change.  The number
compared is ``rows_differ``: rows of either side with no equal row on
the other.  Its limit is 0.
"""

from __future__ import annotations

import numpy as np


def closed(cols: dict[str, np.ndarray], windows: dict,
           newest_event_us: int) -> dict[str, np.ndarray]:
    """The rows whose window the watermark has closed."""
    wm = newest_event_us - windows["lag_us"]
    keep = cols[windows["column"]] + windows["size_us"] <= wm
    return {k: v[keep] for k, v in cols.items()}


def _as_records(cols: dict[str, np.ndarray], names: list[str]) -> np.ndarray:
    rec = np.empty(cols[names[0]].shape[0],
                   dtype=[(n, np.int64) for n in names])
    for n in names:
        rec[n] = cols[n]
    return np.sort(rec, order=names)


def rows_differ(got: dict[str, np.ndarray], want: dict[str, np.ndarray],
                names: list[str]) -> tuple[int, str]:
    """How many rows of either side have no equal row on the other
    (as multisets), and a line that shows the first of them."""
    g, w = _as_records(got, names), _as_records(want, names)
    if g.shape == w.shape and np.array_equal(g, w):
        return 0, ""
    ug, cg = np.unique(g, return_counts=True)
    uw, cw = np.unique(w, return_counts=True)
    both = np.concatenate([ug, uw])
    u, inv = np.unique(both, return_inverse=True)
    ng = np.bincount(inv[:ug.shape[0]], weights=cg, minlength=u.shape[0])
    nw = np.bincount(inv[ug.shape[0]:], weights=cw, minlength=u.shape[0])
    diff = np.abs(ng - nw)
    n = int(diff.sum())
    first = u[int(np.flatnonzero(diff)[0])]
    return n, (f"{g.shape[0]} rows against the reference's {w.shape[0]}; "
               f"first unmatched {dict(zip(names, first.tolist()))} "
               f"(held {int(ng[np.flatnonzero(diff)[0]])}x, "
               f"reference {int(nw[np.flatnonzero(diff)[0]])}x)")


def distinct_windows(cols: dict[str, np.ndarray], windows: dict) -> int:
    return int(np.unique(cols[windows["column"]]).shape[0])
