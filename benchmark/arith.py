"""The arithmetic from scrapes and read logs to numbers.

A *sample* is one ``/metrics`` scrape: ``{"t_req", "t_resp", "m"}`` with
the host's monotonic clock around the request and ``m`` the parsed text,
``{(name, ((label, value), ...)): float}``.  A scrape takes the engine
lock, which the barrier loop holds for the whole of a barrier, so one
that was asked during a barrier is answered as that barrier ends:
``t_resp`` of the first sample that shows a barrier is when it ended.

A *read* is ``{"due", "sent", "done", "ok", "rows"}``, on the same clock.
"""

from __future__ import annotations

import math
import statistics


def parse_scrape(text: str) -> dict[tuple, float]:
    """Prometheus text -> {(name, sorted label pairs): value}."""
    out: dict[tuple, float] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        head, value = line.rsplit(" ", 1)
        name, _, rest = head.partition("{")
        labels = []
        if rest:
            for kv in rest.rstrip("}").split(","):
                k, v = kv.split("=", 1)
                labels.append((k, v.strip('"')))
        out[(name, tuple(sorted(labels)))] = float(value)
    return out


def metric(m: dict, name: str, default=None, **labels):
    return m.get((name, tuple(sorted(labels.items()))), default)


def family(m: dict, name: str, **labels) -> dict[tuple, float]:
    """Every series of ``name`` whose labels include ``labels``."""
    want = set(labels.items())
    return {lb: v for (n, lb), v in m.items()
            if n == name and want <= set(lb)}


def delta(first: dict, last: dict, name: str, **labels) -> float | None:
    """A counter's growth between two samples; None where the later
    sample lacks it."""
    b = metric(last["m"], name, **labels)
    if b is None:
        return None
    return b - (metric(first["m"], name, **labels) or 0.0)


def barriers(sample: dict, job: str) -> float:
    return metric(sample["m"], "barrier_latency_seconds_count", 0.0, job=job)


def rows(sample: dict, job: str) -> float:
    return metric(sample["m"], "stream_rows_total", 0.0, job=job)


def barrier_edges(samples: list[dict], job: str) -> list[dict]:
    """The samples that were the first to show a new barrier."""
    out, seen = [], None
    for s in samples:
        n = barriers(s, job)
        if seen is not None and n > seen:
            out.append(s)
        seen = n if seen is None else max(seen, n)
    return out


def rate_between_barriers(edges: list[dict], job: str) -> float | None:
    """Rows taken in between the first and the last barrier seen, over
    the time between those two: not quantised by a barrier more or
    less, and a stall anywhere between them is in the denominator."""
    if len(edges) < 2:
        return None
    dt = edges[-1]["t_resp"] - edges[0]["t_resp"]
    if dt <= 0:
        return None
    return (rows(edges[-1], job) - rows(edges[0], job)) / dt


def per_barrier_ms(first: dict, last: dict, job: str, name: str,
                   **labels) -> float | None:
    """Growth of a seconds counter over the barriers between two
    samples, in ms a barrier."""
    n = barriers(last, job) - barriers(first, job)
    d = delta(first, last, name, job=job, **labels)
    if n <= 0 or d is None:
        return None
    return 1000.0 * d / n


def percentile(values: list[float], q: float) -> float | None:
    """Nearest rank: the smallest value with at least ``q`` of the
    sample at or below it."""
    if not values:
        return None
    v = sorted(values)
    return v[max(math.ceil(q * len(v)), 1) - 1]


def read_latencies_ms(reads: list[dict]) -> list[float]:
    """From when each read was due to its last row; failed reads have no
    latency (they are counted as failed)."""
    return [1000.0 * (r["done"] - r["due"]) for r in reads if r["ok"]]


def median(values: list[float]) -> float | None:
    return statistics.median(values) if values else None


def schedule(t0: float, seconds: float, sessions: int, reads_per_s: float,
             jitter: float, seed: int) -> list[list[float]]:
    """Open-loop due times for each session: one read every
    ``1 / reads_per_s`` seconds, sessions staggered evenly, each read
    moved by a share of the period taken from one fixed set of offsets
    that the seed only puts into another order (every seed offers the
    same arrivals)."""
    import random

    period = 1.0 / reads_per_s
    n = int(seconds * reads_per_s)
    out = []
    for s in range(sessions):
        offsets = [jitter * period * ((i * 0.6180339887) % 1.0 - 0.5)
                   for i in range(n)]
        random.Random(seed * 1009 + s).shuffle(offsets)
        base = t0 + s * period / sessions + 0.5 * jitter * period
        out.append([base + i * period + offsets[i] for i in range(n)])
    return out
