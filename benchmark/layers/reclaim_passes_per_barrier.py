"""Barrier and maintain programs, shadow snapshot: rebuilds of the
aggregate's group table (``hash_agg_reclaim_passes_total``), a barrier
of the window: 1 where every barrier reclaims, 0 where the table is
left alone."""
import arith


def read(window):
    a, b, job = window["scrape_start"], window["scrape_end"], window["job"]
    n = arith.barriers(b, job) - arith.barriers(a, job)
    passes = arith.delta(a, b, "hash_agg_reclaim_passes_total", job=job)
    if passes is None or n <= 0:
        return None
    return passes / n
