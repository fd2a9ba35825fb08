"""Barrier and maintain programs, shadow snapshot: rows a job's
aggregates handed downstream at their flushes
(Δ``hash_agg_emit_rows_total``, a U-/U+ pair counted as two, summed on
the device and read with the maintenance barrier's counters), a barrier
of the window: what the flush's drain rounds carry into the join and
the view."""
import arith


def read(window):
    a, b, job = window["scrape_start"], window["scrape_end"], window["job"]
    n = arith.barriers(b, job) - arith.barriers(a, job)
    rows = arith.delta(a, b, "hash_agg_emit_rows_total", job=job)
    if rows is None or n <= 0:
        return None
    return rows / n
