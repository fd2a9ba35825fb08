"""Barrier and maintain programs, shadow snapshot: rows a retraction
took out of the join's sides (``hash_join_delete_rows_total``, both
sides, summed on the device and read with the maintenance barrier's
counters), a barrier of the window."""
import arith


def read(window):
    a, b, job = window["scrape_start"], window["scrape_end"], window["job"]
    n = arith.barriers(b, job) - arith.barriers(a, job)
    name = "hash_join_delete_rows_total"
    sides = [arith.delta(a, b, name, job=job, side=side)
             for side in ("left", "right")]
    if n <= 0 or all(d is None for d in sides):
        return None
    return sum(d or 0.0 for d in sides) / n
