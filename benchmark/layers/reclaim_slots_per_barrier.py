"""Barrier and maintain programs, shadow snapshot: tombstoned slots the
aggregate's reclaim gave back (``hash_agg_reclaim_slots_total``, summed
on the device, read with the maintenance barrier's counters), a barrier
of the window."""
import arith


def read(window):
    a, b, job = window["scrape_start"], window["scrape_end"], window["job"]
    n = arith.barriers(b, job) - arith.barriers(a, job)
    slots = arith.delta(a, b, "hash_agg_reclaim_slots_total", job=job)
    if slots is None or n <= 0:
        return None
    return slots / n
