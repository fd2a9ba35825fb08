"""Barrier and maintain programs, shadow snapshot: values put into or
taken out of the materialised input of a min/max over a retractable
input (``hash_agg_minput_changes_total``, summed on the device, read
with the maintenance barrier's counters), a barrier of the window."""
import arith


def read(window):
    a, b, job = window["scrape_start"], window["scrape_end"], window["job"]
    n = arith.barriers(b, job) - arith.barriers(a, job)
    changes = arith.delta(a, b, "hash_agg_minput_changes_total", job=job)
    if changes is None or n <= 0:
        return None
    return changes / n
