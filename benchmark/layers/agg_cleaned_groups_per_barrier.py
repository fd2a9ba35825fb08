"""Barrier and maintain programs, shadow snapshot: groups the watermark
retired from a job's aggregates (Δ``hash_agg_cleaned_groups_total``,
summed over the aggregates on the device, read with the maintenance
barrier's counters), a barrier of the window: a window close retires a
window's groups at once, so the mean says how many closes the window
held and how large they were."""
import arith


def read(window):
    a, b, job = window["scrape_start"], window["scrape_end"], window["job"]
    n = arith.barriers(b, job) - arith.barriers(a, job)
    groups = arith.delta(a, b, "hash_agg_cleaned_groups_total", job=job)
    if groups is None or n <= 0:
        return None
    return groups / n
