"""Barrier and maintain programs, shadow snapshot: live plus tombstoned
slots of the job's aggregate tables over their size (gauges
``hash_agg_live_groups``, ``hash_agg_tombstones``,
``hash_agg_table_slots``, each summed over the job's aggregates: the
tables as the last maintenance pass found them, at their fullest), at
the window's last scrape."""
import arith


def read(window):
    m, job = window["scrape_end"]["m"], window["job"]
    live = arith.metric(m, "hash_agg_live_groups", job=job)
    tombs = arith.metric(m, "hash_agg_tombstones", job=job)
    slots = arith.metric(m, "hash_agg_table_slots", job=job)
    if live is None or tombs is None or not slots:
        return None
    return 100.0 * (live + tombs) / slots
