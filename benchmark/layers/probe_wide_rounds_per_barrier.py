"""Barrier and maintain programs, shadow snapshot: probe rounds a view's
upsert and a keyed join side ran at a chunk's full width
(Δ``hash_table_probe_rounds_total{width="chunk"}``, summed on the
device, read with the maintenance barrier's counters), a barrier of the
window: the rounds before the open rows fit a straggler tile."""
import arith


def read(window):
    a, b, job = window["scrape_start"], window["scrape_end"], window["job"]
    n = arith.barriers(b, job) - arith.barriers(a, job)
    rounds = arith.delta(a, b, "hash_table_probe_rounds_total", job=job,
                         width="chunk")
    if rounds is None or n <= 0:
        return None
    return rounds / n
