"""Barrier loop: one turn of the served node's barrier loop, from asking
for the engine lock to ``Engine.tick`` returning (span ``tick``), mean
over the ticks of the window.  Rows a barrier over ``rows_per_s``."""
import arith


def read(window):
    a, b = window["scrape_start"], window["scrape_end"]
    secs = arith.delta(a, b, "trace_span_seconds_total", span="tick")
    n = arith.delta(a, b, "trace_span_total", span="tick")
    if secs is None or not n or n <= 0:
        return None
    return 1000.0 * secs / n
