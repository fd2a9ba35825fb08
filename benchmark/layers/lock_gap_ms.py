"""Front door, engine lock, read path: what reads and scrapes take out of
a tick — the barrier loop waiting for the engine lock (span
``tick.lock_wait``), mean over the ticks of the window."""
import arith


def read(window):
    a, b = window["scrape_start"], window["scrape_end"]
    secs = arith.delta(a, b, "trace_span_seconds_total",
                       span="tick.lock_wait")
    n = arith.delta(a, b, "trace_span_total", span="tick")
    if secs is None or not n or n <= 0:
        return None
    return 1000.0 * secs / n
