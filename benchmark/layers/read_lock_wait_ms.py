"""Front door, engine lock, read path: a statement waiting for the engine
lock (span ``read.lock_wait``), mean over the statements of the window
(the readers' and nobody else's: the window holds no other)."""
import arith


def read(window):
    a, b = window["scrape_start"], window["scrape_end"]
    secs = arith.delta(a, b, "trace_span_seconds_total",
                       span="read.lock_wait")
    n = arith.delta(a, b, "trace_span_total", span="read")
    if secs is None or not n or n <= 0:
        return None
    return 1000.0 * secs / n
