"""Front door, engine lock, read path: ``engine.query`` under the engine
lock (span ``read.execute``: plan, ``_mv_rows``, the device readback),
mean over the statements of the window."""
import arith


def read(window):
    a, b = window["scrape_start"], window["scrape_end"]
    secs = arith.delta(a, b, "trace_span_seconds_total",
                       span="read.execute")
    n = arith.delta(a, b, "trace_span_total", span="read")
    if secs is None or not n or n <= 0:
        return None
    return 1000.0 * secs / n
