"""Front door, engine lock, read path: bytes that crossed from the device
to the host in ``MaterializeExecutor.fetch`` (``mv_read_bytes_total``,
both paths: the view's occupied blocks gathered on the device, and the
table fetched whole), mean over the statements of the window."""
import arith


def read(window):
    a, b, job = window["scrape_start"], window["scrape_end"], window["job"]
    last = arith.family(b["m"], "mv_read_bytes_total", job=job)
    n = arith.delta(a, b, "trace_span_total", span="read")
    if not last or not n or n <= 0:
        return None
    first = arith.family(a["m"], "mv_read_bytes_total", job=job)
    return (sum(last.values()) - sum(first.values())) / n
