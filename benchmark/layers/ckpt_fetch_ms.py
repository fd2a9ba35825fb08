"""Checkpoint upload: the wait for the shadow update's digests, the
diff and the device-to-host fetch of the dirty runs (span
``ckpt_prepare``), mean over the uploads of the window."""
import arith


def read(window):
    a, b, job = window["scrape_start"], window["scrape_end"], window["job"]
    secs = arith.delta(a, b, "trace_span_seconds_total", job=job,
                       span="ckpt_prepare")
    n = arith.delta(a, b, "trace_span_total", job=job, span="ckpt_prepare")
    if secs is None or not n or n <= 0:
        return None
    return 1000.0 * secs / n
