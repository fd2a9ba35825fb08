"""Checkpoint upload: the barrier loop standing still at the end of a
tick until its own upload has been acknowledged (span
``drain_uploads``), a barrier of the window.  The tick's wait, where
``upload_ms`` is the uploader thread's seconds."""
import arith


def read(window):
    return arith.per_barrier_ms(window["scrape_start"], window["scrape_end"],
                                window["job"], "trace_span_seconds_total",
                                span="drain_uploads")
