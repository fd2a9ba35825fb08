"""Checkpoint upload: bytes that crossed from the device to the host in
``CheckpointStore.prepare`` (``checkpoint_fetch_bytes_total``, both
paths: dirty blocks gathered on the device, and leaves fetched whole),
mean over the uploads of the window."""
import arith


def read(window):
    a, b, job = window["scrape_start"], window["scrape_end"], window["job"]
    last = arith.family(b["m"], "checkpoint_fetch_bytes_total", job=job)
    n = arith.delta(a, b, "trace_span_total", job=job, span="ckpt_prepare")
    if not last or not n or n <= 0:
        return None
    first = arith.family(a["m"], "checkpoint_fetch_bytes_total", job=job)
    return (sum(last.values()) - sum(first.values())) / n
