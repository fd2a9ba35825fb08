"""Checkpoint upload: the two ``put``s of an epoch's objects (span
``ckpt_commit.put``) and the manifest's load, GC and store
(``ckpt_commit.manifest``), mean over the uploads of the window."""
import arith


def read(window):
    a, b, job = window["scrape_start"], window["scrape_end"], window["job"]
    put = arith.delta(a, b, "trace_span_seconds_total", job=job,
                      span="ckpt_commit.put")
    manifest = arith.delta(a, b, "trace_span_seconds_total", job=job,
                           span="ckpt_commit.manifest")
    n = arith.delta(a, b, "trace_span_total", job=job, span="ckpt_commit.put")
    if put is None or manifest is None or not n or n <= 0:
        return None
    return 1000.0 * (put + manifest) / n
