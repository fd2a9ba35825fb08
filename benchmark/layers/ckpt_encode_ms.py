"""Checkpoint upload: ``np.savez`` into memory, ``pickle`` of the meta
object and crc32c of both (span ``ckpt_commit.encode``), mean over the
uploads of the window."""
import arith


def read(window):
    a, b, job = window["scrape_start"], window["scrape_end"], window["job"]
    secs = arith.delta(a, b, "trace_span_seconds_total", job=job,
                       span="ckpt_commit.encode")
    n = arith.delta(a, b, "trace_span_total", job=job,
                    span="ckpt_commit.encode")
    if secs is None or not n or n <= 0:
        return None
    return 1000.0 * secs / n
