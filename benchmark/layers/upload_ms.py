"""Checkpoint upload: the uploader thread's seconds (fetch, encode,
write, manifest), a barrier."""
import arith


def read(window):
    return arith.per_barrier_ms(window["scrape_start"], window["scrape_end"],
                                window["job"],
                                "checkpoint_upload_seconds_total")
