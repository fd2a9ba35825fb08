"""Barrier and maintain programs, shadow snapshot: the host blocked on
the chip — the one counters readback of a barrier (span
``_maintain.device_wait``), which returns once the window, barrier and
maintain programs have run; a barrier of the window."""
import arith


def read(window):
    return arith.per_barrier_ms(window["scrape_start"], window["scrape_end"],
                                window["job"], "trace_span_seconds_total",
                                span="_maintain.device_wait")
