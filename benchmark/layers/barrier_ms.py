"""Barrier loop: host time of a barrier (window program dispatched,
barrier and maintain programs, snapshot), a barrier of the window."""
import arith


def read(window):
    return arith.per_barrier_ms(window["scrape_start"], window["scrape_end"],
                                window["job"], "barrier_latency_seconds_sum")
