"""Barrier and maintain programs and the shadow snapshot: host time of
``inject_barrier``, a barrier (it waits for the window program too)."""
import arith


def read(window):
    return arith.per_barrier_ms(window["scrape_start"], window["scrape_end"],
                                window["job"], "barrier_phase_seconds_sum",
                                phase="seal")
