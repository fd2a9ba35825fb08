"""Barrier loop: the share of the window's barriers whose window the
served ticker dispatched ahead, before the previous epoch's drain
(Δ``barrier_windows_ahead_total`` / Δbarriers; PR 38).  1 where every
barrier's window went ahead; a read waiting for the engine lock, a
barrier that sealed no snapshot, or a window that is not one dispatch
keeps it back.  None where the program has no such counter."""
import arith


def read(window):
    a, b, job = window["scrape_start"], window["scrape_end"], window["job"]
    n = arith.barriers(b, job) - arith.barriers(a, job)
    ahead = arith.delta(a, b, "barrier_windows_ahead_total", job=job)
    if ahead is None or n <= 0:
        return None
    return ahead / n
