"""Window program, host side: host time of the asynchronous dispatch
call, a barrier.  Never device time."""
import arith


def read(window):
    return arith.per_barrier_ms(window["scrape_start"], window["scrape_end"],
                                window["job"], "barrier_phase_seconds_sum",
                                phase="dispatch")
