"""Barrier and maintain programs, shadow snapshot: rows the join's
retractable left side holds, one a slot by its stream key (gauge
``hash_join_live_rows{job,side="left"}`` as the last maintenance pass
found it), at the window's last scrape: the state a deployment keeps.
Read only where the program counts retractions on that side
(``hash_join_delete_rows_total``): a program without the counter has no
such side to report."""
import arith


def read(window):
    m, job = window["scrape_end"]["m"], window["job"]
    if arith.metric(m, "hash_join_delete_rows_total", job=job,
                    side="left") is None:
        return None
    return arith.metric(m, "hash_join_live_rows", job=job, side="left")
