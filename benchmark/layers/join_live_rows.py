"""Window program, device: rows the join's left side holds (gauge
``hash_join_live_rows{job,side="left"}``: the ring as the last
maintenance pass found it, read with the maintenance barrier's counters),
at the window's last scrape: the state a deployment keeps."""
import arith


def read(window):
    return arith.metric(window["scrape_end"]["m"], "hash_join_live_rows",
                        job=window["job"], side="left")
