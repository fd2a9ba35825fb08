"""Barrier and maintain programs, shadow snapshot: distinct (group,
value) pairs the materialised input of the job's retractable min/max
holds (gauge ``hash_agg_minput_live_values{job}``: the tables as the
last maintenance pass found them), at the window's last scrape: the
state a deployment keeps."""
import arith


def read(window):
    return arith.metric(window["scrape_end"]["m"],
                        "hash_agg_minput_live_values", job=window["job"])
