"""Window program, device: table slots an inserted row looked at on the
join's left side (Δ``hash_join_probe_steps_total`` /
Δ``hash_join_insert_rows_total``: head and target of the fused
(hash, rank) probe; the table's health as tombstones come and go)."""
import arith


def read(window):
    a, b, job = window["scrape_start"], window["scrape_end"], window["job"]
    steps = arith.delta(a, b, "hash_join_probe_steps_total", job=job,
                        side="left")
    rows = arith.delta(a, b, "hash_join_insert_rows_total", job=job,
                       side="left")
    if steps is None or not rows:
        return None
    return steps / rows
