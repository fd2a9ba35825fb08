"""Barrier and maintain programs, shadow snapshot: rows the join handed
downstream for each change it took in (Δ``hash_join_emit_rows_total`` /
Δ(``hash_join_insert_rows_total`` + ``hash_join_delete_rows_total``),
both sides): one or two with the residual predicate applied where the
pairs are staged, a window's every row with it behind the join."""
import arith


def read(window):
    a, b, job = window["scrape_start"], window["scrape_end"], window["job"]

    def both(name):
        sides = [arith.delta(a, b, name, job=job, side=side)
                 for side in ("left", "right")]
        if all(d is None for d in sides):
            return None
        return sum(d or 0.0 for d in sides)

    emitted = both("hash_join_emit_rows_total")
    deleted = both("hash_join_delete_rows_total")
    inserted = both("hash_join_insert_rows_total")
    if emitted is None or deleted is None or inserted is None \
            or inserted + deleted <= 0:
        return None
    return emitted / (inserted + deleted)
