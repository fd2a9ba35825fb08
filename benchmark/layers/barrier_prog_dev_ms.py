"""Barrier and maintain programs, shadow snapshot: device time of the
barrier program's XLA module (``jit__barrier_impl``: flush, the view's
upsert, drain rounds, watermark cleaning, counters), a run (one run a
barrier), from the trace."""


def read(window):
    tr = window["trace"]
    runs, device_s = (tr or {}).get("modules", {}).get(
        "jit__barrier_impl", (0, 0.0))
    if not runs:
        return None
    return 1000.0 * device_s / runs
