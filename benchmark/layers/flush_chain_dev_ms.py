"""Barrier and maintain programs, shadow snapshot: device time of the
barrier program's XLA module (``jit__barrier_impl``) a run in a cell
whose flush is a chain: the counts' changes driven through the
retractable max, the join's two sides and the view, all inside this one
program because an aggregate's changes leave it at the barrier (one run
a barrier), from the trace."""


def read(window):
    tr = window["trace"]
    runs, device_s = (tr or {}).get("modules", {}).get(
        "jit__barrier_impl", (0, 0.0))
    if not runs:
        return None
    return 1000.0 * device_s / runs
