"""Barrier and maintain programs, shadow snapshot: device time of the
maintain program's XLA module (``jit__maintain_impl``: the tables'
reclaim), a run (one run a maintenance barrier), from the trace."""


def read(window):
    tr = window["trace"]
    runs, device_s = (tr or {}).get("modules", {}).get(
        "jit__maintain_impl", (0, 0.0))
    if not runs:
        return None
    return 1000.0 * device_s / runs
