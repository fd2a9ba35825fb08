"""From a ``jax.profiler`` trace to busy time, per-program time and the
longest idle gaps.

Two steps, so that the arithmetic can be checked on a small recorded
trace without the profiler's reader: ``load_xplane`` turns an
``.xplane.pb`` into plain events ``[plane, line, name, start_ns, dur_ns]``
and ``reduce_events`` does the rest.  Device planes are those named
``/device:TPU:<n>``; on them the line ``XLA Modules`` holds one event for
each run of a compiled program (named ``<jit name>(<fingerprint>)``) and
``XLA Ops`` one for each operation inside it.  Every other plane is the
host's: its lines are threads.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINE, OP_LINE = "XLA Modules", "XLA Ops"
#: plane, line and name of the one event that spans the whole trace
SPAN = "traced-window"


def load_xplane(path: str, host_name_has: list[str]) -> list[list]:
    """Events of the device planes' module and op lines, and those host
    events whose name holds one of ``host_name_has``."""
    from jax.profiler import ProfileData

    out = []
    lo, hi = None, None
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            keep_all = device and line.name in (MODULE_LINE, OP_LINE)
            for ev in line.events:
                start, dur = int(ev.start_ns), int(ev.duration_ns)
                lo = start if lo is None else min(lo, start)
                hi = start + dur if hi is None else max(hi, start + dur)
                if device and not keep_all:
                    continue
                name = short_name(ev.name)
                if not device and not any(h in name for h in host_name_has):
                    continue
                out.append([plane.name, line.name, name, start, dur])
    if lo is not None:
        # the traced window: first to last event of any plane
        out.append([SPAN, SPAN, SPAN, lo, hi - lo])
    return out


def short_name(name: str) -> str:
    """An operation's name without its HLO text (``%while.93 = (...)``),
    a Python frame's without its line (``$engine.py:2167 tick``): what
    stays is stable from one build of the program to the next."""
    name = name.split(" = ", 1)[0]
    if name.startswith("$") and " " in name:
        where, func = name[1:].split(" ", 1)
        name = f"{where.split(':', 1)[0]} {func}"
    return name[:120]


def merge(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Union of [start, end) intervals, sorted."""
    out: list[tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def module_name(event_name: str) -> str:
    """``jit__multi(1234)`` -> ``jit__multi``."""
    return event_name.split("(", 1)[0]


def reduce_events(events: list[list], cfg: dict) -> dict:
    """``cfg`` is the cell's ``trace`` entry: ``window_program`` (the
    window program's module name) and ``host_phases`` (names of host
    events an idle gap may be put down to, innermost first wins)."""
    planes = sorted({e[0] for e in events if DEVICE_PLANE.match(e[0])})
    if not planes:
        raise ValueError("the trace has no device plane: nothing ran on "
                         "a TPU while it was taken")
    host = [e for e in events
            if not DEVICE_PLANE.match(e[0]) and e[0] != SPAN]
    span = [e for e in events if e[0] == SPAN]
    busy_s, window_s = 0.0, 0.0
    modules: dict[str, list] = {}
    ops: dict[str, float] = {}
    gaps: list[tuple[int, int, int]] = []
    for plane in planes:
        mine = [e for e in events if e[0] == plane]
        spans = [(e[3], e[3] + e[4]) for e in mine]
        busy = merge(spans)
        if span:
            t_lo, t_hi = span[0][3], span[0][3] + span[0][4]
            busy = [(t_lo, t_lo)] + busy + [(t_hi, t_hi)]
        else:
            t_lo, t_hi = busy[0][0], busy[-1][1]
        busy_s += sum(e - s for s, e in busy) / 1e9
        window_s += (t_hi - t_lo) / 1e9
        for e in mine:
            if e[1] == MODULE_LINE:
                m = modules.setdefault(module_name(e[2]), [0, 0.0])
                m[0] += 1
                m[1] += e[4] / 1e9
            else:
                ops[e[2]] = ops.get(e[2], 0.0) + e[4] / 1e9
        gaps += [(b[0] - a[1], a[1], b[0]) for a, b in zip(busy, busy[1:])]
    n = len(planes)
    for m in modules.values():
        m[0], m[1] = m[0] / n, m[1] / n

    def put_down_to(lo: int, hi: int) -> str:
        mid = (lo + hi) // 2
        over = [e for e in host if e[3] <= mid < e[3] + e[4]]
        if not over:
            return "host: nothing recorded"
        return "host: " + min(over, key=lambda e: e[4])[2]

    by_cause: dict[str, float] = {}
    for length, lo, hi in sorted(gaps, reverse=True)[:200]:
        cause = put_down_to(lo, hi)
        by_cause[cause] = by_cause.get(cause, 0.0) + length / 1e9
    wanted = cfg["window_program"]
    wp = modules.get(wanted, [0, 0.0])
    top = sorted(modules.items(), key=lambda kv: -kv[1][1])
    return {
        "busy_s": busy_s / n, "window_s": window_s / n,
        "modules": {k: v for k, v in top},
        "window_program": {"name": wanted, "runs": wp[0],
                           "device_s": wp[1]},
        # whole programs first, then the operations inside them
        "device_ops": ([[f"program {k}", v[1]] for k, v in top[:4]]
                       + [[k, v / n] for k, v in sorted(
                           ops.items(), key=lambda kv: -kv[1])[:6]]),
        "idle_gaps": [[k, v / n] for k, v in sorted(
            by_cause.items(), key=lambda kv: -kv[1])[:10]],
        "summary": (
            f"{n} device plane(s), busy {busy_s / n:.3f}s of "
            f"{window_s / n:.3f}s; programs by device time: "
            + ", ".join(f"{k} x{v[0]:g} {v[1]:.3f}s" for k, v in top[:6])),
    }


def reduce_dir(trace_dir: str, cfg: dict) -> dict:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {paths}")
    return reduce_events(load_xplane(paths[0], cfg["host_phases"]), cfg)
