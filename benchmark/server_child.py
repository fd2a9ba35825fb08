#!/usr/bin/env python3
"""The process that holds the chip: ``risingwave_tpu.server``'s own
``main()``, started as a user starts ``python -m risingwave_tpu.server``.

    python benchmark/server_child.py --gen-seed N -- <the server's argv>

This is the one file of the benchmark that reaches into the program, for
two things no option offers today (each is listed in ``PERF.md`` with the
program change that retires it):

1. the generator's seed: ``NexmarkConfig.seed`` cannot be set from
   ``WITH (...)``, so ``NexmarkGenerator.__init__`` is wrapped to replace
   it in the configuration it is given;
2. ``jax.profiler`` has to be started and stopped in the process that
   holds the chip, and ``memory_stats()`` and the count of compiles read
   there: one control thread answers the parent's lines on standard
   input (``trace_start <dir>``, ``trace_stop``, ``stats``), each with
   one JSON line ``{"ctl": ...}`` on standard output.

``main()`` prints the handshake line and sleeps on the main thread until
SIGINT, which stays the orderly stop.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import threading

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Compiles:
    """Programs compiled or loaded from the persistent cache so far, and
    how many of them the cache did not hold."""

    def __init__(self) -> None:
        self.n = 0
        self.names: list[str] = []
        self.asked_cache = 0
        self.cache_hits = 0

    def listen(self) -> None:
        import jax.monitoring

        def on_duration(event: str, _secs: float, **kw) -> None:
            if event.endswith("backend_compile_duration"):
                self.n += 1
                self.names.append(str(kw.get("fun_name", "?")))

        def on_event(event: str, **kw) -> None:
            if event.endswith("compile_requests_use_cache"):
                self.asked_cache += 1
            elif event.endswith("compilation_cache/cache_hits"):
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def reply(**fields) -> None:
    print(json.dumps({"ctl": fields}), flush=True)


def control_loop(compiles: Compiles) -> None:
    import jax

    tracing = False
    for line in sys.stdin:
        words = line.split()
        if not words:
            continue
        cmd = words[0]
        try:
            if cmd == "trace_start":
                jax.profiler.start_trace(words[1])
                tracing = True
                reply(cmd=cmd, ok=True)
            elif cmd == "trace_stop":
                if tracing:
                    jax.profiler.stop_trace()
                tracing = False
                reply(cmd=cmd, ok=True)
            elif cmd == "stats":
                peaks = [int((d.memory_stats() or {}).get(
                    "peak_bytes_in_use", 0)) for d in jax.local_devices()]
                reply(cmd=cmd, ok=True, compiles=compiles.n,
                      cache_misses=compiles.asked_cache - compiles.cache_hits,
                      last_compiled=compiles.names[-5:],
                      memory_peak_bytes=max(peaks))
            else:
                reply(cmd=cmd, ok=False, error="unknown command")
        except Exception as e:  # the parent decides what a failure means
            reply(cmd=cmd, ok=False, error=repr(e))


def main() -> None:
    argv = sys.argv[1:]
    if len(argv) < 3 or argv[0] != "--gen-seed" or argv[2] != "--":
        raise SystemExit(__doc__)
    seed = int(argv[1])
    sys.argv = ["risingwave_tpu.server", *argv[3:]]
    sys.path.insert(0, ROOT)

    import jax

    import risingwave_tpu  # noqa: F401  (x64, the compile cache's place)
    from risingwave_tpu.connector import nexmark

    # small programs too: a run after the first compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    # reach 1: the seed
    plain_init = nexmark.NexmarkGenerator.__init__

    def seeded_init(self, config=nexmark.NexmarkConfig()):
        plain_init(self, dataclasses.replace(config, seed=seed))

    nexmark.NexmarkGenerator.__init__ = seeded_init

    # reach 2: the profiler, memory and compile counts of this process
    compiles = Compiles()
    compiles.listen()
    threading.Thread(target=control_loop, args=(compiles,),
                     name="bench-control", daemon=True).start()

    from risingwave_tpu import server

    server.main()


if __name__ == "__main__":
    main()
