"""Nexmark .slt conformance: run the reference corpus, emit a report.

Consumes the REFERENCE's engine-agnostic sqllogictest corpus
(/root/reference/e2e_test/nexmark/ tables+inserts,
/root/reference/e2e_test/streaming/nexmark/ views+expected results)
against this engine, one query at a time, and writes CONFORMANCE.md:
N passed / M skipped-with-reason / K failed.  Queries the planner or
parser rejects are SKIPPED (feature gaps, listed); result mismatches
are FAILURES (correctness bugs).

Usage: JAX_PLATFORMS=cpu python scripts/conformance.py [ref_root]
"""

from __future__ import annotations

import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import risingwave_tpu  # noqa: F401,E402
from risingwave_tpu.slt import SltError, run_slt  # noqa: E402
from risingwave_tpu.sql import Engine  # noqa: E402
from risingwave_tpu.sql.planner import PlannerConfig  # noqa: E402

REF = sys.argv[1] if len(sys.argv) > 1 else "/root/reference"
SETUP_DIR = os.path.join(REF, "e2e_test/nexmark")
QUERY_DIR = os.path.join(REF, "e2e_test/streaming/nexmark")
OUT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "CONFORMANCE.md")


def make_engine() -> Engine:
    return Engine(PlannerConfig(
        chunk_capacity=512,
        agg_table_size=1 << 12,
        agg_emit_capacity=1 << 11,
        join_table_size=1 << 11,
        join_bucket_cap=64,
        join_out_capacity=1 << 14,
        mv_table_size=1 << 13,
        mv_ring_size=1 << 15,
        topn_pool_size=1 << 11,
        topn_emit_capacity=1 << 10,
    ))


def run() -> dict:
    eng = make_engine()
    run_slt(eng, os.path.join(SETUP_DIR, "create_tables.slt.part"),
            tick_between=0)
    for t in ("person", "auction", "bid"):
        run_slt(eng, os.path.join(SETUP_DIR, f"insert_{t}.slt.part"),
                tick_between=0)
    eng.tick(barriers=2)

    results: dict[str, tuple[str, str]] = {}
    names = sorted(
        (f[:-len(".slt.part")] for f in os.listdir(QUERY_DIR)
         if re.match(r"q\d", f)),
        key=lambda s: [int(x) if x.isdigit() else x
                       for x in re.split(r"(\d+)", s)],
    )
    only = os.environ.get("RWT_ONLY")
    if only:
        names = [n for n in names if n in only.split(",")]
    for name in names:
        view_file = os.path.join(QUERY_DIR, "views", f"{name}.slt.part")
        query_file = os.path.join(QUERY_DIR, f"{name}.slt.part")
        if not os.path.exists(view_file):
            results[name] = ("skip", "no view definition in corpus")
            continue
        before = {e.name for e in eng.catalog.list()}
        try:
            run_slt(eng, view_file, tick_between=0)
        except SltError as e:
            reason = str(e.message)[:160]
            results[name] = ("skip", f"plan: {reason}")
            _drop_new(eng, before)
            continue
        except Exception as e:  # engine bug during CREATE
            results[name] = ("error", f"create: {e}"[:160])
            _drop_new(eng, before)
            continue
        try:
            eng.execute("FLUSH")
            eng.tick(barriers=2)
            run_slt(eng, query_file, tick_between=0)
            results[name] = ("pass", "")
        except SltError as e:
            results[name] = ("fail", str(e.message)[:6000])
        except Exception as e:
            results[name] = ("error", str(e)[:200])
        _drop_new(eng, before)
    return results


def _drop_new(eng: Engine, before: set) -> None:
    new = [e.name for e in eng.catalog.list() if e.name not in before]
    for name in reversed(new):
        try:
            eng.execute(f"DROP MATERIALIZED VIEW {name}")
        except Exception:
            pass


def main() -> None:
    results = run()
    only = os.environ.get("RWT_ONLY")
    counts = {"pass": 0, "skip": 0, "fail": 0, "error": 0}
    for status, _ in results.values():
        counts[status] += 1
    lines = [
        "## Nexmark conformance (reference .slt corpus)",
        "",
        "Source: `/root/reference/e2e_test/{nexmark,streaming/nexmark}`"
        " — the reference's own sqllogictest files run unmodified.",
        "",
        f"**{counts['pass']} passed, {counts['skip']} skipped "
        f"(unsupported feature), {counts['fail']} failed, "
        f"{counts['error']} errored** "
        f"out of {len(results)} queries.",
        "",
        "| query | status | detail |",
        "|---|---|---|",
    ]
    for name, (status, detail) in results.items():
        detail = detail.replace("|", "\\|").replace("\n", " ")
        lines.append(f"| {name} | {status} | {detail} |")
    lines.append("")
    if not only:
        from _report import replace_section
        replace_section(OUT, "nexmark", "\n".join(lines))
    print("\n".join(lines[:8]))
    print(f"... report written to {OUT}")
    for name, (status, detail) in results.items():
        print(f"{name:18s} {status:5s} {detail[:110]}")
        if status in ("fail", "error") and len(detail) > 110:
            print(detail)


if __name__ == "__main__":
    main()
