"""Mini-fuzzer over the supported SQL surface (sqlsmith analog).

Ref: src/tests/sqlsmith/src/lib.rs — random query generation against
the full stack.  Here each generated query runs TWO ways and the
results must agree:

1. streaming: CREATE MATERIALIZED VIEW + FLUSH, read the MV
   (incremental maintenance through the jitted executors);
2. batch: the same query served directly over the base tables
   (one-shot snapshot through the same kernels, different dynamics —
   emission caps, retraction paths, and flush orders all differ).

A crash in either path or any result divergence is a failure.

Usage: JAX_PLATFORMS=cpu python scripts/fuzz.py [N] [seed]
Exit code 0 = all green.
"""

from __future__ import annotations

import os
import random
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import risingwave_tpu  # noqa: F401,E402
from risingwave_tpu.sql import Engine  # noqa: E402
from risingwave_tpu.sql.planner import PlanError, PlannerConfig  # noqa: E402

N = int(sys.argv[1]) if len(sys.argv) > 1 else 500
SEED = int(sys.argv[2]) if len(sys.argv) > 2 else 20260730
R = random.Random(SEED)

T1_ROWS = [
    (
        R.randrange(0, 8),          # a: group key
        R.randrange(-20, 20),       # b
        R.randrange(0, 5),          # k: join key
        R.randrange(-1000, 1000),   # v
    )
    for _ in range(300)
]
T2_ROWS = [(k, R.randrange(-50, 50)) for k in range(5) for _ in range(3)]


def make_engine() -> Engine:
    return Engine(PlannerConfig(
        chunk_capacity=128,
        agg_table_size=1 << 10, agg_emit_capacity=1 << 9,
        join_table_size=1 << 10, join_bucket_cap=64,
        join_out_capacity=1 << 13,
        mv_table_size=1 << 11, mv_ring_size=1 << 13,
        topn_pool_size=1 << 10, topn_emit_capacity=1 << 9,
    ))


# -- random query generation -------------------------------------------


def gen_scalar(depth: int = 0) -> str:
    r = R.random()
    cols = ["a", "b", "v"]
    if depth > 2 or r < 0.35:
        return R.choice(cols)
    if r < 0.5:
        return str(R.randrange(-10, 10))
    if r < 0.75:
        op = R.choice(["+", "-", "*"])
        return f"({gen_scalar(depth + 1)} {op} {gen_scalar(depth + 1)})"
    if r < 0.85:
        return f"abs({gen_scalar(depth + 1)})"
    return (f"(CASE WHEN {gen_pred(depth + 1)} THEN "
            f"{gen_scalar(depth + 1)} ELSE {gen_scalar(depth + 1)} END)")


def gen_pred(depth: int = 0) -> str:
    r = R.random()
    if depth > 2 or r < 0.6:
        op = R.choice(["<", "<=", ">", ">=", "=", "<>"])
        return f"{gen_scalar(depth + 1)} {op} {gen_scalar(depth + 1)}"
    if r < 0.8:
        return f"({gen_pred(depth + 1)} AND {gen_pred(depth + 1)})"
    if r < 0.95:
        return f"({gen_pred(depth + 1)} OR {gen_pred(depth + 1)})"
    return f"{R.choice(['a', 'b', 'v'])} IN (1, 2, 3)"


def gen_agg() -> str:
    kind = R.choice(["count(*)", "sum", "min", "max", "count", "avg"])
    body = "count(*)" if kind == "count(*)" else f"{kind}({gen_scalar(1)})"
    if R.random() < 0.15:
        body += f" FILTER (WHERE {gen_pred(1)})"
    return body


def gen_query(i: int) -> tuple[str, str]:
    """Returns (kind, sql)."""
    shape = R.random()
    if shape < 0.45:
        # single-table GROUP BY aggregate
        n_aggs = R.randrange(1, 4)
        items = ["a AS g"] + [
            f"{gen_agg()} AS x{j}" for j in range(n_aggs)
        ]
        where = f" WHERE {gen_pred()}" if R.random() < 0.7 else ""
        having = f" HAVING count(*) >= {R.randrange(1, 3)}" \
            if R.random() < 0.3 else ""
        return "agg", (f"SELECT {', '.join(items)} FROM t1{where} "
                       f"GROUP BY a{having}")
    if shape < 0.7:
        # global aggregate
        items = [f"{gen_agg()} AS x{j}" for j in range(R.randrange(1, 4))]
        where = f" WHERE {gen_pred()}" if R.random() < 0.7 else ""
        return "agg", f"SELECT {', '.join(items)} FROM t1{where}"
    if shape < 0.9:
        # join + aggregate
        items = ["t1.k AS g", f"count(*) AS n",
                 f"sum({R.choice(['v', 'w', 'b'])}) AS s"]
        where = f" WHERE {gen_pred()}" if R.random() < 0.5 else ""
        return "join", (f"SELECT {', '.join(items)} FROM t1 "
                        f"JOIN t2 ON t1.k = t2.k{where} GROUP BY t1.k")
    # plain projection + filter
    items = [f"{gen_scalar()} AS p{j}" for j in range(R.randrange(1, 4))]
    return "proj", (f"SELECT a, b, v, {', '.join(items)} FROM t1 "
                    f"WHERE {gen_pred()}")


def normalize(rows, ndigits: int = 6) -> list:
    out = []
    for r in rows:
        vals = []
        for v in r:
            if v is None:
                vals.append(None)
            elif isinstance(v, float) or hasattr(v, "dtype") and \
                    "float" in str(getattr(v, "dtype", "")):
                vals.append(round(float(v), ndigits))
            else:
                try:
                    vals.append(int(v))
                except (TypeError, ValueError):
                    vals.append(str(v))
        out.append(tuple(vals))
    return sorted(out, key=lambda t: tuple(
        (x is None, str(type(x)), x) for x in t
    ))


def main() -> int:
    eng = make_engine()
    eng.execute("CREATE TABLE t1 (a BIGINT, b BIGINT, k BIGINT, "
                "v BIGINT)")
    eng.execute("CREATE TABLE t2 (k BIGINT, w BIGINT)")
    for i in range(0, len(T1_ROWS), 64):
        vals = ",".join(str(t) for t in T1_ROWS[i:i + 64])
        eng.execute(f"INSERT INTO t1 VALUES {vals}")
    vals = ",".join(str(t) for t in T2_ROWS)
    eng.execute(f"INSERT INTO t2 VALUES {vals}")
    eng.execute("FLUSH")

    ran = skipped = failed = 0
    for i in range(N):
        kind, sql = gen_query(i)
        mv = f"fz_{i}"
        try:
            try:
                eng.execute(f"CREATE MATERIALIZED VIEW {mv} AS {sql}")
            except (PlanError, ValueError) as e:
                skipped += 1
                continue
            eng.execute("FLUSH")
            streaming = eng.execute(f"SELECT * FROM {mv}")
            batch = eng.execute(sql)
            a, b = normalize(streaming), normalize(batch)
            if a != b:
                failed += 1
                print(f"[MISMATCH] {sql}")
                print(f"  streaming({len(a)}): {a[:5]}")
                print(f"  batch({len(b)}):     {b[:5]}")
            ran += 1
        except Exception as e:
            failed += 1
            print(f"[CRASH] {sql}\n  {type(e).__name__}: {e}")
        finally:
            try:
                eng.execute(f"DROP MATERIALIZED VIEW {mv}")
            except Exception:
                pass
        if (i + 1) % 50 == 0:
            print(f"... {i + 1}/{N} (ran {ran}, skipped {skipped}, "
                  f"failed {failed})", flush=True)

    print(f"fuzz: {ran} compared, {skipped} skipped (unsupported), "
          f"{failed} FAILED  [seed={SEED}]")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
