"""Planner: bound SELECT → streaming executor pipelines.

Reference counterpart: ``src/frontend/src/planner`` + ``optimizer`` +
``stream_fragmenter`` — collapsed into direct executor-pipeline
construction for the supported plan shapes:

- stateless:   source → [wm filter] → project/filter → ring MV
- aggregation: source → [wm filter] → [window] → hash agg → project → MV
- TopN:        ... → group/plain TopN → MV
- join:        two sources → per-side prep → hash join → project → MV

The reference's Distribution property (distribution.rs:68) maps to the
vnode/shard axis; this planner emits single-mesh pipelines and the
sharded runtime applies the hash exchange at the agg/join boundary.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any

from risingwave_tpu.common.config import StateConfig
from risingwave_tpu.common.types import Schema
from risingwave_tpu.expr.node import (
    Expr,
    FuncCall as EFuncCall,
    InputRef,
    Literal as ELiteral,
)
from risingwave_tpu.meta.catalog import Catalog
from risingwave_tpu.sql import ast
from risingwave_tpu.expr.agg import AggCall
from risingwave_tpu.sql.binder import AGG_NAMES, AggRef, BindError, Binder, Scope
from risingwave_tpu.stream.executor import (
    Executor,
    FilterExecutor,
    HopWindowExecutor,
    ProjectExecutor,
)
from risingwave_tpu.stream.fragment import Fragment
from risingwave_tpu.stream.hash_agg import HashAggExecutor
from risingwave_tpu.stream.hash_join import HashJoinExecutor, JoinClean
from risingwave_tpu.stream.materialize import (
    AppendOnlyMaterialize,
    MaterializeExecutor,
)
from risingwave_tpu.stream.top_n import GroupTopNExecutor
from risingwave_tpu.stream.watermark import WatermarkFilterExecutor


class PlanError(ValueError):
    pass


@dataclass
class PlannedInput:
    """One stream input after FROM resolution."""

    reader: Any                  # source reader (next_chunk())
    executors: list[Executor]    # prep chain (wm filter, window, ...)
    scope: Scope
    schema: Schema
    watermark_col: int | None    # col idx in `schema` carrying event time
    window_size: int | None      # tumble/hop size (for cleaning lag)
    append_only: bool
    #: hop slide (== window_size for tumble; None when unwindowed)
    window_slide: "int | None" = None
    #: column positions uniquely identifying a row of this input's
    #: changelog (the reference's *stream key*) — required to key the
    #: materialization of retractable non-agg plans
    stream_key: "list[int] | None" = None
    #: event-time columns that carry a watermark: column position ->
    #: how far (us) its watermark trails the source's watermark filter
    #: (the time column itself 0, ``window_end`` 0, ``window_start`` the
    #: window size; a group key or a projected column keeps its
    #: input's: a closed window cannot change, late rows are dropped at
    #: the filter)
    wm_lags: "dict[int, int] | None" = None
    #: the filter's time column (its own schema's position): what the
    #: runtime looks for upstream (``DagJob._upstream_wm``)
    wm_src_col: "int | None" = None
    #: how far (us) the source's watermark trails its newest event
    wm_delay: "int | None" = None
    #: sizes the planner can state (ROADMAP D8).  ``max_rows``: an upper
    #: bound on the rows this input's changelog holds live (an
    #: aggregate's output: its table's slots).  ``live_keys``: column
    #: position -> an upper bound on the distinct values of that column
    #: live at a time (a window column: the windows the watermark has
    #: not closed, (size + delay) / slide, and two for the barrier in
    #: flight)
    max_rows: "int | None" = None
    live_keys: "dict[int, int] | None" = None


@dataclass
class UnaryPlan:
    reader: Any
    fragment: Fragment
    mv_index: int                # executor index of the MV in the fragment
    #: the source stream never retracts (gates the two-phase rewrite)
    append_only: bool = True


@dataclass
class MvTap:
    """A FROM item that is an existing MV: the plan consumes that MV's
    output changelog (ref: MV-on-MV via the upstream materialize
    fragment's dispatcher).  The engine resolves the tap to the running
    job's materialize node at CREATE time."""

    name: str


@dataclass
class DagPlan:
    """A dataflow graph plan: joins (possibly nested), cascades, shared
    inputs (ref stream_fragmenter/mod.rs:388 building a fragment graph).

    ``nodes`` uses the runtime's FragNode/JoinNode with plan-local refs:
    ("source", name) keys into ``sources`` (a reader or an MvTap);
    ("node", i) indexes ``nodes``.
    """

    sources: dict[str, Any]
    nodes: list
    mv_node: int                 # node holding the terminal executor
    mv_index: int                # executor index within that node


@dataclass
class GroupTopNSpec:
    """A row_number-in-subquery TopN rewrite in flight.

    Ref: the reference plans ``SELECT .. FROM (SELECT *, ROW_NUMBER()
    OVER (PARTITION BY p ORDER BY o) rn FROM t) WHERE rn <= k`` as a
    StreamGroupTopN (optimizer/rule/over_window_to_topn_rule.rs); this
    carries the pieces through the inner plan's construction."""

    partition: tuple        # ast exprs, inner FROM scope
    order: tuple            # ast OrderItems, inner FROM scope
    limit: int
    offset: int
    outer_items: tuple      # outer SELECT items (inner-output scope)
    outer_where: tuple      # residual outer conjuncts
    alias: "str | None"     # subquery alias
    rank_alias: "str | None" = None  # emit the in-band row_number as this


@dataclass
class PlannerConfig(StateConfig):
    """The config file's ``state`` section plus the chunk capacity
    (``streaming.chunk_size`` there)."""

    chunk_capacity: int = 4096


class Planner:
    def __init__(self, catalog: Catalog,
                 config: PlannerConfig | None = None):
        self.catalog = catalog
        self.config = config or PlannerConfig()
        #: session streaming_parallelism at plan time (engine-set):
        #: >1 keeps plans in shapes the sharded runtime can take over
        #: (the pane rewrite produces a 2-agg chain it can't, yet)
        self.parallel_hint = 1

    # ------------------------------------------------------------------
    def plan(self, select: ast.Select, sink=None, eowc: bool = False,
             group_topn: "GroupTopNSpec | None" = None
             ) -> "UnaryPlan | DagPlan":
        """``sink`` replaces the MV terminal; ``eowc`` = EMIT ON WINDOW
        CLOSE (final append-only rows when windows close)."""
        def has_subquery(f) -> bool:
            if isinstance(f, ast.SubqueryRef):
                return True
            if isinstance(f, ast.Join):
                return has_subquery(f.left) or has_subquery(f.right)
            return False

        if group_topn is None:
            rewritten = self._match_group_topn(select)
            if rewritten is not None:
                inner, spec = rewritten
                return self.plan(inner, sink=sink, eowc=eowc,
                                 group_topn=spec)
        select = self._factor_where(select)
        select = self._rewrite_in_subqueries(select)
        select = self._rewrite_exists_subqueries(select)
        select = self._rewrite_correlated_scalar(select)

        if isinstance(select.from_, ast.Join) or has_subquery(select.from_):
            if eowc:
                raise PlanError(
                    "EMIT ON WINDOW CLOSE on joins/subqueries: next round"
                )
            return self._plan_join(select, sink, group_topn=group_topn)
        plan = self._plan_unary(select, sink, eowc, group_topn=group_topn)
        if isinstance(plan.reader, MvTap):
            # cascade: a single fragment node tapping the upstream MV
            from risingwave_tpu.stream.dag import FragNode
            return DagPlan(
                sources={plan.reader.name: plan.reader},
                nodes=[FragNode(plan.fragment,
                                ("source", plan.reader.name))],
                mv_node=0, mv_index=plan.mv_index,
            )
        return plan

    # -- IN (SELECT ...) rewrite ----------------------------------------
    def _rewrite_in_subqueries(self, select: ast.Select) -> ast.Select:
        """``x [NOT] IN (SELECT c FROM ...)`` conjuncts become semi/anti
        joins against the subquery (ref: the reference's apply-to-join
        subquery unnesting, optimizer/rule/ — RisingWave plans the same
        shape as StreamHashJoin LeftSemi/LeftAnti).

        NOTE NULL semantics: ``NOT IN`` with NULLs in the subquery is
        three-valued in SQL (never true); the anti join here treats
        NULL keys as non-matching.  The benchmark columns are NOT NULL.
        """
        if select.where is None:
            return select
        conjs = self._conjuncts(select.where)
        ins = [c for c in conjs if isinstance(c, ast.InSubquery)]
        if not ins:
            return select
        rest = [c for c in conjs if not isinstance(c, ast.InSubquery)]
        from_ = select.from_
        for k, c in enumerate(ins):
            sub = c.select
            if len(sub.items) != 1 or isinstance(sub.items[0].expr,
                                                 ast.Star):
                raise PlanError(
                    "IN subquery must select exactly one column"
                )
            alias = f"_in_sq{k}"
            col_name = sub.items[0].alias or self._default_name(
                sub.items[0].expr, 0
            )
            from_ = ast.Join(
                left=from_,
                right=ast.SubqueryRef(sub, alias),
                on=ast.BinaryOp("equal", c.expr,
                                ast.ColumnRef(col_name, alias)),
                kind="anti" if c.negated else "semi",
            )
        where = None
        for r in rest:
            where = r if where is None else ast.BinaryOp("and", where, r)
        import dataclasses
        return dataclasses.replace(select, from_=from_, where=where)

    # -- OR common-conjunct factoring -----------------------------------
    def _factor_where(self, select: ast.Select) -> ast.Select:
        if select.where is None:
            return select
        new = self._factor_or(select.where)
        if new is select.where:
            return select
        import dataclasses
        return dataclasses.replace(select, where=new)

    def _factor_or(self, e):
        """``(A AND e) OR (B AND e) → e AND (A OR B)``: lifts
        predicates duplicated across every OR branch — notably the
        equi-join conditions TPC-H q19 repeats per branch — up to the
        conjunct level where comma-join mining can consume them (ref:
        the reference optimizer's common-factor extraction in
        condition rewriting)."""
        if isinstance(e, ast.BinaryOp) and e.op == "and":
            lf = self._factor_or(e.left)
            rf = self._factor_or(e.right)
            if lf is e.left and rf is e.right:
                return e
            return ast.BinaryOp("and", lf, rf)
        if not (isinstance(e, ast.BinaryOp) and e.op == "or"):
            return e
        branches: list = []

        def collect(x) -> None:
            if isinstance(x, ast.BinaryOp) and x.op == "or":
                collect(x.left)
                collect(x.right)
            else:
                branches.append(self._factor_or(x))

        collect(e)
        conj_lists = [self._conjuncts(b) for b in branches]
        common: list = []
        for c in conj_lists[0]:
            if any(c == x for x in common):
                continue
            if all(any(c == d for d in cl) for cl in conj_lists[1:]):
                common.append(c)
        if not common:
            return e

        def and_fold(parts):
            out = None
            for p in parts:
                out = p if out is None else ast.BinaryOp("and", out, p)
            return out

        residues: list = []
        some_branch_empty = False
        for cl in conj_lists:
            rem = list(cl)
            for c in common:
                for j, d in enumerate(rem):
                    if d == c:
                        rem.pop(j)
                        break
            if not rem:
                # this branch is exactly the common part: the OR of
                # residues is vacuously true
                some_branch_empty = True
                break
            residues.append(and_fold(rem))
        parts = list(common)
        if not some_branch_empty:
            out = None
            for r in residues:
                out = r if out is None else ast.BinaryOp("or", out, r)
            parts.append(out)
        return and_fold(parts)

    # -- EXISTS rewrite -------------------------------------------------
    def _from_name_sets(self, from_):
        """(names, (qual, name) pairs) visible from a FROM tree — used
        to split an EXISTS subquery's predicates into local vs
        correlated (outer) references."""
        names: set = set()
        quals: set = set()
        if isinstance(from_, ast.Join):
            for side in (from_.left, from_.right):
                n, q = self._from_name_sets(side)
                names |= n
                quals |= q
            return names, quals
        if isinstance(from_, ast.SubqueryRef):
            for i, it in enumerate(from_.select.items):
                if isinstance(it.expr, ast.Star):
                    n, q = self._from_name_sets(from_.select.from_)
                    names |= n
                    continue
                nm = it.alias or self._default_name(it.expr, i)
                names.add(nm)
                if from_.alias:
                    quals.add((from_.alias, nm))
            return names, quals
        if isinstance(from_, (ast.Tumble, ast.Hop)):
            n, q = self._from_name_sets(from_.table)
            names |= n | {"window_start", "window_end"}
            return names, quals
        # TableRef
        try:
            entry = self.catalog.get(from_.name)
        except Exception:
            return names, quals
        qual = from_.alias or from_.name
        for f in entry.schema:
            names.add(f.name)
            quals.add((qual, f.name))
        return names, quals

    def _rewrite_exists_subqueries(self, select: ast.Select) -> ast.Select:
        """``[NOT] EXISTS (SELECT .. FROM u WHERE u.k = outer.k AND
        <local>)`` conjuncts become semi/anti joins on the correlated
        equi keys, with local predicates pushed into the subquery
        (ref: the reference's correlated-subquery unnesting to
        StreamHashJoin LeftSemi/LeftAnti, optimizer/rule/
        apply_join_transpose_rule.rs and kin)."""
        if select.where is None:
            return select
        conjs = self._conjuncts(select.where)
        hits = []
        for c in conjs:
            if isinstance(c, ast.ExistsSubquery):
                hits.append((c, c.select, False))
            elif (isinstance(c, ast.UnaryOp) and c.op == "not"
                    and isinstance(c.operand, ast.ExistsSubquery)):
                hits.append((c, c.operand.select, True))
        if not hits:
            return select
        rest = [c for c in conjs
                if not any(c is h[0] for h in hits)]
        from_ = select.from_
        for k, (_, sub, negated) in enumerate(hits):
            sub_names, sub_quals = self._from_name_sets(sub.from_)

            def is_local(e) -> bool:
                if not isinstance(e, ast.ColumnRef):
                    return False
                if e.table is not None:
                    return (e.table, e.name) in sub_quals
                return e.name in sub_names

            local: list = []
            join_keys: list = []  # (sub_col: ColumnRef, outer_expr)
            neq: list = []        # (sub_col: ColumnRef, outer_expr)
            sub_conjs = self._conjuncts(sub.where) \
                if sub.where is not None else []
            for sc in sub_conjs:
                refs = self._column_refs(sc)
                if refs and all(is_local(r) for r in refs):
                    local.append(sc)
                    continue
                if (isinstance(sc, ast.BinaryOp)
                        and sc.op in ("equal", "not_equal")):
                    a, b = sc.left, sc.right
                    bucket = join_keys if sc.op == "equal" else neq
                    if isinstance(a, ast.ColumnRef) \
                            and isinstance(b, ast.ColumnRef):
                        if is_local(a) and not is_local(b):
                            bucket.append((a, b))
                            continue
                        if is_local(b) and not is_local(a):
                            bucket.append((b, a))
                            continue
                raise PlanError(
                    "EXISTS supports correlated equality predicates "
                    f"only (got {sc!r})"
                )
            if not join_keys:
                raise PlanError(
                    "EXISTS subquery must correlate on at least one "
                    "equality with the outer query"
                )
            if len(neq) > 1:
                raise PlanError(
                    "EXISTS supports at most ONE correlated "
                    "non-equality predicate (the min/max "
                    "decorrelation does not compose across columns)"
                )
            alias = f"_ex_sq{k}"
            import dataclasses
            lwhere = None
            for c2 in local:
                lwhere = c2 if lwhere is None \
                    else ast.BinaryOp("and", lwhere, c2)
            items = tuple(
                ast.SelectItem(sc_col, f"_exk{j}")
                for j, (sc_col, _) in enumerate(join_keys)
            )
            if not neq:
                sub2 = dataclasses.replace(
                    sub, items=items, where=lwhere, group_by=(),
                    having=None, order_by=(), limit=None, offset=None,
                )
                on = None
                for j, (_, outer_e) in enumerate(join_keys):
                    eq = ast.BinaryOp(
                        "equal", outer_e,
                        ast.ColumnRef(f"_exk{j}", alias),
                    )
                    on = eq if on is None else ast.BinaryOp("and", on, eq)
                from_ = ast.Join(
                    left=from_, right=ast.SubqueryRef(sub2, alias),
                    on=on, kind="anti" if negated else "semi",
                )
                continue
            # ONE correlated non-equality (q21's ``l2.l_suppkey <>
            # l1.l_suppkey``): decorrelate through min/max.  Group the
            # subquery by its equi keys carrying min/max/count of the
            # non-equality column; "some row with n_col <> e exists" is
            # exactly ``min <> e OR max <> e`` over the group's
            # non-NULL values, evaluated as a residual filter after an
            # ordinary equi join — so the hash join stays pure equi
            # and its per-key degree bookkeeping untouched.
            n_col, outer_e = neq[0]
            items = items + (
                ast.SelectItem(
                    ast.FuncCall("min", (n_col,)), "_exmn"),
                ast.SelectItem(
                    ast.FuncCall("max", (n_col,)), "_exmx"),
                ast.SelectItem(
                    ast.FuncCall("count", (n_col,)), "_exct"),
            )
            sub2 = dataclasses.replace(
                sub, items=items, where=lwhere,
                group_by=tuple(sc_col for sc_col, _ in join_keys),
                having=None, order_by=(), limit=None, offset=None,
            )
            on = None
            for j, (_, oe) in enumerate(join_keys):
                eq = ast.BinaryOp(
                    "equal", oe, ast.ColumnRef(f"_exk{j}", alias)
                )
                on = eq if on is None else ast.BinaryOp("and", on, eq)
            mn = ast.ColumnRef("_exmn", alias)
            mx = ast.ColumnRef("_exmx", alias)
            if not negated:
                # EXISTS: inner join (grouped sub has ≤1 row per key,
                # no duplication); all-NULL groups or a NULL outer
                # expression make the residual NULL → filtered, which
                # matches ``n_col <> e`` never being true there
                from_ = ast.Join(
                    left=from_, right=ast.SubqueryRef(sub2, alias),
                    on=on, kind="inner",
                )
                rest.append(ast.BinaryOp(
                    "or",
                    ast.BinaryOp("not_equal", mn, outer_e),
                    ast.BinaryOp("not_equal", mx, outer_e),
                ))
            else:
                # NOT EXISTS holds when: no key-group at all (left
                # outer join produced NULLs), or the group has no
                # non-NULL n_col (count = 0), or the outer expression
                # is NULL (<> never true), or every non-NULL value
                # equals it (min = e AND max = e)
                from_ = ast.Join(
                    left=from_, right=ast.SubqueryRef(sub2, alias),
                    on=on, kind="left",
                )
                no_group = ast.FuncCall(
                    "is_null", (ast.ColumnRef(f"_exk0", alias),))
                all_null = ast.BinaryOp(
                    "equal", ast.ColumnRef("_exct", alias),
                    ast.Literal(0, "int"))
                outer_null = ast.FuncCall("is_null", (outer_e,))
                all_eq = ast.BinaryOp(
                    "and",
                    ast.BinaryOp("equal", mn, outer_e),
                    ast.BinaryOp("equal", mx, outer_e),
                )
                rest.append(ast.BinaryOp(
                    "or", no_group, ast.BinaryOp(
                        "or", all_null, ast.BinaryOp(
                            "or", outer_null, all_eq))))
        where = None
        for r in rest:
            where = r if where is None else ast.BinaryOp("and", where, r)
        import dataclasses
        return dataclasses.replace(select, from_=from_, where=where)

    def _column_refs(self, e) -> list:
        """All ColumnRefs in an AST expression."""
        out: list = []
        stack = [e]
        while stack:
            x = stack.pop()
            if isinstance(x, ast.ColumnRef):
                out.append(x)
            elif isinstance(x, ast.Case):
                for c, r in x.conditions:
                    stack += [c, r]
                if x.else_result is not None:
                    stack.append(x.else_result)
            else:
                for a in ("left", "right", "operand", "expr",
                          "filter_where"):
                    v = getattr(x, a, None)
                    if v is not None and not isinstance(v, str):
                        stack.append(v)
                stack.extend(
                    a for a in getattr(x, "args", ())
                    if not isinstance(a, ast.Star)
                )
        return out

    def _rewrite_correlated_scalar(self, select: ast.Select) -> ast.Select:
        """``lhs CMP (SELECT agg(..) FROM .. WHERE sub_col = outer_col
        AND <local>)`` decorrelates into a join against the subquery
        grouped by its correlation keys, with ``lhs CMP agg_out`` as a
        residual predicate (the reference's Apply→Join unnesting,
        optimizer/rule/apply_agg_transpose_rule.rs and kin).

        Empty-group semantics: the scalar subquery yields NULL over an
        empty set, making the comparison never-true — the inner join
        dropping missing keys is equivalent (count/count_star would
        yield 0, NOT NULL, so those stay unsupported here)."""
        if select.where is None:
            return select
        conjs = self._conjuncts(select.where)
        hits = []
        for c in conjs:
            m = self._match_scalar_sub_cmp(c)
            if m is None or self._is_uncorrelated(m[2]):
                continue
            hits.append((c, m))
        if not hits:
            return select
        new_conjs = list(conjs)
        from_ = select.from_
        for k, (c, (lhs, cmp, sub)) in enumerate(hits):
            if (sub.group_by or sub.having is not None
                    or len(sub.items) != 1
                    or isinstance(sub.items[0].expr, ast.Star)):
                raise PlanError(
                    "correlated scalar subquery must be a single "
                    "ungrouped aggregate"
                )
            item = sub.items[0].expr
            if any(f.name == "count"
                   for f in self._column_refs_funcs(item)):
                raise PlanError(
                    "correlated scalar COUNT subquery (0 vs NULL over "
                    "empty groups) is not supported"
                )
            sub_names, sub_quals = self._from_name_sets(sub.from_)

            def is_local(e) -> bool:
                if not isinstance(e, ast.ColumnRef):
                    return False
                if e.table is not None:
                    return (e.table, e.name) in sub_quals
                return e.name in sub_names

            local: list = []
            corr: list = []  # (sub_col, outer_col)
            for sc in (self._conjuncts(sub.where)
                       if sub.where is not None else []):
                refs = self._column_refs(sc)
                if refs and all(is_local(r) for r in refs):
                    local.append(sc)
                    continue
                if isinstance(sc, ast.BinaryOp) and sc.op == "equal":
                    a, b = sc.left, sc.right
                    if isinstance(a, ast.ColumnRef) \
                            and isinstance(b, ast.ColumnRef):
                        if is_local(a) and not is_local(b):
                            corr.append((a, b))
                            continue
                        if is_local(b) and not is_local(a):
                            corr.append((b, a))
                            continue
                raise PlanError(
                    "correlated scalar subquery supports equality "
                    f"correlation only (got {sc!r})"
                )
            if not corr:
                raise PlanError(
                    "correlated scalar subquery lost its correlation"
                )
            alias = f"_cs_sq{k}"
            import dataclasses
            lwhere = None
            for c2 in local:
                lwhere = c2 if lwhere is None \
                    else ast.BinaryOp("and", lwhere, c2)
            items = tuple(
                ast.SelectItem(sc_col, f"_ck{j}")
                for j, (sc_col, _) in enumerate(corr)
            ) + (ast.SelectItem(item, "_cv"),)
            sub2 = dataclasses.replace(
                sub, items=items, where=lwhere,
                group_by=tuple(sc_col for sc_col, _ in corr),
                having=None, order_by=(), limit=None, offset=None,
            )
            on = None
            for j, (_, outer_c) in enumerate(corr):
                eq = ast.BinaryOp(
                    "equal", outer_c, ast.ColumnRef(f"_ck{j}", alias)
                )
                on = eq if on is None else ast.BinaryOp("and", on, eq)
            from_ = ast.Join(
                left=from_, right=ast.SubqueryRef(sub2, alias),
                on=on, kind="inner",
            )
            # replace the conjunct with lhs CMP <agg out>
            inv = {"gt": "greater_than", "ge": "greater_than_or_equal",
                   "lt": "less_than", "le": "less_than_or_equal",
                   "eq": "equal"}
            new_conjs[new_conjs.index(c)] = ast.BinaryOp(
                inv[cmp], lhs, ast.ColumnRef("_cv", alias)
            )
        where = None
        for r in new_conjs:
            where = r if where is None else ast.BinaryOp("and", where, r)
        import dataclasses
        return dataclasses.replace(select, from_=from_, where=where)

    def _column_refs_funcs(self, e) -> list:
        """All FuncCalls in an AST expression."""
        out: list = []
        stack = [e]
        while stack:
            x = stack.pop()
            if isinstance(x, ast.FuncCall):
                out.append(x)
                stack.extend(a for a in x.args
                             if not isinstance(a, ast.Star))
            elif isinstance(x, ast.Case):
                for c, r in x.conditions:
                    stack += [c, r]
                if x.else_result is not None:
                    stack.append(x.else_result)
            else:
                for a in ("left", "right", "operand", "expr"):
                    v = getattr(x, a, None)
                    if v is not None and not isinstance(v, str):
                        stack.append(v)
                stack.extend(
                    a for a in getattr(x, "args", ())
                    if not isinstance(a, ast.Star)
                )
        return out

    def _is_uncorrelated(self, sub: ast.Select) -> bool:
        """Every column the subquery references resolves in its own
        FROM — safe to plan as an independent 1-row changelog."""
        names, quals = self._from_name_sets(sub.from_)

        def local(r) -> bool:
            if r.table is not None:
                return (r.table, r.name) in quals
            return r.name in names

        exprs = [it.expr for it in sub.items
                 if not isinstance(it.expr, ast.Star)]
        if sub.where is not None:
            exprs.append(sub.where)
        exprs.extend(sub.group_by)
        if sub.having is not None:
            exprs.append(sub.having)
        return all(local(r) for e in exprs for r in self._column_refs(e))

    # -- GroupTopN (row_number-in-subquery) rewrite ---------------------
    def _match_group_topn(self, select: ast.Select):
        """Detect SELECT .. FROM (SELECT *, ROW_NUMBER() OVER (..) rn
        FROM ..) WHERE rn <= k and return (inner-sans-window, spec)."""
        f = select.from_
        if not isinstance(f, ast.SubqueryRef):
            return None
        inner = f.select
        if (inner.order_by or inner.limit is not None or inner.offset
                or inner.group_by or inner.having is not None):
            return None
        wins = [(i, it) for i, it in enumerate(inner.items)
                if isinstance(it.expr, ast.WindowCall)]
        if len(wins) != 1:
            return None
        wi, witem = wins[0]
        w = witem.expr
        if w.name != "row_number" or w.frame is not None or not w.order_by:
            return None
        rank_name = witem.alias or "row_number"
        if select.where is None:
            return None
        limit = offset = None
        rest: list = []
        for c in self._conjuncts(select.where):
            lo = self._rank_bound(c, rank_name, f.alias)
            if lo is not None and limit is None:
                limit, offset = lo
            else:
                rest.append(c)
        if limit is None:
            return None
        if select.order_by or select.limit is not None or select.offset:
            return None  # outer ORDER/LIMIT over group topn: next round

        # does the outer query use the rank column (selected by name or
        # via *)?  If so the TopN must emit its in-band row_number.
        def refs_rank(e) -> bool:
            if isinstance(e, ast.ColumnRef):
                return e.name == rank_name
            if isinstance(e, ast.Case):
                return any(refs_rank(c) or refs_rank(r)
                           for c, r in e.conditions) or (
                    e.else_result is not None
                    and refs_rank(e.else_result)
                )
            return any(
                refs_rank(x) for x in getattr(e, "args", ())
                if not isinstance(x, ast.Star)
            ) or any(
                refs_rank(getattr(e, a)) for a in ("left", "right",
                                                   "operand")
                if getattr(e, a, None) is not None
            )
        has_star = any(isinstance(it.expr, ast.Star)
                       for it in select.items)
        with_rank = has_star or any(
            not isinstance(it.expr, ast.Star) and refs_rank(it.expr)
            for it in select.items
        ) or any(refs_rank(c) for c in rest)
        if has_star and wi != len(inner.items) - 1:
            # the rank column is appended LAST by the rewrite; a * over
            # a mid-list window item would reorder columns
            return None
        import dataclasses
        inner2 = dataclasses.replace(
            inner, items=tuple(it for i, it in enumerate(inner.items)
                               if i != wi),
        )
        spec = GroupTopNSpec(
            partition=tuple(w.partition_by), order=tuple(w.order_by),
            limit=limit, offset=offset,
            outer_items=tuple(select.items), outer_where=tuple(rest),
            alias=f.alias,
            rank_alias=rank_name if with_rank else None,
        )
        return inner2, spec

    @staticmethod
    def _rank_bound(c, rank_name: str, alias: "str | None" = None):
        """rn <= k / rn < k / rn = k / k >= rn → (limit, offset)."""
        def is_rank(e) -> bool:
            return (isinstance(e, ast.ColumnRef) and e.name == rank_name
                    and e.table in (None, alias))

        if not isinstance(c, ast.BinaryOp):
            return None
        op, left, right = c.op, c.left, c.right
        if is_rank(right):
            flip = {"greater_than_or_equal": "less_than_or_equal",
                    "greater_than": "less_than",
                    "equal": "equal"}.get(op)
            if flip is None:
                return None
            op, left, right = flip, right, left
        if not (is_rank(left)
                and isinstance(right, ast.Literal)
                and right.type_name == "int"):
            return None
        k = right.value
        if op == "less_than_or_equal" and k >= 1:
            return (k, 0)
        if op == "less_than" and k >= 2:
            return (k - 1, 0)
        if op == "equal" and k >= 1:
            return (1, k - 1)
        return None

    def _resolve_group_topn(self, spec: GroupTopNSpec, scope: Scope,
                            proj: list):
        """Bind the partition/order keys in the INNER scope and locate
        them in the projection (appending hidden columns as needed);
        returns (group_positions, [(position, desc)], spec)."""
        b = Binder(scope)

        def locate(bexpr) -> int:
            for pi, (_, pe) in enumerate(proj):
                if self._expr_eq(pe, bexpr):
                    return pi
            proj.append((f"_hidden_gtn{len(proj)}", bexpr))
            return len(proj) - 1

        group_pos = [locate(b.bind(e)) for e in spec.partition]
        order_pos = [(locate(b.bind(oi.expr)), oi.descending)
                     for oi in spec.order]
        return (group_pos, order_pos, spec)

    # -- FROM resolution ------------------------------------------------
    def _resolve_input(self, from_,
                       read_cols: "set | None" = None) -> PlannedInput:
        """One FROM item as a stream input.  ``read_cols`` (``(table,
        name)`` pairs, ``_named_columns``) lets an append-only source
        drop, ahead of everything else, the columns the statement never
        names: upstream's column pruning.  A join keeps every row of
        such a side in its state, so what the side does not carry it
        does not store."""
        if isinstance(from_, ast.TableRef):
            entry = self.catalog.get(from_.name)
            if entry.kind == "mview":
                # MV-on-MV: consume the upstream MV's output changelog
                qual = from_.alias or from_.name
                return PlannedInput(
                    MvTap(from_.name), [],
                    Scope.of(entry.schema, qual), entry.schema,
                    None, None, entry.append_only,
                    stream_key=entry.stream_key,
                )
            if entry.kind != "source":
                raise PlanError(
                    f"{from_.name} is not a streaming source or "
                    "materialized view"
                )
            reader = entry.reader_factory()
            qual = from_.alias or from_.name
            execs: list[Executor] = []
            schema = entry.schema
            at = {i: i for i in range(len(schema))}
            if read_cols is not None and entry.append_only:
                keep = [
                    i for i, f in enumerate(schema)
                    if any(n == f.name and t in (None, qual, from_.name)
                           for t, n in read_cols)
                    or i in (entry.stream_key or ())
                    or (entry.watermark is not None
                        and i == entry.watermark[0])
                ]
                if keep and len(keep) < len(schema):
                    execs.append(ProjectExecutor(
                        schema, [(schema[i].name, InputRef(i))
                                 for i in keep]))
                    schema = execs[-1].out_schema
                    at = {old: new for new, old in enumerate(keep)}
            wm_col = None
            if entry.watermark is not None:
                col, delay = entry.watermark
                wm_col = at[col]
                execs.append(
                    WatermarkFilterExecutor(schema, wm_col, delay)
                )
            return PlannedInput(
                reader, execs, Scope.of(schema, qual), schema,
                wm_col, None, entry.append_only,
                stream_key=[at[k] for k in entry.stream_key]
                if entry.stream_key else None,
                wm_lags={wm_col: 0} if wm_col is not None else None,
                wm_src_col=wm_col,
                wm_delay=entry.watermark[1]
                if entry.watermark is not None else None,
            )
        if isinstance(from_, (ast.Tumble, ast.Hop)):
            if read_cols is not None:
                # the window's time column is named by a string
                read_cols = read_cols | {(None, from_.time_col)}
                if from_.alias:
                    # an aliased window table re-qualifies its columns
                    read_cols |= {(None, n) for t, n in read_cols
                                  if t == from_.alias}
            inner = self._resolve_input(from_.table, read_cols)
            ts_idx = inner.scope.resolve(from_.time_col, None)
            if isinstance(from_, ast.Tumble):
                size = from_.size.micros
                slide = size
            else:
                size = from_.size.micros
                slide = from_.slide.micros
            hop = HopWindowExecutor(inner.schema, ts_idx, slide, size)
            qual = from_.alias or from_.table.name
            if from_.alias:
                # an aliased window table re-qualifies EVERY column
                quals = tuple(qual for _ in hop.out_schema)
            else:
                quals = tuple(inner.scope.qualifiers) + (qual, qual)
            scope = Scope(hop.out_schema, quals)
            # window_start is addressable by the window alias OR the
            # underlying table name (postgres-ish leniency)
            wm_lags = live_keys = None
            if inner.wm_lags is not None \
                    and inner.wm_lags.get(ts_idx) is not None:
                lag = inner.wm_lags[ts_idx]
                n_in = len(inner.schema)  # window_start, window_end follow
                wm_lags = {**inner.wm_lags, n_in: lag + size,
                           n_in + 1: lag}
                if inner.wm_delay is not None:
                    open_windows = -(-(lag + size + inner.wm_delay)
                                     // slide) + 2
                    live_keys = {n_in: open_windows,
                                 n_in + 1: open_windows}
            return PlannedInput(
                inner.reader, inner.executors + [hop], scope,
                hop.out_schema, inner.watermark_col, size,
                inner.append_only, window_slide=slide,
                wm_lags=wm_lags, wm_src_col=inner.wm_src_col,
                wm_delay=inner.wm_delay, live_keys=live_keys,
            )
        raise PlanError(f"unsupported FROM clause {from_!r}")

    # -- unary pipelines -------------------------------------------------
    @staticmethod
    def _stream_key_projection(proj: list, schema: Schema,
                               stream_key) -> list[int]:
        """Ensure the stream-key columns survive a projection (hidden if
        unselected); returns their output positions (the materialize
        pk).  Ref: stream-key derivation through project nodes."""
        pk_positions: list[int] = []
        for ki in stream_key:
            pos = next(
                (pi for pi, (_, e) in enumerate(proj)
                 if isinstance(e, InputRef) and e.index == ki),
                None,
            )
            if pos is None:
                proj.append((f"_hidden_{schema[ki].name}", InputRef(ki)))
                pos = len(proj) - 1
            pk_positions.append(pos)
        return pk_positions

    def _plan_unary(self, select: ast.Select, sink=None,
                    eowc: bool = False,
                    group_topn: "GroupTopNSpec | None" = None
                    ) -> UnaryPlan:
        if select.from_ is None:
            raise PlanError("SELECT without FROM is not a streaming job")
        pin = self._resolve_input(select.from_)
        execs = list(pin.executors)
        scope = pin.scope

        if select.where is not None:
            b = Binder(scope)
            execs.append(FilterExecutor(scope.schema, b.bind(select.where)))

        has_window = any(
            isinstance(i.expr, ast.WindowCall) for i in select.items
        )
        if has_window:
            if sink is not None or eowc:
                raise PlanError(
                    "window functions with sinks/EOWC: next round"
                )
            return self._plan_over_window(select, pin, execs, scope)

        has_agg = bool(select.group_by) or self._has_agg(select)
        if has_agg and group_topn is not None:
            raise PlanError(
                "row_number subquery over an aggregation: next round"
            )
        if eowc and not has_agg:
            raise PlanError(
                "EMIT ON WINDOW CLOSE needs GROUP BY window_start over a "
                "watermarked windowed source"
            )
        pk_positions: list[int] = []
        gtn = None
        if has_agg:
            pane = self._try_pane_agg(select, scope, pin, execs, eowc)
            if pane is not None:
                execs2, out_schema, pk_positions = pane
            else:
                execs2, out_schema, pk_positions = self._plan_agg(
                    select, scope, pin, eowc
                )
            execs.extend(execs2)
        else:
            items = self._expand_items(select.items, scope)
            b = Binder(scope)
            proj = [(name, b.bind(e)) for name, e in items]
            if not pin.append_only:
                # retractable input without aggregation: the output must
                # stay keyed by the upstream STREAM KEY so deletes hit
                # the right MV row — append the key columns (hidden if
                # unselected) and remember their positions as the pk
                if pin.stream_key is None:
                    raise PlanError(
                        "retractable input without a stream key cannot "
                        "be materialized"
                    )
                pk_positions = self._stream_key_projection(
                    proj, scope.schema, pin.stream_key
                )
            if group_topn is not None:
                gtn = self._resolve_group_topn(group_topn, scope, proj)
            execs.append(ProjectExecutor(scope.schema, proj))
            out_schema = execs[-1].out_schema

        self._append_terminal(
            execs, out_schema, select,
            input_append_only=pin.append_only, has_agg=has_agg,
            pk_positions=pk_positions, sink=sink, eowc=eowc,
            group_topn=gtn,
        )
        return UnaryPlan(pin.reader, Fragment(execs), len(execs) - 1,
                         append_only=pin.append_only)

    def _build_over_window(self, items, scope: Scope, execs: list):
        """Append an OverWindowExecutor + post-projection for SELECT
        items containing fn() OVER (...) calls (one shared OVER clause).
        Returns the projected out_schema."""
        from risingwave_tpu.stream.over_window import (
            OverWindowExecutor,
            WindowFuncCall,
        )

        witems = [(item, item.expr) for item in items
                  if isinstance(item.expr, ast.WindowCall)]
        spec = (witems[0][1].partition_by, witems[0][1].order_by,
                witems[0][1].frame)
        for _, w in witems[1:]:
            if (w.partition_by, w.order_by, w.frame) != spec:
                raise PlanError(
                    "all window calls must share one OVER clause "
                    "(multi-spec plans: next round)"
                )
        b = Binder(scope)
        partition = [b.bind(e) for e in spec[0]]
        order = [(b.bind(oi.expr), oi.descending) for oi in spec[1]]
        for e in partition + [oe for oe, _ in order]:
            if e.return_field(scope.schema).nullable:
                raise PlanError(
                    "OVER (...) on nullable partition/order columns: "
                    "next round"
                )
        calls = []
        supported = {"row_number", "rank", "dense_rank", "lag", "lead",
                     "sum", "count", "avg", "min", "max"}
        needs_arg = {"lag", "lead", "sum", "avg", "min", "max"}
        framable = {"sum", "count", "avg"}
        for idx, (item, w) in enumerate(witems):
            if w.name not in supported:
                raise PlanError(f"window function {w.name} not supported")
            if w.frame is not None:
                if w.name not in framable:
                    raise PlanError(
                        f"ROWS frames on {w.name}() OVER: next round"
                    )
                if w.frame[1] != 0 or w.frame[0] < 0:
                    raise PlanError(
                        "only ROWS BETWEEN n PRECEDING AND CURRENT ROW "
                        "frames are supported"
                    )
            if w.name in needs_arg and (
                not w.args or isinstance(w.args[0], ast.Star)
            ):
                raise PlanError(f"{w.name}() OVER needs an argument")
            if w.name in ("lag", "lead") and len(w.args) > 2:
                raise PlanError(
                    "lag/lead default values are not yet supported"
                )
            arg = b.bind(w.args[0]) if w.args and not isinstance(
                w.args[0], ast.Star
            ) else None
            offset = 1
            if w.name in ("lag", "lead") and len(w.args) > 1:
                off_ast = w.args[1]
                if not (isinstance(off_ast, ast.Literal)
                        and off_ast.type_name == "int"):
                    raise PlanError("lag/lead offset must be an integer")
                offset = off_ast.value
            calls.append(WindowFuncCall(
                w.name, arg, offset,
                item.alias or f"{w.name}{idx}",
                frame=w.frame,
            ))
        ow = OverWindowExecutor(
            scope.schema, partition, order, calls,
            pool_size=max(self.config.topn_pool_size,
                          2 * self.config.chunk_capacity),
            emit_capacity=self.config.topn_emit_capacity,
        )
        execs.append(ow)
        # post-projection: inputs by name, window outputs by position
        out_schema = ow.out_schema
        n_in = len(scope.schema)
        proj = []
        wi = 0
        post_b = Binder(Scope(out_schema,
                              tuple(scope.qualifiers)
                              + tuple(None for _ in calls)))
        for idx, item in enumerate(items):
            if isinstance(item.expr, ast.WindowCall):
                name = item.alias or calls[wi].alias
                proj.append((name, InputRef(n_in + wi)))
                wi += 1
            elif isinstance(item.expr, ast.Star):
                for ci, f in enumerate(scope.schema):
                    if f.name.startswith("_hidden_"):
                        continue
                    proj.append((f.name, InputRef(ci)))
            else:
                name = item.alias or self._default_name(item.expr, idx)
                proj.append((name, post_b.bind(item.expr)))
        execs.append(ProjectExecutor(out_schema, proj))
        return execs[-1].out_schema

    def _plan_over_window(self, select: ast.Select, pin, execs,
                          scope) -> UnaryPlan:
        """SELECT items with fn() OVER (...): one OverWindowExecutor.

        All window calls must share one OVER clause this round (the
        reference groups calls per window spec the same way)."""
        if (select.group_by or select.having is not None
                or select.order_by or select.limit is not None
                or select.offset):
            raise PlanError(
                "window functions with GROUP BY/HAVING/ORDER BY/LIMIT "
                "in one SELECT: next round"
            )
        out_schema = self._build_over_window(select.items, scope, execs)
        execs.append(MaterializeExecutor(
            out_schema, pk_indices=list(range(len(out_schema))),
            table_size=self.config.mv_table_size,
        ))
        return UnaryPlan(pin.reader, Fragment(execs), len(execs) - 1,
                         append_only=False)

    def _append_terminal(self, execs, out_schema, select, *,
                         input_append_only: bool, has_agg: bool,
                         pk_positions, sink, eowc: bool,
                         group_topn=None) -> None:
        """Shared plan tail: optional (group) TopN, then sink or
        materialize."""
        has_topn = bool(select.order_by and select.limit is not None)
        if group_topn is not None:
            group_pos, order_pos, spec = group_topn
            for pos, _ in order_pos:
                if out_schema[pos].nullable:
                    raise PlanError(
                        "row_number ORDER BY on a nullable column: "
                        "next round"
                    )
            pool = max(self.config.topn_pool_size,
                       2 * self.config.chunk_capacity)
            execs.append(GroupTopNExecutor(
                out_schema,
                group_by=[InputRef(i) for i in group_pos],
                order_by=[(InputRef(i), d) for i, d in order_pos],
                limit=spec.limit, offset=spec.offset,
                pool_size=pool,
                emit_capacity=self.config.topn_emit_capacity,
                append_only=input_append_only,
                rank_alias=spec.rank_alias,
            ))
            out_schema = execs[-1].out_schema
            scope2 = Scope.of(out_schema, spec.alias)
            for c in spec.outer_where:
                execs.append(FilterExecutor(
                    out_schema, Binder(scope2).bind(c)
                ))
            if any(isinstance(it.expr, ast.WindowCall)
                   for it in spec.outer_items):
                # q6 shape: fn() OVER (...) over the group-topn output
                out_schema = self._build_over_window(
                    spec.outer_items, scope2, execs
                )
            else:
                items = self._expand_items(spec.outer_items, scope2)
                proj2 = [(nm, Binder(scope2).bind(e))
                         for nm, e in items]
                execs.append(ProjectExecutor(out_schema, proj2))
                out_schema = execs[-1].out_schema
            # group-topn output is retractable, keyed by the whole row
            input_append_only = False
            pk_positions = list(range(len(out_schema)))
        if has_topn:
            if eowc:
                raise PlanError(
                    "ORDER BY ... LIMIT with EMIT ON WINDOW CLOSE: "
                    "next round"
                )
            ob = []
            b = Binder(Scope.of(out_schema))
            for oi in select.order_by:
                ke = self._bind_order_key(oi.expr, b, out_schema)
                if ke.return_field(out_schema).nullable:
                    raise PlanError(
                        "ORDER BY on a nullable column in TopN "
                        "(NULLS FIRST/LAST ordering): next round"
                    )
                ob.append((ke, oi.descending))
            # append-only up to here ⇒ the TopN can evict non-band rows
            pool = max(self.config.topn_pool_size,
                       2 * self.config.chunk_capacity)
            execs.append(GroupTopNExecutor(
                out_schema, group_by=[], order_by=ob, limit=select.limit,
                offset=select.offset or 0,
                pool_size=pool,
                emit_capacity=self.config.topn_emit_capacity,
                append_only=input_append_only and not has_agg,
            ))

        if sink is not None:
            from risingwave_tpu.stream.sink import SinkExecutor
            # hidden MV-pk bookkeeping columns must not leak externally
            visible = [i for i, f in enumerate(out_schema)
                       if not f.name.startswith("_hidden_")]
            if len(visible) != len(out_schema):
                execs.append(ProjectExecutor(
                    out_schema,
                    [(out_schema[i].name, InputRef(i)) for i in visible],
                ))
                out_schema = execs[-1].out_schema
            execs.append(SinkExecutor(
                out_schema, sink, ring_size=self.config.mv_ring_size
            ))
            return

        # materialize (EOWC output is final append-only rows)
        retractable = (has_agg or has_topn or not input_append_only) \
            and not eowc
        if retractable:
            # pk: group keys for aggs; the propagated stream key for
            # retractable projections; whole row for TopN output.
            # KNOWN GAP (advisor r1, low): two identical rows in a TopN
            # band collapse into one MV slot — multiset parity needs a
            # rank column from the TopN state appended to the pk.
            if has_topn:
                pk = list(range(len(out_schema)))
            elif pk_positions:
                pk = pk_positions
            else:
                pk = list(range(len(out_schema)))
            execs.append(MaterializeExecutor(
                out_schema, pk_indices=pk,
                table_size=self.config.mv_table_size,
            ))
        else:
            execs.append(AppendOnlyMaterialize(
                out_schema, ring_size=self.config.mv_ring_size
            ))

    # -- aggregation ------------------------------------------------------
    def _has_agg(self, select: ast.Select) -> bool:
        def walk(e) -> bool:
            if isinstance(e, ast.FuncCall):
                if e.name in AGG_NAMES:
                    return True
                return any(walk(a) for a in e.args
                           if not isinstance(a, ast.Star))
            if isinstance(e, ast.BinaryOp):
                return walk(e.left) or walk(e.right)
            if isinstance(e, ast.UnaryOp):
                return walk(e.operand)
            if isinstance(e, ast.Cast):
                return walk(e.operand)
            if isinstance(e, ast.Case):
                return any(walk(c) or walk(r) for c, r in e.conditions) or (
                    e.else_result is not None and walk(e.else_result)
                )
            return False

        return any(walk(i.expr) for i in select.items
                   if not isinstance(i.expr, ast.Star))

    @staticmethod
    def _canonical_groups(group_asts: list, scope: Scope) -> list:
        """GROUP BY's keys in the order of the columns they name, where
        all are bare columns: ``GROUP BY a, b`` and ``GROUP BY b, a``
        are one relation, and planned alike they are one subplan
        (``_share_subplans``)."""
        b = Binder(scope)
        try:
            bound = [b.bind(g) for g in group_asts]
        except BindError:
            return group_asts
        if not all(isinstance(e, InputRef) for e in bound):
            return group_asts
        order = sorted(range(len(bound)), key=lambda i: bound[i].index)
        return [group_asts[i] for i in order]

    def _agg_sizes(self, pin: PlannedInput, group_by) -> tuple[int, int]:
        """(group-table slots, materialised-input slots) of an
        aggregate over ``pin`` (ROADMAP D8).  The group table is
        ``agg_table_size`` unless the input is a subquery's output and
        every key is a column whose live values the planner can bound
        (``PlannedInput.live_keys``: an aggregate by window over an
        aggregate's output holds a group a window the watermark has not
        closed): then four times the bound, at least 64.  A materialised-input table holds at most
        one (group, value) pair an input row, so the input's
        ``max_rows`` where known, else the group table's size."""
        cfg = self.config
        size = cfg.agg_table_size
        if pin.reader is None and pin.live_keys and all(
                isinstance(e, InputRef) and e.index in pin.live_keys
                for _, e in group_by):
            groups = 1
            for _, e in group_by:
                groups *= pin.live_keys[e.index]
            size = min(size, max(64, 1 << (4 * groups - 1).bit_length()))
        minput = cfg.distinct_table_size
        if minput is None:
            minput = cfg.agg_table_size if pin.max_rows is None \
                else 1 << (pin.max_rows - 1).bit_length()
        return size, minput

    def _plan_agg(self, select: ast.Select, scope: Scope,
                  pin: PlannedInput, eowc: bool = False,
                  extra_out: "list | None" = None,
                  canonical: bool = False):
        """Plan the aggregation chain; with ``extra_out`` (AST exprs in
        the input scope, aggregates allowed) their values are appended
        to the output as hidden columns and their positions returned
        as a 4th element (the dynamic-filter LHS hook).  ``canonical``
        (a FROM subquery) puts the group keys in column order."""
        cfg = self.config
        group_asts = list(select.group_by)
        if canonical:
            group_asts = self._canonical_groups(group_asts, scope)
        in_binder = Binder(scope)
        group_by = []
        for gi, ga in enumerate(group_asts):
            name = ga.name if isinstance(ga, ast.ColumnRef) else f"_key{gi}"
            group_by.append((name, in_binder.bind(ga)))
        if not group_by:
            # global aggregation = one hidden constant group (the
            # reference's simple agg / Distribution::Single)
            from risingwave_tpu.expr.node import as_expr
            group_by.append(("_global", as_expr(0)))

        # bind select items collecting agg calls
        item_binder = Binder(scope, allow_aggs=True)
        bound_items: list[tuple[str, Expr]] = []
        for idx, item in enumerate(select.items):
            if isinstance(item.expr, ast.Star):
                raise PlanError("SELECT * with GROUP BY is not valid")
            name = item.alias or self._default_name(item.expr, idx)
            bound_items.append((name, item_binder.bind(item.expr)))
        agg_calls = item_binder.agg_calls

        having_expr = None
        if select.having is not None:
            having_expr = item_binder.bind(select.having)
            agg_calls = item_binder.agg_calls
        extra_bound: list[Expr] = []
        for e_ast in (extra_out or []):
            extra_bound.append(item_binder.bind(e_ast))
            agg_calls = item_binder.agg_calls

        # watermark-driven cleaning when a group key is the window start
        wm_idx = None
        lag = 0
        if pin.window_size is not None and pin.watermark_col is not None:
            for ki, ga in enumerate(group_asts):
                if (isinstance(ga, ast.ColumnRef)
                        and ga.name == "window_start"):
                    wm_idx, lag = ki, pin.window_size
                elif (isinstance(ga, ast.ColumnRef)
                        and ga.name == "window_end"):
                    wm_idx, lag = ki, 0  # closes when wm >= window_end
        wm_src = pin.watermark_col
        if wm_idx is None and pin.reader is None and pin.wm_lags \
                and pin.wm_src_col is not None:
            # a subquery's output column that carries a watermark (a
            # window column of the aggregate below): the groups it
            # closes are final, so they are cleaned like a window's
            for ki, (_, ge) in enumerate(group_by):
                if isinstance(ge, InputRef) and ge.index in pin.wm_lags:
                    wm_idx, lag = ki, pin.wm_lags[ge.index]
                    wm_src = pin.wm_src_col
                    break
        if eowc and wm_idx is None:
            raise PlanError(
                "EMIT ON WINDOW CLOSE needs GROUP BY window_start over a "
                "watermarked windowed source"
            )
        execs: list[Executor] = []
        if any(a.distinct for a in agg_calls):
            # DISTINCT is native in the agg executor (per-call counted
            # dedup tables, ref distinct.rs) — mixing with plain calls,
            # per-call FILTERs, multiple distinct args, and retractable
            # inputs all compose.  min/max are distinct-insensitive.
            import dataclasses
            agg_calls = [
                dataclasses.replace(a, distinct=False)
                if a.distinct and a.kind in ("min", "max") else a
                for a in agg_calls
            ]
        # min/max over short strings: packed-uint64 monoid (agg.py
        # _pack_str8); wider strings need a materialized-input string
        # state — not yet built
        for ci, a in enumerate(agg_calls):
            if a.kind in ("min", "max") and a.arg is not None:
                f = a.arg.return_field(scope.schema)
                if f.data_type.is_string:
                    if f.str_width > 8:
                        raise PlanError(
                            f"{a.kind} over strings wider than 8 device "
                            "bytes: next round"
                        )
                    if not pin.append_only:
                        # packed monoid can't retract; a string minput
                        # state hasn't been built
                        raise PlanError(
                            f"{a.kind} over strings on a retractable "
                            "input: next round"
                        )
                    import dataclasses
                    agg_calls[ci] = dataclasses.replace(
                        a, kind=f"{a.kind}_str"
                    )
        table_size, minput_size = self._agg_sizes(pin, group_by)
        agg = HashAggExecutor(
            scope.schema, group_by, agg_calls,
            table_size=table_size,
            # a table the planner sized below the configuration's
            # cannot emit more groups a round than it has slots
            emit_capacity=min(cfg.agg_emit_capacity, table_size)
            if table_size < cfg.agg_table_size else cfg.agg_emit_capacity,
            watermark_group_idx=wm_idx,
            watermark_lag=lag,
            watermark_src_col=wm_src,
            emit_on_window_close=eowc,
            # retractable inputs (join outputs, cascades over
            # retractable MVs) switch min/max to materialized-input
            # state (ref minput.rs) instead of crash-on-delete
            retractable_input=not pin.append_only,
            minput_table_size=minput_size,
            distinct_table_size=cfg.distinct_table_size
            or cfg.agg_table_size,
            # spill-to-host for UNBOUNDED key spaces (no watermark
            # cleaning): overflow rows divert to the host tier instead
            # of erroring.  Windowed aggs keep overflow-as-error — their
            # state is bounded by cleaning, and freed slots would break
            # the tier's structural group ownership (stream/spill.py).
            spill_ring=((cfg.agg_spill_ring
                         if cfg.agg_spill_ring is not None
                         else 4 * cfg.chunk_capacity)
                        if wm_idx is None and not eowc else 0),
        )
        agg.spill_table_size = (cfg.agg_spill_table_size
                                or table_size * 8)
        execs.append(agg)

        # post-projection over agg output: group keys + agg results
        agg_scope = Scope.of(agg.out_schema)
        rewritten = []
        for (name, e) in bound_items:
            rewritten.append((name, self._rewrite_post_agg(
                e, group_by, len(group_by)
            )))
        # append hidden group keys that weren't selected (MV pk needs them)
        selected_keys = set()
        for name, e in rewritten:
            if isinstance(e, InputRef) and e.index < len(group_by):
                selected_keys.add(e.index)
        hidden = [
            (f"_hidden_{agg.out_schema[ki].name}", InputRef(ki))
            for ki in range(len(group_by)) if ki not in selected_keys
        ]
        proj_items = rewritten + hidden
        extra_pos: list[int] = []
        for xi, xb in enumerate(extra_bound):
            proj_items.append((
                f"_hidden_dynf{xi}",
                self._rewrite_post_agg(xb, group_by, len(group_by)),
            ))
            extra_pos.append(len(proj_items) - 1)
        if having_expr is not None:
            hv = self._rewrite_post_agg(having_expr, group_by, len(group_by))
            execs.append(FilterExecutor(agg.out_schema, hv))
        post = ProjectExecutor(agg.out_schema, proj_items)
        execs.append(post)
        # pk = positions of the group keys inside the projection
        pk_pos = []
        for ki in range(len(group_by)):
            for pi, (n, e) in enumerate(proj_items):
                if isinstance(e, InputRef) and e.index == ki:
                    pk_pos.append(pi)
                    break
        if extra_out is not None:
            return execs, post.out_schema, pk_pos, extra_pos
        return execs, post.out_schema, pk_pos

    def _try_pane_agg(self, select: ast.Select, scope: Scope,
                      pin: PlannedInput, execs: list, eowc: bool,
                      canonical: bool = False):
        """Sliding-window (HOP) aggregation via PANES — stream slicing.

        The naive hop plan expands every event into size/slide window
        rows BEFORE aggregating (ref hop_window.rs row expansion) — a
        k-fold tax on the agg's scatter path.  Panes aggregate ONCE per
        event into tumbling slide-width panes, then expand only the
        aggregated PANE DELTAS (tiny) into their k covering windows and
        combine with translated partial-agg calls — the classic
        pane/stream-slicing optimization, done with the two-phase
        machinery (partial_agg.translated_global_calls).

        Eligible: append-only hop input, GROUP BY window_start + keys,
        two-phase-decomposable calls without DISTINCT/FILTER, linear
        (unsharded) plans.  Returns None when ineligible."""
        from risingwave_tpu.stream.partial_agg import (
            TWO_PHASE_KINDS,
            translated_global_calls,
        )

        if eowc or not pin.append_only or self.parallel_hint > 1:
            return None
        size, slide = pin.window_size, pin.window_slide
        if size is None or slide is None or slide >= size \
                or size % slide != 0 or pin.watermark_col is None:
            return None
        hop_pos = next(
            (i for i, ex in enumerate(execs)
             if isinstance(ex, HopWindowExecutor)), None,
        )
        if hop_pos is None:
            return None
        hop = execs[hop_pos]
        ws_idx = len(hop.in_schema)  # window_start position (appended)

        def touches_window(e: Expr) -> bool:
            if isinstance(e, InputRef):
                return e.index >= ws_idx
            if isinstance(e, AggRef):
                return e.call.arg is not None \
                    and touches_window(e.call.arg)
            if isinstance(e, EFuncCall):
                return any(touches_window(a) for a in e.args)
            return False

        # bind group keys + items exactly as _plan_agg would
        group_asts = list(select.group_by)
        if canonical:
            group_asts = self._canonical_groups(group_asts, scope)
        in_binder = Binder(scope)
        group_by: list = []
        ws_key_pos = None
        for gi, ga in enumerate(group_asts):
            name = ga.name if isinstance(ga, ast.ColumnRef) else f"_key{gi}"
            ge = in_binder.bind(ga)
            if isinstance(ge, InputRef) and ge.index == ws_idx:
                ws_key_pos = gi
            elif touches_window(ge):
                return None  # window_end/ts-derived keys: no pane form
            group_by.append((name, ge))
        if ws_key_pos is None:
            return None
        item_binder = Binder(scope, allow_aggs=True)
        bound_items = []
        for idx, item in enumerate(select.items):
            if isinstance(item.expr, ast.Star):
                raise PlanError("SELECT * with GROUP BY is not valid")
            name = item.alias or self._default_name(item.expr, idx)
            bound_items.append((name, item_binder.bind(item.expr)))
        agg_calls = item_binder.agg_calls
        having_expr = None
        if select.having is not None:
            having_expr = item_binder.bind(select.having)
            agg_calls = item_binder.agg_calls
        if any(a.kind not in TWO_PHASE_KINDS or a.distinct
               or a.filter is not None for a in agg_calls):
            return None
        if any(a.arg is not None and touches_window(a.arg)
               for a in agg_calls):
            return None
        # min/max over strings: the pane combine phase is retractable,
        # which the packed-string monoid can't support — fall back to
        # _plan_agg (which plans min_str/max_str or raises a clear
        # PlanError) instead of crashing at executor build
        for a in agg_calls:
            if a.kind in ("min", "max") and a.arg is not None \
                    and a.arg.return_field(scope.schema) \
                           .data_type.is_string:
                return None
        # the WHERE filter (already in execs) must not read window cols
        for ex in execs:
            if isinstance(ex, FilterExecutor) \
                    and touches_window(ex.predicate):
                return None

        cfg = self.config
        n_keys = len(group_by)
        k = size // slide
        # 1. panes: tumble by slide (same schema/positions as the hop)
        execs[hop_pos] = HopWindowExecutor(
            hop.in_schema, hop.ts_col, slide, slide
        )
        # 2. per-pane partial agg (append-only, cleans when the pane's
        # LAST covering window closes: wm >= pane_start + size)
        pane_agg = HashAggExecutor(
            execs[hop_pos].out_schema, group_by, agg_calls,
            table_size=cfg.agg_table_size,
            emit_capacity=cfg.agg_emit_capacity,
            watermark_group_idx=ws_key_pos,
            watermark_lag=size,
            watermark_src_col=pin.watermark_col,
        )
        # 3. expand PANE DELTAS to their k covering windows
        expand = HopWindowExecutor(
            pane_agg.out_schema, ws_key_pos, slide, size
        )
        n_pane_out = len(pane_agg.out_schema)
        # 4. combine partials per (keys..., window_start) — pane updates
        # retract, so the global phase runs retractable (minput holds up
        # to k live pane-partials per window for min/max)
        final_group = [
            (nm, InputRef(n_pane_out) if gi == ws_key_pos
             else InputRef(gi))
            for gi, (nm, _) in enumerate(group_by)
        ]
        final_agg = HashAggExecutor(
            expand.out_schema, final_group,
            translated_global_calls(agg_calls, n_keys),
            table_size=cfg.agg_table_size,
            emit_capacity=cfg.agg_emit_capacity,
            watermark_group_idx=ws_key_pos,
            watermark_lag=size,
            watermark_src_col=pin.watermark_col,
            retractable_input=True,
            # at most k live pane partials a window
            minput_table_size=cfg.distinct_table_size
            or cfg.agg_table_size,
        )
        execs2: list = [pane_agg, expand, final_agg]

        # post projection / having / pk — identical to _plan_agg's tail
        # (final agg output = [keys..., agg outs...] in original order)
        rewritten = [
            (name, self._rewrite_post_agg(e, group_by, n_keys))
            for name, e in bound_items
        ]
        selected_keys = {
            e.index for _, e in rewritten
            if isinstance(e, InputRef) and e.index < n_keys
        }
        hidden = [
            (f"_hidden_{final_agg.out_schema[ki].name}", InputRef(ki))
            for ki in range(n_keys) if ki not in selected_keys
        ]
        proj_items = rewritten + hidden
        if having_expr is not None:
            hv = self._rewrite_post_agg(having_expr, group_by, n_keys)
            execs2.append(FilterExecutor(final_agg.out_schema, hv))
        post = ProjectExecutor(final_agg.out_schema, proj_items)
        execs2.append(post)
        pk_pos = []
        for ki in range(n_keys):
            for pi, (nm, e) in enumerate(proj_items):
                if isinstance(e, InputRef) and e.index == ki:
                    pk_pos.append(pi)
                    break
        return execs2, post.out_schema, pk_pos

    def _rewrite_post_agg(self, e: Expr, group_by, n_keys: int) -> Expr:
        """Rewrite a bound select expr to read the agg output schema."""
        if isinstance(e, AggRef):
            return InputRef(n_keys + e.index)
        for ki, (_, ge) in enumerate(group_by):
            if self._expr_eq(e, ge):
                return InputRef(ki)
        if isinstance(e, InputRef):
            raise PlanError(
                "column referenced outside aggregates must appear in "
                "GROUP BY"
            )
        if isinstance(e, EFuncCall):
            return EFuncCall(
                e.name,
                tuple(self._rewrite_post_agg(a, group_by, n_keys)
                      for a in e.args),
            )
        from risingwave_tpu.expr.scalar import RegexpGroup, ToChar
        if isinstance(e, ToChar):
            return ToChar(
                self._rewrite_post_agg(e.arg, group_by, n_keys), e.fmt
            )
        if isinstance(e, RegexpGroup):
            return RegexpGroup(
                self._rewrite_post_agg(e.arg, group_by, n_keys),
                e.pattern, 2,
            )
        return e  # literals

    @staticmethod
    def _expr_eq(a: Expr, b: Expr) -> bool:
        if type(a) is not type(b):
            return False
        if isinstance(a, InputRef):
            return a.index == b.index
        if isinstance(a, EFuncCall):
            return a.name == b.name and len(a.args) == len(b.args) and all(
                Planner._expr_eq(x, y) for x, y in zip(a.args, b.args)
            )
        from risingwave_tpu.expr.node import Literal as ELit
        from risingwave_tpu.expr.scalar import RegexpGroup, ToChar
        if isinstance(a, ELit):
            return a.value == b.value and a.data_type == b.data_type
        if isinstance(a, ToChar):
            return a.fmt == b.fmt and Planner._expr_eq(a.arg, b.arg)
        if isinstance(a, RegexpGroup):
            return a.pattern == b.pattern \
                and Planner._expr_eq(a.arg, b.arg)
        return False

    # -- join pipelines ---------------------------------------------------
    def _plan_join(self, select: ast.Select, sink=None,
                   group_topn: "GroupTopNSpec | None" = None) -> DagPlan:
        """Joins — including nested (multi-way) trees — as a DagPlan.

        Each base input becomes a source (+ optional prep fragment
        node); each ast.Join becomes a JoinNode over the resolved
        refs (ref: the fragmenter cutting a join plan into exchange-
        separated fragments, stream_fragmenter/mod.rs:388)."""
        from risingwave_tpu.stream.dag import FragNode, JoinNode

        cfg = self.config
        sources: dict[str, Any] = {}
        nodes: list = []
        #: catalog source -> the plan source that reads it
        read_once: dict[str, str] = {}
        read_cols = self._named_columns(select, group_topn)

        def reorder_cross(jn: ast.Join) -> ast.Join:
            """Greedy connectivity ordering of a comma-join chain: each
            next factor must share a WHERE equi-conjunct with the
            already-joined set (the reference optimizer's join
            reordering; TPC-H q2 lists part, supplier, partsupp —
            part×supplier has no direct edge, part×partsupp does)."""
            factors: list = []

            def flatten(x) -> None:
                if isinstance(x, ast.Join) and x.kind == "cross":
                    flatten(x.left)
                    flatten(x.right)
                else:
                    factors.append(x)

            flatten(jn)
            if len(factors) <= 2:
                return jn
            fsets = [self._from_name_sets(f) for f in factors]

            def owners(ref) -> list[int]:
                out = []
                for fi, (names, quals) in enumerate(fsets):
                    ok = (ref.table, ref.name) in quals if ref.table \
                        else ref.name in names
                    if ok:
                        out.append(fi)
                return out

            edges: list[tuple[int, int]] = []
            for conj in where_conjs:
                if not (isinstance(conj, ast.BinaryOp)
                        and conj.op == "equal"):
                    continue
                lo = {o for r in self._column_refs(conj.left)
                      for o in owners(r)}
                ro = {o for r in self._column_refs(conj.right)
                      for o in owners(r)}
                if len(lo) == 1 and len(ro) == 1 and lo != ro:
                    edges.append((lo.pop(), ro.pop()))
            order = [0]
            remaining = set(range(1, len(factors)))
            while remaining:
                pick = next(
                    (j for j in sorted(remaining)
                     if any((a in order and b == j)
                            or (b in order and a == j)
                            for a, b in edges)),
                    None,
                )
                if pick is None:
                    pick = min(remaining)  # disconnected: keep order,
                    # resolve_join raises its usual clear error
                order.append(pick)
                remaining.discard(pick)
            out = factors[order[0]]
            for j in order[1:]:
                out = ast.Join(out, factors[j], None, "cross")
            return out

        def resolve(from_):
            if isinstance(from_, ast.Join):
                if from_.kind == "cross":
                    from_ = reorder_cross(from_)
                return resolve_join(from_)
            if isinstance(from_, ast.SubqueryRef):
                return resolve_subquery(from_)
            pin = self._resolve_input(from_, read_cols)
            table = from_
            while not isinstance(table, ast.TableRef):
                table = table.table
            if isinstance(from_, ast.TableRef):
                base = from_.alias or from_.name
            else:
                base = from_.alias or from_.table.name
            # a source the job names twice is read ONCE: its chunk fans
            # out to every consumer (left side first), so an event is
            # generated and counted once
            name = None if isinstance(pin.reader, MvTap) \
                else read_once.get(table.name)
            if name is None:
                name = base
                i = 1
                while name in sources:
                    name = f"{base}_{i}"
                    i += 1
                sources[name] = pin.reader
                if not isinstance(pin.reader, MvTap):
                    read_once[table.name] = name
            ref = ("source", name)
            if pin.executors:
                # window columns shift stream-key positions? no — hop
                # APPENDS columns, existing indices hold
                nodes.append(FragNode(Fragment(pin.executors), ref))
                ref = ("node", len(nodes) - 1)
            return ref, pin

        def resolve_subquery(sq: ast.SubqueryRef):
            """A derived table becomes its own fragment node chain —
            structurally an anonymous inlined MV (ref: the optimizer
            plans subqueries as shared sub-plans)."""
            nonlocal where_conjs
            inner = sq.select
            # subquery bodies get the same unnesting rewrites as the
            # top level (IN / EXISTS → semi/anti joins, correlated
            # scalar aggs → grouped joins)
            inner = self._factor_where(inner)
            inner = self._rewrite_in_subqueries(inner)
            inner = self._rewrite_exists_subqueries(inner)
            inner = self._rewrite_correlated_scalar(inner)
            if inner.order_by or inner.limit is not None or inner.offset:
                raise PlanError(
                    "ORDER BY/LIMIT in FROM subqueries: next round"
                )
            if any(isinstance(i.expr, ast.WindowCall)
                   for i in inner.items):
                raise PlanError(
                    "window functions in FROM subqueries: next round"
                )
            # WHERE conjuncts are scoped per SELECT: the subquery's own
            # comma-joins mine the subquery's WHERE, not the outer one
            saved_conjs = where_conjs
            where_conjs = (
                self._conjuncts(inner.where)
                if inner.where is not None else []
            )
            iref, iinfo = resolve(inner.from_)
            scope = iinfo.scope
            # scalar-subquery comparisons peel into dynamic filters
            # (same rewrite as the top level; q22's derived table)
            inner_dyn: list = []
            for conj in list(where_conjs):
                m = self._match_scalar_sub_cmp(conj)
                if m is not None and isinstance(m[0], ast.ColumnRef) \
                        and self._is_uncorrelated(m[2]):
                    inner_dyn.append(m)
                    where_conjs.remove(conj)
            execs: list[Executor] = []
            for conj in where_conjs:  # filters not consumed by joins
                execs.append(FilterExecutor(
                    scope.schema, Binder(scope).bind(conj)
                ))
            where_conjs = saved_conjs
            ref = iref
            append_only_in = iinfo.append_only
            if inner_dyn:
                from risingwave_tpu.stream.dynamic_filter import (
                    DynamicFilterExecutor,
                )
                if execs:
                    nodes.append(FragNode(Fragment(execs), ref))
                    ref = ("node", len(nodes) - 1)
                    execs = []
                for lhs, cmp, s2 in inner_dyn:
                    if len(s2.items) != 1 or isinstance(
                            s2.items[0].expr, ast.Star):
                        raise PlanError(
                            "scalar subquery must select exactly one "
                            "column"
                        )
                    sref, _si = resolve_subquery(
                        ast.SubqueryRef(s2, f"_sc_sq{len(nodes)}")
                    )
                    nodes.append(JoinNode(DynamicFilterExecutor(
                        scope.schema,
                        filter_col=scope.resolve(lhs.name, lhs.table),
                        cmp=cmp,
                        pool_size=max(cfg.topn_pool_size,
                                      2 * cfg.chunk_capacity),
                    ), ref, sref))
                    ref = ("node", len(nodes) - 1)
                append_only_in = False
                import dataclasses as _dc
                iinfo = _dc.replace(iinfo, append_only=False)
            has_agg = bool(inner.group_by) or self._has_agg(inner)
            pk_positions: list[int] = []
            if has_agg:
                # a hopping-window aggregate takes the pane rewrite the
                # linear path has, where the window node is this
                # subquery's own (made by the resolve above, nothing
                # else reads it yet)
                pane = None
                prep = nodes[ref[1]] if ref[0] == "node" else None
                if isinstance(prep, FragNode) and ref[1] == len(nodes) - 1 \
                        and prep.fragment.executors == iinfo.executors:
                    trial = list(iinfo.executors) + execs
                    pane = self._try_pane_agg(
                        inner, scope, iinfo, trial, False, canonical=True)
                if pane is not None:
                    n_prep = len(iinfo.executors)
                    nodes[ref[1]] = FragNode(
                        Fragment(trial[:n_prep]), prep.input)
                    execs2, out_schema, pk_positions = pane
                else:
                    execs2, out_schema, pk_positions = self._plan_agg(
                        inner, scope, iinfo, canonical=True
                    )
                execs.extend(execs2)
                append_only = False
            else:
                items = self._expand_items(inner.items, scope)
                b = Binder(scope)
                proj = [(nm, b.bind(e)) for nm, e in items]
                if not append_only_in:
                    if iinfo.stream_key is None:
                        raise PlanError(
                            "retractable subquery input without a "
                            "stream key"
                        )
                    pk_positions = self._stream_key_projection(
                        proj, scope.schema, iinfo.stream_key
                    )
                execs.append(ProjectExecutor(scope.schema, proj))
                out_schema = execs[-1].out_schema
                append_only = append_only_in
            if execs:
                nodes.append(FragNode(Fragment(execs), ref))
                ref = ("node", len(nodes) - 1)
            # an output column that is a bare input column (a group key,
            # where the subquery aggregates) keeps that column's
            # watermark: changes to come carry values at or above it
            wm_lags: dict[int, int] = {}
            live_keys: dict[int, int] = {}
            if not inner_dyn \
                    and not any(isinstance(i.expr, ast.Star)
                                for i in inner.items):
                for pos, item in enumerate(inner.items):
                    if not isinstance(item.expr, ast.ColumnRef):
                        continue
                    try:
                        src = scope.resolve(item.expr.name, item.expr.table)
                    except BindError:
                        continue
                    if src in (iinfo.wm_lags or ()):
                        wm_lags[pos] = iinfo.wm_lags[src]
                    if src in (iinfo.live_keys or ()):
                        live_keys[pos] = iinfo.live_keys[src]
            # an aggregate's output holds a row a group: its table's
            # slots bound it; a projection keeps its input's bound
            max_rows = next(
                (ex.table_size for ex in reversed(execs)
                 if isinstance(ex, HashAggExecutor)), iinfo.max_rows)
            info = PlannedInput(
                None, [], Scope.of(out_schema, sq.alias), out_schema,
                None, None, append_only,
                stream_key=pk_positions or None,
                wm_lags=wm_lags or None,
                wm_src_col=iinfo.wm_src_col if wm_lags else None,
                wm_delay=iinfo.wm_delay,
                max_rows=max_rows, live_keys=live_keys or None,
            )
            return ref, info

        KIND_MAP = {"inner": "inner", "left": "left_outer",
                    "right": "right_outer", "full": "full_outer",
                    "cross": "inner",
                    "semi": "left_semi", "anti": "left_anti"}
        #: WHERE conjuncts; comma-joins mine their equi-conditions from
        #: here (the classic implicit-join rewrite), the rest become
        #: post-join filters
        where_conjs: list = (
            self._conjuncts(select.where)
            if select.where is not None else []
        )

        def resolve_temporal(jn: ast.Join):
            """stream JOIN t FOR SYSTEM_TIME AS OF PROCTIME(): probe
            the build table's pk index at process time (ref
            temporal_join.rs; planner requires key == build pk like
            the reference's index-lookup form)."""
            from risingwave_tpu.stream.temporal_join import (
                TemporalJoinExecutor,
            )

            join_type = "inner" if jn.kind == "temporal" else "left_outer"
            lref, left = resolve(jn.left)
            rref, right = resolve(jn.right)
            n_left = len(left.schema)
            if not right.stream_key:
                raise PlanError(
                    "temporal join build side needs a PRIMARY KEY"
                )
            lkeys: list = []
            ridx: list[int] = []
            residual: list = []
            for conj in (self._conjuncts(jn.on) if jn.on is not None
                         else []):
                kp = self._equi_pair(
                    conj, left.scope, right.scope, n_left
                )
                if kp is None:
                    residual.append(conj)
                    continue
                lk, rk = kp
                if not isinstance(rk, InputRef):
                    raise PlanError(
                        "temporal join keys must be build-side columns"
                    )
                lkeys.append(lk)
                ridx.append(rk.index)
            if set(ridx) != set(right.stream_key):
                raise PlanError(
                    "temporal join requires equality keys covering the "
                    "build side's PRIMARY KEY exactly "
                    f"(got cols {sorted(ridx)}, pk "
                    f"{sorted(right.stream_key)})"
                )
            order = [ridx.index(pk) for pk in right.stream_key]
            join = TemporalJoinExecutor(
                left.schema, right.schema,
                [lkeys[i] for i in order], list(right.stream_key),
                table_size=cfg.join_table_size, join_type=join_type,
            )
            nodes.append(JoinNode(join, lref, rref))
            ref = ("node", len(nodes) - 1)
            both = Scope(
                join.out_schema,
                tuple(left.scope.qualifiers)
                + tuple(right.scope.qualifiers),
            )
            if residual:
                b = Binder(both)
                nodes.append(FragNode(Fragment([
                    FilterExecutor(both.schema, b.bind(c))
                    for c in residual
                ]), ref))
                ref = ("node", len(nodes) - 1)
            # build-side changes never retract outputs: append-only
            # follows the PROBE side alone
            info = PlannedInput(
                None, [], both, both.schema, None, None,
                left.append_only, stream_key=left.stream_key,
            )
            return ref, info

        def resolve_join(jn: ast.Join):
            if jn.kind in ("temporal", "temporal_left"):
                return resolve_temporal(jn)
            join_type = KIND_MAP.get(jn.kind)
            if join_type is None:
                raise PlanError(f"unsupported join kind {jn.kind!r}")
            lref, left = resolve(jn.left)
            rref, right = resolve(jn.right)
            n_left = len(left.schema)

            # split ON into equi-conjuncts and residual filters; a
            # comma join (no ON) pulls its equi-conditions out of WHERE
            if jn.on is not None:
                candidates = self._conjuncts(jn.on)
                from_where = False
            else:
                candidates = list(where_conjs)
                from_where = True
            left_keys: list[Expr] = []
            right_keys: list[Expr] = []
            residual: list = []
            for conj in candidates:
                keypair = self._equi_pair(
                    conj, left.scope, right.scope, n_left
                )
                if keypair is not None:
                    lk, rk = keypair
                    left_keys.append(lk)
                    right_keys.append(rk)
                    if from_where:
                        where_conjs.remove(conj)
                elif not from_where:
                    residual.append(conj)
            if not left_keys:
                raise PlanError(
                    "JOIN requires at least one equality condition"
                )
            if residual and join_type != "inner":
                # an ON predicate touching ONLY the null-padded side
                # pushes below the join as a filter on that input —
                # semantically exact for one-sided outer joins (rows
                # failing it simply don't match, and the preserved side
                # still pads).  TPC-H q13's `LEFT JOIN ... ON k AND
                # o_comment NOT LIKE ...` is this shape.
                pushable_side = None
                if join_type == "left_outer":
                    pushable_side = "right"
                elif join_type == "right_outer":
                    pushable_side = "left"
                if pushable_side is not None:
                    pin = right if pushable_side == "right" else left
                    other = left if pushable_side == "right" else right
                    kept: list = []
                    pushed: list = []
                    for conj in residual:
                        # pushable iff every column ref resolves on the
                        # padded side and NO unqualified ref also
                        # resolves on the preserved side (ambiguous —
                        # keep it so the full-scope bind raises instead
                        # of silently filtering the wrong side)
                        refs = self._column_refs(conj)
                        ok = bool(refs)
                        for r in refs:
                            try:
                                pin.scope.resolve(r.name, r.table)
                            except Exception:
                                ok = False
                                break
                            if r.table is None:
                                try:
                                    other.scope.resolve(r.name, None)
                                    ok = False  # ambiguous
                                    break
                                except Exception:
                                    pass
                        if not ok:
                            kept.append(conj)
                            continue
                        try:
                            pushed.append(FilterExecutor(
                                pin.scope.schema,
                                Binder(pin.scope).bind(conj),
                            ))
                        except Exception:
                            kept.append(conj)
                    if pushed:
                        src_ref = rref if pushable_side == "right" \
                            else lref
                        nodes.append(FragNode(Fragment(pushed), src_ref))
                        if pushable_side == "right":
                            rref = ("node", len(nodes) - 1)
                        else:
                            lref = ("node", len(nodes) - 1)
                    residual = kept
            if residual and join_type != "inner":
                # the count-based degree design assumes match == key
                # equality; a residual predicate would need in-executor
                # filtering (ref non-equi join conditions)
                raise PlanError(
                    "outer joins with non-equality ON conditions: "
                    "next round"
                )

            # a side's store, from what the planner sees of its
            # changelog (hash_join.py, module docstring): append-only ->
            # the ring ("pool"); retractable, its join key a proper part
            # of its stream key (many rows a key) while the other side
            # holds at most a row a key (its stream key within its join
            # key: few rows probe) -> "keyed" by the stream key; every
            # other retractable side the dense buckets.
            # ``join_force_dense`` can only veto (conformance runs).
            def key_cols(keys) -> "set | None":
                if all(isinstance(k, InputRef) for k in keys):
                    return {k.index for k in keys}
                return None

            def storage(pin, keys, other, other_keys) -> str:
                if cfg.join_force_dense:
                    return "dense"
                if pin.append_only:
                    return "pool"
                mine, theirs = key_cols(keys), key_cols(other_keys)
                if join_type == "inner" and mine is not None \
                        and theirs is not None and pin.stream_key \
                        and mine < set(pin.stream_key) \
                        and not other.append_only and other.stream_key \
                        and set(other.stream_key) <= theirs:
                    return "keyed"
                return "dense"

            def sizes(pin, keys, store, table_size, bucket_cap):
                """(table slots, bucket depth) of a retractable side:
                the configuration's where it names them; else, where the
                planner can bound the side's rows (``max_rows``: an
                aggregate's output), a slot a row for a keyed side, and
                for a dense side whose stream key lies within its join
                key (a row a key) a slot a key and buckets of four (a
                chunk's delete and insert of one key, twice over)."""
                cols = key_cols(keys)
                if pin.max_rows is None or pin.append_only:
                    return table_size, bucket_cap
                bound = 1 << max(pin.max_rows - 1, 63).bit_length()
                if store == "keyed":
                    return table_size or bound, bucket_cap
                if cols is not None and pin.stream_key \
                        and set(pin.stream_key) <= cols:
                    return table_size or bound, bucket_cap or 4
                return table_size, bucket_cap

            left_store = storage(left, left_keys, right, right_keys)
            right_store = storage(right, right_keys, left, left_keys)
            left_size, left_bucket = sizes(
                left, left_keys, left_store,
                cfg.join_left_table_size, cfg.join_left_bucket_cap)
            right_size, right_bucket = sizes(
                right, right_keys, right_store,
                cfg.join_right_table_size, cfg.join_right_bucket_cap)
            join = HashJoinExecutor(
                left.schema, right.schema, left_keys, right_keys,
                table_size=cfg.join_table_size,
                bucket_cap=cfg.join_bucket_cap,
                out_capacity=cfg.join_out_capacity,
                left_table_size=left_size,
                right_table_size=right_size,
                left_bucket_cap=left_bucket,
                right_bucket_cap=right_bucket,
                join_type=join_type,
                left_storage=left_store,
                right_storage=right_store,
                left_pool_size=cfg.join_pool_size,
                right_pool_size=cfg.join_pool_size,
                left_row_key=left.stream_key,
                right_row_key=right.stream_key,
            )
            if residual and join_type == "inner" \
                    and "pool" not in (left_store, right_store):
                # the executor applies a non-equality conjunct where it
                # stages pairs: a change emits the pairs that qualify
                # before and after it, not the key's every row.  Behind
                # a pool side (addressed by rank) it stays a filter.
                inner_scope = Scope(
                    join.out_schema,
                    tuple(left.scope.qualifiers)
                    + tuple(right.scope.qualifiers))
                pred = None
                for conj in residual:
                    e = Binder(inner_scope).bind(conj)
                    pred = e if pred is None else pred & e
                join.residual = pred
                in_join, residual = residual, []
            else:
                in_join = []
            # the join's OUTPUT schema carries the pad nullability;
            # semi/anti joins emit only the preserved side's columns
            if join.is_semi or join.is_anti:
                pres = left if join.preserve_left else right
                both = Scope(join.out_schema,
                             tuple(pres.scope.qualifiers))
            else:
                both = Scope(
                    join.out_schema,
                    tuple(left.scope.qualifiers)
                    + tuple(right.scope.qualifiers),
                )
            # window-keyed joins over watermarked sources clean closed
            # windows at barriers (bounded state, ref q8 pattern)
            for side_name, pin, keys in (("left", left, left_keys),
                                         ("right", right, right_keys)):
                if pin.window_size is None or pin.watermark_col is None:
                    continue
                window_idxs = [
                    i for i, f in enumerate(pin.schema)
                    if f.name in ("window_start", "window_end")
                ]
                for ke in keys:
                    if isinstance(ke, InputRef) and ke.index in window_idxs:
                        setattr(join, f"{side_name}_clean", JoinClean(
                            ke, pin.window_size, pin.wm_src_col))
                        break
            if join_type == "inner":
                # a time band between the sides' watermarked event-time
                # columns bounds the state too (ON or WHERE; the
                # conjuncts stay behind as post-join filters)
                self._band_cleaning(
                    join, left, right,
                    in_join + residual + list(where_conjs),
                    both,
                )
                # so does an equality of two watermarked columns (a
                # window column of an aggregate on each side, Nexmark
                # q5): a row is dead once neither side can change at
                # its key any more
                if left.wm_lags and right.wm_lags \
                        and left.wm_src_col is not None \
                        and right.wm_src_col is not None:
                    for lk, rk in zip(left_keys, right_keys):
                        if not (isinstance(lk, InputRef)
                                and isinstance(rk, InputRef)
                                and lk.index in left.wm_lags
                                and rk.index in right.wm_lags):
                            continue
                        lag = max(left.wm_lags[lk.index],
                                  right.wm_lags[rk.index])
                        if join.left_clean is None:
                            join.left_clean = JoinClean(
                                lk, lag, left.wm_src_col,
                                right.wm_src_col)
                        if join.right_clean is None:
                            join.right_clean = JoinClean(
                                rk, lag, right.wm_src_col,
                                left.wm_src_col)
                        break
            nodes.append(JoinNode(join, lref, rref))
            ref = ("node", len(nodes) - 1)
            if residual:
                b = Binder(both)
                nodes.append(FragNode(Fragment([
                    FilterExecutor(both.schema, b.bind(c))
                    for c in residual
                ]), ref))
                ref = ("node", len(nodes) - 1)
            # outer-join transitions retract pads even over append-only
            # inputs, so only an inner join preserves append-only-ness
            if join.emit_pairs:
                skey = None
                if left.stream_key is not None \
                        and right.stream_key is not None:
                    skey = list(left.stream_key) + [
                        n_left + k for k in right.stream_key
                    ]
            else:
                skey = (left if join.preserve_left else right).stream_key
            info = PlannedInput(
                None, [], both, both.schema, None, None,
                left.append_only and right.append_only
                and join_type == "inner",
                stream_key=skey,
            )
            return ref, info

        root_ref, root = resolve(select.from_)
        both = root.scope
        post_execs: list[Executor] = []
        b = Binder(both)
        # WHERE conjuncts comparing a column against an uncorrelated
        # scalar subquery peel off into dynamic-filter nodes (ref
        # dynamic_filter.rs); the rest become post-join filters
        where_dyn: list = []
        for conj in list(where_conjs):
            m = self._match_scalar_sub_cmp(conj)
            if m is not None and isinstance(m[0], ast.ColumnRef) \
                    and self._is_uncorrelated(m[2]):
                where_dyn.append(m)
                where_conjs.remove(conj)
        for conj in where_conjs:
            post_execs.append(
                FilterExecutor(both.schema, b.bind(conj))
            )
        if where_dyn:
            from risingwave_tpu.stream.dynamic_filter import (
                DynamicFilterExecutor,
            )
            ref = root_ref
            if post_execs:
                nodes.append(FragNode(Fragment(post_execs), ref))
                ref = ("node", len(nodes) - 1)
                post_execs = []
            for lhs, cmp, sub in where_dyn:
                if len(sub.items) != 1 or isinstance(
                        sub.items[0].expr, ast.Star):
                    raise PlanError(
                        "scalar subquery must select exactly one column"
                    )
                sref, _sinfo = resolve_subquery(
                    ast.SubqueryRef(sub, f"_sc_sq{len(nodes)}")
                )
                nodes.append(JoinNode(DynamicFilterExecutor(
                    both.schema,
                    filter_col=both.resolve(lhs.name, lhs.table),
                    cmp=cmp,
                    pool_size=max(cfg.topn_pool_size,
                                  2 * cfg.chunk_capacity),
                ), ref, sref))
                ref = ("node", len(nodes) - 1)
            root_ref = ref
            # the dynamic filter's output retracts when the threshold
            # moves, even over append-only inputs
            import dataclasses as _dc
            root = _dc.replace(root, append_only=False)

        has_agg = bool(select.group_by) or self._has_agg(select)
        # HAVING conjuncts comparing an aggregate against a scalar
        # subquery peel off into dynamic-filter nodes (ref
        # dynamic_filter.rs — `HAVING agg >= (SELECT ...)`)
        having_subs: list = []
        if has_agg and select.having is not None:
            plain_hv: list = []
            for c in self._conjuncts(select.having):
                m = self._match_scalar_sub_cmp(c)
                if m is not None:
                    having_subs.append(m)
                else:
                    plain_hv.append(c)
            if having_subs:
                import dataclasses
                new_hv = None
                for r in plain_hv:
                    new_hv = r if new_hv is None \
                        else ast.BinaryOp("and", new_hv, r)
                select = dataclasses.replace(select, having=new_hv)
        if has_agg and having_subs:
            from risingwave_tpu.stream.dynamic_filter import (
                DynamicFilterExecutor,
            )
            execs2, out_schema, pk_pos, extra_pos = self._plan_agg(
                select, both, root,
                extra_out=[lhs for lhs, _, _ in having_subs],
            )
            post_execs.extend(execs2)
            nodes.append(FragNode(Fragment(post_execs), root_ref))
            ref = ("node", len(nodes) - 1)
            for (lhs, cmp, sub), pos in zip(having_subs, extra_pos):
                if len(sub.items) != 1 or isinstance(sub.items[0].expr,
                                                     ast.Star):
                    raise PlanError(
                        "scalar subquery must select exactly one column"
                    )
                sref, _sinfo = resolve_subquery(
                    ast.SubqueryRef(sub, f"_sc_sq{len(nodes)}")
                )
                nodes.append(JoinNode(DynamicFilterExecutor(
                    out_schema, filter_col=pos, cmp=cmp,
                    pool_size=max(cfg.topn_pool_size,
                                  2 * cfg.chunk_capacity),
                ), ref, sref))
                ref = ("node", len(nodes) - 1)
            tail: list[Executor] = []
            self._append_terminal(
                tail, out_schema, select,
                input_append_only=False, has_agg=True,
                pk_positions=pk_pos, sink=sink, eowc=False,
            )
            nodes.append(FragNode(Fragment(tail), ref))
            return DagPlan(
                sources, nodes, len(nodes) - 1, len(tail) - 1
            )
        if has_agg:
            if group_topn is not None:
                raise PlanError(
                    "row_number subquery over an aggregation: next round"
                )
            # aggregation over the joined stream (TPC-H/q4 shape): the
            # join's retractions flow into the agg, which handles them
            execs2, out_schema, pk_pos = self._plan_agg(
                select, both, root
            )
            post_execs.extend(execs2)
            self._append_terminal(
                post_execs, out_schema, select,
                input_append_only=False, has_agg=True,
                pk_positions=pk_pos, sink=sink, eowc=False,
            )
        else:
            items = self._expand_items(select.items, both)
            proj = [(name, b.bind(e)) for name, e in items]
            pk_positions: list[int] = []
            if sink is None and not root.append_only \
                    and root.stream_key is not None:
                # keyed by the join output's stream key (left ++ right
                # input keys) so duplicate projected rows keep multiset
                # semantics (e.g. nexmark q5: identical (auction, num)
                # rows from different windows)
                pk_positions = self._stream_key_projection(
                    proj, both.schema, root.stream_key
                )
            gtn = None
            if group_topn is not None:
                gtn = self._resolve_group_topn(group_topn, both, proj)
            post_execs.append(ProjectExecutor(both.schema, proj))
            out_schema = post_execs[-1].out_schema
            self._append_terminal(
                post_execs, out_schema, select,
                input_append_only=root.append_only, has_agg=False,
                pk_positions=pk_positions, sink=sink, eowc=False,
                group_topn=gtn,
            )
        nodes.append(FragNode(Fragment(post_execs), root_ref))
        return self._share_subplans(DagPlan(
            sources, nodes, len(nodes) - 1, len(post_execs) - 1
        ))

    # -- shared subplans ---------------------------------------------------
    def _share_subplans(self, plan: DagPlan) -> DagPlan:
        """Plan once what a statement states twice (upstream shares the
        subplan): two fragment nodes that read the same input through
        the same executors keep ONE copy of their common prefix — one
        state in the checkpoint, one pass over the input — and each its
        own suffix.  Nexmark q5 names ``count(*) ... GROUP BY auction,
        window_start`` under two aliases; its window node and its
        aggregate are planned once and feed both the max and the join's
        left side."""
        from risingwave_tpu.stream.dag import FragNode, JoinNode

        order = list(plan.nodes)
        mv_ex = order[plan.mv_node].fragment.executors[plan.mv_index]

        def deref(ref):
            return order[ref[1]] if ref[0] == "node" else ref

        # refs become the node objects themselves while nodes move
        for n in list(order):
            if isinstance(n, FragNode):
                n.input = deref(n.input)
            else:
                n.left, n.right = deref(n.left), deref(n.right)

        def redirect(old, new) -> None:
            for n in order:
                if isinstance(n, FragNode):
                    if n.input is old:
                        n.input = new
                else:
                    if n.left is old:
                        n.left = new
                    if n.right is old:
                        n.right = new

        def share_one() -> bool:
            frags = [n for n in order if isinstance(n, FragNode)]
            for ai, a in enumerate(frags):
                for b in frags[ai + 1:]:
                    if not (a.input is b.input or a.input == b.input):
                        continue
                    xa, xb = a.fragment.executors, b.fragment.executors
                    n = 0
                    while n < min(len(xa), len(xb)) \
                            and self._exec_eq(xa[n], xb[n]):
                        n += 1
                    if n == 0:
                        continue
                    if n < len(xa):
                        shared = FragNode(Fragment(xa[:n]), a.input)
                        order.insert(order.index(a), shared)
                        a.fragment, a.input = Fragment(xa[n:]), shared
                    else:
                        shared = a
                    if n < len(xb):
                        b.fragment, b.input = Fragment(xb[n:]), shared
                    else:
                        redirect(b, shared)
                        order.remove(b)
                    return True
            return False

        while share_one():
            pass
        at = {id(n): i for i, n in enumerate(order)}

        def ref_of(x):
            return x if isinstance(x, tuple) else ("node", at[id(x)])

        for n in order:
            if isinstance(n, FragNode):
                n.input = ref_of(n.input)
            else:
                n.left, n.right = ref_of(n.left), ref_of(n.right)
        mv_node, mv_index = next(
            (i, n.fragment.executors.index(mv_ex))
            for i, n in enumerate(order)
            if isinstance(n, FragNode) and mv_ex in n.fragment.executors)
        return DagPlan(plan.sources, order, mv_node, mv_index)

    @staticmethod
    def _exec_eq(a, b) -> bool:
        """Two executors that do the same to the same input (the kinds a
        FROM subquery is planned from; anything else is its own)."""
        from risingwave_tpu.stream.executor import HopWindowExecutor
        eq = Planner._expr_eq
        if type(a) is not type(b) or a.in_schema != b.in_schema:
            return False
        if isinstance(a, ProjectExecutor):
            return len(a.exprs) == len(b.exprs) and all(
                na == nb and eq(ea, eb)
                for (na, ea), (nb, eb) in zip(a.exprs, b.exprs))
        if isinstance(a, FilterExecutor):
            return eq(a.predicate, b.predicate)
        if isinstance(a, WatermarkFilterExecutor):
            return (a.ts_col, a.delay_us) == (b.ts_col, b.delay_us)
        if isinstance(a, HopWindowExecutor):
            return (a.ts_col, a.slide_us, a.size_us) \
                == (b.ts_col, b.slide_us, b.size_us)
        if isinstance(a, HashAggExecutor):
            ka, kb = dict(a._ctor_kwargs), dict(b._ctor_kwargs)
            ga, gb = ka.pop("group_by"), kb.pop("group_by")
            ca, cb = ka.pop("aggs"), kb.pop("aggs")

            def opt_eq(x, y):
                return (x is None and y is None) or (
                    x is not None and y is not None and eq(x, y))

            return ka == kb and len(ga) == len(gb) and len(ca) == len(cb) \
                and all(na == nb and eq(ea, eb)
                        for (na, ea), (nb, eb) in zip(ga, gb)) \
                and all((x.kind, x.alias, x.distinct)
                        == (y.kind, y.alias, y.distinct)
                        and opt_eq(x.arg, y.arg)
                        and opt_eq(x.filter, y.filter)
                        for x, y in zip(ca, cb)) \
                and (a.table_size, a.minput_table_size,
                     a.distinct_table_size, a.spill_ring) \
                == (b.table_size, b.minput_table_size,
                    b.distinct_table_size, b.spill_ring)
        return False

    @staticmethod
    def _named_columns(*roots) -> "set | None":
        """``(table, name)`` of every column a statement names anywhere
        (its FROM subqueries included), or None where a ``SELECT *``
        makes the set open."""
        refs: set = set()
        stack = list(roots)
        while stack:
            x = stack.pop()
            if isinstance(x, ast.ColumnRef):
                refs.add((x.table, x.name))
            elif isinstance(x, ast.SelectItem) \
                    and isinstance(x.expr, ast.Star):
                return None
            elif dataclasses.is_dataclass(x) and not isinstance(x, type):
                stack.extend(getattr(x, f.name)
                             for f in dataclasses.fields(x))
            elif isinstance(x, (tuple, list)):
                stack.extend(x)
        return refs

    @staticmethod
    def _time_offset(e: Expr) -> "tuple[int, int] | None":
        """``(column, offset us)`` of ``col`` or ``col +/- constant``."""
        if isinstance(e, InputRef):
            return e.index, 0
        if isinstance(e, EFuncCall) and e.name in ("add", "subtract") \
                and len(e.args) == 2:
            a, b = e.args
            if e.name == "add" and isinstance(a, ELiteral):
                a, b = b, a
            if isinstance(a, InputRef) and isinstance(b, ELiteral) \
                    and isinstance(b.value, int) \
                    and not isinstance(b.value, bool):
                return a.index, b.value if e.name == "add" else -b.value
        return None

    def _band_cleaning(self, join: HashJoinExecutor, left: PlannedInput,
                       right: PlannedInput, conjuncts: list,
                       both: Scope) -> None:
        """Turn a band between two watermarked event-time columns into
        the join's cleaning rules.

        ``L.t >= R.u + lo`` (a lower bound on the left): a left row is
        dead once every right row still to come lies too far ahead, so
        the left side retires below ``watermark(R.u) + lo``.
        ``L.t <= R.u + hi``: a right row is dead once every left row
        still to come lies past it: the right side retires below
        ``watermark(L.t) - hi``.  Strict bounds count as loose ones
        (keeps a row a moment longer).  A side whose join key already
        cleans it (a window key) keeps that rule."""
        if not (left.wm_lags and right.wm_lags) \
                or left.wm_src_col is None or right.wm_src_col is None:
            return
        n_left = len(left.schema)
        flip = {"greater_than_or_equal": "less_than_or_equal",
                "greater_than": "less_than",
                "less_than_or_equal": "greater_than_or_equal",
                "less_than": "greater_than"}
        lower = upper = None   # (left col, right col, offset us)
        for conj in conjuncts:
            try:
                e = Binder(both).bind(conj)
            except Exception:
                continue  # not this join's columns (or not a scalar)
            if not (isinstance(e, EFuncCall) and e.name in flip
                    and len(e.args) == 2):
                continue
            x, y = (self._time_offset(a) for a in e.args)
            if x is None or y is None:
                continue
            op = e.name
            if x[0] >= n_left > y[0]:       # R.. op L..  ->  L.. op' R..
                x, y, op = y, x, flip[op]
            if not (x[0] < n_left <= y[0]):
                continue
            lcol, rcol = x[0], y[0] - n_left
            if lcol not in left.wm_lags or rcol not in right.wm_lags:
                continue
            bound = (lcol, rcol, y[1] - x[1])   # L.t op R.u + offset
            if op.startswith("greater"):
                if lower is None or bound[2] > lower[2]:
                    lower = bound
            elif upper is None or bound[2] < upper[2]:
                upper = bound
        if lower is not None and join.left_clean is None:
            lcol, rcol, lo = lower
            join.left_clean = JoinClean(
                InputRef(lcol), right.wm_lags[rcol] - lo,
                left.wm_src_col, right.wm_src_col)
        if upper is not None and join.right_clean is None:
            lcol, rcol, hi = upper
            join.right_clean = JoinClean(
                InputRef(rcol), left.wm_lags[lcol] + hi,
                right.wm_src_col, left.wm_src_col)

    def _conjuncts(self, e) -> list:
        if isinstance(e, ast.BinaryOp) and e.op == "and":
            return self._conjuncts(e.left) + self._conjuncts(e.right)
        return [e]

    _SUB_CMPS = {"greater_than": "gt", "greater_than_or_equal": "ge",
                 "less_than": "lt", "less_than_or_equal": "le",
                 "equal": "eq"}
    _SUB_FLIP = {"gt": "lt", "ge": "le", "lt": "gt", "le": "ge",
                 "eq": "eq"}

    def _match_scalar_sub_cmp(self, c):
        """``lhs CMP (SELECT ...)`` → (lhs_ast, cmp, sub_select)."""
        if not (isinstance(c, ast.BinaryOp)
                and c.op in self._SUB_CMPS):
            return None
        cmp = self._SUB_CMPS[c.op]
        if isinstance(c.right, ast.ScalarSubquery) \
                and not isinstance(c.left, ast.ScalarSubquery):
            return (c.left, cmp, c.right.select)
        if isinstance(c.left, ast.ScalarSubquery) \
                and not isinstance(c.right, ast.ScalarSubquery):
            return (c.right, self._SUB_FLIP[cmp], c.left.select)
        return None

    def _equi_pair(self, e, lscope: Scope, rscope: Scope, n_left: int):
        if not (isinstance(e, ast.BinaryOp) and e.op == "equal"):
            return None
        sides = []
        for operand in (e.left, e.right):
            try:
                lb = Binder(lscope).bind(operand)
                sides.append(("l", lb))
                continue
            except BindError:
                pass
            try:
                rb = Binder(rscope).bind(operand)
                sides.append(("r", rb))
            except BindError:
                return None
        if len(sides) != 2 or {s[0] for s in sides} != {"l", "r"}:
            return None
        l = next(x for t, x in sides if t == "l")
        r = next(x for t, x in sides if t == "r")
        return l, r

    # -- misc -------------------------------------------------------------
    def _expand_items(self, items, scope: Scope):
        out = []
        for idx, item in enumerate(items):
            if isinstance(item.expr, ast.Star):
                want = item.expr.table
                if want is not None and want not in scope.qualifiers:
                    raise PlanError(
                        f"table {want!r} in {want}.* not found in FROM"
                    )
                for ci, f in enumerate(scope.schema):
                    # pk bookkeeping columns of an upstream MV are not
                    # user-visible (each plan re-derives its own)
                    if f.name.startswith("_hidden_"):
                        continue
                    if want is not None and scope.qualifiers[ci] != want:
                        continue
                    out.append((f.name, ast.ColumnRef(f.name,
                                                      scope.qualifiers[ci])))
                continue
            out.append(
                (item.alias or self._default_name(item.expr, idx), item.expr)
            )
        return out

    @staticmethod
    def _bind_order_key(e, binder: Binder, schema: Schema) -> Expr:
        """ORDER BY <n> is positional (postgres); otherwise bind."""
        if isinstance(e, ast.Literal) and e.type_name == "int":
            if not (1 <= e.value <= len(schema)):
                raise PlanError(f"ORDER BY position {e.value} out of range")
            return InputRef(e.value - 1)
        return binder.bind(e)

    @staticmethod
    def _default_name(e, idx: int) -> str:
        if isinstance(e, ast.ColumnRef):
            return e.name
        if isinstance(e, ast.FuncCall):
            return e.name
        return f"col{idx}"
