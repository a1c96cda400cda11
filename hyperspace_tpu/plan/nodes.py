"""Logical plan IR: the minimal operator set the rewrite rules reason over.

The reference matches Catalyst trees of Scan/Filter/Project/Join
(FilterIndexRule.scala:158-197's ExtractFilterNode/ExtractRelation;
JoinIndexRule.scala:165-166's linear-plan requirement).  We model exactly
that: a small immutable tree, leaf ``Scan`` nodes carrying relation metadata,
and helpers (``leaf_relations``, ``is_linear``, ``output_columns``) the rules
need.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

from hyperspace_tpu.plan.expr import Col as ColRef, Expr


@dataclasses.dataclass(frozen=True)
class ScanRelation:
    """Leaf relation metadata: where and how to read the data.

    ``index_scan_of`` marks a scan that was already rewritten to an index
    (the marker the rules use to avoid double application — the reference's
    ``indexRelation→true`` option, IndexConstants.scala:59 /
    RuleUtils.scala:173-183).  ``bucket_spec`` carries (num_buckets,
    bucket_columns, sort_columns) for bucketed index data.
    ``file_paths``, when set, overrides root-path listing (used for index
    scans and hybrid-scan file subsets).
    """

    root_paths: Tuple[str, ...]
    file_format: str = "parquet"
    options: Tuple[Tuple[str, str], ...] = ()
    index_scan_of: Optional[str] = None
    bucket_spec: Optional[Tuple[int, Tuple[str, ...], Tuple[str, ...]]] = None
    file_paths: Optional[Tuple[str, ...]] = None
    # Predicate-derived bucket pruning: only these buckets need scanning
    # (FilterIndexRule bucket pruning, IndexConstants.scala:52-53).
    prune_to_buckets: Optional[Tuple[int, ...]] = None
    # Data-skipping: the scan still reads the SOURCE relation, but its file
    # list was pruned by this sketch index (rules/data_skipping.py).
    # (pruned files, total files) for display.
    data_skipping_of: Optional[str] = None
    data_skipping_stats: Optional[Tuple[int, int]] = None
    # Hybrid scan (rules/hybrid.py): this scan reads the source files the
    # index ``hybrid_appended_of`` does not cover, as listed at the
    # source's ``source_version`` (None for a source without versions).
    hybrid_appended_of: Optional[str] = None
    source_version: Optional[int] = None
    # What-if planning (advisor/hypothetical.py): this scan was rewritten
    # onto a HYPOTHETICAL index — a plan-only artifact with zero data
    # files.  The executor refuses to run it; only the advisor's plan
    # diff / bytes estimation ever consumes such a plan.  With no files
    # to read a footer from, the relation carries its own schema
    # ((column, dtype) pairs, the index's indexed+included columns) so
    # downstream pruning/pushdown still resolve.
    hypothetical: bool = False
    hypothetical_schema: Optional[Tuple[Tuple[str, str], ...]] = None

    @property
    def options_dict(self) -> Dict[str, str]:
        return dict(self.options)


class LogicalPlan:
    children: Tuple["LogicalPlan", ...] = ()

    def leaf_relations(self) -> List["Scan"]:
        if isinstance(self, Scan):
            return [self]
        out: List[Scan] = []
        for c in self.children:
            out.extend(c.leaf_relations())
        return out

    def is_linear(self) -> bool:
        """True if no node has more than one child (JoinIndexRule.scala:165-166
        requires each join side to be a linear chain over one relation)."""
        if len(self.children) > 1:
            return False
        return all(c.is_linear() for c in self.children)

    def output_columns(self, schema_of) -> List[str]:
        """Columns this plan produces; ``schema_of(scan)`` resolves leaf
        schemas (host callback so the IR stays IO-free)."""
        raise NotImplementedError

    def transform_up(self, fn) -> "LogicalPlan":
        new_children = tuple(c.transform_up(fn) for c in self.children)
        node = self.with_children(new_children) if new_children != self.children else self
        return fn(node)

    def with_children(self, children: Tuple["LogicalPlan", ...]) -> "LogicalPlan":
        raise NotImplementedError

    def simple_string(self) -> str:
        raise NotImplementedError

    def tree_string(self, indent: int = 0) -> str:
        lines = ["  " * indent + self.simple_string()]
        for c in self.children:
            lines.append(c.tree_string(indent + 1))
        return "\n".join(lines)


class Scan(LogicalPlan):
    def __init__(self, relation: ScanRelation) -> None:
        self.relation = relation
        self.children = ()

    def output_columns(self, schema_of) -> List[str]:
        return schema_of(self)

    def with_children(self, children) -> "Scan":
        assert not children
        return self

    def simple_string(self) -> str:
        rel = self.relation
        if rel.index_scan_of:
            tag = f"Hyperspace(Type: CI, Name: {rel.index_scan_of})"
            if rel.prune_to_buckets is not None:
                tag += f" [buckets: {len(rel.prune_to_buckets)}/{rel.bucket_spec[0]}]"
            if rel.data_skipping_stats is not None:
                kept, total = rel.data_skipping_stats
                tag += f" [files: {kept}/{total}]"
            return f"Scan {tag}"
        base = f"Scan {','.join(rel.root_paths)} ({rel.file_format})"
        if rel.data_skipping_of:
            tag = f"Hyperspace(Type: DS, Name: {rel.data_skipping_of})"
            if rel.data_skipping_stats is not None:
                kept, total = rel.data_skipping_stats
                tag += f" [files: {kept}/{total}]"
            return f"{base} {tag}"
        return base


class Filter(LogicalPlan):
    def __init__(self, condition: Expr, child: LogicalPlan) -> None:
        self.condition = condition
        self.children = (child,)

    @property
    def child(self) -> LogicalPlan:
        return self.children[0]

    def output_columns(self, schema_of) -> List[str]:
        return self.child.output_columns(schema_of)

    def with_children(self, children) -> "Filter":
        (child,) = children
        return Filter(self.condition, child)

    def simple_string(self) -> str:
        return f"Filter {self.condition!r}"


class Project(LogicalPlan):
    def __init__(self, columns: Sequence[str], child: LogicalPlan) -> None:
        self.columns = list(columns)
        self.children = (child,)

    @property
    def child(self) -> LogicalPlan:
        return self.children[0]

    def output_columns(self, schema_of) -> List[str]:
        return list(self.columns)

    def with_children(self, children) -> "Project":
        (child,) = children
        return Project(self.columns, child)

    def simple_string(self) -> str:
        return f"Project [{', '.join(self.columns)}]"


class Compute(LogicalPlan):
    """Expression-valued projection: output is exactly ``exprs`` — (name,
    Expr) pairs, where a bare column passthrough is ``(name, Col(name))``.
    The computed analog of Catalyst's Project-with-expressions (the
    reference rides Catalyst for ``1 - l_discount`` arithmetic; this engine
    owns it).  The rewrite rules never match a Compute itself — pruning
    derives its input needs from the expressions' referenced columns, so
    a plain Project lands over the scan below and the rules match THAT."""

    def __init__(self, exprs: Sequence[Tuple[str, Expr]],
                 child: LogicalPlan) -> None:
        if not exprs:
            raise ValueError("Compute needs at least one output expression")
        names = [n for n, _ in exprs]
        if len(set(names)) != len(names):
            raise ValueError(f"Duplicate output names in select: {names}")
        self.exprs = tuple((n, e) for n, e in exprs)
        self.children = (child,)

    @property
    def child(self) -> LogicalPlan:
        return self.children[0]

    def input_columns(self) -> List[str]:
        out: set = set()
        for _n, e in self.exprs:
            out |= e.referenced_columns()
        return sorted(out)

    def output_columns(self, schema_of) -> List[str]:
        return [n for n, _e in self.exprs]

    def with_children(self, children) -> "Compute":
        (child,) = children
        return Compute(self.exprs, child)

    def simple_string(self) -> str:
        parts = []
        for n, e in self.exprs:
            if isinstance(e, ColRef) and e.name == n:
                parts.append(n)
            else:
                parts.append(f"{e!r} AS {n}")
        return f"Compute [{', '.join(parts)}]"


class Window(LogicalPlan):
    """One analytic column: ``func(value) OVER (PARTITION BY keys ORDER BY
    keys)`` appended to the child's output — the reference's corpus uses
    these throughout (rank()/row_number()/sum() OVER ... in TPC-DS q36,
    q44, q47, q49, q57 under
    /root/reference/src/test/resources/tpcds/queries/).

    Spark semantics:
      - row_number/rank/dense_rank need an ORDER BY; results are INT.
      - aggregates (sum/min/max/mean/count) WITHOUT order_by reduce the
        whole partition; WITH order_by they are running aggregates over
        the default RANGE frame (UNBOUNDED PRECEDING..CURRENT ROW), so
        rows tied on the order key share one value.
      - order_by nulls sort FIRST ascending / LAST descending (Spark's
        null order, same as Sort).
    Host-evaluated (sort + segment scan); ties in the order key make the
    ranking functions deterministic regardless of input order.
    """

    RANKING = ("row_number", "rank", "dense_rank", "ntile")
    AGGREGATES = ("sum", "min", "max", "mean", "count")
    SHIFTS = ("lag", "lead")  # TPC-DS q47/q57's prev/next-period shape
    POSITIONAL = ("first_value", "last_value")

    def __init__(self, name: str, func: str, value: Optional[str],
                 partition_by: Sequence[str],
                 order_by: Sequence[Tuple[str, bool]],
                 child: LogicalPlan, offset: int = 1,
                 frame: Optional[Tuple[Optional[int],
                                       Optional[int]]] = None) -> None:
        all_funcs = (self.RANKING + self.AGGREGATES + self.SHIFTS
                     + self.POSITIONAL)
        if func not in all_funcs:
            raise ValueError(
                f"Unsupported window function {func!r}; one of "
                f"{all_funcs}")
        if func in self.RANKING + self.SHIFTS and not order_by:
            raise ValueError(f"{func}() requires an ORDER BY")
        if func in self.RANKING and func != "ntile" and value is not None:
            raise ValueError(f"{func}() takes no value column")
        if func in self.AGGREGATES and func != "count" and value is None:
            raise ValueError(f"window {func}() needs a value column")
        if func in self.SHIFTS:
            if value is None:
                raise ValueError(f"{func}() needs a value column")
            if not isinstance(offset, int) or offset < 0:
                raise ValueError(f"{func}() offset must be a "
                                 f"non-negative int, got {offset!r}")
        if func == "ntile":
            if not isinstance(offset, int) or offset < 1:
                raise ValueError(f"ntile(n) needs a positive integer "
                                 f"tile count, got {offset!r}")
            if value is not None:
                raise ValueError("ntile() takes no value column")
        if func in self.POSITIONAL and value is None:
            raise ValueError(f"{func}() needs a value column")
        if frame is not None:
            if func not in self.AGGREGATES + self.POSITIONAL:
                raise ValueError(
                    f"A ROWS frame only applies to aggregate/"
                    f"first_value/last_value windows, not {func}()")
            if not order_by:
                raise ValueError("A ROWS frame requires an ORDER BY")
            lo, hi = frame
            for b in (lo, hi):
                if b is not None and not isinstance(b, int):
                    raise ValueError(f"Frame bounds must be ints or "
                                     f"None (unbounded), got {b!r}")
            if lo is not None and hi is not None and lo > hi:
                raise ValueError(
                    f"Frame lower bound {lo} is above upper bound {hi}")
            frame = (lo, hi)
        self.name = name
        self.func = func
        self.value = value
        self.offset = int(offset)
        self.frame = frame
        self.partition_by = tuple(partition_by)
        self.order_by = tuple((c, bool(a)) for c, a in order_by)
        self.children = (child,)

    @property
    def child(self) -> LogicalPlan:
        return self.children[0]

    def output_columns(self, schema_of) -> List[str]:
        base = self.child.output_columns(schema_of)
        return list(base) + ([self.name] if self.name not in base else [])

    def with_children(self, children) -> "Window":
        (child,) = children
        return Window(self.name, self.func, self.value, self.partition_by,
                      self.order_by, child, offset=self.offset,
                      frame=self.frame)

    @staticmethod
    def _bound_string(off: Optional[int], upper: bool) -> str:
        if off is None:
            return ("UNBOUNDED FOLLOWING" if upper
                    else "UNBOUNDED PRECEDING")
        if off == 0:
            return "CURRENT ROW"
        return (f"{off} FOLLOWING" if off > 0
                else f"{-off} PRECEDING")

    def simple_string(self) -> str:
        arg = self.value or ""
        if self.func in self.SHIFTS:
            arg = f"{arg}, {self.offset}"
        elif self.func == "ntile":
            arg = str(self.offset)
        over = []
        if self.partition_by:
            over.append(f"PARTITION BY {', '.join(self.partition_by)}")
        if self.order_by:
            keys = ", ".join(f"{c}{'' if a else ' DESC'}"
                             for c, a in self.order_by)
            over.append(f"ORDER BY {keys}")
        if self.frame is not None:
            lo, hi = self.frame
            over.append(f"ROWS BETWEEN {self._bound_string(lo, False)} "
                        f"AND {self._bound_string(hi, True)}")
        return (f"Window {self.name} := {self.func}({arg}) "
                f"OVER ({' '.join(over)})")


class WithColumns(LogicalPlan):
    """Append (or replace, by name) computed columns while keeping the
    child's full output — ``df.with_column('rev', ...)``.  Lazy like every
    node: the child's column set resolves at execution."""

    def __init__(self, exprs: Sequence[Tuple[str, Expr]],
                 child: LogicalPlan) -> None:
        if not exprs:
            raise ValueError("with_column needs at least one expression")
        names = [n for n, _ in exprs]
        if len(set(names)) != len(names):
            raise ValueError(f"Duplicate with_column names: {names}")
        self.exprs = tuple((n, e) for n, e in exprs)
        self.children = (child,)

    @property
    def child(self) -> LogicalPlan:
        return self.children[0]

    def output_columns(self, schema_of) -> List[str]:
        base = self.child.output_columns(schema_of)
        new = [n for n, _e in self.exprs if n not in base]
        return list(base) + new

    def with_children(self, children) -> "WithColumns":
        (child,) = children
        return WithColumns(self.exprs, child)

    def simple_string(self) -> str:
        parts = ", ".join(f"{n} := {e!r}" for n, e in self.exprs)
        return f"WithColumns [{parts}]"


class Join(LogicalPlan):
    """Equi-join with SQL join types.  The engine executes every type (the
    reference's engine, Spark, does too); the JoinIndexRule REWRITE stays
    scoped to inner equi-joins exactly like JoinIndexRule.scala:134-140 —
    but index scans introduced by other rules still execute bucket-aligned
    under any type, since per-bucket null-extension composes (non-matching
    rows can only be certified unmatched within their own bucket, and
    co-partitioning guarantees their matches couldn't live elsewhere)."""

    HOW = ("inner", "left", "right", "full", "semi", "anti")

    def __init__(self, left: LogicalPlan, right: LogicalPlan,
                 condition: Expr, how: str = "inner",
                 residual: "Optional[Expr]" = None) -> None:
        if how not in self.HOW:
            raise ValueError(f"Unsupported join type {how!r}; "
                             f"expected one of {self.HOW}")
        self.condition = condition
        self.how = how
        # Residual predicate over the MATCHED pair rows, evaluated after
        # the equi match (NULL => no match, SQL semantics).  Constructed
        # by the subquery rewrite for inequality correlations (TPC-H
        # Q21's literal EXISTS: l2.l_suppkey <> l1.l_suppkey rides the
        # l_orderkey equality as a residual) — the public join() surface
        # stays equi-only, like JoinIndexRule.scala:134-140's scope.
        self.residual = residual
        self.children = (left, right)

    @property
    def left(self) -> LogicalPlan:
        return self.children[0]

    @property
    def right(self) -> LogicalPlan:
        return self.children[1]

    def output_columns(self, schema_of) -> List[str]:
        if self.how in ("semi", "anti"):
            # Existence joins produce the LEFT side only (Spark semantics).
            return self.left.output_columns(schema_of)
        return (self.left.output_columns(schema_of)
                + self.right.output_columns(schema_of))

    def with_children(self, children) -> "Join":
        left, right = children
        return Join(left, right, self.condition, self.how,
                    residual=self.residual)

    def simple_string(self) -> str:
        if self.residual is not None:
            return (f"Join {self.how} on {self.condition!r} "
                    f"residual {self.residual!r}")
        return f"Join {self.how} on {self.condition!r}"


class Distinct(LogicalPlan):
    """Unique rows over the child's FULL output (SQL DISTINCT).  Lazy: the
    column set resolves at execution, not plan construction, like every
    other node (the IR stays IO-free)."""

    def __init__(self, child: LogicalPlan) -> None:
        self.children = (child,)

    @property
    def child(self) -> LogicalPlan:
        return self.children[0]

    def output_columns(self, schema_of) -> List[str]:
        return self.child.output_columns(schema_of)

    def with_children(self, children) -> "Distinct":
        (child,) = children
        return Distinct(child)

    def simple_string(self) -> str:
        return "Distinct"


class SetOp(LogicalPlan):
    """INTERSECT / EXCEPT with SQL SET semantics: distinct rows of the
    left side that do (intersect) or do not (except) appear in the right
    side, comparing rows null-safely (SQL set operations treat NULL as
    equal to NULL, unlike join predicates).  Columns pair POSITIONALLY —
    the SQL layer renames the right branch to the left's names before
    constructing this node, Spark-style.

    Reference contract: the TPC-DS corpus the reference validates
    against uses INTERSECT (e.g. q14's cross-channel item selection,
    /root/reference/src/test/resources/tpcds/queries/q14a.sql); Spark
    plans these as left-semi/anti joins with null-safe equality."""

    KINDS = ("intersect", "except")

    def __init__(self, kind: str, left: LogicalPlan,
                 right: LogicalPlan) -> None:
        if kind not in self.KINDS:
            raise ValueError(f"SetOp kind must be one of {self.KINDS}, "
                             f"got {kind!r}")
        self.kind = kind
        self.children = (left, right)

    @property
    def left(self) -> LogicalPlan:
        return self.children[0]

    @property
    def right(self) -> LogicalPlan:
        return self.children[1]

    def output_columns(self, schema_of) -> List[str]:
        return self.left.output_columns(schema_of)

    def with_children(self, children) -> "SetOp":
        left, right = children
        return SetOp(self.kind, left, right)

    def simple_string(self) -> str:
        return self.kind.upper()


class Sort(LogicalPlan):
    """Total order by ``keys`` — (column, ascending) pairs.  Like
    Aggregate, the rewrite rules pass through it and rewrite the patterns
    below."""

    def __init__(self, keys: Sequence[Tuple[str, bool]],
                 child: LogicalPlan) -> None:
        if not keys:
            raise ValueError("Sort needs at least one key")
        self.keys = tuple((c, bool(asc)) for c, asc in keys)
        self.children = (child,)

    @property
    def child(self) -> LogicalPlan:
        return self.children[0]

    def output_columns(self, schema_of) -> List[str]:
        return self.child.output_columns(schema_of)

    def with_children(self, children) -> "Sort":
        (child,) = children
        return Sort(self.keys, child)

    def simple_string(self) -> str:
        keys = ", ".join(f"{c} {'ASC' if asc else 'DESC'}"
                         for c, asc in self.keys)
        return f"Sort [{keys}]"


class Limit(LogicalPlan):
    """First ``n`` rows of the child's order."""

    def __init__(self, n: int, child: LogicalPlan) -> None:
        if n < 0:
            raise ValueError(f"Limit must be non-negative, got {n}")
        self.n = int(n)
        self.children = (child,)

    @property
    def child(self) -> LogicalPlan:
        return self.children[0]

    def output_columns(self, schema_of) -> List[str]:
        return self.child.output_columns(schema_of)

    def with_children(self, children) -> "Limit":
        (child,) = children
        return Limit(self.n, child)

    def simple_string(self) -> str:
        return f"Limit {self.n}"


class Aggregate(LogicalPlan):
    """Group-by + aggregations: ``aggs`` is a tuple of (function, input,
    output_name), functions from arrow's hash-aggregate set (sum, min,
    max, mean, count, count_distinct, stddev, variance; count_all counts
    ROWS — its input is ignored).  ``input`` is a column name OR an Expr —
    ``sum(l_extendedprice * (1 - l_discount))`` is an Expr input; the
    executor materializes it before reducing.  Empty ``group_by`` = global
    aggregation.  The rewrite rules never match an Aggregate itself —
    they rewrite the Filter/Scan/Join patterns BELOW it (Catalyst's rules
    behave the same way: the reference's TPC-DS q1 plans keep their
    Aggregates while the scans underneath swap to indexes)."""

    FUNCTIONS = ("sum", "min", "max", "mean", "count", "count_all",
                 "count_distinct", "stddev", "variance")

    def __init__(self, group_by: Sequence[str],
                 aggs: Sequence[Tuple[str, Any, str]],
                 child: LogicalPlan) -> None:
        for func, agg_in, _out in aggs:
            if func not in self.FUNCTIONS:
                raise ValueError(
                    f"Unsupported aggregate function {func!r}; "
                    f"expected one of {self.FUNCTIONS}")
            if not isinstance(agg_in, (str, Expr)):
                raise ValueError(
                    f"Aggregate input must be a column name or expression, "
                    f"got {agg_in!r}")
        self.group_by = tuple(group_by)
        self.aggs = tuple(aggs)
        self.children = (child,)

    def input_columns(self) -> List[str]:
        """Source columns the aggregations read (group keys excluded)."""
        out: set = set()
        for _f, agg_in, _o in self.aggs:
            if isinstance(agg_in, Expr):
                out |= agg_in.referenced_columns()
            elif agg_in:
                out.add(agg_in)
        return sorted(out)

    @property
    def child(self) -> LogicalPlan:
        return self.children[0]

    def output_columns(self, schema_of) -> List[str]:
        return list(self.group_by) + [out for _f, _c, out in self.aggs]

    def with_children(self, children) -> "Aggregate":
        (child,) = children
        return Aggregate(self.group_by, self.aggs, child)

    def simple_string(self) -> str:
        def render(f, agg_in):
            if f == "count_all":
                return "*"
            return repr(agg_in) if isinstance(agg_in, Expr) else str(agg_in)

        aggs = ", ".join(f"{f}({render(f, c)}) AS {out}"
                         for f, c, out in self.aggs)
        return f"Aggregate [{', '.join(self.group_by)}] [{aggs}]"


class BucketUnion(LogicalPlan):
    """Partition-preserving union of identically bucketed children
    (index/plans/logical/BucketUnion.scala:31-68).  In this engine a bucketed
    dataset is per-bucket batches; the physical op concatenates per-bucket
    batches without re-hashing (BucketUnionExec.scala:52-81)."""

    def __init__(self, children: Sequence[LogicalPlan],
                 bucket_spec: Tuple[int, Tuple[str, ...], Tuple[str, ...]]) -> None:
        self.bucket_spec = bucket_spec
        self.children = tuple(children)

    def output_columns(self, schema_of) -> List[str]:
        return self.children[0].output_columns(schema_of)

    def with_children(self, children) -> "BucketUnion":
        return BucketUnion(children, self.bucket_spec)

    def simple_string(self) -> str:
        return f"BucketUnion (buckets={self.bucket_spec[0]})"


class InMemory(LogicalPlan):
    """A materialized table literal — the leaf behind ``Dataset.cache()``
    and two internal uses: the bucket-aligned hybrid join re-injects
    hash-routed appended rows through it (the analog of the reference's
    on-the-fly RepartitionByExpression output, RuleUtils.scala:511-570),
    and the NOT-IN rewrite joins against its materialized subquery.
    Rules treat it as an opaque leaf (it is not a Scan, so no index
    rewrite applies)."""

    def __init__(self, table) -> None:
        self.table = table
        self.children = ()

    def output_columns(self, schema_of) -> List[str]:
        return list(self.table.column_names)

    def with_children(self, children) -> "InMemory":
        # A fresh node object, like Scan's handling in _uniquify: cached
        # datasets reused under several branches (c.join(c, ...)) must
        # not share one node identity, or identity-keyed rewrite state
        # would cross-contaminate branches.  The TABLE is shared — only
        # the plan node is remade.
        assert not children
        return InMemory(self.table)

    def simple_string(self) -> str:
        return f"InMemory [{self.table.num_rows} rows]"


class Union(LogicalPlan):
    """Plain union — the non-bucketed hybrid-scan merge
    (RuleUtils.scala:422-439) and the public ``Dataset.union``.  Schemas
    merge BY NAME with null promotion (the executor's concat does the
    same), so the output is the first child's columns followed by any
    names only later children produce.

    ``strict`` controls type promotion at execution: the public verb
    widens numeric widths like Spark's unionByName; engine-internal
    merges (hybrid scan: index ∪ appended source rows) stay strict so
    index/source schema drift fails loudly instead of silently widening
    (int64 ∪ float64 -> double would corrupt >2^53 keys)."""

    def __init__(self, children: Sequence[LogicalPlan],
                 strict: bool = False) -> None:
        self.children = tuple(children)
        self.strict = bool(strict)

    def output_columns(self, schema_of) -> List[str]:
        out = list(self.children[0].output_columns(schema_of))
        seen = set(out)
        for c in self.children[1:]:
            for name in c.output_columns(schema_of):
                if name not in seen:
                    seen.add(name)
                    out.append(name)
        return out

    def with_children(self, children) -> "Union":
        return Union(children, strict=self.strict)

    def simple_string(self) -> str:
        return "Union"
