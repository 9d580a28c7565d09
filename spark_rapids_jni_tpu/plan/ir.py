"""Logical plan IR: frozen, hashable nodes over ``ColumnBatch`` inputs.

Nodes are LOGICAL — they say what, not how.  Physical choices (which
join/group-by engine, whether an exchange fuses into the downstream
aggregation, broadcast vs shuffled build) belong to the compiler and
the adaptive layer, so the same plan object lowers differently per
platform/knobs while its identity — :meth:`PlanNode.signature` — stays
stable.  The signature is a nested tuple of primitives (node kind +
canonicalized fields, children inline), which makes a plan shape usable
as a dict key for the plan cache without hashing any device data.

Every field that reaches a signature must be hashable; list-ish inputs
are canonicalized to tuples at construction (``__post_init__``), so two
plans built from lists and tuples compare equal.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Tuple

FILTER_OPS = ("<", "<=", ">", ">=", "==", "!=", "like", "not_like")
STRING_FILTER_OPS = ("like", "not_like")
JOIN_STRATEGIES = ("shuffled", "broadcast", "auto")
ARITH_OPS = ("+", "-", "*")


class _Signed:
    """What plan nodes and the expressions and literals inside them
    share: a canonical nested-tuple identity."""

    def signature(self) -> tuple:
        """Canonical nested-tuple identity of this plan shape."""
        out = [type(self).__name__]
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            out.append(v.signature() if isinstance(v, _Signed) else v)
        return tuple(out)


class PlanNode(_Signed):
    """Base for IR nodes; subclasses are frozen dataclasses."""

    def children(self) -> tuple:
        return tuple(getattr(self, f.name) for f in dataclasses.fields(self)
                     if isinstance(getattr(self, f.name), PlanNode))

    def walk(self):
        """Depth-first (children before self) node iterator."""
        for c in self.children():
            yield from c.walk()
        yield self


def _tup(v):
    return tuple(v) if v is not None else None


# ---------------------------------------------------------------------------
# expressions (the values a Project computes, a Filter compares against)
# ---------------------------------------------------------------------------

class Expr(_Signed):
    """Base for expression nodes: frozen, hashable, part of the owning
    node's :meth:`PlanNode.signature`.  An expression says WHAT is
    computed; its Spark result type and the arithmetic that carries it
    are the compiler's (``plan/compile.py:expr_type``)."""

    def __add__(self, other):
        return Arith("+", self, _expr(other))

    def __radd__(self, other):
        return Arith("+", _expr(other), self)

    def __sub__(self, other):
        return Arith("-", self, _expr(other))

    def __rsub__(self, other):
        return Arith("-", _expr(other), self)

    def __mul__(self, other):
        return Arith("*", self, _expr(other))

    def __rmul__(self, other):
        return Arith("*", _expr(other), self)


@dataclass(frozen=True)
class Col(Expr):
    """A column of the child's output."""

    name: str


@dataclass(frozen=True)
class Lit(Expr):
    """An exact decimal literal ``unscaled * 10^-scale``.  Typed as Spark
    types a literal beside a decimal (``DecimalType.fromLiteral``): as
    many digits as it has, so ``Lit(1)`` is ``decimal(1,0)``."""

    unscaled: int
    scale: int = 0


@dataclass(frozen=True)
class Arith(Expr):
    """``left <op> right`` with ``op`` in ``+ - *``."""

    op: str
    left: Expr
    right: Expr

    def __post_init__(self):
        if self.op not in ARITH_OPS:
            raise ValueError(f"unknown arithmetic op {self.op!r}; "
                             f"known: {ARITH_OPS}")


def _expr(v) -> Expr:
    if isinstance(v, Expr):
        return v
    if isinstance(v, str):
        return Col(v)
    if isinstance(v, int) and not isinstance(v, bool):
        return Lit(v)
    raise TypeError(f"not an expression: {v!r}")


@dataclass(frozen=True)
class DateLit(_Signed):
    """A ``DATE`` literal for a :class:`Filter`: an ISO day plus a whole
    number of days (``date '1998-12-01' - interval '90' day`` is
    ``DateLit("1998-12-01", -90)``), compared as int32 days since the
    epoch, which is what a ``DATE`` column holds."""

    iso: str
    plus_days: int = 0

    @property
    def days(self) -> int:
        import datetime

        return (datetime.date.fromisoformat(self.iso)
                - datetime.date(1970, 1, 1)).days + int(self.plus_days)


@dataclass(frozen=True)
class Scan(PlanNode):
    """Read one named input batch (the leaf; bindings come at execute).

    ``snapshot`` (optional, hashable) is the CONTENT snapshot id of the
    bound input — a content hash for in-memory batches, a
    path+mtime+size fingerprint for file readers (see
    :mod:`~spark_rapids_jni_tpu.serve.result_cache`).  It participates
    in :meth:`PlanNode.signature`, so two plans over the same shape but
    different input *contents* have different identities — the exactness
    the fleet-wide result cache keys on.  ``None`` means "contents
    unknown": such a plan still compiles and runs, but result caching
    refuses it (no snapshot id, no caching, never a guess).
    """

    name: str
    snapshot: object = None


@dataclass(frozen=True)
class Filter(PlanNode):
    """Keep rows where ``column <op> value``.

    Lowered as a row mask carried to the next mask consumer (group-by
    ``row_valid`` / join ``left_valid``) — never as a compaction pass.
    Above an :class:`Aggregate` it is SQL's ``HAVING``.
    On a dictionary-encoded column the predicate evaluates over the
    d-entry dictionary once and pushes down onto codes
    (``predicate_mask``).

    ``op`` ``'like'`` / ``'not_like'`` is SQL's ``LIKE`` / ``NOT LIKE`` of
    a string column against a pattern ``value`` (a ``str``) with Spark's
    semantics: ``%`` is any run of characters; ``_`` and escapes are
    refused when the plan is lowered.  A null string gives null, and the
    row goes, under either.
    """

    child: PlanNode
    column: str
    op: str
    # a hashable scalar literal, a DateLit, or a Lit: an exact decimal
    # compared with a decimal column at the column's scale
    value: object

    def __post_init__(self):
        if self.op not in FILTER_OPS:
            raise ValueError(f"unknown filter op {self.op!r}; "
                             f"known: {FILTER_OPS}")
        if (self.op in STRING_FILTER_OPS) != isinstance(self.value, str):
            raise ValueError(f"filter op {self.op!r} against {self.value!r}: "
                             "a pattern is a str, and only LIKE takes one")


@dataclass(frozen=True)
class Project(PlanNode):
    """The output columns, in order: a name keeps that column of the
    child, a ``(name, expression)`` pair computes one.  Where the
    consumer is a domain :class:`Aggregate` the computed columns are
    evaluated inside its row blocks and never exist whole."""

    child: PlanNode
    columns: Tuple[object, ...]  # str | (str, Expr)

    def __post_init__(self):
        object.__setattr__(self, "columns", tuple(
            c if isinstance(c, str) else (str(c[0]), _expr(c[1]))
            for c in self.columns))

    def outputs(self) -> Tuple[Tuple[str, Expr], ...]:
        """Every output as ``(name, expression)``."""
        return tuple((c, Col(c)) if isinstance(c, str) else c
                     for c in self.columns)

    def signature(self) -> tuple:
        return ("Project", self.child.signature(), tuple(
            c if isinstance(c, str) else (c[0], c[1].signature())
            for c in self.columns))


@dataclass(frozen=True)
class Join(PlanNode):
    """Equality join; ``right`` is the BUILD side (usually a dim Scan).

    ``dense_domain`` asserts the build keys are unique ints in
    ``[0, domain)`` so the shuffled lowering may take the rowid-table
    path (``join_dense_or_hash``): an int domain, or the sentinel
    ``"build"`` meaning "the build side's row count" (the TPC-DS dim
    shape, where keys are an arange over the dim's rows — a property of
    the DATA, resolved when the plan meets its inputs).  An int domain
    may be a sparse key range far wider than the build side (TPC-H order
    keys take 8 of every 32 values: 1,500,000 keys below 6,000,001); the
    program checks what it assumes at run time and takes the general
    engine where the data says otherwise.  ``how='semi'`` / ``'anti'``
    (an ``IN`` / ``NOT EXISTS`` subquery as Spark plans one) keep the left
    rows whose key is, or is not, on the build side, and hand on no right
    column; over a dense domain that is one lookup a row.  ``how='right'``
    is the outer join that keeps every build row: SQL's ``customer LEFT
    OUTER JOIN orders`` written from the side whose keys are unique, with
    ORDERS probing CUSTOMER; the output has the build side's columns, its
    key among them, and the probe side's but its key, null where a build
    row matched nothing.  Over a dense domain, and where its consumer
    takes a row mask, it keeps the probe rows where they are and puts
    every build row after them (``join_dense_or_hash``).  ``'left'`` and
    ``'full'`` go to ``hash_join`` alone and compact.  ``strategy``
    picks the physical form: ``'shuffled'`` (the hand-q95 lowering),
    ``'broadcast'`` (spill-registered prebuilt build table +
    ``hash_join(prebuilt=)``), or ``'auto'`` (the adaptive layer
    decides from the observed build row count at plan time).
    """

    child: PlanNode
    right: PlanNode
    left_on: str
    right_on: str
    how: str = "inner"
    dense_domain: object = None  # None | int | "build"
    strategy: str = "shuffled"

    def __post_init__(self):
        if self.strategy not in JOIN_STRATEGIES:
            raise ValueError(f"unknown join strategy {self.strategy!r}; "
                             f"known: {JOIN_STRATEGIES}")


@dataclass(frozen=True)
class Agg(PlanNode):
    """One aggregation: ``op`` in sum/count/min/max/mean, ``column``
    None only for count(*)."""

    op: str
    column: Optional[str]
    out_name: str


@dataclass(frozen=True)
class Aggregate(PlanNode):
    """Group by ``keys`` computing ``aggs``.

    ``domain`` (optional) asserts a single int key lives in
    ``[0, domain)`` so the compiler may pick the adaptive domain engine
    (``group_by_domain_or_sort``); ``onehot=True`` additionally routes
    through the q6 MXU path (``group_by_onehot`` under the
    ``q6_group_path``/``q6_onehot_engine`` knobs).  Both are HINTS: a
    string or encoded key column ignores them and runs the general
    engine-selectable ``group_by``, which is exactly what the
    hand-fused paths do.

    A tuple gives one domain per key (TPC-H Q1: ``(3, 2)``): with
    ``onehot=True`` the keys become one composite bucket of the same
    engine, a null of either key a bucket of its own, and the groups
    come out in key order, nulls first (what a :class:`Sort` on the
    same keys would give, so the compiler elides one).
    """

    child: PlanNode
    keys: Tuple[str, ...]
    aggs: Tuple[Agg, ...]
    domain: object = None  # None | int | one int per key
    onehot: bool = False

    def __post_init__(self):
        object.__setattr__(self, "keys", _tup(self.keys))
        if isinstance(self.domain, (list, tuple)):
            object.__setattr__(self, "domain",
                               tuple(int(d) for d in self.domain))
            if len(self.domain) != len(self.keys):
                raise ValueError(f"{len(self.domain)} domains for "
                                 f"{len(self.keys)} keys")
        aggs = tuple(a if isinstance(a, Agg) else Agg(*a)
                     for a in self.aggs)
        object.__setattr__(self, "aggs", aggs)

    def signature(self) -> tuple:
        return ("Aggregate", self.child.signature(), self.keys,
                tuple(a.signature() for a in self.aggs), self.domain,
                self.onehot)


@dataclass(frozen=True)
class Exchange(PlanNode):
    """Shuffle rows by the Spark-exact hash of ``key`` over
    ``partitions`` slots — on one chip, the LOCAL leg (murmur3 pid +
    stable regroup) every multi-chip stage pays around its all-to-all.
    The compiler fuses an Exchange directly under an Aggregate on the
    same key into the aggregation (secondary sort operands or outright
    elision), mirroring the hand-fused q95 paths.
    """

    child: PlanNode
    key: str
    partitions: int = 8


@dataclass(frozen=True)
class SortOrder(_Signed):
    """One key of an ordering with its direction.  ``nulls_first=None`` is
    Spark's default for the direction: nulls first ascending, last
    descending."""

    name: str
    ascending: bool = True
    nulls_first: Optional[bool] = None

    def resolved_nulls_first(self) -> bool:
        return self.ascending if self.nulls_first is None \
            else bool(self.nulls_first)

    def describe(self) -> str:
        return (f"{self.name} {'asc' if self.ascending else 'desc'} nulls "
                f"{'first' if self.resolved_nulls_first() else 'last'}")


def Desc(name: str, nulls_first: Optional[bool] = None) -> SortOrder:
    """``name`` descending (nulls last unless said otherwise)."""
    return SortOrder(name, False, nulls_first)


def _orders(keys) -> tuple:
    """Keys as given, a bare name left a bare name (so that an ascending
    plan's signature stays what it was)."""
    return tuple(k if isinstance(k, SortOrder) else str(k) for k in keys)


def _order_signature(keys) -> tuple:
    return tuple(k.signature() if isinstance(k, SortOrder) else k
                 for k in keys)


def _as_orders(keys) -> tuple:
    """Every key as a :class:`SortOrder`."""
    return tuple(k if isinstance(k, SortOrder) else SortOrder(k)
                 for k in keys)


@dataclass(frozen=True)
class Sort(PlanNode):
    """Order rows by ``keys``: a name is ascending, nulls first; a
    :class:`SortOrder` (``Desc(name)``) says its own direction and null
    placement."""

    child: PlanNode
    keys: Tuple[object, ...]  # str | SortOrder

    def __post_init__(self):
        object.__setattr__(self, "keys", _orders(self.keys))

    def order(self) -> Tuple[SortOrder, ...]:
        return _as_orders(self.keys)

    def signature(self) -> tuple:
        return ("Sort", self.child.signature(), _order_signature(self.keys))


@dataclass(frozen=True)
class TopK(PlanNode):
    """The first ``n`` rows of ``child`` in the order of ``keys`` (Spark's
    ``TakeOrderedAndProject``: ``ORDER BY ... LIMIT n``).  SQL leaves rows
    equal in every key unordered, so which of them make the cut is the
    engine's choice.  The output has ``n`` row slots, the live rows in
    front."""

    child: PlanNode
    keys: Tuple[object, ...]  # str | SortOrder
    n: int

    def __post_init__(self):
        object.__setattr__(self, "keys", _orders(self.keys))
        object.__setattr__(self, "n", int(self.n))
        if self.n < 1:
            raise ValueError(f"TopK of {self.n} rows")

    def order(self) -> Tuple[SortOrder, ...]:
        return _as_orders(self.keys)

    def signature(self) -> tuple:
        return ("TopK", self.child.signature(),
                _order_signature(self.keys), self.n)


def scan_names(plan: PlanNode) -> tuple:
    """All Scan names in the plan, first-appearance order."""
    seen = []
    for node in plan.walk():
        if isinstance(node, Scan) and node.name not in seen:
            seen.append(node.name)
    return tuple(seen)


def bind_snapshots(plan: PlanNode, snapshots: dict) -> PlanNode:
    """Rebuild ``plan`` with each :class:`Scan` carrying the snapshot id
    from ``snapshots`` (scan name -> snapshot id).

    Nodes are frozen, so the tree is rebuilt bottom-up with
    ``dataclasses.replace``; scans absent from ``snapshots`` keep their
    existing ``snapshot`` (usually ``None``).  The rebound plan's
    :meth:`PlanNode.signature` then pins the exact input contents —
    the form the result cache keys on.
    """
    if isinstance(plan, Scan):
        if plan.name in snapshots:
            return dataclasses.replace(plan, snapshot=snapshots[plan.name])
        return plan
    kwargs = {}
    changed = False
    for f in dataclasses.fields(plan):
        v = getattr(plan, f.name)
        if isinstance(v, PlanNode):
            nv = bind_snapshots(v, snapshots)
            changed = changed or nv is not v
            kwargs[f.name] = nv
    if not changed:
        return plan
    return dataclasses.replace(plan, **kwargs)
