"""Whole-plan compiler: lower a logical plan into ONE jitted program.

The lowering rules are the hand-fused flagship pipelines, factored:

* Filter -> a row mask carried forward (never a compaction pass); on a
  dictionary-encoded column the predicate evaluates over the d-entry
  dictionary once and pushes down onto codes (``predicate_mask``) —
  late materialization preserved, no decode under jit.  Above an
  Aggregate (``HAVING``) the group count in front becomes ``arange <
  count`` and, with the predicate, a scattered mask; a decimal column
  compares with an exact literal (``ir.Lit``) at the column's scale.  A
  ``like`` / ``not_like`` on a string column is ``ops.strings.like``
  over its padded bytes (``decisions``: ``filter<i>:<column>`` with the
  op, the pattern and the route), under ``strings.like``.
* Exchange -> the local shuffle leg (Spark-exact murmur3 pid + stable
  ``regroup_order``), dead rows routed to the trailing
  pseudo-partition so live prefixes survive the permutation.  Above an
  Aggregate (a group-by over a group-by) it, and an Aggregate straight
  above one, take the group count in front as ``arange < count``.
* Exchange directly under an Aggregate on the same key FUSES, exactly
  the way ``_q95_prefix`` does: under the pinned sort group-by engine
  the group key's radix words ride the regroup sort as SECONDARY
  operands and ``group_by(assume_grouped=True)`` skips its own sort
  (one row-sized sort where the naive plan pays two); under the
  scatter/auto engines — and on encoded keys — the single-chip
  exchange is a no-op before a complete local aggregation, so it is
  ELIDED outright.
* Join -> ``join_dense_or_hash`` on plain inputs with a dense-domain
  hint, the general engine-selectable ``hash_join`` otherwise (the
  encoded lowering — the rowid fast path keys on raw ``.data``, which
  an encoded column deliberately does not expose).  A broadcast join
  (adaptive decision) probes a spill-registered prebuilt
  :class:`~spark_rapids_jni_tpu.relational.join.SpillableBuildTable`,
  pinned to the engine the plan decided so eviction-driven rebuilds
  cannot disagree with the compiled program's traced shapes.  An inner,
  semi or anti dense-domain join whose consumer takes a scattered row
  mask (an Exchange, an Aggregate, a Filter, a Join on either side; a
  Project passes the question up) leaves the left rows where they are
  and hands on ``match`` as the mask (``decisions``: ``"output":
  "mask"``, and ``"how"`` where it is not inner); at the root or under a
  Sort it compacts.  The build side may be an Aggregate's output, whose
  group count becomes its ``right_valid``.  A ``right`` (outer) join over a
  dense domain takes the mask form too: the probe rows, then every build
  row, live where no probe row matched it.
* Aggregate -> ``group_by_onehot`` / ``group_by_domain_or_sort`` /
  general ``group_by`` by exactly the hand paths' dispatch (domain
  hints apply only to plain int keys; string/encoded keys run the
  general engine).
* Project -> its expressions typed by Spark's decimal rules
  (:func:`expr_type`) and lowered to the narrowest exact arithmetic the
  type allows (int64 up to 18 digits, typed-width limbs past them, the
  256-bit rounding multiply only where a product loses scale).  Under
  an Aggregate that takes the one-hot engine the computed columns are
  handed over unevaluated and computed inside its row slices.
* Sort on exactly the keys of a composite-domain Aggregate below it is
  elided: that engine emits key order, nulls first.  Any other Sort is
  one sort over the keys' radix words packed end to end (a descending
  key's complemented, a dead row's flag first, the row id last) and a
  gather of every column.
* TopK -> ``k`` rounds of selection over the order's radix words among
  the live rows (``relational.sort.top_k_rows``) and a gather of ``k``
  rows of every column: no row slot is sorted or moved, whatever the
  input's size.

One ``jax.jit`` wraps the whole lowered pipeline, so XLA sees every
stage together.  Programs are cached in :mod:`cache` keyed on
(canonical IR signature, input schema fingerprint, config fingerprint,
adaptive decisions); a cache hit reuses the already-traced program —
:func:`trace_count` observes that ZERO retraces happen on repeats.
"""

from __future__ import annotations

import operator
from typing import Optional

import jax
import jax.numpy as jnp

from .. import config, profiler
from ..columnar import types as T
from ..columnar.column import Column, ColumnBatch, Decimal128Column, \
    StringColumn
from ..columnar.encoded import PACKED_COLUMNS, is_encoded, \
    packed_filter_mask, predicate_mask
from . import adaptive, ir
from .cache import get_plan_cache

# incremented INSIDE the traced program body — a trace-time side effect,
# so it counts (re)traces, not executions.  The plan-cache acceptance
# bar ("repeated shape -> zero retraces") is asserted against this.
_TRACE_COUNT = [0]


def trace_count() -> int:
    return _TRACE_COUNT[0]


# ---------------------------------------------------------------------------
# fingerprints
# ---------------------------------------------------------------------------

def _schema_fingerprint(inputs: dict) -> tuple:
    """Hashable identity of the input schemas: pytree structure (which
    carries column names, dtypes and dictionary tokens as static aux)
    plus every leaf's shape/dtype — any row-count, dtype, column-set or
    dictionary change misses the cache by construction."""
    out = []
    for name in sorted(inputs):
        batch = inputs[name]
        leaves, treedef = jax.tree_util.tree_flatten(batch)
        out.append((name, treedef,
                    tuple((tuple(l.shape), str(l.dtype)) for l in leaves)))
    return tuple(out)


def _config_fingerprint() -> tuple:
    """Every registered knob's resolved value — a flip of ANY knob is a
    plan-cache miss (knobs select engines and fusion shapes, so a stale
    hit could replay the wrong physical plan).  Delegates to the result
    cache's :func:`~spark_rapids_jni_tpu.serve.result_cache.
    knob_fingerprint` so the plan cache and the fleet-wide result cache
    agree on one fingerprint discipline."""
    from ..serve.result_cache import knob_fingerprint

    return knob_fingerprint()


def _freeze(obj):
    if isinstance(obj, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in obj.items()))
    if isinstance(obj, (list, tuple)):
        return tuple(_freeze(v) for v in obj)
    return obj


def plan_cache_key(plan: ir.PlanNode, inputs: dict,
                   decisions: Optional[dict] = None) -> tuple:
    return (plan.signature(), _schema_fingerprint(inputs),
            _config_fingerprint(), _freeze(decisions or {}))


def result_key(plan: ir.PlanNode, inputs: dict) -> Optional[tuple]:
    """The fleet result cache's three-component key for ``plan`` over
    ``inputs`` — ``(bound plan signature, snapshot ids, knob
    fingerprint)`` — or ``None`` when ANY scan's input contents are
    unproven.

    Snapshot ids come from the bound source (``MorselSource.
    snapshot_id``) or from a snapshot already carried by the Scan node
    itself (:func:`~spark_rapids_jni_tpu.plan.ir.bind_snapshots`);
    nothing is ever hashed implicitly here.  Unlike
    :func:`plan_cache_key` this key pins input CONTENTS, not input
    schemas: the plan cache reuses a compiled program across data, the
    result cache may only reuse the finished bytes of the exact data.
    """
    snaps = {}
    for name in ir.scan_names(plan):
        src = inputs.get(name)
        sid = getattr(src, "snapshot_id", None)
        if sid is not None:
            snaps[name] = sid
    bound = ir.bind_snapshots(plan, snaps)
    ids = []
    for node in bound.walk():
        if isinstance(node, ir.Scan):
            if node.snapshot is None:
                return None  # no snapshot id, no caching, never a guess
            ids.append((node.name, node.snapshot))
    return (bound.signature(), tuple(sorted(set(ids))),
            _config_fingerprint())


# ---------------------------------------------------------------------------
# lowering
# ---------------------------------------------------------------------------

_FILTER_OPS = {
    "<": operator.lt, "<=": operator.le, ">": operator.gt,
    ">=": operator.ge, "==": operator.eq, "!=": operator.ne,
}


def _filter_mask(col, op: str, value):
    """Row mask for ``col <op> value`` — pushed onto dictionary codes
    for encoded columns (one d-entry predicate + one gather), and onto
    u32 residual lanes for packed columns (``packed_filter_mask``:
    literal transformed once per frame, bit-identical to
    decode-then-compare, zero decodes on the fast path)."""
    if op in ir.STRING_FILTER_OPS:
        return _like_mask(col, op, value)
    fn = _FILTER_OPS[op]
    if isinstance(value, ir.Lit):
        return _decimal_filter_mask(col, op, value)
    if isinstance(value, ir.DateLit):
        value = jnp.int32(value.days)   # what a DATE column holds
    if isinstance(col, PACKED_COLUMNS):
        return packed_filter_mask(col, op, value)
    if is_encoded(col) and hasattr(col, "codes"):
        return predicate_mask(col, lambda d: fn(d.data, value))
    # a comparison with a null is null, and the row goes (as on codes)
    return fn(col.data, value) & col.validity


def _like_mask(col, op: str, pattern: str):
    """``col LIKE pattern`` (or ``NOT LIKE``) over a padded string column:
    a null string gives null, and the row goes under either."""
    from ..ops.strings import like

    if not isinstance(col, StringColumn):
        raise NotImplementedError(f"LIKE over {type(col).__name__}: only a "
                                  "padded StringColumn")
    with profiler.scope("strings.like"):
        hit = like(col, pattern)
        return (hit if op == "like" else ~hit) & col.validity


def _decimal_filter_mask(col, op: str, lit: ir.Lit):
    """``col <op> lit`` for a decimal column in 64- or 128-bit storage:
    Spark casts both sides to one decimal type, so the literal is brought
    to the column's scale (an exact multiple of a power of ten) and the
    unscaled integers are compared, signed."""
    if col.dtype.kind is not T.Kind.DECIMAL or lit.scale > col.dtype.scale:
        raise NotImplementedError(
            f"a filter of {col.dtype!r} against a decimal literal of "
            f"scale {lit.scale}")
    unscaled = int(lit.unscaled) * 10 ** (col.dtype.scale - lit.scale)
    if not isinstance(col, Decimal128Column):
        if abs(unscaled) >= 1 << 63:
            raise NotImplementedError("a literal past 64 bits")
        return _FILTER_OPS[op](col.data.astype(jnp.int64),
                               jnp.int64(unscaled)) & col.validity
    from ..relational.aggregate import _dec128_lt

    two = unscaled % (1 << 128)   # two's complement, as the limbs are
    clo, chi = jnp.uint64(two & (2**64 - 1)), jnp.uint64(two >> 64)
    lo, hi = col.limbs[:, 0], col.limbs[:, 1]
    less = _dec128_lt(lo, hi, clo, chi)
    equal = (lo == clo) & (hi == chi)
    mask = {"<": less, "<=": less | equal, ">": ~(less | equal),
            ">=": ~less, "==": equal, "!=": ~equal}[op]
    return mask & col.validity


# ---------------------------------------------------------------------------
# expressions: Spark's decimal typing and the arithmetic that carries it
# ---------------------------------------------------------------------------

_MAX_PRECISION = 38
_MINIMUM_ADJUSTED_SCALE = 6
# DecimalType.forType: what an integer column is beside a decimal
_INT_AS_DECIMAL = {T.Kind.INT8: (3, 0), T.Kind.INT16: (5, 0),
                   T.Kind.INT32: (10, 0), T.Kind.INT64: (20, 0)}
# DecimalPrecision: (p1, s1, p2, s2) -> the result's (precision, scale)
# before adjustPrecisionScale
_RAW_RESULT = {
    "+": lambda p1, s1, p2, s2: (max(p1 - s1, p2 - s2) + max(s1, s2) + 1,
                                 max(s1, s2)),
    "-": lambda p1, s1, p2, s2: (max(p1 - s1, p2 - s2) + max(s1, s2) + 1,
                                 max(s1, s2)),
    "*": lambda p1, s1, p2, s2: (p1 + p2 + 1, s1 + s2),
}


def adjust_precision_scale(precision: int, scale: int) -> tuple:
    """``DecimalType.adjustPrecisionScale`` (``allowPrecisionLoss``, the
    default): past 38 digits the integral digits are kept and the scale
    gives way, down to ``min(scale, 6)``."""
    if precision <= _MAX_PRECISION:
        return precision, scale
    int_digits = precision - scale
    return _MAX_PRECISION, max(_MAX_PRECISION - int_digits,
                               min(scale, _MINIMUM_ADJUSTED_SCALE))


def _as_decimal(t: T.SparkType) -> tuple:
    if t.kind is T.Kind.DECIMAL:
        return t.precision, t.scale
    if t.kind in _INT_AS_DECIMAL:
        return _INT_AS_DECIMAL[t.kind]
    raise NotImplementedError(f"arithmetic over {t!r}")


def expr_type(expr: ir.Expr, schema: dict) -> T.SparkType:
    """Spark's result type of ``expr`` over columns typed by ``schema``
    (name -> SparkType): the one typing pass, read by the lowering, by
    the plan's decisions and by the tests."""
    if isinstance(expr, ir.Col):
        return schema[expr.name]
    if isinstance(expr, ir.Lit):
        digits = len(str(abs(int(expr.unscaled))))
        return T.SparkType.decimal(max(digits, expr.scale), expr.scale)
    lt, rt = expr_type(expr.left, schema), expr_type(expr.right, schema)
    if T.Kind.DECIMAL not in (lt.kind, rt.kind):
        raise NotImplementedError(
            f"{lt!r} {expr.op} {rt!r}: only decimal arithmetic is typed")
    return T.SparkType.decimal(*adjust_precision_scale(
        *_RAW_RESULT[expr.op](*_as_decimal(lt), *_as_decimal(rt))))


def _route(expr: ir.Arith, lt, rt, out) -> str:
    """The arithmetic that carries ``expr`` exactly: chosen from the types."""
    _p, raw_scale = _RAW_RESULT[expr.op](*_as_decimal(lt), *_as_decimal(rt))
    if expr.op == "*":
        if out.scale < raw_scale:
            return "mul_rounded:dec256"
        return "mul_exact:int64" if out.precision <= 18 \
            else "mul_exact:limbs"
    return "add:int64" if out.precision <= 18 and out.scale == raw_scale \
        else "add:dec256"


def expr_routes(expr: ir.Expr, schema: dict, seen=None) -> list:
    """``route:arithmetic:type`` of every operation under ``expr``, operands
    first; ``seen`` (a set) leaves out what an earlier output of the same
    Project already computes."""
    seen = set() if seen is None else seen
    if not isinstance(expr, ir.Arith) or expr in seen:
        return []
    seen.add(expr)
    lt, rt = expr_type(expr.left, schema), expr_type(expr.right, schema)
    t = expr_type(expr, schema)
    return (expr_routes(expr.left, schema, seen)
            + expr_routes(expr.right, schema, seen)
            + [f"{_route(expr, lt, rt, t)}:{t!r}"])


def _batch_schema(b: ColumnBatch) -> dict:
    """name -> SparkType of the columns an expression can read: plain
    fixed-width and decimal128 columns."""
    return {n: c.dtype for n, c in zip(b.names, b.columns)
            if isinstance(c, (Column, Decimal128Column))}


def _as_dec128(col, dt: T.SparkType) -> Decimal128Column:
    from ..relational.aggregate import _widen_decimal

    wide = _widen_decimal(col)
    return Decimal128Column(wide.limbs, wide.validity,
                            T.SparkType.decimal(*_as_decimal(dt)))


class _ExprEval:
    """Evaluates expressions over the rows of one batch (a whole table, or
    one slice inside the aggregate's loop); an expression met twice (Q1's
    ``l_extendedprice * (1 - l_discount)`` under two outputs) is computed
    once, under the scope of the output that met it first."""

    def __init__(self, batch: ColumnBatch):
        self.batch = batch
        self.schema = _batch_schema(batch)
        self.memo = {}

    def __call__(self, expr: ir.Expr):
        if expr not in self.memo:
            self.memo[expr] = self._eval(expr)
        return self.memo[expr]

    def _eval(self, expr):
        from ..ops import decimal as D

        if isinstance(expr, ir.Col):
            if expr.name not in self.schema:
                raise NotImplementedError(
                    f"expression over column {expr.name!r}: "
                    f"{type(self.batch[expr.name]).__name__}")
            return self.batch[expr.name]
        n = self.batch.num_rows
        t = expr_type(expr, self.schema)
        if isinstance(expr, ir.Lit):
            if t.decimal_storage_bits == 128:
                raise NotImplementedError("a literal past 18 digits")
            return Column(jnp.full((n,), expr.unscaled, jnp.int64),
                          jnp.ones((n,), jnp.bool_), t)
        a, b = self(expr.left), self(expr.right)
        lt = expr_type(expr.left, self.schema)
        rt = expr_type(expr.right, self.schema)
        route = _route(expr, lt, rt, t)
        valid = a.validity & b.validity
        with profiler.scope("expr." + route.split(":")[0]):
            if route == "add:int64":
                x, y = (c.data.astype(jnp.int64)
                        * 10 ** (t.scale - _as_decimal(ct)[1])
                        for c, ct in ((a, lt), (b, rt)))
                return Column(x + y if expr.op == "+" else x - y, valid, t)
            if route == "mul_exact:int64":
                return Column(a.data.astype(jnp.int64)
                              * b.data.astype(jnp.int64), valid, t)
            if route == "mul_exact:limbs":
                over, res = D.multiply_exact(a, b, _as_decimal(lt)[0],
                                             _as_decimal(rt)[0], t)
            elif route == "mul_rounded:dec256":
                over, res = D.multiply_decimal128(
                    _as_dec128(a, lt), _as_dec128(b, rt), t.scale,
                    cast_interim_result=False)
            else:
                op = D.add_decimal128 if expr.op == "+" else D.sub_decimal128
                over, res = op(_as_dec128(a, lt), _as_dec128(b, rt), t.scale)
            # non-ANSI: a result past its type's precision is null
            return Decimal128Column(res.limbs, valid & ~over.data, t)


def _project_columns(node: ir.Project, b: ColumnBatch) -> dict:
    """Every output of ``node`` over the rows of ``b``; a computed one
    under ``plan.project.<output>``."""
    ev = _ExprEval(b)
    out = {}
    for name, expr in node.outputs():
        if isinstance(expr, ir.Col):
            out[name] = b[expr.name]
        else:
            with profiler.scope("plan.project." + profiler.scope_name(name)):
                out[name] = ev(expr)
    return out


def _derived(node: ir.Project, b: ColumnBatch):
    """``b`` with ``node``'s kept columns under their output names, and its
    computed columns as a :class:`Derived` for a consumer that evaluates
    them itself, over its own row slices (``fn`` enters no scope of the
    consumer's: it is called under an empty path)."""
    from ..relational.aggregate import Derived

    schema = _batch_schema(b)
    cols = dict(zip(b.names, b.columns))
    computed = []
    for name, expr in node.outputs():
        if not isinstance(expr, ir.Col):
            computed.append(name)
        elif name != expr.name and name in cols:
            return b, None   # a kept column renamed over one still read
        else:
            cols[name] = b[expr.name]
    dtypes = {name: expr_type(expr, schema)
              for name, expr in node.outputs() if name in computed}
    only = ir.Project(node.child, [c for c in node.columns
                                   if not isinstance(c, str)])

    def fn(blk):
        return _project_columns(only, blk)

    return ColumnBatch(cols), Derived(dtypes, fn)


def _exchange_local(b: ColumnBatch, key: str, live, partitions: int,
                    secondary=None, lead_bits=None) -> ColumnBatch:
    """The hand paths' ``exchange_local``: dead rows get pseudo-partition
    P (``spark_partition_id``) and the stable regroup sends them LAST,
    so live rows stay compacted in front and an arange<count mask
    remains valid after the regroup."""
    from ..parallel.partition import regroup_order, spark_partition_id
    from ..relational.gather import gather_batch

    with profiler.scope("exchange.partition_id"):
        pid = spark_partition_id([b[key]], partitions, live)
    with profiler.scope("exchange.regroup"):
        order = regroup_order(pid, partitions + 1, secondary=secondary,
                              lead_bits=lead_bits)
    with profiler.scope("exchange.scatter"):
        return gather_batch(b, order)


def _plain_int_key(col) -> bool:
    return (isinstance(col, Column)
            and jnp.issubdtype(col.data.dtype, jnp.integer))


class _State:
    """Per-trace lowering cursor: ordinals into the compile-time join
    plans / aggregate hints, consumed in walk order (lowering recursion
    visits nodes in the same children-first order as ``PlanNode.walk``).
    """

    def __init__(self, join_plans, agg_hints):
        self.join_plans = join_plans
        self.agg_hints = agg_hints
        self.join_i = 0
        self.agg_i = 0
        # Aggregate nodes (by id) lowered onto an engine that emits its
        # groups in key order, nulls first: a Sort on the same keys
        # above one has nothing left to do
        self.key_ordered = set()
        # joins lowered by output form: a row mask handed on, or the
        # matches compacted in front
        self.joins_masked = 0
        self.joins_compacted = 0
        # row slots the plan's ordered limits put through their selection
        self.topk_sorted_rows = 0
        # row slots the plan's aggregates take in, summed
        self.agg_input_slots = 0
        # char slots (rows x stored width) the plan's string predicates scan
        self.like_char_slots = 0


def node_scope(node: ir.PlanNode) -> str:
    """The named scope a node's own operations are lowered under:
    ``plan.filter.<column>``, ``plan.exchange.<key>``, ``plan.join.<right
    scan name>``, ``plan.aggregate.<first key>``, ``plan.sort``, ``plan.topk``; a
    Project's computed outputs each have ``plan.project.<output>``.  A
    child is lowered before and outside its parent's scope, so a device
    operation's path starts at the one node it belongs to."""
    if isinstance(node, ir.Filter):
        return "plan.filter." + profiler.scope_name(node.column)
    if isinstance(node, ir.Exchange):
        return "plan.exchange." + profiler.scope_name(node.key)
    if isinstance(node, ir.Join):
        right = node.right.name if isinstance(node.right, ir.Scan) \
            else node.right_on
        return "plan.join." + profiler.scope_name(right)
    if isinstance(node, ir.Aggregate):
        return "plan.aggregate." + profiler.scope_name(node.keys[0])
    return "plan." + type(node).__name__.lower()


def _lower(node: ir.PlanNode, env: dict, prebuilts: tuple, st: _State):
    """Returns ``(batch, live, prefix)``: ``live`` is a bool row mask,
    None (statically all-live) or, from an Aggregate up, the scalar count
    of live rows in front; ``prefix`` records that the mask is of
    arange<count form (live rows compacted in front), which is what
    lets it pass through an exchange untouched — a scattered filter
    mask instead becomes ``arange < sum(live)`` on the far side."""
    if isinstance(node, ir.Scan):
        return env[node.name], None, True

    if isinstance(node, ir.Filter):
        b, live, _pfx = _lower(node.child, env, prebuilts, st)
        with profiler.scope(node_scope(node)):
            mask = _filter_mask(b[node.column], node.op, node.value)
            if node.op in ir.STRING_FILTER_OPS:
                st.like_char_slots += b[node.column].chars.size
            # above an Aggregate (HAVING): its groups are in front
            live = _counted_rows(b, live)
            live = mask if live is None else live & mask
        return b, live, False

    if isinstance(node, ir.Project):
        b, live, pfx = _lower(node.child, env, prebuilts, st)
        return ColumnBatch(_project_columns(node, b)), live, pfx

    if isinstance(node, ir.Exchange):
        b, live, pfx = _lower(node.child, env, prebuilts, st)
        with profiler.scope(node_scope(node)):
            if _is_count(live):   # above an Aggregate: its groups in front
                live, pfx = _counted_rows(b, live), True
            live_arr = (jnp.ones((b.num_rows,), jnp.bool_) if live is None
                        else live)
            staged = _exchange_local(b, node.key, live_arr,
                                     node.partitions)
            if live is None or pfx:
                return staged, live, pfx
            n = staged.num_rows
            new_live = jnp.arange(n, dtype=jnp.int32) < jnp.sum(
                live.astype(jnp.int32))
            return staged, new_live, True

    if isinstance(node, ir.Sort):
        return _lower_sort(node, env, prebuilts, st)

    if isinstance(node, ir.TopK):
        return _lower_topk(node, env, prebuilts, st)

    if isinstance(node, ir.Join):
        return _lower_join(node, env, prebuilts, st)

    if isinstance(node, ir.Aggregate):
        return _lower_aggregate(node, env, prebuilts, st)

    raise TypeError(f"cannot lower {type(node).__name__}")


def _is_count(live) -> bool:
    """From an Aggregate up ``live`` is the scalar count of rows in front."""
    return live is not None and live.ndim == 0


def _counted_rows(b: ColumnBatch, live):
    """``live`` as a row mask where it is an Aggregate's group count."""
    if _is_count(live):
        return jnp.arange(b.num_rows, dtype=jnp.int32) < live
    return live


def _no_count(node: ir.PlanNode, live, side: str = "") -> None:
    """A node that takes row masks only (a Join's probe side) met an
    Aggregate's group count."""
    if _is_count(live):
        raise TypeError(
            f"{type(node).__name__} ({node_scope(node)}) cannot take the "
            f"output of an Aggregate{side}: it hands on a group count, not "
            "a row mask (a Filter above the Aggregate makes one)")


def _sort_keys(node) -> list:
    from ..relational.sort import SortKey

    return [SortKey(o.name, o.ascending, o.resolved_nulls_first())
            for o in node.order()]


def _lower_sort(node: ir.Sort, env, prebuilts, st):
    from ..relational.sort import sort_by

    b, live, _pfx = _lower(node.child, env, prebuilts, st)
    # from an Aggregate: the count of live rows, which are in front
    count = live if _is_count(live) else None
    if count is not None and id(node.child) in st.key_ordered \
            and node.keys == node.child.keys:
        return b, count, True   # already in this order: elided
    keys = _sort_keys(node)
    with profiler.scope(node_scope(node)):
        if count is not None:
            live = jnp.arange(b.num_rows, dtype=jnp.int32) < count
        out = sort_by(b, keys, live)   # dead rows last
        if live is None:
            return out, None, True
        new_live = jnp.arange(out.num_rows, dtype=jnp.int32) < jnp.sum(
            live.astype(jnp.int32))
        return out, new_live if count is None else count, True


def _lower_topk(node: ir.TopK, env, prebuilts, st):
    """The ordered limit: selection among the live rows (from an Aggregate
    the ``count`` in front), then ``n`` rows of every column."""
    from ..relational.gather import gather_batch
    from ..relational.sort import top_k_rows

    b, live, _pfx = _lower(node.child, env, prebuilts, st)
    counted = _is_count(live)
    rows = b.num_rows
    st.topk_sorted_rows += rows
    with profiler.scope(node_scope(node)):
        with profiler.scope("topk.select"):
            if counted:
                live = jnp.arange(rows, dtype=jnp.int32) < live
            idx, cnt = top_k_rows(b, _sort_keys(node), node.n, live)
        with profiler.scope("topk.gather"):
            front = jnp.arange(node.n, dtype=jnp.int32) < cnt
            out = gather_batch(b, idx, front)
    return out, (cnt if counted else front), True


def _lower_join(node: ir.Join, env, prebuilts, st):
    from ..relational.join import hash_join, join_dense_or_hash

    b, live, _pfx = _lower(node.child, env, prebuilts, st)
    _no_count(node, live, " as its probe side")
    rb, rlive, _rpfx = _lower(node.right, env, prebuilts, st)
    # an Aggregate's output as the build side: its groups are in front
    rlive = _counted_rows(rb, rlive)
    info = st.join_plans[st.join_i]
    st.join_i += 1

    with profiler.scope(node_scope(node)):
        if info["strategy"] == "broadcast":
            out, cnt = hash_join(
                b, rb, [node.left_on], [node.right_on], node.how,
                left_valid=live, right_valid=rlive,
                prebuilt=prebuilts[info["prebuilt"]],
                engine=info["engine"])
        elif info["output"] == "mask":
            # the consumer takes a scattered mask: the matches stay where
            # the left rows are
            st.joins_masked += 1
            out, new_live = join_dense_or_hash(
                b, rb, node.left_on, node.right_on, info["dense_domain"],
                node.how, left_valid=live, right_valid=rlive, compact=False)
            return out, new_live, False
        elif info["dense_domain"] is not None:
            out, cnt = join_dense_or_hash(
                b, rb, node.left_on, node.right_on, info["dense_domain"],
                node.how, left_valid=live, right_valid=rlive)
        else:
            out, cnt = hash_join(b, rb, [node.left_on], [node.right_on],
                                 node.how, left_valid=live,
                                 right_valid=rlive)
        st.joins_compacted += 1
        new_live = jnp.arange(out.num_rows, dtype=jnp.int32) < cnt
    return out, new_live, True


def _lower_aggregate(node: ir.Aggregate, env, prebuilts, st):
    from ..relational.aggregate import AggSpec

    aggs = [AggSpec(a.op, a.column, a.out_name) for a in node.aggs]
    hint = st.agg_hints[st.agg_i]
    st.agg_i += 1

    child = node.child
    fuse = (isinstance(child, ir.Exchange) and len(node.keys) == 1
            and child.key == node.keys[0])
    derive = None
    if isinstance(child, ir.Project) and any(
            not isinstance(c, str) for c in child.columns):
        # a computed column is evaluated where its consumer reads it: by
        # the one-hot engine inside its row slices; any other engine
        # reads whole columns, so there they are made whole
        b, live, pfx = _lower(child.child, env, prebuilts, st)
        pb, derive = _derived(child, b)
        if derive is None or not _takes_onehot(node, _batch_schema(pb)):
            b, derive = ColumnBatch(_project_columns(child, b)), None
        else:
            b = pb
    else:
        # a fused Exchange is lowered here, under the aggregate's scope
        # (as the regroup that orders its rows, or not at all)
        b, live, pfx = _lower(child.child if fuse else child, env,
                              prebuilts, st)
    st.agg_input_slots += b.num_rows
    with profiler.scope(node_scope(node)):
        if _is_count(live):   # above an Aggregate: its groups are in front
            live, pfx = _counted_rows(b, live), True
        return _aggregate(node, aggs, hint, child if fuse else None,
                          b, live, pfx, derive, st)


def _takes_onehot(node: ir.Aggregate, schema: dict) -> bool:
    """Whether ``node`` over columns typed by ``schema`` (plain columns
    only) is lowered onto ``group_by_onehot``: every key a plain integer
    column with a domain, and the knob says so."""
    ints = (T.Kind.INT8, T.Kind.INT16, T.Kind.INT32, T.Kind.INT64)
    return (node.onehot and node.domain is not None
            and (isinstance(node.domain, tuple) or len(node.keys) == 1)
            and all(k in schema and schema[k].kind in ints
                    for k in node.keys)
            and config.get("q6_group_path") == "onehot")


def _aggregate(node: ir.Aggregate, aggs, hint, fused, b, live, pfx,
               derive=None, st=None):
    from ..relational import keys as _rk
    from ..relational.aggregate import (_resolve_groupby_engine, group_by,
                                        group_by_domain_or_sort,
                                        group_by_onehot)

    if fused is not None:
        key_col = b[node.keys[0]]
        if (_plain_int_key(key_col)
                and _resolve_groupby_engine(None) == "sort"):
            # sort-order reuse (pinned "sort", or what "auto" resolves
            # to off the CPU): the seg radix words ride the regroup
            # sort as secondary operands, so the group-by receives an
            # already-grouped input and skips its own sort
            segkeys = _rk.batch_radix_keys([key_col], equality=True,
                                           nulls_first=True)
            live_arr = (jnp.ones((b.num_rows,), jnp.bool_) if live is None
                        else live)
            # (the first of the key's words is its null flag: one bit)
            staged = _exchange_local(b, fused.key, live_arr,
                                     fused.partitions, secondary=segkeys,
                                     lead_bits=1)
            if live is not None and not pfx:
                live = jnp.arange(staged.num_rows, dtype=jnp.int32) < \
                    jnp.sum(live.astype(jnp.int32))
            res, ng = group_by(staged, [node.keys[0]], aggs,
                               row_valid=live, assume_grouped=True)
            return res, ng, True
        # scatter/auto engines and encoded keys: the single-chip
        # exchange feeds a complete local aggregation — elide it

    composite = isinstance(node.domain, tuple)
    domain_ok = (node.domain is not None
                 and (composite or len(node.keys) == 1)
                 and all(_plain_int_key(b[k]) for k in node.keys))
    if node.onehot and domain_ok:
        if config.get("q6_group_path") == "onehot":
            res, ng, _overflow = group_by_onehot(
                b, node.keys if composite else node.keys[0], aggs,
                domain=node.domain if composite else int(node.domain),
                row_valid=live, float_mode=config.get("q6_float_mode"),
                engine=config.get("q6_onehot_engine"), derive=derive)
            if composite and st is not None:
                st.key_ordered.add(id(node))
            return res, ng, True
        res, ng = group_by(b, list(node.keys), aggs, row_valid=live)
        return res, ng, True
    assert derive is None   # _takes_onehot said so, or it was made whole
    if domain_ok and not node.onehot and not composite:
        res, ng = group_by_domain_or_sort(b, node.keys[0], aggs,
                                          int(node.domain), row_valid=live)
        return res, ng, True
    kwargs = {"engine": hint} if hint else {}
    res, ng = group_by(b, list(node.keys), aggs, row_valid=live, **kwargs)
    return res, ng, True


# ---------------------------------------------------------------------------
# compiled plans
# ---------------------------------------------------------------------------

class CompiledPlan:
    """One whole-plan jitted program plus its execute-time adjuncts:
    the spill-registered broadcast build handles (fetched per run
    through the retry ladder, OUTSIDE the jitted region) and the
    recorded adaptive decisions.  ``last_lookup`` says whether the most
    recent :func:`compile_plan` returning this object was a cache hit.
    """

    def __init__(self, plan, key, fn, input_names, build_handles,
                 decisions):
        self.plan = plan
        self.key = key
        self.fn = fn
        self.input_names = input_names
        self.build_handles = build_handles
        self.decisions = decisions
        self.last_lookup = "miss"

    def __call__(self, inputs: dict):
        from ..mem.executor import run_with_retry

        # one span per launch of the compiled program: its count in
        # profiler.stage_totals() is the "plan.dispatches" counter
        with profiler.span("plan.dispatch"):
            missing = [n for n in self.input_names if n not in inputs]
            if missing:
                raise KeyError(f"plan inputs missing: {missing}")
            env = {n: inputs[n] for n in self.input_names}
            prebuilts = []
            for h in self.build_handles:
                # pin across get(): an evictor may not drop the table
                # while the fetch is in flight; the returned arrays keep
                # their buffers alive on their own afterwards
                with profiler.span("plan.prebuilt_fetch"), h.pinned():
                    prebuilts.append(tuple(run_with_retry(h.get)))
            return self.fn(env, tuple(prebuilts))

    def close(self):
        for h in self.build_handles:
            h.close()


def _resolve_join_plans(plan, inputs, decisions, ctx):
    """Walk-order physical join plans + broadcast build handles.

    Broadcast builds are registered as spillable tables under the
    owning query's ``ctx`` (TaskContext) with the decided engine PINNED
    — a parked tenant's broadcast can be evicted, and its rebuild comes
    back in the shape the compiled program was traced against."""
    from ..relational.join import spillable_build_table

    join_plans = []
    agg_hints = []
    handles = []
    ji = ai = 0
    for node in plan.walk():
        if isinstance(node, ir.Join):
            d = decisions.get(f"join{ji}:{node.left_on}", {})
            strategy = d.get("strategy", node.strategy)
            if strategy == "auto":
                strategy = "shuffled"
            rb = inputs.get(node.right.name) \
                if isinstance(node.right, ir.Scan) else None
            info = {"strategy": strategy,
                    "dense_domain": _dense_domain(node, inputs),
                    "output": d.get("output", "compact"),
                    "prebuilt": None, "engine": None}
            if strategy == "broadcast":
                if rb is None:
                    raise ValueError(
                        "broadcast join needs a Scan build side bound "
                        "to an input batch")
                engine = d.get("engine") or adaptive.choose_join_engine()
                h = spillable_build_table(
                    rb, [node.right_on], ctx=ctx,
                    name=f"plan-bcast-{ji}-{node.left_on}", engine=engine)
                info["prebuilt"] = len(handles)
                info["engine"] = engine
                handles.append(h)
            join_plans.append(info)
            ji += 1
        elif isinstance(node, ir.Aggregate):
            d = decisions.get(f"aggregate{ai}:{','.join(node.keys)}", {})
            agg_hints.append(d.get("engine"))
            ai += 1
    return join_plans, agg_hints, handles


def _inputs_encoded(inputs: dict) -> bool:
    return any(is_encoded(c) for b in inputs.values() for c in b.columns)


def _dense_domain(node: ir.Join, inputs: dict):
    """The domain ``node``'s rowid-table path may assume, or None."""
    dense = node.dense_domain
    if dense == "build":
        rb = inputs.get(node.right.name) \
            if isinstance(node.right, ir.Scan) else None
        dense = rb.num_rows if rb is not None else None
    if _inputs_encoded(inputs):
        # the rowid fast path keys on raw .data, which encoded
        # columns do not expose — the hand encoded q95 lowering
        dense = None
    return dense


# joins whose output, compacted or not, has the left side's row slots
_ROW_KEEPING_JOINS = ("inner", "semi", "anti")
# ... and those that may hand on a row mask: with the outer join that keeps
# every build row, whose mask form has the build side's rows after them
_MASKED_JOINS = _ROW_KEEPING_JOINS + ("right",)


def _join_outputs(plan: ir.PlanNode, inputs: dict, decisions: dict) -> None:
    """Adds to each join's decision its output form: ``"mask"`` where the
    join is an inner, semi, anti or right, shuffled one over a dense domain and
    what consumes its rows takes a scattered row mask (an Exchange, an
    Aggregate, a Filter, a Join on either side: the build takes
    ``right_valid`` as the probe takes ``left_valid``; a Project hands its
    own consumer's answer down), so that it need not put the matches in
    front; ``"compact"`` at the root, under a Sort or TopK and everywhere
    else.  A join that is not inner also says its ``"how"``."""
    joins = []   # (Join, its consumer takes a mask), in walk order

    def visit(node, masked):
        for c in node.children():
            if isinstance(node, ir.Project):
                visit(c, masked)
            elif isinstance(node, ir.Join):
                visit(c, True)
            else:
                visit(c, isinstance(node, (ir.Exchange, ir.Aggregate,
                                           ir.Filter)))
        if isinstance(node, ir.Join):
            joins.append((node, masked))

    visit(plan, False)
    for ji, (node, masked) in enumerate(joins):
        d = decisions[f"join{ji}:{node.left_on}"]
        d["output"] = "mask" if (
            masked and node.how in _MASKED_JOINS
            and d["strategy"] == "shuffled"
            and _dense_domain(node, inputs) is not None) else "compact"
        if node.how != "inner":
            d["how"] = node.how


def _rows_at(node: ir.PlanNode, inputs: dict) -> Optional[int]:
    """Rows of ``node``'s output as it is lowered, where the plan and the
    inputs tell: every node but an Aggregate hands on as many rows as its
    (left) child has, an inner join within that budget, a semi or anti
    join the left rows it keeps."""
    while not isinstance(node, ir.Scan):
        if isinstance(node, ir.TopK):
            return node.n
        if isinstance(node, ir.Aggregate) or (
                isinstance(node, ir.Join)
                and node.how not in _ROW_KEEPING_JOINS):
            return None
        node = node.child
    return getattr(inputs.get(node.name), "num_rows", None)


def _aggregate_heads(plan: ir.PlanNode, inputs: dict,
                     decisions: dict) -> None:
    """Adds ``"head"`` and ``"tiers"`` to the decision of each Aggregate
    that the sort engine may run (every lowering but the one-hot one: the
    scatter and domain engines keep it as their fallback): the group
    slots at which it fetches its result, the narrowest first, short of
    every row.  With the ``num_groups`` a query returns that tells which
    branch ran: the narrowest width that holds them (the head's up to
    ``"head"``), the row-wide one past the last."""
    from ..relational.aggregate import sortscan_tiers

    ai = 0
    for node in plan.walk():
        if not isinstance(node, ir.Aggregate):
            continue
        schema = _schema_at(node.child, inputs)
        if schema is None or not _takes_onehot(node, schema):
            rows = _rows_at(node.child, inputs)
            tiers = sortscan_tiers(rows)   # ends at ``rows`` where known
            decisions.setdefault(
                f"aggregate{ai}:{','.join(node.keys)}", {}).update(
                    head=tiers[0],
                    tiers=tuple(w for w in tiers if w != rows))
        ai += 1


def _default_stats() -> Optional[dict]:
    """Live stats the system already recorded: the process-wide
    :class:`~spark_rapids_jni_tpu.shuffle.registry.ShuffleMetrics`
    snapshot, when any shuffle has actually run.  An empty registry
    returns ``None`` so first-query planning is byte-identical to the
    explicit ``stats=None`` behavior (and the plan-cache key does not
    pick up a noise dict)."""
    from ..shuffle import get_registry

    snap = get_registry().metrics.snapshot()
    if snap.get("shuffles"):
        return {"shuffle": snap}
    return None


def compile_plan(plan: ir.PlanNode, inputs: dict, ctx=None,
                 stats: Optional[dict] = None) -> CompiledPlan:
    """Compile ``plan`` against the schemas/stats of ``inputs`` (a dict
    binding every Scan name to a ``ColumnBatch``), consulting the plan
    cache first.  ``ctx`` (TaskContext) owns any broadcast build tables
    the adaptive layer decides to create; ``stats`` feeds the adaptive
    decisions (see :func:`adaptive.plan_decisions`) and defaults to the
    ShuffleRegistry's recorded metrics — Spark's AQE loop: earlier
    exchanges' observed skew/rows inform later plans with no caller
    plumbing."""
    with profiler.span("plan.lookup"):
        with profiler.span("plan.decisions"):
            if stats is None:
                stats = _default_stats()
            decisions = adaptive.plan_decisions(plan, inputs, stats)
            decisions.update(_typed_decisions(plan, inputs))
            _join_outputs(plan, inputs, decisions)
            _aggregate_heads(plan, inputs, decisions)
        with profiler.span("plan.key"):
            key = plan_cache_key(plan, inputs, decisions)
        cache = get_plan_cache()
        cached = cache.get(key)
        if cached is not None:
            cached.last_lookup = "hit"
            return cached

        join_plans, agg_hints, handles = _resolve_join_plans(
            plan, inputs, decisions, ctx)
        input_names = ir.scan_names(plan)

        def run(env, prebuilts):
            # the Python lowering: runs only when jit traces, so this
            # span appears on a cache miss (or a retrace) and never on
            # a hit
            from ..relational.aggregate import rowwide_gathers
            from ..relational.gather import row_gathers, validity_gathers

            with profiler.span("plan.trace"):
                _TRACE_COUNT[0] += 1
                st = _State(join_plans, agg_hints)
                rowwide = rowwide_gathers()
                validity = validity_gathers()
                gathers = row_gathers()
                batch, live, _pfx = _lower(plan, env, prebuilts, st)
                get_plan_cache().note_joins(st.joins_masked,
                                            st.joins_compacted)
                get_plan_cache().note_rowwide_gathers(
                    rowwide_gathers() - rowwide)
                get_plan_cache().note_validity_gathers(
                    validity_gathers() - validity)
                get_plan_cache().note_row_gathers(row_gathers() - gathers)
                get_plan_cache().note_topk_rows(st.topk_sorted_rows)
                get_plan_cache().note_agg_input_slots(st.agg_input_slots)
                get_plan_cache().note_like_char_slots(st.like_char_slots)
                # from an Aggregate up ``live`` is the group count
                return batch if live is None else (batch, live)

        compiled = CompiledPlan(plan, key, jax.jit(run), input_names,
                                handles, decisions)
        cache.put(key, compiled)
        cache.note_routes(r for k, d in decisions.items()
                          if k.startswith("project")
                          for r in d["routes"])
        return compiled


def _schema_at(node: ir.PlanNode, inputs: dict) -> Optional[dict]:
    """name -> SparkType of ``node``'s output where it can be told from
    the plan and the inputs alone (Scan, Filter, Exchange, Sort, TopK,
    Project, a Join whose sides share no name but the key); else None."""
    if isinstance(node, ir.Scan):
        return _batch_schema(inputs[node.name]) if node.name in inputs \
            else None
    if isinstance(node, (ir.Filter, ir.Exchange, ir.Sort, ir.TopK)):
        return _schema_at(node.child, inputs)
    if isinstance(node, ir.Join):
        left = _schema_at(node.child, inputs)
        right = _schema_at(node.right, inputs)
        if left is None or right is None:
            return None
        right = {n: t for n, t in right.items() if n != node.right_on}
        if set(left) & set(right):
            return None   # suffixed by the join: not followed here
        return {**left, **right}
    if isinstance(node, ir.Project):
        below = _schema_at(node.child, inputs)
        if below is None:
            return None
        try:
            return {name: expr_type(expr, below)
                    for name, expr in node.outputs()}
        except (KeyError, NotImplementedError):
            return None
    return None


def _typed_decisions(plan: ir.PlanNode, inputs: dict) -> dict:
    """What the compiler decides from types: for each computed output of a
    Project its Spark type and the arithmetic of every operation under
    it (``project<i>:<output>``), a Sort that the Aggregate below it
    makes redundant (``sort<i>:<keys>``), and an ordered limit's ``n``, its
    keys with direction and null placement and its route
    (``topk<i>:<keys>``), and a string predicate's op, pattern and route
    (``filter<i>:<column>``).  Plans with none of them get
    nothing, so their cache keys are what they were."""
    from ..ops.strings import like_segments

    out = {}
    pi = si = ti = fi = 0
    for node in plan.walk():
        if isinstance(node, ir.Filter):
            if node.op in ir.STRING_FILTER_OPS:
                like_segments(node.value)   # refuses what it cannot match
                out[f"filter{fi}:{node.column}"] = {
                    "op": node.op, "pattern": node.value,
                    "route": "strings.like"}
            fi += 1
        elif isinstance(node, ir.Project):
            below = _schema_at(node.child, inputs)
            seen = set()
            for name, expr in node.outputs():
                if below is None or isinstance(expr, ir.Col):
                    continue
                try:
                    out[f"project{pi}:{name}"] = {
                        "type": repr(expr_type(expr, below)),
                        "routes": tuple(expr_routes(expr, below, seen))}
                except (KeyError, NotImplementedError):
                    pass   # the lowering says what it cannot do
            pi += 1
        elif isinstance(node, ir.Sort):
            agg = node.child
            if (isinstance(agg, ir.Aggregate) and node.keys == agg.keys
                    and isinstance(agg.domain, tuple)):
                schema = _schema_at(agg.child, inputs)
                if schema is not None and _takes_onehot(agg, schema):
                    out[f"sort{si}:{','.join(node.keys)}"] = {
                        "elided": "the composite-domain aggregate below "
                                  "emits key order, nulls first"}
            si += 1
        elif isinstance(node, ir.TopK):
            order = node.order()
            out[f"topk{ti}:{','.join(o.name for o in order)}"] = {
                "n": node.n,
                "keys": tuple(o.describe() for o in order),
                "route": "selection"}
            ti += 1
    return out


def _maybe_execute_streaming(plan: ir.PlanNode, inputs: dict, ctx=None):
    """The streaming lowering: a root ``Exchange(Scan)`` whose input
    binds a :class:`~spark_rapids_jni_tpu.shuffle.MorselSource` under
    the ``shuffle_stream`` knob runs the morsel-driven out-of-core
    :meth:`~spark_rapids_jni_tpu.shuffle.ShuffleService.exchange_stream`
    — decode overlaps round drains, round chunks spill host→disk —
    instead of materializing the scan for the jitted local exchange.
    Returns ``(batch, occupancy)`` (the "batch plus live mask" root
    contract) or ``None`` when the pattern does not apply."""
    from ..shuffle import ShuffleService
    from ..shuffle.morsel import MorselSource

    if not config.get("shuffle_stream"):
        return None
    if not (isinstance(plan, ir.Exchange)
            and isinstance(plan.child, ir.Scan)):
        return None
    src = inputs.get(plan.child.name)
    if not isinstance(src, MorselSource):
        return None
    if src.mesh is None:
        raise ValueError(
            "streaming lowering needs a MorselSource built against a "
            "mesh (use MorselSource.from_batch/from_parquet)")
    P = src.mesh.shape[src.axis_name]
    if plan.partitions != P:
        raise ValueError(
            f"Exchange(partitions={plan.partitions}) cannot stream over "
            f"a {P}-device mesh: the service partitions across devices")
    res = ShuffleService(src.mesh, src.axis_name).exchange_stream(
        src, key_names=[plan.key], ctx=ctx)
    return res.batch, res.occupancy


def execute(plan: ir.PlanNode, inputs: dict, ctx=None,
            stats: Optional[dict] = None):
    """Compile (or fetch) and run ``plan`` over ``inputs``.  Aggregate
    roots return ``(result, num_groups)`` — the hand-fused steps'
    contract; other roots return the batch (plus a live mask when one
    is in flight).  With the ``shuffle_stream`` knob on, a root
    ``Exchange(Scan)`` bound to a ``MorselSource`` takes the streaming
    out-of-core path instead (see :func:`_maybe_execute_streaming`)."""
    streamed = _maybe_execute_streaming(plan, inputs, ctx=ctx)
    if streamed is not None:
        return streamed
    return compile_plan(plan, inputs, ctx=ctx, stats=stats)(inputs)
