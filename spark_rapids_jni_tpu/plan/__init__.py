"""Logical plan IR + whole-query compiler.

The hand-fused flagship pipelines (``_q6_step``/``_q95_step`` in
``__graft_entry__.py``) each hard-code one physical plan; every new NDS
query used to mean hand-writing another.  This package makes a query
DATA instead:

* :mod:`ir` — a small logical IR (Scan/Filter/Project/Join/Aggregate/
  Exchange/Sort/TopK over ``ColumnBatch``, expressions ``Col``/``Lit``/``+ - *``
  inside a Project), hashable and canonicalized so a plan SHAPE is a
  dict key;
* :mod:`compile` — lowers a whole plan into ONE jitted program, fusing
  adjacent exchange + group-by stages exactly the way the hand paths do
  (``regroup_order(secondary=)``), dispatching into the existing
  engine-selectable relational/shuffle kernels, encoded inputs included
  (predicate pushdown onto dictionary codes, late materialization);
* :mod:`adaptive` — plan-time decisions from stats the system already
  collects (``ShuffleMetrics``, counts passes, ``stages_ms`` notes):
  broadcast vs shuffled join, scatter vs sort engine, per-exchange
  round capacity;
* :mod:`cache` — a plan cache keyed on canonical IR shape + input
  schema + config fingerprint, so a repeated shape re-executes with
  ZERO retraces (hit/miss counters surface through ``RmmSpark`` and the
  profiler).

Correctness bar: q6 and q95 expressed as IR (:mod:`queries`) are
bit-identical to the hand-fused paths on plain AND encoded inputs,
under both engine knob settings.
"""

from .ir import (Aggregate, Agg, Arith, Col, DateLit, Desc, Exchange, Filter,
                 Join, Lit, Project, Scan, Sort, SortOrder, TopK)
from .compile import (CompiledPlan, compile_plan, execute, expr_type,
                      trace_count)
from .cache import get_plan_cache, plan_cache_metrics, reset_plan_cache
from .adaptive import (choose_exchange_capacity, choose_groupby_engine,
                       choose_join_engine, choose_join_strategy,
                       plan_decisions)
from . import queries

__all__ = [
    "Scan", "Filter", "Project", "Join", "Aggregate", "Agg", "Exchange",
    "Sort", "SortOrder", "Desc", "TopK", "Col", "Lit", "Arith", "DateLit",
    "CompiledPlan", "compile_plan", "execute", "expr_type", "trace_count",
    "get_plan_cache", "plan_cache_metrics", "reset_plan_cache",
    "choose_join_strategy", "choose_join_engine", "choose_groupby_engine",
    "choose_exchange_capacity", "plan_decisions",
    "queries",
]
