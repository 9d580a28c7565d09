"""Plan cache: canonical IR shape + input schema + config fingerprint
-> compiled program, with LRU eviction and hit/miss counters.

A hit returns the SAME :class:`~spark_rapids_jni_tpu.plan.compile.
CompiledPlan` object, whose jitted callable has already traced for the
cached shapes — so a repeated-shape execution costs zero retraces (the
property tests assert via :func:`~spark_rapids_jni_tpu.plan.compile.
trace_count`).  Any knob flip changes the config fingerprint and any
shape/dtype/dict-token change the schema fingerprint, so both are
misses by construction rather than by invalidation logic.

Counters surface the same way the spill/shuffle metrics do:
``RmmSpark.plan_cache_metrics()`` reads :func:`plan_cache_metrics`.
"""

from __future__ import annotations

import threading
from collections import OrderedDict

from .. import config


class PlanCache:
    """LRU cache with explicit hit/miss/eviction counters.

    ``maxsize`` defaults to the ``plan_cache_size`` knob, re-read at
    every insert so a live knob change takes effect without rebuilding
    the cache (shrinking evicts immediately).
    """

    def __init__(self, maxsize=None):
        self._maxsize = maxsize
        self._lock = threading.Lock()
        self._entries: OrderedDict = OrderedDict()
        # key -> set of owners holding the entry resident (serving
        # tenants pin plans they are executing; pinned entries are
        # skipped by LRU eviction so one tenant's compile storm cannot
        # evict a plan another tenant is mid-flight on)
        self._pins: dict = {}
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        # products the compiler lowered, by route (exact: int64 or typed
        # limbs; rounded: the 256-bit multiply with its long division)
        self.routes = {"mul_exact": 0, "mul_rounded": 0}
        # the newest plan traced: its joins by output form (a row mask
        # handed on, or the matches compacted in front)
        self.joins = {"joins_masked": 0, "joins_compacted": 0}
        # ... and the gathers of one index a row that its sort-engine
        # aggregates traced outside the branch that many groups take
        self.agg_rowwide_gathers = 0
        # ... and the validity buffers its row gathers move (a packed
        # word counts one)
        self.validity_gathers = 0
        # ... and the gather operations its row gathers make (a matrix
        # of words counts one)
        self.row_gathers = 0
        # ... and the row slots its ordered limits put through a sort or
        # a selection
        self.topk_sorted_rows = 0
        # ... and the row slots its aggregates take in, summed
        self.agg_input_slots = 0
        # ... and the char slots (rows x stored width) its string
        # predicates scan
        self.like_char_slots = 0

    def note_routes(self, routes) -> None:
        """Count a newly compiled plan's ``route:arithmetic:type``s."""
        with self._lock:
            for r in routes:
                name = r.split(":")[0]
                if name in self.routes:
                    self.routes[name] += 1

    def note_joins(self, masked: int, compacted: int) -> None:
        """A plan was traced: how many of its joins took each form."""
        with self._lock:
            self.joins = {"joins_masked": int(masked),
                          "joins_compacted": int(compacted)}

    def note_rowwide_gathers(self, count: int) -> None:
        """A plan was traced: the row-wide gathers of its sort-engine
        aggregates (``relational.aggregate.rowwide_gathers``)."""
        with self._lock:
            self.agg_rowwide_gathers = int(count)

    def note_validity_gathers(self, count: int) -> None:
        """A plan was traced: the validity buffers its row gathers of
        more than 4096 indices move (``relational.gather.validity_gathers``)."""
        with self._lock:
            self.validity_gathers = int(count)

    def note_row_gathers(self, count: int) -> None:
        """A plan was traced: the gather operations its row gathers of
        more than 4096 indices make (``relational.gather.row_gathers``)."""
        with self._lock:
            self.row_gathers = int(count)

    def note_topk_rows(self, rows: int) -> None:
        """A plan was traced: the row slots its ordered limits (``TopK``)
        put through their selection, 0 for a plan with none."""
        with self._lock:
            self.topk_sorted_rows = int(rows)

    def note_agg_input_slots(self, slots: int) -> None:
        """A plan was traced: the row slots its aggregates take in,
        summed over them, whatever share of the slots holds a live row."""
        with self._lock:
            self.agg_input_slots = int(slots)

    def note_like_char_slots(self, slots: int) -> None:
        """A plan was traced: the char slots (rows times the stored width)
        that its ``LIKE`` filters scan, summed, 0 for a plan with none."""
        with self._lock:
            self.like_char_slots = int(slots)

    def _capacity(self) -> int:
        if self._maxsize is not None:
            return int(self._maxsize)
        return int(config.get("plan_cache_size"))

    def get(self, key):
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                self.misses += 1
                return None
            self._entries.move_to_end(key)
            self.hits += 1
            return entry

    def put(self, key, value) -> None:
        with self._lock:
            self._entries[key] = value
            self._entries.move_to_end(key)
            cap = max(self._capacity(), 1)
            while len(self._entries) > cap:
                victim = next((k for k in self._entries
                               if k not in self._pins), None)
                if victim is None:
                    break  # everything pinned: overflow beats breaking a tenant
                del self._entries[victim]
                self.evictions += 1

    def pin(self, key, owner) -> None:
        """Hold ``key`` resident on behalf of ``owner`` (any hashable —
        the serving runtime uses its session id).  Pinning a key not in
        the cache is allowed: the pin applies when the plan lands."""
        with self._lock:
            self._pins.setdefault(key, set()).add(owner)

    def unpin(self, key, owner) -> None:
        with self._lock:
            owners = self._pins.get(key)
            if owners is None:
                return
            owners.discard(owner)
            if not owners:
                del self._pins[key]

    def release_owner(self, owner) -> None:
        """Drop every pin ``owner`` holds — the kill-safe unwind path: a
        cancelled tenant must not leave plans unevictable."""
        with self._lock:
            for key in list(self._pins):
                owners = self._pins[key]
                owners.discard(owner)
                if not owners:
                    del self._pins[key]

    def pinned(self, key) -> bool:
        with self._lock:
            return key in self._pins

    def invalidate_snapshot(self, snapshot_id) -> int:
        """Drop every cached plan whose key embeds ``snapshot_id``.

        Plan signatures may carry Scan snapshot ids (plan/ir.py): a
        long-lived serving process that learns an input mutated can
        drop the dead generation's compiled plans instead of waiting
        for LRU churn.  The result cache's
        ``ResultCache.invalidate_snapshot`` routes through here so one
        call retires BOTH caches' entries for the old contents.
        Pinned plans are dropped too — a mutated input makes them
        unservable regardless of in-flight interest.
        """
        def embeds(obj) -> bool:
            if obj == snapshot_id:
                return True
            if isinstance(obj, tuple):
                return any(embeds(v) for v in obj)
            return False

        with self._lock:
            victims = [k for k in self._entries if embeds(k)]
            for k in victims:
                del self._entries[k]
                self._pins.pop(k, None)
                self.evictions += 1
            return len(victims)

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def metrics(self) -> dict:
        from ..relational.aggregate import onehot_slots

        with self._lock:
            return {
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "size": len(self._entries),
                "capacity": self._capacity(),
                "pinned": len(self._pins),
                **self.routes,
                **self.joins,
                "agg_rowwide_gathers": self.agg_rowwide_gathers,
                "validity_gathers": self.validity_gathers,
                "row_gathers": self.row_gathers,
                "topk_sorted_rows": self.topk_sorted_rows,
                "agg_input_slots": self.agg_input_slots,
                "like_char_slots": self.like_char_slots,
                # int8 slots of the newest one-hot contraction traced
                "onehot_slots": onehot_slots(),
            }

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._pins.clear()


_cache = PlanCache()


def get_plan_cache() -> PlanCache:
    return _cache


def plan_cache_metrics() -> dict:
    """Snapshot of the global plan cache's counters (zeros-safe)."""
    return _cache.metrics()


def reset_plan_cache() -> None:
    """Drop every cached plan AND zero the counters (test isolation)."""
    global _cache
    _cache = PlanCache()
