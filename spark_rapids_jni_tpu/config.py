"""Unified config/flag registry.

The reference spreads its knobs over four layers (SURVEY.md §5: maven/
cmake build properties, Java system properties, env vars for injected
libs, and per-call arguments).  Here one registry holds every documented
runtime knob with an env-var override (``SPARK_RAPIDS_TPU_<KEY>``),
while per-call arguments keep winning at call sites — the same precedence
story, minus the scatter.
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

_ENV_PREFIX = "SPARK_RAPIDS_TPU_"


@dataclass(frozen=True)
class _Entry:
    default: Any
    parse: Callable[[str], Any]
    doc: str


_REGISTRY: Dict[str, _Entry] = {}
_overrides: Dict[str, Any] = {}
_lock = threading.Lock()


def _register(key: str, default, parse, doc: str):
    _REGISTRY[key] = _Entry(default, parse, doc)


def _parse_bool(s: str) -> bool:
    return s.strip().lower() in ("1", "true", "yes", "on")


# ---- documented knobs ------------------------------------------------------
_register("watchdog_poll_ms", 100.0, float,
          "Deadlock watchdog period for the resource adaptor "
          "(reference: ai.rapids.cudf.spark.rmmWatchdogPollingPeriod).")
_register("mem_pool_bytes", 0, int,
          "Default logical HBM arena size for RmmSpark.set_event_handler "
          "(0 = caller must pass one explicitly).")
_register("json_max_out", 0, int,
          "get_json_object output width cap (0 = provable 6*L+20 bound).")
_register("json_fast_path", True, _parse_bool,
          "Route wildcard-free get_json_object paths through the "
          "bit-parallel fast engine (ops/json_fast.py): O(path + log L) "
          "data-parallel passes instead of max_len sequential scan "
          "steps; rows it cannot prove it handles fall back to the scan "
          "machine per batch.")
_register("json_fallback_div", 16, int,
          "Per-row fallback compaction capacity for the JSON hybrid: "
          "flagged rows are gathered into fixed chunks of ceil(n/div) "
          "rows and only those chunks run the serial scan machine "
          "(lax.while_loop; clean batches run zero iterations). div=1 "
          "degenerates to whole-batch chunks; 0 disables compaction "
          "(any flagged row routes the whole batch, pre-r5 behavior). "
          "Default 16 from the r5 CPU sweep at 4K docs: 1.82x/2.47x the "
          "all-clean rate at 1%/10% dirty rows (div=8: 2.53x/2.64x; "
          "div=32: 1.64x/3.68x) — the chunk then costs about one fast "
          "pass, balancing low-rate latency against high-rate chunk "
          "count.")
_register("json_scan_unroll", 2, int,
          "Chars processed per while-loop iteration in the JSON scan "
          "(lax.scan unroll): the scan carry round-trips HBM once per "
          "iteration, so higher = fewer latency-bound steps, more code. "
          "Compile time scales ~linearly with the unroll (round 4: 23s/"
          "91s/~550s for 1/4/8 on a 1-core CPU) and the hybrid compiles "
          "the scan as the fallback branch of every wildcard-free query, "
          "so the default is a compile-friendly 2 now that the "
          "bit-parallel fast path carries clean batches.")
_register("spill_dir", "", str,
          "Directory for the spill framework's disk tier (mem/spill.py). "
          "Empty (default) = a fresh mkdtemp owned — and removed — by "
          "the SpillFramework; set it to put spill files on a chosen "
          "volume (reference: spark.local.dir for RapidsDiskStore).")
_register("shuffle_capacity_bucket", 256, int,
          "Rounding bucket for auto-planned exchange capacities (bigger = "
          "fewer recompiles, more slot padding).")
_register("shuffle_round_rows", 1 << 16, int,
          "Per-(sender,destination) slot rows one ShuffleService round may "
          "carry (shuffle/planner.py).  Buckets bigger than this drain "
          "over multiple all_to_all rounds instead of inflating the slot "
          "grid — the TPU analogue of the reference's fixed-size shuffle "
          "batch discipline.")
_register("shuffle_strict_pids", False, _parse_bool,
          "Raise ShuffleError on out-of-range partition ids (< 0 or > P) "
          "instead of routing them to the null partition and counting "
          "them in ShuffleMetrics.oob_rows.")
_register("shuffle_max_rounds", 64, int,
          "Cap on ShuffleService rounds per exchange; a plan that would "
          "exceed it RAISES per-round capacity (never drops rows) so the "
          "host-side round loop stays bounded under extreme skew.")
_register("spill_checksum", True, _parse_bool,
          "Record a CRC32 + byte length for every leaf the spill "
          "framework writes to disk and verify both on read-back "
          "(mem/spill.py).  A mismatch means the spilled copy is damaged: "
          "the handle rebuilds via its recompute= lineage when it has "
          "one, else raises SpillCorruptionError LOUDLY instead of "
          "silently computing on garbage.  Off = trust the filesystem.")
_register("shuffle_max_recoveries", 8, int,
          "Per-exchange budget for lineage recoveries in the "
          "ShuffleService (shuffle/service.py): each lost/corrupt "
          "PartitionBuffer rebuilt by re-running its map shards or "
          "re-driving its round counts against this bound "
          "(ShuffleMetrics.recovered_partitions); exceeding it raises "
          "ShuffleError so a flapping disk cannot loop a shuffle "
          "forever.")
_register("scan_morsel_rows", 4096, int,
          "Per-device rows in one scan morsel (shuffle/morsel.py): the "
          "streaming scan→shuffle pipeline decodes, maps and routes one "
          "morsel at a time so earlier exchange rounds drain while later "
          "morsels are still decoding.  Smaller = finer overlap and a "
          "lower device-resident peak; bigger = fewer map dispatches.")
_register("shuffle_stream", False, _parse_bool,
          "Lower Exchange(Scan) plans bound to a MorselSource through "
          "ShuffleService.exchange_stream (plan/compile.py) instead of "
          "materializing the whole scan before round 1 drains.  The "
          "streaming path is bit-identical on delivered rows; off = "
          "always materialize.")
_register("shuffle_scatter_engine", "auto", str,
          "Morsel->round-chunk scatter engine for the streaming shuffle "
          "map step (shuffle/service.py _scatter_step): 'lax' (XLA "
          "searchsorted + per-column scatters with the row->slot map "
          "rematerialized between programs), 'pallas' (ONE fused kernel "
          "computing pid, per-partition cumulative offsets, and every "
          "column's chunk scatter with the map resident in VMEM — "
          "interpret mode off-accelerator, bit-identical chunks), or "
          "'auto' (lax everywhere until a hardware round measures the "
          "kernel faster — PALLAS_MEMO.md's delete-or-measure rule).")
_register("shuffle_capacity_dcn", 0, int,
          "Override for the per-(sender, destination-host) slot capacity "
          "of hop one (DCN) in hierarchical exchanges "
          "(shuffle/planner.py plan_hierarchical); 0 = plan it from the "
          "observed count matrix instead of the flat worst-case grid.")
_register("shuffle_capacity_ici", 0, int,
          "Override for the per-(sender, destination-chip) slot capacity "
          "of hop two (ICI) in hierarchical exchanges "
          "(shuffle/planner.py plan_hierarchical); 0 = plan it from the "
          "observed count matrix.")
_register("chaos_trials", 4, int,
          "Seeded multi-fault trials per scenario in the chaos campaign "
          "(tools/chaos.py) on top of the exhaustive one-fault-per-trial "
          "sweep; each trial samples 2-3 recoverable fault rules with "
          "deterministic skip/count offsets from the campaign seed.")
_register("q6_group_path", "onehot", str,
          "Aggregation path of a domain-key Aggregate (q6_plan, "
          "_q6_step): 'onehot' (group_by_onehot over the key's static "
          "domain, engine picked by q6_onehot_engine) or 'sort' (the "
          "general engine-selectable group_by — despite the legacy value "
          "name it honors the groupby_engine knob, so on CPU it runs "
          "the slot-table scatter engine, not a hard-wired sort).")
_register("q6_onehot_engine", "auto", str,
          "Engine for the q6 domain-key aggregation: 'auto' (scatter on "
          "CPU, xla on accelerators — measured both ways round 4), 'xla' "
          "(materialized one-hot contraction), 'pallas' (fused VMEM "
          "one-hot kernel), or 'scatter' (DOMAIN segment sums — keys "
          "index segments directly, no key normalization or slot table, "
          "unlike the general groupby_engine='scatter'; fast on CPU, 2 "
          "orders slow on TPU v5e).")
_register("group_sort_payload", "gather", str,
          "How sort-scan group_by moves agg values into sorted order: "
          "'gather' (sort only [keys..., row-id], then one take() per agg "
          "column — fewest sort operands) or 'ride' (agg words ride the "
          "sort as payload operands — no post-sort gathers).  The "
          "emulated-64-bit multi-operand sort measured ~1s/iter at 256K "
          "rows on v5e (round 3), so 'gather' is the default; 'ride' is "
          "kept for A/B.")
_register("groupby_engine", "auto", str,
          "General group_by engine (relational/aggregate.py): 'sort' "
          "(one stable multi-operand lax.sort + segmented scans — the "
          "accelerator engine), 'scatter' (open-addressing slot table + "
          "segment_* reductions, no row-sized sort — the CPU engine; "
          "falls back to sort via lax.cond when the slot table "
          "overflows), or 'auto' (scatter on CPU, sort on accelerators "
          "— XLA-CPU's lax.sort is its slowest primitive and its "
          "scatters the fastest; on TPU v5e the inversion holds, "
          "scatters at 16-150ms per 2M rows).")
_register("join_engine", "auto", str,
          "hash_join probe engine (relational/join.py): 'sort' "
          "(sorted build side + fused binary-search equal_range probe), "
          "'hash' (open-addressing slot table build + linear-probe "
          "walk; bit-identical output, no build-side lax.sort), or "
          "'auto' (hash on CPU, sort on accelerators — same hardware "
          "facts as groupby_engine).")
_register("encoded_execution", "auto", str,
          "Dictionary/RLE encoded columnar execution "
          "(columnar/encoded.py): 'on' encodes eligible columns at the "
          "host boundary (Parquet dictionary pages pass through as "
          "DictionaryColumn, bench inputs encode) and operators run on "
          "u32 codes with late materialization; 'off' decodes "
          "everything up front (the pre-PR-6 behavior); 'auto' = on for "
          "CPU, off for accelerators (the encoded paths lean on "
          "gathers, which serialize on the TPU VPU).  Bit-parity with "
          "the decoded path is the correctness contract either way — "
          "relational operators accept encoded and plain columns "
          "mixed, so the knob only gates where encoding is "
          "INTRODUCED.")
_register("packed_predicates", True, _parse_bool,
          "Evaluate comparison filters (<, <=, ==, !=, >=, >) directly "
          "on BitPackedColumn/FrameOfReferenceColumn residuals "
          "(columnar/encoded.py packed_filter_mask): the literal is "
          "transformed once per frame (subtract the reference, clamp to "
          "the pack-width domain, out-of-domain literals fold to "
          "constant masks) and u32 lanes compare without ever calling "
          "decode().  Bit-identical to decode-then-compare; off = "
          "always decode first (the exact-parity fallback).")
_register("zone_maps", True, _parse_bool,
          "Record a CRC32'd per-block min/max sidecar (ZoneMap) on "
          "packed columns at encode time and let MorselSource skip "
          "whole morsels a filter's zone-map check proves cold "
          "(shuffle/morsel.py), counted as ShuffleMetrics "
          "blocks_skipped/blocks_scanned.  A sidecar whose CRC or "
          "stats disagree raises ZoneMapCorruptionError LOUDLY at skip "
          "time — wrong rows are never silently returned.  Off = no "
          "sidecars, every block scanned.")
_register("scan_pruning", True, _parse_bool,
          "Push scan-level predicates into the Parquet footer "
          "(io/parquet.py / io/parquet_footer.py): row groups whose "
          "column min/max statistics cannot satisfy the predicate are "
          "dropped before any data page is read, and "
          "MorselSource.from_parquet never builds replays for them.  "
          "Groups with missing stats or nulls are conservatively kept; "
          "off = read every split-surviving row group.")
_register("plan_cache_size", 64, int,
          "Max compiled programs the plan cache (plan/cache.py) holds; "
          "LRU past it.  Keys are (canonical IR shape, input schema, "
          "config fingerprint), so a hit replays an already-traced "
          "program with zero retraces.")
_register("broadcast_threshold_rows", 1 << 16, int,
          "Adaptive-join build-side row cutoff (plan/adaptive.py): a "
          "strategy='auto' join whose observed build side is at or "
          "under this goes broadcast (spill-registered prebuilt build "
          "table), over it shuffled — Spark's "
          "autoBroadcastJoinThreshold, in rows.")
_register("adaptive_execution", True, _parse_bool,
          "Plan-time adaptive decisions (plan/adaptive.py): broadcast "
          "vs shuffled joins from observed build sizes, group-by engine "
          "from skewed counts passes, per-exchange round capacity from "
          "ShuffleMetrics.  Off = the static defaults everywhere "
          "(shuffled joins, knob-resolved engines).")
_register("q6_float_mode", "f32x3", str,
          "Float-sum mode for the q6 onehot path: 'f64' (the exact sum: "
          "the doubles ride the int8 contraction as fixed-point digits, "
          "Spark's double average to the last places) or 'f32x3' (Dekker "
          "split accumulated in f32 on the MXU, about 5e-5 relative off; "
          "no faster since the digits, kept as the benchmark's control).")
_register("serve_max_concurrent", 4, int,
          "Admission slots of the serving runtime (serve/runtime.py): "
          "how many tenant queries may hold a TaskContext at once; the "
          "rest wait in the admission queue (their wait is visible to "
          "the deadlock scan via ThreadStateRegistry).")
_register("serve_admit_timeout_s", 30.0, float,
          "Max seconds a submitted query may wait in the admission "
          "queue before failing with QueryTimeout (per admission "
          "attempt; re-admissions get a fresh window).")
_register("serve_stall_break_ms", 2000.0, float,
          "Serving-mode watchdog escalation: threads continuously "
          "blocked past this are treated as a cross-tenant deadlock "
          "cycle even while OTHER tenants keep running (the global scan "
          "only fires when every task thread is blocked), and the "
          "lowest-priority one is rolled back (RetryOOM).  0 disables; "
          "armed by ServeRuntime on construction.")
_register("serve_max_readmissions", 2, int,
          "How many times a query killed by its own timeout is backed "
          "off and re-admitted before QueryTimeout surfaces to the "
          "caller (bounded re-admission; external cancels never "
          "re-admit).")
_register("serve_backoff_ms", 50.0, float,
          "Base backoff between a query's timeout-kill and its "
          "re-admission, doubled per attempt (serve/runtime.py); the "
          "front door reuses it as the base delay of its session "
          "re-placement and worker-respawn ladders.")
_register("serve_workers", 2, int,
          "Executor worker processes the multi-process front door "
          "(serve/frontdoor.py) spawns; each hosts its own ServeRuntime, "
          "arena, spill store, and plan cache, with tenant sessions "
          "pinned to one worker over the local-socket protocol — one "
          "wedged interpreter can't take the fleet down.")
_register("serve_heartbeat_ms", 100.0, float,
          "Front-door heartbeat period: the supervisor pings every "
          "worker this often; a worker silent for ~3.5 periods (or "
          "whose native stall-breaker epoch keeps climbing with no "
          "completions) is declared wedged and SIGKILLed.")
_register("serve_respawn_max", 3, int,
          "Circuit breaker on worker respawns: how many times one "
          "worker slot may be respawned (with exponential backoff) "
          "before the front door stops replacing it and serves "
          "degraded on the surviving workers.")
_register("serve_shed_threshold", 0.5, float,
          "Degradation threshold: when the healthy fraction of "
          "configured workers drops below this, the front door sheds "
          "lowest-priority pending admissions beyond the surviving "
          "capacity (AdmissionShed) instead of queueing unboundedly.")
_register("serve_transport", "unix", str,
          "Fleet transport the front door serves workers over: 'unix' "
          "(one Unix-domain socket under the private fleet dir — the "
          "single-box default) or 'tcp' (workers dial the supervisor's "
          "127.0.0.1 listener; the multi-host placement path).  Both "
          "ride the same framed protocol with CRC32 trailers and "
          "frame deadlines (serve/wire.py).")
_register("serve_hosts", "", str,
          "Comma-separated logical host names for worker placement "
          "(e.g. 'hostA,hostB'): worker slots are distributed "
          "round-robin across hosts and the shutdown report records "
          "each worker's host.  More than one host forces the tcp "
          "transport (a Unix socket cannot span boxes).  Empty = one "
          "implicit local host.")
_register("serve_partition_grace_ms", 1500.0, float,
          "Split-brain budget: a worker that cannot reach the "
          "supervisor for this long SELF-FENCES — it revokes its own "
          "store epoch (shuffle/store.py revoke()) so a "
          "partitioned-but-alive worker can never zombie-commit, then "
          "drains and exits.  The supervisor mirrors the same window "
          "before declaring a silent connection a partition and "
          "re-placing the worker's sessions.")
_register("serve_reconnect_max", 4, int,
          "Bounded reconnect ladder: how many times a worker retries "
          "dialing the supervisor (exponential backoff, capped by "
          "serve_partition_grace_ms) after losing its CONNECTION "
          "before treating the link as a partition.  A successful "
          "re-dial re-attaches the same incarnation via its resume "
          "token — live sessions survive, nothing is re-run.")
_register("shuffle_store_dir", "", str,
          "Root of the persistent shuffle plane (shuffle/store.py): "
          "committed map outputs and drained round chunks land here "
          "(crash-safe tmp+fsync+rename commits, CRC-per-chunk "
          "manifests) so a replacement worker ADOPTS a dead worker's "
          "finished shards instead of lineage re-running them.  Empty "
          "disables the durable tier everywhere except the front door, "
          "which defaults its fleet to a store under its own fleet "
          "dir.")
_register("shuffle_store_retain", False, _parse_bool,
          "Whether FrontDoor.shutdown() leaves the shuffle store's "
          "committed entries on disk (for a later fleet to adopt) "
          "instead of reaping them with the fleet dir.  The zero-orphan "
          "shutdown report excludes the store subtree either way — "
          "retained entries are intentional, not leaks.")
_register("shuffle_store_max_attempts", 2, int,
          "Committed attempts the store keeps per (key, shard): after "
          "a successful commit, older attempts beyond this are pruned "
          "(adoption always reads the highest committed attempt, so "
          "extras only buy corruption fallback depth).  0 or negative "
          "keeps everything.")
_register("serve_data_plane", "auto", str,
          "How result BATCHES cross the supervisor<->worker boundary "
          "(serve/data_plane.py).  Control messages always stay on the "
          "framed JSON wire; this knob only routes columnar payloads: "
          "'shm' ships Arrow IPC bytes in a memfd segment passed by fd "
          "(SCM_RIGHTS, Unix transport only), 'frames' chunks the same "
          "IPC bytes into binary data frames on the existing socket "
          "(works over TCP), 'json' inlines a base64 payload in the "
          "result message (debug fallback; raises DataPlaneOverflow "
          "above the 16MB control-frame cap), and 'auto' picks shm on "
          "the unix transport and frames on tcp.")
_register("serve_segment_bytes", 1 << 20, int,
          "Chunk granularity of the zero-copy data plane: payloads are "
          "CRC32-stamped per chunk of this many bytes (torn-segment "
          "detection resolution) and the frames plane caps each binary "
          "data frame at this size so control messages interleave "
          "instead of queueing behind a monolithic payload frame.")
_register("shuffle_compress", "auto", str,
          "Pack columnar leaves before the all_to_all collective "
          "(shuffle/service.py): 'pack' bit-packs bool/dictionary-code "
          "leaves and frame-of-reference-packs int leaves into u32 lane "
          "words per round chunk (unpacked at the sanctioned reassembly "
          "seam), 'auto' packs only the cheap always-wins leaves "
          "(codes + bools), 'off' ships plain words.  Saved bytes are "
          "visible per-exchange as ShuffleMetrics.compressed_bytes_saved.")
_register("spill_codec", "off", str,
          "Codec for the spill framework's disk tier and the persistent "
          "shuffle store (mem/spill.py, shuffle/store.py): 'pack' "
          "frame-of-reference bit-packs eligible int leaves, 'block' runs "
          "a byte-wise RLE block codec over any leaf, 'off' writes raw "
          "npy.  CRCs are recorded over the STORED (compressed) bytes; "
          "a damaged frame fails loudly into the same quarantine + "
          "lineage-rebuild path as raw-leaf corruption.")
_register("result_cache", True, _parse_bool,
          "Fleet-wide result cache at the FrontDoor supervisor "
          "(serve/result_cache.py): submits that carry an input "
          "snapshot id are keyed (query signature, snapshot id, "
          "config-knob fingerprint) and repeat hits are served from the "
          "sealed Arrow IPC segment with zero compute and zero "
          "admission — bypassed entirely when off.  Submits WITHOUT a "
          "snapshot id are never cached regardless of this knob (no "
          "snapshot id, no caching, never a guess).")
_register("result_cache_bytes", 64 << 20, int,
          "Host-resident byte budget of the result cache.  Over budget, "
          "least-recently-served entries demote host->disk through the "
          "spill framework's checksummed paths before anything is "
          "dropped; 0 or negative disables the host bound (entries "
          "still honor per-tenant quotas).")
_register("result_cache_tenant_quota", 16 << 20, int,
          "Per-tenant byte quota of the result cache (host + disk "
          "tiers): inserts are charged to the submitting tenant, and a "
          "tenant over quota drops its own least-recently-served "
          "entries first — one dashboard's storm can never evict the "
          "whole fleet's cache.  0 or negative means unlimited.")
_register("serve_launcher", "local", str,
          "How worker processes come to exist (serve/launcher.py): "
          "'local' forks the worker argv on this box (today's spawn, "
          "verbatim); any other value is an agent/ssh-style command "
          "template (shlex-split, worker argv spliced at '{argv}' or "
          "appended) run per launch — the argv, resume token, and fence "
          "epoch are identical either way, so fencing and reattach work "
          "unmodified for remote workers.")
_register("serve_placement", "load", str,
          "Dispatch/placement policy of the front door (serve/"
          "elastic.py): 'load' scores workers by effective depth "
          "(placed sessions + pong queue depth), arena pressure, and "
          "stall suspicion, and spreads new incarnations across hosts "
          "fewest-live-slots-first; 'round_robin' keeps the legacy "
          "rotation.")
_register("serve_autoscale", False, _parse_bool,
          "Queue-driven autoscaling of the worker fleet (serve/"
          "elastic.py): admission-queue depth above the high-water mark "
          "for a full hold dwell spawns a worker; a slack queue with an "
          "idle worker retires one through the drain -> self-fence -> "
          "reap ladder.  Off = fixed capacity, today's behavior.")
_register("serve_autoscale_high_water", 4, int,
          "Admission-queue depth ABOVE which the autoscaler counts "
          "pressure; depth must stay above it for serve_autoscale_"
          "hold_ms before a worker is added.")
_register("serve_autoscale_low_water", 0, int,
          "Admission-queue depth AT OR BELOW which the autoscaler "
          "considers retiring an idle worker (drain ladder, never a "
          "kill).")
_register("serve_autoscale_min", 0, int,
          "Floor of the autoscaled fleet; 0 means the configured "
          "serve_workers is the floor (the fleet never shrinks below "
          "its starting size).")
_register("serve_autoscale_max", 8, int,
          "Ceiling of the autoscaled fleet: scale-ups stop here no "
          "matter the queue depth.")
_register("serve_autoscale_hold_ms", 250.0, float,
          "Debounce dwell for scale decisions: queue depth must hold "
          "above the high-water mark this long before a spawn, and "
          "consecutive scale actions are spaced by at least this much "
          "(up) / the idle dwell (down).")
_register("serve_autoscale_idle_ms", 1000.0, float,
          "How long a worker must sit with zero placed sessions and a "
          "zero pong queue depth before it is a retirement candidate.")
_register("serve_autoscale_drain_ms", 5000.0, float,
          "Drain deadline for a retiring worker: past it the drain is "
          "declared stuck and the supervisor escalates to the ordinary "
          "loss protocol (kill, fence, reap, re-place) — the "
          "drain_stuck fault kind proves this ladder.")
_register("serve_tenant_quota_bytes", 0, int,
          "Per-tenant admission byte quota at the front door: every "
          "submit is charged its est_bytes at admission, and a tenant "
          "over quota is rejected loudly with QuotaExceeded (counted "
          "in the shutdown report).  0 or negative means unlimited.")
_register("serve_tenant_quota_s", 0.0, float,
          "Per-tenant wall-clock quota at the front door: completed "
          "sessions charge their submit-to-finish seconds, and a "
          "tenant over quota has further submits rejected with "
          "QuotaExceeded.  0 or negative means unlimited.")
_register("serve_plan_warm", 4, int,
          "Warm plan-cache sharing on worker spawn: the supervisor "
          "records the last completed (kind, params) per TENANT CLASS "
          "(the tenant id up to its trailing -suffix) and ships up to "
          "this many entries to every new worker, which pre-traces "
          "them off the critical path so a fresh generation doesn't "
          "pay first-query compile for warm tenant classes.  0 "
          "disables the warm hand-off.")
_register("serve_journal", True, _parse_bool,
          "Write-ahead session journal of the front door (serve/"
          "journal.py): every session lifecycle transition and fleet "
          "fact is appended O_APPEND+fsync with a per-record CRC32 "
          "trailer to <fleet_dir>/journal.wal BEFORE the in-memory "
          "state mutates, so a supervisor crash loses no committed "
          "fact.  Off = PR-19 behavior (supervisor death loses the "
          "fleet).")
_register("serve_adopt", True, _parse_bool,
          "Restart adoption: a FrontDoor constructed with adopt_dir= "
          "pointed at a dead supervisor's fleet dir replays the "
          "journal, fences the dead generations (stamp/revoke), "
          "re-dials surviving workers over the resume-token hello, and "
          "re-places journal-known queued/replayable sessions.  Off = "
          "adopt_dir is refused loudly.")
_register("serve_orphan_grace_ms", 0.0, float,
          "Orphaned-worker self-fence grace: a worker that has heard "
          "NOTHING from its supervisor (no pings, no frames) for this "
          "long — even over a socket that still looks up — assumes the "
          "supervisor died without closing the link, and runs the "
          "self-fence ladder (revoke own epoch, sentinel, drain, exit "
          "rc=3) so a never-restarted supervisor leaks no processes "
          "and no unfenced generations.  0 disables (the reconnect "
          "ladder + serve_partition_grace_ms still cover dead-socket "
          "orphans).")


def get(key: str):
    """Resolve ``key``: programmatic override > env var > default."""
    entry = _REGISTRY.get(key)
    if entry is None:
        raise KeyError(f"unknown config key {key!r}; known: "
                       f"{sorted(_REGISTRY)}")
    with _lock:
        if key in _overrides:
            return _overrides[key]
    env = os.environ.get(_ENV_PREFIX + key.upper())
    if env is not None:
        return entry.parse(env)
    return entry.default


def set(key: str, value) -> None:  # noqa: A001 - mirrors a settings API
    if key not in _REGISTRY:
        raise KeyError(f"unknown config key {key!r}")
    with _lock:
        _overrides[key] = value


def reset(key: Optional[str] = None) -> None:
    with _lock:
        if key is None:
            _overrides.clear()
        else:
            _overrides.pop(key, None)


def describe() -> Dict[str, str]:
    """key -> one-line doc (for --help style listings)."""
    return {k: e.doc for k, e in sorted(_REGISTRY.items())}
