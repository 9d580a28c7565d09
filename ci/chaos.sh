#!/usr/bin/env bash
# Chaos-campaign gate: deterministic fault sweep over
# spill/shuffle/q95/sort/streaming_scan/jni/serving/frontdoor/
# store_recovery/multihost (frontdoor = multi-process supervisor:
# executor workers SIGKILLed or wedged at every session lifecycle
# point; store_recovery = the durable shuffle plane: map outputs torn
# mid-commit, corrupted post-commit, or orphaned by a SIGKILLed worker
# must be adopted, quarantined, or lineage-rebuilt — and every revoked
# zombie generation fence-rejected; multihost = a two-host TCP fleet:
# net_drop/net_stall/net_torn landed at the transport probes on both
# sides must resolve via reconnect+reattach, and a partitioned worker
# must self-fence with zero zombie-committed shards; dataplane = the
# zero-copy columnar result path: Arrow IPC segments torn after their
# CRC stamps, announced under a dead fence generation, or orphaned by a
# worker crashed with a segment in flight must be detected by the
# supervisor's epoch-then-CRC verify and re-placed bit-identically;
# result_cache = the fleet result cache: replayed snapshot-pinned
# queries served from sealed cached segments, with cache_stale rewound
# snapshot ids rejected by the descriptor verify, cache_corrupt
# post-seal byte flips quarantined-and-recomputed bit-identically, and
# a mutated input NEVER served a stale snapshot; elastic = the
# autoscaling front door: a worker SIGKILLed mid-wave while the
# autoscaler is still adding capacity, launches failed at the launcher
# boundary, and drains wedged past the deadline must all converge to
# bit-identical digests with >=1 scale-up, >=1 retirement, and zero
# fenced commits on every drained generation; supervisor_failover = the
# SUPERVISOR itself killed mid-wave — deliberately every run and again
# wherever supervisor_crash/journal_torn rules land on the write-ahead
# journal's append seam, plus an adopting generation killed mid-replay —
# with every death resolved by a fresh FrontDoor adopting the same
# fleet dir: journal replay, dead-generation fencing, resume-token
# re-dial, re-placement, a double-restart leg that must resurrect
# nothing, and a journal-proven zero-duplicate-run audit).
#
# Runs tools/chaos.py — every faultinj.FAULT_KINDS entry fired at every
# instrumented boundary (one fault per trial, exhaustively) plus seeded
# multi-fault trials — and fails unless every faulted run is bit-identical
# to its fault-free baseline with clean post-run invariants (arenas
# drained, spill store empty, no orphaned files, attempts bounded).  On
# failure the runner dumps each failing trial's faultinj.fired_log() to
# stderr: the (name, occurrence) pairs are the exact replay recipe.
#
# Deterministic by construction (fixed --seed, occurrence-clock rules),
# so a red gate is a real regression, never flake.
set -euo pipefail
cd "$(dirname "$0")/.."

CHAOS_SEED="${CHAOS_SEED:-0}"

echo "== chaos campaign (seed=${CHAOS_SEED}) =="
SRJ_FORCE_CPU=1 python -m tools.chaos --seed "${CHAOS_SEED}" \
    --report /tmp/chaos_report.json

# the full matrix must cover the distributed-sort, streaming-scan,
# JNI-boundary and multi-tenant-serving fault domains — a silently
# shrunken scenario set would pass the campaign's own exit code, so
# assert the report
python - /tmp/chaos_report.json <<'EOF'
import json, sys
doc = json.load(open(sys.argv[1]))
for scenario in ("sort", "streaming_scan", "jni", "serving", "frontdoor",
                 "store_recovery", "multihost", "dataplane",
                 "result_cache", "elastic", "supervisor_failover"):
    trials = [t for t in doc["trials"]
              if t["label"].startswith(scenario + ":")]
    assert trials, f"chaos report has no {scenario!r} trials"
    bad = [t["label"] for t in trials if not t.get("ok")]
    assert not bad, f"{scenario!r} trials failed: {bad}"
    print(f"chaos gate: {len(trials)} {scenario} trial(s) ok")
# the pallas engine tier must stay under fire: q95 and streaming_scan
# each need trials with the engine knobs pinned (+pallas labels), whose
# digests were checked against the default-engine fault-free baseline
for scenario in ("q95", "streaming_scan"):
    pinned = [t for t in doc["trials"]
              if t["label"].startswith(scenario + ":")
              and "+pallas]" in t["label"]]
    assert pinned, f"chaos report has no pallas-pinned {scenario!r} trials"
    bad = [t["label"] for t in pinned if not t.get("ok")]
    assert not bad, f"pallas-pinned {scenario!r} trials failed: {bad}"
    print(f"chaos gate: {len(pinned)} pallas-pinned {scenario} trial(s) ok")
EOF
echo "== chaos campaign OK (report: /tmp/chaos_report.json) =="
