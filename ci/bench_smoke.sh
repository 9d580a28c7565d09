#!/usr/bin/env bash
# CPU smoke of the benchmark harness (chip_smoke.py is the check on the chip).
set -euo pipefail
cd "$(dirname "$0")/.."
# chip_smoke.py is for the chip: a CPU run of it must fail and say what it found
if err=$(JAX_PLATFORMS=cpu python chip_smoke.py 2>&1 >/dev/null); then
  echo "chip_smoke.py passed on the CPU" >&2; exit 1
fi
grep -q "platform 'cpu'" <<<"$err"
BENCH_FORCE_CPU=1 BENCH_N_ROWS=65536 BENCH_REPS=2 python bench.py \
  | tee /tmp/bench_smoke_q6.out
# plan-IR scenario: q6/q95 plus the IR-only q9 lowered by the whole-plan
# compiler; each row's note carries the plan-cache outcome + the adaptive
# decisions (cache must be a hit — zero retraces on repeated shapes)
BENCH_FORCE_CPU=1 BENCH_PLAN_ROWS=65536 BENCH_REPS=2 python bench.py --plan \
  | tee /tmp/bench_smoke_plan.out
# streaming scan scenario: morsel-driven scan→shuffle on an over-arena
# Parquet input; the note must show >=2 rounds draining while later
# morsels still decode (scan_main fails the run otherwise)
BENCH_FORCE_CPU=1 BENCH_SCAN_ROWS=32768 python bench.py --scan \
  | tee /tmp/bench_smoke_scan.out
# serving scenario: >=4 concurrent tenant streams through the
# ServeRuntime; the wave must be bit-identical to the solo pass and the
# note carries solo vs concurrent p50/p99 (the serve_p99_floor ratchet).
# The same run then replays the query set through the multi-process
# FrontDoor (>=2 supervised executor workers) — note.mp_bit_identical
# must be true with mp_workers >= 2 or the gate fails — and once more
# over the multi-host TCP transport (two workers on two named hosts) —
# note.tcp_bit_identical must be true with tcp_workers >= 2
BENCH_FORCE_CPU=1 BENCH_SERVE_ROWS=16384 python bench.py --serve \
  | tee /tmp/bench_smoke_serve.out
# pallas device-kernel A/B rows: each asserts its pallas kernel
# bit-identical to the lax twin IN-ROW before measuring (interpret mode
# on CPU); BENCH_MICRO_ONLY runs just the requested entry per child
: > /tmp/bench_smoke_pallas.out
for row in slot_build_pallas slot_probe_pallas partition_scatter_pallas; do
  BENCH_FORCE_CPU=1 BENCH_MICRO_ONLY="$row" python bench.py --micro \
    | tee -a /tmp/bench_smoke_pallas.out
done
# multidevice scenario: the fused pallas scatter driving a real ICI
# shuffle over 8 (virtual) devices, the streaming scan on the same
# engine, and q95 with both relational engine knobs pinned to pallas —
# every row parity-asserted before its rate is reported
BENCH_FORCE_CPU=1 python bench.py --multidevice \
  | tee /tmp/bench_smoke_multidevice.out
# compressed-execution scenario: the encoded q95-shape exchange with
# shuffle_compress=pack vs off (bit-identical rows asserted in-child;
# vs_baseline = wire-byte ratio, floor shuffle_compress_floor) plus the
# spill-codec frame round-trip micro row
BENCH_FORCE_CPU=1 BENCH_COMPRESS_ROWS=32768 python bench.py --compress \
  | tee /tmp/bench_smoke_compress.out
# selectivity sweep: a q6-style filter at 1%/10%/90% selectivity over a
# sorted FoR-packed column — zone-map morsel skipping AND footer
# row-group pruning both counted per point, pruned streams asserted
# bit-identical in-child; the 1% skip fraction rides
# blocks_skipped_floor (only-shrinks)
BENCH_FORCE_CPU=1 BENCH_SELECTIVITY_ROWS=32768 python bench.py --selectivity \
  | tee /tmp/bench_smoke_selectivity.out
# result-cache scenario: a zipf-skewed q6/q95/q9-shaped replay trace
# through a 2-worker FrontDoor with the fleet result cache on — repeats
# served from sealed cached Arrow segments bit-identically with zero
# compute; note.hit_rate must clear 0.5 and vs_baseline (p99_miss /
# p99_hit) rides result_cache_floor
BENCH_FORCE_CPU=1 python bench.py --cache \
  | tee /tmp/bench_smoke_cache.out
# elastic-fleet scenario: the skewed-tenant trace under placement=load
# vs round_robin (vs_baseline = p99_rr / p99_load over the light
# tenants, floor placement_p99_floor) plus the queue-driven autoscale
# phase — note.scaled_up/scaled_down must both be >= 1 with the
# scale_up_ms/scale_down_ms reaction latencies recorded
BENCH_FORCE_CPU=1 python bench.py --elastic \
  | tee /tmp/bench_smoke_elastic.out
# the q95 lines must be self-explaining (per-stage note + engines; cache +
# decisions on the IR rows) and their vs_baseline must not regress below
# the recorded floors — ratchets in the same only-shrinks spirit as
# graftlint's baseline (ci/q95_floor.json); a missing q9 IR row,
# streaming-scan row, serving row, pallas A/B row, multidevice row,
# result-cache row, or elastic row fails too
python ci/check_q95_line.py /tmp/bench_smoke_q6.out \
  /tmp/bench_smoke_plan.out /tmp/bench_smoke_scan.out \
  /tmp/bench_smoke_serve.out /tmp/bench_smoke_pallas.out \
  /tmp/bench_smoke_multidevice.out /tmp/bench_smoke_compress.out \
  /tmp/bench_smoke_selectivity.out \
  /tmp/bench_smoke_cache.out /tmp/bench_smoke_elastic.out
# spill scenario: device arena capped below q6's working set; the emitted
# line carries spill-bytes counters so BENCH_*.json tracks spill overhead
BENCH_FORCE_CPU=1 BENCH_SPILL_ROWS=65536 python bench.py --spill
# shuffle scenario: skewed multi-round exchange through the out-of-core
# ShuffleService under a capped arena (rounds/skew/spill counters)
BENCH_FORCE_CPU=1 BENCH_SHUFFLE_ROWS=8192 python bench.py --shuffle
