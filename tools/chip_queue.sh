#!/bin/bash
# A LIST of measurement legs (round 5), not the way onto the chip.  Every
# leg starts a child process that wants the chip, one after another, and
# failures don't stop the rest; that suited a host with a local backend.
# The chip is now reached through the builder's tool, one command per
# call, with `python chip_smoke.py` as the first command: fold the legs
# you need into one such command instead of running this file there.
#
# CHIP_QUEUE_DRY_RUN=1 exercises the queue's WIRING on the CPU backend
# without burning chip time: heavy measurement legs are printed and
# skipped, while the artifact-producing legs (kernel-variant sweep,
# train-schedule winner) run tiny CPU workloads end-to-end and their
# output contracts are validated — this is what tests/test_tools.py
# runs in tier-1, so a flag/json drift in the queue fails BEFORE a
# chip window is spent discovering it.
set -u
cd "$(dirname "$0")/.."
DRY=${CHIP_QUEUE_DRY_RUN:-0}
if [ "$DRY" = "1" ]; then
    export JAX_PLATFORMS=cpu
fi

# run <timeout_s> <cmd...> — dry mode prints the command and skips it
run() {
    local t=$1; shift
    if [ "$DRY" = "1" ]; then
        echo "[dry-run] skip (${t}s): $*"
        return 0
    fi
    timeout "$t" "$@"
}

mkdir -p chiprun_out
LOG=${1:-chiprun_out/chip_queue_results.txt}
# every scratch file of a run lives in a directory of its own, removed on
# exit: two runs on one machine (tier-1 runs the dry mode) share nothing
SCRATCH=$(mktemp -d "${TMPDIR:-chiprun_out}/chip_queue.XXXXXX")
export SCRATCH
trap 'rm -rf "$SCRATCH"' EXIT
{
echo "== chip queue $(date -u +%FT%TZ) =="

echo "-- 1. headline bench, stock config (warm cache expected)"
# the sweep baseline is stock BatchNorm, spelled out whatever the default
run 580 python bench.py --chunks 3 --ghost-bn 0 --passes '' \
    | tee "$SCRATCH/bench_stock.txt"

echo "-- 2. per-kernel BN DMA-efficiency microbench (round 4)"
run 1200 python tools/bn_kernel_bench.py --residual \
    --out chiprun_out/bn_kernel_results.jsonl

echo "-- 2b. round-20 kernel-variant sweep (lane-fold stem + spatial-tiled"
echo "       exits vs whole-L vs stock XLA, JSON artifact)"
if [ "$DRY" = "1" ]; then
    timeout 300 python tools/bn_kernel_bench.py --variants --dry-run \
        --format json --out "$SCRATCH/bn_kernel_variants.json" \
        && python -c "
import json
rows = [json.loads(l) for l in open('$SCRATCH/bn_kernel_variants.json')]
assert rows and all('variant' in r and 'stock_xla_ms' in r for r in rows), rows
print('kernel-variant sweep contract ok: %d rows' % len(rows))"
else
    run 1800 python tools/bn_kernel_bench.py --variants --residual \
        --format json --out chiprun_out/bn_kernel_variants.json
fi

echo "-- 3. perf variant sweep"
run 900 python bench.py --chunks 3 --s2d-stem --ghost-bn 0 \
    --passes '' | tee "$SCRATCH/bench_s2d.txt"
run 900 python bench.py --chunks 3 --ghost-bn 16 --passes '' \
    | tee "$SCRATCH/bench_gbn.txt"
run 1200 python bench.py --chunks 3 --s2d-stem --ghost-bn 16 \
    --passes '' | tee "$SCRATCH/bench_both.txt"
run 1200 python bench.py --chunks 3 --ghost-bn 16 \
    --passes space_to_depth,maxpool_bwd_mask \
    | tee "$SCRATCH/bench_composed.txt"

echo "-- 4. name the measured winner (bench.py reads no config file: a"
echo "      winner becomes the default by editing DEFAULT_* in bench.py)"
python - <<'EOF'
import json
import os

def best(name, **flags):
    try:
        v = max((json.loads(l).get("value", 0.0)
                 for l in open(os.path.join(os.environ["SCRATCH"], name))
                 if l.startswith('{"metric"')), default=0.0)
    except OSError:
        v = 0.0
    return v, flags

runs = [
    best("bench_stock.txt", ghost_bn=0, passes=""),
    best("bench_s2d.txt", s2d_stem=True, ghost_bn=0, passes=""),
    best("bench_gbn.txt", ghost_bn=16, passes=""),
    best("bench_both.txt", s2d_stem=True, ghost_bn=16, passes=""),
    best("bench_composed.txt",
         ghost_bn=16, passes="space_to_depth,maxpool_bwd_mask"),
]
win_v, win_flags = max(runs, key=lambda r: r[0])
print("stock %.1f; winner %.1f img/s %s" % (runs[0][0], win_v, win_flags))
EOF

echo "-- 4b. graftsched train-schedule winner vs the hand-built default"
# zero-compile per-site schedule search over the byte-diet passes; the
# winner JSON is the exact artifact bench.py --schedule-config consumes
# (knobs.schedule canonical dict + knobs.schedule_hash stamp)
if [ "$DRY" = "1" ]; then
    timeout 300 python tools/autotune.py --target train-schedule \
        --model conv-bn --passes space_to_depth,maxpool_bwd_mask \
        --batches 8 --budget-compiles 0 \
        --winner-out "$SCRATCH/sched_winner.json" \
        && python -c "
import json
from incubator_mxnet_tpu.analysis.passes import PassSchedule
w = json.load(open('$SCRATCH/sched_winner.json'))
h = PassSchedule.from_dict(w['knobs']['schedule']).hash()
assert h == w['knobs']['schedule_hash'], (h, w['knobs'])
print('schedule-winner contract ok: hash', h)"
else
    run 900 python tools/autotune.py --target train-schedule \
        --model resnet50 --passes space_to_depth,maxpool_bwd_mask \
        --batches 32 --budget-compiles 0 \
        --winner-out "$SCRATCH/sched_winner.json"
    run 1200 python bench.py --chunks 3 \
        --schedule-config "$SCRATCH/sched_winner.json" \
        | tee "$SCRATCH/bench_schedwin.txt"
    python - <<'EOF'
import json
import os

def best(name):
    try:
        return max((json.loads(l).get("value", 0.0)
                    for l in open(os.path.join(os.environ["SCRATCH"], name))
                    if l.startswith('{"metric"')), default=0.0)
    except OSError:
        return 0.0

hand = best("bench_composed.txt")
win = best("bench_schedwin.txt")
if hand and win:
    print("schedule winner %.1f img/s vs hand-built default %.1f img/s "
          "(%+.1f%%)" % (win, hand, 100.0 * (win - hand) / hand))
else:
    print("schedule-winner delta unavailable (hand=%.1f winner=%.1f)"
          % (hand, win))
EOF
fi

echo "-- 5. headline, default flags"
run 1200 python bench.py --chunks 3

echo "-- 6. inference (bf16 batch-128 vs the V100 fp16 BASELINE row)"
run 580 python bench.py --mode infer

echo "-- 6b. int8 inference through the wire"
run 580 python bench.py --mode infer-int8

echo "-- 7. TPU consistency gate (375-op sweep + int8-wire resnet)"
run 2700 python -m pytest tests/test_tpu_consistency.py -m tpu -q

echo "-- 8. recordio-fed training (host-core bound on 1-vCPU driver)"
run 1200 python bench.py --data recordio --record-format .npy --chunks 3

echo "-- 9. attention (XLA default headline + Pallas long-seq crossover)"
run 900 python bench.py --mode attention

echo "-- 10. per-op TPU latency sweep (hot ResNet-50 ops + default set)"
run 580 python benchmark/opperf.py --resnet \
    --json chiprun_out/opperf_resnet.json
run 580 python benchmark/opperf.py --json chiprun_out/opperf_default.json

echo "-- 11. IO thread scaling (flat on a 1-core driver; per-core cost is the tracked number)"
run 420 python tools/io_thread_scaling.py --images 256

echo "== done $(date -u +%FT%TZ) =="
} 2>&1 | tee "$LOG"
