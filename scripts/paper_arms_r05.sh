#!/bin/bash
# Round-5 accuracy-vs-communication frontier AT PAPER SCALE (BASELINE
# config #2): 10,000 sort-by-label clients, W=100 (~1% participation),
# 24 epochs = 2,400 rounds, 50k synthetic images (5/client), the exact
# flag set of the round-5 paper-scale sketch run — which already ran the SKETCH
# arm (results/paper_scale_r05.jsonl, test 0.6545). This script runs the
# other four arms so the frontier table compares modes at the
# reference's own cohort scale, where the W=16 study's two failure
# modes (lr-peak instability at 0.03, memorization at 0.015 —
# results/tradeoff_table_r05.md / tradeoff_table2_r05.md) are absent:
# the G run was stable AND generalized at this exact schedule.
# Wedge-resilient like the other studies: checkpoint/resume + sentinels.
set -x
cd "$(dirname "$0")/.."
. scripts/tradeoff_arms.sh
mkdir -p results/logs .jax_cache
export JAX_COMPILATION_CACHE_DIR="$PWD/.jax_cache"
LR="${TRADEOFF_LR:-0.03}"  # phase G's pinned lr: stable at W=100

run_arm() {  # name, extra flags...
    local name="$1"; shift
    [ -f "results/logs/paper_r05_${name}.done" ] && {
        echo "arm $name already complete"; return 0; }
    [ -d "ckpt_paper_${name}" ] || rm -f "results/paper_${name}.jsonl"
    COMMEFFICIENT_NO_PALLAS=1 timeout 4200 python -u cv_train.py \
        --dataset cifar10 --synthetic_separation 0.025 \
        --synthetic_train 50000 \
        --num_clients 10000 --num_workers 100 --local_batch_size 5 \
        --num_epochs 24 --eval_every 100 --rounds_per_dispatch 50 \
        --client_chunk 25 \
        --checkpoint_dir "ckpt_paper_${name}" --checkpoint_every 200 \
        --resume \
        --lr_scale "$LR" --seed 42 --dtype bfloat16 \
        --log_jsonl "results/paper_${name}.jsonl" "$@" 2>&1 \
        | tee -a "results/logs/paper_${name}.log" | grep -v WARNING | tail -4
    local rc=${PIPESTATUS[0]}
    [ "$rc" -eq 0 ] && touch "results/logs/paper_r05_${name}.done"
    return "$rc"
}

FAIL=0
# sketch is phase G's artifact; run the comparators (fedavg last: its
# per-client state forces per-round dispatch, the slowest arm by far)
for arm in uncompressed localtopk truetopk fedavg; do
    # shellcheck disable=SC2046
    run_arm "$arm" $(arm_flags "$arm") || FAIL=1
done

# render: phase G's sketch curve joins the four arms run here (copied so
# tradeoff_table.py's name-from-last-underscore-token yields "sketch")
cp results/paper_scale_r05.jsonl results/paper_sketch.jsonl
files="results/paper_sketch.jsonl"
for n in uncompressed localtopk truetopk fedavg; do
    [ -f "results/logs/paper_r05_${n}.done" ] && files="$files results/paper_${n}.jsonl"
done
# shellcheck disable=SC2086
if python scripts/tradeoff_table.py $files \
        > results/paper_table_r05.md.tmp 2> results/logs/paper_table.log; then
    mv results/paper_table_r05.md.tmp results/paper_table_r05.md
    echo "PAPER-SCALE TABLE RENDERED"
else
    rm -f results/paper_table_r05.md.tmp
    echo "PAPER TABLE RENDER FAILED (see results/logs/paper_table.log)"
    FAIL=1
fi
[ "$FAIL" -eq 0 ] && echo "PAPER-SCALE STUDY COMPLETE"
exit "$FAIL"
