#!/bin/bash
# BASELINE row 2 at the PAPER'S cohort scale: FEMNIST-family workload with
# 3,550 writer clients (LEAF's natural count; synthetic fallback — no LEAF
# files in this zero-egress container), W=36 (~1% participation), 24
# epochs. The round-3/5 FEMNIST evidence was 200-client smoke scale; this
# is the cohort-scale counterpart of scripts/paper_arms_r05.sh for the
# CIFAR config. Sketch dims c=2^19 (12.6x table compression for d=6.60M,
# and Pallas-eligible: c % 1024 == 0, so the kernels ride the training
# loop on-chip). fedavg last (5x client compute per round).
set -x
cd "$(dirname "$0")/.."
mkdir -p results/logs .jax_cache
export JAX_COMPILATION_CACHE_DIR="$PWD/.jax_cache"
LR="${TRADEOFF_LR:-0.03}"

run_arm() {  # name, extra flags...
    local name="$1"; shift
    [ -f "results/logs/fpaper_r05_${name}.done" ] && {
        echo "arm $name already complete"; return 0; }
    [ -d "ckpt_fpaper_${name}" ] || rm -f "results/fpaper_${name}.jsonl"
    timeout 4200 python -u cv_train.py \
        --dataset femnist \
        --num_clients 3550 --num_workers 36 --local_batch_size 20 \
        --num_epochs 24 --eval_every 100 \
        --checkpoint_dir "ckpt_fpaper_${name}" --checkpoint_every 200 \
        --resume \
        --pivot_epoch 4 --lr_scale "$LR" --seed 42 --dtype bfloat16 \
        --log_jsonl "results/fpaper_${name}.jsonl" "$@" 2>&1 \
        | tee -a "results/logs/fpaper_${name}.log" | grep -v WARNING | tail -3
    local rc=${PIPESTATUS[0]}
    [ "$rc" -eq 0 ] && touch "results/logs/fpaper_r05_${name}.done"
    return "$rc"
}

FAIL=0
run_arm uncompressed --mode uncompressed \
    --momentum_type virtual --momentum 0.9 --error_type none || FAIL=1
run_arm sketch --mode sketch --k 20000 --num_cols 524288 --num_rows 5 \
    --num_blocks 4 --momentum_type virtual --error_type virtual || FAIL=1
run_arm fedavg --mode fedavg --num_local_iters 5 \
    --momentum_type virtual --momentum 0.9 --error_type none || FAIL=1

if python scripts/tradeoff_table.py results/fpaper_*.jsonl \
        > results/fpaper_table_r05.md.tmp 2> results/logs/fpaper_table.log; then
    mv results/fpaper_table_r05.md.tmp results/fpaper_table_r05.md
    echo "FEMNIST PAPER-SCALE TABLE RENDERED"
else
    rm -f results/fpaper_table_r05.md.tmp
    FAIL=1
fi
[ "$FAIL" -eq 0 ] && echo "FEMNIST PAPER-SCALE STUDY COMPLETE"
exit "$FAIL"
