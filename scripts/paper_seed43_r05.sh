#!/bin/bash
# Seed replication for the approx-top-k accuracy study: the session-3
# three-arm comparison (exact 0.682 > approx@0.99 0.652 > approx@0.95
# 0.644 best test acc, seed 42) rests on one seed per arm. This runs the
# EXACT and approx@0.99 arms at seed 43 — if the exact > approx ordering
# and ~3-point gap replicate, the claim is seed-robust; if they invert,
# the session-3 conclusion gets downgraded to seed noise in the docs.
set -x
cd "$(dirname "$0")/.."
. scripts/tradeoff_arms.sh
mkdir -p results/logs .jax_cache
export JAX_COMPILATION_CACHE_DIR="$PWD/.jax_cache"
LR="${TRADEOFF_LR:-0.03}"

run_arm() {  # name, extra flags...
    local name="$1"; shift
    [ -f "results/logs/paper_r05_${name}.done" ] && {
        echo "arm $name already complete"; return 0; }
    [ -d "ckpt_paper_${name}" ] || rm -f "results/paper_${name}.jsonl"
    # shellcheck disable=SC2046
    COMMEFFICIENT_NO_PALLAS=1 timeout 4200 python -u cv_train.py \
        --dataset cifar10 --synthetic_separation 0.025 \
        --synthetic_train 50000 \
        --num_clients 10000 --num_workers 100 --local_batch_size 5 \
        --num_epochs 24 --eval_every 100 \
        --client_chunk 25 \
        --checkpoint_dir "ckpt_paper_${name}" --checkpoint_every 200 \
        --resume \
        --lr_scale "$LR" --seed 43 --dtype bfloat16 \
        --log_jsonl "results/paper_${name}.jsonl" \
        $(arm_flags sketch) "$@" 2>&1 \
        | tee -a "results/logs/paper_${name}.log" | grep -v WARNING | tail -4
    local rc=${PIPESTATUS[0]}
    [ "$rc" -eq 0 ] && touch "results/logs/paper_r05_${name}.done"
    return "$rc"
}

FAIL=0
run_arm sketch_s43 || FAIL=1
run_arm sketchapprox99_s43 --topk_impl approx --topk_recall 0.99 || FAIL=1
[ "$FAIL" -eq 0 ] && echo "SEED-43 REPLICATION COMPLETE"
exit "$FAIL"
