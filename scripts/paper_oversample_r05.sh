#!/bin/bash
# The oversample validation arm: identical to the phase-G sketch arm
# (seed 42) with --topk_impl oversample — approx 4k-candidate preselect +
# exact refine (csvec.topk_abs). Context: the seed-42 approx arms
# suggested a ~3-point recall cost, but the seed-43 replication inverted
# the pairing (exact-vs-approx@0.99 is within seed variance —
# results/README.md). Oversample is near-exact BY CONSTRUCTION, so this
# arm just confirms it lands in the exact/approx band; its value is
# making the selection-quality question moot at PartialReduce speed.
set -x
cd "$(dirname "$0")/.."
. scripts/tradeoff_arms.sh
mkdir -p results/logs .jax_cache
export JAX_COMPILATION_CACHE_DIR="$PWD/.jax_cache"
LR="${TRADEOFF_LR:-0.03}"

name=sketchover
[ -f "results/logs/paper_r05_${name}.done" ] && {
    echo "arm $name already complete"; exit 0; }
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
    --lr_scale "$LR" --seed 42 --dtype bfloat16 \
    --log_jsonl "results/paper_${name}.jsonl" \
    $(arm_flags sketch) --topk_impl oversample 2>&1 \
    | tee -a "results/logs/paper_${name}.log" | grep -v WARNING | tail -4
rc=${PIPESTATUS[0]}
[ "$rc" -eq 0 ] && touch "results/logs/paper_r05_${name}.done"
exit "$rc"
