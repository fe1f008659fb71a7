#!/bin/bash
# Approx-vs-exact top-k convergence validation AT PAPER SCALE: the exact
# flag set of the phase-G sketch arm (scripts/paper_arms_r05.sh) with the
# ONLY delta being --topk_impl approx (single approx_max_k PartialReduce
# instead of exact lax.top_k over d). Matched seed (42), schedule, dims.
# If the final/best test accuracy matches the exact arm
# (results/paper_sketch.jsonl: final 0.6545 / best 0.682) within noise,
# approx becomes the documented TPU default for the flagship bench path —
# it is the TPU-idiomatic selection and is 1,418 vs 1,094 updates/s/chip
# at W=64 (BENCH_flagship_approx_r05.json vs BENCH_flagship_r05.json).
set -x
cd "$(dirname "$0")/.."
. scripts/tradeoff_arms.sh
mkdir -p results/logs .jax_cache
export JAX_COMPILATION_CACHE_DIR="$PWD/.jax_cache"
LR="${TRADEOFF_LR:-0.03}"

name=sketchapprox
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
    $(arm_flags sketch) --topk_impl approx 2>&1 \
    | tee -a "results/logs/paper_${name}.log" | grep -v WARNING | tail -4
rc=${PIPESTATUS[0]}
[ "$rc" -eq 0 ] && touch "results/logs/paper_r05_${name}.done"
exit "$rc"
