#!/bin/bash
# Second point on the approx-top-k accuracy-vs-recall dial at paper scale:
# identical to scripts/paper_approx_r05.sh (= phase-G sketch arm + approx)
# except --topk_recall 0.99. The recall-0.95 arm measured best 0.644 /
# final 0.623 vs exact's 0.682 / 0.6545 — if 0.99 closes that gap while
# keeping most of the approx speed win (exact top-k is 433 ms at d=124M,
# 13 ms at flagship d; approx 4.3 ms), it becomes the recommended TPU
# configuration; if not, exact stays the accuracy-faithful default.
set -x
cd "$(dirname "$0")/.."
. scripts/tradeoff_arms.sh
mkdir -p results/logs .jax_cache
export JAX_COMPILATION_CACHE_DIR="$PWD/.jax_cache"
LR="${TRADEOFF_LR:-0.03}"

name=sketchapprox99
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
    $(arm_flags sketch) --topk_impl approx --topk_recall 0.99 2>&1 \
    | tee -a "results/logs/paper_${name}.log" | grep -v WARNING | tail -4
rc=${PIPESTATUS[0]}
[ "$rc" -eq 0 ] && touch "results/logs/paper_r05_${name}.done"
exit "$rc"
