#!/bin/bash
# LR sweep for the reduced-signal tradeoff study (sep 0.025, smooth
# prototypes): at lr_scale 0.3 the task diverges (train loss 3-5, above the
# ln10 floor), so find the stable lr with short uncompressed runs before
# spending chip time on the 3-arm study. The persistent XLA compile cache
# makes retries cheap.
set -x
cd "$(dirname "$0")/.."
mkdir -p results/logs .jax_cache
export JAX_COMPILATION_CACHE_DIR="$PWD/.jax_cache"

# Grid revised DOWN after the CPU preview (results/cpu_tradeoff_uncompressed
# .jsonl): train loss left the ln10 floor upward once the ramp passed
# ~0.04, so 0.08/0.15 are near-certain divergence — probe {0.01,0.03,0.06}.
# --pivot_epoch 2.5 completes a full triangle within the 5-epoch arm
# (default pivot 5 == num_epochs would make it a pure ramp, ending every
# arm at its least stable lr).
# clear the WHOLE family, not just the current grid's files: pick_lr globs
# results/lr_sweep_*.jsonl, and stale old-grid arms (0.08/0.15, pure-ramp
# schedule) must not be candidates against the revised triangle arms
rm -f results/lr_sweep_*.jsonl
for lr in 0.01 0.03 0.06; do
    COMMEFFICIENT_NO_PALLAS=1 timeout 900 python -u cv_train.py \
        --dataset cifar10 --synthetic_separation 0.025 \
        --num_clients 1000 --num_workers 16 --local_batch_size 8 \
        --num_rounds 300 --num_epochs 5 --pivot_epoch 2.5 --eval_every 50 \
        --rounds_per_dispatch 50 \
        --lr_scale "$lr" --seed 42 --dtype bfloat16 \
        --mode uncompressed \
        --log_jsonl "results/lr_sweep_${lr}.jsonl" 2>&1 \
        | tee "results/logs/lr_sweep_${lr}.log" | grep -v WARNING | tail -3 \
        || echo "lr=$lr arm FAILED/timed out"
done
echo "sweep done"
