#!/bin/bash
# Evidence run for --mc_hard_negatives: tiny GPT-2,
# 4 candidates, hard (same-pool, other-persona) distractors. The easy
# corpus saturates mc_acc at 1.0 within rounds (token-identity shortcut);
# here chance is 0.25 and the only signal is matching reply words against
# the persona sentence, so a non-trivial curve is mc_acc leaving chance
# WITHOUT pinning to 1.0. Checkpoint/resume; CPU-mesh; ~40-60 min on the
# 1-core box. Renders results/personachat_mc_hard.jsonl.
set -x
cd "$(dirname "$0")/.."
mkdir -p results/logs .jax_cache
[ -f results/logs/mc_hard_r05.done ] && { echo done already; exit 0; }
[ -d ckpt_mc_hard ] || rm -f results/personachat_mc_hard.jsonl
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
JAX_COMPILATION_CACHE_DIR="$PWD/.jax_cache" COMMEFFICIENT_NO_PALLAS=1 \
nice -n 10 timeout 7200 python -u gpt2_train.py \
    --model_size tiny --seq_len 128 --num_clients 64 --num_workers 8 \
    --local_batch_size 2 --num_rounds 400 --num_epochs 50 --pivot_epoch 10 --eval_every 40 \
    --mc_coef 8 --num_candidates 4 --mc_hard_negatives \
    --mode uncompressed \
    --momentum_type virtual --error_type none \
    --checkpoint_dir ckpt_mc_hard --checkpoint_every 80 --resume \
    --lr_scale 0.04 --seed 7 \
    --log_jsonl results/personachat_mc_hard.jsonl \
    >> results/logs/mc_hard_r05.log 2>&1
rc=$?
[ "$rc" -eq 0 ] && touch results/logs/mc_hard_r05.done
exit "$rc"
