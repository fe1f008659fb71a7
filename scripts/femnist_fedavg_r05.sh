#!/bin/bash
# BASELINE row 2's missing comparator: a FedAvg arm on the FEMNIST-family
# workload, same schedule/seed/cohort as the existing smoke arms
# (results/README.md "FEMNIST reduced-dims study"), so the claim
# "FetchSGD ~ FedAvg-level accuracy at lower total communication" gets a
# measured row instead of a paper citation. FedAvg sends dense weights
# down + deltas up but takes 5 local iterations per round, so its
# accuracy-per-round is high and its comm-per-accuracy is the interesting
# column. Horizon is 32 rounds, not the sketch arms' 96: the uncompressed
# control saturates (1.000) by round 48 and fedavg sees 5x the data per
# round, so the equal-accuracy crossing lands well before 32 — and on the
# round-5 host (~3-4x slower than round 4's) 96
# fedavg rounds would take ~7h. Checkpoint/resume every 8.
set -x
cd "$(dirname "$0")/.."
mkdir -p results/logs .jax_cache
[ -f results/logs/femnist_fedavg_r05.done ] && { echo done already; exit 0; }
[ -d ckpt_femnist_fedavg ] || rm -f results/femnist_smoke_fedavg.jsonl
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
JAX_COMPILATION_CACHE_DIR="$PWD/.jax_cache" COMMEFFICIENT_NO_PALLAS=1 \
nice -n 10 timeout 14400 python -u cv_train.py \
    --dataset femnist --mode fedavg --num_local_iters 5 \
    --momentum_type virtual --momentum 0.9 --error_type none \
    --num_clients 200 --num_workers 8 --num_rounds 32 --num_epochs 4 \
    --pivot_epoch 1 --eval_every 8 --lr_scale 0.03 --seed 42 \
    --checkpoint_dir ckpt_femnist_fedavg --checkpoint_every 8 --resume \
    --log_jsonl results/femnist_smoke_fedavg.jsonl \
    >> results/logs/femnist_fedavg_r05.log 2>&1
rc=$?
if [ "$rc" -eq 0 ]; then
    touch results/logs/femnist_fedavg_r05.done
    python scripts/tradeoff_table.py results/femnist_smoke_*.jsonl \
        > results/femnist_table_r05.md.tmp \
        && mv results/femnist_table_r05.md.tmp results/femnist_table_r05.md
fi
exit "$rc"
