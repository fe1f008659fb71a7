#!/bin/bash
# Round-4 reduced-signal accuracy-vs-communication study, resumable: every
# arm checkpoints every 100 rounds and resumes, completed arms leave a .done
# sentinel, and the XLA compile cache persists across retries. Re-running
# this script after an interruption loses at most 100 rounds of one arm.
#
# Task: synthetic CIFAR at --synthetic_separation 0.025 (smooth 8x8
# prototypes, Bayes ~0.865 — data/cifar.py), 1000 non-iid clients.
# TRADEOFF_LR overrides the peak lr (default from scripts/lr_sweep_r04.sh).
set -x
cd "$(dirname "$0")/.."
mkdir -p results/logs .jax_cache
export JAX_COMPILATION_CACHE_DIR="$PWD/.jax_cache"
LR="${TRADEOFF_LR:-0.03}"  # CPU preview: ramps past ~0.04 destabilize

run_arm() {  # name, extra flags...
    local name="$1"; shift
    [ -f "results/logs/tradeoff_${name}.done" ] && {
        echo "arm $name already complete"; return 0; }
    # fresh start only when there is no checkpoint to resume (TableLogger
    # appends; a stale jsonl without a checkpoint would double-log round 0)
    [ -d "ckpt_tradeoff_${name}" ] || rm -f "results/tradeoff_${name}.jsonl"
    COMMEFFICIENT_NO_PALLAS=1 timeout 3000 python -u cv_train.py \
        --dataset cifar10 --synthetic_separation 0.025 \
        --num_clients 1000 --num_workers 16 --local_batch_size 8 \
        --num_rounds 600 --num_epochs 10 --eval_every 50 \
        --checkpoint_dir "ckpt_tradeoff_${name}" --checkpoint_every 100 \
        --resume \
        --lr_scale "$LR" --seed 42 --dtype bfloat16 \
        --log_jsonl "results/tradeoff_${name}.jsonl" "$@" 2>&1 \
        | tee -a "results/logs/tradeoff_${name}.log" | grep -v WARNING | tail -4
    local rc=${PIPESTATUS[0]}
    [ "$rc" -eq 0 ] && touch "results/logs/tradeoff_${name}.done"
    return "$rc"
}

FAIL=0
run_arm uncompressed --mode uncompressed || FAIL=1
run_arm sketch --mode sketch --k 50000 --num_cols 524288 --num_rows 5 \
    --num_blocks 4 --momentum_type virtual --error_type virtual || FAIL=1
run_arm localtopk --mode local_topk --k 50000 \
    --momentum_type none --error_type virtual || FAIL=1
# the paper's other comparators (SURVEY.md §6 row 1: "local_topk/fedavg
# degrade notably under non-iid"; true_topk is FetchSGD's idealized
# upper-bound control); best-effort — their failure must not fail the
# study (the 3 planned arms above are the deliverable)
run_arm fedavg --mode fedavg --num_local_iters 5 \
    || echo "fedavg arm failed (best-effort; study unaffected)"
run_arm truetopk --mode true_topk --k 50000 \
    --momentum_type virtual --error_type virtual \
    || echo "true_topk arm failed (best-effort; study unaffected)"

# render whatever completed — a 3-arm table beats no table after a wedge
done_files=$(for f in results/tradeoff_*.jsonl; do
    n=$(basename "$f" .jsonl); n=${n#tradeoff_}
    [ -f "results/logs/tradeoff_${n}.done" ] && echo "$f"
done)
if [ -n "$done_files" ]; then
    # render to a temp file first: a tradeoff_table.py crash must neither
    # truncate a previously-good table nor count as success
    # shellcheck disable=SC2086
    if python scripts/tradeoff_table.py $done_files \
            > results/tradeoff_table_r04.md.tmp 2> results/logs/tradeoff_table.log; then
        mv results/tradeoff_table_r04.md.tmp results/tradeoff_table_r04.md
        echo "TRADEOFF TABLE RENDERED ($(echo $done_files | wc -w) arms)"
    else
        rm -f results/tradeoff_table_r04.md.tmp
        echo "TABLE RENDER FAILED (see results/logs/tradeoff_table.log)"
        FAIL=1
    fi
fi
[ "$FAIL" -eq 0 ] && echo "TRADEOFF STUDY COMPLETE"
exit "$FAIL"
