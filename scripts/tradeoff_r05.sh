#!/bin/bash
# Round-5 converged accuracy-vs-communication study: the
# FetchSGD headline claim, reproduced end-to-end on the FIXED smooth-
# prototype task (data/cifar.py::_prototypes; separation 0.025, Bayes
# 0.8653). Five first-class arms x 600 rounds: uncompressed, sketch
# (~12.5x table compression), local_topk, fedavg, true_topk (idealized
# upper-bound control). Resumable: every arm checkpoints every 100
# rounds and resumes, completed arms leave .done sentinels, the XLA compile
# cache persists — a re-run after an interruption loses <=100 rounds of one
# arm. TRADEOFF_LR overrides the peak lr (default from scripts/pick_lr.py
# over the lr_sweep_r04.sh grid).
set -x
cd "$(dirname "$0")/.."
. scripts/tradeoff_arms.sh
mkdir -p results/logs .jax_cache
export JAX_COMPILATION_CACHE_DIR="$PWD/.jax_cache"
LR="${TRADEOFF_LR:-0.03}"  # CPU preview: ramps past ~0.04 destabilize

run_arm() {  # name, extra flags...
    local name="$1"; shift
    [ -f "results/logs/tradeoff_r05_${name}.done" ] && {
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
    [ "$rc" -eq 0 ] && touch "results/logs/tradeoff_r05_${name}.done"
    return "$rc"
}

FAIL=0
for arm in uncompressed sketch localtopk fedavg truetopk; do
    # shellcheck disable=SC2046
    run_arm "$arm" $(arm_flags "$arm") || FAIL=1
done

# render whatever completed — a partial table beats no table after a wedge
done_files=$(for f in results/tradeoff_*.jsonl; do
    n=$(basename "$f" .jsonl); n=${n#tradeoff_}
    [ -f "results/logs/tradeoff_r05_${n}.done" ] && echo "$f"
done)
if [ -n "$done_files" ]; then
    # render to a temp file first: a tradeoff_table.py crash must neither
    # truncate a previously-good table nor count as success
    # shellcheck disable=SC2086
    if python scripts/tradeoff_table.py $done_files \
            > results/tradeoff_table_r05.md.tmp 2> results/logs/tradeoff_table.log; then
        mv results/tradeoff_table_r05.md.tmp results/tradeoff_table_r05.md
        echo "TRADEOFF TABLE RENDERED ($(echo $done_files | wc -w) arms)"
    else
        rm -f results/tradeoff_table_r05.md.tmp
        echo "TABLE RENDER FAILED (see results/logs/tradeoff_table.log)"
        FAIL=1
    fi
fi
[ "$FAIL" -eq 0 ] && echo "TRADEOFF STUDY COMPLETE"
exit "$FAIL"
