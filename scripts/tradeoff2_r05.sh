#!/bin/bash
# Round-5 tradeoff study, pass 2 — the lr lesson from pass 1 applied.
#
# Pass 1 (scripts/tradeoff_r05.sh, results/tradeoff_table_r05.md) ran the
# full triangular schedule at peak 0.03 and showed every W=16 arm DIP
# through the lr peak (rounds 200-300) and only climb once lr decayed
# below ~0.02 — the 600-round budget was spent recovering, so the final
# ordering measured recovery speed, not the steady-state accuracy-vs-
# communication frontier. (The W=100 paper-scale run at the same peak was
# stable: more clients per round average away the variance. The
# instability is a W=16 property, not a mode property.)
#
# Pass 2: peak lr 0.015 (fully inside pass 1's observed productive range),
# 900 rounds / 15 epochs so the decay phase is as long as pass 1's whole
# run. Fresh checkpoint/jsonl namespace (tradeoff2_*) — pass 1's curves
# stay banked as the instability evidence. Same arms, same task, same
# seed; arm hyperparameters from the shared scripts/tradeoff_arms.sh.
set -x
cd "$(dirname "$0")/.."
. scripts/tradeoff_arms.sh
mkdir -p results/logs .jax_cache
export JAX_COMPILATION_CACHE_DIR="$PWD/.jax_cache"
LR="${TRADEOFF2_LR:-0.015}"
ROUNDS="${TRADEOFF2_ROUNDS:-900}"
EPOCHS="${TRADEOFF2_EPOCHS:-15}"

run_arm() {  # name, extra flags...
    local name="$1"; shift
    [ -f "results/logs/tradeoff2_r05_${name}.done" ] && {
        echo "arm $name already complete"; return 0; }
    # fresh start only when there is no checkpoint to resume (TableLogger
    # appends; a stale jsonl without a checkpoint would double-log round 0)
    [ -d "ckpt_tradeoff2_${name}" ] || rm -f "results/tradeoff2_${name}.jsonl"
    COMMEFFICIENT_NO_PALLAS=1 timeout 4200 python -u cv_train.py \
        --dataset cifar10 --synthetic_separation 0.025 \
        --num_clients 1000 --num_workers 16 --local_batch_size 8 \
        --num_rounds "$ROUNDS" --num_epochs "$EPOCHS" --eval_every 50 \
        --checkpoint_dir "ckpt_tradeoff2_${name}" --checkpoint_every 100 \
        --resume \
        --lr_scale "$LR" --seed 42 --dtype bfloat16 \
        --log_jsonl "results/tradeoff2_${name}.jsonl" "$@" 2>&1 \
        | tee -a "results/logs/tradeoff2_${name}.log" | grep -v WARNING | tail -4
    local rc=${PIPESTATUS[0]}
    [ "$rc" -eq 0 ] && touch "results/logs/tradeoff2_r05_${name}.done"
    return "$rc"
}

FAIL=0
for arm in uncompressed sketch localtopk fedavg truetopk; do
    # shellcheck disable=SC2046
    run_arm "$arm" $(arm_flags "$arm") || FAIL=1
done

done_files=$(for f in results/tradeoff2_*.jsonl; do
    n=$(basename "$f" .jsonl); n=${n#tradeoff2_}
    [ -f "results/logs/tradeoff2_r05_${n}.done" ] && echo "$f"
done)
if [ -n "$done_files" ]; then
    # shellcheck disable=SC2086
    if python scripts/tradeoff_table.py $done_files \
            > results/tradeoff_table2_r05.md.tmp \
            2> results/logs/tradeoff_table2.log; then
        mv results/tradeoff_table2_r05.md.tmp results/tradeoff_table2_r05.md
        echo "TRADEOFF2 TABLE RENDERED ($(echo $done_files | wc -w) arms)"
    else
        rm -f results/tradeoff_table2_r05.md.tmp
        echo "TABLE2 RENDER FAILED (see results/logs/tradeoff_table2.log)"
        FAIL=1
    fi
fi
[ "$FAIL" -eq 0 ] && echo "TRADEOFF2 STUDY COMPLETE"
exit "$FAIL"
