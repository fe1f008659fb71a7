# Single source of truth for the round-5 tradeoff-study arm
# hyperparameters. BOTH writers of the shared checkpoints/JSONLs —
# scripts/tradeoff_r05.sh and any slicing driver — source this file, so an
# arm's flags can never diverge mid-study between the two (a resumed checkpoint with silently different
# hyperparameters would corrupt the 600-round curve).
#
# Usage: arm_flags <name> -> echoes the extra cv_train flags for that arm.
# The common task/config flags (dataset, clients, workers, schedule) stay
# in each caller — they are also shared-checkpoint-critical, but callers
# differ only in --num_rounds / checkpoint cadence, which are safe.
arm_flags() {
    case "$1" in
        uncompressed) echo "--mode uncompressed" ;;
        sketch) echo "--mode sketch --k 50000 --num_cols 524288 --num_rows 5 \
            --num_blocks 4 --momentum_type virtual --error_type virtual" ;;
        localtopk) echo "--mode local_topk --k 50000 \
            --momentum_type none --error_type virtual" ;;
        fedavg) echo "--mode fedavg --num_local_iters 5" ;;
        truetopk) echo "--mode true_topk --k 50000 \
            --momentum_type virtual --error_type virtual" ;;
        *) echo "unknown arm $1" >&2; return 64 ;;
    esac
}
