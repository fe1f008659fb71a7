#!/usr/bin/env bash
# Forced-8-device tier-1 job slice: the sharded-round (SPMD mesh) tests on
# an 8-way virtual CPU mesh, flags pinned EXPLICITLY so the slice holds even
# where tests/conftest.py's defaults are overridden (CI shards, bare
# environments). Sharded-path regressions fail here fast, off-TPU.
#
# Covers: mesh-vs-single-device bit parity (3 mode configs), hybrid DCN
# mesh, K-round blocks, checkpoint+resume mid-run on the sharded path, mesh
# spec parsing, runner auto-inflight policy — plus the cohort
# fault-tolerance slice (test_cohort_faults.py: masked-cohort bit parity on
# the mesh path, sketch-space quarantine mesh == single-device), the
# serving layer (test_serve.py: served-round W-of-N bit parity fused AND
# sharded, CLI serve runs riding the 8-device mesh) and the engine's existing
# mesh suite.
set -euo pipefail
cd "$(dirname "$0")/.."

# static-analysis gate first (graftlint + ruff + mypy, < 60 s, jax-free):
# a contract violation should fail the slice before any test compiles.
# LINT_SKIP=1 skips it (escape hatch, e.g. mid-bisect). The checked-in
# GRAFTLINT.json must be byte-identical to a fresh run — a drifting
# archive means someone changed rules/code without regenerating it (and
# the parallel fan-out must be deterministic for this gate to hold).
if [[ "${LINT_SKIP:-0}" != "1" && -f GRAFTLINT.json ]]; then
    cp GRAFTLINT.json /tmp/_graftlint_checked_in.json
    scripts/lint.sh
    cmp /tmp/_graftlint_checked_in.json GRAFTLINT.json || {
        echo "tier1_8dev: GRAFTLINT.json drifted from the checked-in copy" \
             "— rerun scripts/lint.sh and commit the result" >&2
        exit 1
    }
else
    scripts/lint.sh
fi

export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8"

python -m pytest tests/test_sharded_round.py tests/test_engine.py \
    tests/test_client_state_sharding.py tests/test_cohort_faults.py \
    tests/test_serve.py tests/test_obs.py tests/test_layerwise.py \
    tests/test_byzantine.py tests/test_pipeline_serve.py \
    tests/test_sketch_health.py tests/test_async_robust.py \
    tests/test_scale.py \
    -q -m 'not slow' -p no:cacheprovider "$@"

# the async x robust composition end to end (per-buffer robust merge under
# the adaptive attackers, through the real CLI): < 1 min CPU
scripts/chaos_smoke.sh async_byzantine

# the two-tier edge-aggregation topology end to end (real cv_train over
# --serve_edges 2 with an edge killed mid-round + a wire_delay straggler;
# edge-death == shard-dropped pinned BITWISE via the run's own ledger
# cohort): < 1 min CPU
scripts/chaos_smoke.sh edge

echo "tier1_8dev: OK"
