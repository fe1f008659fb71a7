"""Round program: device time a traced round spent raveling the per-client
gradients and reducing them over the cohort."""

from benchmark.layer_metrics._profile_phases import phase_ms


def read(ctx):
    return phase_ms("cohort_reduce")
