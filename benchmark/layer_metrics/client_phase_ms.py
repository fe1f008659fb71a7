"""Round program: device time of the client phase a traced round (per-client
forward and backward, and reducing the cohort's gradients)."""

from benchmark.layer_metrics._profile_phases import phase_ms


def read(ctx):
    return phase_ms("client_grad", "cohort_reduce")
