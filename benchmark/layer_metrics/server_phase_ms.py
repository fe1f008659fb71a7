"""Round program: device time of the server phase a traced round (compress,
sketch-space or dense algebra, query, top-k, apply)."""

from benchmark.layer_metrics._profile_phases import phase_ms


def read(ctx):
    return phase_ms("compress", "server_algebra", "server_query", "server_topk", "apply")
