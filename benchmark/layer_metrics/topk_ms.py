"""Round program: device time of the server's top-k a traced round. A cell
with no top-k (the dense control) reads nothing."""

from benchmark.layer_metrics._profile_phases import phase_ms


def read(ctx):
    if ctx.facts.get("mode") == "uncompressed":
        return None
    return phase_ms("server_topk")
