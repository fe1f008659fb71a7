"""Model blocks: device time a traced round in the expert blocks: router and sort, the held experts' grouped matmuls, the shared expert, forward and backward."""

from benchmark.layer_metrics._profile_blocks import block_ms


def read(ctx):
    return block_ms("moe_route", "moe_experts", "moe_shared")
