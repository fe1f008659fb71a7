"""Model blocks: device time a traced round in the grouped-query attention blocks: projections, the heads' norms, rotary positions, T x T scores and the output projection, forward and backward."""

from benchmark.layer_metrics._profile_blocks import block_ms


def read(ctx):
    return block_ms("gqa_attn")
