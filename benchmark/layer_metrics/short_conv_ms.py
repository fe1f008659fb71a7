"""Model blocks: device time a traced round in the gated short convolutions: the projection to the three streams, the gates, the causal depthwise convolution and the output projection, forward and backward."""

from benchmark.layer_metrics._profile_blocks import block_ms


def read(ctx):
    return block_ms("short_conv")
