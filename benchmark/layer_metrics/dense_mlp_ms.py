"""Model blocks: device time a traced round in the leading dense layer's SwiGLU feed-forward, forward and backward."""

from benchmark.layer_metrics._profile_blocks import block_ms


def read(ctx):
    return block_ms("dense_mlp")
