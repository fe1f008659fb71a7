"""Model blocks: device time a traced round in the Gated DeltaNet mixers (projections, convolution, the chunked delta rule, gated norm), forward and backward."""

from benchmark.layer_metrics._profile_blocks import block_ms


def read(ctx):
    return block_ms("gdn")
