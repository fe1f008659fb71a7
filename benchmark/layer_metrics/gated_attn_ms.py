"""Model blocks: device time a traced round in the gated softmax attention mixers (projections, rotary, unfused T x T scores, output gate), forward and backward."""

from benchmark.layer_metrics._profile_blocks import block_ms


def read(ctx):
    return block_ms("gated_attn")
