"""Model blocks: device time a traced round in the final norm and the untied head's logits, forward and backward (the loss's softmax is outside the scope)."""

from benchmark.layer_metrics._profile_blocks import block_ms


def read(ctx):
    return block_ms("lm_head")
