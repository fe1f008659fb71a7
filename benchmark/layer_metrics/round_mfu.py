"""Round program: the whole step's share of the chip's bf16 peak. Forward and
backward FLOPs the model requires for a round (counting.py, from shapes), over
all the rounds and all the wall time of the window (in a traced run: of its
untraced segment, before the profiler starts)."""


def read(ctx):
    if not ctx.rounds or ctx.window_s <= 0:
        return None
    flops = ctx.facts["train_flops_per_round"] * ctx.rounds
    return 100.0 * flops / ctx.window_s / (ctx.chips * ctx.peaks["bf16_flops_per_s"])
