"""Round program: device busy time per traced round."""


def read(ctx):
    t = ctx.trace
    if t is None or not ctx.traced_rounds:
        return None
    return 1e3 * t.busy_s / ctx.traced_rounds
