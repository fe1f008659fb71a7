"""Run loop: the part of the wait across a drain in which the host had not yet
handed the device its next round (from the drain's last ready stamp to the
return of the next dispatch call), per round."""


def read(ctx):
    h = ctx.registry.get("runner_bubble_host_ms")
    return h["sum"] / ctx.rounds if h and h["count"] and ctx.rounds else None
