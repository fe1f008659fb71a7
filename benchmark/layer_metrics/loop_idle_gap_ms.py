"""Run loop: commit-to-next-dispatch gap after each drain, per round."""


def read(ctx):
    h = ctx.registry.get("runner_idle_ms")
    return h["sum"] / ctx.rounds if h and h["count"] and ctx.rounds else None
