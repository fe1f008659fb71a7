"""Session: host time inside dispatch_round, per round."""


def read(ctx):
    h = ctx.registry.get("runner_phase_dispatch_ms")
    return h["sum"] / ctx.rounds if h and ctx.rounds else None
