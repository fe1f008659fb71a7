"""Run loop: time the loop waited for the next prepared round, per round."""


def read(ctx):
    h = ctx.registry.get("runner_phase_prepare_ms")
    return h["sum"] / ctx.rounds if h and ctx.rounds else None
