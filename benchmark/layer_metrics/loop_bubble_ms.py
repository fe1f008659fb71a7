"""Run loop: device wait across a drain, per round. The loop stamps each
pending dispatch as the drain reads it back: a dispatch queued behind its
predecessor takes the device's own time for a round (chained), the first of a
drain takes that plus whatever the device waited across the drain (first).
Their difference, once per drain, over the window's rounds."""


def read(ctx):
    first = ctx.registry.get("runner_round_interval_first_ms")
    chained = ctx.registry.get("runner_round_interval_chained_ms")
    if not first or not chained or not first["count"] or not chained["count"] or not ctx.rounds:
        return None
    wait = first["sum"] / first["count"] - chained["sum"] / chained["count"]
    return wait * first["count"] / ctx.rounds
