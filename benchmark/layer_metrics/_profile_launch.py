"""Shared by the readers of the capture's third summary. `ProfileWindow`
(commefficient_tpu/obs/profiler.py `summarize_launches`) pairs every execution
of the round program in the capture it wrote with the loop's ready stamps and
the session's launch, which the program mirrors into the capture, and publishes
what a queued round adds to the wall beyond its operations, and its parts, as
gauges in ms a round over the queued pairs of consecutive rounds. Gauges are
not in `ctx.registry`, so they are read here, at `read()` time. Under three
queued pairs there is no reading; a program with no such gauges, as the parent
of PR 35, reads nothing."""


def launch_ms(name: str):
    from commefficient_tpu.obs import registry as obreg

    reg = obreg.default()
    if reg.gauge("profile_launch_pairs").value < 3:
        return None
    return reg.gauge(f"profile_launch_{name}_ms").value
