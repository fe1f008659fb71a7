"""Shared by the readers of the capture's own summary. `ProfileWindow`
(commefficient_tpu/obs/profiler.py) reads the capture it wrote and publishes
device ms per traced round by `jax.named_scope` phase as gauges in the
program's process-wide registry. Gauges are not in `ctx.registry` (the window's
deltas, taken before the profiled segment), so they are read here, at `read()`
time. A program with no such gauges, as the parent of PR 25, reads nothing."""


def phase_ms(*phases: str):
    from commefficient_tpu.obs import registry as obreg

    reg = obreg.default()
    if not reg.gauge("profile_traced_rounds").value:
        return None
    return sum(reg.gauge(f"profile_phase_device_ms_{p}").value for p in phases)
