"""Round program: device time a traced round that the capture's summary could
attribute to no named phase (operations the compiler made or rewrote without
the JAX op_name). The phase metrics are only as good as this is small: they
are registered for the cells in which it is under 5% of the busy time."""

from benchmark.layer_metrics._profile_phases import phase_ms


def read(ctx):
    return phase_ms("other")
