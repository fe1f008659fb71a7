"""Device: the interval between two consecutive rounds' ready stamps less the
interval between the ends of their last operations on the capture's device
line. 0 on a device line laid on the host's clock; where it is not, every
`device_trace` metric under-reads idle time by this much a round."""

from benchmark.layer_metrics._profile_launch import launch_ms


def read(ctx):
    return launch_ms("drift")
