"""Device: what a queued round adds to the wall beyond its own operations: the
interval between the ready stamps of two consecutive rounds, the second of
which the device had in hand before the first ended, less the busy time of the
second's operations. Mean over the traced window's queued pairs."""

from benchmark.layer_metrics._profile_launch import launch_ms


def read(ctx):
    return launch_ms("gap")
