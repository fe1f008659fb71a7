"""Sketch kernels: the accumulate kernel against its HBM roofline."""

from benchmark.layer_metrics._sketch_kernel import roofline


def read(ctx):
    return roofline(ctx, "accumulate")
