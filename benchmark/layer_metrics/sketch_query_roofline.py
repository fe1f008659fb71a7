"""Sketch kernels: the query kernel against its HBM roofline."""

from benchmark.layer_metrics._sketch_kernel import roofline


def read(ctx):
    return roofline(ctx, "query")
