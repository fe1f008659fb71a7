"""Shared by the two kernel rooflines. Both kernels are HBM-bound: the least
time is counting.sketch_kernel_bytes over the chip's HBM bandwidth. The trace
names a Pallas call after its kernel function (`_accumulate_kernel`,
`_query_kernel` in sketch/pallas_kernels.py), so the readers match on the
word; a round that holds no such operation reads nothing."""

from benchmark import counting


def roofline(ctx, word: str):
    t = ctx.trace
    if t is None or ctx.facts.get("mode") != "sketch":
        return None
    secs, runs = t.ops_matching(word)
    if not runs or secs <= 0:
        return None
    f = ctx.facts
    least = counting.sketch_kernel_bytes(f["d"], f["rows"], f["cols"]) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (secs / runs)
