"""Attention kernel: the causal attention kernel pair's share of its roofline.
The least time a round is the larger of counting_attention's FLOPs over the
chip's bf16 peak and its bytes over the HBM bandwidth; at the expert cells'
shapes the bound is compute (GLM-4.7-Flash: 6.02 TFLOP, 30.5 ms, against 10.1
GB, 12.3 ms). The kernel's time a round is the mean device time of a forward
and of a backward event, each times the kernel's calls a round
(counting_attention.calls_per_round): every event the trace matches counts,
and the capture's first round, which starts before the capture does, lowers
no mean. A Pallas call is named after its jitted wrapper
(`flash_attention_fwd_call`, `flash_attention_bwd_call` in
commefficient_tpu/ops/attention.py), so the reader matches those words. A
program that runs no such kernel, or a model with no attention layer, reads
nothing."""

from benchmark import counting_attention


def read(ctx):
    t = ctx.trace
    flops = counting_attention.flops_per_round(ctx.config, ctx.traffic)
    if t is None or flops <= 0:
        return None
    calls = counting_attention.calls_per_round(ctx.config, ctx.traffic)
    kernel_s = 0.0
    for word in ("flash_attention_fwd", "flash_attention_bwd"):
        secs, runs = t.ops_matching(word)
        if not runs or secs <= 0:
            return None
        kernel_s += secs / runs * calls
    least = max(flops / ctx.peaks["bf16_flops_per_s"],
                counting_attention.bytes_per_round(ctx.config, ctx.traffic) / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
