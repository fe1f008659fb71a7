"""benchmark/counting_attention.py and its reader: the attention kernel pair's
operations and bytes a round in the four expert cells, from their
configuration and traffic files; the reader reads nothing from a trace that
holds no kernel event, and reads the share of the roofline from one that
does."""

import json
import os

import pytest

from benchmark import counting_attention, trace_reduce
from benchmark.harness import Context
from benchmark.layer_metrics import attention_kernel_roofline

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _files(config, traffic):
    with open(os.path.join(ROOT, "benchmark", "configs", config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "traffic", traffic + ".json")) as f:
        return cfg, json.load(f)


# (configuration file, traffic file, TFLOP a round, attention layers, query
# heads, key/value heads, d_qk, d_v)
CELLS = {
    "glm47flash_sketch_w8_t2048": ("glm47_flash_fetchsgd", "sketch_w8_t2048_chunk1", 6.02,
                                   (5, 20, 20, 256, 256)),
    "lfm2moe_sketch_w8_t2048": ("lfm2_24b_a2b_fetchsgd", "sketch_w8_t2048_chunk1", 0.481,
                                (1, 32, 8, 64, 64)),
    "nemotron3nano_sketch_w8_t2048": ("nemotron3_nano_30b_a3b_fetchsgd",
                                      "sketch_w8_t2048_chunk1", 0.963, (1, 32, 2, 128, 128)),
    "qwen3next_sketch_w8_t2048": ("qwen3next_80b_a3b_fetchsgd", "sketch_w8_t2048", 0.963,
                                  (1, 16, 2, 256, 256)),
}


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_cells_count_a_round(cell):
    config, traffic, tflop, layers = CELLS[cell]
    cfg, tr = _files(config, traffic)
    assert counting_attention.attention_layers(cfg["model"]) == layers
    flops = counting_attention.flops_per_round(cfg, tr)
    assert flops / 1e12 == pytest.approx(tflop, rel=1e-3)
    # 8 documents of 2,048 tokens: 2,098,176 causal pairs each, 8 d_qk + 6 d_v a pair and head
    _, H, _, d_qk, d_v = layers
    assert flops == 8 * layers[0] * H * 2_098_176 * (8 * d_qk + 6 * d_v)
    # compute bound at these shapes: the FLOPs take longer at peak than the bytes
    nbytes = counting_attention.bytes_per_round(cfg, tr)
    assert flops / PEAKS["bf16_flops_per_s"] > nbytes / PEAKS["hbm_bytes_per_s"]


@pytest.mark.parametrize("cell, calls", [("glm47flash_sketch_w8_t2048", 40),
                                         ("lfm2moe_sketch_w8_t2048", 8),
                                         ("nemotron3nano_sketch_w8_t2048", 8),
                                         ("qwen3next_sketch_w8_t2048", 4)])
def test_calls_a_round_are_a_layers_client_chunks(cell, calls):
    """One call of each kernel a layer and client chunk: 8 clients one at a
    time in three cells, two at a time in Qwen3-Next's."""
    config, traffic, _, _ = CELLS[cell]
    assert counting_attention.calls_per_round(*_files(config, traffic)) == calls


def test_a_model_with_no_attention_layer_counts_nothing():
    cfg, tr = _files("gpt2_small_personachat_fetchsgd", "sketch_w8")
    assert counting_attention.flops_per_round(cfg, tr) == 0


def _context(red, cell="glm47flash_sketch_w8_t2048"):
    config, traffic, _, _ = CELLS[cell]
    cfg, tr = _files(config, traffic)
    return Context(trace=red, config=cfg, traffic=tr, peaks=PEAKS)


def test_the_reader_reads_nothing_from_a_trace_with_no_kernel_event():
    with open(os.path.join(HERE, "fixtures", "v5e_trace_cut.json")) as f:
        red = trace_reduce.reduce_planes(json.load(f))
    assert red.ops_matching("flash_attention") == (0, 0)
    assert attention_kernel_roofline.read(_context(red)) is None


def test_the_reader_divides_the_least_time_by_the_kernels_time_a_round():
    """Two rounds of a cell with two calls of each kernel a round, the first
    round cut by the capture's start (one of its forward events is missing),
    and one other operation a round: the kernels' time a round is the mean
    event of each kind times its calls a round, whatever the cut left out."""
    ops = []
    for r in range(2):
        t0 = r * 2_000_000_000
        # (name, start, duration) in ns: forward 10 ms, backward 20 ms a call
        ops += [(f"%flash_attention_fwd_call.{c} = f32[1,20,2048,256] custom-call()",
                 t0 + c * 100_000_000, 10_000_000) for c in range(2) if (r, c) != (0, 0)]
        ops += [(f"%flash_attention_bwd_call.{c} = f32[1,20,2048,256] custom-call()",
                 t0 + 300_000_000 + c * 100_000_000, 20_000_000) for c in range(2)]
        ops += [(f"%fusion.{r} = f32[8] fusion()", t0 + 600_000_000, 10_000_000)]
    modules = [("jit_step(1)", r * 2_000_000_000, 700_000_000) for r in range(2)]
    red = trace_reduce.reduce_planes([("/device:TPU:0", [("XLA Modules", modules), ("XLA Ops", ops)])])
    assert red.ops_matching("flash_attention") == (pytest.approx(0.11), 7)
    config, traffic, _, _ = CELLS["glm47flash_sketch_w8_t2048"]
    cfg, tr = _files(config, traffic)
    tr = dict(tr, argv=["--client_chunk", "4"], cohort=8)  # 5 layers x 2 chunks: 10 calls
    got = attention_kernel_roofline.read(Context(trace=red, config=cfg, traffic=tr, peaks=PEAKS))
    # 6.0159 TFLOP at 197 TFLOP/s is 30.54 ms against 10 x (10 + 20) ms of kernel a round
    assert got == pytest.approx(100 * 6.0158902272e12 / 197e12 / 0.300, rel=1e-6)
