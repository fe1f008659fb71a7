"""The counting functions against numbers worked out by hand."""

import pytest

from benchmark import counting


def test_resnet9_conv_macs_by_hand():
    macs = counting.resnet9_conv_macs()
    # 32x32x9x3x64, then five groups of 75,497,472 (each halving of the side
    # doubles the channels), and the 512x10 linear layer
    assert macs["prep"] == 1_769_472
    for name in ("layer1", "res1", "layer2", "layer3", "res2"):
        assert macs[name] == 75_497_472
    convs = sum(v for k, v in macs.items() if k != "linear")
    assert convs == 379_256_832  # the 379.3M of ISSUE 24
    assert counting.resnet9_train_flops_per_image() == pytest.approx(2.2756e9, rel=1e-4)


@pytest.mark.parametrize("fn, args, want", [
    (counting.resnet9_params, (), 6_573_130),
    (counting.gpt2_params, (50_262, 1024, 768, 12), 124_443_648),
    (counting.gpt2_params, (50_257, 1024, 768, 12), 124_439_808),  # the public gpt2
    (counting.sketch_kernel_bytes, (6_573_130, 5, 524_288), 4 * 6_573_130 + 4 * 5 * 524_288),
    (counting.sketch_kernel_bytes, (124_443_648, 5, 1_048_576), 4 * 124_443_648 + 20 * 1_048_576),
])
def test_counts_by_hand(fn, args, want):
    assert fn(*args) == want


def test_gpt2_flops_per_token_by_hand():
    # 12 layers x 12 x 768^2 weights + the tied 50262 x 768 head, plus
    # 12 x 2 x 256 x 768 for scores and values; 6 FLOPs each
    want = 6 * (12 * 12 * 768 * 768 + 50_262 * 768 + 12 * 2 * 256 * 768)
    assert counting.gpt2_train_flops_per_token(50_262, 768, 12, 256) == want
    assert want == pytest.approx(0.77e9, rel=0.01)


def test_unknown_device_kind_is_an_error():
    assert counting.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert counting.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    for kind in ("TPU v9", "cpu", "_source"):
        with pytest.raises(KeyError):
            counting.peaks(kind)
