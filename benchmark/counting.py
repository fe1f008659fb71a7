"""Operations and bytes an algorithm needs, from shapes alone: the yardstick
for `mfu` and the kernels' rooflines. Nothing here looks at what the program
compiled, so a count cannot move when an implementation changes."""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json; "
                       "add a row with its source, there is no default")
    return table[device_kind]


def resnet9_conv_macs(channels=(64, 128, 256, 512), image: int = 32, in_ch: int = 3,
                      num_classes: int = 10) -> dict:
    """Multiply-accumulates of one image's forward pass, layer by layer.
    3x3 'same' convs; a 2x2 max-pool after the 2nd, 4th and 5th conv."""
    c0, c1, c2, c3 = channels
    s0, s1, s2, s3 = image, image // 2, image // 4, image // 8
    conv = lambda side, cin, cout: side * side * 9 * cin * cout  # noqa: E731
    return {
        "prep": conv(s0, in_ch, c0),
        "layer1": conv(s0, c0, c1),
        "res1": 2 * conv(s1, c1, c1),
        "layer2": conv(s1, c1, c2),
        "layer3": conv(s2, c2, c3),
        "res2": 2 * conv(s3, c3, c3),
        "linear": c3 * num_classes,
    }


def resnet9_train_flops_per_image(**kw) -> float:
    """Forward + backward: 2 FLOPs a MAC, the backward pass twice the forward
    (gradients to inputs and to weights). Batch norm, ReLU and pooling are
    left out (under 1% of the convs' count)."""
    return 3 * 2 * float(sum(resnet9_conv_macs(**kw).values()))


def resnet9_params(channels=(64, 128, 256, 512), in_ch: int = 3, num_classes: int = 10) -> int:
    c0, c1, c2, c3 = channels
    convs = [(in_ch, c0), (c0, c1), (c1, c1), (c1, c1), (c1, c2), (c2, c3), (c3, c3), (c3, c3)]
    return sum(9 * a * b + 2 * b for a, b in convs) + c3 * num_classes + num_classes


def gpt2_params(vocab: int, n_positions: int, n_embd: int, n_layer: int) -> int:
    """Tied embeddings; each block: two layer norms, qkv, attention output,
    and the 4x MLP, all with biases; a final layer norm."""
    e = n_embd
    block = 2 * 2 * e + (e * 3 * e + 3 * e) + (e * e + e) + (e * 4 * e + 4 * e) + (4 * e * e + e)
    return vocab * e + n_positions * e + n_layer * block + 2 * e


def gpt2_train_flops_per_token(vocab: int, n_embd: int, n_layer: int, seq_len: int) -> float:
    """Forward + backward of one token in a sequence of seq_len, full causal
    attention counted over the whole T x T square as it is computed (no
    recomputation): 6 FLOPs a matmul weight, plus 6 * 2 * T * n_embd a layer
    for the scores and the weighted values, plus the tied output matmul."""
    e = n_embd
    weights = n_layer * (3 * e * e + e * e + 8 * e * e) + vocab * e
    attention = n_layer * 2 * seq_len * e
    return 6.0 * (weights + attention)


def sketch_kernel_bytes(d: int, rows: int, cols: int) -> float:
    """Accumulate reads the vector once and writes the table; query reads the
    table and writes d estimates: 4 d + 4 r c bytes either way, float32."""
    return 4.0 * d + 4.0 * rows * cols
