"""ResNet-9 (cifar10-fast lineage), forward and loss, written plainly.

prep conv(64) - conv(128)+pool - residual(128) - conv(256)+pool -
conv(512)+pool - residual(512) - maxpool(4) - linear, batch norm with the
BATCH's own statistics after every conv (a client normalises over its own
images), ReLU, logits scaled by 0.125. float32 arrays, NHWC.

Parameters are a nested dict; its sorted-key leaf order is the flat
coordinate order the sketch hashes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

CHANNELS = (64, 128, 256, 512)
EPS = 1e-5


def param_shapes(channels=CHANNELS, num_classes: int = 10, in_ch: int = 3) -> dict:
    c0, c1, c2, c3 = channels

    def conv_bn(cin, cout):
        return {"Conv_0": {"kernel": (3, 3, cin, cout)},
                "BatchNorm_0": {"scale": (cout,), "bias": (cout,)}}

    return {
        "ConvBN_0": conv_bn(in_ch, c0),
        "ConvBN_1": conv_bn(c0, c1),
        "Residual_0": {"ConvBN_0": conv_bn(c1, c1), "ConvBN_1": conv_bn(c1, c1)},
        "ConvBN_2": conv_bn(c1, c2),
        "ConvBN_3": conv_bn(c2, c3),
        "Residual_1": {"ConvBN_0": conv_bn(c3, c3), "ConvBN_1": conv_bn(c3, c3)},
        "Dense_0": {"kernel": (c3, num_classes), "bias": (num_classes,)},
    }


def init_params(key, shapes: dict) -> dict:
    """Seeded weights: kernels ~ N(0, 1/fan_in), BN scale near 1, biases small
    but not zero (so no leaf's gradient path is dead)."""
    leaves, treedef = jax.tree.flatten(shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, shape in zip(keys, leaves):
        if len(shape) > 1:
            fan_in = 1
            for s in shape[:-1]:
                fan_in *= s
            out.append(jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5)
        else:
            out.append(0.05 * jax.random.normal(k, shape, jnp.float32))
    params = jax.tree.unflatten(treedef, out)

    def fix(path, x):
        return x + 1.0 if path[-1].key == "scale" else x

    return jax.tree_util.tree_map_with_path(fix, params)


def _conv_bn(p, x):
    y = jax.lax.conv_general_dilated(
        x, p["Conv_0"]["kernel"], (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    mean = y.mean(axis=(0, 1, 2))
    var = jnp.square(y - mean).mean(axis=(0, 1, 2))
    y = (y - mean) * jax.lax.rsqrt(var + EPS)
    return jax.nn.relu(y * p["BatchNorm_0"]["scale"] + p["BatchNorm_0"]["bias"])


def _pool(x, n):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, n, n, 1),
                                 (1, n, n, 1), "VALID")


def logits(params, x):
    """x: [B, 32, 32, 3] images of ONE client -> [B, classes]."""
    x = _conv_bn(params["ConvBN_0"], x)
    x = _pool(_conv_bn(params["ConvBN_1"], x), 2)
    r = params["Residual_0"]
    x = x + _conv_bn(r["ConvBN_1"], _conv_bn(r["ConvBN_0"], x))
    x = _pool(_conv_bn(params["ConvBN_2"], x), 2)
    x = _pool(_conv_bn(params["ConvBN_3"], x), 2)
    r = params["Residual_1"]
    x = x + _conv_bn(r["ConvBN_1"], _conv_bn(r["ConvBN_0"], x))
    x = _pool(x, 4).reshape(x.shape[0], -1)
    return (x @ params["Dense_0"]["kernel"] + params["Dense_0"]["bias"]) * 0.125


def client_loss(params, batch):
    """Mean softmax cross-entropy of one client's images; also the sum, so
    that a round's loss is sum / count over the cohort."""
    logp = jax.nn.log_softmax(logits(params, batch["x"]))
    per_ex = -jnp.take_along_axis(logp, batch["y"][:, None], axis=1)[:, 0]
    return per_ex.mean(), per_ex.sum()
