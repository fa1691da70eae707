"""ResNet's forward pass, plainly (He et al. 2015, arXiv:1512.03385, Table 1,
the bottleneck form; GroupNorm in place of BatchNorm after Wu & He 2018,
arXiv:1803.08494): a strided ``stem_kernel`` convolution, GroupNorm, ReLU, a
3x3/2 max pool; stages of bottleneck blocks (1x1, 3x3, 1x1 convolutions, each
followed by GroupNorm, ReLU after the first two; the stride on the 3x3 of a
stage's first block from the second stage on; a projected shortcut where the
shape changes); global average pool; a linear classifier.

Departures of the model under test, followed here: GroupNorm's epsilon is
flax's 1e-6 (the paper: 1e-5); uint8 pixels are scaled by 1/255 and not
mean-subtracted.

Parameters are read from the model's own tree by name (flax numbers the
convolutions and norms of a block in the order they are made).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

#: Relative L2 on the logits, model in bfloat16 against this in float32.
#: GroupNorm renormalizes after every convolution, so the roundings of 53
#: layers do not compound in scale, only add: sqrt(53) * 2e-3 = 1.5e-2 at
#: most, and 0.7e-2 to 0.9e-2 was measured on the chip at full width over ten
#: seeds (PERF.md, PR 24). 4e-2 still fails a skipped block or a wrong
#: grouping, each of which moves the logits by 1e-1 or more (the CPU test
#: skips a block; in float32 the agreement is 1e-6).
TOLERANCE = 4e-2
TOLERANCE_FLOAT32 = 1e-4


def _conv(x, kernel, stride=1):
    return lax.conv_general_dilated(
        x, kernel, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _group_norm(x, p, groups, relu=False, eps=1e-6):
    B, H, W, C = x.shape
    g = x.reshape(B, H * W, groups, C // groups)
    mean = g.mean((1, 3), keepdims=True)
    var = ((g - mean) ** 2).mean((1, 3), keepdims=True)
    y = ((g - mean) * lax.rsqrt(var + eps)).reshape(x.shape)
    y = y * p["scale"] + p["bias"]
    return jnp.maximum(y, 0.0) if relu else y


def _bottleneck(x, p, features, stride, groups):
    y = _conv(x, p["Conv_0"]["kernel"])
    y = _group_norm(y, p["GN_0"], min(groups, features), relu=True)
    y = _conv(y, p["Conv_1"]["kernel"], stride)
    y = _group_norm(y, p["GN_1"], min(groups, features), relu=True)
    y = _conv(y, p["Conv_2"]["kernel"])
    y = _group_norm(y, p["GN_2"], min(groups, features * 4))
    if "Conv_3" in p:
        x = _conv(x, p["Conv_3"]["kernel"], stride)
        x = _group_norm(x, p["GN_3"], min(groups, features * 4))
    return jnp.maximum(x + y, 0.0)


def forward(params, images, *, stage_sizes, base_features=64, groups=32, **_):
    """Logits ``[B, classes]`` in float32 with exact matmuls; ``images`` are
    uint8 ``[B, H, W, 3]``."""
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        x = images.astype(jnp.float32) / 255.0
        x = _conv(x, params["Conv_0"]["kernel"], 2)
        x = _group_norm(x, params["GN_0"], min(groups, base_features),
                        relu=True)
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
        for i, count in enumerate(stage_sizes):
            for j in range(count):
                x = _bottleneck(x, params[f"stage{i}_block{j}"],
                                base_features * 2 ** i,
                                2 if i > 0 and j == 0 else 1, groups)
        x = x.mean((1, 2))
        return x @ params["Dense_0"]["kernel"] + params["Dense_0"]["bias"]
