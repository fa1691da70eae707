"""Bottleneck ResNets with GroupNorm through ``models/resnet.py::ResNet``."""

from __future__ import annotations

import numpy as np

from benchmarks.families import compare_with_reference
from benchmarks.references import resnet as reference

#: What ``--rehearse`` swaps in: two stages of CIFAR-sized input. Two blocks
#: in the first stage, so that the tests can skip one.
TINY = {"module": {"stage_sizes": [2, 1], "base_features": 8,
                   "num_outputs": 10, "stem_kernel": 3, "groups": 4},
        "image_size": 32}

REFERENCE_BATCH = 8


def _module_kwargs(config: dict) -> dict:
    kw = dict(config["module"])
    kw["stage_sizes"] = tuple(kw["stage_sizes"])
    return kw


def build_model(config: dict, seed: int):
    import jax.numpy as jnp

    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.resnet import ResNet

    s = config["image_size"]
    return Model.build(ResNet(**_module_kwargs(config)),
                       jnp.zeros((1, s, s, 3), jnp.float32), seed=seed)


def learnable_images(n: int, size: int, classes: int, seed: int):
    """Seeded uint8 images a network can learn, after
    ``datasets._synthetic_images``: noise in [0, 90), and the class lights up
    its own block of the flattened image (+153, as there). A pooled
    convolutional network sees a 150-value block among 150,528 only faintly,
    so the class also sets the three channels' levels (its digits in base
    ``ceil(cbrt(classes))``, 16 grey levels apart): the loss of a working
    model falls within tens of rounds. Random labels on noise would give a
    loss that cannot fall. Made in uint8 throughout (0.6 GB at 4,096 x 224^2,
    never a float copy)."""
    rng = np.random.default_rng(seed)
    y = rng.integers(0, classes, size=n).astype(np.int32)
    x = rng.integers(0, 90, size=(n, size, size, 3), dtype=np.uint8)
    base = int(np.ceil(classes ** (1 / 3)))
    digits = np.stack([y % base, (y // base) % base, y // base ** 2], axis=1)
    x += (digits * (160 // max(base, 1))).astype(np.uint8)[:, None, None, :]
    flat = x.reshape(n, -1)
    block = max(flat.shape[1] // classes, 1)
    cols = y[:, None] * block + np.arange(block)[None, :]
    rows = np.arange(n)[:, None]
    flat[rows, cols] = np.minimum(flat[rows, cols].astype(np.int32) + 153,
                                  255).astype(np.uint8)
    return x, y


def make_dataframe(config: dict, rows: int, seed: int):
    import distkeras_tpu as dk

    x, y = learnable_images(rows, config["image_size"],
                            config["module"]["num_outputs"], seed)
    return dk.DataFrame({"features": x, "label": y})


def sample_shapes(config: dict):
    s = config["image_size"]
    return (s, s, 3), np.uint8, (), np.int32


def units_per_sample(config: dict) -> int:
    return 1  # samples


def forward_flops(config: dict) -> float:
    """Forward operations per image from the shapes: ``2*H*W*Cout*Cin*k*k`` for
    every convolution at its output size (SAME padding: the input size over
    the stride, rounded up), and ``2*Cin*Cout`` for the classifier. Norms,
    ReLUs and pools are elementwise and not counted."""
    m = config["module"]
    base, k = m.get("base_features", 64), m.get("stem_kernel", 7)

    def conv(size, cin, cout, kernel, stride=1):
        out = -(-size // stride)
        return 2.0 * out * out * cout * cin * kernel * kernel, out

    total, size = conv(config["image_size"], 3, base, k, 2)
    size = -(-size // 2)  # 3x3/2 max pool
    cin = base
    for i, count in enumerate(m["stage_sizes"]):
        f = base * 2 ** i
        for j in range(count):
            stride = 2 if i > 0 and j == 0 else 1
            a, _ = conv(size, cin, f, 1)
            b, out = conv(size, f, f, 3, stride)
            c, _ = conv(out, f, 4 * f, 1)
            total += a + b + c
            if cin != 4 * f or stride != 1:
                total += conv(size, cin, 4 * f, 1, stride)[0]
            size, cin = out, 4 * f
    return total + 2.0 * cin * m["num_outputs"]


def train_flops_per_unit(config: dict) -> float:
    """Forward plus backward (twice the forward: one product for the input's
    gradient, one for the weight's) per sample."""
    return 3.0 * forward_flops(config)


def expects_mosaic(config: dict) -> bool:
    return config["module"].get("norm_impl") == "pallas"


def reference_check(model, config: dict, seed: int, compute_dtype) -> dict:
    import jax.numpy as jnp

    x, _ = learnable_images(REFERENCE_BATCH, config["image_size"],
                            config["module"]["num_outputs"], seed + 1)
    # The trainer divides raw bytes by 255 in the compute dtype on the device
    # (workers.make_local_loop's cast_input); Model.apply would do it in
    # float32, so the check hands the model what training hands it.
    x_model = (jnp.asarray(x).astype(compute_dtype) / 255.0
               if compute_dtype else x)
    tol = reference.TOLERANCE if compute_dtype else reference.TOLERANCE_FLOAT32
    return compare_with_reference(model, reference.forward, x, x_model,
                                  _module_kwargs(config), compute_dtype, tol)
