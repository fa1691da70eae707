"""ResNet for ImageNet-class training (BASELINE config #5: ResNet-50, sync DP at scale).

Design decision vs. the 2016-era reference: normalization is **GroupNorm**, not
BatchNorm. BatchNorm's running statistics are mutable cross-batch state that (a) breaks
the pure-functional replica model the async disciplines rely on and (b) couples
statistics to the per-chip batch slice under data parallelism. GroupNorm is
batch-independent, needs no state collection, and is the standard TPU-scale substitute
(same accuracy class at ResNet-50 scale).

Param-naming note (round 3): blocks are explicitly named ``stage{i}_block{j}``
and norms ``GN_k`` — a ONE-TIME break from the earlier auto-generated
``BottleneckBlock_i/GroupNorm_k`` paths, required so ``remat=True`` (which
changes flax's auto prefix) cannot silently re-draw init or orphan
checkpoints across remat settings. Checkpoints written before this rename
need their ResNet param paths remapped on restore —
:func:`remap_legacy_params` does it.
"""

from __future__ import annotations


import jax
from flax import linen as nn

from distkeras_tpu.models.base import DKModule, Model, register_model
from distkeras_tpu.scopes import owner


class GN(nn.Module):
    """GroupNorm with a fused-kernel option (and optionally fused ReLU).

    ``impl='pallas'`` routes to the one-pass Pallas kernel
    (``ops/pallas/groupnorm.py``): stats + normalize + affine + ReLU on a
    single HBM read/write — ResNet-class training here is bandwidth-bound and
    GroupNorm is ~28% of the step (docs/PERFORMANCE.md). ``impl='xla'`` is
    flax's ``nn.GroupNorm`` (+ separate relu), numerically equivalent."""

    num_groups: int
    impl: str = "xla"
    relu: bool = False

    @nn.compact
    def __call__(self, x):
        import jax.numpy as jnp

        with owner("norm"):
            C = x.shape[-1]
            # One param layout for both impls, so impl is a runtime choice (a
            # checkpoint trained either way loads under the other).
            gamma = self.param("scale", nn.initializers.ones, (C,))
            beta = self.param("bias", nn.initializers.zeros, (C,))
            # is_initializing: flax init may run eagerly on a CPU device even in
            # a TPU process (param init is host work) — the compiled kernel can't;
            # both impls share the param layout, so init through the HLO path.
            if self.impl == "pallas" and not self.is_initializing():
                from distkeras_tpu.ops.pallas.groupnorm import group_norm

                return group_norm(x, gamma, beta, groups=self.num_groups,
                                  relu=self.relu)
            # Functional GroupNorm, flax-equivalent: float32 stats over
            # (spatial..., C/G) with biased variance, eps 1e-6.
            G = self.num_groups
            xf = x.astype(jnp.float32)
            gshape = x.shape[:-1] + (G, C // G)
            xg = xf.reshape(gshape)
            axes = tuple(range(1, len(gshape) - 2)) + (len(gshape) - 1,)
            mean = xg.mean(axes, keepdims=True)
            var = ((xg - mean) ** 2).mean(axes, keepdims=True)
            y = ((xg - mean) * jax.lax.rsqrt(var + 1e-6)).reshape(x.shape)
            y = y * gamma + beta
            if self.relu:
                y = jnp.maximum(y, 0.0)
            return y.astype(x.dtype)


class BottleneckBlock(nn.Module):
    features: int
    strides: int = 1
    groups: int = 32
    norm_impl: str = "xla"

    @nn.compact
    def __call__(self, x):
        residual = x
        with owner("conv"):
            y = nn.Conv(self.features, (1, 1), use_bias=False)(x)
        y = GN(min(self.groups, self.features), self.norm_impl, relu=True)(y)
        with owner("conv"):
            y = nn.Conv(
                self.features, (3, 3), strides=(self.strides, self.strides),
                padding="SAME", use_bias=False,
            )(y)
        y = GN(min(self.groups, self.features), self.norm_impl, relu=True)(y)
        with owner("conv"):
            y = nn.Conv(self.features * 4, (1, 1), use_bias=False)(y)
        y = GN(min(self.groups, self.features * 4), self.norm_impl)(y)
        if residual.shape != y.shape:
            with owner("conv"):
                residual = nn.Conv(
                    self.features * 4, (1, 1),
                    strides=(self.strides, self.strides), use_bias=False,
                )(x)
            residual = GN(min(self.groups, self.features * 4), self.norm_impl)(residual)
        return nn.relu(residual + y)


@register_model
class ResNet(DKModule):
    stage_sizes: tuple = (3, 4, 6, 3)  # ResNet-50
    base_features: int = 64
    num_outputs: int = 1000
    stem_kernel: int = 7
    groups: int = 32
    #: jax.checkpoint each bottleneck block: activations are recomputed in
    #: backward instead of saved, cutting peak HBM ~3x on the 224x224 stack —
    #: what buys the larger per-chip batch the MXU needs to stay busy
    #: (ImageNet ResNet is HBM-bound at small B; see docs/PERFORMANCE.md).
    remat: bool = False
    #: 'pallas' = fused one-pass GroupNorm(+ReLU) kernels; 'xla' = plain HLO.
    norm_impl: str = "xla"

    @nn.compact
    def __call__(self, x, train: bool = False):
        k = (self.stem_kernel, self.stem_kernel)
        with owner("conv"):
            x = nn.Conv(self.base_features, k, strides=(2, 2), padding="SAME",
                        use_bias=False)(x)
        x = GN(min(self.groups, self.base_features), self.norm_impl,
               relu=True)(x)
        with owner("conv"):  # the stem's pooling, with the stem
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        block_cls = nn.remat(BottleneckBlock) if self.remat else BottleneckBlock
        for i, block_count in enumerate(self.stage_sizes):
            features = self.base_features * (2**i)
            for j in range(block_count):
                strides = 2 if i > 0 and j == 0 else 1
                # Explicit names: nn.remat changes the auto-generated module
                # prefix, which would silently re-draw init and orphan
                # checkpoints across remat settings.
                x = block_cls(features, strides=strides, groups=self.groups,
                              norm_impl=self.norm_impl,
                              name=f"stage{i}_block{j}")(x)
        with owner("head"):
            x = x.mean(axis=(1, 2))  # global average pool
            return nn.Dense(self.num_outputs)(x)


def resnet50(num_outputs: int = 1000, seed: int = 0, remat: bool = False,
             norm_impl: str = "xla") -> Model:
    import jax.numpy as jnp

    module = ResNet(stage_sizes=(3, 4, 6, 3), num_outputs=num_outputs,
                    remat=remat, norm_impl=norm_impl)
    return Model.build(module, jnp.zeros((1, 224, 224, 3), jnp.float32), seed=seed)


def remap_legacy_params(params, stage_sizes: tuple = (3, 4, 6, 3)):
    """Remap a pre-round-3 ResNet param tree (flax auto-generated
    ``BottleneckBlock_n`` / ``GroupNorm_k`` module paths) to the current
    explicit ``stage{i}_block{j}`` / ``GN_k`` layout.

    Use when restoring a checkpoint written before the round-3 rename::

        old = ckpt.restore_host(legacy_target)
        model = model.with_params(remap_legacy_params(old, module.stage_sizes))

    Raises ``KeyError`` with guidance if the tree has no legacy-named
    modules at all (e.g. an already-current tree, or a remat-era auto
    prefix), so a no-op remap cannot masquerade as a successful migration.
    """
    if not detect_legacy_layout(params):
        raise KeyError(
            "params tree has no legacy 'BottleneckBlock_n'/'GroupNorm_k' "
            f"modules (top-level keys: {sorted(dict(params))}). Either it is "
            "already in the current stage{i}_block{j}/GN_k layout (no remap "
            "needed), or it was written under a different auto-naming (e.g. "
            "remat-wrapped modules) and needs a hand-written key map.")
    order = [f"stage{i}_block{j}"
             for i, n in enumerate(stage_sizes) for j in range(n)]

    def rename_gn(tree):
        return {(k.replace("GroupNorm_", "GN_", 1)
                 if k.startswith("GroupNorm_") else k): v
                for k, v in tree.items()}

    out = {}
    for k, v in dict(params).items():
        if k.startswith("BottleneckBlock_"):
            n = int(k.rsplit("_", 1)[1])
            if n >= len(order):
                raise KeyError(
                    f"{k} has no slot in stage_sizes={stage_sizes} "
                    f"({len(order)} blocks) — pass the module's actual "
                    "stage_sizes")
            out[order[n]] = rename_gn(dict(v))
        elif k.startswith("GroupNorm_"):
            out[k.replace("GroupNorm_", "GN_", 1)] = v
        else:
            out[k] = v
    return out


def detect_legacy_layout(params) -> bool:
    """True if ``params`` is a pre-round-3 ResNet tree (auto-generated block
    names) — for restore-path callers that want to raise with remap
    instructions instead of a bare missing-key error."""
    return any(k.startswith(("BottleneckBlock_", "GroupNorm_"))
               for k in dict(params))


def tiny_resnet(num_outputs: int = 10, seed: int = 0) -> Model:
    """A test-sized ResNet (CIFAR-shaped input) for CI on the CPU mesh."""
    import jax.numpy as jnp

    module = ResNet(stage_sizes=(1, 1), base_features=8, num_outputs=num_outputs,
                    stem_kernel=3, groups=4)
    return Model.build(module, jnp.zeros((1, 32, 32, 3), jnp.float32), seed=seed)
