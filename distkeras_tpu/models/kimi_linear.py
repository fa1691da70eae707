"""The Kimi Linear decoders (moonshotai; ``model_type`` ``kimi_linear``, the
published ``config.json`` of ``Kimi-Linear-48B-A3B-Instruct``; Kimi Linear,
arXiv:2510.26692): a stack of layer *kinds*. Three layers in four mix the
sequence with **Kimi Delta Attention**, a gated delta rule with a decay a
channel that carries a ``[128, 128]`` state a head and no attention at all;
the fourth is **latent attention** (a compressed K/V projection, keys of 192
beside values of 128) **without positions**. The leading layer's feed-forward
is a dense SwiGLU, the others' a sparse mixture of SwiGLU experts routed by
sigmoid scores with a selection bias, **beside a shared expert** that every
token passes. The head is its own matrix.

Layer ``l`` on the residual stream ``x`` ``[B, L, d_model]``, no bias in any
projection:

* ``x += Op_l(RMSNorm(x))`` with ``Op_l`` by ``layer_types[l]``: ``"kda"``
  (``blocks.KimiDeltaAttention``, the chunked scan of ``ops/delta_rule.py``)
  or ``"mla"`` (``blocks.LatentAttention``).
* ``x += FF_l(RMSNorm(x))``: for ``l < num_dense_layers`` ``(silu(g W_gate) *
  (g W_up)) W_down`` of width ``d_ff``; after them ``p = sigmoid(g W_r)`` in
  float32, ``experts = top_k(p + b)`` with ``b`` the correction bias, ``w =
  p[experts]``, ``w <- w / (sum(w) + 1e-20) * routed_scaling_factor``, and
  ``Shared(g) +`` the sum over the chosen experts held here of ``w_k E_k(g)``
  (``blocks.DroplessExperts``); ``E`` and ``Shared`` both ``(silu(g W_gate) *
  (g W_up)) W_down``, ``Shared`` of width ``num_shared_experts * d_expert``,
  computed once a token whatever was chosen, under the scope
  ``dk_moe_shared``. One group of all experts makes the published grouped
  top-k the plain one.
* ``logits = RMSNorm(x) . W_head`` over the held rows of the vocabulary.

**What this chip holds** of a layer that several chips share:
``experts_held = (first, count)`` of the routed experts and ``vocab_size``
rows, as in the other expert models, and ``heads_held = (first, count)`` of
the ``num_heads`` heads of every ``kda`` and ``mla`` layer: the head
projections and ``W_o``'s rows are the held heads', while ``W_fa``, ``W_ga``,
``W_kva``, the norms, the router, the shared expert and the dense layer are
whole on every chip (a width is never cut). What the absent heads and experts would
have added is left out; the sum over the chips that share a layer's heads (an
all-reduce after ``W_o``) is not built. The routed feed-forward is
``blocks.RoutedFFN``, which says why a share of the experts does not train
its router and how the correction bias moves against the load.

``remat=True`` recomputes each layer in the backward pass, all but the flash
forward of the ``mla`` layers (``blocks.remat_block``). A round's routing and
the delta rule's decay leave the program in ``ROUND_COUNTERS``
(:meth:`KimiLinearLM.publish_round_counters`: ``moe.round`` and
``kda.round``); the gauges ``kda.chunk`` and ``kda.state_bytes`` are set as
the model is traced, and the held layer kinds are written once as the event
``model.layer_kinds``.

Parameters do not depend on the sequence length: build with a short sample.
"""

from __future__ import annotations

import functools
from collections.abc import Callable

import jax.numpy as jnp
from flax import linen as nn

from distkeras_tpu.models.base import DKModule, Model, register_model
from distkeras_tpu.models.blocks import (GatedMLP, KimiDeltaAttention,
                                         LatentAttention, RMSNorm, RoutedFFN,
                                         check_share, publish_moe_round,
                                         remat_block,
                                         route_sigmoid_bias_top_k)
from distkeras_tpu.ops.delta_rule import chunk_for
from distkeras_tpu.scopes import owner

#: the operator of each published layer: ``full_attn_layers`` 4, 8, ... 24 and
#: 27 (counted from 1) are ``mla``, the other twenty ``kda``
PUBLISHED_LAYER_TYPES = tuple(
    "mla" if l in (4, 8, 12, 16, 20, 24, 27) else "kda" for l in range(1, 28))


class KimiLinearBlock(nn.Module):
    operator: str          # 'kda' | 'mla'
    routed: Callable | None  # blocks.RoutedFFN with its fields; None: dense
    heads: int             # the heads held here
    kda_head_dim: int
    conv_kernel: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_head_dim: int
    kv_rank: int
    d_ff: int
    rms_eps: float
    attn_impl: str

    @nn.compact
    def __call__(self, x):
        h = RMSNorm(self.rms_eps, name="ln_op")(x)
        if self.operator == "kda":
            x = x + KimiDeltaAttention(self.heads, self.kda_head_dim,
                                       self.conv_kernel, self.rms_eps,
                                       name="kda")(h)
        else:
            x = x + LatentAttention(
                self.heads, self.qk_nope_dim, self.qk_rope_dim,
                self.v_head_dim, self.kv_rank, self.rms_eps, self.attn_impl,
                name="mla")(h)
        g = RMSNorm(self.rms_eps, name="ln_ffn")(x)
        if self.routed is None:
            return x + GatedMLP(self.d_ff, "silu", name="mlp")(g)
        return x + self.routed(name="routed")(g)


@register_model
class KimiLinearLM(DKModule):
    vocab_size: int = 163840
    num_layers: int = 27
    d_model: int = 2304
    num_heads: int = 32            # of every kda and mla layer, as published
    heads_held: tuple = (0, 32)    # (first id, count) of those heads
    kda_head_dim: int = 128        # linear_attn_config.head_dim: keys, values
    conv_kernel: int = 4           # linear_attn_config.short_conv_kernel_size
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    d_ff: int = 9216               # the dense layer's feed-forward
    d_expert: int = 1024
    num_experts: int = 256         # the router's width, as published
    experts_per_token: int = 8
    experts_held: tuple = (0, 256)  # (first id, count) of a layer's experts
    num_shared_experts: int = 1
    num_dense_layers: int = 1      # first_k_dense_replace
    layer_types: tuple = PUBLISHED_LAYER_TYPES  # per layer: the operator
    routed_scaling_factor: float = 2.446
    rms_eps: float = 1e-5
    embed_std: float = 0.02        # initialisation (the configuration's file)
    expert_bias_std: float = 0.02
    expert_bias_update: float = 5e-3  # a step's move of the bias; 0: as given
    attn_impl: str = "dense"
    remat: bool = False  # jax.checkpoint each layer: trade FLOPs for HBM

    def _kinds(self) -> list:
        return [(self.layer_types[l], l >= self.num_dense_layers)
                for l in range(self.num_layers)]

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        check_share("experts_held", self.experts_held, self.num_experts)
        check_share("heads_held", self.heads_held, self.num_heads)
        if len(self.layer_types) < self.num_layers \
                or set(self.layer_types) - {"kda", "mla"}:
            raise ValueError("layer_types needs 'kda' or 'mla' for each of "
                             f"{self.num_layers} layers, got "
                             f"{self.layer_types}")
        kinds = self._kinds()
        heads = self.heads_held[1]
        B, L = tokens.shape
        from distkeras_tpu import telemetry

        if self.is_initializing():
            telemetry.event("model.layer_kinds", {
                "model": type(self).__name__,
                "operators": [op for op, _ in kinds],
                "feed_forward": ["routed" if r else "dense"
                                 for _, r in kinds],
                "experts_held": list(self.experts_held),
                "heads_held": list(self.heads_held),
                "vocab_size": self.vocab_size})
        else:
            telemetry.gauge("kda.chunk").set(chunk_for(L))
            telemetry.gauge("kda.state_bytes").set(
                sum(op == "kda" for op, _ in kinds)
                * B * heads * self.kda_head_dim ** 2 * 4)
        with owner("embed"):
            x = nn.Embed(self.vocab_size, self.d_model, name="tok_embed",
                         embedding_init=nn.initializers.normal(
                             self.embed_std))(tokens)
        block_cls = KimiLinearBlock
        if self.remat:
            attention = sum(op == "mla" for op, _ in kinds)
            block_cls = remat_block(
                KimiLinearBlock, self,
                attention if self.attn_impl == "flash" else 0,
                B, L, heads, self.v_head_dim, x.dtype)
        routed = functools.partial(
            RoutedFFN, self.num_experts, tuple(self.experts_held),
            self.d_expert, "silu", functools.partial(
                route_sigmoid_bias_top_k, k=self.experts_per_token,
                scale=self.routed_scaling_factor, eps=1e-20),
            expert_bias_std=self.expert_bias_std,
            expert_bias_update=self.expert_bias_update,
            shared=functools.partial(
                GatedMLP, self.num_shared_experts * self.d_expert, "silu")
            if self.num_shared_experts else None)
        for l, (operator, is_routed) in enumerate(kinds):
            x = block_cls(
                operator, routed if is_routed else None, heads,
                self.kda_head_dim, self.conv_kernel, self.qk_nope_head_dim,
                self.qk_rope_head_dim, self.v_head_dim, self.kv_lora_rank,
                self.d_ff, self.rms_eps, self.attn_impl,
                name=f"block_{l}")(x)
        x = RMSNorm(self.rms_eps, name="ln_final")(x)
        with owner("head"):
            return nn.Dense(
                self.vocab_size, use_bias=False, name="head",
                kernel_init=nn.initializers.normal(self.embed_std))(x)

    def publish_round_counters(self, round_index: int, counters) -> None:
        """``moe.round`` from the routed layers' counts, and from the ``kda``
        layers' the gauges ``kda.min_chunk_decay`` (the round's smallest
        summed log-decay of a chunk and channel, over the layers) and
        ``kda.mean_beta``, and one ``kda.round`` event."""
        from distkeras_tpu import telemetry

        publish_moe_round(round_index, counters, self.experts_per_token)
        kda = [c["kda"] for _, c in sorted(counters.items()) if "kda" in c]
        if not kda:
            return
        least = [float(c["min_chunk_decay"]) for c in kda]
        beta = (sum(float(c["beta_sum"]) for c in kda)
                / max(sum(float(c["beta_count"]) for c in kda), 1.0))
        telemetry.gauge("kda.min_chunk_decay").set(min(least))
        telemetry.gauge("kda.mean_beta").set(beta)
        telemetry.event("kda.round", {
            "round": int(round_index), "layers": len(kda),
            "steps": float(kda[0]["steps"]),
            "min_chunk_decay": min(least),
            "min_chunk_decay_by_layer": least, "mean_beta": beta})


def small_kimi_linear_lm(seq_len: int = 64, seed: int = 0, **kwargs) -> Model:
    """A CPU-sized preset: a dense ``kda`` layer, then an ``mla`` and a
    ``kda`` layer with a shared expert beside two of eight routed ones, two of
    four heads held."""
    config = dict(vocab_size=128, num_layers=3, d_model=32, num_heads=4,
                  heads_held=(0, 2), kda_head_dim=16, qk_nope_head_dim=16,
                  qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=24,
                  d_ff=48, d_expert=16, num_experts=8, experts_per_token=2,
                  experts_held=(0, 2), num_dense_layers=1,
                  layer_types=("kda", "mla", "kda"))
    config.update(kwargs)
    return Model.build(KimiLinearLM(**config),
                       jnp.zeros((1, seq_len), jnp.int32), seed=seed)
