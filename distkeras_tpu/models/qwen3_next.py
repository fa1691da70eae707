"""The Qwen3-Next decoders (Qwen; ``model_type`` ``qwen3_next``, the published
``config.json`` of ``Qwen3-Next-80B-A3B-Instruct``): a stack of layer *kinds*.
Three layers in four mix the sequence with **Gated DeltaNet**, a gated delta
rule with one decay a head whose 32 value heads are served by 16 key heads;
the fourth is **gated attention** (16 query heads over 2 K/V heads of 256,
query and key heads RMS-normed, rotary positions on the first quarter of a
head's columns, the output multiplied by a sigmoid gate that ``W_q`` gives
beside each head's query). Every layer's feed-forward is a sparse mixture of
512 SwiGLU experts, 10 a token by softmax, **beside a shared expert weighed
by a sigmoid gate**. The head is its own matrix.

Layer ``l`` on the residual stream ``x`` ``[B, L, d_model]``, no bias in any
projection:

* ``x += Op_l(RMSNorm(x))`` with ``Op_l`` by ``layer_types[l]``: ``"gdn"``
  (``blocks.GatedDeltaNet``, the chunked form of ``ops/delta_rule.py``) or
  ``"attention"`` (``blocks.GroupedQueryAttention`` with ``qk_norm``,
  ``rotary_dim`` and ``output_gate``).
* ``x += FF_l(g)``, ``g = RMSNorm(x)``: ``p = softmax(g W_r)`` over all
  ``num_experts`` in float32, the ``k`` largest renormalised to sum to one
  (``norm_topk_prob``, ``blocks.route_top_k``), the sum over the chosen
  experts held here of ``w_k E_k(g)`` (``blocks.DroplessExperts``), plus
  ``sigmoid(g W_sg) * Shared(g)``; ``E`` and ``Shared`` both ``(silu(g
  W_gate) * (g W_up)) W_down``. The shared expert and its gate are computed
  once a token whatever was chosen, under the scope ``dk_moe_shared``.
* ``logits = RMSNorm(x) . W_head`` over the held rows of the vocabulary.

**What this chip holds** of a layer that several chips share:
``experts_held = (first, count)`` of the routed experts and ``vocab_size``
rows, as in the other expert models; the heads of both operators, the
router and the shared expert are whole on every chip. What the absent
experts would have added is left out. The feed-forward is
``blocks.RoutedFFN``, which says why a share of the experts does not train
its router.

``remat=True`` recomputes each layer in the backward pass, all but the flash
forward of the attention layers (``blocks.remat_block``). A round's routing,
the delta rule's decay and ``beta``, and the shared expert's gate leave the
program in ``ROUND_COUNTERS`` (:meth:`Qwen3NextLM.publish_round_counters`:
``moe.round`` and ``gdn.round``); the gauges ``gdn.chunk`` and
``gdn.state_bytes`` are set as the model is traced, and the held layer kinds
are written once as the event ``model.layer_kinds``.

Parameters do not depend on the sequence length: build with a short sample.
"""

from __future__ import annotations

import functools
from collections.abc import Callable

import jax.numpy as jnp
from flax import linen as nn

from distkeras_tpu.models.base import DKModule, Model, register_model
from distkeras_tpu.models.blocks import (GatedDeltaNet, GatedMLP,
                                         GroupedQueryAttention, RMSNorm,
                                         RoutedFFN, check_share,
                                         publish_moe_round, remat_block,
                                         route_top_k)
from distkeras_tpu.ops.delta_rule import chunk_for
from distkeras_tpu.scopes import owner

#: the operator of each published layer: ``full_attention_interval`` 4 makes
#: every fourth (counted from 1) gated attention, the others Gated DeltaNet
PUBLISHED_LAYER_TYPES = tuple(
    "attention" if (l + 1) % 4 == 0 else "gdn" for l in range(48))


class Qwen3NextBlock(nn.Module):
    operator: str          # 'gdn' | 'attention'
    routed: Callable       # blocks.RoutedFFN with its fields
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rotary_dim: int
    rope_theta: float
    linear_key_heads: int
    linear_value_heads: int
    linear_head_dim: int
    conv_kernel: int
    rms_eps: float
    attn_impl: str

    @nn.compact
    def __call__(self, x):
        h = RMSNorm(self.rms_eps, name="ln_op")(x)
        if self.operator == "gdn":
            x = x + GatedDeltaNet(
                self.linear_key_heads, self.linear_value_heads,
                self.linear_head_dim, self.conv_kernel, self.rms_eps,
                name="gdn")(h)
        else:
            x = x + GroupedQueryAttention(
                self.num_heads, self.num_kv_heads, self.head_dim,
                rope_theta=self.rope_theta, attn_impl=self.attn_impl,
                qk_norm=self.rms_eps, rotary_dim=self.rotary_dim,
                output_gate=True, name="attn")(h)
        g = RMSNorm(self.rms_eps, name="ln_ffn")(x)
        return x + self.routed(name="routed")(g)


@register_model
class Qwen3NextLM(DKModule):
    vocab_size: int = 151936
    num_layers: int = 48
    d_model: int = 2048
    num_heads: int = 16            # gated attention: query heads
    num_kv_heads: int = 2
    head_dim: int = 256
    rotary_dim: int = 64           # head_dim * partial_rotary_factor 0.25
    rope_theta: float = 1e7
    linear_key_heads: int = 16     # Gated DeltaNet: linear_num_key_heads
    linear_value_heads: int = 32   # linear_num_value_heads
    linear_head_dim: int = 128     # linear_key_head_dim = linear_value_head_dim
    conv_kernel: int = 4           # linear_conv_kernel_dim
    d_expert: int = 512            # moe_intermediate_size
    d_shared: int = 512            # shared_expert_intermediate_size
    num_experts: int = 512         # the router's width, as published
    experts_per_token: int = 10
    experts_held: tuple = (0, 512)  # (first id, count) of a layer's experts
    layer_types: tuple = PUBLISHED_LAYER_TYPES  # per layer: the operator
    rms_eps: float = 1e-6
    embed_std: float = 0.02        # initialisation (the configuration's file)
    attn_impl: str = "dense"
    remat: bool = False  # jax.checkpoint each layer: trade FLOPs for HBM

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        check_share("experts_held", self.experts_held, self.num_experts)
        if len(self.layer_types) < self.num_layers \
                or set(self.layer_types) - {"gdn", "attention"}:
            raise ValueError("layer_types needs 'gdn' or 'attention' for each "
                             f"of {self.num_layers} layers, got "
                             f"{self.layer_types}")
        kinds = list(self.layer_types[:self.num_layers])
        B, L = tokens.shape
        from distkeras_tpu import telemetry

        if self.is_initializing():
            telemetry.event("model.layer_kinds", {
                "model": type(self).__name__, "operators": kinds,
                "feed_forward": ["routed"] * len(kinds),
                "experts_held": list(self.experts_held),
                "vocab_size": self.vocab_size})
        else:
            telemetry.gauge("gdn.chunk").set(chunk_for(L))
            telemetry.gauge("gdn.state_bytes").set(
                kinds.count("gdn") * B * self.linear_value_heads
                * self.linear_head_dim ** 2 * 4)
        with owner("embed"):
            x = nn.Embed(self.vocab_size, self.d_model, name="tok_embed",
                         embedding_init=nn.initializers.normal(
                             self.embed_std))(tokens)
        block_cls = Qwen3NextBlock
        if self.remat:
            attention = kinds.count("attention")
            block_cls = remat_block(
                Qwen3NextBlock, self,
                attention if self.attn_impl == "flash" else 0,
                B, L, self.num_heads, self.head_dim, x.dtype)
        routed = functools.partial(
            RoutedFFN, self.num_experts, tuple(self.experts_held),
            self.d_expert, "silu",
            functools.partial(route_top_k, k=self.experts_per_token),
            shared=functools.partial(GatedMLP, self.d_shared, "silu"),
            shared_gate=True)
        for l, operator in enumerate(kinds):
            x = block_cls(
                operator, routed, self.num_heads, self.num_kv_heads,
                self.head_dim, self.rotary_dim, self.rope_theta,
                self.linear_key_heads, self.linear_value_heads,
                self.linear_head_dim, self.conv_kernel, self.rms_eps,
                self.attn_impl, name=f"block_{l}")(x)
        x = RMSNorm(self.rms_eps, name="ln_final")(x)
        with owner("head"):
            return nn.Dense(
                self.vocab_size, use_bias=False, name="head",
                kernel_init=nn.initializers.normal(self.embed_std))(x)

    def publish_round_counters(self, round_index: int, counters) -> None:
        """``moe.round`` from the expert layers' counts; from the ``gdn``
        layers the gauges ``gdn.min_chunk_decay`` (the round's smallest
        summed log-decay of a chunk, over the layers) and ``gdn.mean_beta``;
        the gauge ``moe.shared_gate_mean``; and one ``gdn.round`` event."""
        from distkeras_tpu import telemetry

        blocks = [c for _, c in sorted(counters.items())]
        publish_moe_round(round_index, counters, self.experts_per_token)
        gate = (sum(float(c["shared_gate_sum"]) for c in blocks)
                / max(sum(float(c["shared_gate_count"]) for c in blocks), 1.0))
        telemetry.gauge("moe.shared_gate_mean").set(gate)
        gdn = [c["gdn"] for c in blocks if "gdn" in c]
        least = [float(c["min_chunk_decay"]) for c in gdn]
        beta = (sum(float(c["beta_sum"]) for c in gdn)
                / max(sum(float(c["beta_count"]) for c in gdn), 1.0))
        if gdn:
            telemetry.gauge("gdn.min_chunk_decay").set(min(least))
            telemetry.gauge("gdn.mean_beta").set(beta)
        telemetry.event("gdn.round", {
            "round": int(round_index), "layers": len(gdn),
            "steps": float(blocks[0]["moe"]["steps"]),
            "min_chunk_decay": min(least) if least else 0.0,
            "min_chunk_decay_by_layer": least, "mean_beta": beta,
            "shared_gate_mean": gate})


def small_qwen3_next_lm(seq_len: int = 64, seed: int = 0, **kwargs) -> Model:
    """A CPU-sized preset: two Gated DeltaNet layers of two key heads over
    four value heads and a gated attention layer of four query heads over two
    K/V heads with a quarter rotated, each with two of eight routed experts
    held beside the gated shared expert."""
    config = dict(vocab_size=128, num_layers=3, d_model=32, num_heads=4,
                  num_kv_heads=2, head_dim=16, rotary_dim=4,
                  linear_key_heads=2, linear_value_heads=4, linear_head_dim=16,
                  d_expert=16, d_shared=16, num_experts=8,
                  experts_per_token=2, experts_held=(0, 2),
                  layer_types=("gdn", "gdn", "attention"))
    config.update(kwargs)
    return Model.build(Qwen3NextLM(**config),
                       jnp.zeros((1, seq_len), jnp.int32), seed=seed)
