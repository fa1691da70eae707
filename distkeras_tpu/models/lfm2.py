"""The LFM2 mixture-of-experts decoders (LiquidAI; ``model_type`` ``lfm2_moe``,
the published ``config.json`` of ``LFM2-24B-A2B``): a stack of layer *kinds*.
Three layers in four mix the sequence with a **gated short convolution** and
carry no attention at all; the fourth is grouped-query attention with an
RMSNorm on every query and key head. The leading layers' feed-forward is a
dense SwiGLU, the others' a sparse mixture of SwiGLU experts routed by
**sigmoid scores with a selection bias**. The head is the embedding.

Layer ``l`` on the residual stream ``x`` ``[B, L, d_model]``, no bias anywhere:

* ``x += Op_l(RMSNorm(x))`` with ``Op_l`` by ``layer_types[l]``: ``"conv"``
  (``blocks.GatedShortConv``: ``[Bg, Cg, u] = split_3(h W_in)``, a causal
  depthwise convolution of ``conv_kernel`` taps over ``Bg * u``, gated by
  ``Cg``, then ``W_out``) or ``"full_attention"``
  (``blocks.GroupedQueryAttention``: q/k RMSNorm a head, then RoPE by the
  half-split rule, causal, no window).
* ``x += FF_l(RMSNorm(x))``: for ``l < num_dense_layers`` ``(silu(g W_1) * (g
  W_3)) W_2`` of width ``d_ff``; after them ``p = sigmoid(g W_r)`` in float32,
  ``experts = top_k(p + b)`` with ``b`` the expert bias, ``w = p[experts]``,
  ``w <- w / (sum(w) + 1e-6) * routed_scaling_factor``, and the sum over the
  chosen experts held here of ``w_k E_k(g)``, ``E(g) = (silu(g W_gate) * (g
  W_up)) W_down`` (``blocks.DroplessExperts``). The router reads the
  *normalised* stream the experts read.
* ``logits = RMSNorm(x) . Embed^T`` over the held rows of the vocabulary.

``experts_held = (first, count)`` and ``vocab_size`` are this chip's share, as
in ``models/smallthinker.py``: the router keeps its published width and
``k``, the weights are normalised over all chosen experts held or not, and
embedding, head and loss are over the held rows. **A share does not train its
router** (``smallthinker.py`` says why): where fewer experts are held than
routed over, no gradient passes through the router's logits; a module that
holds them all trains it.

**The expert bias balances the load, without a gradient.** Top-k passes none,
so none reaches ``b``; it lives in the collection ``ROUTER_BIAS`` beside the
parameters, and a training step moves it by the rule of the families that
publish ``use_expert_bias`` (auxiliary-loss-free balancing): after routing a
step's tokens, ``b_e += expert_bias_update * sign(mean load - load_e)`` over
all ``num_experts``, the load counted over the tokens this module was given
(which in the benchmark's cell are those of all the chips that share a layer;
across chips the counts would be summed first, and that exchange is not
built). The speed (``expert_bias_update``) is not in ``config.json``: an
assumption, set by measurement. With it at 0 the bias stays as the seed drew
it, and a frozen router reading a stream that training moves drifts away from
an even load within a few rounds: on the chip, at the published widths from a
random start, the held experts took 0.7 to 1.6 times the even load and 3 to
5 times the mean on the worst expert of a layer, and the round's time moved
with it (PERF.md §6, PR 32). 1e-3 a step, the speed DeepSeek-V3's report
gives, does not hold the load through the first hundred steps of such a
start; 5e-3 holds every layer within a few percent of even from the third
round on.

``remat=True`` recomputes each layer in the backward pass, all but the flash
forward of the attention layers (``blocks.remat_block``). What a round routed
and what the bias moved leave the program in ``ROUND_COUNTERS``
(:meth:`Lfm2MoeLM.publish_round_counters`); the held pattern of layer kinds is
written once, as the model is built, as the event ``model.layer_kinds``.

Parameters do not depend on the sequence length: build with a short sample
(``Model.build`` runs the dense attention path).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn

from distkeras_tpu.models.base import (ROUND_COUNTERS, DKModule, Model,
                                       register_model)
from distkeras_tpu.models.blocks import (DroplessExperts, GatedMLP,
                                         GatedShortConv,
                                         GroupedQueryAttention, RMSNorm,
                                         Router, publish_moe_round,
                                         remat_block,
                                         route_sigmoid_bias_top_k)
from distkeras_tpu.scopes import owner

#: the collection that holds each routed layer's expert bias: state a training
#: step updates (like BatchNorm's statistics), never a gradient's
ROUTER_BIAS = "router_bias"

#: ``layer_types`` as published: two ``conv`` layers, then periods of one
#: attention layer and three ``conv`` layers, 40 in all
PUBLISHED_LAYER_TYPES = ("conv", "conv") + (
    "full_attention", "conv", "conv", "conv") * 9 + ("full_attention", "conv")


class Lfm2Block(nn.Module):
    operator: str          # 'conv' | 'full_attention'
    routed: bool           # a mixture of experts, or the dense feed-forward
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    d_expert: int
    num_experts: int
    experts_per_token: int
    experts_held: tuple
    routed_scaling_factor: float
    expert_bias_std: float
    expert_bias_update: float
    conv_kernel: int
    rope_theta: float
    rms_eps: float
    attn_impl: str

    @nn.compact
    def __call__(self, x):
        B, L, D = x.shape
        h = RMSNorm(self.rms_eps, name="ln_op")(x)
        if self.operator == "conv":
            x = x + GatedShortConv(self.conv_kernel, name="conv")(h)
        else:
            x = x + GroupedQueryAttention(
                self.num_heads, self.num_kv_heads, self.head_dim,
                rope_theta=self.rope_theta, attn_impl=self.attn_impl,
                qk_norm=self.rms_eps, name="attn")(h)
        g = RMSNorm(self.rms_eps, name="ln_ffn")(x)
        if not self.routed:
            return x + GatedMLP(self.d_ff, "silu", name="mlp")(g)
        first, held = self.experts_held
        g = g.reshape(B * L, D)
        with owner("ffn"), jax.named_scope("dk_moe_route"):
            logits = Router(self.num_experts, name="router")(g)
            if held < self.num_experts:
                # A share does not train its router (the module doc says why).
                logits = jax.lax.stop_gradient(logits)
            bias = self.variable(
                ROUTER_BIAS, "expert_bias",
                lambda: nn.initializers.normal(self.expert_bias_std)(
                    self.make_rng("params"), (self.num_experts,)))
            weights, experts, moved = route_sigmoid_bias_top_k(
                logits, bias.value, self.experts_per_token,
                self.routed_scaling_factor)
            if self.expert_bias_update and not self.is_initializing() \
                    and self.is_mutable_collection(ROUTER_BIAS):
                # A training step: the next one chooses with a bias moved
                # against this one's load (the module doc has the rule).
                load = jnp.sum(experts.reshape(-1, 1)
                               == jnp.arange(self.num_experts), axis=0,
                               dtype=jnp.float32)
                bias.value = bias.value + self.expert_bias_update * jnp.sign(
                    jnp.mean(load) - load)
        if self.is_mutable_collection(ROUND_COUNTERS):
            count = self.variable(ROUND_COUNTERS, "assignments_moved_by_bias",
                                  lambda: jnp.zeros((), jnp.float32))
            if not self.is_initializing():  # init declares it, at zero
                count.value = count.value + jnp.sum(moved, dtype=jnp.float32)
        # For whoever asks (`mutable=["intermediates"]`): the reference check
        # needs to know which experts this side chose (smallthinker.py).
        self.sow("intermediates", "experts", experts.reshape(B, L, -1))
        y = DroplessExperts(first, held, D, self.d_expert, "silu",
                            name="moe")(g, weights, experts)
        return x + y.reshape(B, L, D)


@register_model
class Lfm2MoeLM(DKModule):
    vocab_size: int = 65536
    num_layers: int = 40
    d_model: int = 2048
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    d_ff: int = 11776              # the dense layers' feed-forward
    d_expert: int = 1536
    num_experts: int = 64          # the router's width, as published
    experts_per_token: int = 4
    experts_held: tuple = (0, 64)  # (first id, count) of every layer's experts
    num_dense_layers: int = 2      # leading layers with the dense feed-forward
    layer_types: tuple = PUBLISHED_LAYER_TYPES  # per layer: the operator
    conv_kernel: int = 3           # conv_L_cache
    routed_scaling_factor: float = 1.0
    rope_theta: float = 1e6
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
        first, held = self.experts_held
        if not 0 <= first <= first + held <= self.num_experts or held < 1:
            raise ValueError(f"experts_held {self.experts_held} is no share "
                             f"of {self.num_experts} experts")
        if len(self.layer_types) < self.num_layers \
                or set(self.layer_types) - {"conv", "full_attention"}:
            raise ValueError("layer_types needs 'conv' or 'full_attention' "
                             f"for each of {self.num_layers} layers, got "
                             f"{self.layer_types}")
        kinds = self._kinds()
        if self.is_initializing():
            from distkeras_tpu import telemetry

            telemetry.event("model.layer_kinds", {
                "model": type(self).__name__,
                "operators": [op for op, _ in kinds],
                "feed_forward": ["routed" if r else "dense"
                                 for _, r in kinds],
                "experts_held": [first, held], "vocab_size": self.vocab_size})
        embed = nn.Embed(self.vocab_size, self.d_model, name="tok_embed",
                         embedding_init=nn.initializers.normal(self.embed_std))
        with owner("embed"):
            x = embed(tokens)
        block_cls = Lfm2Block
        if self.remat:
            attention = sum(op == "full_attention" for op, _ in kinds)
            block_cls = remat_block(
                Lfm2Block, self,
                attention if self.attn_impl == "flash" else 0,
                *tokens.shape, self.num_heads, self.head_dim, x.dtype)
        for l, (operator, routed) in enumerate(kinds):
            x = block_cls(
                operator, routed, self.num_heads, self.num_kv_heads,
                self.head_dim, self.d_ff, self.d_expert, self.num_experts,
                self.experts_per_token, (first, held),
                self.routed_scaling_factor, self.expert_bias_std,
                self.expert_bias_update, self.conv_kernel, self.rope_theta,
                self.rms_eps, self.attn_impl, name=f"block_{l}")(x)
        x = RMSNorm(self.rms_eps, name="ln_final")(x)
        with owner("head"):
            return embed.attend(x)

    def publish_round_counters(self, round_index: int, counters) -> None:
        publish_moe_round(round_index, counters, self.experts_per_token)


def small_lfm2_lm(seq_len: int = 64, seed: int = 0, **kwargs) -> Model:
    """A CPU-sized preset: a dense ``conv`` layer, then an attention and a
    ``conv`` layer with two of eight experts held."""
    config = dict(vocab_size=128, num_layers=3, d_model=32, num_heads=4,
                  num_kv_heads=2, head_dim=8, d_ff=48, d_expert=16,
                  num_experts=8, experts_per_token=2, experts_held=(0, 2),
                  num_dense_layers=1,
                  layer_types=("conv", "full_attention", "conv"))
    config.update(kwargs)
    return Model.build(Lfm2MoeLM(**config),
                       jnp.zeros((1, seq_len), jnp.int32), seed=seed)
