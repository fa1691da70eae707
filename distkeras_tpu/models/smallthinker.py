"""SmallThinker (PowerInfer, 2025; arXiv:2507.20984): a decoder whose every
layer is a sparse mixture of ReGLU experts routed from the block's *input*
(the router sits before attention), with grouped-query attention that is
full and without position encoding in one layer of four and windowed with
RoPE in the other three.

Block ``l`` on ``x`` ``[B, L, d_model]``, no projection with a bias:

* ``(w, e) = top_k(softmax(x W_router))`` over all ``num_experts``, ``w``
  renormalised over the ``k`` chosen; read from the un-normalised ``x``.
* ``x += GQA(RMSNorm(x)) W_o``: ``rope_layout[l]`` switches RoPE (theta
  ``rope_theta``, half-split rule), ``window_layout[l]`` the window (query
  ``i`` sees ``0 <= i - j < window``).
* ``x += sum over the chosen experts held here of w_k E_k(RMSNorm(x))``,
  ``E(g) = (relu(g W_gate) * (g W_up)) W_down``.

``experts_held = (first, count)`` is this chip's share of every layer's
experts (``models/blocks.py::DroplessExperts``): the router keeps its
published width and ``k``, the weights are normalised over all chosen experts
held or not, and what the absent experts would have added is left out.
``vocab_size`` is the rows of the vocabulary held here: embedding, head and
loss are over that slice, and the data draws its ids from it.

**A share does not train its router.** Where fewer experts are held than
routed over, no gradient passes through the router's logits. The gradient a
share can compute is the held experts' part of the router's, and the absent
experts' part is what balances it: applied alone, it sends every token to the
held experts (measured on the chip at the published widths, Adam 3e-4: by the
second round of four steps one layer routed all 98,304 of a step's 98,304
assignments to its 8 held experts, and the round's time swung by 17 % with
the load; PERF.md §6, PR 28). In the deployment that part arrives with the
exchange this cut leaves out, so the share routes with the router it was
given, and the load it sees is the deployment's. A module that holds every
expert trains its router as any other parameter.

``remat=True`` recomputes each block in the backward pass, all but the flash
kernel's forward: its ``out`` and ``lse`` are kept from the first pass (2 B x
tokens x heads x head_dim a layer in bfloat16, and 4 B a token and head), so
``dk_flash_fwd`` runs once a layer. The gauge ``remat.flash_residual_bytes``
says how much that is a step.

Parameters do not depend on the sequence length: build with a short sample
(``Model.build`` runs the dense attention path, whose scores at L = 8192
would be ``[28, 8192, 8192]`` float32).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn

from distkeras_tpu.models.base import DKModule, Model, register_model
from distkeras_tpu.models.blocks import (DroplessExperts,
                                         GroupedQueryAttention, RMSNorm,
                                         Router, publish_moe_round,
                                         remat_block, route_top_k)
from distkeras_tpu.scopes import owner


class SmallThinkerBlock(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_expert: int
    num_experts: int
    experts_per_token: int
    experts_held: tuple
    window: int | None
    rope_theta: float | None
    rms_eps: float
    attn_impl: str

    @nn.compact
    def __call__(self, x):
        B, L, D = x.shape
        first, held = self.experts_held
        with owner("ffn"), jax.named_scope("dk_moe_route"):
            logits = Router(self.num_experts, name="router")(
                x.reshape(B * L, D))
            if held < self.num_experts:
                # A share does not train its router (the module doc says why).
                logits = jax.lax.stop_gradient(logits)
            weights, experts = route_top_k(logits, self.experts_per_token)
        # For whoever asks (`mutable=["intermediates"]`): top-k is
        # discontinuous, and a comparison in another precision needs to know
        # which experts this side chose.
        self.sow("intermediates", "experts", experts.reshape(B, L, -1))
        h = RMSNorm(self.rms_eps, name="ln_attn")(x)
        x = x + GroupedQueryAttention(
            self.num_heads, self.num_kv_heads, self.head_dim,
            window=self.window, rope_theta=self.rope_theta,
            attn_impl=self.attn_impl, name="attn")(h)
        g = RMSNorm(self.rms_eps, name="ln_moe")(x)
        y = DroplessExperts(first, held, D, self.d_expert, name="moe")(
            g.reshape(B * L, D), weights, experts)
        return x + y.reshape(B, L, D)


@register_model
class SmallThinkerLM(DKModule):
    vocab_size: int = 151936
    num_layers: int = 52
    d_model: int = 2560
    num_heads: int = 28
    num_kv_heads: int = 4
    head_dim: int = 128
    d_expert: int = 768
    num_experts: int = 64          # the router's width, as published
    experts_per_token: int = 6
    experts_held: tuple = (0, 64)  # (first id, count) of every layer's experts
    rope_layout: tuple = (0, 1, 1, 1) * 13     # per layer: rotate q and k
    window_layout: tuple = (0, 1, 1, 1) * 13   # per layer: sliding window
    window: int = 4096
    rope_theta: float = 1.5e6
    rms_eps: float = 1e-6
    attn_impl: str = "dense"
    remat: bool = False  # jax.checkpoint each block: trade FLOPs for HBM

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        first, held = self.experts_held
        if not 0 <= first <= first + held <= self.num_experts or held < 1:
            raise ValueError(f"experts_held {self.experts_held} is no share "
                             f"of {self.num_experts} experts")
        if min(len(self.rope_layout), len(self.window_layout)) \
                < self.num_layers:
            raise ValueError("rope_layout and window_layout need an entry a "
                             f"layer ({self.num_layers})")
        # N(0, 1) rows: the stream the router reads stays the token's own for
        # the layers a share holds. flax's default (std 1/sqrt(d_model)) is a
        # fiftieth of what one block adds to it, after which every token's
        # stream is the same vector and every token chooses the same experts.
        with owner("embed"):
            x = nn.Embed(self.vocab_size, self.d_model, name="tok_embed",
                         embedding_init=nn.initializers.normal(1.0))(tokens)
        block_cls = SmallThinkerBlock
        if self.remat:
            block_cls = remat_block(
                SmallThinkerBlock, self,
                self.num_layers if self.attn_impl == "flash" else 0,
                *tokens.shape, self.num_heads, self.head_dim, x.dtype)
        for l in range(self.num_layers):
            x = block_cls(
                self.num_heads, self.num_kv_heads, self.head_dim,
                self.d_expert, self.num_experts, self.experts_per_token,
                (first, held),
                window=self.window if self.window_layout[l] else None,
                rope_theta=self.rope_theta if self.rope_layout[l] else None,
                rms_eps=self.rms_eps, attn_impl=self.attn_impl,
                name=f"block_{l}")(x)
        x = RMSNorm(self.rms_eps, name="ln_final")(x)
        with owner("head"):
            return nn.Dense(self.vocab_size, use_bias=False,
                            name="lm_head")(x)

    def publish_round_counters(self, round_index: int, counters) -> None:
        publish_moe_round(round_index, counters, self.experts_per_token)


def small_smallthinker_lm(seq_len: int = 64, seed: int = 0, **kwargs) -> Model:
    """A CPU-sized preset: two layers (one full, one windowed and rotated), a
    window shorter than the sequence, two of eight experts held."""
    config = dict(vocab_size=128, num_layers=2, d_model=32, num_heads=4,
                  num_kv_heads=2, head_dim=8, d_expert=16, num_experts=8,
                  experts_per_token=2, experts_held=(0, 2),
                  rope_layout=(0, 1), window_layout=(0, 1), window=32)
    config.update(kwargs)
    return Model.build(SmallThinkerLM(**config),
                       jnp.zeros((1, seq_len), jnp.int32), seed=seed)
