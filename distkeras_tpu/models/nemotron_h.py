"""The Nemotron-H decoders (NVIDIA; ``model_type`` ``nemotron_h``, the published
``config.json`` of ``NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``): a stack of layers
that each hold **one** sublayer, of three kinds by ``hybrid_override_pattern``:
``M``, a **Mamba-2 state-space mixer** (64 heads of 64 over a ``[128, 64]``
state a head, ``B`` and ``C`` in 8 groups, a scalar decay a head and no delta
rule); ``E``, a sparse mixture of 128 **ReLU^2 experts, not gated**, 6 a
token by sigmoid scores with a selection bias, **beside a ReLU^2 shared
expert**; ``*``, grouped-query attention (32 query heads over 2 K/V heads of
128) **without positions**. The head is its own matrix.

Layer ``l`` on the residual stream ``x`` ``[B, L, d_model]``:
``x += Op_l(RMSNorm(x))``, ``Op_l`` by ``layer_types[l]``:

* ``"mamba"``: ``blocks.Mamba2Mixer`` (the chunked form of
  ``ops/delta_rule.py::chunked_ssd``);
* ``"moe"``: ``p = sigmoid(h W_r)`` in float32, ``experts = top_k(p + b)``
  with ``b`` the correction bias, ``w = p[experts]``, ``w <- w / (sum(w) +
  1e-20) * routed_scaling_factor``, and ``Shared(h) +`` the sum over the
  chosen experts held here of ``w_k E_k(h)`` (``blocks.DroplessExperts(...,
  gated=False)``); ``E`` and ``Shared`` both ``relu(h W_up)^2 W_down``, the
  shared expert computed once a token whatever was chosen, under the scope
  ``dk_moe_shared``. One group of all experts (``n_group`` = ``topk_group`` =
  1) makes the published grouped top-k the plain one;
* ``"attention"``: ``blocks.GroupedQueryAttention`` with ``rope_theta=None``
  (the published modelling code rotates nothing).

Then ``logits = RMSNorm(x) . W_head`` over the held rows of the vocabulary.

**What this chip holds** of a layer that several chips share:
``experts_held = (first, count)`` of the routed experts and ``vocab_size``
rows, as in the other expert models; ``mamba_heads_held = (first, count)``
of the ``mamba_num_heads`` heads of every ``mamba`` layer, in whole groups
(``B``, ``C`` and the gated norm's groups go with their heads, so the norm
never crosses chips), and ``heads_held = (first, count)`` of the attention's
``num_heads`` query heads with the K/V heads they read. ``W_in``'s columns and
``W_out``'s rows are the held heads'; the router, the shared expert and the
norms are whole on every chip (a width is never cut). What the absent heads
and experts would have added is left out; the sum over the chips that share a
layer's heads (an all-reduce after ``W_out`` and ``W_o``) is not built. The
``moe`` layer is ``blocks.RoutedFFN``, which says why a share of the experts
does not train its router and how the correction bias moves against the
load.

``remat=True`` recomputes each layer in the backward pass, all but the flash
forward of the attention layers (``blocks.remat_block``). A round's routing and
the state-space layers' decay and ``dt`` leave the program in
``ROUND_COUNTERS`` (:meth:`NemotronHLM.publish_round_counters`: ``moe.round``
and ``ssd.round``); the gauges ``ssd.chunk`` and ``ssd.state_bytes`` are set
as the model is traced, and the held layer kinds are written once as the
event ``model.layer_kinds``.

Parameters do not depend on the sequence length: build with a short sample.
"""

from __future__ import annotations

import functools
from collections.abc import Callable

import jax.numpy as jnp
from flax import linen as nn

from distkeras_tpu.models.base import DKModule, Model, register_model
from distkeras_tpu.models.blocks import (GroupedQueryAttention, Mamba2Mixer,
                                         PlainMLP, RMSNorm, RoutedFFN,
                                         check_share, publish_moe_round,
                                         remat_block,
                                         route_sigmoid_bias_top_k)
from distkeras_tpu.ops.delta_rule import chunk_for
from distkeras_tpu.scopes import owner

#: the letters of ``hybrid_override_pattern`` and the layer kinds they name
KINDS = {"M": "mamba", "E": "moe", "*": "attention"}
#: the published ``hybrid_override_pattern``, 52 layers
PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
PUBLISHED_LAYER_TYPES = tuple(KINDS[c] for c in PUBLISHED_PATTERN)


class NemotronHBlock(nn.Module):
    kind: str              # 'mamba' | 'moe' | 'attention'
    routed: Callable       # blocks.RoutedFFN with its fields
    num_heads: int         # attention: query heads held
    num_kv_heads: int      # attention: K/V heads held
    head_dim: int
    mamba_heads: int       # Mamba-2 heads held
    mamba_groups: int      # their groups of B and C
    mamba_first: int
    mamba_head_dim: int
    state_dim: int
    conv_kernel: int
    rms_eps: float
    attn_impl: str

    @nn.compact
    def __call__(self, x):
        h = RMSNorm(self.rms_eps, name="norm")(x)
        if self.kind == "mamba":
            return x + Mamba2Mixer(
                self.mamba_heads, self.mamba_groups, self.mamba_first,
                self.mamba_head_dim, self.state_dim, self.conv_kernel,
                self.rms_eps, name="mixer")(h)
        if self.kind == "attention":
            return x + GroupedQueryAttention(
                self.num_heads, self.num_kv_heads, self.head_dim,
                attn_impl=self.attn_impl, name="attn")(h)
        return x + self.routed(name="routed")(h)


@register_model
class NemotronHLM(DKModule):
    vocab_size: int = 131072
    num_layers: int = 52
    d_model: int = 2688
    num_heads: int = 32            # attention: query heads, as published
    num_kv_heads: int = 2
    head_dim: int = 128
    heads_held: tuple = (0, 32)    # (first id, count) of the query heads
    mamba_num_heads: int = 64      # as published
    mamba_head_dim: int = 64
    mamba_heads_held: tuple = (0, 64)  # (first id, count), whole groups
    n_groups: int = 8              # groups of B and C, and of the gated norm
    ssm_state_size: int = 128
    conv_kernel: int = 4
    d_expert: int = 1856           # moe_intermediate_size
    d_shared: int = 3712           # moe_shared_expert_intermediate_size
    num_experts: int = 128         # the router's width, as published
    experts_per_token: int = 6
    experts_held: tuple = (0, 128)  # (first id, count) of a layer's experts
    routed_scaling_factor: float = 2.5
    layer_types: tuple = PUBLISHED_LAYER_TYPES  # per layer: its one sublayer
    rms_eps: float = 1e-5
    embed_std: float = 0.02        # initialisation (the configuration's file)
    expert_bias_std: float = 0.02
    expert_bias_update: float = 5e-3  # a step's move of the bias; 0: as given
    attn_impl: str = "dense"
    remat: bool = False  # jax.checkpoint each layer: trade FLOPs for HBM

    def _shares(self) -> dict:
        """What a ``mamba`` and an ``attention`` layer hold, checked: whole
        groups of Mamba heads, and query heads that read whole K/V heads or
        lie inside one K/V head's group (which every chip that reads it
        holds whole)."""
        per_group = self.mamba_num_heads // self.n_groups
        per_kv = self.num_heads // self.num_kv_heads
        query = self.heads_held[1]
        grain = min(per_kv, query)
        check_share("experts_held", self.experts_held, self.num_experts)
        check_share("mamba_heads_held", self.mamba_heads_held,
                    self.mamba_num_heads, per_group)
        check_share("heads_held", self.heads_held, self.num_heads, grain)
        if per_kv % grain:  # inside one K/V head's group, shares divide it
            raise ValueError(f"heads_held {tuple(self.heads_held)} is no "
                             f"share of {self.num_heads} in whole groups of "
                             f"{grain}")
        return {"mamba_heads": self.mamba_heads_held[1],
                "mamba_groups": self.mamba_heads_held[1] // per_group,
                "mamba_first": self.mamba_heads_held[0],
                "num_heads": query,
                "num_kv_heads": max(query // per_kv, 1)}

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        shares = self._shares()
        if len(self.layer_types) < self.num_layers \
                or set(self.layer_types) - set(KINDS.values()):
            raise ValueError("layer_types needs 'mamba', 'moe' or 'attention' "
                             f"for each of {self.num_layers} layers, got "
                             f"{self.layer_types}")
        kinds = list(self.layer_types[:self.num_layers])
        B, L = tokens.shape
        from distkeras_tpu import telemetry

        if self.is_initializing():
            telemetry.event("model.layer_kinds", {
                "model": type(self).__name__, "operators": kinds,
                "feed_forward": ["routed" if k == "moe" else "none"
                                 for k in kinds],
                "experts_held": list(self.experts_held),
                "heads_held": list(self.heads_held),
                "mamba_heads_held": list(self.mamba_heads_held),
                "vocab_size": self.vocab_size})
        else:
            telemetry.gauge("ssd.chunk").set(chunk_for(L))
            telemetry.gauge("ssd.state_bytes").set(
                kinds.count("mamba") * B * shares["mamba_heads"]
                * self.ssm_state_size * self.mamba_head_dim * 4)
        with owner("embed"):
            x = nn.Embed(self.vocab_size, self.d_model, name="tok_embed",
                         embedding_init=nn.initializers.normal(
                             self.embed_std))(tokens)
        block_cls = NemotronHBlock
        if self.remat:
            attention = kinds.count("attention")
            block_cls = remat_block(
                NemotronHBlock, self,
                attention if self.attn_impl == "flash" else 0,
                B, L, shares["num_heads"], self.head_dim, x.dtype)
        routed = functools.partial(
            RoutedFFN, self.num_experts, tuple(self.experts_held),
            self.d_expert, "relu2", functools.partial(
                route_sigmoid_bias_top_k, k=self.experts_per_token,
                scale=self.routed_scaling_factor, eps=1e-20),
            gated=False, expert_bias_std=self.expert_bias_std,
            expert_bias_update=self.expert_bias_update,
            shared=functools.partial(PlainMLP, self.d_shared, "relu2"))
        for l, kind in enumerate(kinds):
            x = block_cls(
                kind, routed, shares["num_heads"], shares["num_kv_heads"],
                self.head_dim, shares["mamba_heads"], shares["mamba_groups"],
                shares["mamba_first"], self.mamba_head_dim,
                self.ssm_state_size, self.conv_kernel, self.rms_eps,
                self.attn_impl, name=f"block_{l}")(x)
        x = RMSNorm(self.rms_eps, name="ln_final")(x)
        with owner("head"):
            return nn.Dense(
                self.vocab_size, use_bias=False, name="head",
                kernel_init=nn.initializers.normal(self.embed_std))(x)

    def publish_round_counters(self, round_index: int, counters) -> None:
        """``moe.round`` from the ``moe`` layers' counts, and from the
        ``mamba`` layers' the gauges ``ssd.min_chunk_decay`` (the round's
        smallest summed log-decay of a chunk, over the layers) and
        ``ssd.mean_dt``, and one ``ssd.round`` event."""
        from distkeras_tpu import telemetry

        publish_moe_round(round_index, counters, self.experts_per_token)
        ssd = [c["mixer"] for _, c in sorted(counters.items())
               if "mixer" in c]
        if not ssd:
            return
        least = [float(c["min_chunk_decay"]) for c in ssd]
        dt = (sum(float(c["dt_sum"]) for c in ssd)
              / max(sum(float(c["dt_count"]) for c in ssd), 1.0))
        telemetry.gauge("ssd.min_chunk_decay").set(min(least))
        telemetry.gauge("ssd.mean_dt").set(dt)
        telemetry.event("ssd.round", {
            "round": int(round_index), "layers": len(ssd),
            "steps": float(ssd[0]["steps"]),
            "min_chunk_decay": min(least),
            "min_chunk_decay_by_layer": least, "mean_dt": dt})


def small_nemotron_h_lm(seq_len: int = 64, seed: int = 0, **kwargs) -> Model:
    """A CPU-sized preset: one period of ``E M E M E M *`` as the
    configuration holds it, each ``moe`` layer with two of eight routed
    experts beside the shared expert, four of eight Mamba heads (two of four
    groups) and two of four attention heads (one of two K/V heads) held."""
    config = dict(vocab_size=128, num_layers=7, d_model=32, num_heads=4,
                  num_kv_heads=2, head_dim=16, heads_held=(0, 2),
                  mamba_num_heads=8, mamba_head_dim=8,
                  mamba_heads_held=(0, 4), n_groups=4, ssm_state_size=16,
                  d_expert=16, d_shared=32, num_experts=8,
                  experts_per_token=2, experts_held=(0, 2),
                  layer_types=("moe", "mamba") * 3 + ("attention",))
    config.update(kwargs)
    return Model.build(NemotronHLM(**config),
                       jnp.zeros((1, seq_len), jnp.int32), seed=seed)
