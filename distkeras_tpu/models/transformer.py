"""Decoder-only transformer LM — the flagship model for multi-axis sharding.

The reference (2016-era MLPs/CNNs/LSTMs) has nothing like this; it exists because the
rebuild treats long-context + model parallelism as first-class. Design points:

* Pre-LN blocks, GELU MLP, learned positional embeddings; all matmuls MXU-shaped.
* ``nn.DenseGeneral`` projections named ``query/key/value/out`` so tensor-parallel
  PartitionSpecs can target the head axis (see ``parallel/sharding.py``).
* Sequence parallelism: when ``seq_axis`` is set and the module runs inside a
  ``shard_map`` whose mesh has that axis, activations arrive sequence-sharded
  ``[B, L/S, D]``. Attention then either all-gathers K/V (``attn_impl='gather'``) or
  streams K/V blocks around the ring with ``ppermute`` (``attn_impl='ring'``, see
  ``ops/ring_attention.py``); positions/causal masks are computed from the global
  offset ``axis_index(seq_axis) * local_len``.
* ``remat=True`` recomputes each block in the backward pass from its input,
  all but the flash kernel's forward (``attn_impl='flash'``): its ``out`` and
  ``lse`` are kept from the first pass, so ``dk_flash_fwd`` runs once a layer.
  That costs 2 B x tokens x heads x head_dim a layer in bfloat16 (and 4 B a
  token and head); the gauge ``remat.flash_residual_bytes`` says how much it
  is a step: 0.42 GB at GPT-2 medium's sizes (8 x 1024 tokens, 24 layers),
  where it fits beside the rest (PERF.md §6, PR 31, has the measurement).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from distkeras_tpu.models.base import DKModule, Model, register_model
from distkeras_tpu.models.blocks import remat_block
from distkeras_tpu.runtime.mesh import MODEL_AXIS
from distkeras_tpu.scopes import owner


def _axis_is_auto(abstract_mesh, name: str) -> bool:
    """True if ``name`` is a GSPMD-managed (Auto) axis of the ambient mesh."""
    types = dict(zip(abstract_mesh.axis_names, abstract_mesh.axis_types))
    return types[name] == jax.sharding.AxisType.Auto


def _global_positions(local_len: int, seq_axis: Optional[str]) -> jax.Array:
    pos = jnp.arange(local_len)
    if seq_axis is not None:
        pos = pos + jax.lax.axis_index(seq_axis) * local_len
    return pos


def _flash_block(L: int) -> int:
    """The flash kernel's grain for sequence length ``L``: every tile edge
    is a multiple of it, and the kernel sizes its tiles from ``L`` and the
    head width itself (``flash_attention.default_tiling``). The Mosaic
    kernel needs lane-aligned blocks (L a multiple of 128); the interpreter
    also accepts any single short block. Anything else is an error naming
    ``L`` — never a quiet switch to the O(L^2) dense path."""
    from distkeras_tpu.ops.pallas import mode

    if L % 128 == 0:
        return 128
    if L < 128 and not mode.compiles():
        return L
    raise ValueError(
        f"attn_impl='flash' needs a sequence length that is a multiple of "
        f"128 (the Mosaic kernel's lane-aligned block), got L={L}; pad the "
        "sequences or build the model with attn_impl='dense'")


class CausalSelfAttention(nn.Module):
    num_heads: int
    d_model: int
    seq_axis: Optional[str] = None
    attn_impl: str = "dense"  # 'dense' | 'gather' | 'ring'

    @nn.compact
    def __call__(self, x, train: bool = False):
        with owner("mixer"):
            B, L, D = x.shape
            H = self.num_heads
            Dh = D // H
            q = nn.DenseGeneral((H, Dh), name="query")(x)
            k = nn.DenseGeneral((H, Dh), name="key")(x)
            v = nn.DenseGeneral((H, Dh), name="value")(x)
            q = q / jnp.sqrt(Dh).astype(q.dtype)

            if self.seq_axis is not None and self.attn_impl == "ring":
                from distkeras_tpu.ops.ring_attention import ring_attention

                out = ring_attention(q, k, v, axis_name=self.seq_axis)
            elif (self.seq_axis is None and self.attn_impl == "flash"
                  and not self.is_initializing()):
                # Init only declares params (attention has none of its own), so
                # Model.build's shape-inference pass — any L, often a (1, 1)
                # dummy, eagerly on the default device — takes the numerically
                # identical dense path below instead of compiling the kernel.
                from distkeras_tpu.ops.pallas import flash_attention

                block = _flash_block(L)

                def fa(q, k, v):
                    return flash_attention(q, k, v, block_size=block)

                # Tensor parallelism: a Mosaic kernel cannot be GSPMD-auto-
                # partitioned, so when the ambient mesh carries an (auto) model
                # axis we manualize it locally — each shard runs flash on its own
                # heads (attention has no cross-head communication). Works inside
                # the SPMD engine's partially-manual region via nested shard_map.
                am = jax.sharding.get_abstract_mesh()
                if (MODEL_AXIS in am.axis_names and am.shape[MODEL_AXIS] > 1
                        and _axis_is_auto(am, MODEL_AXIS)):
                    from jax.sharding import PartitionSpec as P

                    spec = P(None, None, MODEL_AXIS, None)
                    fa = jax.shard_map(fa, mesh=am, in_specs=(spec, spec, spec),
                                       out_specs=spec, axis_names={MODEL_AXIS},
                                       check_vma=False)
                out = fa(q, k, v)
            else:
                q_pos = _global_positions(L, self.seq_axis)
                if self.seq_axis is not None:
                    # 'gather' sequence parallelism: K/V become global, Q stays local.
                    k = jax.lax.all_gather(k, self.seq_axis, axis=1, tiled=True)
                    v = jax.lax.all_gather(v, self.seq_axis, axis=1, tiled=True)
                k_pos = jnp.arange(k.shape[1])
                scores = jnp.einsum("bqhd,bkhd->bhqk", q, k)
                mask = q_pos[:, None] >= k_pos[None, :]
                scores = jnp.where(mask[None, None, :, :], scores, jnp.finfo(scores.dtype).min)
                probs = jax.nn.softmax(scores, axis=-1)
                out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
            return nn.DenseGeneral(D, axis=(-2, -1), name="out")(out)


class TransformerBlock(nn.Module):
    num_heads: int
    d_model: int
    d_ff: int
    dropout_rate: float = 0.0
    seq_axis: Optional[str] = None
    attn_impl: str = "dense"

    @nn.compact
    def __call__(self, x, train: bool = False):
        with owner("norm"):
            h = nn.LayerNorm(name="ln_attn")(x)
        h = CausalSelfAttention(
            self.num_heads, self.d_model, seq_axis=self.seq_axis,
            attn_impl=self.attn_impl, name="attn",
        )(h, train=train)
        if self.dropout_rate > 0.0:
            h = nn.Dropout(self.dropout_rate, deterministic=not train)(h)
        x = x + h
        with owner("norm"):
            h = nn.LayerNorm(name="ln_mlp")(x)
        with owner("ffn"):
            h = nn.Dense(self.d_ff, name="mlp_up")(h)
            h = nn.gelu(h)
            h = nn.Dense(self.d_model, name="mlp_down")(h)
        if self.dropout_rate > 0.0:
            h = nn.Dropout(self.dropout_rate, deterministic=not train)(h)
        return x + h


@register_model
class TransformerLM(DKModule):
    vocab_size: int = 32000
    num_layers: int = 4
    d_model: int = 256
    num_heads: int = 8
    d_ff: int = 1024
    max_seq_len: int = 2048
    dropout_rate: float = 0.0
    seq_axis: Optional[str] = None
    attn_impl: str = "dense"
    remat: bool = False  # jax.checkpoint each block: trade FLOPs for HBM

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        B, L = tokens.shape
        with owner("embed"):
            x = nn.Embed(self.vocab_size, self.d_model, name="tok_embed")(tokens)
            pos = _global_positions(L, self.seq_axis)
            x = x + nn.Embed(self.max_seq_len, self.d_model, name="pos_embed")(pos)[None, :, :]
        block_cls = TransformerBlock
        if self.remat:
            block_cls = remat_block(
                TransformerBlock, self,
                self.num_layers if self.attn_impl == "flash" else 0, B, L,
                self.num_heads, self.d_model // self.num_heads, x.dtype,
                static_argnums=(2,))
        for i in range(self.num_layers):
            x = block_cls(
                self.num_heads, self.d_model, self.d_ff,
                dropout_rate=self.dropout_rate, seq_axis=self.seq_axis,
                attn_impl=self.attn_impl, name=f"block_{i}",
            )(x, train)
        with owner("norm"):
            x = nn.LayerNorm(name="ln_final")(x)
        with owner("head"):
            return nn.Dense(self.vocab_size, name="lm_head")(x)


def small_transformer_lm(
    vocab_size: int = 1024,
    num_layers: int = 2,
    d_model: int = 128,
    num_heads: int = 4,
    d_ff: int = 512,
    max_seq_len: int = 256,
    seq_len: int = 64,
    seed: int = 0,
    **kwargs,
) -> Model:
    module = TransformerLM(
        vocab_size=vocab_size, num_layers=num_layers, d_model=d_model,
        num_heads=num_heads, d_ff=d_ff, max_seq_len=max_seq_len, **kwargs,
    )
    return Model.build(module, jnp.zeros((1, seq_len), jnp.int32), seed=seed)
