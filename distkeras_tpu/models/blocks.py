"""Blocks of today's open decoders, for the modules that are built from them
(``models/smallthinker.py``, ``models/lfm2.py``, ``models/kimi_linear.py``):
RMSNorm, rotary positions by the half-split rule, grouped-query attention
without biases under a full or a sliding-window causal mask (with RMSNorm on
each query and key head where the family has it), latent attention (a
compressed K/V projection, keys wider than values, no positions), a gated
short convolution and Kimi Delta Attention (a gated delta rule with a decay a
channel, as a chunked scan) in attention's place, a gated dense feed-forward,
two routers (softmax, and sigmoid with a selection bias), and a dropless
mixture of gated experts that holds a share of the experts it routes over.
The two attention modules and the delta rule are told how many *heads* they
hold, as the expert layer is told its experts: a chip that shares a layer's
heads computes its own heads' part of ``W_o``'s sum. ``TransformerLM`` and
``MoETransformerLM`` share none of these parts (LayerNorm, learned positions,
biases, GELU, capacity) but the one ``nn.remat`` site (:func:`remat_block`).

**The expert layer** (:class:`DroplessExperts`) is the layer expert
parallelism needs: it is told which experts it holds (``first``, ``held``),
takes the routing over all of them, and computes its own experts' part of the
result. The assignments to held experts are sorted by expert (a stable sort;
the others sort behind them), their tokens' rows gathered into one buffer,
multiplied group by group (``jax.lax.ragged_dot``: on a TPU one grouped
Mosaic matmul a product, which visits the tiles that hold rows and no
others), weighted and summed back per token. Nothing is dropped: the buffer
has a row for every assignment a step can produce, ``tokens x k``, because
any token may choose all of its ``k`` experts here.

Rows move between token order and sorted order by :func:`rows_of_tokens` (a
gather) and :func:`tokens_from_rows` (its transpose, as a gather by token,
which also weights the rows as it sums them), each the other's transpose
under ``jax.custom_vjp``: the Pallas kernels of ``ops/pallas/rows.py``, which
start a DMA a live row and visit the live tiles only, so a movement costs by
the rows a step routed here and not by the buffer. The buffer's rows from
``live`` on are zeros up to their tile's end and beyond it **unwritten and
never read**: the grouped products visit the tiles that hold rows, the
elementwise passes between them compute on whatever the dead rows hold, and
nothing takes a dead row to a live one, to an output or to a gradient (what
reads the buffer by row reads below ``live``, or selects). A router that
sends every assignment here moves the whole buffer. The products are under
no control flow.

What a round routed here leaves the program with the loss: the layer adds its
counts to the collection ``ROUND_COUNTERS`` (``models/base.py`` says who
zeroes, carries and publishes it).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from distkeras_tpu.models.base import ROUND_COUNTERS
from distkeras_tpu.ops.delta_rule import chunk_for, chunked_gated_delta_rule
from distkeras_tpu.ops.pallas import rows
from distkeras_tpu.ops.pallas.flash_attention import (FLASH_RESIDUALS,
                                                      residual_bytes)
from distkeras_tpu.scopes import owner

#: the gate of a gated feed-forward, by the name a configuration gives it
ACTIVATIONS = {"relu": nn.relu, "silu": nn.silu}


def remat_block(block_cls, module, flash_layers: int, batch: int,
                seq_len: int, heads: int, head_dim: int, dtype, **kwargs):
    """``block_cls`` recomputed in the backward pass from its input, all but
    the flash forward: the kernel's ``out`` and ``lse`` are kept from the
    first pass (``flash_attention.FLASH_RESIDUALS``), so ``dk_flash_fwd``
    runs once a layer. The one ``nn.remat`` site of the language models; the
    gauge ``remat.flash_residual_bytes`` says what ``module``'s
    ``flash_layers`` layers that run the kernel keep a step (0: none does)."""
    if not module.is_initializing():
        from distkeras_tpu import telemetry

        telemetry.gauge("remat.flash_residual_bytes").set(
            flash_layers * residual_bytes(batch, seq_len, heads, head_dim,
                                          dtype))
    return nn.remat(
        block_cls, policy=jax.checkpoint_policies.save_only_these_names(
            *FLASH_RESIDUALS), **kwargs)


class RMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * scale``, the statistics in float32."""

    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        with owner("norm"):
            scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
            x32 = x.astype(jnp.float32)
            y = x32 * jax.lax.rsqrt(
                jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.eps)
            return (y * scale.astype(jnp.float32)).astype(x.dtype)


def rope(x, theta: float):
    """Rotary positions over the whole head width by the half-split rule:
    ``x``: [B, L, H, D]; pair ``i`` is ``(x[i], x[i + D/2])``, turned by
    ``pos * theta^(-2i/D)``. Angles and products in float32."""
    L, D = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    angle = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


class GroupedQueryAttention(nn.Module):
    """Causal attention of ``num_heads`` query heads over ``num_kv_heads``
    K/V heads (query head ``n`` reads K/V head ``n // group``), no biases.
    ``window``: query ``i`` sees keys ``j`` with ``0 <= i - j < window``.
    ``rope_theta``: rotate q and k, or leave positions out (``None``).
    ``qk_norm``: the epsilon of an RMSNorm over each head of q and of k (one
    weight vector of ``head_dim`` each), before the rotation; ``None``: no
    such norm and no such parameters."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: int | None = None
    rope_theta: float | None = None
    attn_impl: str = "dense"  # 'dense' | 'flash'
    qk_norm: float | None = None

    @nn.compact
    def __call__(self, x):
        B, L, D = x.shape
        with owner("mixer"):
            H, G, Dh = self.num_heads, self.num_kv_heads, self.head_dim

            def proj(heads, name):
                return nn.DenseGeneral((heads, Dh), use_bias=False,
                                       name=name)(x)

            q, k, v = proj(H, "query"), proj(G, "key"), proj(G, "value")
            if self.qk_norm is not None:
                q = RMSNorm(self.qk_norm, name="query_norm")(q)
                k = RMSNorm(self.qk_norm, name="key_norm")(k)
            if self.rope_theta is not None:
                q, k = rope(q, self.rope_theta), rope(k, self.rope_theta)
            q = q / jnp.sqrt(Dh).astype(q.dtype)
            if self.attn_impl == "flash" and not self.is_initializing():
                # Init only declares parameters: it takes the dense path on
                # whatever short sample it is given (transformer.py says why).
                from distkeras_tpu.models.transformer import _flash_block
                from distkeras_tpu.ops.pallas import flash_attention, mode

                block = _flash_block(L)
                if self.window is not None and self.window < L \
                        and self.window % block and not mode.compiles():
                    # The interpreter takes any grain; a CPU preset's window is
                    # shorter than a lane-aligned block.
                    block = math.gcd(L, self.window)
                out = flash_attention(q, k, v, block_size=block,
                                      window=self.window)
            else:
                i, j = jnp.arange(L)[:, None], jnp.arange(L)[None, :]
                seen = j <= i
                if self.window is not None:
                    seen &= i - j < self.window
                qg = q.reshape(B, L, G, H // G, Dh)
                scores = jnp.einsum("bqgnd,bkgd->bgnqk", qg, k)
                scores = jnp.where(seen, scores, jnp.finfo(scores.dtype).min)
                probs = jax.nn.softmax(scores, axis=-1)
                out = jnp.einsum("bgnqk,bkgd->bqgnd", probs, v).reshape(
                    B, L, H, Dh)
            return nn.DenseGeneral(D, axis=(-2, -1), use_bias=False,
                                   name="out")(out)


def route_top_k(logits, k: int):
    """The published router: softmax in float32 over all the experts, the
    ``k`` largest, their weights renormalised to sum to one
    (``norm_topk_prob``). Returns ``(weights [T, k] float32, experts [T, k])``."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    w, e = jax.lax.top_k(probs, k)
    return w / jnp.sum(w, axis=-1, keepdims=True), e


def route_sigmoid_bias_top_k(logits, bias, k: int, scale: float = 1.0,
                             eps: float = 1e-6):
    """The router of the families that balance their experts by a bias
    (``use_expert_bias``): scores ``p = sigmoid(logits)`` in float32, the
    ``k`` experts with the largest ``p + bias``, weighed by their *unbiased*
    scores, renormalised (``w / (sum(w) + eps)``) and scaled. The bias
    chooses and never weighs, and no gradient reaches it. Returns ``(weights
    [T, k] float32, experts [T, k], moved [T, k] bool)``; ``moved`` marks the
    assignments the bias made: those whose expert is not among the ``k`` with
    the largest unbiased score (fewer than ``k`` scores lie above it)."""
    probs = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, e = jax.lax.top_k(probs + bias.astype(jnp.float32), k)
    w = jnp.take_along_axis(probs, e, axis=-1)
    above = jnp.sum(probs[:, None, :] > w[:, :, None], axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps) * scale
    return w, e, above >= k


class Router(nn.Module):
    """``x W_router`` accumulated and kept in float32 whatever ``x`` is: a
    logit rounded to bfloat16 moves a token across the top-k boundary."""

    num_experts: int

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], self.num_experts))
        return jnp.einsum("td,de->te", x, kernel.astype(x.dtype),
                          preferred_element_type=jnp.float32)


class GatedShortConv(nn.Module):
    """A gated short convolution in attention's place (the ``conv`` layers of
    the LFM2 family): ``[Bg, Cg, u] = split_3(h W_in)``, ``s = Bg * u``, ``c_t
    = sum_j w_j * s_{t - (K-1) + j}`` a channel with ``s`` zero before the
    sequence starts (causal and depthwise, ``K`` taps of which the last
    multiplies the current position), ``y = (Cg * c) W_out``. No bias, no
    activation, no state carried between sequences. The taps are shifted
    multiply-adds, so the chain between the two projections is elementwise
    and lies under one scope, ``dk_shortconv``, in every pass."""

    kernel_size: int = 3

    @nn.compact
    def __call__(self, h):
        D = h.shape[-1]
        K = self.kernel_size
        with owner("mixer"):
            bcu = nn.Dense(3 * D, use_bias=False, name="in_proj")(h)
            taps = self.param(
                "taps", nn.initializers.variance_scaling(
                    1.0, "fan_in", "truncated_normal", in_axis=-1,
                    out_axis=-2),
                (D, K)).astype(bcu.dtype)
            with jax.named_scope("dk_shortconv"):
                gate_b, gate_c, u = jnp.split(bcu, 3, axis=-1)
                y = gate_c * _causal_taps(gate_b * u, taps)
            return nn.Dense(D, use_bias=False, name="out_proj")(y)


def _causal_taps(x, taps):
    """``c_t = sum_j taps[:, j] * x_{t - (K-1) + j}`` a channel, ``x`` zero
    before the sequence starts: a causal depthwise convolution as ``K``
    shifted multiply-adds. ``x``: [..., L, D]; ``taps``: [D, K]."""
    L, K = x.shape[-2], taps.shape[1]
    x = jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(K - 1, 0), (0, 0)])
    return sum(taps[:, j] * x[..., j:j + L, :] for j in range(K))


class KimiDeltaAttention(nn.Module):
    """Kimi Delta Attention in attention's place (the ``kda`` layers of Kimi
    Linear, arXiv:2510.26692), for the ``num_heads`` heads held here, each of
    ``head_dim`` keys and values, no bias in any projection:

    * ``q~, k~, v = SiLU(conv(h W_q)), SiLU(conv(h W_k)), SiLU(conv(h W_v))``,
      ``conv`` a causal depthwise convolution of ``conv_kernel`` taps;
    * ``q = q~ / |q~| * head_dim^-1/2``, ``k = k~ / |k~|`` over a head;
    * the decay, a channel of the key: ``g = -exp(A_log) * softplus((h W_fa)
      W_fb + dt_bias)`` in float32, ``alpha = exp(g)``; ``beta = sigmoid(h
      W_b)`` a head;
    * the state ``S`` ``[head_dim, head_dim]`` a head, zero as the sequence
      starts: ``S' = Diag(alpha_t) S_{t-1}``, ``S_t = S' + beta_t k_t (v_t -
      S'^T k_t)^T``, ``o_t = S_t^T q_t``: computed in the chunked form of
      ``ops/delta_rule.py`` (which says how no exponential can overflow), in
      chunks of ``chunk_for(L)`` positions, by the two kernel pairs of
      ``ops/pallas/delta_rule.py`` (the in-chunk half, which reads ``q, k, v,
      g`` as they are reshaped here; the scan over chunks);
    * ``y = (RMSNorm_head(o; w) * sigmoid((h W_ga) W_gb)) W_o``.

    Scopes: ``dk_kda_conv`` (the three tap sums and their SiLU), ``dk_kda``
    (norms, decay, the four kernels of the chunked form, the gated norm);
    the projections and ``W_o`` are matmuls of the step. A round's smallest
    summed log-decay of a chunk (how near the overflow hazard the run is: -88
    is where ``e^-G`` would leave float32) and its mean ``beta`` leave the
    program in ``ROUND_COUNTERS``."""

    num_heads: int
    head_dim: int = 128
    conv_kernel: int = 4
    rms_eps: float = 1e-5

    @nn.compact
    def __call__(self, h):
        B, L, D = h.shape
        H, Dh = self.num_heads, self.head_dim

        with owner("mixer"):
            def dense(width, name, x=h):
                return nn.Dense(width, use_bias=False, name=name)(x)

            def taps(name):
                return self.param(
                    name, nn.initializers.variance_scaling(
                        1.0, "fan_in", "truncated_normal", in_axis=-1,
                        out_axis=-2),
                    (H * Dh, self.conv_kernel)).astype(h.dtype)

            projected = [(dense(H * Dh, f"{n}_proj"), taps(f"{n}_taps"))
                         for n in ("q", "k", "v")]
            a_log = self.param(
                "A_log", lambda key, shape: jnp.log(jax.random.uniform(
                    key, shape, minval=1.0, maxval=16.0)), (H,))
            dt_bias = self.param(
                "dt_bias", _inverse_softplus_uniform(1e-3, 0.1), (H * Dh,))
            decay_in = dense(H * Dh, "f_b", dense(Dh, "f_a"))
            beta_in = dense(H, "b_proj")
            gate_in = dense(H * Dh, "g_b", dense(Dh, "g_a"))
            scale = self.param("o_norm", nn.initializers.ones, (Dh,))
            with jax.named_scope("dk_kda_conv"):
                q, k, v = (nn.silu(_causal_taps(x, w)).reshape(B, L, H, Dh)
                           for x, w in projected)
            with jax.named_scope("dk_kda"):
                def unit(x):
                    x32 = x.astype(jnp.float32)
                    return (x32 * jax.lax.rsqrt(jnp.sum(
                        jnp.square(x32), -1, keepdims=True) + 1e-6))

                g = -jnp.exp(a_log.astype(jnp.float32))[:, None] \
                    * jax.nn.softplus((decay_in.astype(jnp.float32)
                                       + dt_bias).reshape(B, L, H, Dh))
                beta = jax.nn.sigmoid(beta_in.astype(jnp.float32))
                o, least = chunked_gated_delta_rule(
                    (unit(q) * Dh ** -0.5).astype(h.dtype),
                    unit(k).astype(h.dtype), v, g, beta)
                o32 = o.astype(jnp.float32)
                o32 = o32 * jax.lax.rsqrt(jnp.mean(
                    jnp.square(o32), -1, keepdims=True) + self.rms_eps)
                y = (o32 * scale.astype(jnp.float32) * jax.nn.sigmoid(
                    gate_in.astype(jnp.float32).reshape(B, L, H, Dh)))
                y = y.astype(h.dtype).reshape(B, L, H * Dh)
            if self.is_mutable_collection(ROUND_COUNTERS):
                for name, value, join in (
                        ("min_chunk_decay", least, jnp.minimum),
                        ("beta_sum", jnp.sum(beta), jnp.add),
                        ("beta_count", jnp.float32(beta.size), jnp.add),
                        ("steps", jnp.float32(1), jnp.add)):
                    var = self.variable(ROUND_COUNTERS, name,
                                        lambda: jnp.zeros((), jnp.float32))
                    if not self.is_initializing():  # init declares them
                        var.value = join(var.value, value.astype(jnp.float32))
            return dense(D, "o_proj", y)


def _inverse_softplus_uniform(low: float, high: float):
    """An initializer: ``x`` with ``softplus(x)`` uniform in ``[low, high)``."""
    def init(key, shape, dtype=jnp.float32):
        u = jax.random.uniform(key, shape, dtype, minval=low, maxval=high)
        return u + jnp.log(-jnp.expm1(-u))
    return init


class LatentAttention(nn.Module):
    """Causal attention over a **compressed K/V projection**, without
    positions (the ``mla`` layers of Kimi Linear: ``mla_use_nope``), for the
    ``num_heads`` heads held here, no biases: ``Q = h W_q``, a head's
    ``qk_nope_dim + qk_rope_dim`` columns ``[q_nope | q_rot]``; ``[c | k_rot]
    = h W_kva`` (``kv_rank + qk_rope_dim`` columns, whole on every chip);
    ``[k_nope | v] = RMSNorm(c) W_kvb`` a head; a head's key is ``[k_nope_h |
    k_rot]`` with ``k_rot`` **one vector for all heads**; no rotation is
    applied to either part; softmax of ``q . k * (qk_nope_dim +
    qk_rope_dim)^-1/2`` over the earlier positions; ``y = concat_h(P_h v_h)
    W_o``. Keys are wider than values: ``attn_impl="flash"`` runs
    ``flash_attention`` with a value width of its own."""

    num_heads: int
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    kv_rank: int = 512
    rms_eps: float = 1e-5
    attn_impl: str = "dense"  # 'dense' | 'flash'

    @nn.compact
    def __call__(self, x):
        B, L, D = x.shape
        H, Dn, Dr, Dv = (self.num_heads, self.qk_nope_dim, self.qk_rope_dim,
                         self.v_head_dim)
        with owner("mixer"):
            q = nn.DenseGeneral((H, Dn + Dr), use_bias=False, name="query")(x)
            kva = nn.Dense(self.kv_rank + Dr, use_bias=False, name="kv_a")(x)
            c, k_rot = kva[..., :self.kv_rank], kva[..., self.kv_rank:]
            kv = nn.DenseGeneral((H, Dn + Dv), use_bias=False, name="kv_b")(
                RMSNorm(self.rms_eps, name="kv_norm")(c))
            k = jnp.concatenate([kv[..., :Dn], jnp.broadcast_to(
                k_rot[:, :, None, :], (B, L, H, Dr))], axis=-1)
            v = kv[..., Dn:]
            q = q * jnp.asarray((Dn + Dr) ** -0.5, q.dtype)
            if self.attn_impl == "flash" and not self.is_initializing():
                from distkeras_tpu.models.transformer import _flash_block
                from distkeras_tpu.ops.pallas import flash_attention

                out = flash_attention(q, k, v, block_size=_flash_block(L))
            else:
                scores = jnp.einsum("bqhd,bkhd->bhqk", q, k)
                seen = jnp.arange(L)[None, :] <= jnp.arange(L)[:, None]
                scores = jnp.where(seen, scores, jnp.finfo(scores.dtype).min)
                out = jnp.einsum("bhqk,bkhd->bqhd",
                                 jax.nn.softmax(scores, -1), v)
            return nn.DenseGeneral(D, axis=(-2, -1), use_bias=False,
                                   name="out")(out)


class GatedMLP(nn.Module):
    """``(act(g W_gate) * (g W_up)) W_down``, no bias: a dense layer's
    feed-forward in the families whose experts are gated the same way."""

    d_ff: int
    activation: str = "silu"

    @nn.compact
    def __call__(self, g):
        with owner("ffn"):
            def proj(width, name):
                return nn.Dense(width, use_bias=False, name=name)

            hidden = ACTIVATIONS[self.activation](proj(self.d_ff, "gate")(g)) \
                * proj(self.d_ff, "up")(g)
            return proj(g.shape[-1], "down")(hidden)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def rows_of_tokens(x, order, slot, live, k: int):
    """``out[r] = x[order[r] // k]`` for the ``live`` first rows of the sorted
    buffer; later rows are zeros or unwritten (``ops/pallas/rows.py``).
    ``x``: [tokens, D]; ``order``: [N], the sorted assignments (``t * k +
    j``); ``slot``: [tokens, k], its inverse."""
    return rows.gather(x, order // k, live)


@jax.custom_vjp
def tokens_from_rows(buffer, weights, order, slot, live):
    """``out[t]`` = the sum over ``j`` of ``weights[t, j]`` times row
    ``slot[t, j]`` of ``buffer``, for the rows below ``live``, in float32 and
    in a fixed order; later rows are not read. ``buffer``: [N, D];
    ``weights``: [tokens, k] float32; returns [tokens, D]."""
    return rows.combine(buffer, slot, live, weights)


# Each is linear in its first argument and, but for the weights, the other's
# transpose; a gradient takes the dtype of what it is the gradient of (before
# the rows move: a cast and a movement commute, and narrow rows are cheaper
# to move).
rows_of_tokens.defvjp(
    lambda x, order, slot, live, k: (
        rows_of_tokens(x, order, slot, live, k), (slot, live)),
    lambda k, res, g: (
        rows.combine(g, *res).astype(g.dtype), None, None, None))


def _tokens_from_rows_bwd(res, g):
    buffer, weights, order, slot, live = res
    k = slot.shape[1]
    g = g.astype(buffer.dtype)
    # d buffer[r] = weight of r . g[token of r]: the gather, scaled as it
    # writes. d weights[t, j] = <buffer[slot[t, j]], g[t]> for the live
    # assignments: computed by the row, then read by `slot`; a select keeps
    # what dead rows hold out of it. Where nothing asks for it (a share does
    # not train its router) it is dead code.
    by_row = jnp.sum(rows.gather(g, order // k, live).astype(jnp.float32)
                     * buffer.astype(jnp.float32), axis=-1)
    return (rows.gather(g, order // k, live,
                        scale=weights.reshape(-1)[order]),
            jnp.where(slot < live, by_row[slot], 0).astype(weights.dtype),
            None, None, None)


tokens_from_rows.defvjp(
    lambda buffer, weights, order, slot, live: (
        tokens_from_rows(buffer, weights, order, slot, live),
        (buffer, weights, order, slot, live)),
    _tokens_from_rows_bwd)


class _ExpertBank(nn.Module):
    """One matrix an expert, stacked: ``kernel`` [held, d_in, d_out]; the
    leading axis is the expert's (``parallel/sharding.py::MOE_RULES``)."""

    held: int
    d_in: int
    d_out: int

    @nn.compact
    def __call__(self):
        return self.param(
            "kernel", nn.initializers.variance_scaling(
                1.0, "fan_in", "truncated_normal", in_axis=-2, out_axis=-1,
                batch_axis=(0,)), (self.held, self.d_in, self.d_out))


class _GatedExperts(nn.Module):
    """``(act(g W_gate) * (g W_up)) W_down`` for sorted rows, group by
    group. No loop: three grouped products the trace can see."""

    held: int
    d_model: int
    d_expert: int
    activation: str = "relu"

    @nn.compact
    def __call__(self, rows, group_sizes):
        D, F = self.d_model, self.d_expert
        gate = _ExpertBank(self.held, D, F, name="gate")().astype(rows.dtype)
        up = _ExpertBank(self.held, D, F, name="up")().astype(rows.dtype)
        down = _ExpertBank(self.held, F, D, name="down")().astype(rows.dtype)

        def grouped(a, w):
            return jax.lax.ragged_dot(a, w, group_sizes,
                                      preferred_element_type=rows.dtype)

        act = ACTIVATIONS[self.activation]
        return grouped(act(grouped(rows, gate)) * grouped(rows, up), down)


class DroplessExperts(nn.Module):
    """This chip's part of a routed expert layer: experts ``first .. first +
    held - 1`` of those ``experts [T, k]`` names, weighted by ``weights``
    (already normalised over all ``k``, held or not). A token none of whose
    experts is held gets zero. ``activation``: the experts' gate, ``"relu"``
    (ReGLU) or ``"silu"`` (SwiGLU)."""

    first: int
    held: int
    d_model: int
    d_expert: int
    activation: str = "relu"

    @nn.compact
    def __call__(self, x, weights, experts):
        T, D = x.shape
        k = experts.shape[-1]
        N = T * k
        with owner("ffn"):
            with jax.named_scope("dk_moe_route"):
                local = experts.reshape(N) - self.first
                here = (local >= 0) & (local < self.held)
                # Held assignments first, by expert; the rest behind them.
                key = jnp.where(here, local, self.held)
                order = jnp.argsort(key, stable=True)
                # The inverse of `order` without a second sort: an
                # assignment's place is its group's first row plus how many
                # of its group came before it (the sort is stable, and the
                # key has held + 1 values).
                of_group = key[None, :] == jnp.arange(self.held + 1)[:, None]
                before = jnp.cumsum(of_group, axis=1, dtype=jnp.int32)
                sizes = before[:, -1]
                first_row = jnp.cumsum(sizes) - sizes
                slot = jnp.sum(jnp.where(
                    of_group, before - 1 + first_row[:, None], 0),
                    axis=0).reshape(T, k)
                group_sizes = sizes[:self.held]
                live = jnp.sum(group_sizes)
                buffer = rows_of_tokens(x, order, slot, live, k)
            if self.is_mutable_collection(ROUND_COUNTERS):
                self._count(group_sizes, here.reshape(T, k), T,
                            rows.visited_rows(live, N, rows.gather_tile(
                                N, D, x.dtype)))
            with jax.named_scope("dk_moe_experts"):
                out = _GatedExperts(
                    self.held, D, self.d_expert, self.activation,
                    name="experts")(buffer, group_sizes)
            with jax.named_scope("dk_moe_combine"):
                # The grouped product writes the tiles that hold rows and
                # leaves the others as they were: never read. The weights
                # meet the rows in the kernel's sum, rounded to the rows'
                # dtype as they were when a pass over the whole buffer
                # multiplied by them.
                return tokens_from_rows(
                    out, weights.astype(jnp.float32), order, slot,
                    live).astype(x.dtype)

    def _count(self, group_sizes, here, tokens, rows_moved):
        """Add this step's routing to the round's counters (float32: exact
        up to 2**24 a round). ``rows_moved``: the buffer rows in the tiles
        the row kernels visited."""
        for name, value in (
                ("assignments_held", group_sizes.astype(jnp.float32)),
                ("tokens_without_held_expert", jnp.sum(
                    ~jnp.any(here, axis=-1)).astype(jnp.float32)),
                ("tokens", jnp.float32(tokens)),
                ("rows_moved", rows_moved.astype(jnp.float32)),
                ("steps", jnp.float32(1))):
            var = self.variable(ROUND_COUNTERS, name,
                                lambda v=value: jnp.zeros_like(v))
            if not self.is_initializing():  # init declares them, at zero
                var.value = var.value + value


def publish_moe_round(round_index: int, counters,
                      experts_per_token: int) -> None:
    """A round's expert load, from the routed layers' ``ROUND_COUNTERS``
    (numpy, one entry a routed layer: its expert layer's counts under
    ``"moe"``, and ``"assignments_moved_by_bias"`` where its router has a
    selection bias): counter ``moe.assignments_held``, gauges
    ``moe.load_max_over_mean`` (over the held experts, worst layer),
    ``moe.tokens_without_held_expert_share``, ``moe.rows_moved_share`` (buffer
    rows in the tiles the row kernels visited over the buffers' rows) and,
    with a bias, ``moe.bias_moved_share`` (assignments whose expert the
    unbiased top-k would not have chosen, over all assignments), and one
    ``moe.round`` event that keeps the round's index, its steps and the
    layers with them. What a model's ``publish_round_counters`` calls."""
    from distkeras_tpu import telemetry

    routed = [c for _, c in sorted(counters.items())]
    layers = [c["moe"] for c in routed]
    assigned = np.stack([np.asarray(c["assignments_held"], np.float64)
                         for c in layers])            # [layers, held]
    without = sum(float(c["tokens_without_held_expert"]) for c in layers)
    tokens = sum(float(c["tokens"]) for c in layers)
    by_layer = (assigned.max(axis=1)
                / np.maximum(assigned.mean(axis=1), 1e-30))
    imbalance = float(np.max(by_layer))
    share = without / tokens if tokens else 0.0
    moved = sum(float(c["rows_moved"]) for c in layers)
    assignments = tokens * experts_per_token
    moved_share = moved / assignments if tokens else 0.0
    telemetry.counter("moe.assignments_held").add(float(assigned.sum()))
    telemetry.gauge("moe.load_max_over_mean").set(imbalance)
    telemetry.gauge("moe.tokens_without_held_expert_share").set(share)
    telemetry.gauge("moe.rows_moved_share").set(moved_share)
    event = {
        "round": int(round_index), "layers": len(layers),
        "steps": float(layers[0]["steps"]),
        "assignments_held": float(assigned.sum()),
        "assignments_held_by_layer": assigned.sum(axis=1).tolist(),
        "load_max_over_mean": imbalance,
        "load_max_over_mean_by_layer": by_layer.tolist(),
        "tokens_without_held_expert_share": share,
        "rows_moved_share": moved_share}
    if all("assignments_moved_by_bias" in c for c in routed):
        by_bias = sum(float(c["assignments_moved_by_bias"]) for c in routed)
        event["bias_moved_share"] = by_bias / assignments if tokens else 0.0
        telemetry.gauge("moe.bias_moved_share").set(event["bias_moved_share"])
    telemetry.event("moe.round", event)
