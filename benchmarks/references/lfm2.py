"""The forward pass, loss and (through ``jax.grad``) gradients of the LFM2
mixture-of-experts decoders, plainly (LiquidAI; the published ``config.json``
of ``LFM2-24B-A2B``, ``model_type`` ``lfm2_moe``), for one chip's share of the
model.

Layer ``l`` on ``x`` ``[B, L, d]``; nothing has a bias; RMSNorm's epsilon is
inside the root:

* ``h = RMSNorm(x)``, then by ``layer_types[l]``:

  - ``"conv"``: ``[Bg, Cg, u] = split_3(h W_in)`` (``W_in`` ``[d, 3d]``);
    ``s = Bg * u``; the sequence padded with ``K - 1`` zero positions in
    front, ``c_t = sum_{j < K} w_j * s_{t - (K-1) + j}`` a channel (``K`` =
    ``conv_kernel`` taps ``[d, K]``, the last on the current position: causal
    and depthwise); ``x <- x + (Cg * c) W_out``.
  - ``"full_attention"``: ``q = h W_q`` (``num_heads`` x ``head_dim``), ``k =
    h W_k``, ``v = h W_v`` (``num_kv_heads`` x ``head_dim``); RMSNorm over
    each head of q and of k (one weight vector of ``head_dim`` each); both
    turned by the half-split rule, theta ``rope_theta``; query ``i`` sees keys
    ``j <= i``; scores scaled by ``1 / sqrt(head_dim)``; query head ``n`` reads
    K/V head ``n // (num_heads / num_kv_heads)``; ``x <- x + attn W_o``.

* ``g = RMSNorm(x)``, then for ``l < num_dense_layers`` ``x <- x + (silu(g
  W_1) * (g W_3)) W_2``, and after them: ``p = sigmoid(g W_r)`` over all
  ``num_experts`` in float32; ``e = top_k(p + b)`` with ``b`` the expert bias;
  ``w = p[e]`` (the unbiased scores); ``w <- w / (sum(w) + 1e-6) *
  routed_scaling_factor``; ``x <- x + sum over the chosen experts e_k held
  here of w_k E_{e_k}(g)``, ``E(g) = (silu(g W_gate) * (g W_up)) W_down``. The
  weights are normalised over all chosen experts, held or not. A plain loop
  over the held experts, every token through every one, masked: nothing can
  be dropped. Where fewer experts are held than routed over, no gradient
  passes through the router's logits (``models/lfm2.py`` says why); none
  reaches the bias anywhere.
* After the last layer RMSNorm and the embedding's held rows as the head.
  Loss: mean cross-entropy over those rows.

Assumed, where the catalog's copy of the config is silent (the configuration
file lists them): what the family's public modelling code does. ``head_dim``
= hidden / heads; the head tied; q/k RMSNorm a head; no bias on ``W_in``,
``W_out`` or the taps; SiLU gates; the ``1e-6``; the router reads the
normalised ``g``.

Attention runs in blocks of queries so that ``[heads, block, L]`` scores are
alive at once. ``round_to`` rounds every product's operands, and the
convolution's and the gates' factors, to that dtype first (float32
accumulation stays): how the tests and PERF.md compute "the reference in a
precision below the configuration's". ``chosen`` and ``with_routing`` are
``references/smallthinker.py``'s: top-k is discontinuous, so a comparison in
another precision is of the arithmetic where both sides route alike, and of
the routing apart.

Parameters are read from the model's own tree by name; the expert biases,
which the model keeps beside its parameters as state a training step moves
(no gradient reaches them), are put into that tree by :func:`with_bias`.
Nothing else of the program is used. How a step moves the bias is
:func:`bias_after_step`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: Two limits on one sequence of 8,192 at the published widths, model in
#: bfloat16 against this in float32 (``families/lfm2.py::reference_check``);
#: readings on the chip (PERF.md, PR 32).
#:
#: ``TOLERANCE``: relative L2 on the logits with the reference using the
#: model's choice of experts in every routed layer (its own scores of them):
#: the arithmetic alone. It reads 2.03e-2 to 2.08e-2 on 23 seeds, four
#: times SmallThinker's: the tied table is N(0, 0.02^2), so the stream is
#: what the layers add, and every product stage's rounding at 2**-9 (a
#: convolution layer has eight between its norm and the next) reaches the
#: logits undiluted; this reference with bfloat16 operands alone reads
#: 1.8e-2 on a CPU. Against this reference with every operand rounded to 8
#: bits the same comparison reads 2.25e-1 (float8_e4m3) and 5.0e-1
#: (float8_e5m2); with the first tap left out 1.12, with the scaling factor
#: halved 1.2e-1, and with the q/k norms left out 2.93e-2: at the seed's unit
#: weights those norms change a head of unit-variance entries by a few
#: percent, the faintest fault of the list. 2.5e-2 lies between 2.08e-2 and
#: 2.93e-2, some thirty times the spread over seeds (1.4e-4) from either.
#:
#: ``TOLERANCE_ROUTING``: the least share of (routed layer, token) pairs
#: whose k experts the two sides choose alike: 0.936 to 0.940 in bfloat16
#: (the scores are sigmoids of logits of unit variance, so the fourth and
#: fifth of 64 lie about 0.02 apart, and the rounded stream moves six tokens
#: in a hundred across). 0.48 and 0.18 with 8-bit operands, 0.52 with the
#: expert bias left out (which leaves the logits' reading where it was: the
#: reference follows the model's choice), 0.76 with the scaling factor
#: halved. A model that chose by another rule would agree on the logits
#: above and read near 0 here.
TOLERANCE = 2.5e-2
TOLERANCE_ROUTING = 0.85
#: float32 against float32 on the CPU, dense attention in the module: only
#: the order of sums differs.
TOLERANCE_FLOAT32 = 1e-4

QUERY_BLOCK = 512


def with_bias(params, state):
    """``params`` with each routed layer's ``expert_bias`` beside its router,
    from the model's collection ``router_bias`` (``model.state``)."""
    return {**params, **{block: {**params[block], **held}
                         for block, held in state["router_bias"].items()}}


def bias_after_step(bias, chosen, update):
    """The bias a training step leaves: ``b_e + update * sign(mean load -
    load_e)``, the load of every expert counted over the step's choices
    ``chosen`` (any shape of expert ids)."""
    load = jnp.sum(chosen.reshape(-1, 1) == jnp.arange(bias.shape[0]), axis=0)
    return bias + update * jnp.sign(load.mean() - load)


def _rounded(a, round_to):
    return a if round_to is None else a.astype(round_to).astype(jnp.float32)


def _dot(a, b, spec, round_to):
    return jnp.einsum(spec, _rounded(a, round_to), _rounded(b, round_to))


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"]


def _rope(x, theta):
    L, D = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angle = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def short_conv(h, p, *, round_to=None):
    """The ``conv`` operator on the normalised stream ``h`` ``[B, L, d]``."""
    L, d = h.shape[1:]
    taps = p["taps"]                                     # [d, K]
    K = taps.shape[1]
    bcu = _dot(h, p["in_proj"]["kernel"], "bld,de->ble", round_to)
    gate_b, gate_c, u = (_rounded(a, round_to)
                         for a in (bcu[..., :d], bcu[..., d:2 * d],
                                   bcu[..., 2 * d:]))
    s = jnp.pad(_rounded(gate_b * u, round_to), ((0, 0), (K - 1, 0), (0, 0)))
    c = jnp.zeros_like(gate_b)
    for j in range(K):
        c = c + _rounded(taps[:, j], round_to) * s[:, j:j + L]
    return _dot(gate_c * _rounded(c, round_to), p["out_proj"]["kernel"],
                "bld,de->ble", round_to)


def attention(h, p, *, num_heads, num_kv_heads, head_dim, rope_theta, rms_eps,
              round_to=None):
    """The ``full_attention`` operator on the normalised stream ``h``."""
    L = h.shape[1]
    q = _dot(h, p["query"]["kernel"], "bld,dhk->blhk", round_to)
    k = _dot(h, p["key"]["kernel"], "bld,dhk->blhk", round_to)
    v = _dot(h, p["value"]["kernel"], "bld,dhk->blhk", round_to)
    q = _rope(_rms_norm(q, p["query_norm"], rms_eps), rope_theta)
    k = _rope(_rms_norm(k, p["key_norm"], rms_eps), rope_theta)
    group = num_heads // num_kv_heads
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    step = min(QUERY_BLOCK, L)
    j = jnp.arange(L)[None, :]
    blocks = []
    for q0 in range(0, L, step):
        i = jnp.arange(q0, min(q0 + step, L))[:, None]
        s = _dot(q[:, q0:q0 + step], k, "bqhk,bthk->bhqt", round_to) \
            / jnp.sqrt(jnp.float32(head_dim))
        s = jnp.where((j <= i)[None, None], s, -jnp.inf)
        blocks.append(_dot(jax.nn.softmax(s, -1), v, "bhqt,bthk->bqhk",
                           round_to))
    return _dot(jnp.concatenate(blocks, axis=1), p["out"]["kernel"],
                "bqhk,hkd->bqd", round_to)


def route(g, p, k, scale, round_to=None, chosen=None, trained=True):
    """``(weights, experts used, the reference's own choice)``. ``chosen``
    ([B, L, k]) puts another's choice of experts in the place of the
    reference's own biased top-k; the weights are the reference's unbiased
    scores of those experts, renormalised and scaled. ``trained=False``: no
    gradient passes through the logits."""
    logits = _dot(g, p["router"]["kernel"], "bld,de->ble", round_to)
    scores = jax.nn.sigmoid(
        logits if trained else jax.lax.stop_gradient(logits))
    _, own = jax.lax.top_k(scores + p["expert_bias"], k)
    used = own if chosen is None else chosen
    w = jnp.take_along_axis(scores, used, -1)
    return w / (w.sum(-1, keepdims=True) + 1e-6) * scale, used, own


def _swiglu(g, gate, up, down, round_to):
    hidden = (jax.nn.silu(_dot(g, gate, "bld,df->blf", round_to))
              * _dot(g, up, "bld,df->blf", round_to))
    return _dot(hidden, down, "blf,fd->bld", round_to)


def experts(g, weights, chosen, p, first, held, round_to=None):
    """The held experts' part of the routed feed-forward."""
    out = jnp.zeros_like(g)
    for n in range(held):
        w = jnp.sum(jnp.where(chosen == first + n, weights, 0.0), -1)
        out += w[..., None] * _swiglu(
            g, p["gate"]["kernel"][n], p["up"]["kernel"][n],
            p["down"]["kernel"][n], round_to)
    return out


def forward(params, tokens, *, num_layers, num_heads, num_kv_heads, head_dim,
            experts_per_token, experts_held, num_dense_layers, layer_types,
            routed_scaling_factor, rope_theta, rms_eps, round_to=None,
            chosen=None, with_routing=False, **_):
    """Logits ``[B, L, V_held]`` in float32 with exact matmuls. ``chosen`` (a
    ``[B, L, k]`` array of expert ids a *routed* layer, in order) makes every
    routed layer use that choice in the place of its own top-k;
    ``with_routing`` also returns the reference's own choice of every routed
    layer."""
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    first, held = experts_held
    routing = []
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed"]["embedding"][tokens]
        for l in range(num_layers):
            p = params[f"block_{l}"]
            h = _rms_norm(x, p["ln_op"], rms_eps)
            if layer_types[l] == "conv":
                x = x + short_conv(h, p["conv"], round_to=round_to)
            else:
                x = x + attention(
                    h, p["attn"], num_heads=num_heads,
                    num_kv_heads=num_kv_heads, head_dim=head_dim,
                    rope_theta=rope_theta, rms_eps=rms_eps, round_to=round_to)
            g = _rms_norm(x, p["ln_ffn"], rms_eps)
            if l < num_dense_layers:
                m = p["mlp"]
                x = x + _swiglu(g, m["gate"]["kernel"], m["up"]["kernel"],
                                m["down"]["kernel"], round_to)
                continue
            weights, used, own = route(
                g, p, experts_per_token, routed_scaling_factor, round_to,
                None if chosen is None else chosen[len(routing)],
                trained=held == p["router"]["kernel"].shape[1])
            routing.append(own)
            x = x + experts(g, weights, used, p["moe"]["experts"], first,
                            held, round_to)
        x = _rms_norm(x, params["ln_final"], rms_eps)
        logits = _dot(x, params["tok_embed"]["embedding"], "bld,vd->blv",
                      round_to)
        return (logits, routing) if with_routing else logits


def loss(params, tokens, labels, **module):
    """Mean cross-entropy over the held rows of the vocabulary."""
    logits = forward(params, tokens, **module)
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp, labels[..., None], -1).mean()
