"""SmallThinker's forward pass, loss and (through ``jax.grad``) gradients,
plainly (PowerInfer 2025, arXiv:2507.20984; the published ``config.json`` of
``SmallThinker-21BA3B-Instruct``), for one chip's share of the model.

Block ``l`` on ``x`` ``[B, L, d]``; no projection has a bias:

* ``p = softmax(x W_router)`` over all ``num_experts`` in float32, read from
  the block's input **before** ``input_layernorm`` (the router is placed
  before attention); ``(w, e) = top_k(p)``; ``w <- w / sum(w)``.
* ``h = RMSNorm(x)``; ``q = h W_q`` (``num_heads`` x ``head_dim``), ``k = h
  W_k``, ``v = h W_v`` (``num_kv_heads`` x ``head_dim``). Where
  ``rope_layout[l]``: q and k turned over the whole head width by the
  half-split rule, theta ``rope_theta``. Where ``window_layout[l]``: query
  ``i`` sees keys ``j`` with ``0 <= i - j < window``, else all ``j <= i``.
  Scores scaled by ``1 / sqrt(head_dim)``; query head ``n`` reads K/V head ``n
  // (num_heads / num_kv_heads)``. ``x <- x + attn W_o``.
* ``g = RMSNorm(x)``; ``E_e(g) = (relu(g W_gate,e) * (g W_up,e)) W_down,e``;
  ``x <- x + sum over the chosen experts e_k held here of w_k E_{e_k}(g)``.
  The weights are normalised over all chosen experts, held or not; a token
  none of whose experts is held gets nothing. A plain loop over the held
  experts, every token through every one, masked: nothing can be dropped.
  Where fewer experts are held than routed over, no gradient passes through
  the router's logits (``models/smallthinker.py`` says why).
* After the last block RMSNorm and the head over the held rows of the
  vocabulary. Loss: mean cross-entropy over those rows.

Assumed, where the published config is silent (the configuration file lists
them): the router reads the un-normalised residual stream; the window holds
``window`` keys, the query's own among them; no bias and no QK-norm in
attention; RMSNorm's epsilon inside the root.

Attention runs in blocks of queries so that ``[heads, block, L]`` scores, not
``[heads, L, L]``, are alive at once: at L = 8192 that is what fits a chip.
``round_to`` rounds every product's operands to that dtype first (float32
accumulation stays): how the tests and PERF.md compute "the reference in a
precision below the configuration's".

Parameters are read from the model's own tree by name; nothing else of the
program is used.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: Two limits on one sequence of 8,192 at the published widths, model in
#: bfloat16 against this in float32 (``families/smallthinker.py::
#: reference_check``); readings in PERF.md, PR 28.
#:
#: Top-k is discontinuous: a rounded residual stream moves the tokens whose
#: sixth and seventh experts lie within the rounding across the boundary
#: (some percent of them a layer), and where one of the two is held here the
#: token's output changes by a whole expert. On a Zipf stream one frequent id
#: on the boundary in layer 0 moves a tenth of the sequence and, through
#: attention, every token after it: free-running logits read 1.7e-2 on three
#: seeds and 5.5e-2 on a fourth. So the two sides are compared where they
#: route alike, and their routing apart.
#:
#: ``TOLERANCE``: relative L2 on the logits with the reference using the
#: model's choice of experts in every layer (its own probabilities of them):
#: the arithmetic alone. bfloat16 rounds at 2**-9 and four blocks' product
#: stages add like a random walk: 5.2e-3 to 5.4e-3 on 25 seeds. The same
#: comparison against this reference with every product's operands rounded
#: to 8 bits reads 9.1e-2 to 9.3e-2 (float8_e5m2) and 1.7e-1 (float8_e4m3);
#: the windows left out 3.5e-2 to 3.9e-2. 2e-2 is 3.8 times the first
#: reading and a 4.5th of the second.
#:
#: ``TOLERANCE_ROUTING``: the least share of (layer, token) pairs whose k
#: experts the two sides choose alike: 0.977 to 0.982 in bfloat16, 0.59 to
#: 0.69 with 8-bit operands. A model that chose other experts would agree on
#: the logits above and read near 0 here.
TOLERANCE = 2e-2
TOLERANCE_ROUTING = 0.85
#: float32 against float32 on the CPU, dense attention in the module: only
#: the order of sums differs. 1e-4 is what the issue's acceptance states.
TOLERANCE_FLOAT32 = 1e-4

QUERY_BLOCK = 512


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


def _attention(h, p, *, num_heads, num_kv_heads, head_dim, window, theta,
               round_to):
    B, L, _ = h.shape
    q = _dot(h, p["query"]["kernel"], "bld,dhk->blhk", round_to)
    k = _dot(h, p["key"]["kernel"], "bld,dhk->blhk", round_to)
    v = _dot(h, p["value"]["kernel"], "bld,dhk->blhk", round_to)
    if theta is not None:
        q, k = _rope(q, theta), _rope(k, theta)
    group = num_heads // num_kv_heads
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    step = min(QUERY_BLOCK, L)
    j = jnp.arange(L)[None, :]
    blocks = []
    for q0 in range(0, L, step):
        i = jnp.arange(q0, min(q0 + step, L))[:, None]
        seen = j <= i
        if window is not None:
            seen &= i - j < window
        s = _dot(q[:, q0:q0 + step], k, "bqhk,bthk->bhqt", round_to) \
            / jnp.sqrt(jnp.float32(head_dim))
        s = jnp.where(seen[None, None], s, -jnp.inf)
        blocks.append(_dot(jax.nn.softmax(s, -1), v, "bhqt,bthk->bqhk",
                           round_to))
    out = jnp.concatenate(blocks, axis=1)
    return _dot(out, p["out"]["kernel"], "bqhk,hkd->bqd", round_to)


def _route(x, p, k, round_to, chosen=None, trained=True):
    """``(weights, experts used, the reference's own choice)``. ``chosen``
    ([B, L, k]) puts another's choice of experts in the place of the
    reference's own top-k; the weights are the reference's probabilities of
    those experts, renormalised. ``trained=False``: no gradient passes
    through the logits (a share of the experts does not train its router)."""
    logits = _dot(x, p["router"]["kernel"], "bld,de->ble", round_to)
    probs = jax.nn.softmax(
        logits if trained else jax.lax.stop_gradient(logits), -1)
    w, own = jax.lax.top_k(probs, k)
    if chosen is not None:
        w = jnp.take_along_axis(probs, chosen, -1)
    return w / w.sum(-1, keepdims=True), own if chosen is None else chosen, own


def _experts(g, weights, chosen, p, first, held, round_to):
    out = jnp.zeros_like(g)
    for n in range(held):
        w = jnp.sum(jnp.where(chosen == first + n, weights, 0.0), -1)
        hidden = (jax.nn.relu(_dot(g, p["gate"]["kernel"][n], "bld,df->blf",
                                   round_to))
                  * _dot(g, p["up"]["kernel"][n], "bld,df->blf", round_to))
        out += w[..., None] * _dot(hidden, p["down"]["kernel"][n],
                                   "blf,fd->bld", round_to)
    return out


def forward(params, tokens, *, num_layers, num_heads, num_kv_heads, head_dim,
            experts_per_token, experts_held, rope_layout, window_layout,
            window, rope_theta, rms_eps, round_to=None, chosen=None,
            with_routing=False, **_):
    """Logits ``[B, L, V_held]`` in float32 with exact matmuls. ``chosen``
    (a ``[B, L, k]`` array of expert ids a layer) makes every layer use that
    choice of experts in the place of its own top-k: top-k is discontinuous,
    and a comparison with a model in another precision is of the arithmetic
    only where both sides route alike (``families/smallthinker.py::
    reference_check`` compares the choices apart). ``with_routing`` also
    returns the reference's own top-k of every layer."""
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    first, held = experts_held
    routing = []
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed"]["embedding"][tokens]
        for l in range(num_layers):
            p = params[f"block_{l}"]
            weights, used, own = _route(
                x, p, experts_per_token, round_to,
                None if chosen is None else chosen[l],
                trained=held == p["router"]["kernel"].shape[1])
            routing.append(own)
            x = x + _attention(
                _rms_norm(x, p["ln_attn"], rms_eps), p["attn"],
                num_heads=num_heads, num_kv_heads=num_kv_heads,
                head_dim=head_dim, window=window if window_layout[l] else None,
                theta=rope_theta if rope_layout[l] else None,
                round_to=round_to)
            x = x + _experts(_rms_norm(x, p["ln_moe"], rms_eps), weights,
                             used, p["moe"]["experts"], first, held,
                             round_to)
        x = _rms_norm(x, params["ln_final"], rms_eps)
        logits = _dot(x, params["lm_head"]["kernel"], "bld,dv->blv", round_to)
        return (logits, routing) if with_routing else logits


def loss(params, tokens, labels, **module):
    """Mean cross-entropy over the held rows of the vocabulary."""
    logits = forward(params, tokens, **module)
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp, labels[..., None], -1).mean()
