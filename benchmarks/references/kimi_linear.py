"""The forward pass, loss and (through ``jax.grad``) gradients of the Kimi
Linear decoders, plainly (moonshotai; the published ``config.json`` of
``Kimi-Linear-48B-A3B-Instruct``, ``model_type`` ``kimi_linear``; Kimi Linear,
arXiv:2510.26692), for one chip's share of the model: ``heads_held[1]`` of the
heads of every ``kda`` and ``mla`` layer, ``experts_held`` of the routed
experts, ``vocab_size`` rows.

Layer ``l`` on ``x`` ``[B, L, d]``; nothing has a bias; RMSNorm's epsilon is
inside the root; ``H`` heads held:

* ``h = RMSNorm(x)``, then by ``layer_types[l]``:

  - ``"kda"`` (Kimi Delta Attention), a head of ``d_k = d_v = kda_head_dim``:
    ``q~, k~, v = SiLU(conv(h W_q)), SiLU(conv(h W_k)), SiLU(conv(h W_v))``,
    ``conv`` causal and depthwise with ``conv_kernel`` taps a channel (the
    last on the current position), zeros before the sequence starts; ``q = q~
    / sqrt(|q~|^2 + 1e-6) * d_k^-1/2``, ``k = k~ / sqrt(|k~|^2 + 1e-6)``; ``g
    = -exp(A_log) * softplus((h W_fa) W_fb + dt_bias)`` a channel of the key,
    ``alpha = exp(g)``; ``beta = sigmoid(h W_b)`` a head; the state ``S``
    ``[d_k, d_v]``, ``S_0 = 0``, **a position at a time** (``lax.scan`` over
    the positions, nothing chunked: independent of the path under test):

        S' = Diag(alpha_t) S_{t-1}
        S_t = S' + beta_t k_t (v_t - S'^T k_t)^T          o_t = S_t^T q_t

    ``x <- x + (RMSNorm_head(o; w) * sigmoid((h W_ga) W_gb)) W_o``.
  - ``"mla"`` (latent attention without positions): ``Q = h W_q``, a head's
    ``[q_nope | q_rot]`` of ``qk_nope_head_dim + qk_rope_head_dim``; ``[c |
    k_rot] = h W_kva``; ``[k_nope | v] = RMSNorm(c) W_kvb`` a head; a head's
    key is ``[k_nope_h | k_rot]``, ``k_rot`` the same for every head; no
    rotation on any column; query ``i`` sees keys ``j <= i``; scores scaled by
    ``(qk_nope_head_dim + qk_rope_head_dim)^-1/2``; ``x <- x + concat_h(P_h
    v_h) W_o``.

* ``g = RMSNorm(x)``, then for ``l < num_dense_layers`` ``x <- x + (silu(g
  W_gate) * (g W_up)) W_down``, and after them: ``p = sigmoid(g W_r)`` over
  all ``num_experts`` in float32; ``e = top_k(p + b)`` with ``b`` the
  correction bias; ``w = p[e]``; ``w <- w / (sum(w) + 1e-20) *
  routed_scaling_factor``; ``x <- x + Shared(g) + sum over the chosen experts
  e_k held here of w_k E_{e_k}(g)``, ``E`` and ``Shared`` both SwiGLU. A plain
  loop over the held experts, every token through every one, masked. Where
  fewer experts are held than routed over, no gradient passes through the
  router's logits; none reaches the bias anywhere.
* After the last layer RMSNorm and the head's held columns (untied). Loss:
  mean cross-entropy over those rows.

Assumed, where the catalog's copy of the config is silent (the configuration
file lists them): what the family's public modelling code and the paper do, to
the best of what is known here.

Attention runs in blocks of queries so that ``[heads, block, L]`` scores are
alive at once. ``round_to`` rounds every product's operands, the
convolution's, the gates' and the recurrence's factors to that dtype first
(float32 accumulation and a float32 state stay): how the tests and PERF.md
compute "the reference in a precision below the configuration's". ``without``
names terms to leave out (``FAULTS``): how they show that each term of the
mathematics fails a limit when dropped. ``chosen`` and ``with_routing`` are
``references/smallthinker.py``'s.

Parameters are read from the model's own tree by name; the correction biases,
which the model keeps beside its parameters as state, are put into that tree
by :func:`with_bias`. Nothing else of the program is used.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.references.lfm2 import (_dot, _rms_norm, _rounded, _swiglu,
                                        bias_after_step, experts,  # noqa: F401
                                        with_bias)

#: Two limits on one sequence of 8,192 at the published widths, model in
#: bfloat16 against this in float32 (``families/kimi_linear.py::
#: reference_check``); readings on the chip (PERF.md, PR 34).
#:
#: ``TOLERANCE``: relative L2 on the logits with the reference using the
#: model's choice of experts in every routed layer: the arithmetic alone. It
#: reads 1.87e-2 to 2.02e-2 on 15 seeds, LFM2's level: table and head are
#: N(0, 0.02^2), so the stream is what the layers add and every product
#: stage's rounding at 2**-9 reaches the logits undiluted (against this
#: reference with bfloat16 operands the model reads 1.8e-2 to 1.9e-2 too:
#: two roundings differ as much as either does from float32). With every
#: operand rounded to 8 bits the same comparison reads 3.14e-1 (float8_e4m3) and
#: 4.2e-1 to 4.4e-1 (float8_e5m2); with the decay dropped (alpha = 1) 1.26,
#: beta = 1 0.66 to 0.68, the output gate dropped 0.84 to 0.86, the shared
#: expert dropped 0.88 to 0.90, the 2.446 dropped 9.1e-2 to 9.5e-2, the L2
#: norms dropped not a number (the delta rule diverges on keys that are not
#: unit), and with ``k_rot`` dropped **3.05e-2 to 3.14e-2**: 64 of 192 key
#: columns in one layer of five, the faintest fault of the list. 2.5e-2 lies
#: between 2.02e-2 and 3.05e-2, 5e-3 from either, some twenty times the
#: spread over seeds (2.5e-4).
#:
#: ``TOLERANCE_ROUTING``: the least share of (routed layer, token) pairs
#: whose k experts the two sides choose alike: 0.852 to 0.865 in bfloat16
#: (all eight of 256 must agree, and the eighth and ninth sigmoid scores lie
#: closer than LFM2's fourth and fifth of 64). 0.16 and 0.015 with 8-bit
#: operands, 0.22 with the shared expert dropped, 0.65 with the 2.446
#: dropped, 0.82 with ``k_rot`` dropped (which the logits' limit refuses).
#: A model that chose by another rule would agree on the logits above and
#: read near 0 here. 0.75 lies a tenth from the readings on either side.
TOLERANCE = 2.5e-2
TOLERANCE_ROUTING = 0.75
#: float32 against float32 on the CPU, dense attention in the module: the
#: order of sums differs, and the chunked form against the recurrence.
TOLERANCE_FLOAT32 = 1e-4

QUERY_BLOCK = 512

#: what ``without`` may name: each a term of the equations above
FAULTS = ("decay", "beta", "l2norm", "gate", "k_rot", "shared", "scale")


def _taps(x, taps, round_to):
    L, K = x.shape[1], taps.shape[1]
    x = jnp.pad(_rounded(x, round_to), ((0, 0), (K - 1, 0), (0, 0)))
    return sum(_rounded(taps[:, j], round_to) * x[:, j:j + L]
               for j in range(K))


def delta_rule(q, k, v, g, beta, round_to=None):
    """The recurrence itself, a position at a time. ``q, k, g``: [B, L, H,
    d_k]; ``v``: [B, L, H, d_v]; ``beta``: [B, L, H]. Returns ``o`` [B, L, H,
    d_v]."""
    B, L, H, K = k.shape

    def step(S, x):
        q, k, v, g, b = x
        S = jnp.exp(g)[..., None] * S
        seen = jnp.einsum("bhkv,bhk->bhv", S, k)
        S = S + (b[..., None] * k)[..., None] * (v - seen)[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q)

    xs = tuple(jnp.moveaxis(_rounded(a, round_to), 1, 0)
               for a in (q, k, v)) + (jnp.moveaxis(g, 1, 0),
                                      jnp.moveaxis(beta, 1, 0))
    _, o = jax.lax.scan(step, jnp.zeros((B, H, K, v.shape[-1])), xs)
    return jnp.moveaxis(o, 0, 1)


def kda(h, p, *, heads, head_dim, rms_eps, round_to=None, without=()):
    """The ``kda`` operator on the normalised stream ``h`` ``[B, L, d]``."""
    B, L, _ = h.shape

    def proj(name, x=h):
        return _dot(x, p[name]["kernel"], "bld,de->ble", round_to)

    def unit(x):
        if "l2norm" in without:
            return x
        return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    q, k, v = (jax.nn.silu(_taps(proj(f"{n}_proj"), p[f"{n}_taps"], round_to))
               .reshape(B, L, heads, head_dim) for n in ("q", "k", "v"))
    q, k = unit(q) * head_dim ** -0.5, unit(k)
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        (proj("f_b", proj("f_a")) + p["dt_bias"]).reshape(
            B, L, heads, head_dim))
    beta = jax.nn.sigmoid(proj("b_proj"))
    if "decay" in without:
        g = jnp.zeros_like(g)
    if "beta" in without:
        beta = jnp.ones_like(beta)
    o = delta_rule(q, k, v, g, beta, round_to)
    o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + rms_eps) \
        * p["o_norm"]
    if "gate" not in without:
        o = o * jax.nn.sigmoid(proj("g_b", proj("g_a")).reshape(o.shape))
    return _dot(o.reshape(B, L, -1), p["o_proj"]["kernel"], "bld,de->ble",
                round_to)


def latent_attention(h, p, *, kv_rank, qk_nope_dim, qk_rope_dim, rms_eps,
                     round_to=None, without=()):
    """The ``mla`` operator on the normalised stream ``h``."""
    L = h.shape[1]
    q = _dot(h, p["query"]["kernel"], "bld,dhk->blhk", round_to)
    kva = _dot(h, p["kv_a"]["kernel"], "bld,de->ble", round_to)
    c, k_rot = kva[..., :kv_rank], kva[..., kv_rank:]
    if "k_rot" in without:
        k_rot = jnp.zeros_like(k_rot)
    kv = _dot(_rms_norm(c, p["kv_norm"], rms_eps), p["kv_b"]["kernel"],
              "blr,rhk->blhk", round_to)
    k_nope, v = kv[..., :qk_nope_dim], kv[..., qk_nope_dim:]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rot[:, :, None, :], k_nope.shape[:-1] + (qk_rope_dim,))], -1)
    step = min(QUERY_BLOCK, L)
    j = jnp.arange(L)[None, :]
    blocks = []
    for q0 in range(0, L, step):
        i = jnp.arange(q0, min(q0 + step, L))[:, None]
        s = _dot(q[:, q0:q0 + step], k, "bqhk,bthk->bhqt", round_to) \
            * (qk_nope_dim + qk_rope_dim) ** -0.5
        s = jnp.where((j <= i)[None, None], s, -jnp.inf)
        blocks.append(_dot(jax.nn.softmax(s, -1), v, "bhqt,bthk->bqhk",
                           round_to))
    return _dot(jnp.concatenate(blocks, axis=1), p["out"]["kernel"],
                "bqhk,hkd->bqd", round_to)


def route(g, p, k, scale, round_to=None, chosen=None, trained=True):
    """``(weights, experts used, the reference's own choice)``: sigmoid
    scores, the ``k`` largest of score + bias, the unbiased scores of those
    divided by ``(their sum + 1e-20)`` and scaled. ``chosen`` ([B, L, k]) puts
    another's choice in the place of the reference's own."""
    logits = _dot(g, p["router"]["kernel"], "bld,de->ble", round_to)
    scores = jax.nn.sigmoid(
        logits if trained else jax.lax.stop_gradient(logits))
    _, own = jax.lax.top_k(scores + p["expert_bias"], k)
    used = own if chosen is None else chosen
    w = jnp.take_along_axis(scores, used, -1)
    return w / (w.sum(-1, keepdims=True) + 1e-20) * scale, used, own


def forward(params, tokens, *, num_layers, heads_held, kda_head_dim,
            qk_nope_head_dim, qk_rope_head_dim, kv_lora_rank,
            experts_per_token, experts_held, num_dense_layers, layer_types,
            routed_scaling_factor, rms_eps, round_to=None, chosen=None,
            with_routing=False, without=(), **_):
    """Logits ``[B, L, V_held]`` in float32 with exact matmuls. ``chosen`` (a
    ``[B, L, k]`` array of expert ids a *routed* layer, in order) makes every
    routed layer use that choice in the place of its own top-k;
    ``with_routing`` also returns the reference's own choice of every routed
    layer."""
    if set(without) - set(FAULTS):
        raise ValueError(f"without {without}: not among {FAULTS}")
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    first, held = experts_held
    routing = []
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed"]["embedding"][tokens]
        for l in range(num_layers):
            p = params[f"block_{l}"]
            h = _rms_norm(x, p["ln_op"], rms_eps)
            if layer_types[l] == "kda":
                x = x + kda(h, p["kda"], heads=heads_held[1],
                            head_dim=kda_head_dim, rms_eps=rms_eps,
                            round_to=round_to, without=without)
            else:
                x = x + latent_attention(
                    h, p["mla"], kv_rank=kv_lora_rank,
                    qk_nope_dim=qk_nope_head_dim,
                    qk_rope_dim=qk_rope_head_dim, rms_eps=rms_eps,
                    round_to=round_to, without=without)
            g = _rms_norm(x, p["ln_ffn"], rms_eps)
            if l < num_dense_layers:
                m = p["mlp"]
                x = x + _swiglu(g, m["gate"]["kernel"], m["up"]["kernel"],
                                m["down"]["kernel"], round_to)
                continue
            weights, used, own = route(
                g, p, experts_per_token,
                1.0 if "scale" in without else routed_scaling_factor,
                round_to, None if chosen is None else chosen[len(routing)],
                trained=held == p["router"]["kernel"].shape[1])
            routing.append(own)
            x = x + experts(g, weights, used, p["moe"]["experts"], first,
                            held, round_to)
            if "shared" in p and "shared" not in without:
                s = p["shared"]
                x = x + _swiglu(g, s["gate"]["kernel"], s["up"]["kernel"],
                                s["down"]["kernel"], round_to)
        x = _rms_norm(x, params["ln_final"], rms_eps)
        logits = _dot(x, params["head"]["kernel"], "bld,dv->blv", round_to)
        return (logits, routing) if with_routing else logits


def loss(params, tokens, labels, **module):
    """Mean cross-entropy over the held rows of the vocabulary."""
    logits = forward(params, tokens, **module)
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp, labels[..., None], -1).mean()
