"""The forward pass, loss and (through ``jax.grad``) gradients of the Qwen3-Next
decoders, plainly (Qwen; the published ``config.json`` of
``Qwen3-Next-80B-A3B-Instruct``, ``model_type`` ``qwen3_next``), for one chip's
share of the model: ``experts_held`` of the routed experts, ``vocab_size``
rows; every head of both operators.

Layer ``l`` on ``x`` ``[B, L, d]``; nothing has a bias; RMSNorm's epsilon is
inside the root:

* ``h = RMSNorm(x)``, then by ``layer_types[l]``:

  - ``"gdn"`` (Gated DeltaNet), ``Hk`` key heads and ``Hv`` value heads of
    ``d = linear_head_dim``: ``[q~ | k~ | v~ | z] = h W_qkvz``, ``[b | a] = h
    W_ba``; ``q, k, v = SiLU(conv(q~ | k~ | v~))``, ``conv`` causal and
    depthwise with ``conv_kernel`` taps a channel (the last on the current
    position), zeros before the sequence starts; ``q = q / sqrt(|q|^2 +
    1e-6) * d^-1/2``, ``k = k / sqrt(|k|^2 + 1e-6)`` over a key head; value
    head ``j`` reads key head ``j // (Hv / Hk)``; ``beta = sigmoid(b)``, ``g =
    -exp(A_log) * softplus(a + dt_bias)``, **one decay a value head**; the
    state ``S`` ``[d, d]`` a value head, ``S_0 = 0``, **a position at a time**
    (``lax.scan`` over the positions, with the scalar decay and the key
    heads read by group: independent of the chunked form and of the kernels
    under test):

        S' = alpha_t S_{t-1}    alpha_t = exp(g_t)
        S_t = S' + beta_t k_t (v_t - S'^T k_t)^T          o_t = S_t^T q_t

    ``x <- x + (RMSNorm_head(o; w) * SiLU(z)) W_o``.
  - ``"attention"`` (gated attention), ``H`` query heads over ``G`` K/V heads
    of ``head_dim``: ``[q | gate]`` a head from ``h W_q``; ``k = h W_k``, ``v =
    h W_v``; q and k RMS-normed a head; RoPE (``rope_theta``) on a head's first
    ``rotary_dim`` columns by the half-split rule within them; query ``i``
    sees keys ``j <= i``; query head ``n`` reads K/V head ``n // (H / G)``;
    scores scaled by ``head_dim^-1/2``; ``x <- x + (attn * sigmoid(gate))
    W_o``.

* ``g = RMSNorm(x)``; ``p = softmax(g W_r)`` over all ``num_experts`` in
  float32; ``(w, e) = top_k(p)``; ``w <- w / sum(w)``; ``x <- x + sum over the
  chosen experts e_k held here of w_k E_{e_k}(g) + sigmoid(g W_sg) *
  Shared(g)``, ``E`` and ``Shared`` both SwiGLU. A plain loop over the held
  experts, every token through every one, masked. Where fewer experts are
  held than routed over, no gradient passes through the router's logits.
* After the last layer RMSNorm and the head's held columns (untied). Loss:
  mean cross-entropy over those rows.

Departures from the published model, as in the program (the configuration
file lists them): no multi-token prediction module, no auxiliary loss, no
dropout; the zero-centred norms (``1 + w``, ``w`` from 0) as weights from 1,
the same function.

Attention runs in blocks of queries so that ``[heads, block, L]`` scores are
alive at once: at L = 8,192 that is what fits a chip. ``round_to`` rounds
every product's operands, the convolution's, the gates' and the
recurrence's factors to that dtype first (float32 accumulation and a float32
state stay): how the tests and PERF.md compute "the reference in a precision
below the configuration's". ``without`` names terms to leave out
(``FAULTS``): how the tests show that each term of the mathematics fails a
limit when dropped. ``chosen`` and ``with_routing`` are
``references/smallthinker.py``'s.

Parameters are read from the model's own tree by name; nothing else of the
program is used.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.references.lfm2 import (_dot, _rms_norm, _rope, _rounded,
                                        _swiglu, experts)

#: Two limits on one sequence of 8,192 at the published widths, model in
#: bfloat16 against this in float32 (``families/qwen3_next.py::
#: reference_check``); readings on the chip (PERF.md, PR 40).
#:
#: ``TOLERANCE``: relative L2 on the logits with the reference using the
#: model's choice of experts in every layer (its own probabilities of them):
#: the arithmetic alone. Table and head are N(0, 0.02^2), so the stream is
#: what the layers add and every product stage's rounding at 2**-9 reaches
#: the logits undiluted, as in Kimi Linear and LFM2. It reads 2.380e-2 to
#: 2.478e-2 in twelve readings (ten seeds of the weights).
#: With every operand rounded to 8 bits the same comparison reads 2.06e-1
#: (float8_e4m3) and 4.76e-1 (float8_e5m2); with the decay dropped 1.35, beta
#: = 1 0.49, the L2 norms dropped not a number, Gated DeltaNet's SiLU(z) gate
#: dropped 1.31, value heads on the wrong key heads 1.09, attention's output
#: gate dropped 7.3e-2, the shared expert's gate dropped 0.79, the shared
#: expert dropped 0.93, and **the rotation dropped 2.68e-2**: 64 of 256
#: columns of one layer in four at theta 1e7, which no limit that leaves the
#: bfloat16 readings room can refuse (the CPU tests hold the rotation to the
#: reference instead). 5e-2 is twice the largest bfloat16 reading and a
#: fourth of the float8_e4m3 control's, and refuses every fault but that one
#: (the tiny preset a rehearsal runs reads 2.2e-2 to 3.4e-2 on a CPU).
#:
#: ``TOLERANCE_ROUTING``: the least share of (layer, token) pairs whose ten
#: experts of 512 the two sides choose alike: all ten must agree, and the
#: tenth and eleventh softmax probabilities of 512 lie close: 0.788 to 0.800
#: in bfloat16, 0.145 (float8_e4m3) and 0.013 (float8_e5m2) with 8-bit
#: operands, 0.70 with attention's output gate dropped, 0.22 with the shared
#: expert's gate dropped. 0.65 lies 0.14 below the first and 0.5 above the
#: float8_e4m3 control.
TOLERANCE = 5e-2
TOLERANCE_ROUTING = 0.65
#: float32 against float32 on the CPU, dense attention in the module: the
#: order of sums differs, and the chunked form against the recurrence.
TOLERANCE_FLOAT32 = 1e-4

QUERY_BLOCK = 512

#: what ``without`` may name: each a term of the equations above
FAULTS = ("decay", "beta", "l2norm", "gdn_gate", "key_groups", "attn_gate",
          "rotation", "shared_gate", "shared")


def delta_rule(q, k, v, g, beta, round_to=None, group=None):
    """The recurrence itself, a position at a time, with one decay a value
    head and key heads read by group. ``q, k``: [B, L, Hk, d_k]; ``v``: [B,
    L, Hv, d_v]; ``g``, ``beta``: [B, L, Hv]. Value head ``j`` reads key head
    ``group[j]``, ``j // (Hv / Hk)`` by default. Returns ``o`` [B, L, Hv,
    d_v]."""
    B, L, Hk, K = k.shape
    Hv = v.shape[2]
    if group is None:
        group = jnp.arange(Hv) // (Hv // Hk)

    def step(S, x):
        q, k, v, g, b = x
        q, k = q[:, group], k[:, group]                     # [B, Hv, d_k]
        S = jnp.exp(g)[..., None, None] * S
        seen = jnp.einsum("bhkv,bhk->bhv", S, k)
        S = S + (b[..., None] * k)[..., None] * (v - seen)[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q)

    xs = tuple(jnp.moveaxis(_rounded(a, round_to), 1, 0)
               for a in (q, k, v)) + (jnp.moveaxis(g, 1, 0),
                                      jnp.moveaxis(beta, 1, 0))
    _, o = jax.lax.scan(step, jnp.zeros((B, Hv, K, v.shape[-1])), xs)
    return jnp.moveaxis(o, 0, 1)


def gdn(h, p, *, key_heads, value_heads, head_dim, rms_eps, round_to=None,
        without=()):
    """The ``gdn`` operator on the normalised stream ``h`` ``[B, L, d]``."""
    B, L, _ = h.shape
    keys, values = key_heads * head_dim, value_heads * head_dim
    qkvz = _dot(h, p["in_proj_qkvz"]["kernel"], "bld,de->ble", round_to)
    ba = _dot(h, p["in_proj_ba"]["kernel"], "bld,de->ble", round_to)
    taps = p["conv_taps"]                                  # [channels, K]
    K = taps.shape[1]
    x = jnp.pad(_rounded(qkvz[..., :2 * keys + values], round_to),
                ((0, 0), (K - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(_rounded(taps[:, j], round_to) * x[:, j:j + L]
                          for j in range(K)))

    def unit(x):
        if "l2norm" in without:
            return x
        return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    q = unit(qkv[..., :keys].reshape(B, L, key_heads, head_dim)) \
        * head_dim ** -0.5
    k = unit(qkv[..., keys:2 * keys].reshape(B, L, key_heads, head_dim))
    v = qkv[..., 2 * keys:].reshape(B, L, value_heads, head_dim)
    beta = jax.nn.sigmoid(ba[..., :value_heads])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., value_heads:]
                                                + p["dt_bias"])
    if "decay" in without:
        g = jnp.zeros_like(g)
    if "beta" in without:
        beta = jnp.ones_like(beta)
    # the fault: value head j on key head j mod Hk (tile, not repeat)
    o = delta_rule(q, k, v, g, beta, round_to,
                   jnp.arange(value_heads) % key_heads
                   if "key_groups" in without else None)
    o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + rms_eps) \
        * p["o_norm"]
    if "gdn_gate" not in without:
        o = o * jax.nn.silu(qkvz[..., 2 * keys + values:].reshape(o.shape))
    return _dot(o.reshape(B, L, values), p["out_proj"]["kernel"],
                "bld,de->ble", round_to)


def attention(h, p, *, num_heads, num_kv_heads, head_dim, rotary_dim,
              rope_theta, rms_eps, round_to=None, without=()):
    """The gated ``attention`` operator on the normalised stream ``h``."""
    L = h.shape[1]
    qg = _dot(h, p["query"]["kernel"], "bld,dhk->blhk", round_to)
    q, gate = qg[..., :head_dim], qg[..., head_dim:]
    k = _dot(h, p["key"]["kernel"], "bld,dhk->blhk", round_to)
    v = _dot(h, p["value"]["kernel"], "bld,dhk->blhk", round_to)
    q = _rms_norm(q, p["query_norm"], rms_eps)
    k = _rms_norm(k, p["key_norm"], rms_eps)
    if "rotation" not in without:
        q, k = (jnp.concatenate([_rope(a[..., :rotary_dim], rope_theta),
                                 a[..., rotary_dim:]], -1) for a in (q, k))
    group = num_heads // num_kv_heads
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    step = min(QUERY_BLOCK, L)
    j = jnp.arange(L)[None, :]
    blocks = []
    for q0 in range(0, L, step):
        i = jnp.arange(q0, min(q0 + step, L))[:, None]
        s = _dot(q[:, q0:q0 + step], k, "bqhk,bthk->bhqt", round_to) \
            * head_dim ** -0.5
        s = jnp.where((j <= i)[None, None], s, -jnp.inf)
        blocks.append(_dot(jax.nn.softmax(s, -1), v, "bhqt,bthk->bqhk",
                           round_to))
    out = jnp.concatenate(blocks, axis=1)
    if "attn_gate" not in without:
        out = out * jax.nn.sigmoid(_rounded(gate, round_to))
    return _dot(out, p["out"]["kernel"], "bqhk,hkd->bqd", round_to)


def route(g, p, k, round_to=None, chosen=None, trained=True):
    """``(weights, experts used, the reference's own choice)``: softmax over
    all experts, the ``k`` largest renormalised. ``chosen`` ([B, L, k]) puts
    another's choice in the place of the reference's own; the weights are
    then the reference's probabilities of those experts, renormalised."""
    logits = _dot(g, p["router"]["kernel"], "bld,de->ble", round_to)
    probs = jax.nn.softmax(
        logits if trained else jax.lax.stop_gradient(logits), -1)
    _, own = jax.lax.top_k(probs, k)
    used = own if chosen is None else chosen
    w = jnp.take_along_axis(probs, used, -1)
    return w / w.sum(-1, keepdims=True), used, own


def forward(params, tokens, *, num_layers, num_heads, num_kv_heads, head_dim,
            rotary_dim, rope_theta, linear_key_heads, linear_value_heads,
            linear_head_dim, experts_per_token, experts_held, layer_types,
            rms_eps, round_to=None, chosen=None, with_routing=False,
            without=(), **_):
    """Logits ``[B, L, V_held]`` in float32 with exact matmuls. ``chosen`` (a
    ``[B, L, k]`` array of expert ids a layer, in order) makes every layer
    use that choice in the place of its own top-k; ``with_routing`` also
    returns the reference's own choice of every layer."""
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
            if layer_types[l] == "gdn":
                x = x + gdn(h, p["gdn"], key_heads=linear_key_heads,
                            value_heads=linear_value_heads,
                            head_dim=linear_head_dim, rms_eps=rms_eps,
                            round_to=round_to, without=without)
            else:
                x = x + attention(
                    h, p["attn"], num_heads=num_heads,
                    num_kv_heads=num_kv_heads, head_dim=head_dim,
                    rotary_dim=rotary_dim, rope_theta=rope_theta,
                    rms_eps=rms_eps, round_to=round_to, without=without)
            g = _rms_norm(x, p["ln_ffn"], rms_eps)
            weights, used, own = route(
                g, p, experts_per_token, round_to,
                None if chosen is None else chosen[l],
                trained=held == p["router"]["kernel"].shape[1])
            routing.append(own)
            x = x + experts(g, weights, used, p["moe"]["experts"], first,
                            held, round_to)
            if "shared" not in without:
                s = p["shared"]
                shared = _swiglu(g, s["gate"]["kernel"], s["up"]["kernel"],
                                 s["down"]["kernel"], round_to)
                if "shared_gate" not in without:
                    shared = shared * jax.nn.sigmoid(_dot(
                        g, p["shared_gate"]["kernel"], "bld,de->ble",
                        round_to))
                x = x + shared
        x = _rms_norm(x, params["ln_final"], rms_eps)
        logits = _dot(x, params["head"]["kernel"], "bld,dv->blv", round_to)
        return (logits, routing) if with_routing else logits


def loss(params, tokens, labels, **module):
    """Mean cross-entropy over the held rows of the vocabulary."""
    logits = forward(params, tokens, **module)
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp, labels[..., None], -1).mean()
