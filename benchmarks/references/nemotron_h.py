"""The forward pass, loss and (through ``jax.grad``) gradients of the
Nemotron-H decoders, plainly (NVIDIA; the published ``config.json`` of
``NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``, ``model_type`` ``nemotron_h``), for
one chip's share of the model: ``mamba_heads_held[1]`` of the Mamba-2 heads
(whole groups of ``B``/``C`` and of the gated norm), ``heads_held[1]`` of the
attention's query heads with their K/V heads, ``experts_held`` of the routed
experts, ``vocab_size`` rows.

Every layer holds **one** sublayer: ``x <- x + Op_l(RMSNorm(x))``, ``Op_l`` by
``layer_types[l]``; nothing has a bias but the convolution; RMSNorm's epsilon
is inside the root:

* ``"mamba"`` (Mamba-2), ``H`` heads of ``P = mamba_head_dim``, ``G`` groups
  of ``N = ssm_state_size``: ``[z | x | B | C | dt] = h W_in`` (widths ``HP,
  HP, GN, GN, H``); ``x, B, C = SiLU(conv(x | B | C) + b)``, ``conv`` causal
  and depthwise with ``conv_kernel`` taps a channel (the last on the current
  position), zeros before the sequence starts; ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)``, one of each a head; head ``j`` reads group
  ``j // (H / G)`` of ``B`` and ``C``; its state ``h`` ``[N, P]``, ``h_0 =
  0``, **a position at a time** (``lax.scan`` over the positions, nothing
  chunked: independent of the chunked form and the kernels under test):

      h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T        y_t = h_t^T C_t + D x_t

  ``y <- RMSNorm_group(y * SiLU(z)) * w`` over groups of ``HP / G`` columns
  (one weight of ``HP``), then ``W_out``.
* ``"attention"``: ``q = h W_q`` (``heads_held[1]`` heads of ``head_dim``),
  ``k = h W_k``, ``v = h W_v`` (the K/V heads those query heads read); **no
  positions**; query ``i`` sees keys ``j <= i``; query head ``n`` reads K/V
  head ``n // (query heads / K/V heads)``; scores scaled by
  ``head_dim^-1/2``; ``W_o``.
* ``"moe"``: ``p = sigmoid(h W_r)`` over all ``num_experts`` in float32; ``e =
  top_k(p + b)`` with ``b`` the correction bias; ``w = p[e]``; ``w <- w /
  (sum(w) + 1e-20) * routed_scaling_factor``; ``sum over the chosen experts
  e_k held here of w_k E_{e_k}(h) + Shared(h)``, ``E`` and ``Shared`` both
  ``relu(h W_up)^2 W_down``, **not gated**. Experts densely: every token
  through every held expert, masked. Where fewer experts are held than routed
  over, no gradient passes through the router's logits; none reaches the
  bias anywhere.

After the last layer RMSNorm and the head's held columns (untied). Loss: mean
cross-entropy over those rows.

Departures from the published model, as in the program (the configuration
file lists them): no multi-token prediction, no auxiliary loss, no dropout;
the Mamba state and the convolution's history start at zero with every
sequence.

Attention runs in blocks of queries so that ``[heads, block, L]`` scores are
alive at once. ``round_to`` rounds every product's operands, the
convolution's, the gates' and the recurrence's inputs to that dtype first
(float32 accumulation and a float32 state stay): how the tests and PERF.md
compute "the reference in a precision below the configuration's". ``without``
names terms to leave out (``FAULTS``): how the tests show that each term of
the mathematics fails a limit when dropped. ``chosen`` and ``with_routing``
are ``references/smallthinker.py``'s.

Parameters are read from the model's own tree by name; the correction biases,
which the model keeps beside its parameters as state, are put into that tree
by :func:`with_bias`. Nothing else of the program is used.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.references.kimi_linear import route
from benchmarks.references.lfm2 import (_dot, _rms_norm, _rounded,  # noqa: F401
                                        with_bias)

#: Two limits on one sequence of 8,192 at the published widths, model in
#: bfloat16 against this in float32 (``families/nemotron_h.py::
#: reference_check``); readings on the chip (PERF.md §6).
#:
#: ``TOLERANCE``: relative L2 on the logits with the reference using the
#: model's choice of experts in every ``moe`` layer (its own scores of
#: them): the arithmetic alone. Table and head are N(0, 0.02^2), so the
#: stream is what the layers add and every product stage's rounding at 2**-9
#: reaches the logits, as in the other expert families. It reads 1.057e-2 to
#: 1.082e-2 in nine readings (nine seeds of the weights; the reference with
#: its own operands rounded to bfloat16 reads 8.1e-3 to 8.3e-3 against
#: itself in float32), about half of what Kimi Linear and Qwen3-Next read
#: against their references. With every operand
#: rounded to 8 bits the same comparison reads 1.43e-1 and 1.51e-1
#: (float8_e4m3) and 2.56e-1 and 2.62e-1 (float8_e5m2). 3e-2 lies 2.8 times
#: above the largest bfloat16 reading and 4.8 times below the float8_e4m3
#: control (the tiny preset a rehearsal runs reads 8.8e-3 to 9.4e-3 on a
#: CPU).
TOLERANCE = 3e-2
#: ``TOLERANCE_ROUTING``: the least share of (``moe`` layer, token) pairs
#: whose six experts of 128 the two sides choose alike: 0.952 to 0.960 in
#: bfloat16, 0.658 (float8_e4m3) and 0.327 (float8_e5m2) with 8-bit
#: operands. 0.8 lies 0.15 below the first and 0.14 above the control. A
#: model that chose by another rule would agree on the logits above and read
#: near 0 here.
TOLERANCE_ROUTING = 0.8
#: float32 against float32 on the CPU, dense attention in the module: the
#: order of sums differs, and the chunked form against the recurrence.
TOLERANCE_FLOAT32 = 1e-4

QUERY_BLOCK = 512

#: what ``without`` may name: each a term of the equations above
FAULTS = ("decay", "d_skip", "z_gate", "groups", "norm_groups", "conv_bias",
          "shared", "scale")


def ssd_recurrence(x, dt, A, Bm, Cm, round_to=None, group=None):
    """The state-space recurrence itself, a position at a time, without the
    ``D`` skip: ``x`` [B, L, H, P], ``dt`` [B, L, H], ``A`` [H], ``Bm``,
    ``Cm`` [B, L, G, N]; head ``j`` reads group ``group[j]``, ``j // (H /
    G)`` by default. Returns ``y`` [B, L, H, P]."""
    Bsz, L, H, P = x.shape
    G, N = Bm.shape[2:]
    if group is None:
        group = jnp.arange(H) // (H // G)

    def step(h, xs):
        x, dt, b, c = xs
        b, c = b[:, group], c[:, group]                     # [B, H, N]
        h = jnp.exp(dt * A)[..., None, None] * h \
            + (dt[..., None] * b)[..., None] * x[..., None, :]
        return h, jnp.einsum("bhnp,bhn->bhp", h, c)

    xs = tuple(jnp.moveaxis(_rounded(a, round_to), 1, 0)
               for a in (x, dt, Bm, Cm))
    _, y = jax.lax.scan(step, jnp.zeros((Bsz, H, N, P)), xs)
    return jnp.moveaxis(y, 0, 1)


def mamba(h, p, *, heads, groups, head_dim, state_dim, rms_eps,
          round_to=None, without=()):
    """The ``mamba`` operator on the normalised stream ``h`` ``[B, L, d]``."""
    B, L, _ = h.shape
    inner, bc = heads * head_dim, groups * state_dim
    zxbcdt = _dot(h, p["in_proj"]["kernel"], "bld,de->ble", round_to)
    z, xbc = zxbcdt[..., :inner], zxbcdt[..., inner:2 * inner + 2 * bc]
    dt = zxbcdt[..., 2 * inner + 2 * bc:]
    taps = p["conv_taps"]                                  # [channels, K]
    K = taps.shape[1]
    padded = jnp.pad(_rounded(xbc, round_to), ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(_rounded(taps[:, j], round_to) * padded[:, j:j + L]
               for j in range(K))
    if "conv_bias" not in without:
        conv = conv + p["conv_bias"]
    xbc = jax.nn.silu(conv)
    x = xbc[..., :inner].reshape(B, L, heads, head_dim)
    Bm = xbc[..., inner:inner + bc].reshape(B, L, groups, state_dim)
    Cm = xbc[..., inner + bc:].reshape(B, L, groups, state_dim)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    if "decay" in without:
        A = jnp.zeros_like(A)
    # the fault: head j on group j mod G (a tile where a repeat belongs)
    y = ssd_recurrence(x, dt, A, Bm, Cm, round_to,
                       jnp.arange(heads) % groups if "groups" in without
                       else None)
    if "d_skip" not in without:
        y = y + p["D"][:, None] * x
    y = y.reshape(B, L, inner)
    if "z_gate" not in without:
        y = y * jax.nn.silu(z)
    by = 1 if "norm_groups" in without else groups
    y = y.reshape(B, L, by, inner // by)
    y = y * jax.lax.rsqrt((y * y).mean(-1, keepdims=True) + rms_eps)
    y = y.reshape(B, L, inner) * p["norm"]
    return _dot(y, p["out_proj"]["kernel"], "bld,de->ble", round_to)


def attention(h, p, *, head_dim, round_to=None):
    """The ``attention`` operator on the normalised stream ``h``: no
    positions, query heads over the K/V heads the parameters hold."""
    L = h.shape[1]
    q = _dot(h, p["query"]["kernel"], "bld,dhk->blhk", round_to)
    k = _dot(h, p["key"]["kernel"], "bld,dhk->blhk", round_to)
    v = _dot(h, p["value"]["kernel"], "bld,dhk->blhk", round_to)
    group = q.shape[2] // k.shape[2]
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
    return _dot(jnp.concatenate(blocks, axis=1), p["out"]["kernel"],
                "bqhk,hkd->bqd", round_to)


def relu2_mlp(g, up, down, round_to=None):
    """``relu(g W_up)^2 W_down``: an expert, or the shared expert."""
    hidden = jnp.square(jax.nn.relu(_dot(g, up, "bld,df->blf", round_to)))
    return _dot(hidden, down, "blf,fd->bld", round_to)


def experts(g, weights, chosen, p, first, held, round_to=None):
    """The held experts' part of the routed sublayer, every token through
    every held expert, masked."""
    out = jnp.zeros_like(g)
    for n in range(held):
        w = jnp.sum(jnp.where(chosen == first + n, weights, 0.0), -1)
        out += w[..., None] * relu2_mlp(g, p["up"]["kernel"][n],
                                        p["down"]["kernel"][n], round_to)
    return out


def forward(params, tokens, *, num_layers, layer_types, mamba_heads_held,
            mamba_num_heads, n_groups, mamba_head_dim, ssm_state_size,
            head_dim, experts_per_token, experts_held, routed_scaling_factor,
            rms_eps, round_to=None, chosen=None, with_routing=False,
            without=(), **_):
    """Logits ``[B, L, V_held]`` in float32 with exact matmuls. ``chosen`` (a
    ``[B, L, k]`` array of expert ids a ``moe`` layer, in order) makes every
    ``moe`` layer use that choice in the place of its own top-k;
    ``with_routing`` also returns the reference's own choice of every ``moe``
    layer."""
    if set(without) - set(FAULTS):
        raise ValueError(f"without {without}: not among {FAULTS}")
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    first, held = experts_held
    heads = mamba_heads_held[1]
    groups = heads * n_groups // mamba_num_heads
    routing = []
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed"]["embedding"][tokens]
        for l in range(num_layers):
            p = params[f"block_{l}"]
            h = _rms_norm(x, p["norm"], rms_eps)
            if layer_types[l] == "mamba":
                x = x + mamba(h, p["mixer"], heads=heads, groups=groups,
                              head_dim=mamba_head_dim,
                              state_dim=ssm_state_size, rms_eps=rms_eps,
                              round_to=round_to, without=without)
            elif layer_types[l] == "attention":
                x = x + attention(h, p["attn"], head_dim=head_dim,
                                  round_to=round_to)
            else:
                weights, used, own = route(
                    h, p, experts_per_token,
                    1.0 if "scale" in without else routed_scaling_factor,
                    round_to, None if chosen is None else chosen[len(routing)],
                    trained=held == p["router"]["kernel"].shape[1])
                routing.append(own)
                x = x + experts(h, weights, used, p["moe"]["experts"], first,
                                held, round_to)
                if "shared" not in without:
                    s = p["shared"]
                    x = x + relu2_mlp(h, s["up"]["kernel"],
                                      s["down"]["kernel"], round_to)
        x = _rms_norm(x, params["ln_final"], rms_eps)
        logits = _dot(x, params["head"]["kernel"], "bld,dv->blv", round_to)
        return (logits, routing) if with_routing else logits


def loss(params, tokens, labels, **module):
    """Mean cross-entropy over the held rows of the vocabulary."""
    logits = forward(params, tokens, **module)
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp, labels[..., None], -1).mean()
