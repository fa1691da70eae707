"""GPT-2's forward pass, plainly (Radford et al. 2019; the block of
``huggingface.co/openai-community/gpt2-medium``): token + learned position
embeddings, ``num_layers`` pre-LN blocks (LayerNorm, causal multi-head
attention, residual; LayerNorm, ``d_ff`` MLP with the tanh form of GELU,
residual), a final LayerNorm and a linear head.

Departures of the model under test from the published one, followed here
because the reference must compute what the configuration states: the head is
its own matrix with a bias (not tied to the token embedding), LayerNorm's
epsilon is flax's 1e-6 (GPT-2: 1e-5), no dropout.

Parameters are read from the model's own tree by name; nothing else of the
program is used.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: Relative L2 on the logits, model in bfloat16 against this in float32.
#: bfloat16 keeps 8 bits of mantissa (2**-9 = 2e-3 a rounding); through 24
#: blocks of two matmul stages each the roundings add like a random walk,
#: sqrt(50) * 2e-3 = 1.4e-2, and 1.1e-2 to 1.2e-2 was measured on the chip at
#: full width over ten seeds (PERF.md, PR 24). 3e-2 leaves room for the seed
#: and still fails what matters: a skipped block moves the logits by 1e-1 or
#: more, as does computing in fewer bits than bfloat16. The two forms of GELU
#: differ by less than bfloat16's own noise, so the form is pinned by the CPU
#: test, in float32 at 1e-4 (with the module's dense attention: the flash
#: kernel rounds its operands to bfloat16 by design, 2e-3 by itself).
TOLERANCE = 3e-2
TOLERANCE_FLOAT32 = 1e-4


def _layer_norm(x, p, eps=1e-6):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def _attention(x, p, num_heads):
    B, L, D = x.shape
    q = jnp.einsum("bld,dhk->blhk", x, p["query"]["kernel"]) + p["query"]["bias"]
    k = jnp.einsum("bld,dhk->blhk", x, p["key"]["kernel"]) + p["key"]["bias"]
    v = jnp.einsum("bld,dhk->blhk", x, p["value"]["kernel"]) + p["value"]["bias"]
    scores = jnp.einsum("bqhk,bthk->bhqt", q, k) / jnp.sqrt(D // num_heads)
    causal = jnp.tril(jnp.ones((L, L), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    out = jnp.einsum("bhqt,bthk->bqhk", jax.nn.softmax(scores, -1), v)
    return jnp.einsum("bqhk,hkd->bqd", out, p["out"]["kernel"]) + p["out"]["bias"]


def forward(params, tokens, *, num_layers, num_heads, **_):
    """Logits ``[B, L, V]`` in float32 with exact matmuls."""
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        L = tokens.shape[1]
        x = (params["tok_embed"]["embedding"][tokens]
             + params["pos_embed"]["embedding"][:L][None])
        for i in range(num_layers):
            p = params[f"block_{i}"]
            x = x + _attention(_layer_norm(x, p["ln_attn"]), p["attn"],
                               num_heads)
            h = _layer_norm(x, p["ln_mlp"])
            h = _gelu_tanh(h @ p["mlp_up"]["kernel"] + p["mlp_up"]["bias"])
            x = x + h @ p["mlp_down"]["kernel"] + p["mlp_down"]["bias"]
        x = _layer_norm(x, params["ln_final"])
        return x @ params["lm_head"]["kernel"] + params["lm_head"]["bias"]
