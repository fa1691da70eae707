"""The Nemotron-H decoders through ``models/nemotron_h.py::NemotronHLM``: layers
of one sublayer each, of three kinds (a Mamba-2 state-space mixer run as the
chunked form whose scan over chunks is the delta rule's pair with no ``W``;
ReLU^2 experts, not gated, routed by sigmoid scores with a selection bias
beside a ReLU^2 shared expert; grouped-query attention without positions); an
untied head.

What a later PR needs to know (``benchmarks/README.md`` is not edited for it):

* A configuration of this family is **one chip's share** of a deployment, and
  there are **three shares**: ``module.experts_held`` ``[first, count]`` of
  every ``moe`` layer's experts (the router keeps its published width and
  ``experts_per_token``), ``module.mamba_heads_held`` of the
  ``mamba_num_heads`` heads of every ``mamba`` layer in whole groups (their
  ``B``/``C`` groups and norm groups go with them), and ``module.heads_held``
  of the attention's ``num_heads`` query heads with the K/V heads they read.
  The router, the shared expert and the norms are whole. ``module.vocab_size``
  rows of embedding and head are held. The reference is given the same
  shares. The data draws its ids from the held rows, so the loss is over them.
* The **held layer pattern**: ``module.layer_types`` names the one sublayer of
  each of the ``module.num_layers`` held layers (``"mamba"``, ``"moe"`` or
  ``"attention"``). The published pattern (``hybrid_override_pattern``) stays
  at the file's top level; the held seven are published layers 6-12 (counted
  from 0), ``EMEMEM*``.
* The model is built on a short sample (``BUILD_LEN``), and ``build_model``
  moves its parameters to host memory, as ``families/lfm2.py`` does and for
  its reason.
* ``train_flops_per_unit`` counts the share's matmuls (6 a parameter), the
  scores of the attention layer (``12 k heads head_dim``), and the
  state-space recurrence's own operations (:func:`recurrence_flops_per_token`,
  the count ``readers/trace_ssd.py::ssd_floor`` takes). The chunked form's
  extra products, the taps, norms and gates are not counted.
* Which reader reads what: ``trace_scope`` the phases and the flash kernels'
  time; ``trace_moe`` the expert layer's scopes; ``readers/trace_ssd.py`` (new
  with this family) the scopes ``dk_ssd`` + ``dk_ssd_conv``
  (``kernel.ssd_ms.lm``, and ``kernel.ssd_roofline.lm`` against its own
  ``ssd_floor``) and the plain experts' share of their floor
  (``moe.experts_roofline.nemotron``: two products, where
  ``trace_moe.experts_floor`` counts three). The reference check's scan over
  8,192 positions is part of ``setup_s``.
"""

from __future__ import annotations

import numpy as np

from benchmarks.families import rel_l2
# the same seeded stream over the held rows, sample shapes, unit (a token) and
# Mosaic expectation as the other families that hold a share
from benchmarks.families.smallthinker import (expects_mosaic,  # noqa: F401
                                              make_dataframe, mean_keys_seen,
                                              sample_shapes,
                                              units_per_sample)
from benchmarks.families.transformer_lm import learnable_tokens
from benchmarks.references import nemotron_h as reference

#: What ``--rehearse`` swaps in for the configuration's sizes: control flow on
#: a CPU in seconds (the flash, row and scan kernels interpret there), all
#: three kinds of layer present, fewer experts, Mamba heads and attention
#: heads held than the model has, a group of ``B``/``C`` serving two heads.
#: Never measured.
TINY = {"module": {"vocab_size": 256, "num_layers": 3, "d_model": 128,
                   "num_heads": 4, "num_kv_heads": 2, "head_dim": 64,
                   "heads_held": [0, 2], "mamba_num_heads": 8,
                   "mamba_head_dim": 32, "mamba_heads_held": [0, 4],
                   "n_groups": 4, "ssm_state_size": 32, "d_expert": 64,
                   "d_shared": 128, "num_experts": 16, "experts_per_token": 4,
                   "experts_held": [0, 4],
                   "layer_types": ["moe", "mamba", "attention"]},
        "seq_len": 128}

#: the sample ``Model.build`` traces the module on
BUILD_LEN = 128


def build_model(config: dict, seed: int):
    import jax
    import jax.numpy as jnp

    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.nemotron_h import NemotronHLM

    model = Model.build(
        NemotronHLM.from_config(config["module"]),
        jnp.zeros((1, min(BUILD_LEN, config["seq_len"])), jnp.int32),
        seed=seed)
    # Host memory, as families/lfm2.py::build_model and for its reason: the
    # chip holds the trainer's state and, while a round runs, the NaN guard's
    # second copy of it.
    return model.with_params(jax.device_get(model.params))


def held_layers(module: dict) -> list:
    """The sublayers of the held layers, in order."""
    return list(module["layer_types"][:module["num_layers"]])


def held_heads(module: dict) -> dict:
    """Heads and groups a layer holds here: Mamba heads, their ``B``/``C``
    groups, attention's query heads and the K/V heads they read."""
    mamba = module["mamba_heads_held"][1]
    query = module["heads_held"][1]
    return {"mamba": mamba,
            "groups": mamba * module["n_groups"] // module["mamba_num_heads"],
            "query": query,
            "kv": query * module["num_kv_heads"] // module["num_heads"]}


def matmul_params_per_token(module: dict) -> float:
    """Parameters that multiply a token's activations here: a ``mamba``
    layer's ``W_in`` and ``W_out``, an ``attention`` layer's ``W_q``, ``W_k``,
    ``W_v``, ``W_o``, a ``moe`` layer's router, shared expert and the expected
    share of its routed experts (``experts_per_token * held / num_experts`` of
    them), and the head. The embedding is looked up; norms, taps, ``A_log``,
    ``dt_bias`` and ``D`` are elementwise."""
    d, held = module["d_model"], held_heads(module)
    inner = held["mamba"] * module["mamba_head_dim"]
    bc = held["groups"] * module["ssm_state_size"]
    sublayer = {
        "mamba": d * (2 * inner + 2 * bc + held["mamba"]) + inner * d,
        "attention": 2 * d * (held["query"] + held["kv"]) * module["head_dim"],
        "moe": d * module["num_experts"] + 2 * d * module["d_shared"]
        + module["experts_per_token"] * module["experts_held"][1]
        / module["num_experts"] * 2 * d * module["d_expert"]}
    return (sum(sublayer[kind] for kind in held_layers(module))
            + d * module["vocab_size"])


def attention_keys_seen(config: dict) -> list:
    """The mean number of keys a query sees, for each held attention layer
    (full and causal: ``(L + 1) / 2``)."""
    return [mean_keys_seen(config["seq_len"], None)
            for kind in held_layers(config["module"]) if kind == "attention"]


def recurrence_flops_per_token(module: dict) -> float:
    """The state-space recurrence's own operations, forward and backward, a
    token over the held ``mamba`` layers and heads: 4 a state element forward
    (the decay, the rank-one write, ``h^T C``) and 8 backward, ``12 *
    ssm_state_size * mamba_head_dim`` a token and head."""
    return 12.0 * module["ssm_state_size"] * module["mamba_head_dim"] \
        * held_heads(module)["mamba"] * held_layers(module).count("mamba")


def train_flops_per_unit(config: dict) -> float:
    """Forward and backward operations per token: 6 per matmul parameter,
    for each attention layer its two products over the keys a query sees,
    ``12 * k * heads * head_dim`` with the backward pass (``k`` = 4096.5 at
    8,192), and the recurrence's own count. The recomputed forward of
    ``remat`` is not counted."""
    m = config["module"]
    scores = 12.0 * held_heads(m)["query"] * m["head_dim"] \
        * sum(attention_keys_seen(config))
    return 6.0 * matmul_params_per_token(m) + scores \
        + recurrence_flops_per_token(m)


def reference_check(model, config: dict, seed: int, compute_dtype,
                    forward=reference.forward) -> dict:
    """One sequence at the timed length, in the trainer's compute dtype,
    against the plain reference on the same parameters (its ``mamba`` layers a
    scan over the positions). Two limits (``references/nemotron_h.py`` gives
    the reason for each): the relative L2 of the logits with the reference
    taking the model's choice of experts in every ``moe`` layer, and the share
    of (``moe`` layer, token) pairs at which the reference's own biased top-k
    is that choice."""
    import jax
    import jax.numpy as jnp

    m = config["module"]
    x, _ = learnable_tokens(1, config["seq_len"], m["vocab_size"], seed + 1)
    routed = [l for l, kind in enumerate(held_layers(m)) if kind == "moe"]

    def cast(a):
        if compute_dtype and jnp.issubdtype(a.dtype, jnp.floating):
            return a.astype(compute_dtype)
        return a

    def model_side(params, state, x):
        logits, sown = model.module.apply(
            {"params": jax.tree.map(cast, params), **state}, x,
            mutable=["intermediates"])
        chosen = [sown["intermediates"][f"block_{l}"]["experts"][0]
                  for l in routed]
        return logits.astype(jnp.float32), chosen

    # One copy of the parameters on the chip for both sides, gone when this
    # returns; the state is an argument, so the compile cache serves every
    # seed (families/lfm2.py).
    params = jax.device_put(model.params)
    got, chosen = jax.jit(model_side)(params, model.state or {}, x)
    ref, own = jax.jit(lambda p, x, chosen: forward(
        p, x, **m, chosen=chosen, with_routing=True))(
            reference.with_bias(params, model.state), x, chosen)
    alike = float(np.mean([
        np.all(np.sort(np.asarray(a), -1) == np.sort(np.asarray(b), -1), -1)
        for a, b in zip(chosen, own)])) if routed else 1.0
    err = rel_l2(got, ref)
    if compute_dtype:
        tol, tol_routing = reference.TOLERANCE, reference.TOLERANCE_ROUTING
    else:
        tol, tol_routing = reference.TOLERANCE_FLOAT32, 1.0
    return {"rel_l2": err, "tolerance": tol, "routing_agreement": alike,
            "tolerance_routing": tol_routing,
            "ok": bool(np.all(np.isfinite(np.asarray(got)))) and err <= tol
            and alike >= tol_routing}
