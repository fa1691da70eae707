"""The Kimi Linear decoders through ``models/kimi_linear.py::KimiLinearLM``:
layers of two kinds (Kimi Delta Attention, a gated delta rule with a decay a
channel run as a chunked scan, in three of four; latent attention without
positions, keys of 192 beside values of 128, in the fourth), a leading dense
SwiGLU layer, then SwiGLU experts routed by sigmoid scores with a selection
bias beside a shared expert; an untied head.

What a later PR needs to know (``benchmarks/README.md`` is not edited for it):

* A configuration of this family is **one chip's share** of a deployment, and
  there are **two shares**: ``module.experts_held`` ``[first, count]`` of
  every routed layer's experts (as in ``families/lfm2.py``: the router keeps
  its published width and ``experts_per_token``) and ``module.heads_held``
  ``[first, count]`` of the ``module.num_heads`` heads of every ``kda`` and
  ``mla`` layer (head projections and ``W_o``'s rows; ``W_fa``, ``W_ga``,
  ``W_kva``, the norms, the router, the shared expert and the dense layer are
  whole). ``module.vocab_size`` rows of embedding and head are held. The
  reference is given the same shares. The data draws its ids from the held
  rows, so the loss is over them.
* The **held layer pattern**: ``module.layer_types`` names the operator of
  each of the ``module.num_layers`` held layers (``"kda"`` or ``"mla"``), of
  which the first ``module.num_dense_layers`` carry the dense feed-forward and
  the others the shared and routed one. The published pattern
  (``linear_attn_config.kda_layers`` / ``full_attn_layers``) stays at the
  file's top level; the held five are published layers 1-5.
* The model is built on a short sample (``BUILD_LEN``), and ``build_model``
  moves its parameters to host memory, as ``families/lfm2.py`` does and for
  its reason.
* ``train_flops_per_unit`` counts the share's matmuls (6 a parameter), the
  scores of the layers that have attention (``12 k heads (192 + 128) / 2``),
  and the recurrence's own operations in the ``kda`` layers (``21 * 128 *
  128`` a token and held head: decay, ``S'^T k``, the rank-one update and
  ``S^T q`` forward, twice that backward). The chunked form's extra products,
  the taps, norms and gates are not counted.
* Which reader reads what: ``trace_scope`` the phases and the flash kernels'
  time; ``trace_moe`` the expert layer's scopes and ``moe.experts_roofline
  .kimi``; ``readers/trace_kda.py`` (new with this family) the scopes
  ``dk_kda`` + ``dk_kda_conv`` (``kernel.kda_ms.lm``, and
  ``kernel.kda_roofline.lm`` against its own ``kda_floor``), ``dk_moe_shared``
  (``moe.shared_ms.lm``) and the flash kernels' share of
  ``latent_flash_floor`` (``kernel.flash_roofline.mla``: unequal widths, and
  layers without attention count nothing). The reference check's scan over
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
from benchmarks.references import kimi_linear as reference

#: What ``--rehearse`` swaps in for the configuration's sizes: control flow on
#: a CPU in seconds (the flash and row kernels interpret there), both
#: operators and both feed-forwards present (a dense ``kda`` layer, a routed
#: ``mla`` layer), fewer experts and heads held than the model has. Two
#: layers and ``kda`` heads of 64 channels: the delta rule carries its inputs'
#: bfloat16 rounding through the whole sequence, a head of 16 channels
#: averages little of it, and each further ``kda`` layer at such widths adds
#: a hundredth to the reference check's reading (three layers of 16-channel
#: heads read 3e-2 to 4e-2 on a CPU, where the published widths read 2e-2 on
#: the chip); this preset reads 1.6e-2 to 2.1e-2 against the limit of 2.5e-2.
#: Never measured.
TINY = {"module": {"vocab_size": 256, "num_layers": 2, "d_model": 128,
                   "num_heads": 4, "heads_held": [0, 2], "kda_head_dim": 64,
                   "qk_nope_head_dim": 32, "qk_rope_head_dim": 16,
                   "v_head_dim": 32, "kv_lora_rank": 64, "d_ff": 192,
                   "d_expert": 64, "num_experts": 16, "experts_per_token": 4,
                   "experts_held": [0, 4], "num_dense_layers": 1,
                   "layer_types": ["kda", "mla"]},
        "seq_len": 128}

#: the sample ``Model.build`` traces the module on
BUILD_LEN = 128


def build_model(config: dict, seed: int):
    import jax
    import jax.numpy as jnp

    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.kimi_linear import KimiLinearLM

    model = Model.build(
        KimiLinearLM.from_config(config["module"]),
        jnp.zeros((1, min(BUILD_LEN, config["seq_len"])), jnp.int32),
        seed=seed)
    # Host memory, as families/lfm2.py::build_model and for its reason: the
    # chip holds the trainer's state and, while a round runs, the NaN guard's
    # second copy of it.
    return model.with_params(jax.device_get(model.params))


def held_layers(module: dict) -> list:
    """``[(operator, routed)]`` of the held layers, in order."""
    return [(module["layer_types"][l], l >= module["num_dense_layers"])
            for l in range(module["num_layers"])]


def matmul_params_per_token(module: dict) -> float:
    """Parameters that multiply a token's activations here: a layer's
    operator (``kda``: the three head projections, ``W_fa``, ``W_fb``,
    ``W_b``, ``W_ga``, ``W_gb``, ``W_o``; ``mla``: ``W_q``, ``W_kva``,
    ``W_kvb``, ``W_o``), its dense feed-forward's three matrices or its
    router, its shared expert and the expected share of its routed experts
    (``experts_per_token * held / num_experts`` of them), and the head. The
    embedding is looked up; norms, taps, ``A_log`` and ``dt_bias`` are
    elementwise."""
    d, heads = module["d_model"], module["heads_held"][1]
    hk = heads * module["kda_head_dim"]
    qk = module["qk_nope_head_dim"] + module["qk_rope_head_dim"]
    operator = {
        "kda": 3 * d * hk + 2 * (d * module["kda_head_dim"]
                                 + module["kda_head_dim"] * hk)
        + d * heads + hk * d,
        "mla": d * heads * qk
        + d * (module["kv_lora_rank"] + module["qk_rope_head_dim"])
        + module["kv_lora_rank"] * heads * (module["qk_nope_head_dim"]
                                            + module["v_head_dim"])
        + heads * module["v_head_dim"] * d}
    expert = 3 * d * module["d_expert"]
    routed = d * module["num_experts"] + (
        module["num_shared_experts"] + module["experts_per_token"]
        * module["experts_held"][1] / module["num_experts"]) * expert
    dense = 3 * d * module["d_ff"]
    return (sum(operator[op] + (routed if r else dense)
                for op, r in held_layers(module))
            + d * module["vocab_size"])


def attention_keys_seen(config: dict) -> list:
    """The mean number of keys a query sees, for each held layer that has
    attention (full and causal: ``(L + 1) / 2``)."""
    return [mean_keys_seen(config["seq_len"], None)
            for op, _ in held_layers(config["module"]) if op == "mla"]


def recurrence_flops_per_token(module: dict) -> float:
    """The delta rule's own operations, forward and backward, a token over
    the held ``kda`` layers and heads: 7 a state element forward (the decay,
    ``S'^T k``, the rank-one update, ``S^T q``) and twice that backward."""
    layers = sum(op == "kda" for op, _ in held_layers(module))
    return 21.0 * module["kda_head_dim"] ** 2 * module["heads_held"][1] \
        * layers


def train_flops_per_unit(config: dict) -> float:
    """Forward and backward operations per token: 6 per matmul parameter,
    for each attention layer its two products over the keys a query sees,
    ``12 * k * heads * (192 + 128) / 2`` with the backward pass (``k`` =
    4096.5 at 8,192), and the recurrence's own count. The recomputed forward
    of ``remat`` is not counted."""
    m = config["module"]
    width = (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
             + m["v_head_dim"]) / 2
    scores = 12.0 * m["heads_held"][1] * width \
        * sum(attention_keys_seen(config))
    return 6.0 * matmul_params_per_token(m) + scores \
        + recurrence_flops_per_token(m)


def reference_check(model, config: dict, seed: int, compute_dtype,
                    forward=reference.forward) -> dict:
    """One sequence at the timed length, in the trainer's compute dtype,
    against the plain reference on the same parameters (its ``kda`` layers a
    scan over the positions). Two limits (``references/kimi_linear.py`` gives
    the reason for each): the relative L2 of the logits with the reference
    taking the model's choice of experts in every routed layer, and the share
    of (routed layer, token) pairs at which the reference's own biased top-k
    is that choice."""
    import jax
    import jax.numpy as jnp

    m = config["module"]
    x, _ = learnable_tokens(1, config["seq_len"], m["vocab_size"], seed + 1)
    routed = [l for l, (_, r) in enumerate(held_layers(m)) if r]

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
            reference.with_bias(params, model.state) if routed else params,
            x, chosen)
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
