"""The Qwen3-Next decoders through ``models/qwen3_next.py::Qwen3NextLM``: layers
of two kinds (Gated DeltaNet, a gated delta rule with one decay a head and
key heads shared by two value heads each, run as the chunked form Kimi
Linear runs, in three of four; gated attention at head width 256 with a
quarter of each head rotated in the fourth), each with SwiGLU experts routed
by softmax beside a shared expert weighed by a sigmoid gate; an untied head.

What a later PR needs to know (``benchmarks/README.md`` is not edited for it):

* A configuration of this family is **one chip's share** of a deployment:
  ``module.experts_held`` ``[first, count]`` of every layer's experts (the
  router keeps its published width and ``experts_per_token``) and
  ``module.vocab_size`` rows of embedding and head; the heads are whole. The
  reference is given the same share. The data draws its ids from the held
  rows, so the loss is over them.
* The **held layer pattern**: ``module.layer_types`` names the operator of
  each of the ``module.num_layers`` held layers (``"gdn"`` or
  ``"attention"``); every layer is routed. The published pattern
  (``full_attention_interval``) stays at the file's top level.
* The model is built on a short sample (``BUILD_LEN``), and ``build_model``
  moves its parameters to host memory, as ``families/lfm2.py`` does and for
  its reason.
* ``train_flops_per_unit`` counts the share's matmuls (6 a parameter), the
  scores of the attention layers (``12 k heads head_dim``), and the
  recurrence's own operations in the ``gdn`` layers (``21 * 128 * 128`` a
  token and value head). The chunked form's extra products, the taps, norms
  and gates are not counted.
* Which reader reads what: ``trace_scope`` the phases and the flash kernels'
  time; ``trace_moe`` the expert layer's scopes and ``moe.experts_roofline
  .qwen3next``; ``trace_kda`` ``dk_moe_shared`` (``moe.shared_ms.lm``);
  ``readers/trace_gdn.py`` (new with this family) the scopes ``dk_gdn`` +
  ``dk_gdn_conv`` (``kernel.gdn_ms.lm``, and ``kernel.gdn_roofline.lm``
  against its own ``gdn_floor``) and the flash kernels' share of the causal
  floor at head width 256 (``kernel.flash_roofline.hd256``). The reference
  check's scan over 8,192 positions is part of ``setup_s``.
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
from benchmarks.references import qwen3_next as reference

#: What ``--rehearse`` swaps in for the configuration's sizes: control flow on
#: a CPU in seconds (the flash, row and delta-rule kernels interpret there),
#: both operators present, a key head serving two value heads, a quarter of
#: each attention head rotated, fewer experts held than the router routes
#: over. Heads of 64 channels, as ``families/kimi_linear.py``'s preset and
#: for its reason. Never measured.
TINY = {"module": {"vocab_size": 256, "num_layers": 2, "d_model": 128,
                   "num_heads": 4, "num_kv_heads": 2, "head_dim": 64,
                   "rotary_dim": 16, "linear_key_heads": 2,
                   "linear_value_heads": 4, "linear_head_dim": 64,
                   "d_expert": 64, "d_shared": 64, "num_experts": 16,
                   "experts_per_token": 4, "experts_held": [0, 4],
                   "layer_types": ["gdn", "attention"]},
        "seq_len": 128}

#: the sample ``Model.build`` traces the module on
BUILD_LEN = 128


def build_model(config: dict, seed: int):
    import jax
    import jax.numpy as jnp

    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.qwen3_next import Qwen3NextLM

    model = Model.build(
        Qwen3NextLM.from_config(config["module"]),
        jnp.zeros((1, min(BUILD_LEN, config["seq_len"])), jnp.int32),
        seed=seed)
    # Host memory, as families/lfm2.py::build_model and for its reason: the
    # chip holds the trainer's state and, while a round runs, the NaN guard's
    # second copy of it.
    return model.with_params(jax.device_get(model.params))


def held_layers(module: dict) -> list:
    """The operators of the held layers, in order."""
    return list(module["layer_types"][:module["num_layers"]])


def matmul_params_per_token(module: dict) -> float:
    """Parameters that multiply a token's activations here: a layer's
    operator (``gdn``: ``W_qkvz``, ``W_ba``, ``W_o``; ``attention``: ``W_q``
    with its gate columns, ``W_k``, ``W_v``, ``W_o``), its router, shared
    expert and the shared expert's gate, the expected share of its routed
    experts (``experts_per_token * held / num_experts`` of them), and the
    head. The embedding is looked up; norms, taps, ``A_log`` and ``dt_bias``
    are elementwise."""
    d = module["d_model"]
    keys = module["linear_key_heads"] * module["linear_head_dim"]
    values = module["linear_value_heads"] * module["linear_head_dim"]
    width = module["num_heads"] * module["head_dim"]
    operator = {
        "gdn": d * (2 * keys + 2 * values) + d * 2
        * module["linear_value_heads"] + values * d,
        "attention": d * 2 * width
        + 2 * d * module["num_kv_heads"] * module["head_dim"] + width * d}
    routed = d * module["num_experts"] + 3 * d * module["d_shared"] + d \
        + module["experts_per_token"] * module["experts_held"][1] \
        / module["num_experts"] * 3 * d * module["d_expert"]
    return (sum(operator[op] + routed for op in held_layers(module))
            + d * module["vocab_size"])


def attention_keys_seen(config: dict) -> list:
    """The mean number of keys a query sees, for each held attention layer
    (full and causal: ``(L + 1) / 2``)."""
    return [mean_keys_seen(config["seq_len"], None)
            for op in held_layers(config["module"]) if op == "attention"]


def recurrence_flops_per_token(module: dict) -> float:
    """The delta rule's own operations, forward and backward, a token over
    the held ``gdn`` layers and value heads: 7 a state element forward (the
    decay, ``S'^T k``, the rank-one update, ``S^T q``) and twice that
    backward."""
    return 21.0 * module["linear_head_dim"] ** 2 \
        * module["linear_value_heads"] * held_layers(module).count("gdn")


def train_flops_per_unit(config: dict) -> float:
    """Forward and backward operations per token: 6 per matmul parameter,
    for each attention layer its two products over the keys a query sees,
    ``12 * k * heads * head_dim`` with the backward pass (``k`` = 4096.5 at
    8,192), and the recurrence's own count. The recomputed forward of
    ``remat`` is not counted."""
    m = config["module"]
    scores = 12.0 * m["num_heads"] * m["head_dim"] \
        * sum(attention_keys_seen(config))
    return 6.0 * matmul_params_per_token(m) + scores \
        + recurrence_flops_per_token(m)


def reference_check(model, config: dict, seed: int, compute_dtype,
                    forward=reference.forward) -> dict:
    """One sequence at the timed length, in the trainer's compute dtype,
    against the plain reference on the same parameters (its ``gdn`` layers a
    scan over the positions). Two limits (``references/qwen3_next.py`` gives
    the reason for each): the relative L2 of the logits with the reference
    taking the model's choice of experts in every layer, and the share of
    (layer, token) pairs at which the reference's own top-k is that choice."""
    import jax
    import jax.numpy as jnp

    m = config["module"]
    x, _ = learnable_tokens(1, config["seq_len"], m["vocab_size"], seed + 1)

    def cast(a):
        if compute_dtype and jnp.issubdtype(a.dtype, jnp.floating):
            return a.astype(compute_dtype)
        return a

    def model_side(params, state, x):
        logits, sown = model.module.apply(
            {"params": jax.tree.map(cast, params), **state}, x,
            mutable=["intermediates"])
        chosen = [sown["intermediates"][f"block_{l}"]["experts"][0]
                  for l in range(m["num_layers"])]
        return logits.astype(jnp.float32), chosen

    # One copy of the parameters on the chip for both sides, gone when this
    # returns; the state is an argument, so the compile cache serves every
    # seed (families/lfm2.py).
    params = jax.device_put(model.params)
    got, chosen = jax.jit(model_side)(params, model.state or {}, x)
    ref, own = jax.jit(lambda p, x, chosen: forward(
        p, x, **m, chosen=chosen, with_routing=True))(params, x, chosen)
    alike = float(np.mean([
        np.all(np.sort(np.asarray(a), -1) == np.sort(np.asarray(b), -1), -1)
        for a, b in zip(chosen, own)]))
    err = rel_l2(got, ref)
    if compute_dtype:
        tol, tol_routing = reference.TOLERANCE, reference.TOLERANCE_ROUTING
    else:
        tol, tol_routing = reference.TOLERANCE_FLOAT32, 1.0
    return {"rel_l2": err, "tolerance": tol, "routing_agreement": alike,
            "tolerance_routing": tol_routing,
            "ok": bool(np.all(np.isfinite(np.asarray(got)))) and err <= tol
            and alike >= tol_routing}
