"""The LFM2 mixture-of-experts decoders through ``models/lfm2.py::Lfm2MoeLM``:
layers of two kinds (a gated short convolution in three of four, grouped-query
attention with q/k RMSNorm in the fourth), a leading dense SwiGLU layer, then
SwiGLU experts routed by sigmoid scores with a selection bias; a tied head.

What a later PR needs to know (``benchmarks/README.md`` is not edited for it):

* A configuration of this family is **one chip's share** of a deployment, as
  in ``families/smallthinker.py``: ``module.experts_held`` ``[first, count]``
  of every routed layer's experts, ``module.vocab_size`` rows of the (tied)
  vocabulary, and the **held layer pattern**: ``module.layer_types`` names the
  operator of each of the ``module.num_layers`` held layers (``"conv"`` or
  ``"full_attention"``), of which the first ``module.num_dense_layers`` carry
  the dense feed-forward and the others the routed one. The published
  ``layer_types`` (40 entries) stays at the file's top level; the held five
  are published layers 0, 2, 3, 4, 5. The router keeps its published width
  and ``experts_per_token``; the reference is given the same share. The data
  draws its ids from the held rows, so the loss is over them.
* The model is built on a short sample (``BUILD_LEN``): parameters do not
  depend on the length, and ``Model.build`` runs the module's dense attention.
  ``build_model`` then moves the parameters to host memory: the chip holds the
  trainer's state (16 B a parameter) and, inside the round program, the NaN
  guard's second copy of it, and has no room for an idle third.
* ``train_flops_per_unit`` counts the share's matmuls (6 a parameter: the
  operator's projections, the router, ``experts_per_token * held /
  num_experts`` experts' worth, the dense feed-forward where there is one,
  the head) and attention's scores for the layers that have attention. The
  convolution's taps and gates are elementwise and not counted.
* Which reader reads what: ``trace_scope`` the phases and the flash kernels'
  time; ``trace_moe`` the expert layer's scopes and its roofline share (from
  the ``moe.round`` events of the traced rounds: ``layers`` there counts the
  *routed* layers); ``readers/trace_shortconv.py`` (new with this family) the
  scope ``dk_shortconv`` (time, and the share of its own
  ``shortconv_floor``) and the flash kernels' share of the floor of the
  layers that have attention (``trace_moe.flash_window_floor`` over
  :func:`attention_keys_seen`, so that layers without attention count
  nothing).
"""

from __future__ import annotations

import numpy as np

from benchmarks.families import rel_l2
# the same seeded stream over the held rows, sample shapes, unit (a token) and
# Mosaic expectation as the other family that holds a share
from benchmarks.families.smallthinker import (expects_mosaic,  # noqa: F401
                                              make_dataframe, mean_keys_seen,
                                              sample_shapes,
                                              units_per_sample)
from benchmarks.families.transformer_lm import learnable_tokens
from benchmarks.references import lfm2 as reference

#: What ``--rehearse`` swaps in for the configuration's sizes: control flow on
#: a CPU in seconds (the flash and row kernels interpret there), every kind of
#: layer present, fewer experts held than routed. Never measured.
TINY = {"module": {"vocab_size": 256, "num_layers": 3, "d_model": 64,
                   "num_heads": 4, "num_kv_heads": 2, "head_dim": 16,
                   "d_ff": 96, "d_expert": 32, "num_experts": 16,
                   "experts_per_token": 4, "experts_held": [0, 4],
                   "num_dense_layers": 1,
                   "layer_types": ["conv", "full_attention", "conv"]},
        "seq_len": 128}

#: the sample ``Model.build`` traces the module on
BUILD_LEN = 128


def build_model(config: dict, seed: int):
    import jax
    import jax.numpy as jnp

    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.lfm2 import Lfm2MoeLM

    model = Model.build(
        Lfm2MoeLM.from_config(config["module"]),
        jnp.zeros((1, min(BUILD_LEN, config["seq_len"])), jnp.int32),
        seed=seed)
    # The Model a trainer is handed lives in host memory, as one loaded from
    # a checkpoint does: the trainer copies its parameters into its own state
    # (center, replica, Adam's moments: 16 B a parameter on the chip), and the
    # round program keeps a second copy of that state for its NaN guard.
    # With a third, idle copy on the chip the published cut's 469 M
    # parameters do not fit 16 GB (PERF.md, PR 32).
    return model.with_params(jax.device_get(model.params))


def held_layers(module: dict) -> list:
    """``[(operator, routed)]`` of the held layers, in order."""
    return [(module["layer_types"][l], l >= module["num_dense_layers"])
            for l in range(module["num_layers"])]


def matmul_params_per_token(module: dict) -> float:
    """Parameters that multiply a token's activations here: a layer's
    operator (the convolution's ``W_in`` and ``W_out``, or attention's four
    projections), its dense feed-forward's three matrices or its router and
    the expected share of its experts (``experts_per_token * held /
    num_experts`` of them, three matrices each), and the tied head. The
    embedding is looked up; norms, taps and gates are elementwise."""
    d, hd = module["d_model"], module["head_dim"]
    operator = {
        "conv": 4 * d * d,
        "full_attention": d * hd * (2 * module["num_heads"]
                                    + 2 * module["num_kv_heads"])}
    routed = d * module["num_experts"] + (
        module["experts_per_token"] * module["experts_held"][1]
        / module["num_experts"]) * 3 * d * module["d_expert"]
    dense = 3 * d * module["d_ff"]
    return (sum(operator[op] + (routed if r else dense)
                for op, r in held_layers(module))
            + d * module["vocab_size"])


def attention_keys_seen(config: dict) -> list:
    """The mean number of keys a query sees, for each held layer that has
    attention (full and causal: ``(L + 1) / 2``)."""
    return [mean_keys_seen(config["seq_len"], None)
            for op, _ in held_layers(config["module"])
            if op == "full_attention"]


def train_flops_per_unit(config: dict) -> float:
    """Forward and backward operations per token: 6 per matmul parameter (2
    forward, 4 backward), and for each attention layer its two products over
    the keys a query sees, ``12 * k * heads * head_dim`` with the backward
    pass (``k`` = 4096.5 at 8,192). The recomputed forward of ``remat`` is
    not counted."""
    m = config["module"]
    scores = 12.0 * m["num_heads"] * m["head_dim"] \
        * sum(attention_keys_seen(config))
    return 6.0 * matmul_params_per_token(m) + scores


def reference_check(model, config: dict, seed: int, compute_dtype,
                    forward=reference.forward) -> dict:
    """One sequence at the timed length, in the trainer's compute dtype,
    against the plain reference on the same parameters. Two limits
    (``references/lfm2.py`` gives the reason for each): the relative L2 of
    the logits with the reference taking the model's choice of experts in
    every routed layer, and the share of (routed layer, token) pairs at which
    the reference's own biased top-k is that choice."""
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

    # The model's parameters live on the host (build_model): one copy on the
    # chip for both sides, gone when this returns. The state (the expert
    # biases, drawn from the seed) is an argument, not a constant of the
    # program: the compile cache then serves every seed.
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
