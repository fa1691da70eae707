"""SmallThinker decoders through ``models/smallthinker.py::SmallThinkerLM``:
every layer a dropless mixture of ReGLU experts routed from the block's
input, grouped-query attention that is full and unrotated in one layer of
four and windowed with RoPE in the other three.

What a later PR needs to know (``benchmarks/README.md`` is not edited for it):

* A configuration of this family is **one chip's share** of a deployment:
  ``module.experts_held`` ``[first, count]`` of every layer's experts,
  ``module.vocab_size`` rows of the vocabulary, ``module.num_layers`` layers
  of the pattern. The router keeps its published width (``num_experts``) and
  ``experts_per_token``; the reference is given the same share. The data draws
  its ids from the held rows (``make_dataframe``), so the loss is over them.
* The model is built on a short sample (``BUILD_LEN``): parameters do not
  depend on the length, and ``Model.build`` runs the module's dense attention.
* ``train_flops_per_unit`` counts what the forward and backward passes need
  for the share: the expert products a token is *expected* to have here,
  ``experts_per_token * held / num_experts`` experts' worth. What a round
  really routed here is the program's to count
  (``readers/trace_moe.py`` reads it for the experts' roofline).
* New readers for this family's layers: ``readers/trace_moe.py``.
"""

from __future__ import annotations

import numpy as np

from benchmarks.families import rel_l2
from benchmarks.families.transformer_lm import learnable_tokens
from benchmarks.references import smallthinker as reference

#: What ``--rehearse`` swaps in for the configuration's sizes: control flow on
#: a CPU in seconds (the flash kernel interprets there), with a window
#: shorter than the sequence and fewer experts held than routed. Never
#: measured.
TINY = {"module": {"vocab_size": 256, "num_layers": 2, "d_model": 64,
                   "num_heads": 4, "num_kv_heads": 2, "head_dim": 16,
                   "d_expert": 32, "num_experts": 16, "experts_per_token": 4,
                   "experts_held": [0, 4], "rope_layout": [0, 1],
                   "window_layout": [0, 1], "window": 64},
        "seq_len": 128}

#: the sample ``Model.build`` traces the module on
BUILD_LEN = 128


def build_model(config: dict, seed: int):
    import jax.numpy as jnp

    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.smallthinker import SmallThinkerLM

    return Model.build(
        SmallThinkerLM.from_config(config["module"]),
        jnp.zeros((1, min(BUILD_LEN, config["seq_len"])), jnp.int32),
        seed=seed)


def make_dataframe(config: dict, rows: int, seed: int, ids: str = "zipf"):
    """The seeded learnable stream of ``transformer_lm``, its ids drawn from
    the held rows of the vocabulary, by the law the workload's ``feed.ids``
    names."""
    import distkeras_tpu as dk

    x, y = learnable_tokens(rows, config["seq_len"],
                            config["module"]["vocab_size"], seed, ids=ids)
    return dk.DataFrame({"features": x, "label": y})


def sample_shapes(config: dict):
    """``(x shape, x dtype, y shape, y dtype)`` of one sample."""
    L = config["seq_len"]
    return (L,), np.int32, (L,), np.int32


def units_per_sample(config: dict) -> int:
    return config["seq_len"]  # tokens


def mean_keys_seen(seq_len: int, window) -> float:
    """Keys a query sees, averaged over the positions of one sequence:
    ``(L + 1) / 2`` under the causal mask, fewer under a window shorter than
    the sequence."""
    L = seq_len
    if window is None or window >= L:
        return (L + 1) / 2
    return (window * (window + 1) / 2 + (L - window) * window) / L


def matmul_params_per_token(module: dict) -> float:
    """Parameters that multiply a token's activations here: a layer's four
    attention projections, its router, the expected share of its experts
    (``experts_per_token * held / num_experts`` of them, three matrices
    each), and the head. The embedding is looked up, the norms elementwise."""
    d, hd = module["d_model"], module["head_dim"]
    attention = d * hd * (2 * module["num_heads"] + 2 * module["num_kv_heads"])
    router = d * module["num_experts"]
    experts = (module["experts_per_token"] * module["experts_held"][1]
               / module["num_experts"]) * 3 * d * module["d_expert"]
    return (module["num_layers"] * (attention + router + experts)
            + d * module["vocab_size"])


def train_flops_per_unit(config: dict) -> float:
    """Forward and backward operations per token: 6 per matmul parameter (2
    forward, 4 backward), and attention's two products over the keys a query
    sees, ``2 * 2 * k * heads * head_dim`` forward and three times that with
    the backward pass: ``12 * k * 3584`` a token and layer at the published
    heads, ``k`` = 4096.5 for a full layer of 8,192 and 3072.25 under the
    4,096 window. The recomputed forward of ``remat`` is not counted."""
    m = config["module"]
    width = m["num_heads"] * m["head_dim"]
    scores = sum(
        12.0 * mean_keys_seen(config["seq_len"],
                              m["window"] if m["window_layout"][l] else None)
        * width for l in range(m["num_layers"]))
    return 6.0 * matmul_params_per_token(m) + scores


def expects_mosaic(config: dict) -> bool:
    return config["module"].get("attn_impl") == "flash"


def reference_check(model, config: dict, seed: int, compute_dtype,
                    forward=reference.forward) -> dict:
    """One sequence at the timed length, in the trainer's compute dtype,
    against the plain reference on the same parameters. Two limits
    (``references/smallthinker.py`` gives the reason for each): the relative
    L2 of the logits with the reference taking the model's choice of experts
    in every layer, and the share of (layer, token) pairs at which the
    reference's own top-k is that choice."""
    import jax
    import jax.numpy as jnp

    m = config["module"]
    x, _ = learnable_tokens(1, config["seq_len"], m["vocab_size"], seed + 1)

    def cast(a):
        if compute_dtype and jnp.issubdtype(a.dtype, jnp.floating):
            return a.astype(compute_dtype)
        return a

    def model_side(params, x):
        logits, sown = model.module.apply(
            {"params": jax.tree.map(cast, params), **(model.state or {})},
            x, mutable=["intermediates"])
        chosen = [sown["intermediates"][f"block_{l}"]["experts"][0]
                  for l in range(m["num_layers"])]
        return logits.astype(jnp.float32), chosen

    got, chosen = jax.jit(model_side)(model.params, x)
    ref, own = jax.jit(lambda p, x, chosen: forward(
        p, x, **m, chosen=chosen, with_routing=True))(model.params, x, chosen)
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
