"""The expert width the grouped products run at
(``models/blocks.py::padded_expert_width``): the rule at the widths the
configurations publish, the plain and gated expert layers at a width the rule
pads against a per-expert ``jax.numpy`` reference that runs the published
width (the output and the gradients of the rows, the routing weights and
every bank, in float32), the banks' shapes and the counter
``moe.padded_expert_layers``, and at the widths the rule leaves alone no pad
in the program and no count."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu import telemetry
from distkeras_tpu.models.blocks import (ACTIVATIONS, DroplessExperts,
                                         padded_expert_width)

TOKENS, K, EXPERTS = 24, 2, 8
COUNTER = "moe.padded_expert_layers"


@pytest.mark.parametrize("width, runs_at", [
    (16, 16), (64, 64), (464, 512), (512, 512), (768, 768), (1024, 1024),
    (1536, 1536), (1856, 2048), (1920, 2048), (2560, 2560), (3712, 4096)],
    ids=["tiny", "preset", "464", "qwen3-next", "smallthinker",
         "kimi-linear", "lfm2", "nemotron", "1920", "2560", "shared-3712"])
def test_the_rule(width, runs_at):
    """A multiple of 512 where that adds at most an eighth, else the width:
    768 would need a third more and is left alone."""
    assert padded_expert_width(width) == runs_at


def routing(seed=0, tokens=TOKENS):
    rng = np.random.default_rng(seed)
    experts = np.stack([rng.permutation(EXPERTS)[:K] for _ in range(tokens)])
    w = rng.random((tokens, K)).astype(np.float32)
    return (jnp.asarray(experts, jnp.int32),
            jnp.asarray(w / w.sum(1, keepdims=True)))


def by_expert(params, x, weights, experts, first, held, gated, activation):
    """Every held expert on every token at the published width, exact
    matmuls, then each token's weighted sum over its held experts."""
    act = ACTIVATIONS[activation]
    banks = params["experts"]

    def mm(a, w):
        return jnp.einsum("td,edf->etf", a, w, precision="highest")

    h = act(mm(x, banks["up"]["kernel"])) if not gated else (
        act(mm(x, banks["gate"]["kernel"])) * mm(x, banks["up"]["kernel"]))
    y = jnp.einsum("etf,efd->etd", h, banks["down"]["kernel"],
                   precision="highest")                       # [held, T, D]
    local = experts - first
    here = (local >= 0) & (local < held)
    picked = y[jnp.where(here, local, 0), jnp.arange(x.shape[0])[:, None]]
    return jnp.sum(jnp.where(here, weights, 0)[..., None] * picked, axis=1)


def output_and_grads(fn, params, x, weights, cotangent):
    out, vjp = jax.vjp(fn, params, x, weights)
    return out, vjp(cotangent)


@pytest.mark.parametrize("gated, activation", [(False, "relu2"),
                                               (True, "silu")],
                         ids=["plain-relu2", "gated-silu"])
@pytest.mark.parametrize("width, d_model", [(464, 64), (1856, 256)])
def test_a_padded_layer_is_the_published_function(gated, activation, width,
                                                  d_model):
    """Six of eight experts held, so some tokens have none: output and the
    gradients of ``x``, ``weights`` and every bank to 1e-6 of the reference
    at the published width; the banks keep ``[held, D, F]``."""
    first, held = 1, 6
    experts, weights = routing(width)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(TOKENS, d_model)),
                    jnp.float32)
    cotangent = jnp.asarray(
        np.random.default_rng(2).normal(size=(TOKENS, d_model)), jnp.float32)
    layer = DroplessExperts(first, held, d_model, width, activation,
                            gated=gated)
    before = telemetry.counter(COUNTER).value
    params = layer.init(jax.random.key(3), x, weights, experts)["params"]
    assert {name: bank["kernel"].shape for name, bank in
            params["experts"].items()} == {
        **({"gate": (held, d_model, width)} if gated else {}),
        "up": (held, d_model, width), "down": (held, width, d_model)}

    got = output_and_grads(
        lambda p, x, w: layer.apply({"params": p}, x, w, experts), params, x,
        weights, cotangent)
    assert telemetry.counter(COUNTER).value > before
    want = output_and_grads(
        lambda p, x, w: by_expert(p, x, w, experts, first, held, gated,
                                  activation), params, x, weights, cotangent)
    assert len(jax.tree.leaves(got)) == (6 if gated else 5)
    for (path, got), ref in zip(jax.tree_util.tree_leaves_with_path(got),
                                jax.tree.leaves(want)):
        assert got.shape == ref.shape, path
        scale = float(jnp.max(jnp.abs(ref)))
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6 * scale,
                                   err_msg=jax.tree_util.keystr(path))


def pads_of_a_bank(jaxpr, banks):
    """``pad`` equations, in ``jaxpr`` and every jaxpr under it, whose
    operand has one of the shapes ``banks`` (a bank's gradient is a pad too,
    by negative widths: of the padded bank's shape)."""
    found = []
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name == "pad"
                and eqn.invars[0].aval.shape in banks):
            found.append(eqn.invars[0].aval.shape)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += pads_of_a_bank(sub, banks)
    return found


@pytest.mark.parametrize("gated", [False, True], ids=["plain", "gated"])
@pytest.mark.parametrize("width", [16, 512, 768, 1024, 1536, 1856])
def test_the_program_pads_a_bank_only_where_the_rule_says(width, gated):
    """The layer's forward and backward as a jaxpr: a pad of each bank where
    the width is padded (and the counter moves), none and no count at the
    widths the configurations publish that are left alone."""
    held, d_model = 4, 128
    experts, weights = routing(width)
    x = jnp.zeros((TOKENS, d_model), jnp.float32)
    layer = DroplessExperts(0, held, d_model, width, "relu2" if not gated
                            else "relu", gated=gated)
    params = jax.eval_shape(lambda: layer.init(
        jax.random.key(0), x, weights, experts)["params"])
    before = telemetry.counter(COUNTER).value
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, x: jnp.sum(layer.apply(
        {"params": p}, x, weights, experts)), (0, 1)))(params, x)
    pads = pads_of_a_bank(jaxpr.jaxpr, {(held, d_model, width),
                                        (held, width, d_model)})
    counted = telemetry.counter(COUNTER).value - before
    if padded_expert_width(width) == width:
        assert pads == [] and counted == 0
    else:
        assert len(pads) == (3 if gated else 2) and counted > 0
