"""The routed feed-forward the five expert models share
(``models/blocks.py::RoutedFFN``), on the CPU at tiny sizes: its variables lie
where the references read them, in every preset; each router kind routes as
its function does, the bias chooses and never weighs and moves against the
load without a gradient, and a share's router gets no gradient; the shared
expert adds in directly or weighed by its gate."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from distkeras_tpu import models  # noqa: E402
from distkeras_tpu.models.base import ROUND_COUNTERS, ROUTER_BIAS  # noqa: E402
from distkeras_tpu.models.blocks import (  # noqa: E402
    DroplessExperts, GatedMLP, PlainMLP, RoutedFFN, route_sigmoid_bias_top_k,
    route_top_k)

#: preset -> (its routed blocks, the parameters beside ``router`` and
#: ``moe`` a routed block holds, its selection bias, its gate's counters)
PRESETS = {
    "small_smallthinker_lm": (("block_0", "block_1"), set(), False, False),
    "small_lfm2_lm": (("block_1", "block_2"), set(), True, False),
    "small_kimi_linear_lm": (("block_1", "block_2"), {"shared"}, True, False),
    "small_qwen3_next_lm": (("block_0", "block_1", "block_2"),
                            {"shared", "shared_gate"}, False, True),
    "small_nemotron_h_lm": (("block_0", "block_2", "block_4"), {"shared"},
                            True, False),
}


@pytest.mark.parametrize("preset", sorted(PRESETS))
def test_variables_lie_where_the_references_read_them(preset):
    blocks, beside, biased, gated = PRESETS[preset]
    built = {}

    def build():  # traced only: shapes are what this test reads
        built["model"] = model = getattr(models, preset)(seq_len=16)
        return model.params, model.state

    every, state = jax.eval_shape(build)
    _, sown = jax.eval_shape(functools.partial(
        built["model"].module.apply, mutable=["intermediates"]),
        {"params": every, **state}, jnp.zeros((1, 16), jnp.int32))
    biases = state.get(ROUTER_BIAS, {})
    assert set(biases) == (set(blocks) if biased else set())
    for block in blocks:
        params, counters = every[block], state[ROUND_COUNTERS][block]
        assert {"router", "moe"} <= set(params) and "routed" not in params
        assert set(params) & {"shared", "shared_gate"} == beside
        assert params["router"]["kernel"].shape == (32, 8)
        assert set(params["moe"]) == {"experts"}
        assert sown["intermediates"][block]["experts"][0].shape == (1, 16, 2)
        if biased:
            assert biases[block]["expert_bias"].shape == (8,)
        assert set(counters["moe"]) == {
            "assignments_held", "tokens_without_held_expert", "tokens",
            "rows_moved", "steps"}
        assert ("assignments_moved_by_bias" in counters) == biased
        assert ("shared_gate_sum" in counters) == gated
        assert ("shared_gate_count" in counters) == gated


class _Block(nn.Module):
    """A block's use of the routed feed-forward."""

    routed: object

    @nn.compact
    def __call__(self, g):
        return self.routed(name="routed")(g)


E, K, D, T = 8, 2, 16, 32


def _routed(kind, num_experts=E, **kwargs):
    """A block that holds the first ``E`` of ``num_experts`` experts."""
    if kind == "softmax":
        route = functools.partial(route_top_k, k=K)
    else:
        route = functools.partial(route_sigmoid_bias_top_k, k=K, scale=2.5)
        kwargs = {"expert_bias_std": 0.5, "expert_bias_update": 0.25,
                  **kwargs}
    return _Block(functools.partial(RoutedFFN, num_experts, (0, E), 8, "silu",
                                    route, **kwargs))


def _g(seed=0):
    return jax.random.normal(jax.random.key(seed), (1, T, D), jnp.float32)


@pytest.mark.parametrize("kind", ["softmax", "bias"])
def test_each_router_routes_as_its_function_and_a_share_leaves_it_as_given(
        kind):
    block, g = _routed(kind), _g()
    variables = block.init(jax.random.key(1), g)
    params = variables["params"]
    logits = g.reshape(T, D) @ params["router"]["kernel"]
    bias = variables.get(ROUTER_BIAS, {}).get("expert_bias")
    if kind == "softmax":
        weights, experts = route_top_k(logits, K)
    else:
        weights, experts, moved = route_sigmoid_bias_top_k(logits, bias, K,
                                                           2.5)
    y, state = block.apply(variables, g, mutable=[
        "intermediates", ROUTER_BIAS, ROUND_COUNTERS])
    np.testing.assert_array_equal(state["intermediates"]["experts"][0],
                                  experts.reshape(1, T, K))
    expect = DroplessExperts(0, E, D, 8, "silu").apply(
        {"params": params["moe"]}, g.reshape(T, D), weights, experts)
    np.testing.assert_allclose(y.reshape(T, D), expect, rtol=1e-5, atol=1e-6)
    if kind == "bias":
        assert float(state[ROUND_COUNTERS]["assignments_moved_by_bias"]) \
            == float(np.sum(moved))
        # A training step moves the bias against this step's load ...
        load = np.bincount(np.asarray(experts).ravel(), minlength=E)
        np.testing.assert_allclose(
            state[ROUTER_BIAS]["expert_bias"],
            bias + 0.25 * np.sign(load.mean() - load), rtol=1e-6)
        # ... and no gradient reaches it.
        assert not np.any(jax.grad(lambda b: jnp.sum(block.apply(
            {**variables, ROUTER_BIAS: {"expert_bias": b}}, g) ** 2))(bias))
        # The bias chooses and never weighs: tilted to send every token to
        # expert 0, it gives expert 0 the weight of its unbiased score.
        tilted = {**variables, ROUTER_BIAS: {"expert_bias": bias.at[0].set(
            100.0)}}
        y, sown = block.apply(tilted, g, mutable=["intermediates"])
        chosen = sown["intermediates"]["experts"][0].reshape(T, K)
        assert (chosen[:, 0] == 0).all()
        probs = jax.nn.sigmoid(logits)
        unbiased = jnp.take_along_axis(probs, chosen, axis=-1)
        weights = unbiased / (jnp.sum(unbiased, -1, keepdims=True) + 1e-6) \
            * 2.5
        expect = DroplessExperts(0, E, D, 8, "silu").apply(
            {"params": params["moe"]}, g.reshape(T, D), weights, chosen)
        np.testing.assert_allclose(y.reshape(T, D), expect, rtol=1e-5,
                                   atol=1e-6)

    # A module that holds every expert trains its router; one that holds
    # the same experts as a share of twice as many does not.
    def router_grad(num_experts):
        share = _routed(kind, num_experts)
        held = share.init(jax.random.key(1), g)
        return jax.grad(lambda p: jnp.sum(share.apply(
            {**held, "params": p}, g) ** 2))(held["params"])["router"]

    assert np.any(router_grad(E)["kernel"])
    assert not np.any(router_grad(2 * E)["kernel"])


def test_the_shared_expert_adds_in_directly_or_weighed_by_its_gate():
    g = _g(3)
    for shared, gated in ((functools.partial(PlainMLP, 24, "relu2"), False),
                          (functools.partial(GatedMLP, 24, "silu"), True)):
        block = _routed("softmax", shared=shared, shared_gate=gated)
        variables = block.init(jax.random.key(2), g)
        params = variables["params"]
        y, state = block.apply(variables, g, mutable=[ROUND_COUNTERS])
        alone = _routed("softmax").apply(
            {"params": {"router": params["router"], "moe": params["moe"]}}, g)
        expert = shared().apply({"params": params["shared"]}, g)
        if gated:
            gate = jax.nn.sigmoid(g @ params["shared_gate"]["kernel"])
            expert = gate * expert
            assert float(state[ROUND_COUNTERS]["shared_gate_sum"]) \
                == pytest.approx(float(jnp.sum(gate)), rel=1e-5)
            assert float(state[ROUND_COUNTERS]["shared_gate_count"]) == T
        else:
            assert "shared_gate" not in params
            assert "shared_gate_sum" not in state.get(ROUND_COUNTERS, {})
        np.testing.assert_allclose(y, alone + expert, rtol=1e-5, atol=1e-6)
