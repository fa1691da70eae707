"""LFM2's mixture-of-experts decoder on the CPU at a small size, float32,
seeded weights: the module against the plain reference on logits, loss and
every gradient; each operator alone against the reference's; the biased
sigmoid router against a hand-computed case; the convolution's gradients and
its causality; the reference notices each planted fault; the shares add up to
the uncut layer; the dense layer and the tied head; the counters leave the
round program with the loss; and the new fields' defaults leave SmallThinker
and GPT-2 as they were."""

import copy
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import distkeras_tpu as dk  # noqa: E402
from benchmarks.references import lfm2 as reference  # noqa: E402
from distkeras_tpu import telemetry  # noqa: E402
from distkeras_tpu.models import (Lfm2MoeLM, small_lfm2_lm,  # noqa: E402
                                  small_smallthinker_lm,
                                  small_transformer_lm)
from distkeras_tpu.models.base import ROUND_COUNTERS  # noqa: E402
from distkeras_tpu.models.blocks import (DroplessExperts,  # noqa: E402
                                         GatedShortConv,
                                         GroupedQueryAttention,
                                         route_sigmoid_bias_top_k,
                                         route_top_k)
from distkeras_tpu.models.lfm2 import PUBLISHED_LAYER_TYPES  # noqa: E402
from distkeras_tpu.ops.losses import get_loss  # noqa: E402

L = 64
#: float32 on both sides, the module's dense attention: only the order of
#: the sums differs (measured 2e-7 to 8e-7).
TOL = 1e-4


def rel_l2(a, b):
    a, b = np.asarray(a, np.float64).ravel(), np.asarray(b, np.float64).ravel()
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))


def tokens(seed=0, batch=2, vocab=128):
    x = np.random.default_rng(seed).integers(0, vocab, (batch, L + 1))
    return x[:, :-1].astype(np.int32), x[:, 1:].astype(np.int32)


def ref_params(model, params=None):
    """The tree the reference reads: the model's parameters with the expert
    biases it keeps as state."""
    params = model.params if params is None else params
    return reference.with_bias(params, model.state) \
        if "router_bias" in (model.state or {}) else params


def module_loss(model, params, x, y):
    return get_loss("sparse_categorical_crossentropy")(
        model.apply(params, x).astype(jnp.float32), y)


#: the preset (dense conv, routed attention, routed conv); one layer of a
#: kind alone; every expert held (where the router is trained); a bias large
#: enough to make most of the choices
KINDS = {"preset": {},
         "conv-routed": dict(num_layers=1, num_dense_layers=0,
                             layer_types=("conv",)),
         "attention-routed": dict(num_layers=1, num_dense_layers=0,
                                  layer_types=("full_attention",)),
         "attention-dense": dict(num_layers=1, num_dense_layers=1,
                                 layer_types=("full_attention",)),
         "uncut": dict(experts_held=(0, 8)),
         "large-bias": dict(expert_bias_std=0.5),
         "scaled": dict(routed_scaling_factor=2.5)}


@pytest.mark.parametrize("kind", list(KINDS))
def test_module_matches_reference_on_logits_loss_and_gradients(kind):
    model = small_lfm2_lm(seq_len=L, seed=5, **KINDS[kind])
    kwargs = model.module.get_config()
    x, y = tokens(1)
    assert rel_l2(model.predict(x), reference.forward(
        ref_params(model), x, **kwargs)) < TOL
    loss, grads = jax.value_and_grad(
        lambda p: module_loss(model, p, x, y))(model.params)
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: reference.loss(ref_params(model, p), x, y, **kwargs))(
            model.params)
    assert abs(float(loss) - float(ref_loss)) < TOL * float(ref_loss)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    ref_flat = dict(jax.tree_util.tree_leaves_with_path(ref_grads))
    assert len(flat) == len(ref_flat) > 8
    for path, g in flat:
        name = jax.tree_util.keystr(path)
        assert "expert_bias" not in name  # state, not a parameter
        if "router" in name and kind != "uncut":
            # a share does not train its router, here and in the reference
            assert not np.any(g) and not np.any(ref_flat[path]), name
            continue
        assert np.linalg.norm(ref_flat[path]) > 0, name
        assert rel_l2(g, ref_flat[path]) < TOL, name


def _no_bias(g, p, k, scale, round_to=None, chosen=None, trained=True):
    return ORIGINAL_ROUTE(g, {**p, "expert_bias": 0 * p["expert_bias"]}, k,
                          scale, round_to, chosen, trained)


def _biased_weights(g, p, k, scale, round_to=None, chosen=None, trained=True):
    scores = jax.nn.sigmoid(g @ p["router"]["kernel"]) + p["expert_bias"]
    w, e = jax.lax.top_k(scores, k)
    return w / (w.sum(-1, keepdims=True) + 1e-6) * scale, e, e


def _softmax_scores(g, p, k, scale, round_to=None, chosen=None, trained=True):
    probs = jax.nn.softmax(g @ p["router"]["kernel"], -1)
    _, e = jax.lax.top_k(probs + p["expert_bias"], k)
    w = jnp.take_along_axis(probs, e, -1)
    return w / (w.sum(-1, keepdims=True) + 1e-6) * scale, e, e


def _no_qk_norm(x, p, eps):
    return x if x.ndim == 4 else ORIGINAL_NORM(x, p, eps)


ORIGINAL_ROUTE = reference.route
ORIGINAL_NORM = reference._rms_norm

FAULTS = {
    "a skipped layer": dict(kwargs=dict(num_layers=2)),
    "the convolution's two gates swapped": dict(edit=(
        "in_proj", lambda w: jnp.concatenate(
            [w[:, 32:64], w[:, :32], w[:, 64:]], 1))),
    "ReLU in SiLU's place": dict(silu=jax.nn.relu),
    "the first tap dropped": dict(edit=(
        "taps", lambda t: t.at[:, 0].set(0))),
    "the taps in the wrong order": dict(edit=("taps", lambda t: t[:, ::-1])),
    "the expert bias dropped": dict(route=_no_bias, bias=0.5),
    "the bias in the weights": dict(route=_biased_weights, bias=0.5),
    "softmax in sigmoid's place": dict(route=_softmax_scores),
    "the scaling factor dropped": dict(
        model=dict(routed_scaling_factor=2.5),
        kwargs=dict(routed_scaling_factor=1.0)),
    "q/k norm dropped": dict(norm=_no_qk_norm),
    "another RoPE base": dict(kwargs=dict(rope_theta=1e4)),
    "bfloat16 in place of float32": dict(kwargs=dict(round_to=jnp.bfloat16)),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_reference_notices(fault, monkeypatch):
    """Each planted fault, in the reference's place, breaks the float32
    agreement by far more than the tolerance."""
    spec = FAULTS[fault]
    model = small_lfm2_lm(seq_len=L, seed=5,
                          expert_bias_std=spec.get("bias", 0.02),
                          **spec.get("model", {}))
    if "norm" in spec:
        # norms that do something: the preset's unit weights on a head of
        # unit-variance entries would leave a dropped norm nearly unseen
        model = model.with_params(jax.tree_util.tree_map_with_path(
            lambda path, a: a * 3.0 if "_norm" in jax.tree_util.keystr(path)
            else a, model.params))
    kwargs = {**model.module.get_config(), **spec.get("kwargs", {})}
    if "route" in spec:
        monkeypatch.setattr(reference, "route", spec["route"])
    if "norm" in spec:
        monkeypatch.setattr(reference, "_rms_norm", spec["norm"])
    if "silu" in spec:  # the reference looks it up as it runs
        monkeypatch.setattr(jax.nn, "silu", spec["silu"])
    params = ref_params(model)
    if "edit" in spec:
        where, edit = spec["edit"]
        params = jax.tree_util.tree_map_with_path(
            lambda path, a: edit(a)
            if where in jax.tree_util.keystr(path) else a, params)
    x, _ = tokens(1)
    err = rel_l2(model.predict(x), reference.forward(params, x, **kwargs))
    assert err > 10 * TOL, (fault, err)


def test_router_by_hand_the_bias_chooses_and_never_weighs():
    """Two tokens over four experts, k = 2. Token 0: scores sigmoid([2, 1, 0,
    -1]); a bias of +0.5 on expert 2 lifts it over expert 1 (0.5 + 0.5 >
    0.731) and over expert 0 (1.0 > 0.8808), so the choice is (2, 0) in the
    order of the biased scores, and the weights are the *unbiased* scores 0.5
    and 0.8808, renormalised. Token 1: the bias changes nothing."""
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0], [-3.0, 0.0, 3.0, 1.0]])
    bias = jnp.asarray([0.0, 0.0, 0.5, 0.0])
    w, e, moved = route_sigmoid_bias_top_k(logits, bias, 2, scale=1.0)
    s = 1 / (1 + np.exp(-np.asarray(logits)))
    np.testing.assert_array_equal(np.asarray(e), [[2, 0], [2, 3]])
    np.testing.assert_allclose(
        np.asarray(w[0]), np.array([0.5, s[0, 0]]) / (s[0, 0] + 0.5 + 1e-6),
        rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(w[1]), np.array([s[1, 2], s[1, 3]])
        / (s[1, 2] + s[1, 3] + 1e-6), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(moved),
                                  [[True, False], [False, False]])
    # without the bias: the unbiased top-k, nothing moved
    w0, e0, moved0 = route_sigmoid_bias_top_k(logits, 0 * bias, 2)
    np.testing.assert_array_equal(np.asarray(e0), [[0, 1], [2, 3]])
    assert not np.any(moved0)
    # the scale multiplies the renormalised weights
    w3, _, _ = route_sigmoid_bias_top_k(logits, bias, 2, scale=3.0)
    np.testing.assert_allclose(np.asarray(w3), 3 * np.asarray(w), rtol=1e-6)
    # no gradient reaches the bias, and the softmax router is as it was
    grad = jax.grad(lambda b: jnp.sum(route_sigmoid_bias_top_k(
        logits, b, 2)[0] ** 2))(bias)
    assert not np.any(grad)
    ws, es = route_top_k(logits, 2)
    np.testing.assert_array_equal(np.asarray(es), [[0, 1], [2, 3]])
    np.testing.assert_allclose(np.asarray(ws.sum(-1)), 1.0, rtol=1e-6)


def _conv(d=16, seed=0, batch=2):
    layer = GatedShortConv()
    h = jnp.asarray(np.random.default_rng(seed).normal(size=(batch, L, d)),
                    jnp.float32)
    return layer, layer.init(jax.random.key(seed), h), h


def test_short_conv_matches_reference_and_its_gradients():
    layer, variables, h = _conv()
    p = variables["params"]
    assert (p["taps"].shape, p["in_proj"]["kernel"].shape,
            p["out_proj"]["kernel"].shape) == ((16, 3), (16, 48), (16, 16))
    assert rel_l2(layer.apply(variables, h),
                  reference.short_conv(h, p)) < 1e-6
    target = jnp.asarray(np.random.default_rng(9).normal(size=h.shape),
                         jnp.float32)
    got = jax.grad(lambda p, h: jnp.sum(
        layer.apply({"params": p}, h) * target), argnums=(0, 1))(p, h)
    want = jax.grad(lambda p, h: jnp.sum(
        reference.short_conv(h, p) * target), argnums=(0, 1))(p, h)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        assert np.linalg.norm(w) > 0 and rel_l2(g, w) < 1e-5, \
            jax.tree_util.keystr(path)


def test_short_conv_by_hand():
    """One channel, ``W_in = [1, 2, -1]``, ``W_out = [1]``, taps ``[0.25,
    -0.5, 2]``, ``h = [1, 2, 3, -1]``: ``Bg = h``, ``Cg = 2h``, ``u = -h``, so
    ``s = -h^2 = [-1, -4, -9, -1]``; ``c_t = 0.25 s_{t-2} - 0.5 s_{t-1} + 2
    s_t`` with zeros before the start: ``[-2, -7.5, -16.25, 1.5]``; ``y = Cg
    * c = [-4, -30, -97.5, -3]``. The last tap is the current position's."""
    params = {"in_proj": {"kernel": jnp.asarray([[1.0, 2.0, -1.0]])},
              "taps": jnp.asarray([[0.25, -0.5, 2.0]]),
              "out_proj": {"kernel": jnp.ones((1, 1))}}
    h = jnp.asarray([1.0, 2.0, 3.0, -1.0]).reshape(1, 4, 1)
    want = np.array([-4.0, -30.0, -97.5, -3.0]).reshape(1, 4, 1)
    np.testing.assert_allclose(
        np.asarray(GatedShortConv().apply({"params": params}, h)), want,
        rtol=1e-6)
    np.testing.assert_allclose(np.asarray(reference.short_conv(h, params)),
                               want, rtol=1e-6)


def test_short_conv_is_causal():
    """A change at position t moves no output before t, and moves t, t + 1
    and t + 2 (three taps) and nothing after them."""
    layer, variables, h = _conv(batch=1)
    t = 20
    moved = np.asarray(layer.apply(variables, h.at[0, t].add(1.0))
                       - layer.apply(variables, h))[0]
    changed = np.abs(moved).max(-1) > 0
    assert not changed[:t].any()
    assert changed[t:t + 3].all()
    assert not changed[t + 3:].any()
    # the reference alike
    ref = np.asarray(reference.short_conv(h.at[0, t].add(1.0),
                                          variables["params"])
                     - reference.short_conv(h, variables["params"]))[0]
    assert not (np.abs(ref).max(-1) > 0)[:t].any()


def test_attention_operator_alone_matches_reference():
    """32-wide stream, 4 query and 2 K/V heads of 8, q/k norm with weights
    that are not ones, RoPE: the module's dense and flash paths against the
    reference's operator."""
    attn = GroupedQueryAttention(4, 2, 8, rope_theta=1e6, qk_norm=1e-5)
    h = jnp.asarray(np.random.default_rng(3).normal(size=(2, L, 32)),
                    jnp.float32)
    variables = attn.init(jax.random.key(1), h)
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a * np.linspace(0.5, 2.0, a.size, dtype=np.float32)
        if "_norm" in jax.tree_util.keystr(path) else a, variables["params"])
    assert params["query_norm"]["scale"].shape == (8,)
    want = reference.attention(h, params, num_heads=4, num_kv_heads=2,
                               head_dim=8, rope_theta=1e6, rms_eps=1e-5)
    assert rel_l2(attn.apply({"params": params}, h), want) < 1e-5
    flash = GroupedQueryAttention(4, 2, 8, rope_theta=1e6, qk_norm=1e-5,
                                  attn_impl="flash")
    # the kernel's products take bfloat16 operands whatever its input is
    assert rel_l2(flash.apply({"params": params}, h), want) < 1e-2
    # the default leaves the module as SmallThinker has it: no such parameters
    plain = GroupedQueryAttention(4, 2, 8, rope_theta=1e6)
    assert set(plain.init(jax.random.key(1), h)["params"]) \
        == {"query", "key", "value", "out"}


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Eight chips holding one of eight SwiGLU experts each, routed by the
    biased sigmoid router: their parts of the result, summed, are what one
    layer holding all eight gives, and what the reference's loop gives."""
    T, k, E, d, f = 96, 4, 8, 16, 8
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
    weights, experts, _ = route_sigmoid_bias_top_k(
        jnp.asarray(rng.normal(size=(T, E)), jnp.float32),
        jnp.asarray(rng.normal(size=E) * 0.3, jnp.float32), k, scale=1.5)
    whole = DroplessExperts(0, E, d, f, "silu")
    variables = whole.init(jax.random.key(0), x, weights, experts)
    uncut = whole.apply(variables, x, weights, experts)
    parts = []
    for first in range(E):
        params = jax.tree.map(lambda a: a[first:first + 1],
                              variables["params"])
        parts.append(DroplessExperts(first, 1, d, f, "silu").apply(
            {"params": params}, x, weights, experts))
    assert rel_l2(sum(parts), uncut) < 1e-5
    assert rel_l2(parts[0], uncut) > 0.1  # a share alone is not the layer
    plain = reference.experts(x[None], weights[None], experts[None],
                              variables["params"]["experts"], 0, E)[0]
    assert rel_l2(uncut, plain) < 1e-5
    # the gate is the field's: ReGLU with the same weights is another layer
    relu = DroplessExperts(0, E, d, f).apply(variables, x, weights, experts)
    assert rel_l2(relu, uncut) > 0.05


def test_dense_layer_and_tied_head():
    """The leading layer has the dense feed-forward and no router; the head
    is the embedding: there is no ``lm_head``, the logits are the normalised
    stream times the table, and the first loss is near ln V."""
    model = small_lfm2_lm(seq_len=L, seed=1)
    assert set(model.params["block_0"]) == {"ln_op", "conv", "ln_ffn", "mlp"}
    assert set(model.params["block_1"]) == {"ln_op", "attn", "ln_ffn",
                                            "router", "moe"}
    assert set(model.params["block_2"]) == {"ln_op", "conv", "ln_ffn",
                                            "router", "moe"}
    assert "lm_head" not in model.params
    # the expert bias is state beside the parameters, for the routed layers
    assert set(model.state["router_bias"]) == {"block_1", "block_2"}
    assert model.state["router_bias"]["block_1"]["expert_bias"].shape == (8,)
    x, y = tokens(2)
    base = model.predict(x)
    table = model.params["tok_embed"]["embedding"]
    doubled = model.with_params({**model.params, "tok_embed": {
        "embedding": table.at[7].multiply(2.0)}})
    moved = np.asarray(doubled.predict(x) - base)
    assert np.abs(moved[..., 7]).min() > 0       # row 7 is logit 7's weights
    ref = float(reference.loss(ref_params(model), x, y,
                               **model.module.get_config()))
    assert abs(float(module_loss(model, model.params, x, y)) - ref) \
        < 1e-5 * ref
    assert abs(ref - np.log(128)) < 0.5


def test_model_round_trips_through_its_config_and_names_its_layers():
    tele = telemetry.get()
    n = len([e for e in tele.events() if e["kind"] == "model.layer_kinds"])
    module = small_lfm2_lm(seq_len=L).module
    again = Lfm2MoeLM.from_config(
        copy.deepcopy({k: list(v) if isinstance(v, tuple) else v
                       for k, v in module.get_config().items()}))
    assert again == module
    event = [e for e in tele.events() if e["kind"] == "model.layer_kinds"][n]
    assert event["operators"] == ["conv", "full_attention", "conv"]
    assert event["feed_forward"] == ["dense", "routed", "routed"]
    assert event["experts_held"] == [0, 2]
    # the defaults are the published model
    full = Lfm2MoeLM()
    assert len(PUBLISHED_LAYER_TYPES) == full.num_layers == 40
    assert PUBLISHED_LAYER_TYPES.count("full_attention") == 10
    assert PUBLISHED_LAYER_TYPES[:6] == ("conv", "conv", "full_attention",
                                         "conv", "conv", "conv")
    with pytest.raises(ValueError, match="layer_types"):
        small_lfm2_lm(layer_types=("conv", "window", "conv"))
    with pytest.raises(ValueError, match="experts_held"):
        small_lfm2_lm(experts_held=(6, 4))


def test_counters_leave_the_round_program_with_the_loss():
    """`dk.AEASGD(...).train(df)` trains the model through the engine for two
    rounds, and what each round routed to the held experts and what the bias
    moved reach telemetry."""
    model = small_lfm2_lm(seq_len=L, seed=2, expert_bias_std=0.2)
    assert set(model.state_collections) == {ROUND_COUNTERS, "router_bias"}
    rng = np.random.default_rng(4)
    data = rng.integers(0, 128, (8, L + 1)).astype(np.int32)
    df = dk.DataFrame({"features": data[:, :-1], "label": data[:, 1:]})
    tele = telemetry.get()
    before = tele.counter("moe.assignments_held").value
    n_events = len([e for e in tele.events() if e["kind"] == "moe.round"])
    trainer = dk.AEASGD(model, worker_optimizer="sgd",
                        loss="sparse_categorical_crossentropy", num_workers=1,
                        batch_size=4, communication_window=1,
                        learning_rate=1e-6, num_epoch=1)
    trainer.train(df)
    events = [e for e in tele.events() if e["kind"] == "moe.round"][n_events:]
    assert [e["round"] for e in events] == [0, 1]
    # the two routed layers; the dense one counts nothing
    assert all(e["steps"] == 1 and e["layers"] == 2 for e in events)
    _, counted = model.module.apply(
        {"params": model.params, **model.state}, data[:4, :-1],
        mutable=[ROUND_COUNTERS])  # the bias not mutable: it stays
    counted = counted[ROUND_COUNTERS]
    assert set(counted) == {"block_1", "block_2"}
    want = sum(float(np.sum(c["moe"]["assignments_held"]))
               for c in counted.values())
    assert events[0]["assignments_held"] == want > 0
    assert tele.counter("moe.assignments_held").value - before == sum(
        e["assignments_held"] for e in events)
    by_bias = sum(float(c["assignments_moved_by_bias"])
                  for c in counted.values())
    assert events[0]["bias_moved_share"] == by_bias / (2 * 4 * L * 2)
    assert 0 < events[0]["bias_moved_share"] < 0.5
    assert tele.gauge("moe.bias_moved_share").value \
        == events[-1]["bias_moved_share"]
    assert len(events[0]["load_max_over_mean_by_layer"]) == 2
    assert tele.gauge("moe.load_max_over_mean").value >= 1.0
    assert np.isfinite(trainer.get_history()).all()


def test_a_training_step_moves_the_bias_against_the_load():
    """One step by hand: the bias of every expert that took more than the
    mean load falls by ``expert_bias_update``, of every one that took less
    rises by it; inference moves nothing; with the update at 0 the bias
    stays as given."""
    model = small_lfm2_lm(seq_len=L, seed=3, expert_bias_update=0.05,
                          num_layers=2)
    x, _ = tokens(6)
    variables = {"params": model.params, **model.state}
    before = np.asarray(model.state["router_bias"]["block_1"]["expert_bias"])
    _, mut = model.module.apply(variables, x,
                                mutable=["router_bias", "intermediates"])
    chosen = np.asarray(mut["intermediates"]["block_1"]["experts"][0])
    load = np.bincount(chosen.ravel(), minlength=8)
    assert load.sum() == 2 * L * 2 and load.max() > load.mean() > load.min()
    want = before + 0.05 * np.sign(load.mean() - load)
    after = np.asarray(mut["router_bias"]["block_1"]["expert_bias"])
    np.testing.assert_allclose(after, want, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(reference.bias_after_step(
        jnp.asarray(before), jnp.asarray(chosen), 0.05)), want, rtol=1e-6)
    # the step itself routed with the bias it was given
    own = reference.forward(ref_params(model), x, with_routing=True,
                            **model.module.get_config())[1][0]
    np.testing.assert_array_equal(np.sort(np.asarray(own), -1),
                                  np.sort(chosen, -1))
    # inference: the collection is not mutable, nothing moves
    model.predict(x)
    np.testing.assert_array_equal(
        np.asarray(model.state["router_bias"]["block_1"]["expert_bias"]),
        before)
    frozen = small_lfm2_lm(seq_len=L, seed=3, expert_bias_update=0.0,
                           num_layers=2)
    _, mut = frozen.module.apply({"params": frozen.params, **frozen.state},
                                 x, mutable=["router_bias"])
    np.testing.assert_array_equal(
        np.asarray(mut["router_bias"]["block_1"]["expert_bias"]), before)


def test_trainer_balances_the_load_and_leaves_a_shares_router_as_given():
    """Through `dk.AEASGD(...).train(df)`: the bias moves (by at most a step
    of `expert_bias_update` a local step, and never by a gradient: it is not
    among the parameters), the worst expert's load falls towards the mean,
    and a share's router stays bit-equal to the built model's."""
    update = 0.02
    model = small_lfm2_lm(seq_len=L, seed=3, expert_bias_update=update,
                          expert_bias_std=0.3)
    rng = np.random.default_rng(5)
    data = rng.integers(0, 128, (16, L + 1)).astype(np.int32)
    df = dk.DataFrame({"features": data[:, :-1], "label": data[:, 1:]})

    def worst_load(m):
        _, mut = m.module.apply({"params": m.params, **m.state},
                                data[:8, :-1], mutable=["intermediates"])
        chosen = np.asarray(mut["intermediates"]["block_1"]["experts"][0])
        load = np.bincount(chosen.ravel(), minlength=8)
        return load.max() / load.mean()

    trained = dk.AEASGD(model, worker_optimizer="adam",
                        loss="sparse_categorical_crossentropy", num_workers=1,
                        batch_size=4, communication_window=2,
                        learning_rate=1e-3, num_epoch=4).train(df)
    steps = 16
    for block in ("block_1", "block_2"):
        np.testing.assert_array_equal(
            np.asarray(trained.params[block]["router"]["kernel"]),
            np.asarray(model.params[block]["router"]["kernel"]))
        moved = np.abs(
            np.asarray(trained.state["router_bias"][block]["expert_bias"])
            - np.asarray(model.state["router_bias"][block]["expert_bias"]))
        assert 0 < moved.max() <= steps * update * (1 + 1e-5)
    assert worst_load(trained) < worst_load(model)
    assert worst_load(model) > 1.5  # a bias of 0.3 had unbalanced it
    assert rel_l2(trained.params["block_2"]["conv"]["taps"],
                  model.params["block_2"]["conv"]["taps"]) > 1e-4


def test_defaults_leave_smallthinker_and_gpt2_as_they_were():
    st = small_smallthinker_lm(seq_len=L, seed=5)
    assert set(st.params["block_0"]) == {"router", "ln_attn", "attn",
                                         "ln_moe", "moe"}
    assert set(st.params["block_0"]["attn"]) == {"query", "key", "value",
                                                 "out"}
    assert st.num_params == 21_152
    assert st.module.get_config().keys() >= {"experts_held", "window"}
    assert "qk_norm" not in st.module.get_config()
    x, _ = tokens(1)
    from benchmarks.references import smallthinker as st_reference

    assert rel_l2(st.predict(x), st_reference.forward(
        st.params, x, **st.module.get_config())) < TOL
    gpt = small_transformer_lm(vocab_size=128, seq_len=L, seed=5)
    assert set(gpt.params["block_0"]) == {"ln_attn", "attn", "ln_mlp",
                                          "mlp_up", "mlp_down"}
    from benchmarks.references import transformer_lm as gpt_reference

    assert rel_l2(gpt.predict(x), gpt_reference.forward(
        gpt.params, x, **gpt.module.get_config())) < TOL
