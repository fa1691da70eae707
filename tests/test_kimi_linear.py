"""Kimi Linear's decoder on the CPU at a small size, float32, seeded weights:
both operators' causality (the chunked delta rule against the recurrence
and the flash kernels with values of another width than keys are
``tests/test_kimi_linear_ops.py``'s); latent attention against the reference's, one ``k_rot`` for all heads; the
router at 256 / 8 / 2.446 by hand; the shared expert beside the routed ones;
the shares of experts and of heads add up to the uncut layer; the module
against the plain reference on logits, loss and every gradient; the reference
notices each dropped term; the trainer path publishes ``moe.round`` and
``kda.round``; and the new fields' defaults leave GPT-2, SmallThinker and
LFM2 as they were."""

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
from benchmarks.references import kimi_linear as reference  # noqa: E402
from distkeras_tpu import telemetry  # noqa: E402
from distkeras_tpu.models import (KimiLinearLM, small_kimi_linear_lm,  # noqa: E402
                                  small_lfm2_lm, small_smallthinker_lm,
                                  small_transformer_lm)
from distkeras_tpu.models.base import ROUND_COUNTERS  # noqa: E402
from distkeras_tpu.models.blocks import (DroplessExperts,  # noqa: E402
                                         GatedMLP, KimiDeltaAttention,
                                         LatentAttention,
                                         route_sigmoid_bias_top_k)
from distkeras_tpu.models.kimi_linear import PUBLISHED_LAYER_TYPES  # noqa: E402
from distkeras_tpu.ops.losses import get_loss  # noqa: E402

L = 64
#: float32 on both sides: the chunked form against the recurrence and the
#: order of sums (measured 1e-7 to 3e-6).
TOL = 1e-4


def rel_l2(a, b):
    a, b = np.asarray(a, np.float64).ravel(), np.asarray(b, np.float64).ravel()
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))


def tokens(seed=0, batch=2, vocab=128, length=L):
    x = np.random.default_rng(seed).integers(0, vocab, (batch, length + 1))
    return x[:, :-1].astype(np.int32), x[:, 1:].astype(np.int32)


def ref_params(model, params=None):
    params = model.params if params is None else params
    return reference.with_bias(params, model.state) \
        if "router_bias" in (model.state or {}) else params


def module_loss(model, params, x, y):
    return get_loss("sparse_categorical_crossentropy")(
        model.apply(params, x).astype(jnp.float32), y)


# -- the operators --------------------------------------------------------------

@pytest.mark.parametrize("operator", ["kda", "mla"])
def test_operators_are_causal(operator):
    """A change at position t moves no output before t."""
    module = KimiDeltaAttention(2, 16) if operator == "kda" \
        else LatentAttention(2, 16, 8, 16, 24)
    h = jax.random.normal(jax.random.key(0), (1, 128, 32))
    variables = module.init(jax.random.key(1), h)
    out = module.apply(variables, h)
    t = 70
    moved = module.apply(variables, h.at[:, t].add(1.0))
    assert np.array_equal(np.asarray(out[:, :t]), np.asarray(moved[:, :t]))
    assert rel_l2(moved[:, t:], out[:, t:]) > 1e-3


# -- latent attention, the router, the shared expert --------------------------

def test_latent_attention_matches_reference_with_one_k_rot_for_all_heads():
    module = LatentAttention(4, 16, 8, 16, 24, rms_eps=1e-5)
    h = jax.random.normal(jax.random.key(0), (2, L, 32))
    params = module.init(jax.random.key(1), h)["params"]
    assert jax.tree.map(lambda a: a.shape, params) == {
        "query": {"kernel": (32, 4, 24)}, "kv_a": {"kernel": (32, 32)},
        "kv_norm": {"scale": (24,)}, "kv_b": {"kernel": (24, 4, 32)},
        "out": {"kernel": (4, 16, 32)}}
    want = reference.latent_attention(
        h, params, kv_rank=24, qk_nope_dim=16, qk_rope_dim=8, rms_eps=1e-5)
    assert rel_l2(module.apply({"params": params}, h), want) < TOL
    flash = LatentAttention(4, 16, 8, 16, 24, attn_impl="flash")
    assert rel_l2(flash.apply({"params": params}, h), want) < 1e-2
    # k_rot is W_kva's last 8 columns, the same for every head: without them
    # the result is another, and they are no head's own parameters
    dropped = reference.latent_attention(
        h, params, kv_rank=24, qk_nope_dim=16, qk_rope_dim=8, rms_eps=1e-5,
        without=("k_rot",))
    assert rel_l2(dropped, want) > 0.05
    zeroed = jax.tree.map(lambda a: a, params)
    zeroed["kv_a"] = {"kernel": params["kv_a"]["kernel"].at[:, 24:].set(0)}
    assert rel_l2(module.apply({"params": zeroed}, h), dropped) < TOL


def test_router_by_hand_at_256_outputs_8_a_token_and_2446():
    """Token 0: logits so that experts 0-7 score highest, a bias that lifts
    expert 200 over expert 7. The bias chooses and never weighs; the weights
    are the unbiased scores over their sum, times 2.446."""
    logits = np.full((2, 256), -4.0, np.float32)
    logits[0, :8] = np.linspace(2.0, 0.6, 8)   # 7 is the weakest chosen
    logits[0, 200] = 0.5                       # just below it
    logits[1, 100:108] = 1.0
    bias = np.zeros(256, np.float32)
    bias[200] = 0.1
    w, e, moved = route_sigmoid_bias_top_k(jnp.asarray(logits),
                                           jnp.asarray(bias), 8, 2.446,
                                           eps=1e-20)
    sig = 1 / (1 + np.exp(-logits.astype(np.float64)))
    assert sorted(np.asarray(e[0]).tolist()) == [0, 1, 2, 3, 4, 5, 6, 200]
    chosen = sig[0, np.asarray(e[0])]
    np.testing.assert_allclose(np.asarray(w[0]), chosen / chosen.sum() * 2.446,
                               rtol=1e-5)
    assert float(w[0].sum()) == pytest.approx(2.446, rel=1e-5)
    assert np.asarray(moved[0]).sum() == 1
    assert np.asarray(e[0])[np.asarray(moved[0])].tolist() == [200]
    assert sorted(np.asarray(e[1]).tolist()) == list(range(100, 108))
    np.testing.assert_allclose(np.asarray(w[1]), 2.446 / 8, rtol=1e-5)
    assert not np.asarray(moved[1]).any()
    # the 1e-20 is this family's: scores of 1e-13 still weigh 2.446 in all,
    # where LFM2's 1e-6 (the default, as its call has it) swallows them
    faint = jnp.full((1, 256), -30.0)
    w20, _, _ = route_sigmoid_bias_top_k(faint, jnp.asarray(bias), 8, 2.446,
                                         eps=1e-20)
    w6, _, _ = route_sigmoid_bias_top_k(faint, jnp.asarray(bias), 8, 2.446)
    assert float(w20.sum()) == pytest.approx(2.446, rel=1e-5)
    assert float(w6.sum()) < 1e-5


def test_the_32_shares_of_experts_add_up_with_the_shared_expert_once():
    """Four chips (here; 32 in the deployment) holding two of eight routed
    experts each, every one with the whole shared expert: the routed parts
    summed and the shared expert counted once are the uncut layer, and the
    reference's."""
    T, k, E, d, f = 96, 4, 8, 16, 8
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
    weights, experts, _ = route_sigmoid_bias_top_k(
        jnp.asarray(rng.normal(size=(T, E)), jnp.float32),
        jnp.asarray(rng.normal(size=E) * 0.3, jnp.float32), k, scale=2.446,
        eps=1e-20)
    whole = DroplessExperts(0, E, d, f, "silu")
    variables = whole.init(jax.random.key(0), x, weights, experts)
    shared = GatedMLP(f, "silu")
    shared_vars = shared.init(jax.random.key(1), x)
    uncut = whole.apply(variables, x, weights, experts) \
        + shared.apply(shared_vars, x)
    parts = [DroplessExperts(first, 2, d, f, "silu").apply(
        {"params": jax.tree.map(lambda a: a[first:first + 2],
                                variables["params"])}, x, weights, experts)
        for first in range(0, E, 2)]
    assert rel_l2(sum(parts) + shared.apply(shared_vars, x), uncut) < 1e-5
    assert rel_l2(parts[0] + shared.apply(shared_vars, x), uncut) > 0.1
    s = shared_vars["params"]
    plain = reference.experts(x[None], weights[None], experts[None],
                              variables["params"]["experts"], 0, E)[0] \
        + reference._swiglu(x[None], s["gate"]["kernel"], s["up"]["kernel"],
                            s["down"]["kernel"], None)[0]
    assert rel_l2(uncut, plain) < 1e-5


@pytest.mark.parametrize("operator", ["kda", "mla"])
def test_the_four_shares_of_heads_add_up_to_the_whole_operator(operator):
    """Two chips (here; four in the deployment) holding two of four heads
    each: the head columns of the projections and ``W_o``'s rows are a
    share's, ``W_fa``, ``W_ga``, ``W_kva`` and the norms are whole on each;
    their outputs summed are the four-head operator's."""
    H, Dh = 4, 16
    whole = KimiDeltaAttention(H, Dh) if operator == "kda" \
        else LatentAttention(H, 16, 8, 16, 24)
    h = jax.random.normal(jax.random.key(0), (2, L, 32))
    params = whole.init(jax.random.key(1), h)["params"]
    uncut = whole.apply({"params": params}, h)

    def share(first, count):
        heads = slice(first, first + count)
        cols = slice(first * Dh, (first + count) * Dh)
        if operator == "mla":
            return {**params,
                    "query": {"kernel": params["query"]["kernel"][:, heads]},
                    "kv_b": {"kernel": params["kv_b"]["kernel"][:, heads]},
                    "out": {"kernel": params["out"]["kernel"][heads]}}
        p = dict(params)
        for name in ("q_proj", "k_proj", "v_proj", "f_b", "g_b"):
            p[name] = {"kernel": params[name]["kernel"][:, cols]}
        for name in ("q_taps", "k_taps", "v_taps", "dt_bias"):
            p[name] = params[name][cols]
        p["A_log"] = params["A_log"][heads]
        p["b_proj"] = {"kernel": params["b_proj"]["kernel"][:, heads]}
        p["o_proj"] = {"kernel": params["o_proj"]["kernel"][cols]}
        return p

    half = KimiDeltaAttention(2, Dh) if operator == "kda" \
        else LatentAttention(2, 16, 8, 16, 24)
    parts = [half.apply({"params": share(first, 2)}, h) for first in (0, 2)]
    assert rel_l2(sum(parts), uncut) < 1e-5
    assert rel_l2(parts[0], uncut) > 0.1  # a share alone is not the operator


# -- the model ----------------------------------------------------------------

#: the preset (dense kda, routed mla, routed kda; two of four heads); one
#: layer of a kind alone; every expert and head held; no shared expert
KINDS = {"preset": {},
         "kda-routed": dict(num_layers=1, num_dense_layers=0,
                            layer_types=("kda",)),
         "mla-routed": dict(num_layers=1, num_dense_layers=0,
                            layer_types=("mla",)),
         "mla-dense": dict(num_layers=1, num_dense_layers=1,
                           layer_types=("mla",)),
         "uncut": dict(experts_held=(0, 8), heads_held=(0, 4)),
         "two-shared": dict(num_shared_experts=2),
         "no-shared": dict(num_shared_experts=0)}


@pytest.mark.parametrize("kind", list(KINDS))
def test_module_matches_reference_on_logits_loss_and_gradients(kind):
    model = small_kimi_linear_lm(seq_len=L, seed=5, **KINDS[kind])
    kwargs = model.module.get_config()
    x, y = tokens(1)
    assert rel_l2(model.predict(x), jax.jit(lambda p: reference.forward(
        p, x, **kwargs))(ref_params(model))) < TOL
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: module_loss(model, p, x, y)))(model.params)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(ref_params(model, p), x, y, **kwargs)))(
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
        assert rel_l2(g, ref_flat[path]) < 1e-3, name


def test_module_in_bfloat16_through_flash_meets_the_chip_checks_form():
    """bfloat16 parameters and flash attention against the float32 reference
    with the model's choice of experts: the comparison the chip makes at the
    published widths, here at the preset (whose narrow heads and few channels
    read higher than the published widths do; PERF.md has those readings)."""
    model = small_kimi_linear_lm(seq_len=128, seed=5, attn_impl="flash",
                                 remat=True)
    kwargs = model.module.get_config()
    x, _ = tokens(1, batch=1, length=128)
    cast = jax.tree.map(lambda a: a.astype(jnp.bfloat16), model.params)
    logits, sown = model.module.apply(
        {"params": cast, **model.state}, x, mutable=["intermediates"])
    chosen = [sown["intermediates"][f"block_{l}"]["experts"][0]
              for l in (1, 2)]
    ref, own = reference.forward(ref_params(model), x, **kwargs,
                                 chosen=chosen, with_routing=True)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    assert rel_l2(logits.astype(jnp.float32), ref) < 0.1
    alike = np.mean([np.all(np.sort(np.asarray(a), -1)
                            == np.sort(np.asarray(b), -1), -1)
                     for a, b in zip(chosen, own)])
    assert alike > 0.8


@pytest.mark.parametrize("fault", reference.FAULTS + ("float8", "bfloat16"))
def test_reference_notices(fault):
    """Each dropped term, and operands in a lower precision, break the
    float32 agreement by far more than the tolerance."""
    model = small_kimi_linear_lm(seq_len=L, seed=5)
    kwargs = model.module.get_config()
    x, _ = tokens(1)
    extra = {"float8": dict(round_to=jnp.float8_e4m3fn),
             "bfloat16": dict(round_to=jnp.bfloat16)}.get(
                 fault, dict(without=(fault,)))
    got = reference.forward(ref_params(model), x, **kwargs, **extra)
    assert rel_l2(model.predict(x), got) > 100 * TOL
    with pytest.raises(ValueError, match="not among"):
        reference.forward(ref_params(model), x, **kwargs, without=("rope",))


def test_model_round_trips_through_its_config_and_names_its_layers():
    assert PUBLISHED_LAYER_TYPES.count("mla") == 7
    assert PUBLISHED_LAYER_TYPES[:5] == ("kda", "kda", "kda", "mla", "kda")
    assert [i + 1 for i, t in enumerate(PUBLISHED_LAYER_TYPES)
            if t == "mla"] == [4, 8, 12, 16, 20, 24, 27]
    n_events = len([e for e in telemetry.get().events()
                    if e["kind"] == "model.layer_kinds"])
    model = small_kimi_linear_lm(seq_len=L, seed=5)
    event = [e for e in telemetry.get().events()
             if e["kind"] == "model.layer_kinds"][n_events:][-1]
    assert event["operators"] == ["kda", "mla", "kda"]
    assert event["feed_forward"] == ["dense", "routed", "routed"]
    assert event["heads_held"] == [0, 2] and event["experts_held"] == [0, 2]
    config = model.module.get_config()
    again = KimiLinearLM.from_config(
        {k: list(v) if isinstance(v, tuple) else v for k, v in config.items()})
    assert again == model.module
    x, _ = tokens(1)
    assert np.array_equal(
        np.asarray(again.apply({"params": model.params, **model.state}, x)),
        np.asarray(model.predict(x)))
    assert set(model.params["block_0"]) == {"ln_op", "kda", "ln_ffn", "mlp"}
    assert set(model.params["block_1"]) == {"ln_op", "mla", "ln_ffn",
                                            "router", "moe", "shared"}
    assert "head" in model.params  # untied
    assert set(model.params["block_2"]["kda"]) == {
        "q_proj", "k_proj", "v_proj", "q_taps", "k_taps", "v_taps", "A_log",
        "dt_bias", "f_a", "f_b", "b_proj", "g_a", "g_b", "o_norm", "o_proj"}
    a = np.exp(np.asarray(model.params["block_2"]["kda"]["A_log"]))
    assert a.shape == (2,) and (a >= 1).all() and (a < 16).all()
    dt = np.log1p(np.exp(np.asarray(model.params["block_2"]["kda"]["dt_bias"])))
    assert (dt >= 1e-3 * 0.999).all() and (dt < 0.1).all()
    for bad, match in ((dict(layer_types=("kda", "conv", "kda")),
                        "layer_types"),
                       (dict(experts_held=(6, 4)), "experts_held"),
                       (dict(heads_held=(3, 2)), "heads_held")):
        with pytest.raises(ValueError, match=match):
            small_kimi_linear_lm(**bad)


def test_trainer_path_publishes_moe_round_and_kda_round():
    """`dk.AEASGD(...).train(df)` for two rounds: the routed layers' load and
    the delta rule's decay and beta leave the round program with the loss."""
    model = small_kimi_linear_lm(seq_len=L, seed=2, expert_bias_std=0.2,
                                 remat=True)
    assert set(model.state_collections) == {ROUND_COUNTERS, "router_bias"}
    rng = np.random.default_rng(4)
    data = rng.integers(0, 128, (8, L + 1)).astype(np.int32)
    df = dk.DataFrame({"features": data[:, :-1], "label": data[:, 1:]})
    tele = telemetry.get()
    seen = {kind: len([e for e in tele.events() if e["kind"] == kind])
            for kind in ("moe.round", "kda.round")}
    trainer = dk.AEASGD(model, worker_optimizer="sgd",
                        loss="sparse_categorical_crossentropy", num_workers=1,
                        batch_size=4, communication_window=1,
                        learning_rate=1e-6, num_epoch=1)
    trained = trainer.train(df)
    moe, kda = ([e for e in tele.events() if e["kind"] == kind][seen[kind]:]
                for kind in ("moe.round", "kda.round"))
    assert [e["round"] for e in moe] == [e["round"] for e in kda] == [0, 1]
    assert all(e["steps"] == 1 and e["layers"] == 2 for e in moe + kda)
    _, counted = model.module.apply(
        {"params": model.params, **model.state}, data[:4, :-1],
        mutable=[ROUND_COUNTERS])
    counted = counted[ROUND_COUNTERS]
    assert set(counted) == {"block_0", "block_1", "block_2"}
    assert set(counted["block_0"]) == {"kda"}          # dense: nothing routed
    assert set(counted["block_1"]) == {"moe", "assignments_moved_by_bias"}
    least = [float(counted[b]["kda"]["min_chunk_decay"])
             for b in ("block_0", "block_2")]
    assert kda[0]["min_chunk_decay_by_layer"] == pytest.approx(least,
                                                               rel=1e-4)
    assert kda[0]["min_chunk_decay"] == pytest.approx(min(least), rel=1e-4)
    assert min(least) < 0
    assert 0.3 < kda[0]["mean_beta"] < 0.7
    assert tele.gauge("kda.min_chunk_decay").value == kda[-1]["min_chunk_decay"]
    assert tele.gauge("kda.mean_beta").value == kda[-1]["mean_beta"]
    assert tele.gauge("kda.chunk").value == 64
    assert tele.gauge("kda.state_bytes").value == 2 * 4 * 2 * 16 * 16 * 4
    # one mla layer keeps its flash... not here: dense attention keeps none
    assert tele.gauge("remat.flash_residual_bytes").value == 0
    want = sum(float(np.sum(c["moe"]["assignments_held"]))
               for c in counted.values() if "moe" in c)
    assert moe[0]["assignments_held"] == want > 0
    assert 0 < moe[0]["bias_moved_share"] < 0.5
    assert np.isfinite(trainer.get_history()).all()
    # a share's router stays as given; the bias moved
    assert np.array_equal(np.asarray(trained.params["block_1"]["router"]["kernel"]),
                          np.asarray(model.params["block_1"]["router"]["kernel"]))
    assert not np.array_equal(
        np.asarray(trained.state["router_bias"]["block_1"]["expert_bias"]),
        np.asarray(model.state["router_bias"]["block_1"]["expert_bias"]))


def test_flash_residuals_are_counted_at_the_value_width():
    model = small_kimi_linear_lm(seq_len=128, seed=2, attn_impl="flash",
                                 remat=True)
    x, _ = tokens(1, batch=2, length=128)
    model.module.apply({"params": model.params, **model.state}, x)
    # one mla layer, 2 x 128 positions, 2 held heads, values of 16 in float32
    assert telemetry.gauge("remat.flash_residual_bytes").value \
        == 1 * 2 * 2 * 128 * (16 * 4 + 4)


def test_defaults_leave_gpt2_smallthinker_and_lfm2_as_they_were():
    x, _ = tokens(1)
    st = small_smallthinker_lm(seq_len=L, seed=5)
    assert st.num_params == 21_152
    from benchmarks.references import smallthinker as st_reference

    assert rel_l2(st.predict(x), st_reference.forward(
        st.params, x, **st.module.get_config())) < TOL
    lfm2 = small_lfm2_lm(seq_len=L, seed=5)
    assert set(lfm2.params["block_0"]["conv"]) == {"in_proj", "taps",
                                                   "out_proj"}
    assert set(lfm2.params["block_1"]) == {"ln_op", "attn", "ln_ffn",
                                           "router", "moe"}  # no shared expert
    assert "heads_held" not in lfm2.module.get_config()
    from benchmarks.references import lfm2 as lfm2_reference

    assert rel_l2(lfm2.predict(x), lfm2_reference.forward(
        lfm2_reference.with_bias(lfm2.params, lfm2.state), x,
        **lfm2.module.get_config())) < TOL
    gpt = small_transformer_lm(vocab_size=128, seq_len=L, seed=5)
    assert set(gpt.params["block_0"]) == {"ln_attn", "attn", "ln_mlp",
                                          "mlp_up", "mlp_down"}
    from benchmarks.references import transformer_lm as gpt_reference

    assert rel_l2(gpt.predict(x), gpt_reference.forward(
        gpt.params, x, **gpt.module.get_config())) < TOL
