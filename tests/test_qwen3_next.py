"""Qwen3-Next's decoder on the CPU at a small size, seeded weights: Gated
DeltaNet through its own in-chunk kernel pair and the scan Kimi Delta
Attention runs (interpreted) against the position-at-a-time recurrence with
one decay a head and key heads read by group, values and every gradient,
under decays held at -1.6 and -20 a step over whole chunks, and with
bfloat16 operands against a float8 control; partial rotation and the output
gate of gated attention, flash against the dense path and the reference; the
shared expert's gate; the shares of experts add up to the uncut layer with
the shared expert once; the module against the plain reference on logits,
loss and every gradient; the reference notices each dropped term; the
trainer path publishes ``moe.round`` and ``gdn.round``."""

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
from benchmarks.references import qwen3_next as reference  # noqa: E402
from distkeras_tpu import telemetry  # noqa: E402
from distkeras_tpu.models import Qwen3NextLM, small_qwen3_next_lm  # noqa: E402
from distkeras_tpu.models.base import ROUND_COUNTERS  # noqa: E402
from distkeras_tpu.models.blocks import (DroplessExperts,  # noqa: E402
                                         GatedMLP, GroupedQueryAttention,
                                         grouped_gated_delta_rule, rope,
                                         route_top_k)
from distkeras_tpu.models.qwen3_next import PUBLISHED_LAYER_TYPES  # noqa: E402
from distkeras_tpu.ops.losses import get_loss  # noqa: E402

L = 64
#: float32 on both sides: the chunked form against the recurrence and the
#: order of sums.
TOL = 1e-4


def rel_l2(a, b):
    a, b = np.asarray(a, np.float64).ravel(), np.asarray(b, np.float64).ravel()
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))


def tokens(seed=0, batch=2, vocab=128, length=L):
    x = np.random.default_rng(seed).integers(0, vocab, (batch, length + 1))
    return x[:, :-1].astype(np.int32), x[:, 1:].astype(np.int32)


# -- Gated DeltaNet's recurrence through the kernels ---------------------------

def gdn_inputs(length, g=None, seed=0, B=1, Hk=2, Hv=4, K=16, V=16):
    """Unit keys and scaled unit queries at the key heads, values at the
    value heads, one decay a value head from ``e^-6`` to ``e^0.5`` a step (or
    held at ``g``), beta in (0, 1)."""
    ks = jax.random.split(jax.random.key(seed), 5)

    def unit(key):
        x = jax.random.normal(key, (B, length, Hk, K))
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    decay = -jnp.exp(jax.random.uniform(ks[3], (B, length, Hv), minval=-6,
                                        maxval=0.5)) if g is None \
        else jnp.full((B, length, Hv), g)
    return (unit(ks[0]) * K ** -0.5, unit(ks[1]),
            jax.random.normal(ks[2], (B, length, Hv, V)), decay,
            jax.nn.sigmoid(jax.random.normal(ks[4], (B, length, Hv))))


def _gradients(fn, args):
    return jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(
        fn(*a).astype(jnp.float32))), argnums=(0, 1, 2, 3, 4)))(*args)


@pytest.mark.parametrize("g", [None, -1.6, -20.0], ids=["drawn", "-1.6",
                                                        "-20"])
def test_gated_deltanet_through_the_kernels_is_the_recurrence(g):
    """Two chunks of 64; ``g`` = -1.6 a step sums to -102 over a chunk, past
    the 88 at which ``e^-G`` leaves float32, and -20 is far past anything
    (the published initialisation draws ``A`` up to 16: -21 a step). The
    per-head pair, reading the key heads by group and one decay a value head,
    is the scalar-decay, grouped recurrence, gradients of the key heads and
    of the scalar decay included."""
    args = gdn_inputs(128, g=g)
    out, least = grouped_gated_delta_rule(*args)
    want = reference.delta_rule(*args)
    assert out.shape == want.shape == (1, 128, 4, 16)
    assert np.isfinite(np.asarray(out)).all()
    assert rel_l2(out, want) < 1e-5
    if g is not None:
        assert float(least) == pytest.approx(64 * g, rel=1e-5)
    got = _gradients(lambda *a: grouped_gated_delta_rule(*a)[0], args)
    ref = _gradients(reference.delta_rule, args)
    for name, a, b in zip("q k v g beta".split(), got, ref):
        assert a.shape == b.shape, name
        assert np.isfinite(np.asarray(a)).all(), name
        if name != "g" or g != -20.0:  # at e^-20 that gradient is rounding
            assert rel_l2(a, b) < 1e-4, name


def test_value_heads_read_their_own_key_heads():
    """Value head ``j`` reads key head ``j // 2``: the other grouping (``j
    mod 2``, a tile where a repeat belongs) is another result."""
    args = gdn_inputs(64, seed=3)
    out, _ = grouped_gated_delta_rule(*args)
    tiled = reference.delta_rule(*args, group=jnp.arange(4) % 2)
    assert rel_l2(out, reference.delta_rule(*args)) < 1e-5
    assert rel_l2(out, tiled) > 0.1


def test_bfloat16_gradients_against_the_float32_recurrence_and_float8():
    """The trainer's dtype: ``q, k, v`` in bfloat16 through the kernels'
    written backward against the float32 recurrence's gradients; with float8
    operands, the control, every gradient reads past the limit. The limit is
    ``tests/test_kimi_linear_ops.py``'s for the per-channel decay (2e-2, a
    factor of three from either side there); measured here on drawn decays."""
    args = gdn_inputs(128, seed=1)
    want = dict(zip("q k v g beta".split(),
                    _gradients(reference.delta_rule, args)))

    def rounded(dtype):
        return lambda q, k, v, g, beta: grouped_gated_delta_rule(
            q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta)[0]

    for dtype, passes in ((jnp.bfloat16, True), (jnp.float8_e4m3fn, False)):
        got = dict(zip(want, _gradients(rounded(dtype), args)))
        for name in want:
            assert np.isfinite(np.asarray(got[name])).all(), (dtype, name)
            assert (rel_l2(got[name], want[name]) < 2e-2) == passes, \
                (dtype, name, rel_l2(got[name], want[name]))


# -- gated attention -------------------------------------------------------------

def test_partial_rotation_turns_the_first_columns_alone():
    x = jax.random.normal(jax.random.key(0), (1, 32, 2, 16))
    turned = rope(x, 1e7, 4)
    assert np.array_equal(np.asarray(turned[..., 4:]), np.asarray(x[..., 4:]))
    assert np.allclose(np.asarray(turned[..., :4]),
                       np.asarray(rope(x[..., :4], 1e7)))
    assert not np.allclose(np.asarray(turned[..., :4]), np.asarray(x[..., :4]))
    # the whole head by default, and a rotary width of the head is the same
    assert np.array_equal(np.asarray(rope(x, 1e7)),
                          np.asarray(rope(x, 1e7, 16)))


@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_gated_attention_with_partial_rotation_against_the_reference(impl):
    """Four query heads over two K/V heads of 16, a quarter rotated, q/k
    norms, the output gate: the module (dense, or the flash kernels
    interpreted, which take bfloat16 operands) against the reference's
    operator."""
    module = GroupedQueryAttention(4, 2, 16, rope_theta=1e7, qk_norm=1e-6,
                                   rotary_dim=4, output_gate=True,
                                   attn_impl=impl)
    h = jax.random.normal(jax.random.key(0), (2, L, 32))
    params = module.init(jax.random.key(1), h)["params"]
    assert params["query"]["kernel"].shape == (32, 4, 32)  # query + gate
    got = module.apply({"params": params}, h)
    want = reference.attention(h, params, num_heads=4, num_kv_heads=2,
                               head_dim=16, rotary_dim=4, rope_theta=1e7,
                               rms_eps=1e-6)
    assert rel_l2(got, want) < (TOL if impl == "dense" else 1e-2)
    for fault in ("attn_gate", "rotation"):
        assert rel_l2(got, reference.attention(
            h, params, num_heads=4, num_kv_heads=2, head_dim=16, rotary_dim=4,
            rope_theta=1e7, rms_eps=1e-6, without=(fault,))) > 5e-2, fault


def test_the_new_fields_default_to_what_the_module_was():
    """Without ``output_gate`` there is no gate column and no gate; without
    ``rotary_dim`` the whole head turns: the same jaxpr as a rotary width of
    the whole head."""
    h = jax.random.normal(jax.random.key(0), (1, 32, 32))
    plain = GroupedQueryAttention(4, 2, 8, rope_theta=1e4)
    params = plain.init(jax.random.key(1), h)["params"]
    assert params["query"]["kernel"].shape == (32, 4, 8)
    whole = GroupedQueryAttention(4, 2, 8, rope_theta=1e4, rotary_dim=8)
    assert str(jax.make_jaxpr(lambda p: plain.apply({"params": p}, h))(params)) \
        == str(jax.make_jaxpr(lambda p: whole.apply({"params": p}, h))(params))


# -- the feed-forward -----------------------------------------------------------

def _gated_shared(x, params):
    s = params["shared"]
    return jax.nn.sigmoid(x @ params["shared_gate"]["kernel"]) \
        * reference._swiglu(x[None], s["gate"]["kernel"], s["up"]["kernel"],
                            s["down"]["kernel"], None)[0]


def test_the_shared_expert_is_weighed_by_its_gate():
    """A layer's output less its routed experts' part is ``sigmoid(g W_sg) *
    Shared(g)``, token by token: the gate takes every value in (0, 1) and
    moves the shared expert's part with it."""
    model = small_qwen3_next_lm(seq_len=L, seed=5, num_layers=1,
                                layer_types=("attention",))
    p = model.params["block_0"]
    x = jax.random.normal(jax.random.key(2), (L, 32))
    shared = GatedMLP(16, "silu").apply({"params": p["shared"]}, x)
    gate = jax.nn.sigmoid(x @ p["shared_gate"]["kernel"])
    assert gate.shape == (L, 1) and float(gate.min()) < 0.4 \
        and float(gate.max()) > 0.6
    assert rel_l2(gate * shared, _gated_shared(x, p)) < 1e-5
    assert rel_l2(shared, _gated_shared(x, p)) > 0.1


def test_the_64_shares_of_experts_add_up_with_the_shared_expert_once():
    """Four chips (here; 64 in the deployment) holding two of eight routed
    experts each, every one with the whole gated shared expert: the routed
    parts summed and the shared expert counted once are the uncut layer, and
    the reference's."""
    T, k, E, d, f = 96, 3, 8, 16, 8
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
    weights, experts = route_top_k(
        jnp.asarray(rng.normal(size=(T, E)), jnp.float32), k)
    whole = DroplessExperts(0, E, d, f, "silu")
    variables = whole.init(jax.random.key(0), x, weights, experts)
    shared_params = {
        "shared": GatedMLP(f, "silu").init(jax.random.key(1), x)["params"],
        "shared_gate": {"kernel": jnp.asarray(rng.normal(size=(d, 1)),
                                              jnp.float32)}}
    gated = _gated_shared(x, shared_params)
    uncut = whole.apply(variables, x, weights, experts) + gated
    parts = [DroplessExperts(first, 2, d, f, "silu").apply(
        {"params": jax.tree.map(lambda a: a[first:first + 2],
                                variables["params"])}, x, weights, experts)
        for first in range(0, E, 2)]
    assert rel_l2(sum(parts) + gated, uncut) < 1e-5
    assert rel_l2(parts[0] + gated, uncut) > 0.1
    plain = reference.experts(x[None], weights[None], experts[None],
                              variables["params"]["experts"], 0, E)[0] + gated
    assert rel_l2(uncut, plain) < 1e-5


# -- the model ----------------------------------------------------------------

#: the preset (gdn, gdn, attention; two of eight experts); one layer of a kind
#: alone; every expert held (the router trains)
KINDS = {"preset": {},
         "gdn": dict(num_layers=1, layer_types=("gdn",)),
         "attention": dict(num_layers=1, layer_types=("attention",)),
         "uncut": dict(experts_held=(0, 8))}


def module_loss(model, params, x, y):
    return get_loss("sparse_categorical_crossentropy")(
        model.apply(params, x).astype(jnp.float32), y)


@pytest.mark.parametrize("kind", list(KINDS))
def test_module_matches_reference_on_logits_loss_and_gradients(kind):
    model = small_qwen3_next_lm(seq_len=L, seed=5, **KINDS[kind])
    kwargs = model.module.get_config()
    x, y = tokens(1)
    assert rel_l2(model.predict(x), jax.jit(lambda p: reference.forward(
        p, x, **kwargs))(model.params)) < TOL
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: module_loss(model, p, x, y)))(model.params)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(p, x, y, **kwargs)))(model.params)
    assert abs(float(loss) - float(ref_loss)) < TOL * float(ref_loss)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    ref_flat = dict(jax.tree_util.tree_leaves_with_path(ref_grads))
    assert len(flat) == len(ref_flat) > 8
    for path, g in flat:
        name = jax.tree_util.keystr(path)
        if "router" in name and kind != "uncut":
            # a share does not train its router, here and in the reference
            assert not np.any(g) and not np.any(ref_flat[path]), name
            continue
        assert np.linalg.norm(ref_flat[path]) > 0, name
        assert rel_l2(g, ref_flat[path]) < 1e-3, name


@pytest.mark.parametrize("fault", reference.FAULTS + ("float8",))
def test_reference_notices(fault):
    """Each dropped term of the mathematics, and the reference in float8,
    moves the logits further than the reference's own rounding to bfloat16
    does, all on the same choice of experts. Half of each attention head
    rotated at theta 1e4, so that the rotation is not lost in the preset's
    short sequence (measured: bfloat16 0.079, the rotation dropped 0.105,
    every other fault 0.22 to 1.3)."""
    model = small_qwen3_next_lm(seq_len=L, seed=5, rotary_dim=8,
                                rope_theta=1e4)
    kwargs = model.module.get_config()
    x, _ = tokens(2)
    exact, chosen = jax.jit(lambda p: reference.forward(
        p, x, **kwargs, with_routing=True))(model.params)

    def reading(**extra):
        return rel_l2(jax.jit(lambda p: reference.forward(
            p, x, **kwargs, **extra, chosen=chosen))(model.params), exact)

    rounding = reading(round_to=jnp.bfloat16)
    wrong = reading(round_to=jnp.float8_e4m3fn) if fault == "float8" \
        else reading(without=(fault,))
    assert rounding < 0.1 and wrong > 1.2 * rounding, (fault, wrong, rounding)


def test_model_round_trips_its_config_and_refuses_what_is_no_share():
    model = small_qwen3_next_lm(seq_len=L, seed=1)
    config = model.module.get_config()
    assert Qwen3NextLM.from_config(config) == model.module
    assert PUBLISHED_LAYER_TYPES[:4] == ("gdn", "gdn", "gdn", "attention")
    assert PUBLISHED_LAYER_TYPES.count("attention") == 12
    for bad, match in ((dict(experts_held=(6, 4)), "no share"),
                       (dict(layer_types=("gdn", "mla", "gdn")), "layer_types"),
                       (dict(linear_value_heads=3), "cannot serve")):
        with pytest.raises(ValueError, match=match):
            small_qwen3_next_lm(**bad)


def test_trainer_path_publishes_moe_round_and_gdn_round():
    """`dk.AEASGD(...).train(df)` for two rounds: the layers' load, the delta
    rule's decay and beta and the shared expert's gate leave the round
    program with the loss."""
    model = small_qwen3_next_lm(seq_len=L, seed=2, remat=True)
    assert set(model.state_collections) == {ROUND_COUNTERS}
    rng = np.random.default_rng(4)
    data = rng.integers(0, 128, (8, L + 1)).astype(np.int32)
    df = dk.DataFrame({"features": data[:, :-1], "label": data[:, 1:]})
    tele = telemetry.get()
    seen = {kind: len([e for e in tele.events() if e["kind"] == kind])
            for kind in ("moe.round", "gdn.round")}
    trainer = dk.AEASGD(model, worker_optimizer="sgd",
                        loss="sparse_categorical_crossentropy", num_workers=1,
                        batch_size=4, communication_window=1,
                        learning_rate=1e-6, num_epoch=1)
    trained = trainer.train(df)
    moe, gdn = ([e for e in tele.events() if e["kind"] == kind][seen[kind]:]
                for kind in ("moe.round", "gdn.round"))
    assert [e["round"] for e in moe] == [e["round"] for e in gdn] == [0, 1]
    assert all(e["steps"] == 1 for e in moe + gdn)
    assert [e["layers"] for e in moe] == [3, 3]
    assert [e["layers"] for e in gdn] == [2, 2]
    _, counted = model.module.apply(
        {"params": model.params, **model.state}, data[:4, :-1],
        mutable=[ROUND_COUNTERS])
    counted = counted[ROUND_COUNTERS]
    assert set(counted["block_0"]) == {"gdn", "moe", "shared_gate_sum",
                                       "shared_gate_count"}
    assert set(counted["block_2"]) == {"moe", "shared_gate_sum",
                                       "shared_gate_count"}
    least = [float(counted[b]["gdn"]["min_chunk_decay"])
             for b in ("block_0", "block_1")]
    assert gdn[0]["min_chunk_decay_by_layer"] == pytest.approx(least,
                                                               rel=1e-4)
    assert min(least) < 0
    assert 0.3 < gdn[0]["mean_beta"] < 0.7
    assert 0.2 < gdn[0]["shared_gate_mean"] < 0.8
    assert tele.gauge("gdn.min_chunk_decay").value == gdn[-1]["min_chunk_decay"]
    assert tele.gauge("gdn.mean_beta").value == gdn[-1]["mean_beta"]
    assert tele.gauge("moe.shared_gate_mean").value \
        == gdn[-1]["shared_gate_mean"]
    assert tele.gauge("gdn.chunk").value == 64
    assert tele.gauge("gdn.state_bytes").value == 2 * 4 * 4 * 16 * 16 * 4
    want = sum(float(np.sum(c["moe"]["assignments_held"]))
               for c in counted.values())
    assert moe[0]["assignments_held"] == want > 0
    assert np.isfinite(trainer.get_history()).all()
    # a share's router stays as given
    assert np.array_equal(
        np.asarray(trained.params["block_0"]["router"]["kernel"]),
        np.asarray(model.params["block_0"]["router"]["kernel"]))
