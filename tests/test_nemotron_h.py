"""Nemotron-H's decoder on the CPU at a small size, seeded weights: the chunked
state-space form (the in-chunk half in ``jax.numpy``, the delta rule's scan
pair with no ``W``, interpreted) against the position-at-a-time recurrence,
values and every gradient, with values of 64 beside a state of 128, eight
heads a group, chunks of 16 and 64, and decays strong enough that ``e^-G``
would leave float32; the scan with no ``W`` against the scan with ``W = 0``;
the plain ReLU^2 experts against a dense loop; the shares of heads and of
experts add up to the uncut sublayers; the module against the plain reference
on logits, loss and every gradient; the reference notices each dropped term;
the trainer path publishes ``moe.round`` and ``ssd.round``."""

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
from benchmarks.references import nemotron_h as reference  # noqa: E402
from distkeras_tpu import telemetry  # noqa: E402
from distkeras_tpu.models import NemotronHLM, small_nemotron_h_lm  # noqa: E402
from distkeras_tpu.models.base import ROUND_COUNTERS, ROUTER_BIAS  # noqa: E402
from distkeras_tpu.models.blocks import (DroplessExperts,  # noqa: E402
                                         GroupedQueryAttention, Mamba2Mixer,
                                         PlainMLP, route_sigmoid_bias_top_k)
from distkeras_tpu.models.nemotron_h import (PUBLISHED_LAYER_TYPES,  # noqa: E402
                                             PUBLISHED_PATTERN)
from distkeras_tpu.ops.delta_rule import chunked_ssd  # noqa: E402
from distkeras_tpu.ops.losses import get_loss  # noqa: E402
from distkeras_tpu.ops.pallas.delta_rule import scan_chunks  # noqa: E402

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


# -- the chunked state-space form --------------------------------------------

def ssd_inputs(length, strong=False, seed=0, B=1, H=16, G=2, P=64, N=128):
    """``x`` at the heads, ``dt`` log-uniform in [1e-3, 1e-1] (or in [0.5,
    2] with ``strong``), ``B`` and ``C`` at the groups, ``A = -(1..H)``:
    strong, a step's decay reaches -32 and a chunk's sums past -88."""
    ks = jax.random.split(jax.random.key(seed), 4)
    low, high = (0.5, 2.0) if strong else (1e-3, 1e-1)
    dt = jnp.exp(jax.random.uniform(ks[1], (B, length, H),
                                    minval=np.log(low), maxval=np.log(high)))
    return (jax.random.normal(ks[0], (B, length, H, P)), dt,
            jax.random.normal(ks[2], (B, length, G, N)) * N ** -0.5,
            jax.random.normal(ks[3], (B, length, G, N)) * N ** -0.5,
            -jnp.arange(1, H + 1, dtype=jnp.float32))


def _chunked(chunk):
    def run(x, dt, Bm, Cm, A):
        return chunked_ssd(Cm, Bm, x * dt[..., None], dt * A, chunk)[0]
    return run


def _gradients(fn, args):
    return jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(
        fn(*a).astype(jnp.float32))), argnums=(0, 1, 2, 3)))(*args)


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("strong", [False, True], ids=["drawn", "strong"])
def test_chunked_ssd_is_the_recurrence(chunk, strong):
    """Values of 64 beside a state of 128, 16 heads over 2 groups: outputs
    and the gradients of ``x``, ``dt``, ``B`` and ``C``. Strong decays sum to
    some -2,000 over a chunk of 64: no exponent of the chunked form is
    positive, so nothing overflows."""
    args = ssd_inputs(128, strong)
    out = jax.jit(_chunked(chunk))(*args)
    want = jax.jit(reference.ssd_recurrence)(*args[:2], args[4], *args[2:4])
    assert out.shape == want.shape == (1, 128, 16, 64)
    assert np.isfinite(np.asarray(out)).all()
    assert rel_l2(out, want) < 1e-5
    _, least = chunked_ssd(args[3], args[2], args[0], args[1] * args[4], chunk)
    G = np.cumsum(np.asarray(args[1] * args[4]).reshape(1, -1, chunk, 16), 2)
    assert float(least) == pytest.approx(G[:, :, -1].min(), rel=1e-5)
    got = _gradients(_chunked(chunk), args)
    ref = _gradients(lambda x, dt, Bm, Cm, A: reference.ssd_recurrence(
        x, dt, A, Bm, Cm), args)
    for name, a, b in zip(("x", "dt", "B", "C"), got, ref):
        assert a.shape == b.shape, name
        assert np.isfinite(np.asarray(a)).all(), name
        assert rel_l2(a, b) < 1e-4, name


def test_heads_read_their_own_groups():
    """Head ``j`` reads group ``j // 8``: the other grouping (``j mod 2``, a
    tile where a repeat belongs) is another result."""
    x, dt, Bm, Cm, A = ssd_inputs(64, seed=3)
    out = _chunked(64)(x, dt, Bm, Cm, A)
    tiled = reference.ssd_recurrence(x, dt, A, Bm, Cm,
                                     group=jnp.arange(16) % 2)
    assert rel_l2(out, reference.ssd_recurrence(x, dt, A, Bm, Cm)) < 1e-5
    assert rel_l2(out, tiled) > 0.1


def test_bfloat16_against_the_float32_recurrence_and_float8():
    """The trainer's dtype: ``B``, ``C`` and ``dt x`` in bfloat16 through the
    chunked form against the float32 recurrence, values and gradients; with
    float8 operands, the control, every reading passes the limit. The limit
    is the delta rule's (``tests/test_qwen3_next.py``): 2e-2."""
    args = ssd_inputs(128, seed=1)
    want = (reference.ssd_recurrence(*args[:2], args[4], *args[2:4]),
            *_gradients(lambda x, dt, Bm, Cm, A: reference.ssd_recurrence(
                x, dt, A, Bm, Cm), args))

    def rounded(dtype):
        def run(x, dt, Bm, Cm, A):
            return chunked_ssd(Cm.astype(dtype), Bm.astype(dtype),
                               (x * dt[..., None]).astype(dtype), dt * A)[0]
        return run

    for dtype, passes in ((jnp.bfloat16, True), (jnp.float8_e4m3fn, False)):
        got = (rounded(dtype)(*args), *_gradients(rounded(dtype), args))
        for name, a, b in zip(("out", "x", "dt", "B", "C"), got, want):
            assert np.isfinite(np.asarray(a, np.float32)).all(), (dtype, name)
            assert (rel_l2(a, b) < 2e-2) == passes, (dtype, name, rel_l2(a, b))


@pytest.mark.parametrize("d_v", [64, 128])
def test_the_scan_with_no_w_is_the_scan_with_w_zero(d_v):
    """``scan_chunks(U, None, ...)`` reads no ``W``: the same outputs and the
    same five cotangents as the scan handed ``W = 0``, whose own ``dW`` the
    case without ``W`` does not compute; values of 64 beside keys of 128."""
    B, H, N, C, K = 1, 4, 3, 16, 128
    ks = jax.random.split(jax.random.key(2), 5)
    U = jax.random.normal(ks[0], (B, H, N, C, d_v))
    Qg, Kd = (0.1 * jax.random.normal(k, (B, H, N, C, K)) for k in ks[1:3])
    Bq = jnp.tril(0.2 * jax.random.normal(ks[3], (B, H, N, C, C)))
    s = jax.random.uniform(ks[4], (B, H, N, K), minval=0.1, maxval=0.5)
    zero = jnp.zeros((B, H, N, C, K))

    def run(W):
        return jax.jit(jax.value_and_grad(lambda *a: jnp.sum(jnp.sin(
            scan_chunks(a[0], W, *a[1:]))), argnums=(0, 1, 2, 3, 4)))(
                U, Qg, Bq, Kd, s)

    calls = telemetry.counter("pallas.ssd.scan_calls").value
    (without, grads), (with_zero, zero_grads) = run(None), run(zero)
    assert telemetry.counter("pallas.ssd.scan_calls").value > calls
    assert float(without) == pytest.approx(float(with_zero), rel=1e-6)
    for a, b in zip(grads, zero_grads):
        assert a.shape == b.shape
        assert rel_l2(a, b) < 1e-6


# -- the experts ---------------------------------------------------------------

def _routing(T=96, E=16, k=4, seed=3):
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(rng.normal(size=(T, E)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(E,)) * 0.1, jnp.float32)
    weights, experts, _ = route_sigmoid_bias_top_k(logits, bias, k, 2.5,
                                                   eps=1e-20)
    return weights, experts


def test_plain_experts_are_a_dense_loop():
    """Two grouped products and ReLU^2 between them, no gate: every token's
    weighted sum over its held experts of ``relu(x W_up)^2 W_down``."""
    T, d, f, E = 96, 16, 24, 16
    x = jax.random.normal(jax.random.key(0), (T, d))
    weights, experts = _routing(T, E)
    layer = DroplessExperts(0, E, d, f, "relu2", gated=False)
    variables = layer.init(jax.random.key(1), x, weights, experts)
    assert set(variables["params"]["experts"]) == {"up", "down"}
    got = layer.apply(variables, x, weights, experts)
    want = reference.experts(x[None], weights[None], experts[None],
                             variables["params"]["experts"], 0, E)[0]
    assert rel_l2(got, want) < 1e-5
    # ReLU not squared is another function; the gated bank has a third matrix
    relu = DroplessExperts(0, E, d, f, "relu", gated=False)
    assert rel_l2(relu.apply(variables, x, weights, experts), want) > 0.1
    gated = DroplessExperts(0, E, d, f, "relu2").init(
        jax.random.key(1), x, weights, experts)
    assert set(gated["params"]["experts"]) == {"gate", "up", "down"}


def test_the_16_shares_of_experts_add_up_with_the_shared_expert_once():
    """Sixteen chips (the deployment's number) holding two of 32 routed
    experts each, every one with the whole ReLU^2 shared expert: the routed
    parts summed and the shared expert counted once are the uncut layer, and
    the reference's."""
    T, d, f, E, k = 96, 16, 8, 32, 6
    x = jax.random.normal(jax.random.key(4), (T, d))
    weights, experts = _routing(T, E, k)
    whole = DroplessExperts(0, E, d, f, "relu2", gated=False)
    variables = whole.init(jax.random.key(0), x, weights, experts)
    shared = PlainMLP(2 * f, "relu2")
    shared_params = shared.init(jax.random.key(1), x)
    once = shared.apply(shared_params, x)
    uncut = whole.apply(variables, x, weights, experts) + once
    parts = [DroplessExperts(first, 2, d, f, "relu2", gated=False).apply(
        {"params": jax.tree.map(lambda a: a[first:first + 2],
                                variables["params"])}, x, weights, experts)
        for first in range(0, E, 2)]
    assert len(parts) == 16
    assert rel_l2(sum(parts) + once, uncut) < 1e-5
    assert rel_l2(parts[0] + once, uncut) > 0.1
    s = shared_params["params"]
    plain = reference.experts(x[None], weights[None], experts[None],
                              variables["params"]["experts"], 0, E)[0] \
        + reference.relu2_mlp(x[None], s["up"]["kernel"],
                              s["down"]["kernel"])[0]
    assert rel_l2(uncut, plain) < 1e-5


# -- the shares of heads -------------------------------------------------------

def _mixer_share(p, first, count, H, G, P, N):
    """The parameters of heads ``first .. first + count - 1`` of an uncut
    mixer's, with their groups: ``W_in``'s columns of ``z``, ``x``, ``B``,
    ``C``, ``dt``, the taps and bias of ``x | B | C``, the heads' scalars,
    the norm's columns and ``W_out``'s rows."""
    inner, per = H * P, H // G
    g0, g1 = first // per, (first + count) // per
    heads = np.arange(first * P, (first + count) * P)
    groups = np.arange(g0 * N, g1 * N)
    cols = np.concatenate([heads, inner + heads, 2 * inner + groups,
                           2 * inner + G * N + groups,
                           2 * inner + 2 * G * N + np.arange(first,
                                                             first + count)])
    conv = np.concatenate([heads, inner + groups, inner + G * N + groups])
    sl = slice(first, first + count)
    return {"in_proj": {"kernel": p["in_proj"]["kernel"][:, cols]},
            "conv_taps": p["conv_taps"][conv],
            "conv_bias": p["conv_bias"][conv],
            "A_log": p["A_log"][sl], "dt_bias": p["dt_bias"][sl],
            "D": p["D"][sl], "norm": p["norm"][heads],
            "out_proj": {"kernel": p["out_proj"]["kernel"][heads]}}


def test_the_two_head_shares_of_a_mixer_add_up_to_the_uncut_mixer():
    """Two chips holding four of eight Mamba heads each, with their two of
    four groups of ``B``, ``C`` and the gated norm: their outputs (each its
    part of ``W_out``'s sum) add up to the uncut mixer's, and the reference's.
    A share's own initialisation gives its heads' ``A``."""
    H, G, P, N, d = 8, 4, 8, 16, 32
    h = jax.random.normal(jax.random.key(0), (2, L, d))
    whole = Mamba2Mixer(H, G, 0, P, N)
    p = whole.init(jax.random.key(1), h)["params"]
    # perturb the scalars so that no head's is another's
    p = {**p, "D": p["D"] + 0.1 * jnp.arange(H), "norm": p["norm"]
         + 0.01 * jnp.arange(H * P)}
    uncut = whole.apply({"params": p}, h)
    parts = [Mamba2Mixer(4, 2, first, P, N).apply(
        {"params": _mixer_share(p, first, 4, H, G, P, N)}, h)
        for first in (0, 4)]
    assert rel_l2(sum(parts), uncut) < 1e-5
    assert rel_l2(parts[0], uncut) > 0.1
    assert rel_l2(uncut, reference.mamba(
        h, p, heads=H, groups=G, head_dim=P, state_dim=N,
        rms_eps=1e-5)) < 1e-5
    second = Mamba2Mixer(4, 2, 4, P, N).init(jax.random.key(1), h)["params"]
    assert np.allclose(np.exp(np.asarray(second["A_log"])), [5, 6, 7, 8])


def test_the_two_head_shares_of_attention_add_up_to_the_uncut_layer():
    """Two chips holding two of four query heads each with the one of two
    K/V heads they read, no positions: their outputs add up to the uncut
    layer's, and the reference's."""
    h = jax.random.normal(jax.random.key(0), (2, L, 32))
    whole = GroupedQueryAttention(4, 2, 16)
    p = whole.init(jax.random.key(1), h)["params"]
    uncut = whole.apply({"params": p}, h)
    parts = []
    for share in (0, 1):
        q = slice(2 * share, 2 * share + 2)
        kv = slice(share, share + 1)
        parts.append(GroupedQueryAttention(2, 1, 16).apply({"params": {
            "query": {"kernel": p["query"]["kernel"][:, q]},
            "key": {"kernel": p["key"]["kernel"][:, kv]},
            "value": {"kernel": p["value"]["kernel"][:, kv]},
            "out": {"kernel": p["out"]["kernel"][q]}}}, h))
    assert rel_l2(sum(parts), uncut) < 1e-5
    assert rel_l2(parts[0], uncut) > 0.1
    assert rel_l2(uncut, reference.attention(h, p, head_dim=16)) < 1e-5


# -- the model ----------------------------------------------------------------

#: the preset (one period E M E M E M *, shares of everything); one layer of a
#: kind alone; everything held (the router trains)
KINDS = {"preset": {},
         "mamba": dict(num_layers=1, layer_types=("mamba",)),
         "moe": dict(num_layers=1, layer_types=("moe",)),
         "attention": dict(num_layers=1, layer_types=("attention",)),
         "uncut": dict(num_layers=3, layer_types=("moe", "mamba", "attention"),
                       experts_held=(0, 8), mamba_heads_held=(0, 8),
                       heads_held=(0, 4))}


def module_loss(model, params, x, y):
    return get_loss("sparse_categorical_crossentropy")(
        model.apply(params, x).astype(jnp.float32), y)


@pytest.mark.parametrize("kind", list(KINDS))
def test_module_matches_reference_on_logits_loss_and_gradients(kind):
    model = small_nemotron_h_lm(seq_len=L, seed=5, **KINDS[kind])
    kwargs = model.module.get_config()
    x, y = tokens(1)
    params = reference.with_bias(model.params, model.state) \
        if "moe" in model.module.layer_types[:model.module.num_layers] \
        else model.params
    assert rel_l2(model.predict(x), jax.jit(lambda p: reference.forward(
        p, x, **kwargs))(params)) < TOL
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: module_loss(model, p, x, y)))(model.params)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(p, x, y, **kwargs)))(params)
    assert abs(float(loss) - float(ref_loss)) < TOL * float(ref_loss)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    ref_flat = dict(jax.tree_util.tree_leaves_with_path(ref_grads))
    assert len(flat) > 4
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
    does, all on the same choice of experts."""
    model = small_nemotron_h_lm(seq_len=L, seed=5)
    kwargs = model.module.get_config()
    params = reference.with_bias(model.params, model.state)
    x, _ = tokens(2)
    exact, chosen = jax.jit(lambda p: reference.forward(
        p, x, **kwargs, with_routing=True))(params)

    def reading(**extra):
        return rel_l2(jax.jit(lambda p: reference.forward(
            p, x, **kwargs, **extra, chosen=chosen))(params), exact)

    rounding = reading(round_to=jnp.bfloat16)
    wrong = reading(round_to=jnp.float8_e4m3fn) if fault == "float8" \
        else reading(without=(fault,))
    assert rounding < 0.1 and wrong > 1.2 * rounding, (fault, wrong, rounding)


def test_the_published_pattern_and_the_held_period():
    assert len(PUBLISHED_PATTERN) == 52
    assert [PUBLISHED_LAYER_TYPES.count(k) for k in
            ("mamba", "moe", "attention")] == [23, 23, 6]
    # published layers 6-12 (counted from 0) are one EMEMEM* period, which
    # repeats verbatim four times after the leading MEMEM*
    assert PUBLISHED_PATTERN[6:13] == "EMEMEM*"
    assert PUBLISHED_PATTERN[6:34] == "EMEMEM*" * 4
    assert PUBLISHED_LAYER_TYPES[6:13] == ("moe", "mamba") * 3 + ("attention",)


def test_model_round_trips_its_config_and_refuses_what_is_no_share():
    model = small_nemotron_h_lm(seq_len=L, seed=1)
    config = model.module.get_config()
    assert NemotronHLM.from_config(config) == model.module
    assert set(model.state_collections) == {ROUND_COUNTERS, ROUTER_BIAS}
    for bad, match in ((dict(experts_held=(6, 4)), "no share"),
                       (dict(mamba_heads_held=(0, 3)), "whole groups of 2"),
                       (dict(heads_held=(1, 2)), "whole groups of 2"),
                       (dict(layer_types=("moe", "gdn") * 4), "layer_types")):
        with pytest.raises(ValueError, match=match):
            small_nemotron_h_lm(**bad)


def test_trainer_path_publishes_moe_round_and_ssd_round():
    """`dk.AEASGD(...).train(df)` for two rounds: the expert layers' load
    and the state-space layers' decay and ``dt`` leave the round program
    with the loss; the bias moves, the share's router does not."""
    model = small_nemotron_h_lm(seq_len=L, seed=2, remat=True)
    rng = np.random.default_rng(4)
    data = rng.integers(0, 128, (8, L + 1)).astype(np.int32)
    df = dk.DataFrame({"features": data[:, :-1], "label": data[:, 1:]})
    tele = telemetry.get()
    seen = {kind: len([e for e in tele.events() if e["kind"] == kind])
            for kind in ("moe.round", "ssd.round")}
    calls = tele.counter("pallas.ssd.scan_calls").value
    trainer = dk.AEASGD(model, worker_optimizer="sgd",
                        loss="sparse_categorical_crossentropy", num_workers=1,
                        batch_size=4, communication_window=1,
                        learning_rate=1e-6, num_epoch=1)
    trained = trainer.train(df)
    assert tele.counter("pallas.ssd.scan_calls").value > calls
    moe, ssd = ([e for e in tele.events() if e["kind"] == kind][seen[kind]:]
                for kind in ("moe.round", "ssd.round"))
    assert [e["round"] for e in moe] == [e["round"] for e in ssd] == [0, 1]
    assert all(e["steps"] == 1 for e in moe + ssd)
    assert [e["layers"] for e in moe] == [e["layers"] for e in ssd] == [3, 3]
    assert all("bias_moved_share" in e for e in moe)
    _, counted = model.module.apply(
        {"params": model.params, **model.state}, data[:4, :-1],
        mutable=[ROUND_COUNTERS])
    counted = counted[ROUND_COUNTERS]
    assert set(counted["block_0"]) == {"moe", "assignments_moved_by_bias"}
    assert set(counted["block_1"]) == {"mixer"}
    assert "block_6" not in counted  # attention counts nothing
    least = [float(counted[b]["mixer"]["min_chunk_decay"])
             for b in ("block_1", "block_3", "block_5")]
    assert ssd[0]["min_chunk_decay_by_layer"] == pytest.approx(least,
                                                               rel=1e-4)
    assert min(least) < 0
    assert 1e-3 < ssd[0]["mean_dt"] < 0.1
    assert tele.gauge("ssd.min_chunk_decay").value \
        == ssd[-1]["min_chunk_decay"]
    assert tele.gauge("ssd.mean_dt").value == ssd[-1]["mean_dt"]
    assert tele.gauge("ssd.chunk").value == 64
    assert tele.gauge("ssd.state_bytes").value == 3 * 4 * 4 * 16 * 8 * 4
    want = sum(float(np.sum(c["moe"]["assignments_held"]))
               for c in counted.values() if "moe" in c)
    assert moe[0]["assignments_held"] == want > 0
    assert np.isfinite(trainer.get_history()).all()
    # a share's router stays as given; the bias moved
    assert np.array_equal(
        np.asarray(trained.params["block_0"]["router"]["kernel"]),
        np.asarray(model.params["block_0"]["router"]["kernel"]))
    assert not np.array_equal(
        np.asarray(trained.state[ROUTER_BIAS]["block_0"]["expert_bias"]),
        np.asarray(model.state[ROUTER_BIAS]["block_0"]["expert_bias"]))
