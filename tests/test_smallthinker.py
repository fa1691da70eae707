"""SmallThinker on the CPU at a small size, float32, seeded weights: the
module against the plain reference on logits, loss and every gradient; the
reference notices each planted fault; the dropless expert layer against a
plain loop under a skewed router; the shares add up to the uncut layer; the
counters leave the round program with the loss."""

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
from benchmarks.references import smallthinker as reference  # noqa: E402
from distkeras_tpu import telemetry  # noqa: E402
from distkeras_tpu.models import SmallThinkerLM, small_smallthinker_lm  # noqa: E402
from distkeras_tpu.models.base import ROUND_COUNTERS  # noqa: E402
from distkeras_tpu.models.blocks import DroplessExperts  # noqa: E402
from distkeras_tpu.ops.losses import get_loss  # noqa: E402
from distkeras_tpu.ops.pallas import rows as row_kernels  # noqa: E402
from distkeras_tpu.parallel.sharding import MOE_RULES, param_path_specs  # noqa: E402
from distkeras_tpu.runtime.mesh import EXPERT_AXIS  # noqa: E402

L = 64
#: float32 on both sides, the module's dense attention: only the order of
#: the sums differs (measured 3e-7); the issue's acceptance states 1e-4.
TOL = 1e-4


def rel_l2(a, b):
    a, b = np.asarray(a, np.float64).ravel(), np.asarray(b, np.float64).ravel()
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))


def tokens(seed=0, batch=2, vocab=128):
    x = np.random.default_rng(seed).integers(0, vocab, (batch, L + 1))
    return x[:, :-1].astype(np.int32), x[:, 1:].astype(np.int32)


def module_loss(model, params, x, y):
    return get_loss("sparse_categorical_crossentropy")(
        model.apply(params, x).astype(jnp.float32), y)


#: one case a layer kind, the two together, and the two with every expert
#: held (where the router is trained)
KINDS = {"full": dict(num_layers=1, rope_layout=(0,), window_layout=(0,)),
         "windowed": dict(num_layers=1, rope_layout=(1,), window_layout=(1,)),
         "period": dict(num_layers=2, rope_layout=(0, 1),
                        window_layout=(0, 1)),
         "uncut": dict(num_layers=2, rope_layout=(0, 1), window_layout=(0, 1),
                       experts_held=(0, 8))}


@pytest.mark.parametrize("kind", list(KINDS))
def test_module_matches_reference_on_logits_loss_and_gradients(kind):
    model = small_smallthinker_lm(seq_len=L, seed=5, **KINDS[kind])
    kwargs = model.module.get_config()
    x, y = tokens(1)
    assert rel_l2(model.predict(x), reference.forward(
        model.params, x, **kwargs)) < TOL
    loss, grads = jax.value_and_grad(
        lambda p: module_loss(model, p, x, y))(model.params)
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: reference.loss(p, x, y, **kwargs))(model.params)
    assert abs(float(loss) - float(ref_loss)) < TOL * float(ref_loss)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    ref_flat = dict(jax.tree_util.tree_leaves_with_path(ref_grads))
    assert len(flat) == len(ref_flat) > 10
    for path, g in flat:
        name = jax.tree_util.keystr(path)
        if "router" in name and kind != "uncut":
            # a share does not train its router, here and in the reference
            assert not np.any(g) and not np.any(ref_flat[path]), name
            continue
        assert np.linalg.norm(ref_flat[path]) > 0, name
        assert rel_l2(g, ref_flat[path]) < TOL, name


def _unnormalised(x, p, k, round_to, chosen=None, trained=True):
    w, e = jax.lax.top_k(jax.nn.softmax(x @ p["router"]["kernel"], -1), k)
    return w, e, e


def _router_after_norm(x, p, k, round_to, chosen=None, trained=True):
    return ORIGINAL_ROUTE(reference._rms_norm(x, p["ln_attn"], 1e-6), p, k,
                          round_to, chosen, trained)


ORIGINAL_ROUTE = reference._route

FAULTS = {
    "a skipped block": dict(kwargs=dict(num_layers=1)),
    "the window ignored": dict(kwargs=dict(window_layout=(0, 0))),
    "RoPE on the full layer": dict(kwargs=dict(rope_layout=(1, 1))),
    "top-k weights not renormalised": dict(route=_unnormalised),
    "the router fed the normalised input": dict(route=_router_after_norm),
    "bfloat16 in place of float32": dict(kwargs=dict(round_to=jnp.bfloat16)),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_reference_notices(fault, monkeypatch):
    """Each planted fault, in the reference's place, breaks the float32
    agreement by far more than the tolerance."""
    model = small_smallthinker_lm(seq_len=L, seed=5)
    kwargs = {**model.module.get_config(), **FAULTS[fault].get("kwargs", {})}
    if "route" in FAULTS[fault]:
        monkeypatch.setattr(reference, "_route", FAULTS[fault]["route"])
    x, _ = tokens(1)
    err = rel_l2(model.predict(x), reference.forward(model.params, x, **kwargs))
    assert err > 10 * TOL, (fault, err)


def _expert_layer(first, held, d=16, f=8, seed=0):
    layer = DroplessExperts(first, held, d, f)
    x = jnp.zeros((4, d))
    variables = layer.init(jax.random.key(seed), x, jnp.ones((4, 2)),
                           jnp.zeros((4, 2), jnp.int32))
    return layer, variables


def _plain_loop(x, weights, experts, params, first, held):
    p = params["experts"]
    out = np.zeros(x.shape, np.float64)
    for t in range(x.shape[0]):
        for w, e in zip(np.asarray(weights[t]), np.asarray(experts[t])):
            if first <= e < first + held:
                n = e - first
                g = np.asarray(x[t], np.float64)
                hidden = (np.maximum(g @ p["gate"]["kernel"][n], 0)
                          * (g @ p["up"]["kernel"][n]))
                out[t] += w * (hidden @ p["down"]["kernel"][n])
    return out


@pytest.mark.parametrize("held", [3, 4], ids=["live-rows-end-inside-the-buffer",
                                              "live-rows-fill-the-buffer"])
def test_expert_layer_drops_nothing_under_a_skewed_router(held):
    """Expert 0 takes nine tokens in ten, expert 1 none: every assignment to
    a held expert is computed, whatever the load. With expert 3 not held,
    under two thirds of the buffer's rows are live and the row kernels stop
    at the tile that holds the last of them; with it held every row is and
    the whole buffer moves."""
    T, k = 200, 2
    rng = np.random.default_rng(2)
    first_choice = np.where(rng.random(T) < 0.9, 0, 2)
    second = np.where(first_choice == 0,
                      rng.choice([2, 3], T, p=[0.3, 0.7]), 3)
    experts = jnp.asarray(np.stack([first_choice, second], 1), jnp.int32)
    w = rng.random((T, k)).astype(np.float32)
    weights = jnp.asarray(w / w.sum(1, keepdims=True))
    layer, variables = _expert_layer(0, held)
    x = jnp.asarray(rng.normal(size=(T, 16)), jnp.float32)
    out, mutated = layer.apply(variables, x, weights, experts,
                               mutable=[ROUND_COUNTERS])
    want = _plain_loop(x, weights, experts, variables["params"], 0, held)
    assert rel_l2(out, want) < 1e-5
    counted = mutated[ROUND_COUNTERS]
    np.testing.assert_array_equal(
        np.asarray(counted["assignments_held"]),
        [(np.asarray(experts) == e).sum() for e in range(held)])
    assert counted["assignments_held"][0] > 0.85 * T
    assert counted["assignments_held"][1] == 0
    assert float(counted["tokens_without_held_expert"]) == 0.0
    live = float(np.sum(counted["assignments_held"]))
    assert (live == T * k) if held == 4 else (0.5 < live / (T * k) < 0.68)
    assert float(counted["tokens"]) == T and float(counted["steps"]) == 1
    # the rows the kernels visited: the live ones, rounded up to a tile
    tile = row_kernels.gather_tile(T * k, 16, jnp.float32)
    assert float(counted["rows_moved"]) == min(-(-live // tile) * tile, T * k)
    # and the gradient of every token reaches it through the sorted buffer
    grad = jax.grad(lambda x: jnp.sum(layer.apply(
        variables, x, weights, experts)))(x)
    assert np.isfinite(np.asarray(grad)).all()
    assert (np.abs(np.asarray(grad)).sum(1) > 0).all()


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips holding two of eight experts each: their parts of the
    result, summed, are what one layer holding all eight gives."""
    T, k, E = 96, 3, 8
    rng = np.random.default_rng(3)
    experts = jnp.asarray(np.stack([rng.choice(E, k, replace=False)
                                    for _ in range(T)]), jnp.int32)
    w = rng.random((T, k)).astype(np.float32)
    weights = jnp.asarray(w / w.sum(1, keepdims=True))
    x = jnp.asarray(rng.normal(size=(T, 16)), jnp.float32)
    whole, variables = _expert_layer(0, E)
    uncut = whole.apply(variables, x, weights, experts)
    parts = []
    for first in range(0, E, 2):
        share, _ = _expert_layer(first, 2)
        params = jax.tree.map(lambda a: a[first:first + 2],
                              variables["params"])
        parts.append(share.apply({"params": params}, x, weights, experts))
    assert rel_l2(sum(parts), uncut) < 1e-5
    assert rel_l2(parts[0], uncut) > 0.1  # a share alone is not the layer
    assert rel_l2(uncut, _plain_loop(x, weights, experts,
                                     variables["params"], 0, E)) < 1e-5


def test_sliced_vocabulary_loss():
    """Ids, logits and loss over the held rows: the trainer's loss on the
    module's logits is the reference's, and the data stays in the slice."""
    from benchmarks.families import smallthinker as family

    config = {"seq_len": L, "module": {"vocab_size": 128}}
    df = family.make_dataframe(config, 8, seed=9)
    x, y = np.asarray(df["features"]), np.asarray(df["label"])
    assert x.shape == (8, L) and 0 <= min(x.min(), y.min())
    assert max(x.max(), y.max()) < 128
    model = small_smallthinker_lm(seq_len=L, seed=1)
    assert model.predict(x[:2]).shape == (2, L, 128)
    got = float(module_loss(model, model.params, x[:2], y[:2]))
    ref = float(reference.loss(model.params, x[:2], y[:2],
                               **model.module.get_config()))
    assert abs(got - ref) < 1e-5 * ref
    assert abs(ref - np.log(128)) < 0.5  # near ln V before training


def test_new_parameters_fall_under_the_expert_axis():
    model = small_smallthinker_lm(seq_len=L)
    specs = param_path_specs(model.params, MOE_RULES)
    experts = specs["block_0"]["moe"]["experts"]
    for name in ("gate", "up", "down"):
        assert experts[name]["kernel"][0] == EXPERT_AXIS
    assert specs["block_0"]["router"]["kernel"] == jax.sharding.PartitionSpec()
    assert len(specs["block_1"]["attn"]["query"]["kernel"]) == 3


def test_counters_leave_the_round_program_with_the_loss():
    """`dk.AEASGD(...).train(df)` trains the model through the engine, and
    what each round routed to the held experts reaches telemetry: the first
    round's count is what the module counts on that batch."""
    model = small_smallthinker_lm(seq_len=L, seed=2)
    assert model.state_collections == (ROUND_COUNTERS,)
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
    assert all(e["steps"] == 1 and e["layers"] == 2 for e in events)
    _, counted = model.module.apply(
        {"params": model.params, **model.state}, data[:4, :-1],
        mutable=[ROUND_COUNTERS])
    want = sum(float(np.sum(c["moe"]["assignments_held"]))
               for c in counted[ROUND_COUNTERS].values())
    assert events[0]["assignments_held"] == want > 0
    assert tele.counter("moe.assignments_held").value - before == sum(
        e["assignments_held"] for e in events)
    assert 0 < tele.gauge("moe.tokens_without_held_expert_share").value < 1
    assert tele.gauge("moe.load_max_over_mean").value >= 1.0
    # the rows the kernels visited over the buffers' rows: the live share
    # (a quarter here) rounded up to a tile, and at this size one tile is
    # the buffer
    moved = tele.gauge("moe.rows_moved_share").value
    assert moved == events[-1]["rows_moved_share"]
    assert moved == sum(float(c["moe"]["rows_moved"]) for c in
                        counted[ROUND_COUNTERS].values()) / (2 * 4 * L * 2)
    assert 0.25 <= moved <= 1.0
    assert np.isfinite(trainer.get_history()).all()


def test_model_round_trips_through_its_config():
    module = small_smallthinker_lm(seq_len=L).module
    again = SmallThinkerLM.from_config(
        copy.deepcopy({k: list(v) if isinstance(v, tuple) else v
                       for k, v in module.get_config().items()}))
    assert again == module
