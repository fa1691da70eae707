"""Pipeline-parallel tests: gpipe schedule correctness, dp x pp training."""

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from distkeras_tpu.models.base import Model
from distkeras_tpu.models.transformer import TransformerLM
from distkeras_tpu.parallel.pipeline import gpipe, last_stage_broadcast
from distkeras_tpu.parallel.pipeline_engine import (
    PipelineEngine,
    merge_transformer_params,
    split_transformer_params,
)
from distkeras_tpu.runtime.mesh import hybrid_mesh


def test_gpipe_matches_sequential():
    """4-stage pipeline of affine stages == sequential composition."""
    S, M, D = 4, 8, 16
    rng = np.random.default_rng(0)
    # stage s: x -> x * w[s] + b[s]  (stacked params sharded over pipe)
    w = jnp.asarray(rng.uniform(0.5, 1.5, size=(S, D)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(S, D)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(M, 4, D)).astype(np.float32))

    mesh = hybrid_mesh({"pipe": S})

    def run(w, b, x):
        def stage_fn(p, h):
            return h * p[0][0] + p[1][0]

        y = gpipe(stage_fn, (w, b), x, "pipe")
        return last_stage_broadcast(y, "pipe")

    y = shard_map(run, mesh=mesh,
                  in_specs=(P("pipe"), P("pipe"), P()),
                  out_specs=P(), check_vma=False)(w, b, x)

    expect = x
    for s in range(S):
        expect = expect * w[s] + b[s]
    np.testing.assert_allclose(np.asarray(y), np.asarray(expect), rtol=1e-5)


def _tiny_lm(num_layers=4):
    arch = dict(vocab_size=64, num_layers=num_layers, d_model=32, num_heads=2,
                d_ff=64, max_seq_len=16)
    return Model.build(TransformerLM(**arch), jnp.zeros((1, 16), jnp.int32))


def test_split_merge_roundtrip():
    model = _tiny_lm()
    rep, stage = split_transformer_params(model.params, num_stages=2)
    merged = merge_transformer_params(rep, stage)
    for (pa, a), (pb, b) in zip(
        sorted(jax.tree_util.tree_flatten_with_path(model.params)[0], key=str),
        sorted(jax.tree_util.tree_flatten_with_path(merged)[0], key=str),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pipeline_forward_matches_dense():
    """dp x pp pipelined forward == the plain single-device forward."""
    model = _tiny_lm(num_layers=4)
    mesh = hybrid_mesh({"data": 2, "pipe": 4})
    engine = PipelineEngine(model, "sgd", "sparse_categorical_crossentropy", mesh,
                            num_microbatches=2)
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, 64, size=(4, 16)), jnp.int32)

    rep, stage = split_transformer_params(model.params, engine.num_stages)

    def fwd(rep, stage, tokens):
        logits = engine._forward(rep, stage, tokens, jax.random.key(0))
        return last_stage_broadcast(logits, "pipe")

    logits = shard_map(
        fwd, mesh=mesh,
        in_specs=(P(), P("pipe"), P("data")),
        out_specs=P("data"), check_vma=False,
    )(rep, stage, tokens)

    expect = model.predict(tokens)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(expect),
                               atol=2e-4, rtol=1e-4)


def test_pipeline_training_matches_single_device():
    """One dp x pp SGD step == one single-device SGD step on the same batch."""
    import optax

    from distkeras_tpu.ops.losses import get_loss

    model = _tiny_lm(num_layers=2)
    mesh = hybrid_mesh({"data": 2, "pipe": 2})
    lr = 0.1
    engine = PipelineEngine(model, "sgd", "sparse_categorical_crossentropy", mesh,
                            num_microbatches=2, learning_rate=lr)
    state = engine.init_state()

    rng = np.random.default_rng(2)
    tokens = np.asarray(rng.integers(0, 64, size=(4, 16)), np.int32)
    targets = np.asarray(np.roll(tokens, -1, 1), np.int32)
    tj = jax.device_put(jnp.asarray(tokens), engine.batch_sharding())
    gj = jax.device_put(jnp.asarray(targets), engine.batch_sharding())

    state, loss = engine.step(state, tj, gj)
    piped = engine.export_params(state)

    # manual single-device step
    loss_fn = get_loss("sparse_categorical_crossentropy")

    def loss_of(p):
        logits = model.module.apply({"params": p}, jnp.asarray(tokens), train=False)
        return loss_fn(logits, jnp.asarray(targets))

    ref_loss, grads = jax.value_and_grad(loss_of)(model.params)
    tx = optax.sgd(lr)
    updates, _ = tx.update(grads, tx.init(model.params), model.params)
    expect = jax.tree.map(jnp.add, model.params, updates)

    assert abs(float(loss) - float(ref_loss)) < 2e-4
    for a, b in zip(jax.tree.leaves(piped), jax.tree.leaves(expect)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)

    # training continues: a few more steps should reduce loss on this batch
    losses = [float(loss)]
    for _ in range(5):
        state, loss = engine.step(state, tj, gj)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
