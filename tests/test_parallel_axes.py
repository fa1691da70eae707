"""Sequence-parallel + tensor-parallel tests: ring attention, gather-SP, TP rules.

Each parallel attention implementation is checked for numerical equivalence against
the dense single-device computation on the same inputs.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from distkeras_tpu.models import Model, small_transformer_lm
from distkeras_tpu.models.transformer import TransformerLM
from distkeras_tpu.ops.ring_attention import ring_attention
from distkeras_tpu.parallel.sharding import (
    TRANSFORMER_TP_RULES,
    param_path_specs,
    param_shardings,
)
from distkeras_tpu.runtime.mesh import hybrid_mesh


B, L, H, D = 2, 32, 2, 8  # global seq L sharded over 4 chips -> 8 per chip


def dense_causal(q, k, v):
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k)
    L_ = q.shape[1]
    mask = jnp.tril(jnp.ones((L_, L_), bool))
    scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def test_ring_attention_matches_dense():
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(B, L, H, D)).astype(np.float32))
               for _ in range(3))
    mesh = hybrid_mesh({"seq": 4})

    ring = shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="seq"),
        mesh=mesh,
        in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
        out_specs=P(None, "seq"),
        check_vma=False,
    )
    out = ring(q, k, v)
    expect = dense_causal(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=2e-5)


def test_transformer_seq_parallel_matches_dense():
    """Full TransformerLM forward, sequence-sharded (gather + ring) == dense."""
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, 64, size=(2, 32)), jnp.int32)

    dense_model = small_transformer_lm(vocab_size=64, num_layers=1, d_model=16,
                                       num_heads=2, d_ff=32, max_seq_len=32, seq_len=32)
    expect = dense_model.predict(tokens)

    mesh = hybrid_mesh({"seq": 4})
    for impl in ("gather", "ring"):
        sp_module = TransformerLM(
            vocab_size=64, num_layers=1, d_model=16, num_heads=2, d_ff=32,
            max_seq_len=32, seq_axis="seq", attn_impl=impl,
        )
        fwd = shard_map(
            lambda p, t: sp_module.apply({"params": p}, t, train=False),
            mesh=mesh,
            in_specs=(P(), P(None, "seq")),
            out_specs=P(None, "seq"),
            check_vma=False,
        )
        out = fwd(dense_model.params, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=3e-4,
                                   err_msg=f"attn_impl={impl}")


def test_tp_rules_cover_transformer_params():
    model = small_transformer_lm(vocab_size=64, num_layers=2, d_model=16,
                                 num_heads=2, d_ff=32, max_seq_len=32)
    specs = param_path_specs(model.params, TRANSFORMER_TP_RULES)
    flat = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, P))[0]
    by_name = {"/".join(str(getattr(p, "key", p)) for p in path): spec
               for path, spec in flat}
    assert by_name["block_0/attn/query/kernel"] == P(None, "model", None)
    assert by_name["block_0/mlp_up/kernel"] == P(None, "model")
    assert by_name["block_0/mlp_down/kernel"] == P("model", None)
    assert by_name["tok_embed/embedding"] == P(None, "model")
    # norms replicated
    assert by_name["block_0/ln_attn/scale"] == P()


def test_tp_sharded_forward_matches_dense():
    """pjit with TP shardings == unsharded forward (GSPMD inserts collectives)."""
    rng = np.random.default_rng(2)
    tokens = jnp.asarray(rng.integers(0, 64, size=(4, 16)), jnp.int32)
    model = small_transformer_lm(vocab_size=64, num_layers=1, d_model=16,
                                 num_heads=2, d_ff=32, max_seq_len=32, seq_len=16)
    expect = model.predict(tokens)

    mesh = hybrid_mesh({"data": 4, "model": 2})
    shardings = param_shardings(model.params, mesh, TRANSFORMER_TP_RULES)
    sharded_params = jax.device_put(model.params, shardings)
    tok_sharded = jax.device_put(tokens, NamedSharding(mesh, P("data")))

    fwd = jax.jit(lambda p, t: model.module.apply({"params": p}, t, train=False))
    out = fwd(sharded_params, tok_sharded)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=3e-4)


def test_flash_attention_under_tensor_parallelism():
    """attn_impl='flash' on a dp x tp mesh: the Mosaic kernel is manualized
    over the model axis by a nested shard_map (heads are independent), so
    flash + TP compose. Must match the dense twin."""
    from distkeras_tpu.models.transformer import TransformerLM
    from distkeras_tpu.parallel.spmd import SPMDEngine
    from distkeras_tpu.runtime.mesh import hybrid_mesh

    arch = dict(vocab_size=128, num_layers=2, d_model=64, num_heads=4,
                d_ff=128, max_seq_len=32)
    model = Model.build(TransformerLM(**arch), jnp.zeros((1, 32), jnp.int32))
    mesh = hybrid_mesh({"data": 2, "seq": 1, "model": 4})
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, 128, size=(4, 32)), jnp.int32)
    targets = jnp.asarray(np.roll(np.asarray(tokens), -1, 1), jnp.int32)

    losses = {}
    for impl in ("dense", "flash"):
        m = Model(module=TransformerLM(**arch, attn_impl=impl),
                  params=model.params)
        eng = SPMDEngine(m, "sgd", "sparse_categorical_crossentropy", mesh,
                         TRANSFORMER_TP_RULES, learning_rate=0.1)
        state = eng.init_state()
        x = jax.device_put(tokens, eng.batch_sharding())
        y = jax.device_put(targets, eng.batch_sharding())
        state, l0 = eng.step(state, x, y)
        state, l1 = eng.step(state, x, y)
        losses[impl] = (float(l0), float(l1))
    np.testing.assert_allclose(losses["flash"], losses["dense"], rtol=2e-3)
    assert losses["flash"][1] < losses["flash"][0]


def test_gspmd_engine_rejects_flash_and_seq_axis_at_init():
    """Unsupported combos must fail at construction with a pointer to
    SPMDEngine, not as an opaque TPU trace-time mesh failure (the CPU
    interpret mode would even mask it entirely)."""
    import pytest

    from distkeras_tpu.models.transformer import TransformerLM
    from distkeras_tpu.parallel.gspmd import GSPMDEngine
    from distkeras_tpu.runtime.mesh import hybrid_mesh

    arch = dict(vocab_size=128, num_layers=1, d_model=32, num_heads=2,
                d_ff=64, max_seq_len=16)
    mesh = hybrid_mesh({"data": 4, "model": 2})
    flash = Model.build(TransformerLM(**arch, attn_impl="flash"),
                        jnp.zeros((1, 1), jnp.int32))
    with pytest.raises(ValueError, match="SPMDEngine"):
        GSPMDEngine(flash, "sgd", "sparse_categorical_crossentropy", mesh,
                    TRANSFORMER_TP_RULES)
    ringy = Model.build(TransformerLM(**arch), jnp.zeros((1, 1), jnp.int32))
    ringy = Model(module=TransformerLM(**arch, seq_axis="seq",
                                       attn_impl="ring"),
                  params=ringy.params)
    with pytest.raises(ValueError, match="SPMDEngine"):
        GSPMDEngine(ringy, "sgd", "sparse_categorical_crossentropy", mesh,
                    TRANSFORMER_TP_RULES)
