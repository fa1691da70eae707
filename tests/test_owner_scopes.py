"""The sublayer (owner) scopes of the round program: ``scopes.owner`` is the
one idiom that opens them, every family of the benchmark's cells opens the
owners it should in every pass, and the counter ``trace.owner_scopes`` says
that this process's lowering did. Lowering only, on abstract parameters:
nothing here compiles or runs a model."""

import os
import re

import jax
import jax.numpy as jnp
import optax
import pytest

import distkeras_tpu
from distkeras_tpu import telemetry, workers
from distkeras_tpu.models.kimi_linear import KimiLinearLM
from distkeras_tpu.models.lfm2 import Lfm2MoeLM
from distkeras_tpu.models.resnet import ResNet
from distkeras_tpu.models.smallthinker import SmallThinkerLM
from distkeras_tpu.models.transformer import TransformerLM
from distkeras_tpu.ops.losses import get_loss
from distkeras_tpu.scopes import OWNERS, PREFIX, owner
from distkeras_tpu.telemetry import registry

L = 64
LM = {"cast", "embed", "norm", "mixer", "ffn", "head", "loss"}
SHARE = dict(vocab_size=128, d_model=32, num_heads=4, num_experts=8,
             experts_per_token=2, experts_held=(0, 2), d_expert=16,
             attn_impl="flash", remat=True)
TOKENS = ((2, 2, L), jnp.int32, (2, 2, L))

#: family -> (module at its small_* / tiny_* size; shape and dtype of a
#: round's inputs, [window, batch, ...], and the shape of its targets; owners
#: it opens; owners a recomputed block repeats)
FAMILIES = {
    "transformer_lm": (
        lambda: TransformerLM(vocab_size=128, num_layers=2, d_model=32,
                              num_heads=4, d_ff=64, max_seq_len=L,
                              attn_impl="flash", remat=True),
        TOKENS, LM, {"norm", "mixer", "ffn"}),
    "smallthinker": (
        lambda: SmallThinkerLM(num_layers=2, num_kv_heads=2, head_dim=8,
                               rope_layout=(0, 1), window_layout=(0, 1),
                               window=32, **SHARE),
        TOKENS, LM, {"norm", "mixer", "ffn"}),
    "lfm2": (
        lambda: Lfm2MoeLM(num_layers=3, num_kv_heads=2, head_dim=8, d_ff=48,
                          num_dense_layers=1,
                          layer_types=("conv", "full_attention", "conv"),
                          **SHARE),
        TOKENS, LM, {"norm", "mixer", "ffn"}),
    "kimi_linear": (
        lambda: KimiLinearLM(num_layers=3, heads_held=(0, 2), kda_head_dim=16,
                             qk_nope_head_dim=16, qk_rope_head_dim=8,
                             v_head_dim=16, kv_lora_rank=24, d_ff=48,
                             num_dense_layers=1,
                             layer_types=("kda", "mla", "kda"), **SHARE),
        TOKENS, LM, {"norm", "mixer", "ffn"}),
    "resnet": (
        lambda: ResNet(stage_sizes=(1, 1), base_features=8, num_outputs=10,
                       stem_kernel=3, groups=4),
        ((2, 2, 32, 32, 3), jnp.float32, (2, 2)),
        {"cast", "conv", "norm", "head", "loss"}, set()),
}


def _op_names(module, shape, dtype, targets) -> set:
    """The ``op_name``s of the lowered local steps (``dk_fwd_bwd`` and
    ``dk_optimizer`` over a window, as the round program runs them) of
    ``module`` in bfloat16, on abstract parameters and inputs."""
    variables = jax.eval_shape(
        module.init, jax.random.key(0), jnp.zeros((1,) + shape[2:], dtype))
    state = {k: v for k, v in variables.items() if k != "params"}
    tx = optax.adam(1e-3)
    loop = workers.make_local_loop(
        module, get_loss("sparse_categorical_crossentropy"), tx,
        compute_dtype=jnp.bfloat16, state_collections=tuple(state))
    text = jax.jit(loop).lower(
        variables["params"], jax.eval_shape(tx.init, variables["params"]),
        jax.ShapeDtypeStruct(shape, dtype),
        jax.ShapeDtypeStruct(targets, jnp.int32), None,
        state or None).as_text(debug_info=True)
    return set(re.findall(r'loc\("([^"]*)"', text))


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_familys_lowering_holds_its_owners_in_every_pass(family):
    make, inputs, owners, recomputed = FAMILIES[family]
    before = telemetry.counter("trace.owner_scopes").value
    seen = {}  # owner -> the passes in which an op_name carries it innermost
    for name in _op_names(make(), *inputs):
        found = re.findall(PREFIX + r"([a-z]+)", name)
        if found:
            seen.setdefault(found[-1], set()).add(
                "recomputed" if "rematted_computation" in name
                else "backward" if "transpose(" in name else "forward")
    assert set(seen) == owners
    for name in owners:
        assert {"forward", "backward"} <= seen[name], (name, seen[name])
    assert {o for o, passes in seen.items()
            if "recomputed" in passes} == recomputed
    assert telemetry.counter("trace.owner_scopes").value > before


def test_owner_refuses_a_name_outside_the_vocabulary():
    with pytest.raises(ValueError, match="nonsense"):
        with owner("nonsense"):
            pass
    assert OWNERS == ("cast", "embed", "norm", "mixer", "ffn", "conv",
                      "head", "loss")


def test_the_counter_is_declared_and_counts_each_scope_opened():
    assert registry.declared("counter", "trace.owner_scopes")
    before = telemetry.counter("trace.owner_scopes").value
    with owner("norm"), owner("mixer"):
        pass
    assert telemetry.counter("trace.owner_scopes").value == before + 2


def test_one_idiom_opens_every_owner_scope():
    """The prefix is written in ``scopes.py`` alone, so nothing else can open
    an owner scope by ``jax.named_scope`` with a literal, and no
    ``named_scope(`` in the package is handed it by name either."""
    root = os.path.dirname(distkeras_tpu.__file__)
    holders, by_name = [], []
    for folder, _, files in os.walk(root):
        for file in files:
            if not file.endswith(".py"):
                continue
            path = os.path.join(folder, file)
            with open(path, encoding="utf-8") as f:
                text = f.read()
            if PREFIX in text:
                holders.append(os.path.relpath(path, root))
            if path != os.path.join(root, "scopes.py"):
                by_name += re.findall(r"named_scope\(\s*(?:scopes\.)?PREFIX",
                                      text)
    assert holders == ["scopes.py"] and not by_name
