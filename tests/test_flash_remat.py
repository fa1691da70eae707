"""What ``remat=True`` recomputes and what it keeps: the flash forward's
``out`` and ``lse`` are named in the kernel's wrapper
(``flash_attention.FLASH_RESIDUALS``) and saved by the models' ``nn.remat``
policy, so the gradient runs ``dk_flash_fwd`` once a layer. Read three ways
that must agree: the Pallas calls in the gradient's jaxpr, the named arrays
the recomputed pass takes in, and the gauge ``remat.flash_residual_bytes``."""

import collections
import sys

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu import telemetry
from distkeras_tpu.models import SmallThinkerLM
from distkeras_tpu.models.base import Model
from distkeras_tpu.models.transformer import TransformerLM
from distkeras_tpu.ops.losses import get_loss
from distkeras_tpu.ops.pallas import flash_attention
from distkeras_tpu.ops.pallas.flash_attention import FLASH_RESIDUALS

LAYERS = 2
L = 64


def _gpt2(**kw):
    return TransformerLM(vocab_size=64, num_layers=LAYERS, d_model=32,
                         num_heads=2, d_ff=64, max_seq_len=L,
                         attn_impl="flash", **kw)


def _smallthinker(rope=(0, 1), window=(0, 1), **kw):
    return SmallThinkerLM(vocab_size=64, num_layers=len(rope), d_model=32,
                          num_heads=4, num_kv_heads=2, head_dim=8,
                          d_expert=16, num_experts=8, experts_per_token=2,
                          experts_held=(0, 2), rope_layout=rope,
                          window_layout=window, window=32,
                          attn_impl="flash", **kw)


MODULES = {"gpt2": _gpt2, "smallthinker": _smallthinker,
           "smallthinker-full": lambda **kw: _smallthinker((0,), (0,), **kw),
           "smallthinker-windowed":
               lambda **kw: _smallthinker((1,), (1,), **kw)}


def _loss_of(module):
    """``(params, loss(params))`` of ``module`` on a batch of two sequences;
    a new function each call, so that ``jax.jit`` traces it anew."""
    model = Model.build(module, jnp.zeros((1, L), jnp.int32))
    ids = np.random.default_rng(0).integers(0, 64, (2, L + 1), np.int32)
    x, y = ids[:, :-1], ids[:, 1:]
    loss = get_loss("sparse_categorical_crossentropy")
    return model.params, lambda p: loss(
        model.apply(p, x).astype(jnp.float32), y)


def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub)


def _kernels(jaxpr) -> collections.Counter:
    return collections.Counter(
        e.params["name"] for e in _walk(jaxpr)
        if e.primitive.name == "pallas_call")


def _kept_bytes(jaxpr) -> int:
    """Bytes of the named arrays of the first pass that a recomputed pass
    (a ``remat2`` equation of the gradient) takes in, as they are or through
    the ``reduce_precision`` to their own type that JAX puts on a residual
    the first pass also uses."""
    named = {e.outvars[0] for e in jaxpr.eqns if e.primitive.name == "name"
             and e.params["name"] in FLASH_RESIDUALS}
    taken = {v for e in jaxpr.eqns if e.primitive.name == "remat2"
             for v in e.invars if isinstance(v, jax.extend.core.Var)}
    taken |= {e.invars[0] for e in jaxpr.eqns
              if e.primitive.name == "reduce_precision"
              and e.outvars[0] in taken}
    return sum(v.aval.size * v.aval.dtype.itemsize for v in named & taken)


def _strip_policy(monkeypatch):
    """``nn.remat`` as it was: nothing of the block saved but its input."""
    monkeypatch.setattr(jax.checkpoint_policies, "save_only_these_names",
                        lambda *names: None)


@pytest.mark.parametrize("name", ["gpt2", "smallthinker"])
def test_gradient_runs_the_flash_forward_once_a_layer(name):
    params, loss = _loss_of(MODULES[name](remat=True))
    jaxpr = jax.make_jaxpr(jax.grad(loss))(params).jaxpr
    calls = _kernels(jaxpr)
    assert calls["dk_flash_fwd"] == LAYERS
    assert calls["dk_flash_dq"] == calls["dk_flash_dkv"] == LAYERS


@pytest.mark.parametrize("name", ["gpt2", "smallthinker"])
def test_plain_remat_runs_it_twice_and_keeps_nothing(name, monkeypatch):
    _strip_policy(monkeypatch)
    params, loss = _loss_of(MODULES[name](remat=True))
    jaxpr = jax.make_jaxpr(jax.grad(loss))(params).jaxpr
    calls = _kernels(jaxpr)
    assert calls["dk_flash_fwd"] == 2 * LAYERS
    assert calls["dk_flash_dq"] == calls["dk_flash_dkv"] == LAYERS
    assert _kept_bytes(jaxpr) == 0


@pytest.mark.parametrize("name", ["gpt2", "smallthinker"])
def test_gauge_is_the_bytes_the_recomputed_pass_takes_in(name):
    gauge = telemetry.gauge("remat.flash_residual_bytes")
    gauge.set(-1.0)
    params, loss = _loss_of(MODULES[name](remat=True))
    jaxpr = jax.make_jaxpr(jax.grad(loss))(params).jaxpr
    kept = _kept_bytes(jaxpr)
    assert gauge.value == kept > 0
    # out [B * H, L, D] float32 and lse, one float32 a row, a layer.
    heads, head_dim = (2, 16) if name == "gpt2" else (4, 8)
    assert kept == LAYERS * 2 * heads * L * (head_dim * 4 + 4)


@pytest.mark.parametrize("name", ["gpt2", "smallthinker"])
def test_gauge_is_zero_where_no_flash_kernel_runs(name):
    gauge = telemetry.gauge("remat.flash_residual_bytes")
    gauge.set(-1.0)
    params, loss = _loss_of(MODULES[name](remat=True).clone(
        attn_impl="dense"))
    jax.make_jaxpr(jax.grad(loss))(params)
    assert gauge.value == 0.0


@pytest.mark.parametrize("name", ["gpt2", "smallthinker-full",
                                  "smallthinker-windowed"])
def test_a_step_with_the_policy_is_bit_equal_to_one_without(name,
                                                            monkeypatch):
    params, loss = _loss_of(MODULES[name](remat=True))
    with_policy = jax.jit(jax.value_and_grad(loss))(params)
    _strip_policy(monkeypatch)
    params, loss = _loss_of(MODULES[name](remat=True))
    plain = jax.jit(jax.value_and_grad(loss))(params)
    assert np.isfinite(float(plain[0]))
    for got, want in zip(jax.tree.leaves(with_policy), jax.tree.leaves(plain),
                         strict=True):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("window", [None, 32], ids=["full", "windowed"])
def test_the_names_are_inert_outside_a_checkpoint(window, monkeypatch):
    """No policy asks for them: the gradient of a bare call compiles to the
    program it is with the names taken out of the wrapper."""
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((1, L, 4, 8)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((1, L, 2, 8)), jnp.float32)
            for _ in range(2))

    def loss(q, k, v):
        return flash_attention(q, k, v, block_size=32, window=window).sum()

    programs = []
    for named in (True, False):
        if not named:
            # The package's attribute of this name is the function.
            monkeypatch.setattr(
                sys.modules["distkeras_tpu.ops.pallas.flash_attention"],
                "checkpoint_name", lambda x, name: x)
        traced = jax.jit(jax.grad(loss, (0, 1, 2))).trace(q, k, v)
        assert all((name in str(traced.jaxpr)) == named
                   for name in FLASH_RESIDUALS)
        programs.append(traced.lower().compile().as_text())
    assert programs[0] == programs[1]
