"""The two operations Kimi Linear forced into ``ops/``, on the CPU in float32:
the gated delta rule's chunked form (``ops/delta_rule.py``) against the
recurrence itself, values and every gradient, at two and three chunks and two
chunk sizes; the overflow case (a decay held at -1.6 a step over whole
chunks, and at -20); keys that point one way (the triangular inverse's
stability); the state carried across a chunk boundary; the gradients with
bfloat16 operands, as the trainer runs it, against the float32 recurrence's,
with float8 operands as the control; and the flash
kernels with values of another width than keys against dense attention,
forward and dq / dk / dv, interpreted."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.references import kimi_linear as reference  # noqa: E402
from distkeras_tpu.ops.delta_rule import (chunk_for,  # noqa: E402
                                          chunked_gated_delta_rule)
from distkeras_tpu.ops.pallas.flash_attention import (  # noqa: E402
    default_tiling, flash_attention)


def rel_l2(a, b):
    a, b = np.asarray(a, np.float64).ravel(), np.asarray(b, np.float64).ravel()
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))


def delta_inputs(length, g=None, seed=0, B=2, H=2, K=16, V=8):
    """Unit keys, scaled unit queries, a decay from ``e^-6`` to ``e^0.5`` a
    step and channel (or held at ``g``), beta in (0, 1)."""
    ks = jax.random.split(jax.random.key(seed), 5)

    def unit(key):
        x = jax.random.normal(key, (B, length, H, K))
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    decay = -jnp.exp(jax.random.uniform(ks[3], (B, length, H, K), minval=-6,
                                        maxval=0.5)) if g is None \
        else jnp.full((B, length, H, K), g)
    return (unit(ks[0]) * K ** -0.5, unit(ks[1]),
            jax.random.normal(ks[2], (B, length, H, V)), decay,
            jax.nn.sigmoid(jax.random.normal(ks[4], (B, length, H))))


# -- the chunked scan ---------------------------------------------------------

@pytest.mark.parametrize("chunks, chunk", [(2, 64), (3, 64), (2, 32), (3, 32)])
def test_chunked_delta_rule_is_the_recurrence_values_and_gradients(chunks,
                                                                   chunk):
    args = delta_inputs(chunks * chunk)
    out, least = chunked_gated_delta_rule(*args, chunk=chunk)
    want = reference.delta_rule(*args)
    assert rel_l2(out, want) < 1e-5
    assert out.shape == want.shape and float(least) < 0

    def scalar(fn):
        return lambda *a: jnp.sum(jnp.sin(fn(*a)))

    got = jax.jit(jax.grad(scalar(lambda *a: chunked_gated_delta_rule(
        *a, chunk=chunk)[0]), argnums=(0, 1, 2, 3, 4)))(*args)
    ref = jax.jit(jax.grad(scalar(reference.delta_rule),
                           argnums=(0, 1, 2, 3, 4)))(*args)
    for name, a, b in zip("q k v g beta".split(), got, ref):
        assert np.linalg.norm(b) > 0, name
        assert rel_l2(a, b) < 1e-5, name


@pytest.mark.parametrize("g", [-1.6, -20.0, 0.0])
def test_a_decay_held_over_whole_chunks_stays_finite_and_agrees(g):
    """``g`` = -1.6 a step is the strongest the initialisation allows (A = 16,
    softplus 0.1): a chunk of 64 sums to -102, past the 88 at which ``e^-G``
    leaves float32. -20 a step is far past anything; 0 is no decay at all."""
    args = delta_inputs(128, g=g)
    out, least = chunked_gated_delta_rule(*args, chunk=64)
    assert float(least) == pytest.approx(64 * g, rel=1e-5)
    assert np.isfinite(np.asarray(out)).all()
    assert rel_l2(out, reference.delta_rule(*args)) < 1e-5
    grads = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(
        chunked_gated_delta_rule(*a, chunk=64)[0])),
        argnums=(0, 1, 2, 3, 4)))(*args)
    ref = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(
        reference.delta_rule(*a))), argnums=(0, 1, 2, 3, 4)))(*args)
    for name, a, b in zip("q k v g beta".split(), grads, ref):
        assert np.isfinite(np.asarray(a)).all(), name
        if name != "g" or g > -20:  # at e^-20 that gradient is rounding
            assert rel_l2(a, b) < 1e-4, name


def alike_inputs(alike, beta):
    """:func:`delta_inputs` with every key ``alike`` parts one common vector,
    hardly any decay and one ``beta``."""
    q, k, v, g, _ = delta_inputs(128, seed=5)
    common = jax.random.normal(jax.random.key(9), (1, 1) + k.shape[2:])
    k = alike * common + (1 - alike) * k
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    return (k * k.shape[-1] ** -0.5, k, v, jnp.full_like(g, -1e-3),
            jnp.full(k.shape[:3], beta))


@pytest.mark.parametrize("alike, beta", [(0.9, 0.5), (1.0, 0.99)])
def test_keys_that_point_one_way_do_not_break_the_triangular_inverse(alike,
                                                                     beta):
    """After one Adam step without warm-up the keys of a sequence share a
    large common part (PERF.md PR 34): ``A`` then holds entries near 1, and
    the product ``(I - N)(I + N^2)(I + N^4)...`` for the inverse, exact on
    paper, read 4e4 to 2e21 away from the recurrence here (its powers grow
    like binomial coefficients). The inverse by blocks agrees."""
    args = alike_inputs(alike, beta)
    out, _ = chunked_gated_delta_rule(*args, chunk=64)
    want = reference.delta_rule(*args)
    assert float(jnp.max(jnp.abs(out - want))) < 1e-4
    grads = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(
        chunked_gated_delta_rule(*a, chunk=64)[0])), argnums=(1, 2)))(*args)
    assert all(np.isfinite(np.asarray(a)).all() for a in grads)


def test_the_state_is_carried_across_the_chunk_boundary():
    """One chunk of 64 and two of 32 of the same sequence agree: the second
    chunk starts from the state the first left. Started from zero, it is
    another result."""
    args = delta_inputs(64, seed=3)
    one, _ = chunked_gated_delta_rule(*args, chunk=64)
    two, _ = chunked_gated_delta_rule(*args, chunk=32)
    assert rel_l2(two, one) < 1e-5
    alone, _ = chunked_gated_delta_rule(*[a[:, 32:] for a in args], chunk=32)
    assert rel_l2(alone, one[:, 32:]) > 0.05
    assert [chunk_for(n) for n in (8192, 128, 96, 48, 24)] \
        == [64, 64, 32, 16, 8]
    with pytest.raises(ValueError, match="divide"):
        chunked_gated_delta_rule(*args, chunk=48)
    with pytest.raises(ValueError, match="power of two"):
        chunked_gated_delta_rule(*[jnp.concatenate([a, a, a], 1)
                                   for a in args], chunk=96)


#: The trainer's dtype: the backward pass is JAX's derivative through a
#: float32 triangular inverse whose products take bfloat16 operands. Relative
#: L2 of each gradient against the float32 recurrence's, on the CPU, seeds 0
#: to 3: 3.9e-3 to 6.2e-3 in bfloat16 on keys drawn apart, 5.8e-2 to 9.6e-2
#: in float8_e4m3; 2e-2 lies a factor of three from either. On keys nine
#: parts in ten alike the system is badly conditioned whatever computes it:
#: q, k, v and beta read 1.9e-2 to 6.5e-2 in bfloat16 and 0.28 to 1.0 in
#: float8, the same factor of fifteen apart (the decay's gradient, of a
#: decay of -1e-3, 0.33 and 6.3: not compared).
BF16_GRADIENT_CASES = {
    "keys-apart": (lambda: delta_inputs(128), 2e-2, "q k v g beta"),
    "strongest-decay": (lambda: delta_inputs(128, g=-1.6, seed=1), 2e-2,
                        "q k v g beta"),
    "keys-alike": (lambda: alike_inputs(0.9, 0.5), 0.12, "q k v beta"),
}


@pytest.mark.parametrize("case", BF16_GRADIENT_CASES)
def test_bfloat16_gradients_of_the_chunked_form_against_the_recurrence(case):
    make, tolerance, compared = BF16_GRADIENT_CASES[case]
    args = make()

    def gradients(fn):
        return jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(
            fn(*a).astype(jnp.float32))), argnums=(0, 1, 2, 3, 4)))(*args)

    def chunked_in(dtype):
        return lambda q, k, v, g, beta: chunked_gated_delta_rule(
            q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta,
            chunk=64)[0]

    want = dict(zip("q k v g beta".split(), gradients(reference.delta_rule)))
    for dtype, passes in ((jnp.bfloat16, True), (jnp.float8_e4m3fn, False)):
        got = dict(zip(want, gradients(chunked_in(dtype))))
        for name in compared.split():
            assert np.isfinite(np.asarray(got[name])).all(), (dtype, name)
            assert (rel_l2(got[name], want[name]) < tolerance) == passes, \
                (dtype, name, rel_l2(got[name], want[name]))


# -- the flash kernels with a value width of their own ------------------------

def dense_attention(q, k, v):
    length, group = q.shape[1], q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, 2), jnp.repeat(v, group, 2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k)
    s = jnp.where(jnp.tril(jnp.ones((length, length), bool)), s, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("dk, dv, kv_heads, tiling", [
    (192, 128, 4, {}), (192, 128, 4, {"block_k": 128}),
    (48, 80, 2, {}), (48, 16, 4, {"block_k": 64})],
    ids=["192-128", "192-128-rectangles", "48-80-groups", "48-16-squares"])
def test_flash_with_values_of_another_width_matches_dense(dk, dv, kv_heads,
                                                          tiling):
    """Forward and dq / dk / dv, interpreted, at latent attention's widths
    and at another pair, with the tolerances of the equal-width cases (the
    kernels' products take bfloat16 operands whatever their input is)."""
    ks = jax.random.split(jax.random.key(7), 4)
    B, length, H = 2, 256, 4
    q = jax.random.normal(ks[0], (B, length, H, dk)) / np.sqrt(dk)
    k = jax.random.normal(ks[1], (B, length, kv_heads, dk))
    v = jax.random.normal(ks[2], (B, length, kv_heads, dv))
    do = jax.random.normal(ks[3], (B, length, H, dv))
    out = flash_attention(q, k, v, block_size=64, interpret=True, **tiling)
    assert out.shape == (B, length, H, dv)
    assert rel_l2(out, dense_attention(q, k, v)) < 1e-2
    got = jax.grad(lambda *a: jnp.sum(flash_attention(
        *a, block_size=64, interpret=True, **tiling) * do), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense_attention(*a) * do),
                    (0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        assert a.shape == b.shape, name
        assert rel_l2(a, b) < 1e-2, name
    with pytest.raises(ValueError, match="queries' width"):
        flash_attention(q, k[..., :32], v, block_size=64, interpret=True)


def test_the_tiling_leaves_equal_widths_alone_and_fits_192_beside_128():
    # what the three LM cells run, as they were
    assert default_tiling(8192, 128) == (512, 512, 256)
    assert default_tiling(8192, 128, window=4096) == (512, 512, 256)
    assert default_tiling(8192, 64) == (512, 512, 256)
    assert default_tiling(1024, 64) == (1024, 1024, 256)
    assert default_tiling(8192, 128, Dv=128) == default_tiling(8192, 128)
    # keys of 192 take 256 lanes: K and V whole leave room for tiles of 256
    assert default_tiling(8192, 192, Dv=128) == (256, 256, 128)
    assert default_tiling(1024, 192, Dv=128) == (1024, 1024, 256)
