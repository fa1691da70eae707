"""The two operations Kimi Linear forced into ``ops/``, on the CPU in float32:
the gated delta rule's chunked form (``ops/delta_rule.py``, both halves the
kernel pairs of ``ops/pallas/delta_rule.py``, interpreted) against the
recurrence itself, values and every gradient, at two and three chunks and two
chunk sizes; the scan's kernel pair alone against the ``jax.numpy`` step it
replaced and JAX's derivative of it, the state across programs and heads, the
last chunk's ``dS`` and an underflowed decay; the in-chunk pair alone against
the kept ``jax.numpy`` form and JAX's derivative of it, six outputs and five
cotangents, across batch rows and heads, in bfloat16 with a float8 control,
under held decays and keys that point one way (all four kernels compiled for
a described v5e at the cell's shape: ``tests/test_pallas_rows.py``, which
describes the topology); the overflow case (a decay held at -1.6 a step over whole
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
from distkeras_tpu.ops.delta_rule import (SUB, chunk_for,  # noqa: E402
                                          chunked_gated_delta_rule,
                                          in_chunk_by_jax_numpy)
from distkeras_tpu.ops.pallas import delta_rule as kda_kernels  # noqa: E402
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


#: The trainer's dtype: the backward pass is the kernels' written one (until
#: PR 39 JAX's derivative of the ``jax.numpy`` in-chunk half), through a
#: float32 triangular inverse whose products take bfloat16 operands. Relative
#: L2 of each gradient against the float32 recurrence's, on the CPU: 3.8e-3 to
#: 5.1e-3 in bfloat16 on keys drawn apart (3.9e-3 to 6.2e-3 before the
#: kernels, seeds 0 to 3), 6.1e-2 to 9.3e-2 in float8_e4m3 (5.8e-2 to
#: 9.6e-2); 2e-2 lies a factor of three from either. On keys nine
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


# -- the scan over chunks as a kernel pair -------------------------------------

def scan_inputs(chunks, C, B=1, H=2, K=16, V=8, seed=0, dtype=jnp.float32,
                shrink=None):
    """One call's chunks as ``chunked_gated_delta_rule`` hands them on, drawn
    so that the state stays bounded: ``U`` float32, ``W``, ``Qg``, ``Bq``
    (lower-triangular), ``Kd`` in ``dtype``, a chunk's decay in (0.1, 0.9) (or
    held at ``shrink``), and a cotangent of ``O``."""
    ks = jax.random.split(jax.random.key(seed), 7)
    lead = (B, H, chunks)
    decay = jax.random.uniform(ks[5], lead + (K,), minval=0.1, maxval=0.9) \
        if shrink is None else jnp.full(lead + (K,), shrink, jnp.float32)
    return ((jax.random.normal(ks[0], lead + (C, V)),
             (0.3 * jax.random.normal(ks[1], lead + (C, K)) / np.sqrt(C)
              ).astype(dtype),
             (jax.random.normal(ks[2], lead + (C, K)) / np.sqrt(K)
              ).astype(dtype),
             (jnp.tril(jax.random.normal(ks[3], lead + (C, C))) / np.sqrt(C)
              ).astype(dtype),
             (0.3 * jax.random.normal(ks[4], lead + (C, K)) / np.sqrt(C)
              ).astype(dtype), decay),
            jax.random.normal(ks[6], lead + (C, V)).astype(dtype))


def scan_by_jax_numpy(U, W, Qg, Bq, Kd, shrink, final_state=False):
    """The scan as it stood before the kernels: ``lax.scan`` over chunks of
    three lines, operands of a product in ``W``'s dtype, float32 sums."""
    dt, f32 = W.dtype, jnp.float32
    B, H, _, _, V = U.shape

    def dot(spec, a, b):
        return jnp.einsum(spec, a.astype(dt), b.astype(dt),
                          preferred_element_type=f32)

    def step(S, xs):
        U, W, Qg, Bq, Kd, shrink = xs
        pseudo = U - dot("bhrk,bhkv->bhrv", W, S)
        out = dot("bhrk,bhkv->bhrv", Qg, S) \
            + dot("bhrc,bhcv->bhrv", Bq, pseudo)
        S = shrink[..., None] * S + dot("bhck,bhcv->bhkv", Kd, pseudo)
        return S, out.astype(dt)

    S, out = jax.lax.scan(step, jnp.zeros((B, H, W.shape[-1], V), f32), tuple(
        jnp.moveaxis(x, 2, 0) for x in (U, W, Qg, Bq, Kd, shrink)))
    out = jnp.moveaxis(out, 0, 2)
    return (out, S) if final_state else out


SCAN_NAMES = "U W Qg Bq Kd shrink".split()


def pair_and_step(args, cotangent, heads):
    """``(O, the six cotangents)`` of the kernel pair and of the step."""
    out, vjp = jax.vjp(lambda *a: kda_kernels.scan_chunks(
        *a, heads=heads, interpret=True), *args)
    want, want_vjp = jax.vjp(scan_by_jax_numpy, *args)
    return (out, vjp(cotangent)), (want, want_vjp(cotangent))


@pytest.mark.parametrize("heads", [1, None], ids=["a-head", "all-heads"])
@pytest.mark.parametrize("chunks, C", [(1, 32), (2, 64), (5, 32), (5, 64)])
def test_the_kernel_pair_is_the_step_and_its_derivative(chunks, C, heads):
    """Every output and each of the six cotangents, in its primal's dtype and
    shape, against ``jax.vjp`` of the ``jax.numpy`` step: float32 to 1e-5."""
    args, cotangent = scan_inputs(chunks, C)
    (out, grads), (want, want_grads) = pair_and_step(args, cotangent, heads)
    assert out.shape == want.shape and out.dtype == want.dtype
    assert rel_l2(out, want) < 1e-5
    for name, x, a, b in zip(SCAN_NAMES, args, grads, want_grads):
        assert a.shape == x.shape and a.dtype == x.dtype, name
        if chunks == 1 and name in ("W", "Qg", "Kd") \
                or chunks <= 2 and name == "shrink":
            # they meet the state alone, and the first chunk's is zero; a
            # decay wants a state before it and a chunk after it
            assert not np.asarray(a).any() and not np.asarray(b).any(), name
            continue
        assert np.linalg.norm(b) > 0, name
        assert rel_l2(a, b) < 1e-5, name


@pytest.mark.parametrize("dtype, passes", [(jnp.bfloat16, True),
                                           (jnp.float8_e4m3fn, False)],
                         ids=["bfloat16", "float8-must-fail"])
def test_the_kernel_pair_with_rounded_operands_against_the_float32_step(
        dtype, passes):
    """The trainer's dtype against the float32 step, ``O`` and all six
    cotangents under the 2e-2 of the chunked form's cases; float8_e4m3
    operands are the control that must not pass it."""
    args, cotangent = scan_inputs(5, 64, seed=2)
    rounded = (args[0],) + tuple(a.astype(dtype) for a in args[1:5]) \
        + (args[5],)
    out, vjp = jax.vjp(lambda *a: kda_kernels.scan_chunks(
        *a, interpret=True), *rounded)
    grads = vjp(cotangent.astype(dtype))
    want, want_vjp = jax.vjp(scan_by_jax_numpy, *args)
    errors = {"O": rel_l2(out.astype(jnp.float32), want)}
    for name, a, b in zip(SCAN_NAMES, grads, want_vjp(cotangent)):
        assert a.dtype == (jnp.float32 if name in ("U", "shrink") else dtype)
        assert np.isfinite(np.asarray(a, np.float32)).all(), name
        errors[name] = rel_l2(a.astype(jnp.float32), b)
    assert (max(errors.values()) < 2e-2) == passes, errors


def test_the_state_crosses_programs_and_starts_at_zero_for_every_head():
    """B = 2, H = 3, other data a head, one head a program and two (a program
    of two heads holds heads of both batch rows): a head's result is the
    result of that head run alone, so the scratch was zero at its chunk 0
    whatever the program before left there, and its second chunk started from
    its own first."""
    args, cotangent = scan_inputs(3, 32, B=2, H=3, seed=4)
    for heads in (1, 2):
        (out, grads), (want, want_grads) = pair_and_step(args, cotangent,
                                                         heads)
        assert rel_l2(out, want) < 1e-5
        for name, a, b in zip(SCAN_NAMES, grads, want_grads):
            assert rel_l2(a, b) < 1e-5, (heads, name)
    alone = kda_kernels.scan_chunks(*[a[1:, 2:] for a in args],
                                    interpret=True)
    np.testing.assert_allclose(np.asarray(out[1:, 2:]), np.asarray(alone),
                               rtol=1e-6, atol=1e-6)
    # the carry matters: the last chunk run from a zero state is another result
    last = kda_kernels.scan_chunks(*[a[:, :, 2:] for a in args],
                                   interpret=True)
    assert rel_l2(last, out[:, :, 2:]) > 0.05


@pytest.mark.parametrize("shrink", [None, float(np.exp(-20.0 * 64))],
                         ids=["decays-drawn", "a-chunks-decay-underflows"])
def test_dS_is_zero_after_the_last_chunk_and_ds_is_exact(shrink):
    """No final state is returned, so nothing flows into the last chunk from
    beyond it: the step that *does* return its final state, with a zero
    cotangent for it, gives the same six cotangents, and the last chunk's
    ``ds`` is the plain sum of ``dS * S`` with ``dS = 0``: exactly zero. With
    ``g = -20`` a step a whole chunk's decay is ``e^-1280 = 0`` in float32:
    ``ds = sum_v dS+ * S`` does not pass through ``s`` and is the step's, not
    zero and not NaN, while nothing of ``dS+`` reaches the chunk before
    through ``s * dS+``."""
    args, cotangent = scan_inputs(3, 64, seed=6, shrink=shrink)
    (out, grads), _ = pair_and_step(args, cotangent, None)
    (want, _), vjp = jax.vjp(lambda *a: scan_by_jax_numpy(
        *a, final_state=True), *args)
    want_grads = vjp((cotangent, jnp.zeros((1, 2, 16, 8))))
    assert rel_l2(out, want) < 1e-5
    for name, a, b in zip(SCAN_NAMES, grads, want_grads):
        assert rel_l2(a, b) < 1e-5, name
    ds = np.asarray(grads[5])
    assert not ds[:, :, -1].any()
    assert ds[:, :, 1].any() and np.isfinite(ds).all()
    assert shrink is None or shrink == 0.0


def test_heads_a_program_follow_the_shapes_and_the_gauges_say_so():
    from distkeras_tpu import telemetry

    # the cell: 1 x 8 heads of 128 in chunks of 64, bfloat16 operands
    assert kda_kernels.heads_per_program(8, 64, 128, 128, 2) == 8
    assert kda_kernels.heads_per_program(6, 64, 128, 128, 2) == 2
    assert kda_kernels.heads_per_program(7, 64, 128, 128, 2) == 1
    # a state of 512 x 512 float32 is a MiB a head: fewer fit
    assert kda_kernels.heads_per_program(8, 64, 256, 256, 4) == 4
    assert kda_kernels.heads_per_program(8, 64, 512, 512, 4) == 2
    assert kda_kernels.heads_per_program(8, 64, 1024, 1024, 4) == 1
    args, _ = scan_inputs(5, 32, B=2, H=3)
    kda_kernels.scan_chunks(*args, interpret=True)
    assert telemetry.gauge("pallas.kda.heads_per_program").value == 2
    assert telemetry.gauge("pallas.kda.grid_steps").value == 3 * 5
    with pytest.raises(ValueError, match="do not divide"):
        kda_kernels.scan_chunks(*args, heads=4, interpret=True)


# -- the in-chunk half as a kernel pair ----------------------------------------

CHUNK_NAMES = "U W Qg Bq Kd s".split()
INPUT_NAMES = "q k v g beta".split()


def chunk_pair_and_form(C, heads, rounded=None):
    """A jitted ``(args, seed) -> (pair, form)``, each the six outputs and
    the five cotangents under ``jax.vjp`` with one set of drawn cotangents:
    of the kernel pair (operands in ``rounded`` where given) and of the kept
    ``jax.numpy`` in-chunk form in float32."""
    def run(args, key):
        q, k, v, g, beta = args
        given = args if rounded is None else (
            q.astype(rounded), k.astype(rounded), v.astype(rounded), g, beta)
        got, vjp = jax.vjp(lambda *a: kda_kernels.chunk_products(
            *a, C, min(SUB, C), heads=heads, interpret=True)[:6], *given)
        want, want_vjp = jax.vjp(lambda *a: in_chunk_by_jax_numpy(*a, C)[0],
                                 *args)
        cotangents = tuple(jax.random.normal(k, w.shape) for k, w in zip(
            jax.random.split(key, 6), want))
        return ((got, vjp(tuple(c.astype(o.dtype) for c, o in zip(
            cotangents, got)))), (want, want_vjp(cotangents)))
    return jax.jit(run)


@pytest.mark.parametrize("chunks, C, heads", [
    (1, 32, None), (2, 64, None), (5, 32, None), (5, 64, None), (2, 64, 1),
    (5, 32, 1)], ids=lambda x: "all-heads" if x is None else str(x))
def test_the_chunk_pair_is_the_in_chunk_form_and_its_derivative(chunks, C,
                                                                heads):
    """``B`` = 2, ``H`` = 3: programs span batch rows and a head is a lane
    slice of ``[B, L, H x d]``. All six outputs and each of the five
    cotangents, in its primal's dtype and shape, against ``jax.vjp`` of the
    ``jax.numpy`` form: float32 to 1e-5. The gauges say what was traced."""
    from distkeras_tpu import telemetry

    args = delta_inputs(chunks * C, B=2, H=3, seed=chunks)
    (got, grads), (want, want_grads) = chunk_pair_and_form(C, heads)(
        args, jax.random.key(1))
    for name, a, b in zip(CHUNK_NAMES, got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert rel_l2(a, b) < 1e-5, name
    for name, x, a, b in zip(INPUT_NAMES, args, grads, want_grads):
        assert a.shape == x.shape and a.dtype == x.dtype, name
        assert np.linalg.norm(b) > 0, name
        assert rel_l2(a, b) < 1e-5, name
    held = 3 if heads is None else heads
    assert telemetry.gauge("pallas.kda.chunk_heads_per_program").value == held
    assert telemetry.gauge("pallas.kda.grid_steps").value \
        == 2 * 3 // held * chunks


@pytest.mark.parametrize("dtype, passes", [(jnp.bfloat16, True),
                                           (jnp.float8_e4m3fn, False)],
                         ids=["bfloat16", "float8-must-fail"])
def test_the_chunk_pair_with_rounded_operands_against_the_float32_form(
        dtype, passes):
    """The trainer's dtype against the float32 form, the six outputs and the
    five cotangents under 2e-2; float8_e4m3 operands are the control that
    must not pass it."""
    args = delta_inputs(5 * 64, B=1, H=2, seed=2)
    (got, grads), (want, want_grads) = chunk_pair_and_form(
        64, None, rounded=dtype)(args, jax.random.key(3))
    errors = {}
    for name, a, b in (*zip(CHUNK_NAMES, got, want),
                       *zip(INPUT_NAMES, grads, want_grads)):
        wanted = jnp.float32 if name in ("U", "s", "g", "beta") else dtype
        assert a.dtype == wanted, name
        assert np.isfinite(np.asarray(a, np.float32)).all(), name
        errors[name] = rel_l2(a.astype(jnp.float32), b)
    assert (max(errors.values()) < 2e-2) == passes, errors


def test_the_chunk_pair_under_held_decays_and_keys_that_point_one_way():
    """One trace, five inputs of one shape. ``g`` held at -1.6, -20 and 0
    over whole chunks: every output and cotangent finite and the form's (a
    sub-chunk's summed decay of -320 underflows both factors of a pair to
    zero and nothing overflows). Keys that point one way: the kernel's
    inverse (substitution inside the 16 x 16 blocks, by blocks above them)
    gives the ``U`` and ``W`` of ``_unit_lower_inverse``."""
    both = chunk_pair_and_form(64, None)
    cases = {f"g={g}": delta_inputs(128, g=g) for g in (-1.6, -20.0, 0.0)}
    cases.update({f"alike={alike}": alike_inputs(alike, beta)
                  for alike, beta in ((0.9, 0.5), (1.0, 0.99))})
    for case, args in cases.items():
        (got, grads), (want, want_grads) = both(args, jax.random.key(4))
        for name, a, b in (*zip(CHUNK_NAMES, got, want),
                           *zip(INPUT_NAMES, grads, want_grads)):
            assert np.isfinite(np.asarray(a)).all(), (case, name)
            if case.startswith("alike") and name in INPUT_NAMES \
                    or case == "g=-20.0" and name == "g":
                # a system that ill conditioned, and a gradient that is
                # rounding at e^-20: finite is the claim
                continue
            assert rel_l2(a, b) < 1e-4, (case, name, rel_l2(a, b))
    # the smallest summed log-decay leaves the kernel beside the products
    last = kda_kernels.chunk_products(*cases["g=-1.6"], 64, SUB,
                                      interpret=True)[6]
    assert last.shape == (2, 2, 2, 16)
    np.testing.assert_allclose(np.asarray(last), -1.6 * 64, rtol=1e-5)


def test_chunk_heads_a_program_follow_the_shapes():
    # the cell: 8 heads of 128 in chunks of 64, bfloat16 operands
    assert kda_kernels.chunk_heads_per_program(8, 64, 128, 128, 2) == 8
    assert kda_kernels.chunk_heads_per_program(32, 64, 128, 128, 2) == 8
    assert kda_kernels.chunk_heads_per_program(6, 64, 128, 128, 2) == 6
    assert kda_kernels.chunk_heads_per_program(7, 64, 128, 128, 2) == 7
    # a program's heads are a lane slice of [B, L, H x d]: whole tiles of
    # 128 lanes, or the whole row
    assert kda_kernels.chunk_heads_per_program(12, 64, 64, 64, 2) == 6
    assert kda_kernels.chunk_heads_per_program(3, 32, 16, 8, 4) == 3
    assert kda_kernels.chunk_heads_per_program(16, 64, 16, 8, 4) == 16
    # wider heads: fewer fit beside their blocks, twice
    assert kda_kernels.chunk_heads_per_program(8, 64, 512, 512, 4) == 2
    with pytest.raises(ValueError, match="do not divide"):
        kda_kernels.chunk_products(*delta_inputs(64, B=1, H=3), 32, SUB,
                                   heads=2, interpret=True)


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
