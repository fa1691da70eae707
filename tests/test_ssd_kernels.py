"""Mamba-2's in-chunk kernel pair (``ops/pallas/delta_rule.py``:
``dk_ssd_chunk_fwd`` / ``dk_ssd_chunk_bwd``, interpreted) on the CPU in
float32: ``C`` and ``B`` at the groups, ``dt x`` at the heads and one decay a
head, against the kept ``jax.numpy`` in-chunk form
(``ops/delta_rule.py::ssd_in_chunk``) fed the same inputs, the five outputs,
the summed log-decay of each chunk and the four cotangents (``q, k``'s at the
groups) under ``jax.vjp`` with random cotangents on every output; across
groups of one, two, three and eight heads, values of 64 and 128, chunks of
16, 32 and 64, batch rows and programs of one group or several; under decays
held at -1.6 and -20 a step over whole chunks; with bfloat16 operands against
a float8 control; how many heads a program holds; and which pair
``chunked_ssd`` takes (kernels by name in the lowered text, and the counter
``pallas.ssd.chunk_calls``, which the delta rule leaves alone). The pair
compiled for a described v5e at the cell's shape is in
``tests/test_pallas_rows.py``, which describes the topology."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from distkeras_tpu import telemetry  # noqa: E402
from distkeras_tpu.ops.delta_rule import (  # noqa: E402
    chunked_gated_delta_rule, chunked_ssd, ssd_in_chunk)
from distkeras_tpu.ops.pallas import delta_rule as kernels  # noqa: E402

OUTPUTS = "U Qg Bq Kd s".split()
INPUTS = "q k v g".split()


def rel_l2(a, b):
    a, b = np.asarray(a, np.float64).ravel(), np.asarray(b, np.float64).ravel()
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))


def ssd_inputs(length, B=1, G=2, H=4, K=16, V=64, g=None, seed=0):
    """``C`` and ``B`` at the groups scaled by ``K^-1/2``, values at the
    heads, one decay a head from ``e^-6`` to ``e^0.5`` a step (or held at
    ``g``)."""
    ks = jax.random.split(jax.random.key(seed), 4)
    decay = -jnp.exp(jax.random.uniform(ks[3], (B, length, H), minval=-6,
                                        maxval=0.5)) if g is None \
        else jnp.full((B, length, H), g)
    return (jax.random.normal(ks[0], (B, length, G, K)) * K ** -0.5,
            jax.random.normal(ks[1], (B, length, G, K)) * K ** -0.5,
            jax.random.normal(ks[2], (B, length, H, V)), decay)


def pair_and_form(C, heads=None, rounded=None):
    """A jitted ``(args, key) -> (pair, form)``, each the five outputs, the
    summed log-decay and the four cotangents under ``jax.vjp`` with one set
    of drawn cotangents: of the kernel pair (operands in ``rounded`` where
    given) and of the ``jax.numpy`` form in float32."""
    def run(args, key):
        q, k, v, g = args
        given = args if rounded is None else (
            q.astype(rounded), k.astype(rounded), v.astype(rounded), g)
        got, vjp, last = jax.vjp(lambda *a: kernels.ssd_chunk_products(
            *a, C, heads=heads, interpret=True), *given, has_aux=True)
        want, want_vjp, want_last = jax.vjp(
            lambda *a: ssd_in_chunk(*a, C), *args, has_aux=True)
        cotangents = tuple(jax.random.normal(k, w.shape) for k, w in zip(
            jax.random.split(key, 5), want))
        return ((got, last, vjp(tuple(c.astype(o.dtype) for c, o in zip(
            cotangents, got)))), (want, want_last, want_vjp(cotangents)))
    return jax.jit(run)


@pytest.mark.parametrize("shape, chunks, C, heads, held", [
    (dict(G=2, H=2, V=64), 4, 16, None, 2),          # groups of one head
    (dict(G=4, H=4, V=128), 3, 32, None, 4),         # groups of one, d_v 128
    (dict(B=2, G=2, H=4, V=64), 2, 64, None, 4),     # groups of two, batch
    (dict(B=2, G=2, H=4, V=128), 3, 32, 2, 2),       # one group a program
    (dict(G=1, H=8, K=32, V=64), 2, 64, None, 8),    # a group of eight
    (dict(G=1, H=8, K=16, V=128), 4, 16, None, 8),   # eight, d_v 128
    (dict(G=1, H=3, V=64), 3, 32, None, 3),          # an odd head
], ids=["group1-C16", "group1-dv128-C32", "group2-C64", "group2-heads2-C32",
        "group8-C64", "group8-dv128-C16", "group3-C32"])
def test_the_ssd_pair_is_the_jax_numpy_form_and_its_derivative(shape, chunks,
                                                                C, heads,
                                                                held):
    """All five outputs, the summed log-decay and each of the four
    cotangents, in its primal's dtype and shape (``q, k``'s at the groups),
    against ``jax.vjp`` of ``ssd_in_chunk``: float32 to 1e-5. The gauge says
    what was traced."""
    args = ssd_inputs(chunks * C, seed=chunks, **shape)
    (got, last, grads), (want, want_last, want_grads) = pair_and_form(
        C, heads)(args, jax.random.key(1))
    for name, a, b in zip(OUTPUTS, got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert rel_l2(a, b) < 1e-5, (name, rel_l2(a, b))
    assert last.shape == want_last.shape
    assert rel_l2(last, want_last) < 1e-5
    for name, x, a, b in zip(INPUTS, args, grads, want_grads):
        assert a.shape == x.shape and a.dtype == x.dtype, name
        assert np.linalg.norm(b) > 0, name
        assert rel_l2(a, b) < 1e-5, (name, rel_l2(a, b))
    assert telemetry.gauge("pallas.ssd.chunk_heads_per_program").value \
        == held


def test_the_ssd_pair_under_held_decays():
    """One trace, three inputs of one shape: ``g`` held at -1.6 (a chunk's
    summed decay -102, past the 88 at which ``e^-G`` leaves float32), -20
    and 0 over whole chunks. Every output and cotangent finite and the
    form's; at e^-20 ``g``'s cotangent is rounding, and finite is the
    claim. The summed log-decay of each chunk leaves beside the products."""
    both = pair_and_form(64)
    cases = {g: ssd_inputs(128, B=2, g=g) for g in (-1.6, -20.0, 0.0)}
    for g, args in cases.items():
        (got, _, grads), (want, _, want_grads) = both(args, jax.random.key(4))
        for name, a, b in (*zip(OUTPUTS, got, want),
                           *zip(INPUTS, grads, want_grads)):
            assert np.isfinite(np.asarray(a)).all(), (g, name)
            if g == -20.0 and name == "g":
                continue
            assert rel_l2(a, b) < 1e-4, (g, name, rel_l2(a, b))
    last = kernels.ssd_chunk_products(*cases[-1.6], 64, interpret=True)[1]
    assert last.shape == (2, 4, 2)
    np.testing.assert_allclose(np.asarray(last), -1.6 * 64, rtol=1e-5)


@pytest.mark.parametrize("dtype, passes", [(jnp.bfloat16, True),
                                           (jnp.float8_e4m3fn, False)],
                         ids=["bfloat16", "float8-must-fail"])
def test_the_ssd_pair_with_rounded_operands_against_the_float32_form(
        dtype, passes):
    """The trainer's dtype against the float32 form, the five outputs and
    the four cotangents under 2e-2; float8_e4m3 operands are the control
    that must not pass it. ``U`` is written in ``v``'s dtype: it *is*
    ``v``."""
    args = ssd_inputs(5 * 64, G=1, H=8, K=32, seed=2)
    (got, _, grads), (want, _, want_grads) = pair_and_form(
        64, rounded=dtype)(args, jax.random.key(3))
    errors = {}
    for name, a, b in (*zip(OUTPUTS, got, want),
                       *zip(INPUTS, grads, want_grads)):
        wanted = jnp.float32 if name in ("s", "g") else dtype
        assert a.dtype == wanted, name
        assert np.isfinite(np.asarray(a, np.float32)).all(), name
        errors[name] = rel_l2(a.astype(jnp.float32), b)
    assert (max(errors.values()) < 2e-2) == passes, errors


def test_ssd_heads_a_program_follow_the_shapes():
    # the cell: 32 heads of 64 over 4 groups of 128, chunks of 64
    assert kernels.ssd_chunk_heads_per_program(32, 4, 64, 128, 64, 2) == 8
    # whole groups, whole lane tiles at both widths, or the whole row
    assert kernels.ssd_chunk_heads_per_program(4, 2, 32, 16, 8, 4) == 4
    assert kernels.ssd_chunk_heads_per_program(3, 1, 32, 16, 64, 4) == 3
    assert kernels.ssd_chunk_heads_per_program(16, 16, 64, 128, 128, 2) == 8
    assert kernels.ssd_chunk_heads_per_program(16, 8, 64, 128, 64, 2) == 8
    # a group wider than eight heads is one program
    assert kernels.ssd_chunk_heads_per_program(16, 1, 64, 128, 64, 2) == 16
    # chunks a program: whole tiles of 128 positions, else the whole row
    assert kernels.ssd_chunks_per_program(128, 64) == 2
    assert kernels.ssd_chunks_per_program(64, 128) == 1
    assert kernels.ssd_chunks_per_program(6, 32) == 6
    assert kernels.ssd_chunks_per_program(8, 32) == 4
    with pytest.raises(ValueError, match="whole"):
        kernels.ssd_chunk_products(*ssd_inputs(64), 32, heads=1,
                                   interpret=True)
    q, k, v, g = ssd_inputs(64, H=3)
    with pytest.raises(ValueError, match="cannot serve"):
        kernels.ssd_chunk_products(q, k, v, g, 32, interpret=True)


# -- which pair the state-space form takes ------------------------------------

def _lowered_kernels(fn, args):
    """The in-chunk and scan kernels, by name, in the debug text of the
    lowered value and gradient of ``fn``'s first output, and how far
    ``pallas.ssd.chunk_calls`` rose as it was traced."""
    before = telemetry.counter("pallas.ssd.chunk_calls").value
    text = jax.jit(jax.grad(
        lambda *a: jnp.sum(fn(*a)[0]),
        argnums=tuple(range(len(args))))).lower(*args).as_text(
            debug_info=True)
    return (set(re.findall(r"dk_(?:ssd|gdn|kda)_(?:chunk|scan)_(?:fwd|bwd)",
                           text)),
            telemetry.counter("pallas.ssd.chunk_calls").value - before)


def test_chunked_ssd_takes_the_ssd_pair_and_counts_it():
    """``chunked_ssd``: ``dk_ssd_chunk_*`` beside the scan and no other
    in-chunk pair, and the counter rises as the pair is traced."""
    names, counted = _lowered_kernels(chunked_ssd, ssd_inputs(64))
    assert names == {"dk_ssd_chunk_fwd", "dk_ssd_chunk_bwd",
                     "dk_kda_scan_fwd", "dk_kda_scan_bwd"}, names
    assert counted >= 1


def test_the_delta_rule_leaves_the_ssd_counter():
    """``chunked_gated_delta_rule`` with a decay a head (Gated DeltaNet's
    shape): ``dk_gdn_chunk_*``, no ``dk_ssd_chunk_*``, the counter unmoved."""
    q, k, v, g = ssd_inputs(64, K=16, V=16)
    beta = jax.nn.sigmoid(v[..., 0])
    names, counted = _lowered_kernels(chunked_gated_delta_rule,
                                      (q / jnp.linalg.norm(
                                          q, axis=-1, keepdims=True), k, v, g,
                                       beta))
    assert names == {"dk_gdn_chunk_fwd", "dk_gdn_chunk_bwd",
                     "dk_kda_scan_fwd", "dk_kda_scan_bwd"}, names
    assert counted == 0
