"""Gated DeltaNet's in-chunk kernel pair (``ops/pallas/delta_rule.py``:
``dk_gdn_chunk_fwd`` / ``dk_gdn_chunk_bwd``, interpreted) on the CPU in
float32: one decay a value head and ``q, k`` at the key heads, against the
kept ``jax.numpy`` in-chunk form fed the same inputs widened (``q, k``
repeated to the value heads, the decay broadcast over the key channels),
all six outputs and the five cotangents (``q, k``'s at the key heads, the
form's summed over each group by the repeat's own derivative); across batch
rows, programs that hold more than one key head, groups of one, two and four,
an odd number of heads (a head paired with itself); under decays held at
-1.6 and -20 a step over whole chunks; with bfloat16 operands against a
float8 control; how many value heads a program holds; and which pair the
rank of the decay picks (kernels by name in the lowered text, and the
counter ``pallas.gdn.chunk_calls``). The pair compiled for a described v5e
at the cell's shape is in ``tests/test_pallas_rows.py``, which describes the
topology."""

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
    chunked_gated_delta_rule, in_chunk_by_jax_numpy)
from distkeras_tpu.ops.pallas import delta_rule as kernels  # noqa: E402

OUTPUTS = "U W Qg Bq Kd s".split()
INPUTS = "q k v g beta".split()


def rel_l2(a, b):
    a, b = np.asarray(a, np.float64).ravel(), np.asarray(b, np.float64).ravel()
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))


def gdn_inputs(length, B=2, Hk=2, Hv=4, K=16, V=8, g=None, seed=0):
    """Unit keys and scaled unit queries at the key heads, values at the
    value heads, one decay a value head from ``e^-6`` to ``e^0.5`` a step
    (or held at ``g``), beta in (0, 1)."""
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


def widened_form(C):
    """The ``jax.numpy`` in-chunk form on ``q, k`` repeated to the value
    heads and the decay broadcast over the key channels."""
    def form(q, k, v, g, beta):
        group = v.shape[2] // k.shape[2]
        return in_chunk_by_jax_numpy(
            jnp.repeat(q, group, 2), jnp.repeat(k, group, 2), v,
            jnp.broadcast_to(g[..., None], v.shape[:3] + k.shape[-1:]), beta,
            C)[0]
    return form


def pair_and_form(C, heads=None, rounded=None):
    """A jitted ``(args, key) -> (pair, form)``, each the six outputs and the
    five cotangents under ``jax.vjp`` with one set of drawn cotangents: of
    the kernel pair (operands in ``rounded`` where given) and of the widened
    form in float32."""
    def run(args, key):
        q, k, v, g, beta = args
        given = args if rounded is None else (
            q.astype(rounded), k.astype(rounded), v.astype(rounded), g, beta)
        got, vjp = jax.vjp(lambda *a: kernels.gdn_chunk_products(
            *a, C, heads=heads, interpret=True)[:6], *given)
        want, want_vjp = jax.vjp(widened_form(C), *args)
        cotangents = tuple(jax.random.normal(k, w.shape) for k, w in zip(
            jax.random.split(key, 6), want))
        return ((got, vjp(tuple(c.astype(o.dtype) for c, o in zip(
            cotangents, got)))), (want, want_vjp(cotangents)))
    return jax.jit(run)


@pytest.mark.parametrize("shape, chunks, C, heads, held", [
    (dict(B=2, Hk=2, Hv=4), 2, 64, None, 4),   # two key heads a program
    (dict(B=2, Hk=2, Hv=4), 2, 64, 2, 2),      # one key head a program
    (dict(B=1, Hk=1, Hv=4, V=16), 3, 32, None, 4),  # a group of four
    (dict(B=2, Hk=3, Hv=3), 3, 32, None, 3),   # Hv == Hk, an odd head
], ids=["Hk2-Hv4", "Hk2-Hv4-heads2", "Hk1-Hv4", "Hk3-Hv3"])
def test_the_gdn_pair_is_the_widened_form_and_its_derivative(shape, chunks, C,
                                                             heads, held):
    """All six outputs and each of the five cotangents, in its primal's
    dtype and shape (``q, k``'s at the key heads), against ``jax.vjp`` of
    the widened form: float32 to 1e-5. The gauge says what was traced."""
    args = gdn_inputs(chunks * C, seed=chunks, **shape)
    (got, grads), (want, want_grads) = pair_and_form(C, heads)(
        args, jax.random.key(1))
    for name, a, b in zip(OUTPUTS, got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert rel_l2(a, b) < 1e-5, (name, rel_l2(a, b))
    for name, x, a, b in zip(INPUTS, args, grads, want_grads):
        assert a.shape == x.shape and a.dtype == x.dtype, name
        assert np.linalg.norm(b) > 0, name
        assert rel_l2(a, b) < 1e-5, (name, rel_l2(a, b))
    assert telemetry.gauge("pallas.gdn.chunk_heads_per_program").value \
        == held


def test_the_gdn_pair_under_held_decays():
    """One trace, three inputs of one shape: ``g`` held at -1.6 (a chunk's
    summed decay -102, past the 88 at which ``e^-G`` leaves float32), -20
    and 0 over whole chunks. Every output and cotangent finite and the
    form's; at e^-20 ``g``'s cotangent is rounding, and finite is the claim.
    The summed log-decay of each chunk leaves beside the products."""
    both = pair_and_form(64)
    cases = {g: gdn_inputs(128, g=g) for g in (-1.6, -20.0, 0.0)}
    for g, args in cases.items():
        (got, grads), (want, want_grads) = both(args, jax.random.key(4))
        for name, a, b in (*zip(OUTPUTS, got, want),
                           *zip(INPUTS, grads, want_grads)):
            assert np.isfinite(np.asarray(a)).all(), (g, name)
            if g == -20.0 and name == "g":
                continue
            assert rel_l2(a, b) < 1e-4, (g, name, rel_l2(a, b))
    last = kernels.gdn_chunk_products(*cases[-1.6], 64, interpret=True)[6]
    assert last.shape == (2, 4, 2)
    np.testing.assert_allclose(np.asarray(last), -1.6 * 64, rtol=1e-5)


@pytest.mark.parametrize("dtype, passes", [(jnp.bfloat16, True),
                                           (jnp.float8_e4m3fn, False)],
                         ids=["bfloat16", "float8-must-fail"])
def test_the_gdn_pair_with_rounded_operands_against_the_float32_form(
        dtype, passes):
    """The trainer's dtype against the float32 form, the six outputs and the
    five cotangents under 2e-2; float8_e4m3 operands are the control that
    must not pass it."""
    args = gdn_inputs(5 * 64, B=1, seed=2)
    (got, grads), (want, want_grads) = pair_and_form(64, rounded=dtype)(
        args, jax.random.key(3))
    errors = {}
    for name, a, b in (*zip(OUTPUTS, got, want),
                       *zip(INPUTS, grads, want_grads)):
        wanted = jnp.float32 if name in ("U", "s", "g", "beta") else dtype
        assert a.dtype == wanted, name
        assert np.isfinite(np.asarray(a, np.float32)).all(), name
        errors[name] = rel_l2(a.astype(jnp.float32), b)
    assert (max(errors.values()) < 2e-2) == passes, errors


def test_gdn_heads_a_program_follow_the_shapes():
    # the cell: 32 value heads over 16 key heads of 128, chunks of 64
    assert kernels.gdn_chunk_heads_per_program(32, 16, 64, 128, 128, 2) == 8
    # whole groups, whole lane tiles at both widths, or the whole row
    assert kernels.gdn_chunk_heads_per_program(4, 2, 64, 16, 16, 4) == 4
    assert kernels.gdn_chunk_heads_per_program(3, 3, 32, 16, 8, 4) == 3
    assert kernels.gdn_chunk_heads_per_program(8, 8, 64, 128, 128, 2) == 8
    # a group wider than eight heads is one program
    assert kernels.gdn_chunk_heads_per_program(16, 1, 64, 128, 128, 2) == 16
    with pytest.raises(ValueError, match="whole"):
        kernels.gdn_chunk_products(*gdn_inputs(64, B=1), 32, heads=1,
                                   interpret=True)
    q, k, v, g, beta = gdn_inputs(64, B=1, Hv=3)
    with pytest.raises(ValueError, match="cannot serve"):
        kernels.gdn_chunk_products(q, k, v, g, beta, 32, interpret=True)


# -- which pair the decay's rank picks ----------------------------------------

def _lowered_kernels(args):
    """The in-chunk and scan kernels, by name, in the debug text of the
    lowered value and gradient of ``chunked_gated_delta_rule``, and how far
    ``pallas.gdn.chunk_calls`` rose as it was traced."""
    before = telemetry.counter("pallas.gdn.chunk_calls").value
    text = jax.jit(jax.grad(
        lambda *a: jnp.sum(chunked_gated_delta_rule(*a)[0]),
        argnums=(0, 1, 2, 3, 4))).lower(*args).as_text(debug_info=True)
    return (set(re.findall(r"dk_(?:gdn|kda)_(?:chunk|scan)_(?:fwd|bwd)", text)),
            telemetry.counter("pallas.gdn.chunk_calls").value - before)


def test_a_decay_a_head_takes_the_gdn_pair_and_counts_it():
    """``g`` [B, L, Hv] with ``q, k`` at two key heads of four value heads:
    ``dk_gdn_chunk_*`` beside the scan, no ``dk_kda_chunk_*``, and the
    counter rises as the pair is traced."""
    names, counted = _lowered_kernels(gdn_inputs(64))
    assert names == {"dk_gdn_chunk_fwd", "dk_gdn_chunk_bwd",
                     "dk_kda_scan_fwd", "dk_kda_scan_bwd"}, names
    assert counted >= 1


def test_a_decay_a_channel_takes_the_kda_pair_and_leaves_the_counter():
    """``g`` [B, L, H, d_k] (Kimi Delta Attention's): ``dk_kda_chunk_*`` as
    before this pair existed, no ``dk_gdn_chunk_*``, the counter unmoved."""
    q, k, v, g, beta = gdn_inputs(64, Hv=2)
    g = jnp.broadcast_to(g[..., None], g.shape + (16,)) * jnp.linspace(
        0.5, 1.5, 16)
    names, counted = _lowered_kernels((q, k, v, g, beta))
    assert names == {"dk_kda_chunk_fwd", "dk_kda_chunk_bwd",
                     "dk_kda_scan_fwd", "dk_kda_scan_bwd"}, names
    assert counted == 0
