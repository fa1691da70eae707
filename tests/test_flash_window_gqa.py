"""The flash kernels under a sliding window and with grouped queries (Pallas
interpreter, small grains): forward and all three gradients against dense
masked attention, the schedule's coverage of the band, and pins on what the
schedule of the benchmark's dense cell returns."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu import telemetry
from distkeras_tpu.ops.pallas import flash_attention
from distkeras_tpu.ops.pallas.flash_attention import (default_tiling,
                                                      tile_schedule,
                                                      visited_share)


def dense(q, k, v, window=None):
    """Masked softmax attention; query head n reads K/V head n // group."""
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    L = q.shape[1]
    i, j = jnp.arange(L)[:, None], jnp.arange(L)[None, :]
    seen = (j <= i) if window is None else (j <= i) & (i - j < window)
    s = jnp.where(seen[None, None], jnp.einsum("bqhd,bkhd->bhqk", q, k), -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


def _run(fn, q, k, v):
    out, vjp = jax.vjp(fn, q, k, v)
    return (out, *vjp(2.0 * out))


@pytest.mark.parametrize("L, heads, kv_heads, window, block, block_k", [
    (128, 4, 2, 64, 16, None),    # L > window, groups of 2, default tiling
    (256, 4, 2, 64, 16, None),    # four tiles: three with an edge, one without
    (128, 6, 2, 32, 16, 16),      # groups of 3, explicit square 16-tiles
    (256, 3, 1, 128, 16, None),   # one K/V head for all, cut < tile
    (128, 2, 2, 64, 16, None),    # a window without groups
    (128, 4, 2, None, 16, None),  # groups without a window
    (64, 4, 1, 128, 16, None),    # L <= window: the window changes nothing
    (64, 7, 1, 64, 16, None),     # L == window, the cell's group of 7
], ids=["win-gqa", "win-gqa-256", "win-gqa-16", "win-mqa", "win-mha", "gqa", "L-le-window",
        "L-eq-window"])
def test_windowed_grouped_flash_matches_dense(L, heads, kv_heads, window,
                                              block, block_k):
    rng = np.random.default_rng(L + heads)
    q = jnp.asarray(rng.normal(size=(2, L, heads, 16)), jnp.float32) * 0.25
    k, v = (jnp.asarray(rng.normal(size=(2, L, kv_heads, 16)), jnp.float32)
            for _ in range(2))
    got = _run(lambda q, k, v: flash_attention(
        q, k, v, block_size=block, block_k=block_k, interpret=True,
        window=window), q, k, v)
    ref = _run(lambda q, k, v: dense(q, k, v, window), q, k, v)
    # bf16 operands, f32 accumulation: 2**-9 a rounding, a few stages.
    for a, b, name in zip(got, ref, ("out", "dq", "dk", "dv")):
        err = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert err < 1e-2, f"{name}: relative L2 {err}"


@pytest.mark.parametrize("L, block, cut, window", [
    (128, 32, 16, 64), (128, 64, 32, 64), (256, 32, 32, 32),
    (8192, 512, 256, 4096)], ids=["128", "128-one-tile", "256", "cell"])
def test_schedule_covers_the_band_once_and_nothing_outside(L, block, cut,
                                                           window):
    """Per kernel: every (q, k) with 0 <= q - k < window lies in exactly one
    visited rectangle, no rectangle lies wholly outside the band, every one
    that straddles an edge of it is marked masked, and the visited share is
    the rectangles' area."""
    i, j = np.arange(L)[:, None], np.arange(L)[None, :]
    band = (j <= i) & (i - j < window)
    for name, tiles in tile_schedule(L, block, block, cut, window).items():
        hits = np.zeros((L, L), np.int8)
        area = 0
        for q0, q1, k0, k1, masked in tiles:
            hits[q0:q1, k0:k1] += 1
            area += (q1 - q0) * (k1 - k0)
            inside = band[q0:q1, k0:k1]
            assert inside.any(), f"{name}: {(q0, k0)} is wholly outside"
            assert masked or inside.all(), f"{name}: {(q0, k0)} unmasked"
        assert (hits[band] == 1).all() and hits.max() == 1, name
        assert visited_share(L, block, block, cut, window) == area / L ** 2
    if L == 8192:  # the exact band is 0.375; the tiling rounds up
        assert 0.375 < visited_share(L, block, block, cut, window) < 0.40
        assert visited_share(L, block, block, cut) == 0.515625


def test_default_tiling_follows_the_window():
    assert default_tiling(8192, 128) == (512, 512, 256)
    assert default_tiling(8192, 128, window=4096) == (512, 512, 256)
    # a short sequence's one whole tile would hold the window and skip none
    assert default_tiling(128, 16, 16) == (128, 128, 32)
    assert default_tiling(128, 16, 16, window=64) == (64, 64, 32)
    with pytest.raises(ValueError, match="window"):
        q = jnp.zeros((1, 128, 1, 16))
        flash_attention(q, q, q, block_size=16, block_k=64, interpret=True,
                        window=64)  # rectangular tiles take no window


def test_the_dense_cells_schedule_is_what_it_was():
    """`gpt2m_aeasgd_w1` runs L = 1024, d_head 64, no window, equal heads:
    its tiling and schedule are pinned to what PR 27 measured."""
    assert default_tiling(1024, 64) == (1024, 1024, 256)
    assert default_tiling(2048, 64) == (2048, 2048, 512)
    sched = tile_schedule(1024, 1024, 1024, 256)
    assert sched["dk_flash_fwd"] == [
        (0, 256, 0, 256, True),
        (256, 512, 0, 256, False), (256, 512, 256, 512, True),
        (512, 768, 0, 512, False), (512, 768, 512, 768, True),
        (768, 1024, 0, 768, False), (768, 1024, 768, 1024, True)]
    assert sched["dk_flash_dkv"] == [
        (0, 256, 0, 256, True), (256, 1024, 0, 256, False),
        (256, 512, 256, 512, True), (512, 1024, 256, 512, False),
        (512, 768, 512, 768, True), (768, 1024, 512, 768, False),
        (768, 1024, 768, 1024, True)]
    assert visited_share(1024, 1024, 1024, 256) == 0.625


def test_visited_share_gauge_is_set_per_call():
    q = jnp.zeros((1, 128, 2, 16))
    kv = jnp.zeros((1, 128, 1, 16))
    for window, want in ((None, visited_share(128, 128, 128, 32)),
                         (64, visited_share(128, 64, 64, 32, 64))):
        jax.jit(lambda q, k, v: flash_attention(
            q, k, v, block_size=16, interpret=True,
            window=window)).lower(q, kv, kv)
        assert telemetry.gauge("pallas.flash.visited_share").value == want


# The five paths of `_dkv_kernel`, as arguments of `flash_attention` on
# [1, L, heads, d] queries: (L, heads, kv_heads, d, block, block_k, window).
# dk and dv of each against dense attention: the cases `cell-1024-d64`,
# `looped-tiles-384`, `rectangular-16x64` of test_flash_attention.py, and
# `win-gqa-256`, `gqa` above.
DKV_PATHS = {
    "one-tile-cuts-1024x64": (1024, 1, 1, 64, 128, None, None),
    "looped-square-tiles": (384, 1, 1, 16, 16, None, None),
    "rectangular": (128, 1, 1, 16, 16, 64, None),
    "window-group": (256, 4, 2, 16, 16, None, 64),
    "group": (128, 4, 2, 16, 16, None, None),
}


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold (loop
    bodies, branches, inlined functions, a kernel's body)."""
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)


def _transposing_steps(jaxpr) -> list:
    """What a key-major step must not hold: a product that contracts over
    dimension 0 of its left operand (Mosaic transposes that tile; a plain
    ``a @ b`` contracts over the last of ``a`` and the first of ``b``), a
    value of shape [n, 1] (a lane vector stood up as a sublane column), or a
    read of a row-statistics block ([1, nq, 1, block]) in another form than
    its [1, Q] lanes."""
    found = []
    for e in _eqns(jaxpr):
        if e.primitive.name == "dot_general":
            (lhs, rhs), _ = e.params["dimension_numbers"]
            if 0 in lhs:
                found.append(f"dot_general contracting {lhs} x {rhs}")
        for out in e.outvars:
            shape = getattr(out.aval, "shape", ())
            if len(shape) == 2 and shape[1] == 1 and shape[0] > 1:
                found.append(f"{e.primitive.name} -> {shape}")
        if e.primitive.name == "get" and e.invars[0].aval.ndim == 4:
            shape = e.outvars[0].aval.shape
            if len(shape) != 2 or shape[0] != 1:
                found.append(f"row statistics read as {shape}")
    return found


def _kernel_jaxpr(name, L, heads, kv_heads, d, block, block_k, window):
    q = jnp.zeros((1, L, heads, d))
    kv = jnp.zeros((1, L, kv_heads, d))

    def grads(q, k, v):
        out, vjp = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, block_size=block, block_k=block_k, interpret=True,
            window=window), q, k, v)
        return vjp(out)

    calls = [e for e in _eqns(jax.make_jaxpr(grads)(q, kv, kv).jaxpr)
             if e.primitive.name == "pallas_call"
             and e.params["name"] == name]
    assert len(calls) == 1
    return calls[0].params["jaxpr"]


@pytest.mark.parametrize("path", list(DKV_PATHS))
def test_dkv_step_is_key_major_on_every_path(path):
    """No product of `dk_flash_dkv` contracts over dimension 0 of its left
    operand, and lse / delta are read as the [1, Q] lane vectors they are
    stored as: nothing in the step is transposed or stood up as a column."""
    jaxpr = _kernel_jaxpr("dk_flash_dkv", *DKV_PATHS[path])
    products = [e for e in _eqns(jaxpr) if e.primitive.name == "dot_general"]
    assert len(products) >= 4 and len(products) % 4 == 0
    assert _transposing_steps(jaxpr) == []


def test_the_walk_finds_what_it_looks_for():
    """The query-major step that left (`p.T @ do` as a contraction over
    dimension 0 of both, `lse[:, None]`) is found by the same walk; so are
    dq's columns, which are that kernel's own space."""
    def old_step(p, do, lse):
        return jax.lax.dot_general(p - lse[:, None], do,
                                   (((0,), (0,)), ((), ())))

    found = _transposing_steps(jax.make_jaxpr(old_step)(
        jnp.zeros((32, 16)), jnp.zeros((32, 8)), jnp.zeros((32,))).jaxpr)
    assert any("dot_general" in f for f in found)
    assert any("(32, 1)" in f for f in found)
    dq = _kernel_jaxpr("dk_flash_dq", *DKV_PATHS["group"])
    assert any("(128, 1)" in f for f in _transposing_steps(dq))


@pytest.mark.parametrize(
    "L, d, window, tiling, share, rects, masked, by_q, by_k", [
        (1024, 64, None, (1024, 1024, 256), 0.625, 7, 4,
         "beaa4d972e91fa68", "a3b7773450e829d5"),
        (8192, 128, 4096, (512, 512, 256), 0.3984375, 156, 48,
         "5f34bdce27b71214", "6954507ead9473c9"),
        (8192, 128, None, (512, 512, 256), 0.515625, 168, 32,
         "52b92b9218b2b95b", "087671ae58192aff"),
        (8192, 64, None, (512, 512, 256), 0.515625, 168, 32,
         "52b92b9218b2b95b", "087671ae58192aff"),
    ], ids=["gpt2m", "smallthinker-window", "smallthinker-full", "lfm2"])
def test_the_cells_schedules_are_what_they_were(L, d, window, tiling, share,
                                                rects, masked, by_q, by_k):
    """The key-major step (PR 33) visits the rectangles the query-major one
    did: tiling, visited share and the schedule itself (its digest, taken on
    PR 32's tree) at the tilings of the benchmark's three LM cells."""
    assert default_tiling(L, d, 128, window) == tiling
    assert visited_share(L, *tiling, window) == share
    sched = tile_schedule(L, *tiling, window)
    assert sched["dk_flash_dq"] == sched["dk_flash_fwd"]
    for name, digest in (("dk_flash_fwd", by_q), ("dk_flash_dkv", by_k)):
        assert len(sched[name]) == rects
        assert sum(m for *_, m in sched[name]) == masked
        assert sum((q1 - q0) * (k1 - k0)
                   for q0, q1, k0, k1, _ in sched[name]) == share * L * L
        assert hashlib.sha1(repr(sched[name]).encode()).hexdigest()[
            :16] == digest
