"""The flash kernels under a sliding window and with grouped queries (Pallas
interpreter, small grains): forward and all three gradients against dense
masked attention, the schedule's coverage of the band, and pins on what the
schedule of the benchmark's dense cell returns."""

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
    (128, 6, 2, 32, 16, 16),      # groups of 3, explicit square 16-tiles
    (256, 3, 1, 128, 16, None),   # one K/V head for all, cut < tile
    (128, 2, 2, 64, 16, None),    # a window without groups
    (128, 4, 2, None, 16, None),  # groups without a window
    (64, 4, 1, 128, 16, None),    # L <= window: the window changes nothing
    (64, 7, 1, 64, 16, None),     # L == window, the cell's group of 7
], ids=["win-gqa", "win-gqa-16", "win-mqa", "win-mha", "gqa", "L-le-window",
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
