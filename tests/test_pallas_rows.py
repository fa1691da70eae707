"""The row kernels of the expert layer (``ops/pallas/rows.py``) under the
Pallas interpreter at small tiles: the gather against ``x[token]`` and the
combine against a plain segment sum at every kind of ``live``, each as the
other's transpose, a poisoned buffer under ``DroplessExperts``, the tile
rule, and the kernels compiled for a described v5e at the benchmark's
shapes (with them the flash kernels at two cells' head shapes and the delta
rule's scan kernels: one file describes the topology)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.models import blocks
from distkeras_tpu.models.base import ROUND_COUNTERS
from distkeras_tpu.ops.pallas import rows

T, K, D, TILE = 40, 3, 24, 16  # a width that is no multiple of 128
N = T * K
#: nothing live, one row, one under a tile's edge, the edge, one over, all
LIVES = [0, 1, TILE - 1, TILE, TILE + 1, N]
DTYPES = [jnp.float32, jnp.bfloat16]


def routing(seed=0, tokens=T, k=K):
    """A sorted order over ``tokens x k`` assignments in which a token's
    rows lie apart and several of them are live at once: ``token[r]``,
    ``slot[t, j]`` (its inverse)."""
    rng = np.random.default_rng(seed)
    clump = 4 * k  # the first rows are all the rows of four tokens
    order = np.concatenate([rng.permutation(clump),
                            clump + rng.permutation(tokens * k - clump)])
    slot = np.empty(tokens * k, np.int64)
    slot[order] = np.arange(tokens * k)
    return (jnp.asarray(order // k, jnp.int32),
            jnp.asarray(slot.reshape(tokens, k), jnp.int32))


def sorted_order(seed=0, tokens=T, k=K):
    """The same routing as the layer hands it on: ``order[r] = t * k + j``."""
    token, slot = routing(seed, tokens, k)
    order = np.empty(tokens * k, np.int32)
    order[np.asarray(slot).reshape(-1)] = np.arange(tokens * k)
    assert (order // k == np.asarray(token)).all()
    return jnp.asarray(order), slot


def values(shape, dtype, seed=1):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape), dtype)


def plain_combine(buffer, token, live, tokens=T):
    part = jnp.where(jnp.arange(buffer.shape[0])[:, None] < live,
                     buffer.astype(jnp.float32), 0)
    return jax.ops.segment_sum(part, token, tokens)


@pytest.mark.parametrize("dtype", DTYPES, ids=["float32", "bfloat16"])
@pytest.mark.parametrize("live", LIVES)
def test_gather_is_x_of_token_below_live(live, dtype):
    token, _ = routing()
    x = values((T, D), dtype)
    out = np.asarray(rows.gather(x, token, jnp.int32(live), tile=TILE)
                     .astype(jnp.float32))
    want = np.asarray(x.astype(jnp.float32))[np.asarray(token)]
    np.testing.assert_array_equal(out[:live], want[:live])
    # the tile that `live` cuts is zeros from there on; later tiles are
    # nobody's business
    edge = max(-(-live // TILE), 1) * TILE
    assert not out[live:edge].any()


@pytest.mark.parametrize("dtype", DTYPES, ids=["float32", "bfloat16"])
@pytest.mark.parametrize("live", LIVES)
def test_combine_is_the_segment_sum_of_the_live_rows(live, dtype):
    token, slot = routing()
    buffer = values((N, D), dtype).at[live:].set(jnp.nan)  # never read
    out = rows.combine(buffer, slot, jnp.int32(live), tile=TILE)
    assert out.dtype == jnp.float32 and out.shape == (T, D)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(plain_combine(buffer, token, live)),
                               rtol=1e-6, atol=1e-6)
    if 2 < live:  # several rows of one token are live at once
        assert np.bincount(np.asarray(token[:live])).max() > 1
    again = rows.combine(buffer, slot, jnp.int32(live), tile=TILE)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(again))


@pytest.mark.parametrize("width", [7, 130])
def test_an_odd_width_and_the_default_tile(width):
    """bfloat16 rows of an odd width are padded to whole words inside; the
    default tile makes one program of the tests' sizes."""
    token, slot = routing(3)
    x, buffer = values((T, width), jnp.bfloat16), values((N, width),
                                                         jnp.bfloat16, 2)
    live = 50
    got = rows.gather(x, token, live)
    np.testing.assert_array_equal(np.asarray(got[:live].astype(jnp.float32)),
                                  np.asarray(x[token[:live]]
                                             .astype(jnp.float32)))
    np.testing.assert_allclose(
        np.asarray(rows.combine(buffer, slot, live)),
        np.asarray(plain_combine(buffer, token, live)), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("dtype", DTYPES, ids=["float32", "bfloat16"])
def test_weights_and_scales_meet_the_rows_in_the_kernels(dtype):
    """``combine(rows, weights)`` is the segment sum of the weighted rows
    (weights rounded to the rows' dtype, the products then exact), and
    ``gather(x, scale)`` the scaled rows, rounded once."""
    token, slot = routing(8)
    live = 2 * TILE + 3
    weights = jnp.asarray(np.random.default_rng(9).random((T, K)), jnp.float32)
    buffer = values((N, D), dtype).at[live:].set(jnp.nan)
    rounded = weights.astype(dtype).astype(jnp.float32)
    by_row = jnp.zeros(N).at[slot.reshape(-1)].set(rounded.reshape(-1))
    want = plain_combine(buffer.astype(jnp.float32) * by_row[:, None], token,
                         live)
    got = rows.combine(buffer, slot, live, weights, tile=TILE)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6,
                               atol=1e-6)
    x, scale = values((T, D), dtype, 3), jnp.asarray(
        np.random.default_rng(10).random(N), jnp.float32)
    got = rows.gather(x, token, live, scale, tile=TILE)[:live]
    want = (x[token[:live]].astype(jnp.float32)
            * scale[:live, None]).astype(dtype)
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.float32)))


@pytest.mark.parametrize("live", [TILE + 1, N])
def test_each_is_the_others_transpose(live):
    """<gather(x), y> = <x, combine(y)> over the live rows, and the same
    through ``jax.grad`` of the layer's two functions, the weights'
    gradient with them."""
    token, slot = routing(4)
    order, _ = sorted_order(4)
    x, y = values((T, D), jnp.float32), values((N, D), jnp.float32, 5)
    alive = jnp.arange(N)[:, None] < live
    gathered = jnp.where(alive, rows.gather(x, token, live, tile=TILE), 0)
    left = float(jnp.sum(gathered * jnp.where(alive, y, 0)))
    right = float(jnp.sum(x * rows.combine(y, slot, live, tile=TILE)))
    assert abs(left - right) < 1e-4 * abs(left)
    live = jnp.int32(live)
    # d/dx <rows_of_tokens(x), y> = combine(y)
    dx = jax.grad(lambda x: jnp.sum(jnp.where(
        alive, blocks.rows_of_tokens(x, order, slot, live, K) * y, 0)))(x)
    np.testing.assert_allclose(np.asarray(dx),
                               np.asarray(plain_combine(y, token, live)),
                               rtol=1e-5, atol=1e-5)
    # <x, tokens_from_rows(y, w)> = sum over live (t, j) of w[t, j] <x[t],
    # y[slot[t, j]]>: d/dy is the scaled gather on the live rows, d/dw the
    # dot products, nothing for a dead assignment
    weights = jnp.asarray(np.random.default_rng(11).random((T, K)),
                          jnp.float32)
    dy, dw = jax.grad(lambda y, w: jnp.sum(blocks.tokens_from_rows(
        y, w, order, slot, live) * x), (0, 1))(y, weights)
    by_row = weights.reshape(-1)[order]
    np.testing.assert_allclose(
        np.asarray(dy[:live]),
        np.asarray(x[token[:live]] * by_row[:live, None]), rtol=1e-6)
    want = jnp.where(slot < live, jnp.einsum(
        "td,tkd->tk", x, jnp.where((slot < live)[..., None],
                                   y[jnp.minimum(slot, N - 1)], 0)), 0)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("held", [2, 8], ids=["a-share", "every-expert"])
def test_a_poisoned_buffer_changes_no_output_and_no_gradient(held,
                                                             monkeypatch):
    """NaN in every row past ``live`` that the gather hands on, forward and
    backward: the layer's output, its counters and every gradient (the
    weights' too) are what they were."""
    tokens, k, width = 48, 2, 16
    rng = np.random.default_rng(6)
    experts = jnp.asarray(np.stack([rng.permutation(8)[:k]
                                    for _ in range(tokens)]), jnp.int32)
    w = rng.random((tokens, k)).astype(np.float32)
    weights = jnp.asarray(w / w.sum(1, keepdims=True))
    x = values((tokens, width), jnp.float32, 7)
    layer = blocks.DroplessExperts(0, held, width, 8)
    variables = layer.init(jax.random.key(0), x, weights, experts)

    def run():
        def loss(params, x, weights):
            out, counted = layer.apply(
                {"params": params, ROUND_COUNTERS: variables[ROUND_COUNTERS]},
                x, weights, experts, mutable=[ROUND_COUNTERS])
            return jnp.sum(out * out), (out, counted)
        (_, aux), grads = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(
            variables["params"], x, weights)
        return jax.tree.leaves((aux, grads))

    clean = run()
    real = rows.gather

    def poisoned(x, token, live, **kw):
        out = real(x, token, live, **kw)
        return jnp.where(jnp.arange(out.shape[0])[:, None] < live, out,
                         jnp.nan)

    monkeypatch.setattr(rows, "gather", poisoned)
    dirty = run()
    assert len(clean) == len(dirty) > 6
    for a, b in zip(clean, dirty):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if held < 8:  # the poison was there: some rows are dead
        live = int(np.sum(np.asarray(experts) < held))
        assert live < tokens * k
        assert np.isnan(np.asarray(poisoned(
            x, jnp.zeros(tokens * k, jnp.int32), live))).any()


def test_the_tile_follows_the_rows_bytes():
    # the benchmark's cell: 98,304 buffer rows of 2560 bfloat16, k = 6
    assert rows.gather_tile(98304, 2560, jnp.bfloat16) == 512
    assert rows.combine_tile(16384, 6, 2560, jnp.bfloat16) == 128
    # float32 rows cost twice the scratch and output
    assert rows.gather_tile(98304, 2560, jnp.float32) == 256
    # the tests' sizes and Model.build's sample: one program, or few
    assert rows.gather_tile(400, 16, jnp.float32) == 400
    assert rows.combine_tile(200, 2, 16, jnp.float32) == 208
    assert rows.gather_tile(768, 2560, jnp.float32) == 256
    assert rows.combine_tile(128, 6, 2560, jnp.float32) == 128
    visited = [int(rows.visited_rows(jnp.int32(n), 98304, 512))
               for n in (0, 1, 512, 513, 12288, 98304)]
    assert visited == [512, 512, 512, 1024, 12288, 98304]


@pytest.fixture(scope="module")
def one_chip():
    """A described v5e (no chip is attached: the compiler is installed)."""
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("TPU_LOG_DIR", "disabled")  # or libtpu logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - whatever keeps it away
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield jax.sharding.SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("tokens, dtype, k, width", [
    (16384, jnp.bfloat16, 6, 2560), (128, jnp.float32, 6, 2560),
    (128, jnp.bfloat16, 6, 2560), (16384, jnp.bfloat16, 4, 2048)],
    ids=["the-cell", "build-float32", "build-bfloat16", "the-lfm2-cell"])
def test_the_kernels_compile_for_a_v5e(one_chip, tokens, dtype, k, width):
    """Mosaic takes both kernels at the published widths: the benchmark's
    steps (16,384 tokens; k = 6 of 2,560 in SmallThinker's cell, k = 4 of
    2,048 in LFM2's) and ``Model.build``'s sample of 128."""
    def shape(*dims, dtype=dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    scalar = shape(dtype=jnp.int32)
    for lowered in (
            jax.jit(lambda x, token, live, scale: rows.gather(
                x, token, live, scale, interpret=False)).lower(
                    shape(tokens, width), shape(tokens * k, dtype=jnp.int32),
                    scalar, shape(tokens * k, dtype=jnp.float32)),
            jax.jit(lambda buffer, slot, live, weights: rows.combine(
                buffer, slot, live, weights, interpret=False)).lower(
                    shape(tokens * k, width), shape(tokens, k,
                                                    dtype=jnp.int32), scalar,
                    shape(tokens, k, dtype=jnp.float32))):
        text = lowered.compile().as_text()
        assert "tpu_custom_call" in text


def test_flash_compiles_for_a_v5e_with_groups_at_head_width_64(one_chip):
    """LFM2's attention layer: 32 query heads over 8 K/V heads of 64 at L =
    8,192, forward and backward (GPT-2 runs width 64 without groups,
    SmallThinker groups at width 128). Kept in this file: a process describes
    the topology once (``on-chip-measurement`` guide, section 2)."""
    from distkeras_tpu.ops.pallas import flash_attention

    def shape(heads):
        return jax.ShapeDtypeStruct((2, 8192, heads, 64), jnp.bfloat16,
                                    sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, interpret=False).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        shape(32), shape(8), shape(8)).compile().as_text()
    for kernel in ("dk_flash_fwd", "dk_flash_dq", "dk_flash_dkv"):
        assert kernel in text, kernel


def test_flash_compiles_for_a_v5e_with_keys_wider_than_values(one_chip):
    """Kimi Linear's latent-attention layer: 8 held heads, keys of 192 beside
    values of 128 at L = 8,192, forward and backward. 192 columns take 256
    lanes in VMEM, so K and V whole leave room for tiles of 256 (at 512
    Mosaic refuses dk/dv by 0.7 MiB of its 16). Kept in this file for the
    reason above."""
    from distkeras_tpu.ops.pallas import flash_attention
    from distkeras_tpu.ops.pallas.flash_attention import default_tiling

    assert default_tiling(8192, 192, Dv=128) == (256, 256, 128)

    def shape(width):
        return jax.ShapeDtypeStruct((2, 8192, 8, width), jnp.bfloat16,
                                    sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, interpret=False).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        shape(192), shape(192), shape(128)).compile().as_text()
    for kernel in ("dk_flash_fwd", "dk_flash_dq", "dk_flash_dkv"):
        assert kernel in text, kernel
    assert text.count("tpu_custom_call") == 3


def test_the_kernel_pairs_compile_for_a_v5e_at_the_cells_shape(one_chip,
                                                                monkeypatch):
    """``[1, 8192, 8, 128]`` in chunks of 64 under ``jax.checkpoint`` and
    ``jax.value_and_grad``, bfloat16: Mosaic takes all four kernels (the
    in-chunk pair ``dk_kda_chunk_*`` and the scan pair ``dk_kda_scan_*``), the
    layer is six calls (both forward kernels in the forward and again in the
    recomputed pass, then the two backward kernels), every one under
    ``dk_kda``; no ``while`` is left there and the pairwise ``[.., 16, 16,
    128]`` tensors of the ``jax.numpy`` form are gone from the program. Kept
    in this file for the reason above."""
    import re

    from distkeras_tpu.ops.delta_rule import chunked_gated_delta_rule
    from distkeras_tpu.ops.pallas import mode

    monkeypatch.setattr(mode, "compiles", lambda: True)  # headed for the chip

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    @jax.checkpoint
    def layer(*a):
        with jax.named_scope("dk_kda"):
            return chunked_gated_delta_rule(*a)[0]

    def loss(*a):
        with jax.named_scope("model"):  # a transform renames the outermost
            return jnp.sum(jnp.square(layer(*a).astype(jnp.float32)))

    wide = (1, 8192, 8, 128)
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        shape(*wide), shape(*wide), shape(*wide),
        shape(*wide, dtype=jnp.float32),
        shape(*wide[:3], dtype=jnp.float32)).compile().as_text()
    calls = re.findall(r"= [^\n]*custom-call\([^\n]*"
                       r'op_name="[^"]*/(dk_kda_(?:scan|chunk)_\w+)/', text)
    assert sorted(calls) == ["dk_kda_chunk_bwd", "dk_kda_chunk_fwd",
                             "dk_kda_chunk_fwd", "dk_kda_scan_bwd",
                             "dk_kda_scan_fwd", "dk_kda_scan_fwd"], calls
    assert text.count('custom_call_target="tpu_custom_call"') == 6
    assert all("/dk_kda/" in line for line in text.splitlines()
               if "custom-call(" in line and "dk_kda_" in line)
    assert not re.search(r'= [^\n]* while\([^\n]*op_name="[^"]*dk_kda', text)
    assert not re.search(r"\[[0-9,]*16,16,128\]", text)
