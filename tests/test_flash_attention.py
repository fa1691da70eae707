"""FlashAttention kernel tests (Pallas interpreter on the CPU mesh).

Forward and backward are checked against dense causal attention — values AND
gradients. The kernels use bf16 MXU operands with f32 accumulation (the same
numerics XLA's dense lowering uses on TPU), so tolerances are at the bf16 noise
floor rather than f32 exactness.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu import telemetry
from distkeras_tpu.models import small_transformer_lm
from distkeras_tpu.models.transformer import TransformerLM
from distkeras_tpu.ops.pallas import flash_attention
from distkeras_tpu.ops.pallas.flash_attention import (default_tiling,
                                                      tile_schedule,
                                                      visited_share)

B, L, H, D = 2, 64, 2, 16
BLOCK = 16


def dense_causal(q, k, v):
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k)
    mask = jnp.tril(jnp.ones((q.shape[1], q.shape[1]), bool))
    scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _inputs(seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=(B, L, H, D)).astype(np.float32))
                 for _ in range(3))


def test_flash_forward_matches_dense():
    q, k, v = _inputs()
    out = flash_attention(q, k, v, block_size=BLOCK, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense_causal(q, k, v)),
                               atol=5e-2)


def test_flash_backward_matches_dense():
    q, k, v = _inputs(1)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, block_size=BLOCK, interpret=True) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_causal(q, k, v) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=0.35, rtol=0.02,
                                   err_msg=f"d{name} mismatch")


def test_asymmetric_blocks_match_dense():
    """block_k > block_q (the TPU-tuned shape) and the multi-chunk loop
    phases (full/masked) must be value-identical to dense."""
    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 128, 1, 16)), jnp.float32)
               for _ in range(3))
    ref = dense_causal(q, k, v)
    for bq, bk in [(16, 64), (16, 128), (32, 64)]:
        out = flash_attention(q, k, v, block_size=bq, block_k=bk,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=5e-2, err_msg=f"bq={bq} bk={bk}")
    # grads through the asymmetric path too
    gf = jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, block_size=16, block_k=64, interpret=True) ** 2))(q)
    gd = jax.grad(lambda q: jnp.sum(dense_causal(q, k, v) ** 2))(q)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                               atol=0.35, rtol=0.02)


def test_default_block_k_covers_all_blockable_lengths():
    """Every L the q-block accepts must get a valid default k-chunk —
    L=1280-style lengths (multiple of 128, not of 1024) must not regress."""
    rng = np.random.default_rng(4)
    for L in (80, 96, 160):  # multiples of 16, not all of 8*16
        q, k, v = (jnp.asarray(rng.normal(size=(1, L, 1, 16)), jnp.float32)
                   for _ in range(3))
        out = flash_attention(q, k, v, block_size=16, interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(dense_causal(q, k, v)),
                                   atol=5e-2, err_msg=f"L={L}")


def test_transformer_flash_impl_matches_dense():
    tokens = jnp.asarray(np.random.default_rng(2).integers(0, 64, size=(2, 32)),
                         jnp.int32)
    dense_model = small_transformer_lm(vocab_size=64, num_layers=1, d_model=32,
                                       num_heads=2, d_ff=64, max_seq_len=32,
                                       seq_len=32)
    arch = dense_model.module.get_config()
    flash_module = TransformerLM(**{**arch, "attn_impl": "flash"})
    out_dense = dense_model.predict(tokens)
    out_flash = flash_module.apply({"params": dense_model.params}, tokens, train=False)
    np.testing.assert_allclose(np.asarray(out_flash), np.asarray(out_dense),
                               atol=5e-2)


def _old_tiling(L):
    """What the kernels ran before the schedule followed L: q-block 128 and
    the largest k-chunk up to 8 blocks that divides L (1024 at L = 1024)."""
    return 128, max(128 * m for m in range(1, 9) if L % (128 * m) == 0), None


@pytest.mark.parametrize("tiling_of", [lambda L: default_tiling(L, 64),
                                       _old_tiling],
                         ids=["default", "old"])
@pytest.mark.parametrize("L", [128, 256, 640, 1024, 1280, 2048])
def test_schedule_covers_the_causal_triangle_once(L, tiling_of):
    """Per kernel: every (q, k) with k <= q lies in exactly one visited tile,
    no visited tile lies wholly above the diagonal, and every tile that
    straddles it is marked masked."""
    tiling = tiling_of(L)
    tril = np.tril(np.ones((L, L), bool))
    for name, tiles in tile_schedule(L, *tiling).items():
        hits = np.zeros((L, L), np.int8)
        for q0, q1, k0, k1, masked in tiles:
            hits[q0:q1, k0:k1] += 1
            inside = tril[q0:q1, k0:k1]
            assert inside.any(), f"{name}: tile at {(q0, k0)} is all above"
            assert masked or inside.all(), \
                f"{name}: tile at {(q0, k0)} straddles the diagonal unmasked"
        assert (hits[tril] == 1).all(), f"{name}: a causal pair missed/twice"
        assert hits.max() == 1
    if L == 1024:  # the old tiling is what the cell ran before: no skip
        share = visited_share(L, *tiling)
        assert (share == 1.0) if tiling_of is _old_tiling else (share <= 0.65)


@pytest.mark.parametrize("L, d, block, block_k, tiling", [
    (1024, 16, 128, None, (1024, 1024, 256)),
    (640, 16, 128, None, (640, 640, 128)),
    (1024, 64, 128, None, (1024, 1024, 256)),
    (384, 16, 16, None, (128, 128, 64)),
    (128, 16, 16, 64, (16, 64, None)),
], ids=["cell-1024", "five-blocks-640", "cell-1024-d64", "looped-tiles-384",
        "rectangular-16x64"])
def test_default_tiling_values_and_grads_match_dense(L, d, block, block_k,
                                                     tiling):
    """out, dq, dk and dv on the tiling the benchmark's cell runs (default
    blocks at L = 1024; at its head width 64 too: one tile, static cuts), a
    length that is not a multiple of 8 blocks, square tiles past 16 grains
    (the ``fori_loop`` over whole tiles, then the cut diagonal tile) and
    rectangular ``block_k`` tiles masked whole: with the window and the group
    of test_flash_window_gqa.py, every path of the key-major dk/dv step."""
    if block_k is None:
        assert default_tiling(L, d, block) == tiling
    rng = np.random.default_rng(L)
    q, k, v = (jnp.asarray(rng.normal(size=(1, L, 1, d)), jnp.float32)
               for _ in range(3))
    q = q * d ** -0.5

    def run(fn):
        out, vjp = jax.vjp(fn, q, k, v)
        return (out, *vjp(2.0 * out))

    got = run(lambda q, k, v: flash_attention(
        q, k, v, block_size=block, block_k=block_k, interpret=True))
    ref = run(dense_causal)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref[0]),
                               atol=5e-2)
    for a, b, name in zip(got[1:], ref[1:], "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=0.35,
                                   rtol=0.02, err_msg=f"d{name} mismatch")


def test_visited_share_gauge_reads_the_schedule():
    """`pallas.flash.visited_share` is set as the call is traced, from the
    same schedule the kernels' loop bounds come from."""
    q, k, v = _inputs(5)
    jax.jit(lambda q, k, v: flash_attention(q, k, v, block_size=BLOCK,
                                            interpret=True)).lower(q, k, v)
    want = visited_share(L, *default_tiling(L, D, BLOCK))
    assert telemetry.gauge("pallas.flash.visited_share").value == want
    assert 0.5 < want < 1.0
    jax.jit(lambda q, k, v: flash_attention(
        q, k, v, block_size=BLOCK, block_k=L, interpret=True)).lower(q, k, v)
    assert telemetry.gauge("pallas.flash.visited_share").value == 1.0

