"""Causal FlashAttention as a Pallas TPU kernel (forward + backward).

The transformer's attention is the one op where XLA's default lowering
materializes an O(L^2) score matrix through HBM. This kernel streams K/V
chunks through VMEM with the usual online-softmax recurrence, so peak memory
is O(BLOCK_Q x BLOCK_K) per core and the MXU sees back-to-back matmuls.
Causality is exploited structurally: a q-block only loops over k-chunks at or
before its diagonal (half the FLOPs of full attention).

Performance shape (v5e, d_head 64, measured round 3):

* **Asymmetric blocks.** Scores/PV matmuls contract over d_head (64), so a
  [128, 64]x[64, 128] tile spends more time in staging than in the MXU —
  symmetric 128-blocks measured 14.7 TFLOPS. A small q-block with a LARGE
  k-chunk (block_k 1024) turns each inner step into [128,64]x[64,1024] +
  [128,1024]x[1024,64] and cuts loop trips ~8x.
* **No revisited output blocks.** lse/delta live as [BH, nq, 1, block_q] —
  one exact block per program — so every grid dim is declared ``parallel``
  and Mosaic overlaps fetch/compute across programs. (A revisited [1, 1, L]
  lse row forced the whole grid sequential in an earlier revision.)
* bf16 operands, f32 accumulation via ``preferred_element_type`` (the same
  numerics XLA's own attention lowering uses).

Layout: inputs are [B, H, L, D] (wrapper transposes from the model's
[B, L, H, D]). Forward/dq grids are (B*H, L/block_q); the dk+dv kernel's
grid is (B*H, L/block_k), each program owning one k-chunk. Backward is two
kernels (dq; dk+dv) using the saved logsumexp, wrapped in ``jax.custom_vjp``.

``interpret=True`` runs the same kernels through the Pallas interpreter —
that is what CI exercises on the CPU mesh; the compiled path runs on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distkeras_tpu.ops.pallas import mode

_NEG = -1e30


def _qblock_spec(block, D):
    return pl.BlockSpec((1, block, D), lambda bh, i: (bh, i, 0),
                        memory_space=pltpu.VMEM)


def _full_spec(L, D):
    return pl.BlockSpec((1, L, D), lambda bh, i: (bh, 0, 0),
                        memory_space=pltpu.VMEM)


def _rowblock_spec(block):
    # lse/delta as [BH, nq, 1, block_q]: one exact block per program —
    # blocked, never revisited, so the grid stays order-independent. The
    # trailing (1, block) dims equal the array dims, satisfying TPU tiling.
    return pl.BlockSpec((1, 1, 1, block), lambda bh, i: (bh, i, 0, 0),
                        memory_space=pltpu.VMEM)


def _fullrow_spec(nq, block):
    return pl.BlockSpec((1, nq, 1, block), lambda bh, i: (bh, 0, 0, 0),
                        memory_space=pltpu.VMEM)


def _parallel_kw(interpret: bool, dims: int = 2) -> dict:
    """All grid dims order-independent -> Mosaic overlaps fetch/compute
    across programs. Only valid because no output block is revisited."""
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel",) * dims)}


def _causal_mask(bq, bk, q0, k0):
    row = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return (q0 + row) >= (k0 + col)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q: int,
                block_k: int):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.bfloat16)  # [BQ, D]
    BQ, D = q.shape

    m0 = jnp.full((BQ, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((BQ, 1), jnp.float32)
    acc0 = jnp.zeros((BQ, D), jnp.float32)

    def step(ki, carry, masked: bool):
        m, l, acc = carry
        kb = k_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.bfloat16)
        vb = v_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.bfloat16)
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if masked:
            mask = _causal_mask(BQ, block_k, qi * block_q, ki * block_k)
            s = jnp.where(mask, s, _NEG)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if masked:
            p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1, keepdims=True)
        acc = acc * corr + jax.lax.dot_general(
            p.astype(jnp.bfloat16), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    # Two phases: k-chunks entirely at/below the diagonal need no mask (and
    # no iota/select VPU work — the fwd loop is VPU-bound, not MXU-bound);
    # only the chunk(s) straddling the diagonal mask. Chunks strictly after
    # the diagonal contribute nothing and are never visited.
    nfull = (qi * block_q) // block_k
    nk = (qi * block_q + block_q + block_k - 1) // block_k
    carry = jax.lax.fori_loop(
        0, nfull, lambda ki, c: step(ki, c, masked=False), (m0, l0, acc0))
    m, l, acc = jax.lax.fori_loop(
        nfull, nk, lambda ki, c: step(ki, c, masked=True), carry)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0, 0, 0] = (m + jnp.log(l))[:, 0]


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
               block_q: int, block_k: int):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.bfloat16)
    do = do_ref[0].astype(jnp.bfloat16)
    lse = lse_ref[0, 0, 0][:, None]    # own q-rows only (blocked spec)
    delta = delta_ref[0, 0, 0][:, None]
    BQ, D = q.shape

    def step(ki, dq, masked: bool):
        kb = k_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.bfloat16)
        vb = v_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.bfloat16)
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        p = jnp.exp(s - lse)
        if masked:
            mask = _causal_mask(BQ, block_k, qi * block_q, ki * block_k)
            p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(jnp.bfloat16)
        return dq + jax.lax.dot_general(ds, kb, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    nfull = (qi * block_q) // block_k
    nk = (qi * block_q + block_q + block_k - 1) // block_k
    dq = jax.lax.fori_loop(0, nfull, lambda ki, a: step(ki, a, masked=False),
                           jnp.zeros((BQ, D), jnp.float32))
    dq = jax.lax.fori_loop(nfull, nk, lambda ki, a: step(ki, a, masked=True),
                           dq)
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, *, block_q: int, block_k: int):
    ki = pl.program_id(1)
    kb = k_ref[0].astype(jnp.bfloat16)  # [BK, D] (this program's k chunk)
    vb = v_ref[0].astype(jnp.bfloat16)
    BK, D = kb.shape
    nq = q_ref.shape[1] // block_q

    def step(qi, carry, masked: bool):
        dk, dv = carry
        q = q_ref[0, pl.ds(qi * block_q, block_q), :].astype(jnp.bfloat16)
        do = do_ref[0, pl.ds(qi * block_q, block_q), :].astype(jnp.bfloat16)
        lse = lse_ref[0, qi, 0, :][:, None]
        delta = delta_ref[0, qi, 0, :][:, None]
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        p = jnp.exp(s - lse)  # [Q, K]
        if masked:
            mask = _causal_mask(block_q, BK, qi * block_q, ki * block_k)
            p = jnp.where(mask, p, 0.0)
        pb = p.astype(jnp.bfloat16)
        dv = dv + jax.lax.dot_general(pb, do, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(jnp.bfloat16)
        dk = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dk, dv

    # q-blocks strictly before this k-chunk contribute nothing; blocks
    # straddling the diagonal mask; blocks fully past it don't need to.
    zero = jnp.zeros((BK, D), jnp.float32)
    qstart = ki * block_k // block_q
    qfull = (ki * block_k + BK + block_q - 1) // block_q
    carry = jax.lax.fori_loop(
        qstart, qfull, lambda qi, c: step(qi, c, masked=True), (zero, zero))
    dk, dv = jax.lax.fori_loop(
        qfull, nq, lambda qi, c: step(qi, c, masked=False), carry)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bhld(q, k, v, block_q, block_k, interpret):
    """Forward on [BH, L, D] inputs; returns (out, lse [BH, nq, 1, block_q])."""
    BH, L, D = q.shape
    grid = (BH, L // block_q)
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, block_q=block_q, block_k=block_k),
        grid=grid,
        in_specs=[_qblock_spec(block_q, D), _full_spec(L, D), _full_spec(L, D)],
        out_specs=[
            _qblock_spec(block_q, D),
            _rowblock_spec(block_q),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, L, D), q.dtype),
            jax.ShapeDtypeStruct((BH, L // block_q, 1, block_q), jnp.float32),
        ],
        interpret=interpret,
        name="dk_flash_fwd",
        **_parallel_kw(interpret),
    )
    # The kernel's name (the Mosaic call's, on a chip) and the scope of the
    # same name (the interpreted ops', on a CPU) are what a trace finds it by.
    with jax.named_scope("dk_flash_fwd"):
        out, lse = call(q, k, v)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, block_q, block_k, interpret):
    out, _ = _flash_bhld(q, k, v, block_q, block_k, interpret)
    return out


def _flash_fwd(q, k, v, block_q, block_k, interpret):
    out, lse = _flash_bhld(q, k, v, block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(block_q, block_k, interpret, res, do):
    q, k, v, out, lse = res
    BH, L, D = q.shape
    nq = L // block_q
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(BH, nq, 1, block_q)

    dq_call = pl.pallas_call(
        functools.partial(_dq_kernel, block_q=block_q, block_k=block_k),
        grid=(BH, nq),
        in_specs=[_qblock_spec(block_q, D), _full_spec(L, D), _full_spec(L, D),
                  _qblock_spec(block_q, D), _rowblock_spec(block_q),
                  _rowblock_spec(block_q)],
        out_specs=_qblock_spec(block_q, D),
        out_shape=jax.ShapeDtypeStruct((BH, L, D), q.dtype),
        interpret=interpret,
        name="dk_flash_dq",
        **_parallel_kw(interpret),
    )
    with jax.named_scope("dk_flash_dq"):
        dq = dq_call(q, k, v, do, lse, delta)

    dkv_call = pl.pallas_call(
        functools.partial(_dkv_kernel, block_q=block_q, block_k=block_k),
        grid=(BH, L // block_k),
        in_specs=[_full_spec(L, D), _qblock_spec(block_k, D),
                  _qblock_spec(block_k, D), _full_spec(L, D),
                  _fullrow_spec(nq, block_q), _fullrow_spec(nq, block_q)],
        out_specs=[_qblock_spec(block_k, D), _qblock_spec(block_k, D)],
        out_shape=[jax.ShapeDtypeStruct((BH, L, D), k.dtype),
                   jax.ShapeDtypeStruct((BH, L, D), v.dtype)],
        interpret=interpret,
        name="dk_flash_dkv",
        **_parallel_kw(interpret),
    )
    with jax.named_scope("dk_flash_dkv"):
        dk, dv = dkv_call(q, k, v, do, lse, delta)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, block_size: int = 128, block_k: int | None = None,
                    interpret: bool | None = None):
    """Causal FlashAttention. ``q, k, v``: [B, L, H, D], q pre-scaled by
    1/sqrt(D). Returns [B, L, H, D]. ``block_size`` is the q-block;
    ``block_k`` is the inner k-chunk — by default the largest multiple of
    ``block_size`` up to ``8*block_size`` that divides ``L`` (e.g. L=1280,
    block 128 -> 640, not 1024). Large k-chunks keep the MXU busy when
    d_head is small (see module doc). ``L`` must be divisible by both.
    ``interpret=None`` compiles on TPU and interprets elsewhere
    (:mod:`distkeras_tpu.ops.pallas.mode`).
    """
    interpret = mode.interpret("flash_attention", interpret)
    B, L, H, D = q.shape
    if block_k is None:
        # Largest multiple of block_size that divides L, capped at 8x — so
        # every L the q-block accepts (L % block_size == 0) keeps working
        # (L=1280/1536/... are not multiples of a fixed 1024 chunk).
        block_k = block_size
        for mult in range(2, 9):
            if L % (block_size * mult) == 0:
                block_k = block_size * mult
    if L % block_size != 0 or L % block_k != 0:
        raise ValueError(
            f"seq_len {L} not divisible by block_q {block_size} / "
            f"block_k {block_k}")

    def to_bhld(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, L, D)

    out = _flash(to_bhld(q), to_bhld(k), to_bhld(v), block_size, block_k,
                 interpret)
    return out.reshape(B, H, L, D).transpose(0, 2, 1, 3)
