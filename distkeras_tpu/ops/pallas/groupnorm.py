"""Fused GroupNorm(+ReLU) as one-pass Pallas TPU kernels (fwd + custom VJP).

Why: ImageNet-class ResNet training on this chip is HBM-bandwidth-bound
(docs/PERFORMANCE.md regime 3) and GroupNorm accounts for ~28% of the step.
XLA lowers each GN to (at best) a stats reduce pass plus a normalize fusion —
two full reads and a write of the activation per norm. These kernels keep a
sample's whole [H·W, C] slab resident in VMEM: statistics, normalization, the
affine transform, and the trailing ReLU all happen on one read and one write.
Backward likewise recomputes the (cheap, VMEM-resident) statistics from the
saved *input* instead of stashing normalized intermediates, so the only
residual is the activation itself.

Group reductions never reshape across lanes: per-channel sums ([1, C]) are
folded to per-group values ([1, G]) by a tiny one-hot matmul (``M [C, G]``),
and expanded back the same way — MXU-friendly, Mosaic-safe.

Numerics match ``flax.linen.GroupNorm`` (contiguous channel groups, biased
variance, float32 statistics regardless of input dtype); equivalence is
tested in ``tests/test_pallas_groupnorm.py`` (interpreter on CPU CI, compiled
on TPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distkeras_tpu.ops.pallas import mode


def _group_matrix(C: int, G: int, fold: int = 1) -> np.ndarray:
    """One-hot [C*fold, G] membership: channel c belongs to group
    c // (C // G) (flax's contiguous grouping). ``fold`` > 1 means the lane
    dim carries ``fold`` spatial rows side by side (lane c' is true channel
    c' % C) — used to fill all 128 lanes for C < 128 layers; the group sums
    are position-independent so membership just tiles."""
    M = np.zeros((C * fold, G), np.float32)
    c = np.arange(C * fold)
    M[c, (c % C) // (C // G)] = 1.0
    return M


#: f32 chunk-temporary size above which the kernel declines the shape and
#: group_norm falls back to XLA. The soft budget below it is a preference
#: (register/stack pressure); known-good ResNet shapes run up to ~800 KB over
#: it, so the hard line sits well above those but below plan-blowing sizes.
_HARD_CHUNK_BYTES = 2e6


def _num_chunks(N: int, C: int, budget_bytes: float = 3e5) -> int | None:
    """Chunk the [N, C] slab's float32 work so per-chunk temporaries fit the
    scoped-VMEM stack (the bf16 slab itself stays resident; chunked loads are
    VMEM->VREG, costing no HBM traffic). Chunk starts stay sublane-aligned
    (CK % 8 == 0; a single chunk starts at 0 and needs no alignment) so
    dynamic slices lower cleanly. The soft ``budget_bytes`` is a preference:
    the most-split aligned candidate is used even over it (measured fine on
    chip for ResNet's 400-800 KB cases), but past ``_HARD_CHUNK_BYTES``
    returns ``None`` — callers fall back to the XLA impl instead of blowing
    the scoped-VMEM plan at compile time (r3 advisor)."""
    best = None
    for cand in (1, 2, 4, 8, 16, 32):  # least-split first: fewest loop trips
        ck = N // cand
        if N % cand == 0 and (cand == 1 or ck % 8 == 0):
            best = cand  # ends at the most-split aligned candidate
            if ck * C * 4 <= budget_bytes:
                return cand
    if best is not None and (N // best) * C * 4 <= _HARD_CHUNK_BYTES:
        return best
    return None


def _lane_fold(N: int, C: int) -> int:
    """Lane-fold factor for C<128 layers: view [B, N, C] as [B, N/f, C*f] so
    every lane is busy (pure reshape in row-major NHWC)."""
    fold = 1
    while C * fold < 128 and N % (fold * 2) == 0:
        fold *= 2
    return fold


def _xla_group_norm(x3, gamma, beta, groups: int, relu: bool):
    """flax-equivalent GroupNorm(+ReLU) in plain HLO: float32 stats, biased
    variance, eps 1e-6 — the fallback for shapes where no sublane-aligned
    VMEM chunking exists for the Pallas kernel."""
    B, N, C = x3.shape
    xf = x3.astype(jnp.float32).reshape(B, N, groups, C // groups)
    mean = xf.mean((1, 3), keepdims=True)
    var = ((xf - mean) ** 2).mean((1, 3), keepdims=True)
    y = ((xf - mean) * lax.rsqrt(var + 1e-6)).reshape(B, N, C)
    y = y * gamma + beta
    if relu:
        y = jnp.maximum(y, 0.0)
    return y.astype(x3.dtype)


def _expand(v, M):
    """[1, G] -> [1, C] by group membership (contract over G)."""
    return lax.dot_general(v, M, (((1,), (1,)), ((), ())))


def _slab_stats(x_ref, m_ref, n_per_group, nck):
    """Per-group (mean, inv_sigma) of the resident [1, N, C] block, reduced
    chunk-by-chunk in float32."""
    N, C = x_ref.shape[1], x_ref.shape[2]
    CK = N // nck

    def chunk(i, acc):
        s, ss = acc
        xc = x_ref[0, pl.ds(i * CK, CK), :].astype(jnp.float32)
        return (s + jnp.sum(xc, axis=0, keepdims=True),
                ss + jnp.sum(xc * xc, axis=0, keepdims=True))

    zero = jnp.zeros((1, C), jnp.float32)
    s, ss = lax.fori_loop(0, nck, chunk, (zero, zero))
    M = m_ref[...]
    mean = jnp.dot(s, M) / n_per_group                  # [1, G]
    var = jnp.dot(ss, M) / n_per_group - mean * mean
    inv = lax.rsqrt(var + 1e-6)
    return mean, inv, M


def _fwd_kernel(x_ref, g_ref, b_ref, m_ref, y_ref, *, n_per_group, relu,
                out_dtype, nck):
    N = x_ref.shape[1]
    CK = N // nck
    mean, inv, M = _slab_stats(x_ref, m_ref, n_per_group, nck)
    a = _expand(inv, M) * g_ref[...]                    # [1, C]
    b = b_ref[...] - _expand(mean * inv, M) * g_ref[...]

    def chunk(i, _):
        xc = x_ref[0, pl.ds(i * CK, CK), :].astype(jnp.float32)
        y = xc * a + b
        if relu:
            y = jnp.maximum(y, 0.0)
        y_ref[0, pl.ds(i * CK, CK), :] = y.astype(out_dtype)
        return 0

    lax.fori_loop(0, nck, chunk, 0)


def _bwd_kernel(x_ref, dy_ref, g_ref, b_ref, m_ref, dx_ref, dg_ref, db_ref,
                *, n_per_group, relu, out_dtype, nck):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dg_ref[...] = jnp.zeros_like(dg_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    N, C = x_ref.shape[1], x_ref.shape[2]
    CK = N // nck
    mean, inv, M = _slab_stats(x_ref, m_ref, n_per_group, nck)
    mean_c = _expand(mean, M)
    inv_c = _expand(inv, M)                             # [1, C]
    g = g_ref[...]
    b = b_ref[...]

    def _chunk_vals(i):
        xc = x_ref[0, pl.ds(i * CK, CK), :].astype(jnp.float32)
        dy = dy_ref[0, pl.ds(i * CK, CK), :].astype(jnp.float32)
        xhat = (xc - mean_c) * inv_c
        if relu:
            # y > 0 <=> pre-ReLU output > 0; recompute, nothing stashed.
            dy = jnp.where(xhat * g + b > 0.0, dy, 0.0)
        return xhat, dy

    # Pass 1 (VMEM-resident re-reads): masked-dy reductions for the group
    # means and the param grads, which accumulate across the sequential grid
    # in constant-index output blocks.
    def red_chunk(i, acc):
        s1, s2, sg, sb = acc
        xhat, dy = _chunk_vals(i)
        dxh = dy * g
        return (s1 + jnp.sum(dxh, axis=0, keepdims=True),
                s2 + jnp.sum(dxh * xhat, axis=0, keepdims=True),
                sg + jnp.sum(dy * xhat, axis=0, keepdims=True),
                sb + jnp.sum(dy, axis=0, keepdims=True))

    zero = jnp.zeros((1, C), jnp.float32)
    s1, s2, sg, sb = lax.fori_loop(0, nck, red_chunk, (zero,) * 4)
    dg_ref[...] += sg
    db_ref[...] += sb
    m1 = _expand(jnp.dot(s1, M) / n_per_group, M)       # [1, C]
    m2 = _expand(jnp.dot(s2, M) / n_per_group, M)

    # Pass 2: dx per chunk.
    def dx_chunk(i, _):
        xhat, dy = _chunk_vals(i)
        dx = inv_c * (dy * g - m1 - xhat * m2)
        dx_ref[0, pl.ds(i * CK, CK), :] = dx.astype(out_dtype)
        return 0

    lax.fori_loop(0, nck, dx_chunk, 0)


def _vmem_kw(interpret: bool, parallel: bool = False) -> dict:
    """Raise the scoped-VMEM cap for the compiled path: the largest layer's
    three double-buffered [1, N, C] blocks (x, dy, dx at 112²x64 bf16) top
    the default 16 MiB by ~2.4 MiB; v5e has headroom above the default.
    ``parallel`` marks the grid dim order-independent (fwd: each program owns
    its own output block) so Mosaic can pipeline block fetches; bwd revisits
    the dg/db accumulator blocks and must stay sequential."""
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=64 * 1024 * 1024,
        dimension_semantics=("parallel",) if parallel else ("arbitrary",),
    )}


@functools.lru_cache(maxsize=None)
def _make_group_norm(groups: int, relu: bool, interpret: bool):
    @jax.custom_vjp
    def gn(x, gamma, beta):
        return _fwd(x, gamma, beta)[0]

    def _prep(x, gamma, beta):
        """Lane-fold C<128 layers: view [B, N, C] as [B, N/f, C*f] so every
        lane is busy (pure reshape, no data movement in row-major NHWC);
        tile gamma/beta and the group matrix to match."""
        B, N, C = x.shape
        fold = _lane_fold(N, C)
        Cf, Nf = C * fold, N // fold
        xf = x.reshape(B, Nf, Cf)
        g = jnp.tile(gamma, fold).reshape(1, Cf)
        b = jnp.tile(beta, fold).reshape(1, Cf)
        M = jnp.asarray(_group_matrix(C, groups, fold))
        n_per_group = N * (C // groups)
        return xf, g, b, M, float(n_per_group), fold

    def _fwd(x, gamma, beta):
        B, N, C = x.shape
        x3, g, b, M, npg, fold = _prep(x, gamma, beta)
        Nf, Cf = x3.shape[1], x3.shape[2]
        call = pl.pallas_call(
            functools.partial(_fwd_kernel, n_per_group=npg,
                              relu=relu, out_dtype=x.dtype,
                              nck=_num_chunks(Nf, Cf)),
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, Nf, Cf), lambda i: (i, 0, 0)),
                pl.BlockSpec((1, Cf), lambda i: (0, 0)),
                pl.BlockSpec((1, Cf), lambda i: (0, 0)),
                pl.BlockSpec((Cf, groups), lambda i: (0, 0)),
            ],
            out_specs=pl.BlockSpec((1, Nf, Cf), lambda i: (i, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((B, Nf, Cf), x.dtype),
            interpret=interpret,
            name="dk_groupnorm_fwd",
            **_vmem_kw(interpret, parallel=True),
        )
        with jax.named_scope("dk_groupnorm_fwd"):
            y = call(x3, g, b, M)
        return y.reshape(B, N, C), (x, gamma, beta)

    def _bwd(res, dy):
        x, gamma, beta = res
        B, N, C = x.shape
        x3, g, b, M, npg, fold = _prep(x, gamma, beta)
        Nf, Cf = x3.shape[1], x3.shape[2]
        call = pl.pallas_call(
            functools.partial(_bwd_kernel, n_per_group=npg,
                              relu=relu, out_dtype=x.dtype,
                              nck=_num_chunks(Nf, Cf)),
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, Nf, Cf), lambda i: (i, 0, 0)),
                pl.BlockSpec((1, Nf, Cf), lambda i: (i, 0, 0)),
                pl.BlockSpec((1, Cf), lambda i: (0, 0)),
                pl.BlockSpec((1, Cf), lambda i: (0, 0)),
                pl.BlockSpec((Cf, groups), lambda i: (0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, Nf, Cf), lambda i: (i, 0, 0)),
                pl.BlockSpec((1, Cf), lambda i: (0, 0)),
                pl.BlockSpec((1, Cf), lambda i: (0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((B, Nf, Cf), x.dtype),
                jax.ShapeDtypeStruct((1, Cf), jnp.float32),
                jax.ShapeDtypeStruct((1, Cf), jnp.float32),
            ],
            interpret=interpret,
            name="dk_groupnorm_bwd",
            **_vmem_kw(interpret),
        )
        with jax.named_scope("dk_groupnorm_bwd"):
            dx, dg, db = call(x3, dy.reshape(B, Nf, Cf), g, b, M)
        # Un-fold the per-lane param grads: lane c' is true channel c' % C.
        dg = dg.reshape(fold, C).sum(0)
        db = db.reshape(fold, C).sum(0)
        return (dx.reshape(B, N, C), dg.astype(gamma.dtype),
                db.astype(beta.dtype))

    gn.defvjp(_fwd, _bwd)
    return gn


def group_norm(x, gamma, beta, *, groups: int, relu: bool = False,
               interpret: bool | None = None):
    """Fused GroupNorm(+optional ReLU) over NHWC (or any [..., spatial..., C])
    input. ``gamma``/``beta`` are per-channel [C]. Returns x's dtype;
    statistics are float32 (flax parity). ``interpret=None`` compiles on TPU
    and interprets elsewhere (:mod:`distkeras_tpu.ops.pallas.mode`)."""
    shape = x.shape
    C = shape[-1]
    if C % groups:
        raise ValueError(f"C={C} not divisible by groups={groups}")
    B = shape[0]
    x3 = x.reshape(B, -1, C)
    N = x3.shape[1]
    fold = _lane_fold(N, C)
    if _num_chunks(N // fold, C * fold) is None:
        # No aligned chunking keeps the f32 temporaries under the hard
        # scoped-VMEM line for this (unusual) slab shape — plain HLO
        # instead of a plan-blowing kernel.
        y = _xla_group_norm(x3, gamma, beta, groups, relu)
    else:
        interpret = mode.interpret("group_norm", interpret)
        y = _make_group_norm(groups, relu, interpret)(x3, gamma, beta)
    return y.reshape(shape)
