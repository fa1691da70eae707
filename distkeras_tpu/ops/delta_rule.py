"""The gated delta rule with a decay a channel (Kimi Delta Attention's
recurrence; Kimi Linear, arXiv:2510.26692), in its **chunked form**: products
inside a chunk, a scan over chunks. Both halves are Pallas kernel pairs of
``ops/pallas/delta_rule.py``, each with a written backward under one
``jax.custom_vjp``: the in-chunk half (``dk_kda_chunk_fwd``,
``dk_kda_chunk_bwd``: ``G``, ``A``, ``Bq``, the triangular inverse, ``U``,
``W`` made in VMEM from ``q, k, v, g, beta`` as the projections leave them)
and the scan over chunks (``dk_kda_scan_fwd``, ``dk_kda_scan_bwd``: the state
stays in VMEM). The in-chunk half in plain ``jax.numpy``
(:func:`in_chunk_by_jax_numpy`) is kept as what the kernels are held to; no
model path reaches it. Not built: the two pairs as one kernel (``U`` to
``Kd`` go through HBM between them), the scan's output written as ``[B, L,
H x d]`` (ROADMAP S14).

A head carries a state ``S`` ``[d_k, d_v]``, zero before the sequence starts.
With ``alpha_t = exp(g_t)`` in (0, 1) a channel of the key and ``beta_t`` in
(0, 1):

    S' = Diag(alpha_t) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T        o_t = S_t^T q_t

Run a position at a time that is ``L`` dependent steps. **Chunked**: with
``G`` the running sum of ``g`` inside a chunk of ``C`` positions (``G_r <=
0``, falling), every state of the chunk is ``S_r = Diag(e^{G_r}) S_0 + sum_{i
<= r} (k_i * e^{G_r - G_i}) u~_i^T`` for pseudo-values ``u~`` that solve a
unit lower-triangular system:

    A_ri = k_r . (k_i * e^{G_r - G_i})   (i < r)     T = (I + Diag(beta) A)^-1
    U = T (beta * V)    W = T (beta * K * e^G)       u~ = U - W S_0
    o = (Q * e^G) S_0 + tril(B) u~    B_ri = q_r . (k_i * e^{G_r - G_i}), i <= r
    S_C = Diag(e^{G_C}) S_0 + (K * e^{G_C - G})^T u~

``A``, ``B``, ``T``, ``U``, ``W`` are computed for all chunks at once
(:func:`~distkeras_tpu.ops.pallas.delta_rule.chunk_products`: a program a
chunk and group of heads, in any order); only the three lines that touch
``S_0`` run in the scan over chunks
(:func:`~distkeras_tpu.ops.pallas.delta_rule.scan_chunks`), which carries
``S`` in float32. ``T`` is built by blocks from the unit diagonal up, in
float32: here (:func:`_unit_lower_inverse`) forward substitution a block at a
time, ``log2 C`` levels of two batched products; in the kernel the ``SUB`` x
``SUB`` blocks on the diagonal by forward substitution a diagonal at a time,
the levels above them by the same two products.

**The overflow hazard, and what is done about it.** ``e^{-G}`` overflows
float32 once a chunk's summed decay passes 88 (``g`` near -1.6 a step, which
the initialisation allows, over 64 positions is -102), so the pairwise decay
is never formed as ``(k * e^G)(k * e^-G)^T``. Every exponential here takes a
difference that is ``<= 0`` by construction, sub-chunk by sub-chunk (``SUB`` =
16 rows, as the public kernels have it):

* a row ``r`` of sub-chunk ``I`` against a column ``i`` of an *earlier*
  sub-chunk: ``e^{G_r - G_i} = e^{G_r - F_I} * e^{F_I - G_i}`` with ``F_I`` the
  running sum at ``I``'s first row, which lies between them: both factors are
  ``<= 1``, and the pair is one product on the MXU. A factor that underflows
  to 0 stands for a decay below 1e-38;
* inside a sub-chunk (the 16 x 16 blocks on the diagonal) the difference ``G_r
  - G_i`` is taken a pair and channel, masked to ``-inf`` above the diagonal
  *before* the exponential, and summed over the channels elementwise;
* ``e^G``, ``e^{G_C - G}`` and ``e^{G_C}`` have exponents ``<= 0`` as they are.

Nothing is clamped: a decay of any strength gives the recurrence's result
(``tests/test_kimi_linear_ops.py`` holds ``g`` at -1.6 over whole chunks, and
at -20).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from distkeras_tpu.ops.pallas.delta_rule import (chunk_products,
                                                   scan_chunks)

#: positions a chunk, where the sequence allows it, and rows a sub-chunk
CHUNK, SUB = 64, 16


def chunk_for(seq_len: int) -> int:
    """The chunk a sequence of ``seq_len`` is cut into: ``CHUNK`` where that
    divides it, else the largest power of two below it that does."""
    chunk = CHUNK
    while seq_len % chunk:
        chunk //= 2
    return chunk


def _unit_lower_inverse(n):
    """``(I + n)^-1`` for strictly lower-triangular ``n`` ``[..., C, C]``, ``C``
    a power of two, by blocks: the inverse of ``[[A, 0], [c, D]]`` is ``[[A^-1,
    0], [-D^-1 c A^-1, D^-1]]``, from the unit diagonal up, two batched
    products a level and ``log2 C`` levels. That is forward substitution a
    block at a time and as stable. (The shorter product ``(I - n)(I + n^2)(I +
    n^4)...`` is exact on paper and useless here: with keys that point one
    way the powers of ``n`` grow like binomial coefficients, to 1e17 at C =
    64, and float32 cancels nothing of that; measured, an error of 1e21.)"""
    lead, C = n.shape[:-2], n.shape[-1]

    def mm(a, b):
        return jnp.einsum("...ij,...jk->...ik", a, b,
                          precision=jax.lax.Precision.HIGHEST)

    blocks, s = jnp.ones(lead + (C, 1, 1), n.dtype), 1
    while s < C:
        P = C // (2 * s)
        pairs = blocks.reshape(*lead, P, 2, s, s)
        a, d = pairs[..., 0, :, :], pairs[..., 1, :, :]
        below = n.reshape(*lead, P, 2, s, P, 2, s)[..., :, 1, :, :, 0, :]
        c = jnp.moveaxis(jnp.diagonal(below, axis1=-4, axis2=-2), -1, -3)
        blocks = jnp.concatenate([
            jnp.concatenate([a, jnp.zeros_like(a)], -1),
            jnp.concatenate([-mm(mm(d, c), a), d], -1)], -2)
        s *= 2
    return blocks.reshape(*lead, C, C)


def in_chunk_by_jax_numpy(q, k, v, g, beta, chunk: int):
    """The in-chunk half in plain ``jax.numpy``, as it ran until the kernels
    of ``ops/pallas/delta_rule.py`` (:func:`~distkeras_tpu.ops.pallas.
    delta_rule.chunk_products`) took its place: **no model path reaches
    this**; tests and ``chip_smoke.py`` hold the kernels, and their written
    backward against JAX's derivative of this, to it. Arguments as
    :func:`chunked_gated_delta_rule`. Returns ``(U, W, Qg, Bq, Kd, s)`` as
    ``scan_chunks`` reads them (``[B, H, N, C, .]``, ``s`` ``[B, H, N, d_k]``)
    and the summed log-decay of each chunk ``[B, H, N, d_k]``."""
    B, L, H, K = k.shape
    C = chunk
    sub = min(SUB, C)
    N, M = L // C, C // sub
    dt, f32 = v.dtype, jnp.float32

    def chunks(x):  # [B, L, H, ...] -> [B, H, N, C, ...]
        x = jnp.moveaxis(x, 2, 1)
        return x.reshape(B, H, N, C, *x.shape[3:])

    q, k, v, g, beta = map(chunks, (q, k, v, g.astype(f32),
                                    beta.astype(f32)))
    q32, k32 = q.astype(f32), k.astype(f32)
    G = jnp.cumsum(g, axis=3)                               # [B, H, N, C, K]

    def dot(spec, a, b):
        return jnp.einsum(spec, a.astype(dt), b.astype(dt),
                          preferred_element_type=f32)

    def subs(x):  # [B, H, N, C, K] -> [B, H, N, M, sub, K]
        return x.reshape(B, H, N, M, sub, K)

    # Rows against the columns of earlier sub-chunks: both factors <= 1.
    Gs = subs(G)
    first = Gs[..., :1, :]                                  # F_I
    rows = jnp.exp(Gs - first)
    earlier = jnp.arange(C)[None, :] < (jnp.arange(M) * sub)[:, None]
    cols = k32[:, :, :, None] * jnp.exp(jnp.where(
        earlier[..., None], first - G[:, :, :, None], -jnp.inf))
    a_pairs = dot("bhnmrk,bhnmck->bhnmrc", subs(k32) * rows, cols)
    b_pairs = dot("bhnmrk,bhnmck->bhnmrc", subs(q32) * rows, cols)
    # Inside a sub-chunk: the difference a pair and channel, masked first.
    lower = jnp.arange(sub)[:, None] >= jnp.arange(sub)[None, :]
    pair = jnp.exp(jnp.where(
        lower[..., None], Gs[..., :, None, :] - Gs[..., None, :, :], -jnp.inf))
    kk = subs(k32)[..., None, :, :] * pair                  # [.., r, i, K]
    on_diagonal = jnp.eye(M, dtype=f32)[:, None, :, None]   # [M, 1, M, 1]

    def whole(pairs, inside):  # -> [B, H, N, C, C]
        inside = jnp.sum(inside, axis=-1)                   # [.., M, r, i]
        return (pairs.reshape(B, H, N, M, sub, M, sub)
                + inside[..., :, :, None, :] * on_diagonal).reshape(
                    B, H, N, C, C)

    A = whole(a_pairs, subs(k32)[..., :, None, :] * kk)
    Bq = whole(b_pairs, subs(q32)[..., :, None, :] * kk)
    tril = jnp.tril(jnp.ones((C, C), f32))
    T = _unit_lower_inverse(beta[..., None] * A * (tril - jnp.eye(C)))
    decay = jnp.exp(G)
    U = dot("bhnrc,bhncv->bhnrv", T, beta[..., None] * v.astype(f32))
    W = dot("bhnrc,bhnck->bhnrk", T, beta[..., None] * k32 * decay)
    last = G[:, :, :, -1, :]
    return (U, W.astype(dt), (q32 * decay).astype(dt), (Bq * tril).astype(dt),
            (k32 * jnp.exp(last[:, :, :, None] - G)).astype(dt),
            jnp.exp(last)), last


def chunked_gated_delta_rule(q, k, v, g, beta, chunk: int | None = None):
    """``o_t`` of the recurrence above for every position, from a zero
    state. ``q, k``: [B, L, H, d_k] (already normalised and scaled); ``v``:
    [B, L, H, d_v]; ``g``: [B, L, H, d_k], the log of the decay (``<= 0``);
    ``beta``: [B, L, H]. ``chunk``, a power of two, divides ``L``
    (``chunk_for(L)`` by default). Products take operands of ``v``'s dtype
    and accumulate in float32; decays, the triangular inverse and the carried
    state are float32. Returns ``(o [B, L, H, d_v] in v's dtype, the smallest
    summed log-decay of a chunk and channel)``."""
    B, L, H, _ = k.shape
    C = chunk_for(L) if chunk is None else chunk
    if L % C or C & (C - 1):
        raise ValueError(f"a sequence of {L} in chunks of {C}: a chunk is a "
                         "power of two and must divide the sequence")
    *products, last = chunk_products(q, k, v, g, beta, C, min(SUB, C))
    out = scan_chunks(*products)
    return (jnp.moveaxis(out.reshape(B, H, L, v.shape[-1]), 1, 2),
            jnp.min(jax.lax.stop_gradient(last)))
