"""The gated delta rule's chunked form as Pallas TPU kernel pairs, each
under one ``jax.custom_vjp`` with a written backward: the in-chunk half
with a decay a channel (:func:`chunk_products`), with a decay a value head
(:func:`gdn_chunk_products`) or of a state-space layer (the last section,
:func:`ssd_chunk_products`), and the scan over chunks (:func:`scan_chunks`)
all three feed. ``ops/delta_rule.py`` has the mathematics and calls them.

**The scan over chunks.** What is sequential: for a head and chunk ``n``, from
``S = 0`` before chunk 0,

    P = U - W S        O = Qg S + Bq P        S+ = s * S + Kd^T P

with ``S`` ``[d_k, d_v]`` float32, operands of a product in ``W``'s dtype,
accumulation in float32. As a ``lax.scan`` with JAX's derivative of it that
was three loops a layer and step; on a v5e at ``[1, 8192, 8, 128]``, chunks
of 64, an iteration took 4.8 us forward, 6.7 recomputed (it stacks the
residuals) and 11.2 backward, and XLA laid the products that feed the loops
out chunk-major, which cost their fusions more than the loops themselves
(PERF.md, PR 35). The kernels take 1.9 us a chunk forward with the state
saved and 3.2 backward, and read the arrays as they are computed.

* :func:`_scan_fwd` (``dk_kda_scan_fwd``): grid ``(batch x heads / heads a
  program, chunks)``, chunks innermost and in order. The state lives in a
  VMEM scratch, zeroed at chunk 0 and never written to HBM as a carry. A
  program reads one chunk's blocks of the ``[B x H, N, C, .]`` arrays as they
  were computed (no chunk-major copy), writes ``O`` and, for the forward
  rule alone, the state *entering* the chunk.
* :func:`_scan_bwd` (``dk_kda_scan_bwd``): the same grid with the chunk index
  reversed, ``dS`` in VMEM, zero after the last chunk (no final state is
  returned). From the saved entering state it recomputes ``P``, then

      dP = Bq^T dO + Kd dS+     dU = dP     dW = -dP S^T    dQg = dO S^T
      dBq = dO P^T    dKd = P dS+^T    ds = sum_v dS+ * S
      dS = Qg^T dO - W^T dP + s * dS+

  each cotangent in its primal's dtype.

* **No ``W``** (``scan_chunks(U, None, ...)``, a state-space layer: ``T =
  I``, ``W = 0``): the same grid and kernels, traced with ``with_w=False``,
  read no ``W`` and take ``P = U``; the backward writes no ``dW`` and leaves
  ``- W^T dP`` out of ``dS``. ``d_v`` (64 there) may differ from ``d_k``
  (128): a block holds both whole.

**The state is held transposed**, ``St = S^T`` ``[d_v, d_k]``: the decay of a
chunk is a value a key channel, stored as the ``[1, d_k]`` lanes it is read
as, and against ``St`` it broadcasts along sublanes as it is (against ``S`` it
would have to stand up as a column, ``ds`` to lie down again). Every product
with the state then contracts the minor dimension of both operands (``W
St^T``, ``Qg St^T``, ``Kd dSt^T``) or is plain (``dP St``, ``dO St``, ``P
dSt``). The products that contract over the chunk's positions (``P^T Kd``
forward; ``Bq^T dO``, ``dO^T Qg``, ``dP^T W`` backward) contract dimension 0
of both operands.

**Heads a program** (:func:`heads_per_program`): independent heads in one
program let the compiler hide one head's chain ``W S -> P -> P^T Kd`` behind
another's. The largest of 8, 4, 2, 1 that divides ``B x H`` and whose blocks,
double-buffered, fit the budget beside the state; at the cell's shape (``d_k =
d_v = 128``, ``C = 64``) that is 8. The gauges ``pallas.kda.heads_per_program``
and ``pallas.kda.grid_steps`` say what a call was traced with.

**The in-chunk half** (PR 39). Everything of a chunk that does not touch the
state, in VMEM from ``q, k, v, g`` read **as** ``[B, L, H x d]`` (what the
projections and taps leave; a block is ``(1, C, heads x d)``, a head a lane
slice, so no ``moveaxis`` copy of an input or of its cotangent) and ``beta``
(laid out twice by XLA, 256 KB: a column a head and a row a pack of heads):

* :func:`_chunk_fwd` (``dk_kda_chunk_fwd``): grid ``(batch x heads / heads a
  program, chunks)``, both ``parallel``. ``G`` by ``log2 C`` shifted adds.
  Rows against the columns of *earlier* sub-chunks: one product a sub-chunk
  of two factors that are each ``<= 1`` (:func:`_earlier_pairs`), operands
  rounded to the products' dtype. Inside the ``sub`` x ``sub`` blocks on the
  diagonal (:func:`_diagonals`): ``G``, ``k``, ``q`` transposed, **channels
  on sublanes and ``(head, position)`` on lanes** (two heads of 64 positions
  fill the 128 lanes), and the pairs taken **a diagonal at a time**: ``r``
  against ``r - d`` is the array against itself rolled ``d`` lanes, the
  difference masked to ``-inf`` before the exponential, and the sum over
  channels is over sublanes (vreg adds, one row out), so nothing is reduced
  across lanes. ``T``'s diagonal blocks by forward substitution on those
  diagonals (:func:`_substituted`, float32 on the VPU); a ``[C, C]`` matrix
  from its diagonals by one transpose and a roll of row ``r`` by ``r``
  (:func:`_from_diagonals`); the levels above the blocks as ``X - X N_s X``
  with ``N_s`` picked by a mask, float32 at ``Precision.HIGHEST``
  (:func:`_inverse_above`: the arithmetic of ``_unit_lower_inverse``; the
  product form ``(I - n)(I + n^2)...`` stays out, PERF.md §6 (11)). Writes
  ``U, W, Qg, Bq, Kd, s`` as the ``[B x H, N, C, .]`` arrays the scan reads,
  the chunk's summed log-decay, and for the forward rule ``T`` (16 KB a head
  and chunk, kept only inside a recomputed block).
* :func:`_chunk_bwd` (``dk_kda_chunk_bwd``): the same grid. From the inputs,
  ``T`` and the cotangents the scan's backward returns, with ``Gamma_ri =
  e^{G_r - G_i}`` a channel:

      R_V = T^T dU     R_K = T^T dW     dT = dU (beta V)^T + dW (beta K e^G)^T
      dM = -strict_lower(T^T dT T^T)    dA_ri = beta_r dM_ri
      dbeta = rowsum(dM * A) + rowsum(R_V * V) + rowsum(R_K * K e^G)
      X^A_r = sum_{i<r} dA_ri (k_i * Gamma_ri)   Y^A_i = sum_{r>i} dA_ri (k_r * Gamma_ri)
      X^B_r = sum_{i<=r} dBq_ri (k_i * Gamma_ri) Y^B_i = sum_{r>=i} dBq_ri (q_r * Gamma_ri)
      dV = beta * R_V     dQ = dQg * e^G + X^B
      dK = beta e^G * R_K + dKd * e^{G_C - G} + X^A + Y^A + Y^B
      dG = beta K e^G * R_K + dQg * Qg - dKd * Kd + k X^A - k Y^A + q X^B - k Y^B
      dG_C += sum_r dKd_r * Kd_r + ds * s      dg = the running sum of dG from below

  ``X`` and ``Y`` take ``Gamma`` by the forward's rule: earlier sub-chunks as
  one product a sub-chunk and side with both factors ``<= 1``, the blocks on
  the diagonal a diagonal at a time on the transposed arrays (``dM`` and
  ``dBq`` come to one lane a diagonal by a roll of row ``r`` by ``r`` of their
  **mirrored** columns: Mosaic rolls a row forward by its number, not back),
  so no exponent is positive in the backward either. Each cotangent in its
  primal's dtype; ``dbeta`` leaves in the two layouts ``beta`` came in.

**Heads a program of the in-chunk kernels** (:func:`chunk_heads_per_program`):
neighbours in one batch row, so their number divides ``H`` and their lanes
are whole tiles or the whole row; 8 at the cell's shape (gauge
``pallas.kda.chunk_heads_per_program``).

**Shapes.** A block holds ``C``, ``d_k`` and ``d_v`` whole, so the rule for
blocks (a multiple of the tile, or the whole dimension) holds for any of them
and nothing is padded or refused; there is one path. Mosaic's products are
measured at the published widths alone (128, 128, chunks of 64).

**The in-chunk half with one decay a value head** (Gated DeltaNet;
:func:`gdn_chunk_products`). A second pair, for ``g`` ``[B, L, Hv]`` and
``q, k`` at ``Hk`` key heads dividing ``Hv`` (value head ``j`` reads key head
``j // (Hv / Hk)``), read as the projections leave them: ``q, k`` as ``[B, L,
Hk x d_k]`` (a program's value heads read their key heads through the index
map), ``v`` as ``[B, L, Hv x d_v]``, ``g`` and ``beta`` as the columns of
``[B, Hv / heads, L, heads]``. Nothing is repeated or broadcast. With one
decay a row, ``Gamma_ri = e^{G_r - G_i}`` is the same for every channel:
one ``[C, C]`` mask a head, its exponent masked to ``-inf`` above the
diagonal before the exponential (``<= 0`` by construction: no sub-chunk
split, nothing to overflow, for any decay), and

    A = strict_lower(k k^T * Gamma)      Bq = q k^T * Gamma
    T = (I + Diag(beta) A)^-1            U = T (beta V)    W = T (beta K e^G)
    Qg = Q e^G    Kd = K e^{G_C - G}     s = e^{G_C} across the [1, d_k] lanes

``k k^T`` and ``q k^T`` are one product a key head for its group. ``T`` is
built by blocks from the unit diagonal up, in float32 at
``Precision.HIGHEST`` (:func:`_inverse_above`'s levels, ``log2 C`` of them,
the first ``I - N_1`` with no product): that chain of dependent products is
what the forward costs, so **two value heads sit side by side** in the 128
lanes (``[C, 2C]``), a level is one product of their 64 rows against the
block-diagonal ``[2C, 2C]`` of the two, and every pair's level is taken
before the next (:func:`_inverses_side_by_side`; on a TPU v5e at Qwen3-Next's
shape a call took 16.0 ms a head at a time and 4.9 side by side, PERF.md
§6). The forward rule saves ``T`` a pair side by side.

* :func:`_gdn_fwd_kernel` (``dk_gdn_chunk_fwd``): grid ``(batch x value
  heads / heads a program, chunks)``, both ``parallel``; writes ``U, W, Qg,
  Bq, Kd, s`` exactly as :func:`scan_chunks` reads them, so the scan pair is
  the same for both models.
* :func:`_gdn_bwd_kernel` (``dk_gdn_chunk_bwd``): JAX's derivative of the
  ``jax.numpy`` form (``ops/delta_rule.py::in_chunk_by_jax_numpy``) with
  ``g`` broadcast and ``q, k`` repeated, written for one decay a head. With
  ``R_V``, ``R_K``, ``dT``, ``dM``, ``dA`` as in the per-channel pair above,
  ``E = dA * Gamma`` (strictly lower) and ``F = dBq * Gamma`` (lower; ``dBq``
  above the diagonal meets ``Gamma``'s zeros):

      dbeta = rowsum(dM * A) + rowsum(R_V * V) + rowsum(R_K * K e^G)
      dV = beta * R_V      dQ = F k + dQg * e^G
      dK = E k + E^T k + F^T q + beta e^G * R_K + dKd * e^{G_C - G}
      P = dA * A + dBq * Bq                  (A, Bq unrounded, float32)
      dG = rowsum(P) - colsum(P) + rowsum(beta K e^G * R_K + dQg * Qg
           - dKd * Kd)
      dG_C += sum_r rowsum(dKd * Kd)_r + sum(ds) * e^{G_C}
      dg = the running sum of dG from below

  ``dQ`` and ``dK`` are summed over a key head's group inside the program and
  written once at key width; ``colsum(P)`` comes to a column through the
  diagonal (one term a row: exact); ``ds`` is summed over its ``[1, d_k]``
  lanes; ``dg`` and ``dbeta`` leave as ``[B, L, Hv]``. The two float32
  products ``T^T dT T^T`` of every head are taken in lockstep.

**Heads a program of the per-head pair**
(:func:`gdn_chunk_heads_per_program`): whole groups of one batch row, whole
lane tiles at the key and the value heads; 8 value heads over 4 key heads at
the cell's shape (gauge ``pallas.gdn.chunk_heads_per_program``; counter
``pallas.gdn.chunk_calls`` counts the pair's traced calls).

Every kernel keeps the caller's named scope: forward, recomputed and
backward calls carry ``dk_kda`` (``dk_gdn``, ``dk_ssd``) in their
``op_name``. Off a TPU the same kernels run under the Pallas interpreter
(``ops/pallas/mode.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distkeras_tpu.ops.pallas import mode
from distkeras_tpu.ops.pallas.flash_attention import _lanes

_VMEM_BUDGET = 12 * 2 ** 20  # of Mosaic's 16 MiB of scoped VMEM on a v5e


def heads_per_program(rows: int, chunk: int, d_k: int, d_v: int,
                      itemsize: int) -> int:
    """Heads one program holds: the largest of 8, 4, 2, 1 that divides
    ``rows`` (batch x heads) and fits :data:`_VMEM_BUDGET`. A head's bytes in
    the backward kernel, the larger: every block in and out twice (the
    pipeline's two buffers; a row takes whole tiles of 128 lanes) and the
    carried ``dS``."""
    k, v, c = _lanes(d_k), _lanes(d_v), _lanes(chunk)
    state = d_v * k * 4
    blocks = (state                           # the saved state
              + 2 * chunk * v * 4             # U and dU
              + 2 * 3 * chunk * k * itemsize  # W, Qg, Kd and theirs
              + 2 * chunk * c * itemsize      # Bq, dBq
              + chunk * v * itemsize)         # dO
    return max(h for h in (8, 4, 2, 1)
               if rows % h == 0
               and (h == 1 or h * (2 * blocks + state) <= _VMEM_BUDGET))


_EXACT = jax.lax.Precision.HIGHEST  # float32 operands: six passes


def _nn(a, b, precision=None):
    return jnp.dot(a, b, preferred_element_type=jnp.float32,
                   precision=precision)


def _nt(a, b, precision=None):  # a b^T: contracts the minor dimension of both
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=precision)


def _tn(a, b, precision=None):  # a^T b: contracts dimension 0 of both
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=precision)


def _fwd_kernel(*refs, heads: int, with_w: bool):
    if not with_w:  # no W is read: P = U
        refs = refs[:1] + (None,) + refs[1:]
    u_ref, w_ref, qg_ref, bq_ref, kd_ref, s_ref, o_ref, *rest = refs
    *entering, state = rest  # the forward rule's output, then the scratch
    dt = qg_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    for h in range(heads):
        St = state[h]                                        # [d_v, d_k]
        if entering:
            entering[0][h, 0] = St
        Sd = St.astype(dt)
        if w_ref is None:
            P = u_ref[h, 0].astype(dt)
        else:
            P = (u_ref[h, 0] - _nt(w_ref[h, 0], Sd)).astype(dt)  # [C, d_v]
        o_ref[h, 0] = (_nt(qg_ref[h, 0], Sd)
                       + _nn(bq_ref[h, 0], P)).astype(o_ref.dtype)
        state[h] = s_ref[h, 0] * St + _tn(P, kd_ref[h, 0])


def _bwd_kernel(*refs, heads: int, with_w: bool):
    if not with_w:  # no W read, no dW written, no W^T dP in dS
        refs = refs[:1] + (None,) + refs[1:8] + (None,) + refs[8:]
    (u_ref, w_ref, qg_ref, bq_ref, kd_ref, s_ref, st_ref, do_ref, du_ref,
     dw_ref, dqg_ref, dbq_ref, dkd_ref, ds_ref, dstate) = refs
    dt = qg_ref.dtype

    @pl.when(pl.program_id(1) == 0)  # the last chunk: the grid runs it first
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    for h in range(heads):
        St, dSt = st_ref[h, 0], dstate[h]                    # [d_v, d_k]
        Sd, dSd, dO = St.astype(dt), dSt.astype(dt), do_ref[h, 0]
        if w_ref is None:
            qg = qg_ref[h, 0]
            P = u_ref[h, 0].astype(dt)
        else:
            w, qg = w_ref[h, 0], qg_ref[h, 0]
            P = (u_ref[h, 0] - _nt(w, Sd)).astype(dt)        # [C, d_v]
        dP32 = _tn(bq_ref[h, 0], dO) + _nt(kd_ref[h, 0], dSd)
        if w_ref is not None:
            dP = dP32.astype(dt)
        du_ref[h, 0] = dP32.astype(du_ref.dtype)
        if w_ref is not None:
            dw_ref[h, 0] = (-_nn(dP, Sd)).astype(dw_ref.dtype)
        dqg_ref[h, 0] = _nn(dO, Sd).astype(dqg_ref.dtype)
        dbq_ref[h, 0] = _nt(dO, P).astype(dbq_ref.dtype)
        dkd_ref[h, 0] = _nn(P, dSd).astype(dkd_ref.dtype)
        ds_ref[h, 0] = jnp.sum(dSt * St, axis=0, keepdims=True)
        if w_ref is None:
            dstate[h] = _tn(dO, qg) + s_ref[h, 0] * dSt
        else:
            dstate[h] = _tn(dO, qg) - _tn(dP, w) + s_ref[h, 0] * dSt


def _specs(heads: int, C: int, K: int, V: int, chunks: int | None = None):
    """One chunk's blocks of ``heads`` heads of ``U``, ``W``, ``Qg``, ``Bq``,
    ``Kd``, the decay, the saved state and ``O`` (each ``[rows, N, ...]``),
    in that order; ``chunks``: their number, for a grid that walks them
    backwards."""
    def spec(*dims):
        def index(i, n):
            return (i, n if chunks is None else chunks - 1 - n) \
                + (0,) * len(dims)
        return pl.BlockSpec((heads, 1) + dims, index,
                            memory_space=pltpu.VMEM)

    return [spec(*dims) for dims in ((C, V), (C, K), (C, K), (C, C), (C, K),
                                     (1, K), (V, K), (C, V))]


def _compiler_kw(interpret: bool,
                 semantics=("parallel", "arbitrary")) -> dict:
    """The scan: programs of different heads in any order, a head's chunks in
    theirs. The in-chunk kernels: every program in any order."""
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=semantics)}


#: As ``flash_attention._traced_once``: a model calls these once a layer and
#: pass with one shape; jit caches the trace of the unrolled heads, and
#: ``inline`` replays it under the caller's name stack.
_traced_once = functools.partial(jax.jit, inline=True)


def _without_w(items, W):
    """``items`` less its second (``W``'s place) where there is no ``W``."""
    return list(items) if W is not None else [items[0], *items[2:]]


@functools.partial(_traced_once, static_argnames=("heads", "interpret", "save"))
def _scan_fwd(U, W, Qg, Bq, Kd, s, heads: int, interpret: bool, save: bool):
    """``O`` ``[rows, N, C, d_v]`` in ``Qg``'s dtype and, with ``save``, the
    transposed state entering each chunk ``[rows, N, d_v, d_k]`` float32.
    ``W=None``: ``P = U``, and nothing of ``W`` is read."""
    rows, N, C, V = U.shape
    K = Qg.shape[-1]
    *in_specs, entering, out = _specs(heads, C, K, V)
    out_shape = [jax.ShapeDtypeStruct((rows, N, C, V), Qg.dtype)]
    if save:
        out_shape.append(jax.ShapeDtypeStruct((rows, N, V, K), jnp.float32))
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads, with_w=W is not None),
        grid=(rows // heads, N), in_specs=_without_w(in_specs, W),
        out_specs=[out, entering][:len(out_shape)], out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((heads, V, K), jnp.float32)],
        interpret=interpret, name="dk_kda_scan_fwd",
        **_compiler_kw(interpret))
    # The kernel's name (the Mosaic call's, on a chip) and the scope of the
    # same name (the interpreted ops', on a CPU) are what a trace finds it by.
    with jax.named_scope("dk_kda_scan_fwd"):
        return call(*_without_w((U, W, Qg, Bq, Kd, s), W))


@functools.partial(_traced_once, static_argnames=("heads", "interpret"))
def _scan_bwd(U, W, Qg, Bq, Kd, s, entering, dO, heads: int, interpret: bool):
    rows, N, C, V = U.shape
    K = Qg.shape[-1]
    specs = _specs(heads, C, K, V, chunks=N)
    primals = _without_w((U, W, Qg, Bq, Kd, s), W)
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads, with_w=W is not None),
        grid=(rows // heads, N), in_specs=_without_w(specs, W),
        out_specs=_without_w(specs[:6], W),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in primals],
        scratch_shapes=[pltpu.VMEM((heads, V, K), jnp.float32)],
        interpret=interpret, name="dk_kda_scan_bwd",
        **_compiler_kw(interpret))
    with jax.named_scope("dk_kda_scan_bwd"):
        grads = list(call(*primals, entering, dO))
    return tuple(grads if W is not None else grads[:1] + [None] + grads[1:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan(U, W, Qg, Bq, Kd, s, heads, interpret):
    return _scan_fwd(U, W, Qg, Bq, Kd, s, heads=heads, interpret=interpret,
                     save=False)[0]


def _scan_fwd_rule(U, W, Qg, Bq, Kd, s, heads, interpret):
    out, entering = _scan_fwd(U, W, Qg, Bq, Kd, s, heads=heads,
                              interpret=interpret, save=True)
    return out, (U, W, Qg, Bq, Kd, s, entering)


def _scan_bwd_rule(heads, interpret, res, dO):
    return _scan_bwd(*res, dO, heads=heads, interpret=interpret)


_scan.defvjp(_scan_fwd_rule, _scan_bwd_rule)


def scan_chunks(U, W, Qg, Bq, Kd, shrink, heads: int | None = None,
                interpret: bool | None = None):
    """``O`` of the three lines above for every chunk, from a zero state.
    ``U``: [B, H, N, C, d_v] float32 (``v``'s dtype, a state-space layer);
    ``W, Qg, Kd``: [.., d_k] and ``Bq``: [.., C, C] (lower) in the products'
    dtype; ``shrink``: [B, H, N, d_k] float32, the decay of a whole chunk.
    ``W=None`` is the case with no ``W`` (a state-space layer, Mamba-2:
    ``P = U``), static: no ``W`` is read, no ``W S`` product, no ``dW`` and
    no ``W^T dP`` in the backward; the counter ``pallas.ssd.scan_calls``
    counts such calls as they are traced. ``d_v`` may differ from ``d_k``.
    Returns [B, H, N, C, d_v] in the products' dtype. Differentiable in all
    it is given. ``heads`` a program follows the shapes
    (:func:`heads_per_program`); ``interpret=None`` compiles on a TPU and
    interprets elsewhere."""
    interpret = mode.interpret("kda_scan", interpret)
    B, H, N, C, V = U.shape
    K = Qg.shape[-1]
    rows = B * H
    if heads is None:
        heads = heads_per_program(rows, C, K, V, jnp.dtype(Qg.dtype).itemsize)
    if rows % heads:
        raise ValueError(f"{heads} heads a program do not divide {B} x {H}")
    from distkeras_tpu import telemetry

    telemetry.gauge("pallas.kda.heads_per_program").set(heads)
    telemetry.gauge("pallas.kda.grid_steps").set(rows // heads * N)
    if W is None:
        telemetry.counter("pallas.ssd.scan_calls").add(1)

    def flat(x):
        return None if x is None else x.reshape(rows, N, *x.shape[3:])

    out = _scan(flat(U), flat(W), flat(Qg), flat(Bq), flat(Kd),
                shrink.reshape(rows, N, 1, K), heads, interpret)
    return out.reshape(B, H, N, C, V)


# -- the in-chunk half ---------------------------------------------------------

_LANES = 128


def chunk_heads_per_program(heads: int, chunk: int, d_k: int, d_v: int,
                            itemsize: int) -> int:
    """Heads one program of the in-chunk kernels holds. A program's heads
    are neighbours in one batch row of ``[B, L, H x d]``, so their number
    divides ``heads`` and their lanes are whole tiles of 128 or the whole
    row; of those the most, up to 8, whose blocks fit :data:`_VMEM_BUDGET` in
    the backward kernel, the larger (every block in and out twice); where
    none does, the fewest that keep the rule for blocks."""
    k, v, c = _lanes(d_k), _lanes(d_v), _lanes(chunk)
    blocks = (chunk * (3 * k + v) * itemsize      # q, k, v and dKd, dQg, dW
              + 2 * chunk * k * 4                 # g, dg
              + 2 * chunk * (k + v) * itemsize    # dq, dk, dv
              + 2 * chunk * v * 4                 # dU
              + chunk * c * (itemsize + 4))       # dBq, T
    whole = [h for h in range(1, heads + 1) if heads % h == 0 and (
        h == heads or not (h * d_k % _LANES or h * d_v % _LANES))]
    fit = [h for h in whole if h <= 8 and (
        h == 1 or 2 * h * blocks <= _VMEM_BUDGET)]
    return max(fit) if fit else min(whole)


def _pack(heads: int, chunk: int) -> tuple[int, int]:
    """Heads whose positions share the lanes of the transposed arrays, and
    those lanes: whole tiles of 128."""
    pack = max(p for p in (8, 4, 2, 1)
               if heads % p == 0 and (p == 1 or p * chunk <= _LANES))
    return pack, _lanes(pack * chunk)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _running_sum(x, up: bool = False):
    """The running sum down the rows of ``[C, K]`` float32 (``up``: of a row
    and those below it), ``log2 C`` shifted adds."""
    rows = x.shape[0]
    row, step = _iota(x.shape, 0), 1
    while step < rows:
        x = x + jnp.where(row < rows - step if up else row >= step,
                          pltpu.roll(x, rows - step if up else step, 0), 0.0)
        step *= 2
    return x


def _across(xs, wide: int):
    """Heads' ``[C, K]`` float32 as one ``[K, wide]``: channels on sublanes,
    ``(head, position)`` on lanes, zeros past the last head."""
    rows = sum(x.shape[0] for x in xs)
    if rows < wide:
        xs = list(xs) + [jnp.zeros((wide - rows, xs[0].shape[1]),
                                   jnp.float32)]
    return jnp.concatenate(xs, 0).T


def _diagonals(Gt, kt, qt, sub: int):
    """Inside the ``sub`` x ``sub`` blocks on the diagonal, as diagonals:
    rows ``a[d]``, ``b[d]`` ``[1, wide]`` with ``a[d][r] = A[r, r - d]`` (``b``:
    ``Bq``), zero where ``r - d`` lies in the sub-chunk before. The
    difference ``G_r - G_{r-d}`` a channel is masked to ``-inf`` before the
    exponential; the sum over channels is over sublanes: vreg adds."""
    lane = _iota((1, Gt.shape[1]), 1)
    a, b = [], []
    for d in range(sub):
        if d == 0:
            kk = kt
        else:
            inside = (lane & (sub - 1)) >= d
            kk = pltpu.roll(kt, d, 1) * jnp.exp(jnp.where(
                inside, Gt - pltpu.roll(Gt, d, 1), -jnp.inf))
        a.append(jnp.sum(kt * kk, axis=0, keepdims=True))
        b.append(jnp.sum(qt * kk, axis=0, keepdims=True))
    return a, b


def _substituted(n):
    """The diagonals ``t[d][r] = T[r, r - d]`` of the inverse of the unit
    lower-triangular blocks whose diagonals below the first are ``n[1:]``:
    forward substitution, ``t_d = -sum_{e=1..d} n_e * t_{d-e}`` shifted by
    ``e``, float32 on the VPU. The term of the diagonal found last is added
    last, so a diagonal waits on one product of the one before it."""
    t = [jnp.ones_like(n[0])]
    for d in range(1, len(n)):
        acc = n[d]
        for e in range(d - 1, 0, -1):
            acc = acc + n[e] * pltpu.roll(t[d - e], e, 1)
        t.append(-acc)
    return t


def _from_diagonals(families, scratch, pack: int, C: int):
    """``[C, C]`` blocks a head from diagonals, a list a head of one a family
    (at most two). A family's ``sub`` rows ``[1, wide]`` are stored backwards
    in ``scratch`` ``[128, wide]``, the second family half way down; its
    transpose holds a position a sublane, and rolling row ``r`` by ``r`` puts
    diagonal ``d`` of row ``r`` on column ``r - d``, the second family's half
    the lanes on."""
    sub, half = len(families[0]), _LANES // 2
    for j, rows in enumerate(families):
        for d, row in enumerate(rows):
            at = j * half + sub - 1 - d
            scratch[at:at + 1, :] = row
    across = scratch[...].T                                  # [wide, 128]
    lane = _iota((C, _LANES), 1)
    held = (lane & (half - 1)) < sub
    out = []
    for h in range(pack):
        turned = pltpu.roll(
            jnp.where(held, across[h * C:(h + 1) * C], 0.0),
            _LANES - (sub - 1), 1, stride=1, stride_axis=0)
        out.append([turned[:, j * half:j * half + C]
                    for j in range(len(families))])
    return out


def _earlier_pairs(G, k, q, sub: int, dt):
    """Rows against the columns of earlier sub-chunks, ``A`` and ``Bq``
    ``[C, C]`` float32 (zero on and above the diagonal blocks): a row's
    factor ``e^{G_r - F_I}`` and a column's ``e^{F_I - G_i}`` are both ``<=
    1``, the pair one product of operands rounded to ``dt``."""
    C = G.shape[0]
    position = _iota((C, 1), 0)
    a, b = [jnp.zeros((sub, C), jnp.float32)], [jnp.zeros((sub, C),
                                                           jnp.float32)]
    for start in range(sub, C, sub):
        mine = slice(start, start + sub)
        first = G[start:start + 1]
        rows = jnp.exp(G[mine] - first)
        cols = (k * jnp.exp(jnp.where(position < start, first - G,
                                      -jnp.inf))).astype(dt)
        both = _nt(jnp.concatenate([k[mine] * rows, q[mine] * rows],
                                   0).astype(dt), cols)     # [2 sub, C]
        a.append(both[:sub])
        b.append(both[sub:])
    return jnp.concatenate(a, 0), jnp.concatenate(b, 0)


def _level(s: int, row, col):
    """Where ``N_s`` lies: the lower-left ``s`` x ``s`` block of each ``2s``
    x ``2s`` block on the diagonal."""
    return ((row ^ col) < 2 * s) & ((row & s) != 0) & ((col & s) == 0)


def _inverse_above(X, N, sub: int):
    """``(I + N)^-1`` from ``X``, the inverse of its ``sub`` x ``sub``
    diagonal blocks, by blocks upward: a level is ``X - X N_s X`` with
    ``N_s`` the lower-left blocks of that level, picked by a mask."""
    C = X.shape[0]
    row, col, s = _iota((C, C), 0), _iota((C, C), 1), sub
    while s < C:
        X = X - _nn(_nn(X, jnp.where(_level(s, row, col), N, 0.0), _EXACT), X,
                    _EXACT)
        s *= 2
    return X


def _chunk_fwd_kernel(q_ref, k_ref, v_ref, g_ref, bc_ref, br_ref, u_ref,
                      w_ref, qg_ref, bq_ref, kd_ref, s_ref, last_ref, *rest,
                      heads: int, pack: int, sub: int):
    *saved, diagonals = rest  # the forward rule's output, then the scratch
    C, K, V = q_ref.shape[1], q_ref.shape[2] // heads, v_ref.shape[2] // heads
    dt, f32 = q_ref.dtype, jnp.float32
    wide = diagonals.shape[1]
    for first in range(0, heads, pack):
        group = range(first, first + pack)
        G = [_running_sum(g_ref[0, :, h * K:(h + 1) * K]) for h in group]
        q = [q_ref[0, :, h * K:(h + 1) * K].astype(f32) for h in group]
        k = [k_ref[0, :, h * K:(h + 1) * K].astype(f32) for h in group]
        a, b = _diagonals(_across(G, wide), _across(k, wide),
                          _across(q, wide), sub)
        beta_row = br_ref[0, 0, 0, first // pack:first // pack + 1, :]
        t = _substituted([beta_row * a_d for a_d in a])
        blocks = _from_diagonals([t, b], diagonals, pack, C)
        for j, h in enumerate(group):
            beta = bc_ref[0, 0, :, h:h + 1]                  # [C, 1]
            A, Bq = _earlier_pairs(G[j], k[j], q[j], sub, dt)
            T = _inverse_above(blocks[j][0], beta * A, sub)
            if saved:
                saved[0][h, 0] = T
            decay, last, Td = jnp.exp(G[j]), G[j][C - 1:], T.astype(dt)
            v = v_ref[0, :, h * V:(h + 1) * V].astype(f32)
            u_ref[h, 0] = _nn(Td, (beta * v).astype(dt))
            w_ref[h, 0] = _nn(Td, (beta * k[j] * decay).astype(dt)).astype(dt)
            qg_ref[h, 0] = (q[j] * decay).astype(dt)
            bq_ref[h, 0] = (Bq + blocks[j][1]).astype(dt)
            kd_ref[h, 0] = (k[j] * jnp.exp(last - G[j])).astype(dt)
            s_ref[h, 0] = jnp.exp(last)
            last_ref[h, 0] = last


def _chunk_specs(H: int, C: int, K: int, V: int, heads: int, pack: int,
                 wide: int):
    """Block specs of one program (``heads`` heads of one batch row, one
    chunk): the inputs ``q, k, v, g`` as ``[B, L, H x d]`` and ``beta`` twice
    (a column a head; a row a pack of heads), then the outputs ``U, W, Qg,
    Bq, Kd, s, last`` and ``T`` as ``[B x H, N, ...]``."""
    groups = H // heads

    def spec(block, index):
        return pl.BlockSpec(block, index, memory_space=pltpu.VMEM)

    def wide_rows(d):
        return spec((1, C, heads * d), lambda i, n: (i // groups, n,
                                                      i % groups))

    def chunk_rows(*dims):
        return spec((heads, 1) + dims, lambda i, n: (i, n) + (0,) * len(dims))

    ins = [wide_rows(K), wide_rows(K), wide_rows(V), wide_rows(K),
           spec((1, 1, C, heads),
                lambda i, n: (i // groups, i % groups, n, 0)),
           spec((1, 1, 1, heads // pack, wide),
                lambda i, n: (i // groups, i % groups, n, 0, 0))]
    outs = [chunk_rows(C, V), chunk_rows(C, K), chunk_rows(C, K),
            chunk_rows(C, C), chunk_rows(C, K), chunk_rows(1, K),
            chunk_rows(1, K), chunk_rows(C, C)]
    return ins, outs


def _betas(beta, C: int, heads: int, pack: int, wide: int):
    """``beta`` ``[B, L, H]`` as the kernels read it: ``[B, H / heads, L,
    heads]`` (a column a head) and ``[B, H / heads, N, heads / pack, wide]``
    (the rows of a pack of heads side by side, zeros past them)."""
    B, L, H = beta.shape
    groups, N = H // heads, L // C
    cols = jnp.moveaxis(beta.reshape(B, L, groups, heads), 2, 1)
    rows = jnp.transpose(beta.reshape(B, N, C, groups, heads // pack, pack),
                         (0, 3, 1, 4, 5, 2)).reshape(
                             B, groups, N, heads // pack, pack * C)
    return cols, jnp.pad(rows, [(0, 0)] * 4 + [(0, wide - pack * C)])


@functools.partial(_traced_once, static_argnames=(
    "chunk", "sub", "heads", "interpret", "save"))
def _chunk_fwd(q, k, v, g, beta, chunk: int, sub: int, heads: int,
               interpret: bool, save: bool):
    """``U, W, Qg, Bq, Kd, s`` and the chunks' summed log-decay, as ``[B x H,
    N, ...]``, from ``q, k, v, g`` ``[B, L, H x d]`` and ``beta`` ``[B, L,
    H]``; with ``save`` also ``T`` ``[B x H, N, C, C]`` float32."""
    B, L, H = beta.shape
    K, V, C, N = k.shape[-1] // H, v.shape[-1] // H, chunk, L // chunk
    pack, wide = _pack(heads, C)
    ins, outs = _chunk_specs(H, C, K, V, heads, pack, wide)
    rows, dt, f32 = B * H, v.dtype, jnp.float32
    out_shape = [jax.ShapeDtypeStruct((rows, N) + dims, dtype)
                 for dims, dtype in (((C, V), f32), ((C, K), dt), ((C, K), dt),
                                     ((C, C), dt), ((C, K), dt), ((1, K), f32),
                                     ((1, K), f32), ((C, C), f32))]
    kept = len(outs) if save else len(outs) - 1
    call = pl.pallas_call(
        functools.partial(_chunk_fwd_kernel, heads=heads, pack=pack, sub=sub),
        grid=(rows // heads, N), in_specs=ins, out_specs=outs[:kept],
        out_shape=out_shape[:kept],
        scratch_shapes=[pltpu.VMEM((_LANES, wide), f32)],
        interpret=interpret, name="dk_kda_chunk_fwd",
        **_compiler_kw(interpret, ("parallel", "parallel")))
    with jax.named_scope("dk_kda_chunk_fwd"):
        return tuple(call(q, k, v, g, *_betas(beta, C, heads, pack, wide)))


def _chunk_bwd_kernel(q_ref, k_ref, v_ref, g_ref, bc_ref, br_ref, t_ref,
                      du_ref, dw_ref, dqg_ref, dbq_ref, dkd_ref, ds_ref,
                      dq_ref, dk_ref, dv_ref, dg_ref, dbc_ref, dbr_ref,
                      diagonals, *, heads: int, pack: int, sub: int):
    C, K, V = q_ref.shape[1], q_ref.shape[2] // heads, v_ref.shape[2] // heads
    dt, f32 = q_ref.dtype, jnp.float32
    wide = diagonals.shape[1]
    row, col = _iota((C, C), 0), _iota((C, C), 1)
    lane = _iota((C, _LANES), 1)
    # x [C, C] @ mirror: x's columns in reverse order, [C, 128] (dBq's half
    # the lanes on). A row can be rolled forward by its own number alone, so
    # a diagonal comes to one lane only from mirrored columns.
    half = _LANES // 2
    mirror = (_iota((C, _LANES), 0) + lane == C - 1).astype(f32)
    mirror_on = (_iota((C, _LANES), 0) + lane == half + C - 1).astype(dt)
    position, column = _iota((C, 1), 0), _iota((sub, C), 1)
    for first in range(0, heads, pack):
        group = range(first, first + pack)
        G = [_running_sum(g_ref[0, :, h * K:(h + 1) * K]) for h in group]
        q = [q_ref[0, :, h * K:(h + 1) * K].astype(f32) for h in group]
        k = [k_ref[0, :, h * K:(h + 1) * K].astype(f32) for h in group]
        # What flows into T, and from there into A; with dBq, a position a
        # lane and a diagonal a row, for the blocks on the diagonal.
        held, families = [], []
        for j, h in enumerate(group):
            beta, T = bc_ref[0, 0, :, h:h + 1], t_ref[h, 0]
            decay, Td = jnp.exp(G[j]), T.astype(dt)
            ke = k[j] * decay
            v = v_ref[0, :, h * V:(h + 1) * V].astype(f32)
            dU, dW = du_ref[h, 0].astype(dt), dw_ref[h, 0]
            dT = _nt(dU, (beta * v).astype(dt)) \
                + _nt(dW, (beta * ke).astype(dt))
            dM = jnp.where(row > col,
                           -_nt(_tn(T, dT, _EXACT), T, _EXACT), 0.0)
            dBq = jnp.where(row >= col, dbq_ref[h, 0], 0)
            families.append(pltpu.roll(
                _nn(dM, mirror, _EXACT) + _nn(dBq, mirror_on),
                _LANES - (C - 1), 1,
                stride=1, stride_axis=0))
            held.append((beta, decay, ke, v, dM, dBq.astype(f32),
                         _tn(Td, dU), _tn(Td, dW)))
        if pack * C < wide:
            families.append(jnp.zeros((wide - pack * C, _LANES), f32))
        diagonals[...] = jnp.concatenate(families, 0).T
        # The blocks on the diagonal, a diagonal at a time.
        Gt, kt, qt = _across(G, wide), _across(k, wide), _across(q, wide)
        beta_row = br_ref[0, 0, 0, first // pack:first // pack + 1, :]
        lanes = _iota((1, wide), 1)
        db = diagonals[half:half + 1, :]
        XA, XB, Y = jnp.zeros_like(kt), db * kt, db * qt
        dbeta_row = jnp.zeros((1, wide), f32)
        for d in range(1, sub):
            dm = diagonals[d:d + 1, :]
            db = diagonals[half + d:half + d + 1, :]
            inside = (lanes & (sub - 1)) >= d
            pair = jnp.exp(jnp.where(inside, Gt - pltpu.roll(Gt, d, 1),
                                     -jnp.inf))
            kk = pltpu.roll(kt, d, 1) * pair
            da = beta_row * dm
            dbeta_row = dbeta_row + dm * jnp.sum(kt * kk, axis=0,
                                                 keepdims=True)
            XA = XA + da * kk
            XB = XB + db * kk
            Y = Y + pltpu.roll((da * kt + db * qt) * pair, wide - d, 1)
        dbr_ref[0, 0, 0, first // pack:first // pack + 1, :] = dbeta_row
        back = [x.T for x in (XB, XA + Y, kt * XA + qt * XB - kt * Y)]
        for j, h in enumerate(group):
            beta, decay, ke, v, dM, dBq, R_V, R_K = held[j]
            dq, dk, dG = (x[j * C:(j + 1) * C] for x in back)
            # Rows against the columns of earlier sub-chunks.
            XA, XB = [jnp.zeros((sub, K), f32)], [jnp.zeros((sub, K), f32)]
            dbeta = [jnp.zeros((sub, 1), f32)]
            Y = jnp.zeros((C, K), f32)
            for start in range(sub, C, sub):
                mine = slice(start, start + sub)
                head = G[j][start:start + 1]
                rows = jnp.exp(G[j][mine] - head)
                shrink = jnp.exp(jnp.where(position < start, head - G[j],
                                           -jnp.inf))
                cols = (k[j] * shrink).astype(dt)
                kq = jnp.concatenate([k[j][mine] * rows, q[j][mine] * rows],
                                     0).astype(dt)
                dMI = jnp.where(column < start, dM[mine], 0.0)
                dAB = jnp.concatenate([
                    beta[mine] * dMI,
                    jnp.where(column < start, dBq[mine], 0.0)], 0).astype(dt)
                dbeta.append(jnp.sum(dMI * _nt(kq[:sub], cols), axis=1,
                                     keepdims=True))
                X = _nn(dAB, cols)
                XA.append(rows * X[:sub])
                XB.append(rows * X[sub:])
                Y = Y + shrink * _tn(dAB, kq)
            XA, XB = jnp.concatenate(XA, 0), jnp.concatenate(XB, 0)
            dQg, dKd = dqg_ref[h, 0].astype(f32), dkd_ref[h, 0].astype(f32)
            last = G[j][C - 1:]
            tail = jnp.exp(last - G[j])
            through_kd = dKd * k[j] * tail                   # dKd * Kd
            dG = dG + beta * ke * R_K + dQg * q[j] * decay - through_kd \
                + k[j] * (XA - Y) + q[j] * XB
            dG = jnp.where(_iota((C, K), 0) == C - 1, dG + jnp.sum(
                through_kd, axis=0, keepdims=True)
                + ds_ref[h, 0] * jnp.exp(last), dG)
            dg_ref[0, :, h * K:(h + 1) * K] = _running_sum(dG, up=True)
            dq_ref[0, :, h * K:(h + 1) * K] = (dq + dQg * decay + XB).astype(
                dt)
            dk_ref[0, :, h * K:(h + 1) * K] = (
                dk + beta * decay * R_K + dKd * tail + XA + Y).astype(dt)
            dv_ref[0, :, h * V:(h + 1) * V] = (beta * R_V).astype(dt)
            dbc_ref[0, 0, :, h:h + 1] = jnp.concatenate(dbeta, 0) \
                + jnp.sum(R_V * v, axis=1, keepdims=True) \
                + jnp.sum(R_K * ke, axis=1, keepdims=True)


@functools.partial(_traced_once, static_argnames=(
    "chunk", "sub", "heads", "interpret"))
def _chunk_bwd(q, k, v, g, beta, T, dU, dW, dQg, dBq, dKd, ds, chunk: int,
               sub: int, heads: int, interpret: bool):
    """The cotangents of ``q, k, v, g`` ``[B, L, H x d]`` and of ``beta``
    ``[B, L, H]``, each in its primal's dtype."""
    B, L, H = beta.shape
    K, V, C, N = k.shape[-1] // H, v.shape[-1] // H, chunk, L // chunk
    pack, wide = _pack(heads, C)
    ins, outs = _chunk_specs(H, C, K, V, heads, pack, wide)
    cols, rows = _betas(beta, C, heads, pack, wide)
    shapes = [jax.ShapeDtypeStruct(x.shape, x.dtype)
              for x in (q, k, v, g, cols, rows)]
    call = pl.pallas_call(
        functools.partial(_chunk_bwd_kernel, heads=heads, pack=pack, sub=sub),
        grid=(B * H // heads, N), in_specs=ins + [outs[7]] + outs[:6],
        out_specs=ins, out_shape=shapes,
        scratch_shapes=[pltpu.VMEM((_LANES, wide), jnp.float32)],
        interpret=interpret, name="dk_kda_chunk_bwd",
        **_compiler_kw(interpret, ("parallel", "parallel")))
    with jax.named_scope("dk_kda_chunk_bwd"):
        dq, dk, dv, dg, dcols, drows = call(q, k, v, g, cols, rows, T, dU, dW,
                                            dQg, dBq, dKd, ds)
    groups = H // heads
    drows = jnp.transpose(drows[..., :pack * C].reshape(
        B, groups, N, heads // pack, pack, C), (0, 2, 5, 1, 3, 4))
    return dq, dk, dv, dg, (jnp.moveaxis(dcols, 1, 2).reshape(B, L, H)
                            + drows.reshape(B, L, H))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _chunk(q, k, v, g, beta, chunk, sub, heads, interpret):
    return _chunk_fwd(q, k, v, g, beta, chunk=chunk, sub=sub, heads=heads,
                      interpret=interpret, save=False)


def _chunk_fwd_rule(q, k, v, g, beta, chunk, sub, heads, interpret):
    *out, T = _chunk_fwd(q, k, v, g, beta, chunk=chunk, sub=sub, heads=heads,
                         interpret=interpret, save=True)
    return tuple(out), (q, k, v, g, beta, T)


def _chunk_bwd_rule(chunk, sub, heads, interpret, res, cotangents):
    return _chunk_bwd(*res, *cotangents[:6], chunk=chunk, sub=sub,
                      heads=heads, interpret=interpret)


_chunk.defvjp(_chunk_fwd_rule, _chunk_bwd_rule)


def chunk_products(q, k, v, g, beta, chunk: int, sub: int,
                   heads: int | None = None, interpret: bool | None = None):
    """Everything of a chunk that does not touch the carried state. ``q, k``:
    [B, L, H, d_k] and ``v``: [B, L, H, d_v] in the products' dtype; ``g``:
    [B, L, H, d_k] and ``beta``: [B, L, H], float32 here whatever they come
    as. Returns ``(U, W, Qg, Bq, Kd, s)`` as :func:`scan_chunks` reads them
    and the summed log-decay of each chunk ``[B, H, N, d_k]`` (no cotangent
    flows into that one). Differentiable in all five. ``heads`` a program
    follows the shapes (:func:`chunk_heads_per_program`); ``interpret=None``
    compiles on a TPU and interprets elsewhere."""
    interpret = mode.interpret("kda_chunk", interpret)
    B, L, H, K = k.shape
    V, N = v.shape[-1], L // chunk
    if heads is None:
        heads = chunk_heads_per_program(H, chunk, K, V,
                                        jnp.dtype(v.dtype).itemsize)
    if H % heads:
        raise ValueError(f"{heads} heads a program do not divide {H}")
    from distkeras_tpu import telemetry

    telemetry.gauge("pallas.kda.chunk_heads_per_program").set(heads)
    telemetry.gauge("pallas.kda.grid_steps").set(B * H // heads * N)
    f32 = jnp.float32
    *products, last = _chunk(
        q.reshape(B, L, H * K), k.reshape(B, L, H * K),
        v.reshape(B, L, H * V), g.astype(f32).reshape(B, L, H * K),
        beta.astype(f32), chunk, sub, heads, interpret)
    return tuple(x.reshape(B, H, N, *x.shape[2:]) for x in products[:5]) + (
        products[5].reshape(B, H, N, K), last.reshape(B, H, N, K))


# -- the in-chunk half with one decay a value head -----------------------------

def gdn_chunk_heads_per_program(value_heads: int, key_heads: int, chunk: int,
                                d_k: int, d_v: int, itemsize: int) -> int:
    """Value heads one program of ``dk_gdn_chunk_*`` holds: whole groups
    (their key heads are read once and their cotangents summed inside the
    program), neighbours in one batch row whose lanes, at the key heads and
    at the value heads, are whole tiles of 128 or the whole row; of those
    the most, up to 8 (or one group where a group is wider), whose blocks fit
    :data:`_VMEM_BUDGET` in the backward kernel, every block in and out
    twice; where none does, the fewest that keep the rule for blocks."""
    group = value_heads // key_heads
    k, v, c = _lanes(d_k), _lanes(d_v), _lanes(chunk)
    blocks = chunk * (4 * k * itemsize / group     # q, k and dq, dk
                      + 2 * v * itemsize + v * 4   # v, dv, dU
                      + 3 * k * itemsize           # dW, dQg, dKd
                      + c * (itemsize + 4))        # dBq, T
    whole = [h for h in range(group, value_heads + 1, group)
             if value_heads % h == 0 and (h == value_heads or not (
                 h // group * d_k % _LANES or h * d_v % _LANES))]
    fit = [h for h in whole if h <= max(8, group) and (
        h == group or 2 * h * blocks <= _VMEM_BUDGET)]
    return max(fit) if fit else min(whole)


def _pairs(heads: int) -> list:
    """Value heads two at a time, side by side in the lanes; an odd last head
    is paired with itself (its second copy is computed and dropped)."""
    return [(a, min(a + 1, heads - 1)) for a in range(0, heads, 2)]


def _sides(a: int, b: int) -> tuple:
    return ((0, a),) if a == b else ((0, a), (1, b))


def _side_by_side(G, a: int, b: int, row, col, right):
    """``Gamma_ri = e^{G_r - G_i}`` for ``i <= r``, else 0, of value heads
    ``a`` and ``b`` side by side ``[C, 2C]``, from ``G`` ``[C, heads]``: a
    column's ``G_i`` is the diagonal summed down the sublanes (one term:
    exact, so the diagonal's exponent is 0), and the difference is masked to
    ``-inf`` before the exponential."""
    Gab = jnp.where(right, G[:, b:b + 1], G[:, a:a + 1])
    along = jnp.sum(jnp.where(row == col, Gab, 0.0), axis=0, keepdims=True)
    return jnp.exp(jnp.where(row >= col, Gab - along, -jnp.inf))


def _pair_gram(x_ref, k_ref, a: int, b: int, group: int, K: int, right):
    """``[x_a k_a^T | x_b k_b^T]`` ``[C, 2C]`` float32 for value heads ``a``
    and ``b``, ``x`` and ``k`` at their key heads: one product where the two
    share a key head."""
    ja, jb = a // group, b // group
    both = jnp.concatenate([k_ref[0, :, ja * K:(ja + 1) * K],
                            k_ref[0, :, jb * K:(jb + 1) * K]], 0)
    left = _nt(x_ref[0, :, ja * K:(ja + 1) * K], both)
    if ja == jb:
        return left
    return jnp.where(right, _nt(x_ref[0, :, jb * K:(jb + 1) * K], both), left)


def _inverses_side_by_side(Ns, row, col, right):
    """``(I + N)^-1`` of each pair ``[C, 2C]`` in ``Ns``, by blocks from the
    unit diagonal up (:func:`_inverse_above`'s levels, ``X - X N_s X`` in
    float32 at ``Precision.HIGHEST``; the first level, from ``X = I``, is ``I
    - N_1``). A product takes the pair's ``C`` rows against the
    block-diagonal ``[2C, 2C]`` of its two heads, and every pair's level is
    taken before the next: the chain of ``2 log2 C`` dependent products is
    what costs, not their passes."""
    C = row.shape[0]

    def blocks(m):
        return jnp.concatenate([jnp.where(right, 0.0, m),
                                jnp.where(right, m, 0.0)], 0)

    Xs = [jnp.where(row == col, 1.0, 0.0) - jnp.where(_level(1, row, col), N,
                                                      0.0) for N in Ns]
    s = 2
    while s < C:
        Ys = [_nn(X, blocks(jnp.where(_level(s, row, col), N, 0.0)), _EXACT)
              for X, N in zip(Xs, Ns)]
        Xs = [X - _nn(Y, blocks(X), _EXACT) for X, Y in zip(Xs, Ys)]
        s *= 2
    return Xs


def _wide_iotas(C: int):
    """``row``, ``col`` (within a head) and ``right`` (the second head) of a
    pair side by side ``[C, 2C]``."""
    row, lane = _iota((C, 2 * C), 0), _iota((C, 2 * C), 1)
    return row, lane & (C - 1), lane >= C


def _gdn_fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, u_ref, w_ref, qg_ref,
                    bq_ref, kd_ref, s_ref, *saved, heads: int, group: int):
    C = q_ref.shape[1]
    K, V = k_ref.shape[2] * group // heads, v_ref.shape[2] // heads
    dt, f32 = q_ref.dtype, jnp.float32
    row, col, right = _wide_iotas(C)
    G = _running_sum(g_ref[0, 0])                            # [C, heads]
    pairs, Ns = _pairs(heads), []
    for a, b in pairs:
        decays = _side_by_side(G, a, b, row, col, right)
        beta = jnp.where(right, b_ref[0, 0, :, b:b + 1],
                         b_ref[0, 0, :, a:a + 1])
        Ns.append(beta * jnp.where(
            row > col, _pair_gram(k_ref, k_ref, a, b, group, K, right)
            * decays, 0.0))
        Bq = _pair_gram(q_ref, k_ref, a, b, group, K, right) * decays
        for side, h in _sides(a, b):
            bq_ref[h, 0] = Bq[:, side * C:(side + 1) * C].astype(dt)
    for p, ((a, b), T) in enumerate(zip(pairs, _inverses_side_by_side(
            Ns, row, col, right))):
        if saved:
            saved[0][p, 0] = T
        Td = T.astype(dt)
        for side, h in _sides(a, b):
            def lifted(x):  # [C, .] in the pair's rows of this head
                x, zero = x.astype(dt), jnp.zeros(x.shape, dt)
                return jnp.concatenate([zero, x] if side else [x, zero], 0)

            j, beta, Gh = h // group, b_ref[0, 0, :, h:h + 1], G[:, h:h + 1]
            k32, q32 = (ref[0, :, j * K:(j + 1) * K].astype(f32)
                        for ref in (k_ref, q_ref))
            decay, last = jnp.exp(Gh), Gh[C - 1:]
            v = v_ref[0, :, h * V:(h + 1) * V].astype(f32)
            u_ref[h, 0] = _nn(Td, lifted(beta * v))
            w_ref[h, 0] = _nn(Td, lifted(beta * k32 * decay)).astype(dt)
            qg_ref[h, 0] = (q32 * decay).astype(dt)
            kd_ref[h, 0] = (k32 * jnp.exp(last - Gh)).astype(dt)
            s_ref[h, 0] = jnp.broadcast_to(jnp.exp(last), (1, K))


def _gdn_specs(Hv: int, C: int, K: int, V: int, heads: int, group: int):
    """Block specs of one program (``heads`` value heads of one batch row and
    their ``heads / group`` key heads, one chunk): the inputs ``q, k`` as
    ``[B, L, Hk x d_k]``, ``v`` as ``[B, L, Hv x d_v]``, ``g`` and ``beta``
    as columns ``[B, Hv / heads, L, heads]``; then the outputs ``U, W, Qg,
    Bq, Kd, s`` and ``T`` as ``[B x Hv, N, ...]``."""
    groups = Hv // heads

    def spec(block, index):
        return pl.BlockSpec(block, index, memory_space=pltpu.VMEM)

    def wide_rows(lanes):
        return spec((1, C, lanes), lambda i, n: (i // groups, n, i % groups))

    def chunk_rows(*dims):
        return spec((heads, 1) + dims, lambda i, n: (i, n) + (0,) * len(dims))

    column = spec((1, 1, C, heads),
                  lambda i, n: (i // groups, i % groups, n, 0))
    ins = [wide_rows(heads // group * K), wide_rows(heads // group * K),
           wide_rows(heads * V), column, column]
    outs = [chunk_rows(C, V), chunk_rows(C, K), chunk_rows(C, K),
            chunk_rows(C, C), chunk_rows(C, K), chunk_rows(1, K),
            spec((len(_pairs(heads)), 1, C, 2 * C), lambda i, n: (i, n, 0, 0))]
    return ins, outs


def _columns(x, heads: int):
    """``[B, L, Hv]`` as the kernels read it: ``[B, Hv / heads, L, heads]``."""
    B, L, H = x.shape
    return jnp.moveaxis(x.reshape(B, L, H // heads, heads), 2, 1)


@functools.partial(_traced_once, static_argnames=(
    "chunk", "heads", "key_heads", "interpret", "save"))
def _gdn_chunk_fwd(q, k, v, g, beta, chunk: int, heads: int, key_heads: int,
                   interpret: bool, save: bool):
    """``U, W, Qg, Bq, Kd, s`` as ``[B x Hv, N, ...]`` from ``q, k`` ``[B, L,
    Hk x d_k]``, ``v`` ``[B, L, Hv x d_v]``, ``g`` and ``beta`` ``[B, L,
    Hv]``; with ``save`` also ``T`` ``[B x Hv, N, C, C]`` float32."""
    B, L, Hv = beta.shape
    K, V, C, N = k.shape[-1] // key_heads, v.shape[-1] // Hv, chunk, L // chunk
    group = Hv // key_heads
    ins, outs = _gdn_specs(Hv, C, K, V, heads, group)
    rows, dt, f32 = B * Hv, v.dtype, jnp.float32
    out_shape = [jax.ShapeDtypeStruct((rows, N) + dims, dtype)
                 for dims, dtype in (((C, V), f32), ((C, K), dt), ((C, K), dt),
                                     ((C, C), dt), ((C, K), dt),
                                     ((1, K), f32))]
    out_shape.append(jax.ShapeDtypeStruct(
        (rows // heads * len(_pairs(heads)), N, C, 2 * C), f32))
    kept = len(outs) if save else len(outs) - 1
    call = pl.pallas_call(
        functools.partial(_gdn_fwd_kernel, heads=heads, group=group),
        grid=(rows // heads, N), in_specs=ins, out_specs=outs[:kept],
        out_shape=out_shape[:kept], interpret=interpret,
        name="dk_gdn_chunk_fwd",
        **_compiler_kw(interpret, ("parallel", "parallel")))
    with jax.named_scope("dk_gdn_chunk_fwd"):
        return tuple(call(q, k, v, _columns(g, heads), _columns(beta, heads)))


def _gdn_bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, t_ref, du_ref, dw_ref,
                    dqg_ref, dbq_ref, dkd_ref, ds_ref, dq_ref, dk_ref, dv_ref,
                    dg_ref, db_ref, *, heads: int, group: int):
    C = q_ref.shape[1]
    K, V = k_ref.shape[2] * group // heads, v_ref.shape[2] // heads
    dt, f32 = q_ref.dtype, jnp.float32
    wide = _wide_iotas(C)
    # a head's own row and column (Mosaic's compiler stops on a slice of an
    # iota)
    right, row, col = wide[2], _iota((C, C), 0), _iota((C, C), 1)
    at_last = _iota((C, 1), 0) == C - 1
    G = _running_sum(g_ref[0, 0])                            # [C, heads]
    # What flows into T, a head at a time; the two float32 products of
    # every head are then taken in lockstep, as the forward's levels are.
    held, Ts, dTs = [], [], []
    for p, (a, b) in enumerate(_pairs(heads)):
        decays = _side_by_side(G, a, b, *wide)
        kk = _pair_gram(k_ref, k_ref, a, b, group, K, right)
        qk = _pair_gram(q_ref, k_ref, a, b, group, K, right)
        for side, h in _sides(a, b):
            cut = slice(side * C, (side + 1) * C)
            j, beta, Gh = h // group, b_ref[0, 0, :, h:h + 1], G[:, h:h + 1]
            ke = k_ref[0, :, j * K:(j + 1) * K].astype(f32) * jnp.exp(Gh)
            v = v_ref[0, :, h * V:(h + 1) * V].astype(f32)
            dU, dW = du_ref[h, 0].astype(dt), dw_ref[h, 0]
            Ts.append(t_ref[p, 0, :, cut])
            dTs.append(_nt(dU, (beta * v).astype(dt))
                       + _nt(dW, (beta * ke).astype(dt)))
            held.append((h, j, beta, Gh, decays[:, cut], kk[:, cut],
                         qk[:, cut], ke, v, dU, dW))
    lefts = [_tn(T, dT, _EXACT) for T, dT in zip(Ts, dTs)]
    dMs = [jnp.where(row > col, -_nt(left, T, _EXACT), 0.0)
           for left, T in zip(lefts, Ts)]
    dq = [jnp.zeros((C, K), f32) for _ in range(heads // group)]
    dk = [jnp.zeros((C, K), f32) for _ in range(heads // group)]
    for (h, j, beta, Gh, decays, kk, qk, ke, v, dU, dW), T, dM in zip(
            held, Ts, dMs):
        k, q = (ref[0, :, j * K:(j + 1) * K] for ref in (k_ref, q_ref))
        k32, q32 = k.astype(f32), q.astype(f32)
        decay, last = jnp.exp(Gh), Gh[C - 1:]
        tail, Td = jnp.exp(last - Gh), T.astype(dt)
        A = jnp.where(row > col, kk * decays, 0.0)
        R_V, R_K = _tn(Td, dU), _tn(Td, dW)
        dA, dBq = beta * dM, dbq_ref[h, 0].astype(f32)
        # Through Gamma: dA * A + dBq * Bq, its rows' sums less its columns'
        # (brought to rows by the diagonal); dBq above the diagonal meets
        # Gamma's zeros.
        pairs = dA * A + dBq * qk * decays
        pairs = pairs - jnp.where(row == col, jnp.sum(
            pairs, axis=0, keepdims=True), 0.0)
        E, F = (dA * decays).astype(dt), (dBq * decays).astype(dt)
        dQg, dKd = dqg_ref[h, 0].astype(f32), dkd_ref[h, 0].astype(f32)
        dq[j] = dq[j] + _nn(F, k) + dQg * decay
        dk[j] = dk[j] + _nn(E, k) + _tn(E, k) + _tn(F, q) \
            + beta * decay * R_K + dKd * tail
        through_kd = jnp.sum(dKd * k32 * tail, axis=1, keepdims=True)
        dG = jnp.sum(pairs, axis=1, keepdims=True) + jnp.sum(
            beta * ke * R_K + dQg * q32 * decay, axis=1,
            keepdims=True) - through_kd
        dg_ref[0, 0, :, h:h + 1] = jnp.where(
            at_last, dG + jnp.sum(through_kd, axis=0, keepdims=True)
            + jnp.sum(ds_ref[h, 0], axis=1, keepdims=True) * jnp.exp(last),
            dG)
        dv_ref[0, :, h * V:(h + 1) * V] = (beta * R_V).astype(dt)
        db_ref[0, 0, :, h:h + 1] = jnp.sum(dM * A, axis=1, keepdims=True) \
            + jnp.sum(R_V * v, axis=1, keepdims=True) \
            + jnp.sum(R_K * ke, axis=1, keepdims=True)
    for j in range(heads // group):
        dq_ref[0, :, j * K:(j + 1) * K] = dq[j].astype(dt)
        dk_ref[0, :, j * K:(j + 1) * K] = dk[j].astype(dt)
    dg_ref[0, 0] = _running_sum(dg_ref[0, 0], up=True)


@functools.partial(_traced_once, static_argnames=(
    "chunk", "heads", "key_heads", "interpret"))
def _gdn_chunk_bwd(q, k, v, g, beta, T, dU, dW, dQg, dBq, dKd, ds, chunk: int,
                   heads: int, key_heads: int, interpret: bool):
    """The cotangents of ``q, k`` (at the key heads), ``v``, ``g`` and
    ``beta``, each in its primal's dtype and shape."""
    B, L, Hv = beta.shape
    K, V, C, N = k.shape[-1] // key_heads, v.shape[-1] // Hv, chunk, L // chunk
    group = Hv // key_heads
    ins, outs = _gdn_specs(Hv, C, K, V, heads, group)
    cols = [_columns(x, heads) for x in (g, beta)]
    call = pl.pallas_call(
        functools.partial(_gdn_bwd_kernel, heads=heads, group=group),
        grid=(B * Hv // heads, N), in_specs=ins + [outs[6]] + outs[:6],
        out_specs=ins, out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                                  for x in (q, k, v, *cols)],
        interpret=interpret, name="dk_gdn_chunk_bwd",
        **_compiler_kw(interpret, ("parallel", "parallel")))
    with jax.named_scope("dk_gdn_chunk_bwd"):
        dq, dk, dv, dg, dbeta = call(q, k, v, *cols, T, dU, dW, dQg, dBq,
                                     dKd, ds)
    return dq, dk, dv, *(jnp.moveaxis(x, 1, 2).reshape(B, L, Hv)
                         for x in (dg, dbeta))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _gdn_chunk(q, k, v, g, beta, chunk, heads, key_heads, interpret):
    return _gdn_chunk_fwd(q, k, v, g, beta, chunk=chunk, heads=heads,
                          key_heads=key_heads, interpret=interpret, save=False)


def _gdn_chunk_fwd_rule(q, k, v, g, beta, chunk, heads, key_heads, interpret):
    *out, T = _gdn_chunk_fwd(q, k, v, g, beta, chunk=chunk, heads=heads,
                             key_heads=key_heads, interpret=interpret,
                             save=True)
    return tuple(out), (q, k, v, g, beta, T)


def _gdn_chunk_bwd_rule(chunk, heads, key_heads, interpret, res, cotangents):
    return _gdn_chunk_bwd(*res, *cotangents, chunk=chunk, heads=heads,
                          key_heads=key_heads, interpret=interpret)


_gdn_chunk.defvjp(_gdn_chunk_fwd_rule, _gdn_chunk_bwd_rule)


def gdn_chunk_products(q, k, v, g, beta, chunk: int,
                       heads: int | None = None,
                       interpret: bool | None = None):
    """:func:`chunk_products` for **one decay a value head** and **key heads
    read by group** (Gated DeltaNet): ``q, k``: [B, L, Hk, d_k] at the key
    heads and ``v``: [B, L, Hv, d_v], ``Hk`` dividing ``Hv``, in the
    products' dtype; ``g`` and ``beta``: [B, L, Hv], float32 here whatever
    they come as. Value head ``j`` reads key head ``j // (Hv / Hk)``. Returns
    ``(U, W, Qg, Bq, Kd, s)`` at the value heads as :func:`scan_chunks` reads
    them and the summed log-decay of each chunk ``[B, Hv, N]`` (no cotangent
    flows into that one). Differentiable in all five, ``q, k``'s cotangents
    at the key heads. ``heads`` (value heads a program) follows the shapes
    (:func:`gdn_chunk_heads_per_program`); ``interpret=None`` compiles on a
    TPU and interprets elsewhere."""
    interpret = mode.interpret("gdn_chunk", interpret)
    B, L, Hk, K = k.shape
    Hv, V, N = v.shape[2], v.shape[-1], L // chunk
    if Hv % Hk:
        raise ValueError(f"{Hk} key heads cannot serve {Hv} value heads")
    if heads is None:
        heads = gdn_chunk_heads_per_program(Hv, Hk, chunk, K, V,
                                            jnp.dtype(v.dtype).itemsize)
    if Hv % heads or heads % (Hv // Hk):
        raise ValueError(f"{heads} value heads a program are not whole "
                         f"groups of {Hv // Hk} that divide {Hv}")
    from distkeras_tpu import telemetry

    telemetry.counter("pallas.gdn.chunk_calls").add(1)
    telemetry.gauge("pallas.gdn.chunk_heads_per_program").set(heads)
    f32 = jnp.float32
    g = g.astype(f32)
    products = _gdn_chunk(
        q.reshape(B, L, Hk * K), k.reshape(B, L, Hk * K),
        v.reshape(B, L, Hv * V), g, beta.astype(f32), chunk, heads, Hk,
        interpret)
    last = jnp.moveaxis(jnp.sum(g.reshape(B, N, chunk, Hv), 2), 1, 2)
    return tuple(x.reshape(B, Hv, N, *x.shape[2:]) for x in products[:5]) + (
        products[5].reshape(B, Hv, N, K), last)


# -- the state-space in-chunk half (Mamba-2) -----------------------------------

def ssd_chunks_per_program(chunks: int, chunk: int) -> int:
    """Chunks one program of ``dk_ssd_chunk_*`` holds: the fewest whose
    positions fill whole tiles of 128 lanes (``dt x`` and the decay are read
    with positions on lanes), else all of them (the whole row)."""
    return next((n for n in range(1, chunks + 1)
                 if chunks % n == 0 and n * chunk % _LANES == 0), chunks)


def ssd_chunk_heads_per_program(heads: int, groups: int, chunk: int, d_k: int,
                                d_v: int, itemsize: int) -> int:
    """Heads one program of ``dk_ssd_chunk_*`` holds: whole groups (a
    group's ``C`` and ``B`` are read once and their cotangents summed inside
    the program), neighbours in one batch row whose blocks keep Mosaic's
    rule (the groups' lanes whole tiles of 128, the heads' rows of ``dt x``
    whole tiles of 16 and of the decay of 8, or the whole row); of those the
    most, up to 8 (or one group where a group is wider), whose blocks fit
    :data:`_VMEM_BUDGET` in the backward kernel, every block in and out
    twice; where none does, the fewest that keep the rule for blocks."""
    group = heads // groups
    k, v, c = _lanes(d_k), _lanes(d_v), _lanes(chunk)
    span = _LANES  # positions a program, at the least
    blocks = span * (4 * k * itemsize / group      # q, k and dq, dk
                     + 2 * v * itemsize + d_v * 4  # dU, dv and its scratch
                     + 2 * k * itemsize            # dQg, dKd
                     + c * itemsize + 3 * 4)       # dBq; g, dg, scratch
    whole = [h for h in range(group, heads + 1, group)
             if heads % h == 0 and (h == heads or not (
                 h // group * d_k % _LANES or h * d_v % 16 or h % 8))]
    fit = [h for h in whole if h <= max(8, group) and (
        h == group or 2 * h * blocks <= _VMEM_BUDGET)]
    return max(fit) if fit else min(whole)


def _grams(q_ref, k_ref, group: int, K: int, right):
    """``(ja, jb) -> [q_ja k_ja^T | q_jb k_jb^T]`` ``[C, 2C]`` float32 for a
    pair of heads by their groups (:func:`_pair_gram`), from one chunk's
    rows of ``q`` and ``k``, each taken once a program and chunk: a pair
    inside one group is the group's one product, laid twice side by side."""
    return functools.cache(lambda ja, jb: _pair_gram(
        q_ref, k_ref, ja * group, jb * group, group, K, right))


def _ssd_fwd_kernel(q_ref, k_ref, v_ref, g_ref, u_ref, qg_ref, bq_ref, kd_ref,
                    s_ref, *, heads: int, group: int):
    chunks, C, V = u_ref.shape[1:]
    K = qg_ref.shape[3]
    dt, f32 = v_ref.dtype, jnp.float32
    row, col, right = _wide_iotas(C)
    # positions to sublanes: one transpose a program of dt x and of g
    vs, gs = v_ref[0].T, g_ref[0].T                  # [span, heads x V, heads]
    for n in range(chunks):
        at = slice(n * C, (n + 1) * C)
        q, k = q_ref[0, at], k_ref[0, at]
        G = _running_sum(gs[at])                             # [C, heads]
        decay, tail = jnp.exp(G), jnp.exp(G[C - 1:] - G)
        gram = _grams(q_ref.at[:, at], k_ref.at[:, at], group, K, right)
        for a, b in _pairs(heads):
            Bq = gram(a // group, b // group) * _side_by_side(
                G, a, b, row, col, right)
            for side, h in _sides(a, b):
                bq_ref[h, n] = Bq[:, side * C:(side + 1) * C].astype(dt)
        for j in range(heads // group):
            q32, k32 = (x[:, j * K:(j + 1) * K].astype(f32) for x in (q, k))
            for h in range(j * group, (j + 1) * group):
                qg_ref[h, n] = (q32 * decay[:, h:h + 1]).astype(dt)
                kd_ref[h, n] = (k32 * tail[:, h:h + 1]).astype(dt)
                s_ref[h, n] = jnp.broadcast_to(jnp.exp(G[C - 1:, h:h + 1]),
                                               (1, K))
                u_ref[h, n] = vs[at, h * V:(h + 1) * V].astype(u_ref.dtype)


def _ssd_specs(H: int, C: int, K: int, V: int, heads: int, group: int,
               chunks: int):
    """Block specs of one program (``heads`` heads of one batch row and
    their ``heads / group`` groups, ``chunks`` chunks): the inputs ``q, k``
    as ``[B, L, G x d_k]``, ``dt x`` as ``[B, H x d_v, L]`` and ``g`` as
    ``[B, H, L]`` (positions on lanes, as XLA lays them out); then the
    outputs ``U, Qg, Bq, Kd, s`` as ``[B x H, N, ...]``."""
    programs, span = H // heads, chunks * C

    def spec(block, index):
        return pl.BlockSpec(block, index, memory_space=pltpu.VMEM)

    def chunk_rows(*dims):
        return spec((heads, chunks) + dims,
                    lambda i, n: (i, n) + (0,) * len(dims))

    positions = spec((1, span, heads // group * K),
                     lambda i, n: (i // programs, n, i % programs))
    ins = [positions, positions,
           spec((1, heads * V, span),
                lambda i, n: (i // programs, i % programs, n)),
           spec((1, heads, span),
                lambda i, n: (i // programs, i % programs, n))]
    outs = [chunk_rows(C, V), chunk_rows(C, K), chunk_rows(C, C),
            chunk_rows(C, K), chunk_rows(1, K)]
    return ins, outs


@functools.partial(_traced_once, static_argnames=(
    "chunk", "heads", "groups", "interpret"))
def _ssd_chunk_fwd(q, k, vT, gT, chunk: int, heads: int, groups: int,
                   interpret: bool):
    """``U, Qg, Bq, Kd, s`` as ``[B x H, N, ...]`` from ``q, k`` ``[B, L, G x
    d_k]``, ``vT`` ``[B, H x d_v, L]`` and ``gT`` ``[B, H, L]``."""
    B, H, L = gT.shape
    K, V, C, N = k.shape[-1] // groups, vT.shape[1] // H, chunk, L // chunk
    group, chunks = H // groups, ssd_chunks_per_program(L // chunk, chunk)
    ins, outs = _ssd_specs(H, C, K, V, heads, group, chunks)
    rows, dt = B * H, vT.dtype
    out_shape = [jax.ShapeDtypeStruct((rows, N) + dims, dtype)
                 for dims, dtype in (((C, V), dt), ((C, K), dt), ((C, C), dt),
                                     ((C, K), dt), ((1, K), jnp.float32))]
    call = pl.pallas_call(
        functools.partial(_ssd_fwd_kernel, heads=heads, group=group),
        grid=(rows // heads, N // chunks), in_specs=ins, out_specs=outs,
        out_shape=out_shape, interpret=interpret, name="dk_ssd_chunk_fwd",
        **_compiler_kw(interpret, ("parallel", "parallel")))
    with jax.named_scope("dk_ssd_chunk_fwd"):
        return tuple(call(q, k, vT, gT))


def _ssd_bwd_kernel(q_ref, k_ref, g_ref, du_ref, dqg_ref, dbq_ref, dkd_ref,
                    ds_ref, dq_ref, dk_ref, dv_ref, dg_ref, dvs, dgs, sums, *,
                    heads: int, group: int):
    chunks, C, V = du_ref.shape[1:]
    K = dqg_ref.shape[3]
    dt, f32 = q_ref.dtype, jnp.float32
    row, col, right = _wide_iotas(C)
    at_last = _iota((C, 1), 0) == C - 1
    gs = g_ref[0].T                                          # [span, heads]
    # a head's three row sums for dG go to its column of ``sums``: dG is
    # then taken for all heads at once, [C, heads]
    for n in range(chunks):
        at = slice(n * C, (n + 1) * C)
        q, k = q_ref[0, at], k_ref[0, at]
        G = _running_sum(gs[at])                             # [C, heads]
        decay, tail = jnp.exp(G), jnp.exp(G[C - 1:] - G)
        gram = _grams(q_ref.at[:, at], k_ref.at[:, at], group, K, right)
        # dBq * Gamma summed over each group's heads, two heads side by side
        F = [jnp.zeros((C, 2 * C), f32) for _ in range(heads // group)]
        for a, b in _pairs(heads):
            dBq = jnp.concatenate([dbq_ref[a, n].astype(f32), (
                dbq_ref[b, n].astype(f32) if b != a
                else jnp.zeros((C, C), f32))], 1)
            E = dBq * _side_by_side(G, a, b, row, col, right)
            ja, jb = a // group, b // group
            if ja == jb:
                F[ja] = F[ja] + E
            else:
                F[ja] = F[ja] + jnp.where(right, 0.0, E)
                F[jb] = F[jb] + jnp.where(right, E, 0.0)
            # dBq * Bq: its rows' sums less its columns' (brought to the rows
            # by the diagonal), a head at a time
            P = E * gram(ja, jb)
            P = P - jnp.where(row == col, jnp.sum(P, axis=0, keepdims=True),
                              0.0)
            for side, h in _sides(a, b):
                sums[0, :, h:h + 1] = jnp.sum(P[:, side * C:(side + 1) * C],
                                              axis=1, keepdims=True)
        for j in range(heads // group):
            qj, kj = (x[:, j * K:(j + 1) * K] for x in (q, k))
            q32, k32 = qj.astype(f32), kj.astype(f32)
            Fj = (F[j][:, :C] + F[j][:, C:]).astype(dt)
            dq, dk = _nn(Fj, kj), _tn(Fj, qj)
            for h in range(j * group, (j + 1) * group):
                dQg = dqg_ref[h, n].astype(f32)
                dKd = dkd_ref[h, n].astype(f32)
                dq = dq + dQg * decay[:, h:h + 1]
                dk = dk + dKd * tail[:, h:h + 1]
                sums[1, :, h:h + 1] = jnp.sum(dQg * q32, axis=1,
                                              keepdims=True)
                sums[2, :, h:h + 1] = jnp.sum(dKd * k32, axis=1,
                                              keepdims=True)
                sums[0, C - 1:, h:h + 1] += jnp.sum(
                    ds_ref[h, n], axis=1, keepdims=True) * decay[C - 1:,
                                                                 h:h + 1]
                dvs[at, h * V:(h + 1) * V] = du_ref[h, n].astype(f32)
            dq_ref[0, at, j * K:(j + 1) * K] = dq.astype(dq_ref.dtype)
            dk_ref[0, at, j * K:(j + 1) * K] = dk.astype(dk_ref.dtype)
        through_kd = tail * sums[2]                          # [C, heads]
        dG = sums[0] + decay * sums[1] - through_kd
        dgs[at] = _running_sum(jnp.where(at_last, dG + jnp.sum(
            through_kd, axis=0, keepdims=True), dG), up=True)
    # positions back to lanes: one transpose a program of each
    dv_ref[0] = dvs[...].T.astype(dv_ref.dtype)
    dg_ref[0] = dgs[...].T


@functools.partial(_traced_once, static_argnames=(
    "chunk", "heads", "groups", "interpret"))
def _ssd_chunk_bwd(q, k, gT, dU, dQg, dBq, dKd, ds, chunk: int, heads: int,
                   groups: int, interpret: bool):
    """The cotangents of ``q, k`` (at the groups), ``vT`` (in ``dU``'s
    dtype, which is ``v``'s) and ``gT``, in their shapes: ``[B, L, G x
    d_k]``, ``[B, H x d_v, L]`` and ``[B, H, L]``."""
    B, H, L = gT.shape
    K, V, C = k.shape[-1] // groups, dU.shape[-1], chunk
    group, chunks = H // groups, ssd_chunks_per_program(L // chunk, chunk)
    ins, outs = _ssd_specs(H, C, K, V, heads, group, chunks)
    f32 = jnp.float32
    call = pl.pallas_call(
        functools.partial(_ssd_bwd_kernel, heads=heads, group=group),
        grid=(B * H // heads, L // (chunks * C)),
        in_specs=[ins[0], ins[1], ins[3]] + outs, out_specs=ins,
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct((B, H * V, L), dU.dtype),
                   jax.ShapeDtypeStruct(gT.shape, f32)],
        scratch_shapes=[pltpu.VMEM((chunks * C, heads * V), f32),
                        pltpu.VMEM((chunks * C, heads), f32),
                        pltpu.VMEM((3, C, heads), f32)],
        interpret=interpret, name="dk_ssd_chunk_bwd",
        **_compiler_kw(interpret, ("parallel", "parallel")))
    with jax.named_scope("dk_ssd_chunk_bwd"):
        return tuple(call(q, k, gT, dU, dQg, dBq, dKd, ds))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _ssd_chunk(q, k, vT, gT, chunk, heads, groups, interpret):
    return _ssd_chunk_fwd(q, k, vT, gT, chunk=chunk, heads=heads,
                          groups=groups, interpret=interpret)


def _ssd_chunk_fwd_rule(q, k, vT, gT, chunk, heads, groups, interpret):
    return _ssd_chunk_fwd(q, k, vT, gT, chunk=chunk, heads=heads,
                          groups=groups, interpret=interpret), (q, k, gT)


def _ssd_chunk_bwd_rule(chunk, heads, groups, interpret, res, cotangents):
    return _ssd_chunk_bwd(*res, *cotangents, chunk=chunk, heads=heads,
                          groups=groups, interpret=interpret)


_ssd_chunk.defvjp(_ssd_chunk_fwd_rule, _ssd_chunk_bwd_rule)


def ssd_chunk_products(q, k, v, g, chunk: int, heads: int | None = None,
                       interpret: bool | None = None):
    """The state-space in-chunk half (Mamba-2's SSD; ``ops/delta_rule.py::
    ssd_in_chunk`` in ``jax.numpy``) as the kernel pair ``dk_ssd_chunk_fwd``
    / ``dk_ssd_chunk_bwd`` under one ``jax.custom_vjp``. ``q`` (``C``) and
    ``k`` (``B``): [B, L, G, d_k] at ``G`` groups dividing the ``H`` heads,
    read as ``[B, L, G x d_k]`` (head ``h`` reads group ``h // (H / G)``, a
    group a lane slice: nothing repeated or moved); ``v`` (``dt x``): [B, L,
    H, d_v], read as ``[B, H x d_v, L]``, and ``g`` (``dt A``, ``<= 0``): [B,
    L, H], float32 here whatever it comes as, read as ``[B, H, L]``: with
    positions on lanes, as XLA lays out the Mixer's ``[B, L, H, 64]`` arrays
    (read with positions on sublanes, they cost it relayouts of float32
    ``[L, H x d_v]`` arrays around the pair, about 4 GB a step at
    Nemotron-3-Nano's cell); a program turns them in VMEM, one transpose of
    each. With ``G`` the running sum of ``g`` a head inside a
    chunk of ``C`` positions and ``Gamma_ri = e^{G_r - G_i}`` for ``i <= r``
    (its exponent masked to ``-inf`` above the diagonal *before* the
    exponential, so every exponent is ``<= 0``, for any decay):

        Bq_h = (q k^T)_group * Gamma_h    Qg_h = q e^{G_h}
        Kd_h = k e^{G_C - G_h}            s_h = e^{G_C} on the [1, d_k] lanes
        U_h = v_h

    ``q k^T`` is one product a group for all its heads; ``Gamma`` of two
    heads side by side fills the 128 lanes (``_side_by_side``). ``U`` is
    written in ``v``'s dtype (it *is* ``v``); ``Qg, Bq, Kd`` in ``v``'s
    dtype from products of operands in it accumulated in float32; decays and
    ``Gamma`` in float32. The backward is written: with ``F_g = sum_{h in g}
    dBq_h * Gamma_h`` (lower-triangular) and ``P_h = dBq_h * Bq_h``
    (``Bq_h`` unrounded),

        dq_g = F_g k_g + sum_h dQg_h e^{G_h}
        dk_g = F_g^T q_g + sum_h dKd_h e^{G_C - G_h}
        dG_h = rowsum(P_h) - colsum(P_h) + rowsum(dQg_h * Qg_h)
               - rowsum(dKd_h * Kd_h)
        dG_h,C += sum_r rowsum(dKd_h * Kd_h)_r + sum(ds_h) e^{G_C}
        dv_h = dU_h      dg = the running sum of dG from below

    ``F_g`` is summed over the group before its two products; ``colsum``
    comes to a column through the diagonal (one term a row: exact). The
    forward rule saves ``q, k, g`` alone. Grid ``(batch x heads / heads a
    program, chunks / chunks a program)``, both ``parallel``; ``heads`` and
    the chunks follow the shapes (:func:`ssd_chunk_heads_per_program`,
    :func:`ssd_chunks_per_program`: one group of 8 heads and two chunks of
    64 at Nemotron-3-Nano's); the counter ``pallas.ssd.chunk_calls`` counts the
    pair's traced calls. Returns, as ``ssd_in_chunk`` does, ``(U, Qg, Bq,
    Kd, s)`` as :func:`scan_chunks` reads them with ``W=None`` and the summed
    log-decay of each chunk ``[B, H, N]`` (no cotangent flows into that
    one). ``interpret=None`` compiles on a TPU and interprets elsewhere."""
    interpret = mode.interpret("ssd_chunk", interpret)
    B, L, G, K = k.shape
    H, V, N = v.shape[2], v.shape[-1], L // chunk
    if H % G:
        raise ValueError(f"{G} groups cannot serve {H} heads")
    if heads is None:
        heads = ssd_chunk_heads_per_program(H, G, chunk, K, V,
                                            jnp.dtype(v.dtype).itemsize)
    if H % heads or heads % (H // G):
        raise ValueError(f"{heads} heads a program are not whole groups of "
                         f"{H // G} that divide {H}")
    from distkeras_tpu import telemetry

    telemetry.counter("pallas.ssd.chunk_calls").add(1)
    telemetry.gauge("pallas.ssd.chunk_heads_per_program").set(heads)
    g = g.astype(jnp.float32)
    *products, s = _ssd_chunk(
        q.reshape(B, L, G * K), k.reshape(B, L, G * K),
        jnp.swapaxes(v.reshape(B, L, H * V), 1, 2), jnp.swapaxes(g, 1, 2),
        chunk, heads, G, interpret)
    last = jnp.moveaxis(jnp.sum(g.reshape(B, N, chunk, H), 2), 1, 2)
    return tuple(x.reshape(B, H, N, *x.shape[2:]) for x in products) + (
        s.reshape(B, H, N, K),), last
