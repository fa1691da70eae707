"""The gated delta rule's chunked form as two Pallas TPU kernel pairs: the
in-chunk half (:func:`chunk_products`) and the scan over chunks
(:func:`scan_chunks`), each under one ``jax.custom_vjp`` with a written
backward. ``ops/delta_rule.py`` has the mathematics and calls them in turn.

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

All four kernels keep the caller's named scope: forward, recomputed and
backward calls carry ``dk_kda`` in their ``op_name``. Off a TPU the same
kernels run under the Pallas interpreter (``ops/pallas/mode.py``).
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


def _fwd_kernel(u_ref, w_ref, qg_ref, bq_ref, kd_ref, s_ref, o_ref, *rest,
                heads: int):
    *entering, state = rest  # the forward rule's output, then the scratch
    dt = w_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    for h in range(heads):
        St = state[h]                                        # [d_v, d_k]
        if entering:
            entering[0][h, 0] = St
        Sd = St.astype(dt)
        P = (u_ref[h, 0] - _nt(w_ref[h, 0], Sd)).astype(dt)  # [C, d_v]
        o_ref[h, 0] = (_nt(qg_ref[h, 0], Sd)
                       + _nn(bq_ref[h, 0], P)).astype(o_ref.dtype)
        state[h] = s_ref[h, 0] * St + _tn(P, kd_ref[h, 0])


def _bwd_kernel(u_ref, w_ref, qg_ref, bq_ref, kd_ref, s_ref, st_ref, do_ref,
                du_ref, dw_ref, dqg_ref, dbq_ref, dkd_ref, ds_ref, dstate, *,
                heads: int):
    dt = w_ref.dtype

    @pl.when(pl.program_id(1) == 0)  # the last chunk: the grid runs it first
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    for h in range(heads):
        St, dSt = st_ref[h, 0], dstate[h]                    # [d_v, d_k]
        Sd, dSd, dO = St.astype(dt), dSt.astype(dt), do_ref[h, 0]
        w, qg = w_ref[h, 0], qg_ref[h, 0]
        P = (u_ref[h, 0] - _nt(w, Sd)).astype(dt)            # [C, d_v]
        dP32 = _tn(bq_ref[h, 0], dO) + _nt(kd_ref[h, 0], dSd)
        dP = dP32.astype(dt)
        du_ref[h, 0] = dP32.astype(du_ref.dtype)
        dw_ref[h, 0] = (-_nn(dP, Sd)).astype(dw_ref.dtype)
        dqg_ref[h, 0] = _nn(dO, Sd).astype(dqg_ref.dtype)
        dbq_ref[h, 0] = _nt(dO, P).astype(dbq_ref.dtype)
        dkd_ref[h, 0] = _nn(P, dSd).astype(dkd_ref.dtype)
        ds_ref[h, 0] = jnp.sum(dSt * St, axis=0, keepdims=True)
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


@functools.partial(_traced_once, static_argnames=("heads", "interpret", "save"))
def _scan_fwd(U, W, Qg, Bq, Kd, s, heads: int, interpret: bool, save: bool):
    """``O`` ``[rows, N, C, d_v]`` in ``W``'s dtype and, with ``save``, the
    transposed state entering each chunk ``[rows, N, d_v, d_k]`` float32."""
    rows, N, C, V = U.shape
    K = W.shape[-1]
    *in_specs, entering, out = _specs(heads, C, K, V)
    out_shape = [jax.ShapeDtypeStruct((rows, N, C, V), W.dtype)]
    if save:
        out_shape.append(jax.ShapeDtypeStruct((rows, N, V, K), jnp.float32))
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads),
        grid=(rows // heads, N), in_specs=in_specs,
        out_specs=[out, entering][:len(out_shape)], out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((heads, V, K), jnp.float32)],
        interpret=interpret, name="dk_kda_scan_fwd",
        **_compiler_kw(interpret))
    # The kernel's name (the Mosaic call's, on a chip) and the scope of the
    # same name (the interpreted ops', on a CPU) are what a trace finds it by.
    with jax.named_scope("dk_kda_scan_fwd"):
        return call(U, W, Qg, Bq, Kd, s)


@functools.partial(_traced_once, static_argnames=("heads", "interpret"))
def _scan_bwd(U, W, Qg, Bq, Kd, s, entering, dO, heads: int, interpret: bool):
    rows, N, C, V = U.shape
    K = W.shape[-1]
    specs = _specs(heads, C, K, V, chunks=N)
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads),
        grid=(rows // heads, N), in_specs=specs, out_specs=specs[:6],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (U, W, Qg, Bq, Kd, s)],
        scratch_shapes=[pltpu.VMEM((heads, V, K), jnp.float32)],
        interpret=interpret, name="dk_kda_scan_bwd",
        **_compiler_kw(interpret))
    with jax.named_scope("dk_kda_scan_bwd"):
        return tuple(call(U, W, Qg, Bq, Kd, s, entering, dO))


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
    ``U``: [B, H, N, C, d_v] float32; ``W``, ``Qg``, ``Kd``: [B, H, N, C,
    d_k] and ``Bq``: [B, H, N, C, C] (lower-triangular) in the products'
    dtype; ``shrink``: [B, H, N, d_k] float32, the decay of a whole chunk.
    Returns [B, H, N, C, d_v] in the products' dtype. Differentiable in all
    six. ``heads`` a program follows the shapes (:func:`heads_per_program`);
    ``interpret=None`` compiles on a TPU and interprets elsewhere."""
    interpret = mode.interpret("kda_scan", interpret)
    B, H, N, C, V = U.shape
    K = W.shape[-1]
    rows = B * H
    if heads is None:
        heads = heads_per_program(rows, C, K, V, jnp.dtype(W.dtype).itemsize)
    if rows % heads:
        raise ValueError(f"{heads} heads a program do not divide {B} x {H}")
    from distkeras_tpu import telemetry

    telemetry.gauge("pallas.kda.heads_per_program").set(heads)
    telemetry.gauge("pallas.kda.grid_steps").set(rows // heads * N)

    def flat(x):
        return x.reshape(rows, N, *x.shape[3:])

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


def _inverse_above(X, N, sub: int):
    """``(I + N)^-1`` from ``X``, the inverse of its ``sub`` x ``sub``
    diagonal blocks, by blocks upward: a level is ``X - X N_s X`` with
    ``N_s`` the lower-left blocks of that level, picked by a mask."""
    C = X.shape[0]
    row, col, s = _iota((C, C), 0), _iota((C, C), 1), sub
    while s < C:
        below = ((row ^ col) < 2 * s) & ((row & s) != 0) & ((col & s) == 0)
        X = X - _nn(_nn(X, jnp.where(below, N, 0.0), _EXACT), X, _EXACT)
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
