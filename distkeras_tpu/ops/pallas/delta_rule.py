"""The gated delta rule's scan over chunks as a Pallas TPU kernel pair.

``ops/delta_rule.py`` computes everything of a chunk that does not touch the
state for all chunks at once (``U``, ``W``, ``Qg``, ``Bq``, ``Kd``, the decay
``s`` of a whole chunk). What is left is sequential: for a head and chunk
``n``, from ``S = 0`` before chunk 0,

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

**Shapes.** A block holds ``C``, ``d_k`` and ``d_v`` whole, so the rule for
blocks (a multiple of the tile, or the whole dimension) holds for any of them
and nothing is padded or refused; there is one path. Mosaic's products are
measured at the published widths alone (128, 128, chunks of 64).

Both rules run inside one ``jax.custom_vjp`` (:func:`scan_chunks`), whose
operations keep the caller's named scope: forward, recomputed and backward
calls carry ``dk_kda`` in their ``op_name``. Off a TPU the same kernels run
under the Pallas interpreter (``ops/pallas/mode.py``).
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


def _nn(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _nt(a, b):  # a b^T: contracts the minor dimension of both
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _tn(a, b):  # a^T b: contracts dimension 0 of both
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


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


def _compiler_kw(interpret: bool) -> dict:
    """Programs of different heads in any order, a head's chunks in theirs."""
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))}


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
