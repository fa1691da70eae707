"""Causal FlashAttention as a Pallas TPU kernel (forward + backward).

The transformer's attention is the one op where XLA's default lowering
materializes an O(L^2) score matrix through HBM. These kernels keep K/V of
one head in VMEM and walk the score matrix tile by tile with the usual
online-softmax recurrence, so the scores never leave the core.

**The schedule** (:func:`causal_span`, :func:`diagonal_cuts`,
:func:`tile_schedule`; one copy of the arithmetic, which the kernels' loop
bounds, the tests and the gauge ``pallas.flash.visited_share`` all read):

* square tiles of ``block`` x ``block`` scores. Tiles wholly above the
  diagonal are never visited; tiles wholly below it run the plain step in a
  ``fori_loop``; the one tile on the diagonal is cut into ``cut`` x ``cut``
  squares in straight-line code, of which those above the diagonal are
  skipped, those below run the plain step and only those on it are masked,
  by the constant ``row >= col``. Visited share of L^2: (1 + cut / L) / 2.
* the default ``(block, cut)`` follows L and D (:func:`default_tiling`):
  up to L = 2048 one tile is the whole sequence — one program a head and no
  loop at all, every step a static rectangle up to ``cut`` x L that the
  scheduler can interleave with the next — and beyond that the largest tile
  up to 1024 that divides L and fits Mosaic's 16 MiB of scoped VMEM beside
  the resident K and V. ``cut`` is 256, and 512 from L = 2048. Where K and V
  whole leave no room for two blocks in 16 MiB (heads of 256 at L = 8,192,
  PR 40), the kernels ask for 48 MiB (:func:`scoped_vmem`) and the tile is
  sized against that: 1024, cut 512 (``dk_flash_dkv`` takes 34.4 MiB there);
  every narrower shape asks for the default and tiles as before.
* an explicit ``block_k`` gives rectangular ``block_size`` x ``block_k``
  tiles whose straddling tiles are visited and masked whole (the general
  path; what ran everywhere until PR 27, with a k-chunk of up to 8 q-blocks,
  which at L <= 1024 was the whole sequence: nothing skipped, all masked).

* ``window`` (sliding-window layers: query ``i`` sees keys ``j`` with ``0 <=
  i - j < window``) needs square tiles that divide it. Tiles wholly older
  than the window are never visited, as tiles past the diagonal are not; the
  one tile the window's edge crosses (``window / block`` tiles left of the
  diagonal tile, :func:`window_span`) keeps its strict upper triangle, cut
  like the diagonal tile (:func:`edge_cuts`). A program that has no such
  tile (the first rows) skips it under a ``cond``.
* K/V heads fewer than Q heads (grouped-query attention): the program of
  query head ``h`` reads K/V head ``h // group`` through its block index, so
  a group's K and V are fetched once. dk/dv get a third grid dimension over
  the group, ``arbitrary``: each program adds its query head's part to
  float32 accumulators in VMEM and the output block leaves once a group.
  Without a window and with as many K/V heads as Q heads the kernels, their
  grids and their text are what they were.

**Measured** (TPU v5e, ``[B, L, H, D] = [8, L, 16, 64]`` bf16, each kernel's
device time from a profiler trace, us a call; TFLOP/s on the products a
kernel executes, 2 / 3 / 4 of them for fwd / dq / dk+dv; the first three
columns 30 Sep 2026 with dk+dv query-major, the last 2 Oct 2026 key-major):

====================  =====  =====  =====  =========  ====================
L = 1024, tiling      fwd    dq     dk+dv  key-major  visited
====================  =====  =====  =====  =========  ====================
128 x 1024 (before)   820    976    1689              100 %
128 x 128             1804   1691   2078              56 %  (loop trips)
256 x 256             877    833    1468              62.5 %
512 x 512             618    649    780               75 %
512 x 512, cut 256    622    611    792               62.5 %
1024 x 1024, cut 512  424    415    598               75 %
1024 x 1024, cut 256  468    363    694    484        62.5 %  (the default)
====================  =====  =====  =====  =========  ====================

At L = 2048: 2286 / 2648 / 5313 before (128 x 1024), 1271 / 1338 / 1887 with
the default 2048 x 2048, cut 512, and dk+dv 1776 key-major. What decides is
the cost of a step, not the operations: a ``fori_loop`` trip is a wall the
scheduler cannot move work across, a [128, 64] x [64, 128] step leaves the MXU
waiting on its own latency (128 x 128 runs at 11-19 TFLOP/s, the default at
37-57), and d_head 64 fills half the MXU's contraction depth whatever the
tile.

dk+dv alone at the calls the benchmark's cells and ``chip_smoke.py`` make
(2 Oct 2026, parent beside change in one process, 10 calls a side and twice a
side; dk and dv bit-equal at all five; fwd and dq read the same to 0.3 us):

==============================  ===========  =========  ======
``[B, L, H / KV heads, D]``     query-major  key-major
==============================  ===========  =========  ======
[8, 1024, 16 / 16, 64]          693.6        484.4      -30 %
[2, 8192, 28 / 4, 128] w 4096   11963.1      10252.4    -14 %
[2, 8192, 28 / 4, 128]          14485.0      12573.5    -13 %
[2, 8192, 32 / 8, 64]           16832.8      14702.8    -13 %
[8, 2048, 16 / 16, 64]          1886.5       1775.5     -6 %
==============================  ===========  =========  ======

Key-major is faster at every one, so there is one form and nothing selects
it. It gains most where a program is one chain of static rectangles (L =
1024: 7 steps of up to 768 x 256) and least where a step's products are large
beside its two transposes (cut 512 at L = 2048; D = 128; the 512-tiles'
loop at L = 8192).

Kept from earlier rounds: lse/delta live as [BH, nq, 1, block_q], one exact
block per program, so no output block is revisited and every grid dim is
``parallel``; bf16 operands with f32 accumulation (``preferred_element_type``),
f32 softmax statistics and ``exp``.

Layout: inputs are [B, H, L, D] (the wrapper transposes from the model's
[B, L, H, D]). Forward/dq grids are (B*H, L/block); the dk+dv kernel's grid
is the same, each program owning one block of keys. Backward is two kernels
(dq; dk+dv) using the saved logsumexp, wrapped in ``jax.custom_vjp``.
Each kernel computes in the space of the block it owns. Forward and dq own
query rows and work query-major: ``s = q k^T`` is [Q, K], the row statistics
(``m``, ``l``; ``lse``, ``delta``) stand beside it as [Q, 1] columns, once a
program, and every product is plain or contracts the two D dimensions. dk+dv
owns keys and works key-major (PR 33): ``s^T = k q^T`` is [K, Q], ``p^T =
exp(s^T - lse[None, :])``, ``dv += p^T do``, ``dp^T = v do^T``, ``dk += (p^T
(dp^T - delta[None, :])) q``. Its sums over queries are then plain [K, Q] x
[Q, D] products, and ``lse`` / ``delta`` are read as the [1, Q] lane vectors
they are stored as and broadcast along sublanes. Query-major, as it was
until PR 33, the same sums contract over dimension 0 of both operands, for
which Mosaic transposes two [Q, K] tiles a step, and each step stood ``lse``
and ``delta`` up as sublane columns; the masks of this side are the
transposed constants (``_keep(..., key_major=True)``).
The forward rule names its two outputs (:data:`FLASH_RESIDUALS`), so a block
under ``nn.remat(..., policy=save_only_these_names(*FLASH_RESIDUALS))`` (both
LM models) keeps them and runs ``dk_flash_fwd`` once a layer, not twice;
with no such policy in force the names change nothing.

``interpret=True`` runs the same kernels through the Pallas interpreter —
that is what CI exercises on the CPU mesh; the compiled path runs on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distkeras_tpu.ops.pallas import mode

#: The names of the forward's ``out`` and ``lse`` as the backward's residuals.
FLASH_RESIDUALS = ("dk_flash_out", "dk_flash_lse")

_NEG = -1e30
_VMEM_BYTES = 16 * 2 ** 20  # Mosaic's scoped limit for one kernel on a v5e
#: what the kernels ask for where K and V whole leave no room for a tile of
#: two blocks in that limit (heads of 256 at L = 8,192: K and V take all 16
#: MiB); a v5e core has 128 MiB of VMEM
_VMEM_WIDE_BYTES = 48 * 2 ** 20


def _qblock_spec(block, D, index=lambda bh, i: (bh, i, 0)):
    return pl.BlockSpec((1, block, D), index, memory_space=pltpu.VMEM)


def _full_spec(L, D, index=lambda bh, i: (bh, 0, 0)):
    return pl.BlockSpec((1, L, D), index, memory_space=pltpu.VMEM)


def _rowblock_spec(block):
    # lse/delta as [BH, nq, 1, block_q]: one exact block per program —
    # blocked, never revisited, so the grid stays order-independent. The
    # trailing (1, block) dims equal the array dims, satisfying TPU tiling.
    return pl.BlockSpec((1, 1, 1, block), lambda bh, i: (bh, i, 0, 0),
                        memory_space=pltpu.VMEM)


def _fullrow_spec(nq, block, index=lambda bh, i: (bh, 0, 0, 0)):
    return pl.BlockSpec((1, nq, 1, block), index, memory_space=pltpu.VMEM)


def _parallel_kw(interpret: bool, arbitrary: int = 0, vmem=None) -> dict:
    """Two grid dims order-independent -> Mosaic overlaps fetch/compute
    across programs. Only valid because no output block is revisited, but
    along the ``arbitrary`` trailing dims (dk/dv's walk over a K/V head's
    group of query heads, which revisits one output block in order).
    ``vmem``: the scoped VMEM the kernel asks for (``None``: Mosaic's
    default, :func:`scoped_vmem`)."""
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel",) * 2 + ("arbitrary",) * arbitrary,
        vmem_limit_bytes=vmem)}


def causal_span(i, own: int, other: int):
    """Tiles of width ``other`` that block ``i`` of width ``own`` overlaps,
    as ``(lo, hi)`` — the arithmetic of the causal schedule, once.

    The program that owns q-block ``i`` (forward, dq: ``own`` = block_q,
    ``other`` = block_k) walks k-tiles: ``[0, lo)`` lie wholly at or below the
    diagonal and need no mask, ``[lo, hi)`` straddle it, ``[hi, ...)`` lie
    wholly above it and are never visited. The program that owns k-block ``i``
    (dk/dv: ``own`` = block_k, ``other`` = block_q) walks q-tiles: ``[0, lo)``
    are never visited, ``[lo, hi)`` straddle, ``[hi, nq)`` need no mask. ``i``
    is a Python int (:func:`tile_schedule`) or a traced program id (the
    kernels' loop bounds)."""
    return (i * own) // other, ((i + 1) * own + other - 1) // other


def diagonal_cuts(tile: int, cut: int, by_rows: bool) -> list:
    """The lower triangle of a square ``tile`` on the diagonal, as one ``(b0,
    b1, rectangles)`` per block ``[b0, b1)`` of ``cut`` rows (forward and dq
    own rows) or columns (dk/dv owns columns). A rectangle is ``(r0, r1, c0,
    c1, masked)`` relative to the tile's corner: a block of rows has its part
    wholly below the diagonal, then its masked ``cut x cut`` square; a block
    of columns its square, then the part below it. The squares above the
    diagonal are in no list."""
    blocks = []
    for b0 in range(0, tile, cut):
        b1 = b0 + cut
        square = (b0, b1, b0, b1, True)
        if by_rows:
            rects = [(b0, b1, 0, b0, False)] * (b0 > 0) + [square]
        else:
            rects = [square] + [(b1, tile, b0, b1, False)] * (b1 < tile)
        blocks.append((b0, b1, rects))
    return blocks


def edge_cuts(tile: int, cut: int, by_rows: bool) -> list:
    """The strict upper triangle of the square ``tile`` that a window's edge
    crosses (a window of a whole number of tiles leaves of it the scores with
    column > row), in :func:`diagonal_cuts`' form: a block of rows has its
    masked ``cut x cut`` square, then the part right of it; a block of
    columns the part above its square, then the square. The squares below
    the diagonal are in no list."""
    blocks = []
    for b0 in range(0, tile, cut):
        b1 = b0 + cut
        square = (b0, b1, b0, b1, True)
        if by_rows:
            rects = [square] + [(b0, b1, b1, tile, False)] * (b1 < tile)
        else:
            rects = [(0, b0, b0, b1, False)] * (b0 > 0) + [square]
        blocks.append((b0, b1, rects))
    return blocks


def window_span(i, reach: int, n: int, by_rows: bool):
    """Square tiles under a window of ``reach`` tiles, as ``(edge, lo, hi)``:
    the program that owns block ``i`` of ``n`` walks the tiles ``[lo, hi)``
    whole and unmasked, between its diagonal tile ``i`` and the tile ``edge``
    that the window's edge crosses, which exists where ``0 <= edge < n``.
    Rows (forward, dq) look left: ``edge = i - reach``; columns (dk/dv) look
    down: ``edge = i + reach``. Everything beyond ``edge`` is wholly outside
    the window and never visited. ``i`` is an int or a traced program id."""
    if by_rows:
        lo = i - reach + 1
        return i - reach, (max(lo, 0) if isinstance(lo, int)
                           else jnp.maximum(lo, 0)), i
    hi = i + reach
    return hi, i + 1, (min(hi, n) if isinstance(hi, int)
                       else jnp.minimum(hi, n))


def tile_schedule(L: int, block_q: int, block_k: int, cut=None,
                  window=None) -> dict:
    """What each kernel visits at ``(L, block_q, block_k, cut, window)``: per
    kernel a list of rectangles ``(q0, q1, k0, k1, masked)`` of the score
    matrix, from :func:`causal_span`, :func:`diagonal_cuts`,
    :func:`window_span` and :func:`edge_cuts` exactly as the kernel walks.
    Static, so it is known as the kernel is traced: the tests prove coverage
    on it and ``pallas.flash.visited_share`` reads it. ``cut`` is the grain of
    a square tiling's diagonal tiles; without it a tile that straddles the
    diagonal is visited, and masked, whole. ``window`` (a multiple of the
    square tile) drops what lies wholly outside it."""

    def tiles(qi, ki, straddles, by_rows, cuts=diagonal_cuts):
        q0, k0 = qi * block_q, ki * block_k
        if not straddles or cut is None:
            return [(q0, q0 + block_q, k0, k0 + block_k, straddles)]
        return [(q0 + r0, q0 + r1, k0 + c0, k0 + c1, m)
                for _, _, rects in cuts(block_q, cut, by_rows)
                for r0, r1, c0, c1, m in rects]

    nq, nk = L // block_q, L // block_k
    by_q, by_k = [], []
    if window is not None:
        reach = window // block_q
        for i in range(nq):
            for by_rows, out in ((True, by_q), (False, by_k)):
                edge, lo, hi = window_span(i, reach, nq, by_rows)
                at = (lambda j: (i, j)) if by_rows else (lambda j: (j, i))
                if 0 <= edge < nq:
                    out += tiles(*at(edge), True, by_rows, edge_cuts)
                for j in range(lo, hi):
                    out += tiles(*at(j), False, by_rows)
                out += tiles(i, i, True, by_rows)
        return {"dk_flash_fwd": by_q, "dk_flash_dq": by_q,
                "dk_flash_dkv": by_k}
    for qi in range(nq):
        lo, hi = causal_span(qi, block_q, block_k)
        for ki in range(hi):
            by_q += tiles(qi, ki, ki >= lo, True)
    for ki in range(nk):
        lo, hi = causal_span(ki, block_k, block_q)
        for qi in range(lo, nq):
            by_k += tiles(qi, ki, qi < hi, False)
    return {"dk_flash_fwd": by_q, "dk_flash_dq": by_q, "dk_flash_dkv": by_k}


def visited_share(L: int, block_q: int, block_k: int, cut=None,
                  window=None) -> float:
    """Score elements a kernel visits over the ``L**2`` it would without the
    causal skip (the three kernels visit the same ones, in another order).
    1.0: the skip is not engaging."""
    tiles = tile_schedule(L, block_q, block_k, cut, window)["dk_flash_fwd"]
    return sum((q1 - q0) * (k1 - k0) for q0, q1, k0, k1, _ in tiles) / (L * L)


def _keep(rows: int, cols: int, shift, key_major: bool = False):
    """Causal mask of a ``rows x cols`` tile whose corner lies ``shift``
    columns right of the diagonal: True where row >= column. ``key_major``:
    the same tile held transposed (dk/dv's space: ``rows`` keys down the
    sublanes, ``cols`` queries along the lanes), where query >= key is
    column - row >= shift."""
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    return (col - row if key_major else row - col) >= shift


def _edge_then_diagonal(has_edge, tile: int, cut: int, by_rows: bool, carry,
                        step, finish):
    """The two cut tiles of a program under a window, after its whole tiles:
    the tile the window's edge crosses (its strict upper triangle,
    :func:`edge_cuts`; under a ``cond``, because the first programs have
    none) and the diagonal tile (:func:`diagonal_cuts`), one chain of
    ``step(on_edge, b0, b1, rect, sub, keep)`` a block of ``cut`` rows or
    columns, ended by ``finish(b0, b1, sub)``. ``carry`` is what the whole
    tiles left, whole; each chain takes its slice of it. Blocks of columns
    (dk/dv) hold their squares key-major, so their masks are the transposed
    ones: the edge tile keeps column < row there."""
    keep = _keep(cut, cut, 0, key_major=not by_rows)
    beyond = jnp.logical_not(keep)
    edge_blocks = edge_cuts(tile, cut, by_rows)

    def chain(on_edge, mask, b0, b1, rects, sub):
        for rect in rects:
            sub = step(on_edge, b0, b1, rect, sub, mask if rect[4] else None)
        return sub

    subs = jax.lax.cond(
        has_edge,
        lambda subs: tuple(chain(True, beyond, b0, b1, rects, sub)
                           for (b0, b1, rects), sub in zip(edge_blocks, subs)),
        lambda subs: subs,
        tuple(jax.tree.map(lambda x: x[b0:b1], carry)
              for b0, b1, _ in edge_blocks))
    for (b0, b1, rects), sub in zip(diagonal_cuts(tile, cut, by_rows), subs):
        finish(b0, b1, chain(False, keep, b0, b1, rects, sub))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q: int,
                block_k: int, cut, window=None):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.bfloat16)  # [BQ, D]
    BQ, Dv = q.shape[0], v_ref.shape[-1]

    def attend(q, k0, width, carry, keep):
        """One online-softmax step of rows ``q`` over keys [k0, k0 + width)."""
        m, l, acc = carry
        kb = k_ref[0, pl.ds(k0, width), :].astype(jnp.bfloat16)
        vb = v_ref[0, pl.ds(k0, width), :].astype(jnp.bfloat16)
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if keep is not None:
            # A row's first step always keeps a column (column 0, or its own
            # diagonal), so m is finite and exp() of a masked score is 0.0.
            s = jnp.where(keep, s, _NEG)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1, keepdims=True)
        acc = acc * corr + jax.lax.dot_general(
            p.astype(jnp.bfloat16), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    def finish(r0, r1, carry):
        m, l, acc = carry
        o_ref[0, r0:r1, :] = (acc / l).astype(o_ref.dtype)
        lse_ref[0, 0, 0, r0:r1] = (m + jnp.log(l))[:, 0]

    lo, hi = causal_span(qi, block_q, block_k)
    first = 0
    if window is not None:
        edge, first, _ = window_span(qi, window // block_k, 0, True)
    carry = jax.lax.fori_loop(
        first, lo, lambda ki, c: attend(q, ki * block_k, block_k, c, None),
        (jnp.full((BQ, 1), _NEG, jnp.float32), jnp.zeros((BQ, 1), jnp.float32),
         jnp.zeros((BQ, Dv), jnp.float32)))
    if window is not None:
        # A row that keeps nothing of the edge tile (the tile's last) leaves
        # p = 1 behind only where no whole tile came before it, and the
        # diagonal tile's step, which always keeps a column, scales that away
        # by exp(_NEG - m) = 0.
        def step(on_edge, b0, b1, rect, sub, keep):
            _, _, c0, c1, _ = rect
            return attend(q[b0:b1], (edge if on_edge else qi) * block_k + c0,
                          c1 - c0, sub, keep)

        _edge_then_diagonal(edge >= 0, BQ, cut, True, carry, step, finish)
        return
    if cut is None:
        finish(0, BQ, jax.lax.fori_loop(lo, hi, lambda ki, c: attend(
            q, ki * block_k, block_k, c,
            _keep(BQ, block_k, ki * block_k - qi * block_q)), carry))
        return
    # Square tiles: the one tile on the diagonal, cut so that its squares
    # above the diagonal are skipped too and only `cut`-squares are masked,
    # by a constant. No loop: each block of rows is its own chain of steps.
    keep = _keep(cut, cut, 0)
    for b0, b1, rects in diagonal_cuts(BQ, cut, True):
        sub = tuple(x[b0:b1] for x in carry)
        for _, _, c0, c1, masked in rects:
            sub = attend(q[b0:b1], qi * block_k + c0, c1 - c0, sub,
                         keep if masked else None)
        finish(b0, b1, sub)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
               block_q: int, block_k: int, cut, window=None):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.bfloat16)
    do = do_ref[0].astype(jnp.bfloat16)
    lse = lse_ref[0, 0, 0][:, None]    # own q-rows only (blocked spec)
    delta = delta_ref[0, 0, 0][:, None]
    BQ, D = q.shape

    def grad(rows, k0, width, dq, keep):
        """dq of the q-rows ``rows`` from keys [k0, k0 + width)."""
        kb = k_ref[0, pl.ds(k0, width), :].astype(jnp.bfloat16)
        vb = v_ref[0, pl.ds(k0, width), :].astype(jnp.bfloat16)
        s = jax.lax.dot_general(q[rows], kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        p = jnp.exp(s - lse[rows])
        if keep is not None:
            p = jnp.where(keep, p, 0.0)
        dp = jax.lax.dot_general(do[rows], vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[rows])).astype(jnp.bfloat16)
        return dq + jax.lax.dot_general(ds, kb, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    whole = slice(0, BQ)
    lo, hi = causal_span(qi, block_q, block_k)
    first = 0
    if window is not None:
        edge, first, _ = window_span(qi, window // block_k, 0, True)
    dq = jax.lax.fori_loop(
        first, lo, lambda ki, a: grad(whole, ki * block_k, block_k, a, None),
        jnp.zeros((BQ, D), jnp.float32))
    if window is not None:
        def step(on_edge, b0, b1, rect, sub, keep):
            _, _, c0, c1, _ = rect
            return grad(slice(b0, b1), (edge if on_edge else qi) * block_k + c0,
                        c1 - c0, sub, keep)

        def finish(b0, b1, sub):
            dq_ref[0, b0:b1, :] = sub.astype(dq_ref.dtype)

        _edge_then_diagonal(edge >= 0, BQ, cut, True, dq, step, finish)
        return
    if cut is None:
        dq = jax.lax.fori_loop(lo, hi, lambda ki, a: grad(
            whole, ki * block_k, block_k, a,
            _keep(BQ, block_k, ki * block_k - qi * block_q)), dq)
        dq_ref[0] = dq.astype(dq_ref.dtype)
        return
    keep = _keep(cut, cut, 0)
    for b0, b1, rects in diagonal_cuts(BQ, cut, True):
        sub = dq[b0:b1]
        for _, _, c0, c1, masked in rects:
            sub = grad(slice(b0, b1), qi * block_k + c0, c1 - c0, sub,
                       keep if masked else None)
        dq_ref[0, b0:b1, :] = sub.astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, *acc_refs, block_q: int, block_k: int, cut,
                window=None):
    ki = pl.program_id(1)
    kb = k_ref[0].astype(jnp.bfloat16)  # [BK, D] (this program's k chunk)
    vb = v_ref[0].astype(jnp.bfloat16)
    BK = kb.shape[0]
    nq = q_ref.shape[1] // block_q

    def grad(qi, r0, r1, cols, carry, keep):
        """dk, dv of this program's keys ``cols`` from rows [r0, r1) of
        q-tile ``qi``, key-major: every tile is [K, Q], so the two sums are
        plain products and lse / delta stay the lane vectors they are."""
        dk, dv = carry
        at = pl.ds(qi * block_q + r0, r1 - r0)
        q = q_ref[0, at, :].astype(jnp.bfloat16)
        do = do_ref[0, at, :].astype(jnp.bfloat16)
        lse = lse_ref[0, qi, :, r0:r1]      # [1, Q]
        delta = delta_ref[0, qi, :, r0:r1]
        st = jax.lax.dot_general(kb[cols], q, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        pt = jnp.exp(st - lse)  # [K, Q]
        if keep is not None:
            pt = jnp.where(keep, pt, 0.0)
        dv = dv + jax.lax.dot_general(
            pt.astype(jnp.bfloat16), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(vb[cols], do, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        dst = (pt * (dpt - delta)).astype(jnp.bfloat16)
        dk = dk + jax.lax.dot_general(dst, q, (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dk, dv

    def finish(c0, c1, carry):
        # With a group of query heads (the grid's third dimension) the
        # output block stays in VMEM until the group's last program has
        # written it; the float32 sums live in the scratch accumulators.
        for acc_ref, part in zip(acc_refs, carry):
            acc_ref[c0:c1, :] = part
        dk_ref[0, c0:c1, :] = carry[0].astype(dk_ref.dtype)
        dv_ref[0, c0:c1, :] = carry[1].astype(dv_ref.dtype)

    whole = slice(0, BK)
    zero = jnp.zeros(kb.shape, jnp.float32)
    # one constant where keys and values are as wide: the kernel's text is
    # then what it was before values could have a width of their own
    start = (zero, zero if vb.shape == kb.shape
             else jnp.zeros(vb.shape, jnp.float32))
    if acc_refs:
        # A select, not a product: what the scratch holds before the
        # group's first program wrote it is anything.
        fresh = pl.program_id(2) == 0
        start = tuple(jnp.where(fresh, zero, ref[...])
                      for zero, ref in zip(start, acc_refs))
    lo, hi = causal_span(ki, block_k, block_q)
    last = nq
    if window is not None:
        edge, _, last = window_span(ki, window // block_q, nq, False)
    carry = jax.lax.fori_loop(
        hi, last, lambda qi, c: grad(qi, 0, block_q, whole, c, None), start)
    if window is not None:
        def step(on_edge, b0, b1, rect, sub, keep):
            r0, r1, _, _, _ = rect
            return grad(edge if on_edge else ki, r0, r1, slice(b0, b1), sub,
                        keep)

        _edge_then_diagonal(edge < nq, BK, cut, False, carry, step, finish)
        return
    if cut is None:
        finish(0, BK, jax.lax.fori_loop(lo, hi, lambda qi, c: grad(
            qi, 0, block_q, whole, c,
            _keep(BK, block_q, ki * block_k - qi * block_q, True)), carry))
        return
    keep = _keep(cut, cut, 0, key_major=True)
    for b0, b1, rects in diagonal_cuts(BK, cut, False):
        sub = tuple(x[b0:b1] for x in carry)
        for r0, r1, _, _, masked in rects:
            sub = grad(ki, r0, r1, slice(b0, b1), sub,
                       keep if masked else None)
        finish(b0, b1, sub)


def _traced_once(fn):
    """A model calls these wrappers once a layer and pass with one shape, and
    a kernel body of static cuts is some hundred ops to trace: jit caches the
    trace, and ``inline`` replays it into the caller's jaxpr under the
    caller's name stack, so the program and its ``op_name``s stay the same."""
    return jax.jit(fn, static_argnames=("block_q", "block_k", "cut",
                                        "interpret", "window", "vmem"),
                   inline=True)


def _kv_specs(L, D, Dv, group: int):
    """K (width ``D``) and V (width ``Dv``) whole, for the program of query
    head ``bh``: its own, or with grouped queries head ``bh // group``'s
    (consecutive programs of a group name the same block, which is then not
    fetched again)."""
    if group == 1:
        return [_full_spec(L, D), _full_spec(L, Dv)]
    return [_full_spec(L, width, lambda bh, i: (bh // group, 0, 0))
            for width in (D, Dv)]


@_traced_once
def _flash_bhld(q, k, v, block_q, block_k, cut, interpret, window=None,
                vmem=None):
    """Forward on [BH, L, D] queries (k: [BH / group, L, D], v: [BH / group,
    L, Dv]); returns (out [BH, L, Dv], lse [BH, nq, 1, block_q])."""
    BH, L, D = q.shape
    Dv = v.shape[-1]
    grid = (BH, L // block_q)
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, block_q=block_q, block_k=block_k,
                          cut=cut, window=window),
        grid=grid,
        in_specs=[_qblock_spec(block_q, D),
                  *_kv_specs(L, D, Dv, BH // k.shape[0])],
        out_specs=[
            _qblock_spec(block_q, Dv),
            _rowblock_spec(block_q),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, L, Dv), q.dtype),
            jax.ShapeDtypeStruct((BH, L // block_q, 1, block_q), jnp.float32),
        ],
        interpret=interpret,
        name="dk_flash_fwd",
        **_parallel_kw(interpret, vmem=vmem),
    )
    # The kernel's name (the Mosaic call's, on a chip) and the scope of the
    # same name (the interpreted ops', on a CPU) are what a trace finds it by.
    with jax.named_scope("dk_flash_fwd"):
        out, lse = call(q, k, v)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, block_q, block_k, cut, interpret, window, vmem):
    out, _ = _flash_bhld(q, k, v, block_q, block_k, cut, interpret, window,
                         vmem)
    return out


def _flash_fwd(q, k, v, block_q, block_k, cut, interpret, window, vmem):
    out, lse = _flash_bhld(q, k, v, block_q, block_k, cut, interpret, window,
                           vmem)
    out, lse = map(checkpoint_name, (out, lse), FLASH_RESIDUALS)
    return out, (q, k, v, out, lse)


def residual_bytes(batch: int, seq_len: int, heads: int, head_dim: int,
                   dtype) -> int:
    """Bytes of the ``out`` and ``lse`` (float32, one a row) that one call on
    ``[batch, seq_len, heads, head_dim]`` queries leaves to its backward:
    what a policy that saves :data:`FLASH_RESIDUALS` keeps a layer."""
    return batch * heads * seq_len * (head_dim * jnp.dtype(dtype).itemsize + 4)


def _flash_bwd(block_q, block_k, cut, interpret, window, vmem, res, do):
    return _flash_grads(*res, do, block_q, block_k, cut, interpret, window,
                        vmem)


@_traced_once
def _flash_grads(q, k, v, out, lse, do, block_q, block_k, cut, interpret,
                 window=None, vmem=None):
    BH, L, D = q.shape
    Dv = v.shape[-1]
    nq = L // block_q
    group = BH // k.shape[0]
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(BH, nq, 1, block_q)

    dq_call = pl.pallas_call(
        functools.partial(_dq_kernel, block_q=block_q, block_k=block_k,
                          cut=cut, window=window),
        grid=(BH, nq),
        in_specs=[_qblock_spec(block_q, D), *_kv_specs(L, D, Dv, group),
                  _qblock_spec(block_q, Dv), _rowblock_spec(block_q),
                  _rowblock_spec(block_q)],
        out_specs=_qblock_spec(block_q, D),
        out_shape=jax.ShapeDtypeStruct((BH, L, D), q.dtype),
        interpret=interpret,
        name="dk_flash_dq",
        **_parallel_kw(interpret, vmem=vmem),
    )
    with jax.named_scope("dk_flash_dq"):
        dq = dq_call(q, k, v, do, lse, delta)

    kernel = functools.partial(_dkv_kernel, block_q=block_q, block_k=block_k,
                               cut=cut, window=window)
    out_shape = [jax.ShapeDtypeStruct(k.shape, k.dtype),
                 jax.ShapeDtypeStruct(v.shape, v.dtype)]
    if group == 1:
        dkv_call = pl.pallas_call(
            kernel,
            grid=(BH, L // block_k),
            in_specs=[_full_spec(L, D), _qblock_spec(block_k, D),
                      _qblock_spec(block_k, Dv), _full_spec(L, Dv),
                      _fullrow_spec(nq, block_q), _fullrow_spec(nq, block_q)],
            out_specs=[_qblock_spec(block_k, D), _qblock_spec(block_k, Dv)],
            out_shape=out_shape,
            interpret=interpret,
            name="dk_flash_dkv",
            **_parallel_kw(interpret, vmem=vmem),
        )
    else:
        # One program a (K/V head, block of keys, query head of the group);
        # the group runs innermost and in order over one output block.
        def of_query(bh, i, g):
            return (bh * group + g, 0, 0)

        def of_keys(bh, i, g):
            return (bh, i, 0)

        def rows_of_query(bh, i, g):
            return (bh * group + g, 0, 0, 0)

        dkv_call = pl.pallas_call(
            kernel,
            grid=(k.shape[0], L // block_k, group),
            in_specs=[_full_spec(L, D, of_query),
                      _qblock_spec(block_k, D, of_keys),
                      _qblock_spec(block_k, Dv, of_keys),
                      _full_spec(L, Dv, of_query),
                      _fullrow_spec(nq, block_q, rows_of_query),
                      _fullrow_spec(nq, block_q, rows_of_query)],
            out_specs=[_qblock_spec(block_k, D, of_keys),
                       _qblock_spec(block_k, Dv, of_keys)],
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((block_k, width), jnp.float32)
                            for width in (D, Dv)],
            interpret=interpret,
            name="dk_flash_dkv",
            **_parallel_kw(interpret, arbitrary=1, vmem=vmem),
        )
    with jax.named_scope("dk_flash_dkv"):
        dk, dv = dkv_call(q, k, v, do, lse, delta)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def _lanes(width: int) -> int:
    """The lanes a row of ``width`` takes in VMEM: whole tiles of 128."""
    return -(-width // 128) * 128


def _resident(L: int, D: int, Dv: int) -> tuple:
    """``(bytes, bytes a row)`` that K and V whole, double-buffered, take in
    VMEM beside a tile, and what a program's own rows add where keys are
    wider than values (:func:`default_tiling` says why)."""
    lanes = _lanes(D) + _lanes(Dv)
    if Dv == D:
        return 2 * L * lanes * 2, 0
    return 2 * L * lanes * 2 + 2 * 2 * L * 8 * 4, 8 * lanes


def scoped_vmem(L: int, D: int, block_size: int = 128,
                Dv: int | None = None):
    """The scoped VMEM the kernels ask for at ``(L, D, Dv)``: ``None``,
    Mosaic's default 16 MiB, wherever K and V whole leave room in it for a
    tile of two blocks (every shape the benchmark ran before heads of 256),
    else ``_VMEM_WIDE_BYTES``."""
    resident, own = _resident(L, D, D if Dv is None else Dv)
    two = 2 * block_size
    if resident + (10 * two + own) * two <= _VMEM_BYTES:
        return None
    return _VMEM_WIDE_BYTES


def default_tiling(L: int, D: int, block_size: int = 128, window=None,
                   Dv: int | None = None):
    """``(block_q, block_k, cut)`` for sequences of ``L`` and heads of ``D``
    (values of ``Dv`` where that is another width),
    every edge a multiple of ``block_size`` that divides ``L`` (the module
    doc has the measurements). Up to 16 blocks the tile is the sequence: no
    loop, only the diagonal's static cuts. Longer, the largest tile up to 8
    blocks whose score temporaries (some 10 bytes an element) fit the scoped
    VMEM beside K and V and the program's own blocks. ``cut`` is a quarter of
    L between 2 and 4 blocks, and at most half the tile. Under a ``window``
    shorter than ``L`` the tile also divides the window, whatever ``L``: a
    tile as long as the sequence would hold the whole window and skip none of
    it. Where K and V whole leave no room for two blocks in the default
    limit (:func:`scoped_vmem`), the tile is sized against the limit the
    kernels then ask for."""
    units = L // block_size
    reach = units if window is None else window // block_size
    if units <= 16 and window is None:
        tile = units
    else:
        # K and V whole, double-buffered, beside the score temporaries. Where
        # keys are wider than values (192 beside 128 take 256 and 128 lanes)
        # that estimate, which fits equal widths as measured, is too near the
        # limit to leave out the rest: a program's own blocks in and out and
        # dk/dv's lse and delta (a row of floats padded to 8 sublanes), all
        # double-buffered. Tiles of 256, not 512, at L = 8,192.
        Dv = D if Dv is None else Dv
        resident, own = _resident(L, D, Dv)
        limit = scoped_vmem(L, D, block_size, Dv) or _VMEM_BYTES
        tile = max(m for m in range(1, 9)
                   if units % m == 0 and reach % m == 0 and (
                       m == 1
                       or resident + (10 * m * block_size + own)
                       * m * block_size <= limit))
    want = min(4, max(2, units // 4))
    cut = max(m for m in range(1, want + 1)
              if tile % m == 0 and (2 * m <= tile or m == 1))
    return tile * block_size, tile * block_size, cut * block_size


def flash_attention(q, k, v, block_size: int = 128, block_k: int | None = None,
                    interpret: bool | None = None, window: int | None = None):
    """Causal FlashAttention. ``q``: [B, L, H, D], pre-scaled by 1/sqrt(D);
    ``k``: [B, L, H / group, D] and ``v``: [B, L, H / group, Dv], where query
    head ``h`` reads K/V head ``h // group`` (group 1: plain multi-head
    attention). Returns [B, L, H, Dv]. Values may have a width of their own
    (latent attention: keys of 192 beside values of 128): ``out``, ``do`` and
    ``dv`` then have the values' width, ``dq`` and ``dk`` the keys', and the
    keys' columns are contracted as one block of ``D`` whatever part of a
    128-lane tile the last of them fills; nothing is padded in HBM. With ``Dv
    == D`` the kernels are what they were. ``block_size`` is the grain: ``L``
    must be a multiple of it (128 on a TPU: the lane width). With
    ``block_k=None`` the tiling follows ``L`` and ``D`` (:func:`default_tiling`: square tiles
    of up to the whole sequence, the diagonal tile cut at 2-4 grains;
    1024 x 1024 cut at 256 for L = 1024, which visits 62.5 % of the scores).
    An explicit ``block_k`` asks for ``block_size`` x ``block_k`` tiles as
    they are, straddling tiles masked whole. ``window``: query ``i`` sees the
    keys ``j`` with ``0 <= i - j < window``; it must be a multiple of the
    (square) tile, and one that reaches past the sequence changes nothing.
    The share of L^2 the schedule
    visits is set on the gauge ``pallas.flash.visited_share`` as the call is
    traced. ``interpret=None`` compiles on TPU and interprets elsewhere
    (:mod:`distkeras_tpu.ops.pallas.mode`).
    """
    interpret = mode.interpret("flash_attention", interpret)
    B, L, H, D = q.shape
    kv_heads, Dv = k.shape[2], v.shape[-1]
    if k.shape != (B, L, kv_heads, D) or v.shape != (B, L, kv_heads, Dv) \
            or H % kv_heads:
        raise ValueError(f"q {q.shape} against k {k.shape} and v {v.shape}: "
                         "K/V heads must divide the query heads, and keys "
                         "have the queries' width")
    if L % block_size != 0 or (block_k is not None and L % block_k != 0):
        raise ValueError(
            f"seq_len {L} not divisible by block_q {block_size} / "
            f"block_k {block_k}")
    if window is not None and window >= L:
        window = None
    if block_k is None:
        if window is not None and window % block_size:
            raise ValueError(f"window {window} is not a multiple of the "
                             f"grain {block_size}")
        block_q, block_k, cut = default_tiling(L, D, block_size, window, Dv)
        vmem = scoped_vmem(L, D, block_size, Dv)
    else:
        vmem = None
        # A square tiling masks its diagonal tile by a constant (cut = tile).
        block_q = block_size
        cut = block_size if block_k == block_size else None
        if window is not None and (cut is None or window % block_q):
            raise ValueError(
                f"window {window} needs square tiles that divide it, got "
                f"{block_q} x {block_k}")
    from distkeras_tpu import telemetry

    telemetry.gauge("pallas.flash.visited_share").set(
        visited_share(L, block_q, block_k, cut, window))

    def to_bhld(x):
        return x.transpose(0, 2, 1, 3).reshape(-1, L, x.shape[-1])

    out = _flash(to_bhld(q), to_bhld(k), to_bhld(v), block_q, block_k, cut,
                 interpret, window, vmem)
    return out.reshape(B, H, L, Dv).transpose(0, 2, 1, 3)
