"""Rows between token order and an expert layer's sorted buffer, as Pallas TPU
kernels that touch the live rows and no others.

The buffer (``models/blocks.py::DroplessExperts``) has a row for every
assignment a step can produce, ``N = tokens x k``, and the ``live`` first of
them hold the assignments to the experts held here: an eighth of it at the
deployment's load. XLA's gather and scatter-add move a static number of rows
at a fixed price a call (3.8 and 3.1 ms for a third of 98,304 rows of 2560
bfloat16 on a v5e, whatever is live); these move ``live`` rows, a DMA each
(17 ns a row measured: PERF.md, PR 29).

* :func:`gather` (``dk_rows_gather``): ``out[r] = x[token[r]]`` for ``r <
  live``. ``token`` and ``live`` arrive by scalar prefetch, the source stays
  in HBM. A program fills one tile of ``R`` buffer rows: it starts a copy a
  live row into a VMEM scratch, waits for them, and writes the tile. The
  grid's bound is the number of tiles that hold a live row.
* :func:`combine` (``dk_rows_combine``), the gather's transpose **as a gather
  by token**: ``out[t] = sum_j [slot[t, j] < live] rows[slot[t, j]]`` in
  float32. ``slot[t, j]`` is the buffer row of assignment ``(t, j)``. A
  program owns a tile of tokens: it fetches their live rows, in assignment
  order, into the head of a VMEM scratch, and sums each token's run of them
  by one product with a 0/1 matrix on the MXU (the rows' values are exact in
  its operands, the sum is float32 in the unit's fixed order). No row of the
  result is written by two programs, nothing is atomic, and a sum is the same
  bits run after run. A token with no live row reads nothing and gets zeros.
  Which slots are live is listed outside the kernel (:func:`_live_lists`): a
  scalar loop that tests all ``tokens x k`` slots costs more than the copies.

**One row, one DMA** needs a source whose rows can be addressed. Mosaic
slices an array in HBM by whole tiles of its two minor dimensions, (8, 128)
words of 32 bits, and a bfloat16 array keeps two rows in a word: a row of a
``[T, D]`` array is neither. So both kernels read a copy laid out a row a
tile, ``[T, 1, W]`` of 32-bit words (:func:`_addressable`, kernel
``dk_rows_pack``): a float32 row as it is, ``W = D``; a bfloat16 row with
column ``c`` in the low half of word ``c`` and column ``c + D / 2`` in the
high half, ``W = D / 2`` (shifts and masks, exact: a bfloat16 is the high
half of its float32). That pass reads dense tiles and writes as many bytes,
and like the gather it visits the live tiles only.

**The buffer's dead rows.** The gather writes the tiles that hold a live row
(the first tile always): in them the rows from ``live`` on are zeros. The
tiles past the last live one are **unwritten and never read**: no program
runs for them, and what they hold is whatever the allocation held. The
combine reads rows below ``live`` only. Whoever sits between the two (the
grouped products, elementwise passes over the whole buffer) may compute on
dead rows but must let none of it reach a live row, an output or a gradient;
``tests/test_pallas_rows.py`` fills them with NaN to hold that.

**The tile** follows the row's bytes by one rule (:func:`tile_rows`), as
``flash_attention.default_tiling`` follows ``(L, D)``: the largest power of
two up to 512 rows whose scratch and double-buffered blocks fit
``_VMEM_BUDGET``, and never more than the (padded) array: the interpreter,
which pays by the grid step, gets one step for the tests' sizes. A buffer or
a token count that is not a whole number of tiles, or a bfloat16 width that
is odd, is padded here, nowhere else.

Off a TPU the same kernels run under the Pallas interpreter
(``ops/pallas/mode.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distkeras_tpu.ops.pallas import mode

_VMEM_BUDGET = 12 * 2 ** 20   # scratch + double-buffered blocks of one call
_VMEM_LIMIT = 32 * 2 ** 20    # of a v5e's 128 MiB; Mosaic's default is 16
_MAX_TILE = 512
_GRAIN = 16                   # rows of a bfloat16 sublane tile
_LANES = 128
_HIGH = 0xFFFF0000             # a word's high half: the second bfloat16
_UNROLL = 8                    # copies started a trip of the gather's loop
_GROUP = 16                    # tokens whose live slots are listed together


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _words(D: int, dtype) -> int:
    """32-bit words that hold a row of ``D`` elements of ``dtype``."""
    size = jnp.dtype(dtype).itemsize
    if size not in (2, 4):
        raise NotImplementedError(f"rows of {jnp.dtype(dtype)}")
    return D if size == 4 else _round_up(D, 2) // 2


def tile_rows(rows: int, bytes_a_row: int) -> int:
    """Rows a program owns, when a row costs ``bytes_a_row`` of VMEM (scratch
    and double-buffered blocks): a power of two of whole sublane tiles, at
    most ``_MAX_TILE``, and at most ``rows`` rounded up to a sublane tile
    (one program, then)."""
    tile = _GRAIN
    while tile * 2 <= _MAX_TILE and tile * 2 * bytes_a_row <= _VMEM_BUDGET:
        tile *= 2
    return min(tile, _round_up(rows, _GRAIN))


def _lane_bytes(D: int, itemsize: int) -> int:
    return _round_up(D, _LANES) * itemsize


def gather_tile(N: int, D: int, dtype) -> int:
    """Buffer rows a program of :func:`gather` fills: a row of scratch words
    and two of output."""
    return tile_rows(N, _lane_bytes(_words(D, dtype), 4)
                     + 2 * _lane_bytes(D, jnp.dtype(dtype).itemsize))


def combine_tile(T: int, k: int, D: int, dtype) -> int:
    """Tokens a program of :func:`combine` sums, a multiple of ``_GROUP``:
    ``k`` rows of scratch words, two float32 rows of output, and two each of
    the token's place, slots and weights."""
    return tile_rows(T, k * _lane_bytes(_words(D, dtype), 4)
                     + 2 * _lane_bytes(D, 4) + 6 * _lane_bytes(k, 4))


def _live_tiles(live, tile: int):
    """Grid steps of a kernel that walks the buffer: the tiles that hold a
    live row, the first always. The bound is dynamic: a tile past them costs
    nothing, not even a step."""
    return jnp.maximum(-(-jnp.asarray(live, jnp.int32) // tile), 1)


def visited_rows(live, rows: int, tile: int):
    """Buffer rows in the tiles the gather writes for ``live`` live rows."""
    return jnp.minimum(_live_tiles(live, tile) * tile, rows)


def _compiler_kw(interpret: bool) -> dict:
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT)}


def _to_words(x):
    """``[R, D]`` -> ``[R, W]`` uint32 (the module doc has the layout)."""
    if x.dtype.itemsize == 4:
        return jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    W = x.shape[1] // 2
    return (bits[:, :W] >> 16) | (bits[:, W:] & jnp.uint32(_HIGH))


def _from_words(words, dtype):
    """``[R, W]`` uint32 to ``[R, D]`` float32, the ``dtype`` values exact."""
    if jnp.dtype(dtype).itemsize == 4:
        return jax.lax.bitcast_convert_type(words, dtype).astype(jnp.float32)
    return jax.lax.bitcast_convert_type(
        jnp.concatenate([words << 16, words & jnp.uint32(_HIGH)], axis=1),
        jnp.float32)


def _each(steps: int, body, carry):
    """``fori_loop(0, steps, body, carry)`` for a static ``steps``, unrolled
    by hand ``_UNROLL`` at a time (Mosaic's ``fori_loop`` unrolls all of a
    loop or nothing)."""
    unroll = _UNROLL if steps % _UNROLL == 0 else 1

    def block(b, carry):
        for u in range(unroll):
            carry = body(b * unroll + u, carry)
        return carry

    return jax.lax.fori_loop(0, steps // unroll, block, carry)


def _pack_kernel(x, out):
    out[:, 0, :] = _to_words(x[...])


def _addressable(x, live, interpret: bool):
    """``x`` [M, D] as ``[M', 1, W]`` uint32, a row a tile: what a DMA can
    take one row of. Tiles past the one that holds row ``live - 1`` are
    neither read nor written."""
    M, D = x.shape
    W = _words(D, x.dtype)
    if x.dtype.itemsize == 2 and D % 2:
        x = jnp.pad(x, ((0, 0), (0, 1)))
    D = x.shape[1]
    tile = tile_rows(M, 2 * _lane_bytes(D, x.dtype.itemsize)
                     + 2 * _lane_bytes(W, 4))
    rows = _round_up(M, tile)
    if rows != M:
        x = jnp.pad(x, ((0, rows - M), (0, 0)))
    return pl.pallas_call(
        _pack_kernel, grid=(_live_tiles(live, tile),),
        in_specs=[pl.BlockSpec((tile, D), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((tile, 1, W), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((rows, 1, W), jnp.uint32),
        interpret=interpret, name="dk_rows_pack", **_compiler_kw(interpret),
    )(x)


def _gather_kernel(token_s, live_s, *refs, tile: int, scaled: bool):
    *scale_v, x_hbm, out, buf, sem = refs  # a scale a row, if scaled
    base = pl.program_id(0) * tile
    live = live_s[0]
    n = jnp.clip(live - base, 0, tile)

    def start(r, carry):
        @pl.when(r < n)
        def _():
            pltpu.make_async_copy(x_hbm.at[token_s[base + r]], buf.at[r],
                                  sem).start()
        return carry

    def wait(r, carry):  # same shape and semaphore: any of them
        pltpu.make_async_copy(x_hbm.at[0], buf.at[0], sem).wait()
        return carry

    _each(tile, start, 0)
    jax.lax.fori_loop(0, n, wait, 0)
    row = base + jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
    got = _from_words(buf[:, 0, :], out.dtype)[:, :out.shape[1]]
    if scaled:
        got = got * scale_v[0][...]
    # A select: what a dead row's scratch holds never gets out.
    out[...] = jnp.where(row < live, got, 0).astype(out.dtype)


def gather(x, token, live, scale=None, *, tile: int | None = None,
           interpret: bool | None = None):
    """``out[r] = x[token[r]]`` for ``r < live``, times ``scale[r]`` if given
    (the product in float32, rounded once); rows from ``live`` to the end of
    their tile are zeros, later tiles unwritten (the module doc). ``x``: [T,
    D]; ``token``: [N] int32, in ``[0, T)`` below ``live``; ``scale``: [N];
    returns [N, D] of ``x.dtype``. ``tile`` is for the tests."""
    return _gather(x, token, live, scale,
                   tile=tile or gather_tile(token.shape[0], x.shape[1],
                                            x.dtype),
                   interpret=mode.interpret("rows_gather", interpret))


# Jitted and inlined: under a trace it changes nothing, and a call outside
# one (the tests') finds its program again by the shapes.
@functools.partial(jax.jit, static_argnames=("tile", "interpret"), inline=True)
def _gather(x, token, live, scale, *, tile: int, interpret: bool):
    T, D = x.shape
    N = token.shape[0]
    rows = _round_up(N, tile)
    token = jnp.pad(token.astype(jnp.int32), (0, rows - N))
    live = jnp.asarray(live, jnp.int32).reshape(1)
    words = _addressable(x, T, interpret)
    scales, scale_specs = (), []
    if scale is not None:  # a value a sublane: what a row's lanes multiply by
        scales = (jnp.pad(scale.astype(jnp.float32),
                          (0, rows - N)).reshape(rows, 1),)
        scale_specs = [pl.BlockSpec((tile, 1), lambda i, t, n: (i, 0),
                                    memory_space=pltpu.VMEM)]
    out = pl.pallas_call(
        functools.partial(_gather_kernel, tile=tile, scaled=bool(scales)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(_live_tiles(live[0], tile),),
            in_specs=scale_specs + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile, D), lambda i, t, n: (i, 0),
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((tile,) + words.shape[1:], jnp.uint32),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct((rows, D), x.dtype),
        interpret=interpret, name="dk_rows_gather", **_compiler_kw(interpret),
    )(token, live, *scales, words)
    return out if rows == N else out[:N]


def _live_lists(slot, live):
    """The live slots of each group of ``_GROUP`` tokens, in assignment
    order, at the head of the group's ``_GROUP x k`` places, and how many
    they are: ``(lists [tokens x k], counts [tokens / _GROUP])``. A one-hot
    sum a group: no sort and no scatter, and XLA makes one fusion of it."""
    places = _GROUP * slot.shape[1]
    of_group = slot.reshape(-1, places).T          # [places, groups]
    alive = of_group < live
    place = jnp.cumsum(alive, axis=0, dtype=jnp.int32) - alive
    # Summed over the leading axis: whole vectors added, nothing crosses lanes.
    lands = alive[:, :, None] & (place[:, :, None] == jnp.arange(places))
    lists = jnp.sum(jnp.where(lands, of_group[:, :, None], 0), axis=0)
    return lists.reshape(-1), jnp.sum(alive, axis=0, dtype=jnp.int32)


def _combine_kernel(list_s, count_s, live_s, first_v, slot_v, weight_v,
                    rows_hbm, out, buf, sem, *, tile: int, k: int, chunk: int,
                    dtype):
    groups = tile // _GROUP
    first_group = pl.program_id(0) * groups
    live = live_s[0]

    def fetch(g, q):  # the group's live rows, behind the earlier groups'
        n = count_s[first_group + g]
        at = (first_group + g) * (_GROUP * k)

        def start(a, carry):
            pltpu.make_async_copy(rows_hbm.at[list_s[at + a]], buf.at[q + a],
                                  sem).start()
            return carry

        jax.lax.fori_loop(0, n, start, 0)
        return q + n

    def wait(n, carry):  # same shape and semaphore: any of them
        pltpu.make_async_copy(rows_hbm.at[0], buf.at[0], sem).wait()
        return carry

    q = jax.lax.fori_loop(0, groups, fetch, jnp.int32(0))
    jax.lax.fori_loop(0, q, wait, 0)
    out[...] = jnp.zeros(out.shape, jnp.float32)
    # Where in the scratch assignment (t, j) lies: behind its token's first
    # row by the live ones among the token's earlier choices; nowhere if dead.
    place_of, at_place = [], first_v[...]
    for j in range(k):
        alive = slot_v[:, j:j + 1] < live
        place_of.append(jnp.where(alive, at_place, -1))
        at_place = at_place + alive.astype(jnp.int32)
    D = out.shape[1]
    exact = jnp.dtype(dtype).itemsize == 4

    def product(c, carry):
        at = pl.multiple_of(c * chunk, chunk)
        place = at + jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
        # [tile, chunk]: assignment (t, j)'s weight at its row's place
        owns = sum(jnp.where(place == place_of[j], weight_v[:, j:j + 1], 0)
                   for j in range(k))
        row = at + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
        # A select: scratch past the fetched rows is whatever was there.
        words = jnp.where(row < q, buf[pl.ds(at, chunk), 0, :], 0)
        if exact:
            out[...] += jnp.dot(
                owns, jax.lax.bitcast_convert_type(words, dtype)
                .astype(jnp.float32), precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
            return carry
        W = words.shape[1]
        owns = owns.astype(jnp.bfloat16)
        for half, at_column in ((words << 16, 0),
                                (words & jnp.uint32(_HIGH), W)):
            width = min(W, D - at_column)
            part = jnp.dot(
                owns, jax.lax.bitcast_convert_type(half, jnp.float32)
                .astype(jnp.bfloat16), preferred_element_type=jnp.float32)
            out[:, at_column:at_column + width] += part[:, :width]
        return carry

    jax.lax.fori_loop(0, (q + chunk - 1) // chunk, product, 0)


def combine(rows, slot, live, weights=None, *, tile: int | None = None,
            interpret: bool | None = None):
    """``out[t] = sum_j [slot[t, j] < live] weights[t, j] rows[slot[t, j]]``
    in float32, in a fixed order (``weights`` None: ones; rounded to ``rows``'
    dtype, each product then exact). ``rows``: [N, D]; ``slot``, ``weights``:
    [T, k], ``slot`` int32 and below ``N`` where below ``live``; returns [T,
    D] float32. Rows from ``live`` on are not read. ``tile`` is for the
    tests."""
    return _combine(rows, slot, live, weights,
                    tile=tile or combine_tile(slot.shape[0], slot.shape[1],
                                              rows.shape[1], rows.dtype),
                    interpret=mode.interpret("rows_combine", interpret))


@functools.partial(jax.jit, static_argnames=("tile", "interpret"), inline=True)
def _combine(rows, slot, live, weights, *, tile: int, interpret: bool):
    N, D = rows.shape
    T, k = slot.shape
    tokens = _round_up(T, tile)
    # A padded token's assignments are dead whatever `live` is.
    slot = jnp.pad(slot.astype(jnp.int32), ((0, tokens - T), (0, 0)),
                   constant_values=jnp.iinfo(jnp.int32).max)
    weights = jnp.ones((tokens, k), jnp.float32) if weights is None \
        else jnp.pad(weights.astype(jnp.float32), ((0, tokens - T), (0, 0)))
    lists, counts = _live_lists(slot, live)
    # Where a token's rows begin in its tile's scratch.
    count = jnp.sum(slot < live, axis=1, dtype=jnp.int32).reshape(-1, tile)
    first = (jnp.cumsum(count, axis=1) - count).reshape(tokens, 1)
    words = _addressable(rows, live, interpret)
    chunk = min(_LANES, _round_up(tile * k, 8))

    def by_token(width):
        return pl.BlockSpec((tile, width), lambda i, *_: (i, 0),
                            memory_space=pltpu.VMEM)

    out = pl.pallas_call(
        functools.partial(_combine_kernel, tile=tile, k=k, chunk=chunk,
                          dtype=rows.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(tokens // tile,),
            in_specs=[by_token(1), by_token(k), by_token(k),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=by_token(D),
            scratch_shapes=[
                pltpu.VMEM((_round_up(tile * k, chunk),) + words.shape[1:],
                           jnp.uint32),
                pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct((tokens, D), jnp.float32),
        interpret=interpret, name="dk_rows_combine",
        **_compiler_kw(interpret),
    )(lists, counts, jnp.asarray(live, jnp.int32).reshape(1), first, slot,
      weights, words)
    return out if tokens == T else out[:T]
