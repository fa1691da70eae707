"""Dequant-fused commit folds: accumulate compressed deltas into f32.

The netps server's hot loop is ``center += scale * delta`` per tensor.
With compressed deltas (``DKTPU_NET_COMPRESS=int8|bf16``) the stock path
decodes the wire tensor to a full f32 copy first — an extra read+write of
every byte, on the host. These kernels fuse the dequantization into the
accumulate: one pass reads the f32 center block and the *wire-dtype*
delta block (int8: 4x fewer delta bytes through the memory system; bf16:
2x), applies ``center + (commit_scale · tensor_scale) · dequant(q)`` in
VREGs, and writes the center block back. Dispatched from the ONE shared
``netps/fold.py`` (so raced-parity evidence transfers); the pure-numpy
reference there is the semantics oracle — interpret-mode parity is pinned
by ``tests/test_pallas_fold.py`` and the CI fold-parity job.

Shapes: tensors are flattened and padded to ``[rows, 128]`` with rows a
multiple of 32 (the int8 sublane tile; covers uint16's 16 and f32's 8),
gridded over row blocks. The scale rides in SMEM as the canonical (1, 1)
scalar block.
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

_LANES = 128
#: rows per grid step (512 x 128 f32 = 256 KiB center block in VMEM).
_BLOCK_ROWS = 512
#: row padding quantum: the int8 min sublane tile (covers u16/f32 too).
_ROW_ALIGN = 32


def _fold_kernel(s_ref, c_ref, q_ref, o_ref, *, codec):
    q = q_ref[...]
    if codec == "int8":
        d = q.astype(jnp.float32)
    else:  # bf16: bit-truncated mantissa — shift back up and bitcast
        d = lax.bitcast_convert_type(
            q.astype(jnp.uint32) << jnp.uint32(16), jnp.float32)
    o_ref[...] = c_ref[...] + s_ref[0, 0] * d


def _compiler_kw(interpret: bool) -> dict:
    if interpret:
        return {}
    # Each program owns its own center block: order-independent grid.
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel",))}


@functools.lru_cache(maxsize=None)
def _folder(codec: str, rows: int, wire_dtype: str, interpret: bool):
    # Callers pad rows to a multiple of _BLOCK_ROWS past one block, so the
    # per-program VMEM footprint is bounded by the block size — a large
    # tensor must never become one whole-tensor block (that would blow the
    # VMEM budget at compile time on a real chip).
    block = min(rows, _BLOCK_ROWS)
    grid = rows // block
    call = pl.pallas_call(
        functools.partial(_fold_kernel, codec=codec),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((block, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((block, _LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block, _LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
        interpret=interpret,
        name="dk_fold_decode",
        **_compiler_kw(interpret),
    )

    def folder(s, c, q):
        with jax.named_scope("dk_fold_decode"):
            return call(s, c, q)

    return folder


def fold_traced(center, q, s, *, codec: str, interpret: bool | None = None):
    """Traceable twin of :func:`fold_compressed` for use INSIDE a jitted
    collective body (the netps mesh dialect folds each device's center
    shard through this under ``shard_map``): same kernel, same pad/
    reshape discipline, but in jnp so the padding and the ``pallas_call``
    trace into the surrounding program instead of staging through host
    numpy. ``center`` is the local f32 shard, ``q`` the matching
    wire-dtype shard, ``s`` a traced f32 scalar already folded to
    ``commit_scale · tensor_scale``. ``interpret=None`` compiles on TPU and
    interprets elsewhere (:mod:`distkeras_tpu.ops.pallas.mode`)."""
    n = int(np.prod(center.shape, dtype=np.int64)) if center.ndim else 1
    if n == 0:
        return center
    interpret = mode.interpret("fold", interpret)
    rows = -(-n // _LANES)
    rows += (-rows) % _ROW_ALIGN
    if rows > _BLOCK_ROWS:
        rows += (-rows) % _BLOCK_ROWS
    total = rows * _LANES
    cp = jnp.reshape(center.astype(jnp.float32), (-1,))
    qp = jnp.reshape(q, (-1,))
    if total != n:
        cp = jnp.pad(cp, (0, total - n))
        qp = jnp.pad(qp, (0, total - n))
    wire_dtype = np.int8 if codec == "int8" else np.uint16
    out = _folder(codec, rows, np.dtype(wire_dtype).str, interpret)(
        jnp.reshape(s, (1, 1)).astype(jnp.float32),
        jnp.reshape(cp, (rows, _LANES)),
        jnp.reshape(qp, (rows, _LANES)))
    return jnp.reshape(jnp.reshape(out, (-1,))[:n], center.shape)


def fold_compressed(center, wire_arr, spec: dict, scale: float,
                    interpret: bool | None = None) -> np.ndarray:
    """``center + scale * dequant(wire_arr)`` with the dequant fused into
    the accumulate — returns a NEW array shaped like ``center`` (the
    caller assigns; the numpy reference mutates in place instead).

    ``spec`` is the wire array spec (``codec`` + ``scale`` for int8);
    ``scale`` is the discipline's commit scale."""
    codec = spec.get("codec")
    if codec == "int8":
        # Strict, like the numpy oracle: a scale-less spec must raise, not
        # silently fold zero — the two backends may never diverge.
        s = float(scale) * float(spec["scale"])
        wire_dtype = np.int8
    elif codec == "bf16":
        s = float(scale)
        wire_dtype = np.uint16
    else:
        raise ValueError(f"unknown codec {codec!r} in delta spec")
    c = np.ascontiguousarray(center, np.float32)
    if c.size == 0 or s == 0.0:
        return c.copy().reshape(np.shape(center))
    interpret = mode.interpret("fold", interpret)
    q = np.ascontiguousarray(wire_arr, wire_dtype).reshape(-1)
    n = c.size
    rows = -(-n // _LANES)
    rows += (-rows) % _ROW_ALIGN
    if rows > _BLOCK_ROWS:  # bounded per-program blocks (see _folder)
        rows += (-rows) % _BLOCK_ROWS
    total = rows * _LANES
    if total == n:
        # Aligned tensor (the common big-tensor case): feed views, no
        # padded staging buffers — the remaining host traffic is the
        # device transfer + copy-back, which the on-device-center
        # follow-up (ROADMAP) removes.
        cp = c.reshape(rows, _LANES)
        qp = q.reshape(rows, _LANES)
    else:
        cp = np.zeros(total, np.float32)
        cp[:n] = c.reshape(-1)
        cp = cp.reshape(rows, _LANES)
        qp = np.zeros(total, wire_dtype)
        qp[:n] = q
        qp = qp.reshape(rows, _LANES)
    out = _folder(codec, rows, np.dtype(wire_dtype).str, interpret)(
        np.asarray([[s]], np.float32), cp, qp)
    return np.asarray(out).reshape(-1)[:n].reshape(np.shape(center))
