"""LSTM recurrence as a single Pallas TPU program (forward + BPTT backward).

XLA lowers an ``nn.RNN``/``lax.scan`` recurrence to a device while-loop whose
per-iteration overhead dwarfs the tiny per-step cell matmul (~35-45us/step
measured in round 4 on an earlier single-chip setup; unroll=8/32 did not
help) — the IMDB LSTM config (BASELINE #4) measured <3% MFU that way. Here the whole
sequence runs inside ONE kernel: the packed weights load into VMEM once and
stay there across all T steps; the grid is (T,) (TPU grids are sequential, so
carried state lives in revisited output blocks — no scratch, interpreter-safe),
and per step the MXU sees one fused [B, E+H] x [E+H, 4H] gate matmul.

Backward is a second kernel walking the grid in reverse (index maps flip t),
accumulating dWx/dWh/db into constant-index output blocks that stay resident
in VMEM until the grid ends — zero per-step HBM traffic for the weight grads.
Residuals are the activated gates + cell states stashed by the forward pass
(the standard BPTT stash; recompute would double the matmul count).

Gate math follows flax's ``OptimizedLSTMCell`` exactly (i,f,g,o order,
sigmoid/tanh, ``c' = f*c + i*g``, ``h' = o*tanh(c')``);
``pack_lstm_params`` converts that cell's param tree into the packed
(Wx, Wh, b) layout so both implementations are interchangeable (equivalence-
tested in ``tests/test_pallas_lstm.py``).

``interpret=True`` runs the same kernels on CPU via the Pallas interpreter —
that is what CI exercises; the compiled path runs on real TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distkeras_tpu.ops.pallas import mode

GATES = ("i", "f", "g", "o")


def _sg(x):
    return jax.nn.sigmoid(x)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(x_ref, wx_ref, wh_ref, b_ref, hs_ref, *refs, T: int, H: int,
                stash: bool):
    if stash:
        cs_ref, gates_ref, h_ref, c_ref = refs
    else:
        cs_ref = gates_ref = None
        h_ref, c_ref = refs
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)
        c_ref[...] = jnp.zeros_like(c_ref)

    x_t = x_ref[0]                      # [B, E]
    h = h_ref[...]                      # [B, H] f32 carry
    c = c_ref[...]
    pre = (
        jax.lax.dot_general(x_t, wx_ref[...], (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
        + jax.lax.dot_general(h.astype(wh_ref.dtype), wh_ref[...],
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
        + b_ref[...].astype(jnp.float32)
    )                                   # [B, 4H] f32
    i = _sg(pre[:, 0 * H:1 * H])
    f = _sg(pre[:, 1 * H:2 * H])
    g = jnp.tanh(pre[:, 2 * H:3 * H])
    o = _sg(pre[:, 3 * H:4 * H])
    c = f * c + i * g
    h = o * jnp.tanh(c)
    h_ref[...] = h
    c_ref[...] = c
    hs_ref[0] = h.astype(hs_ref.dtype)
    if stash:
        cs_ref[0] = c.astype(cs_ref.dtype)
        gates_ref[0] = jnp.concatenate([i, f, g, o], axis=1).astype(gates_ref.dtype)


# ---------------------------------------------------------------------------
# backward (BPTT, grid walks time in reverse)
# ---------------------------------------------------------------------------
def _bwd_kernel(dhs_ref, x_ref, hprev_ref, cs_ref, cprev_ref, gates_ref,
                wx_ref, wh_ref,
                dx_ref, dwx_ref, dwh_ref, db_ref, dh_ref, dc_ref,
                *, T: int, H: int):
    g_idx = pl.program_id(0)
    s = T - 1 - g_idx                   # the time step this iteration owns

    @pl.when(g_idx == 0)
    def _init():
        dwx_ref[...] = jnp.zeros_like(dwx_ref)
        dwh_ref[...] = jnp.zeros_like(dwh_ref)
        db_ref[...] = jnp.zeros_like(db_ref)
        dh_ref[...] = jnp.zeros_like(dh_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    gates = gates_ref[0].astype(jnp.float32)          # [B, 4H]
    i = gates[:, 0 * H:1 * H]
    f = gates[:, 1 * H:2 * H]
    g = gates[:, 2 * H:3 * H]
    o = gates[:, 3 * H:4 * H]
    c_t = cs_ref[0].astype(jnp.float32)
    # c_{t-1} / h_{t-1}: the t-1 blocks (index maps clamp at 0; mask s == 0).
    first = (s == 0)
    c_prev = jnp.where(first, 0.0, cprev_ref[0].astype(jnp.float32))
    h_prev = jnp.where(first, 0.0, hprev_ref[0].astype(jnp.float32))

    dh = dh_ref[...] + dhs_ref[0].astype(jnp.float32)  # carry + incoming
    tanh_c = jnp.tanh(c_t)
    do_ = dh * tanh_c
    dc = dh * o * (1.0 - tanh_c * tanh_c) + dc_ref[...]
    di = dc * g
    df = dc * c_prev
    dg = dc * i
    dc_ref[...] = dc * f                               # carried to step s-1
    # through the activations -> pre-activation grads
    dpre = jnp.concatenate(
        [di * i * (1.0 - i), df * f * (1.0 - f),
         dg * (1.0 - g * g), do_ * o * (1.0 - o)], axis=1)  # [B, 4H] f32
    dpre_c = dpre.astype(wx_ref.dtype)
    # dx_s = dpre @ Wx^T ; dh_{s-1} = dpre @ Wh^T
    dx_ref[0] = jax.lax.dot_general(
        dpre_c, wx_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dx_ref.dtype)
    dh_ref[...] = jax.lax.dot_general(
        dpre_c, wh_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    # weight grads accumulate in-place in the constant-index output blocks
    x_t = x_ref[0]
    dwx_ref[...] += jax.lax.dot_general(
        x_t, dpre_c, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dwh_ref[...] += jax.lax.dot_general(
        h_prev.astype(wx_ref.dtype), dpre_c, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    db_ref[...] += jnp.sum(dpre, axis=0, keepdims=True)


# ---------------------------------------------------------------------------
# pallas_call plumbing
# ---------------------------------------------------------------------------
def _step_spec(B, D):
    return pl.BlockSpec((1, B, D), lambda t: (t, 0, 0))


def _rev_spec(B, D, T):
    return pl.BlockSpec((1, B, D), lambda t: (T - 1 - t, 0, 0))


def _rev_prev_spec(B, D, T):
    # the t-1 block under the reversed walk, clamped at 0 (masked in-kernel)
    return pl.BlockSpec((1, B, D), lambda t: (jnp.maximum(T - 1 - t - 1, 0), 0, 0))


def _const_spec(shape):
    nd = len(shape)
    return pl.BlockSpec(shape, lambda t: (0,) * nd)


def _vmem_kw(interpret: bool) -> dict:
    """Raise the scoped-VMEM cap: the kernel's per-step [B, 4H] gate block
    tops the default 16 MiB plan past B=2048 (18 MiB at B=4096, H=128),
    and large batches are the one lever that amortizes the recurrence's
    serial per-step latency (measured: B 512 -> 2048 lifts MFU 11.4% ->
    17.3%; see docs/PERFORMANCE.md round-4 LSTM section)."""
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=96 * 1024 * 1024)}


def _run_fwd(wx, wh, b, x_tbe, interpret: bool, stash: bool = True):
    """Forward pass; ``stash=False`` (inference/primal) skips the BPTT
    residual outputs — cs and gates are 5x the HBM write traffic of hs."""
    T, B, E = x_tbe.shape
    H = wh.shape[0]
    dt = x_tbe.dtype
    f32 = jnp.float32
    stash_specs = [_step_spec(B, H), _step_spec(B, 4 * H)] if stash else []
    stash_shapes = ([jax.ShapeDtypeStruct((T, B, H), dt),
                     jax.ShapeDtypeStruct((T, B, 4 * H), dt)] if stash else [])
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, T=T, H=H, stash=stash),
        grid=(T,),
        in_specs=[
            _step_spec(B, E),
            _const_spec((E, 4 * H)),
            _const_spec((H, 4 * H)),
            _const_spec((1, 4 * H)),
        ],
        out_specs=[_step_spec(B, H)] + stash_specs + [
            _const_spec((B, H)), _const_spec((B, H)),
        ],
        out_shape=[jax.ShapeDtypeStruct((T, B, H), dt)] + stash_shapes + [
            jax.ShapeDtypeStruct((B, H), f32),
            jax.ShapeDtypeStruct((B, H), f32),
        ],
        interpret=interpret,
        name="dk_lstm_fwd",
        **_vmem_kw(interpret),
    )
    with jax.named_scope("dk_lstm_fwd"):
        outs = call(x_tbe, wx, wh, b.reshape(1, -1))
    if stash:
        hs, cs, gates = outs[0], outs[1], outs[2]
        return hs, cs, gates
    return outs[0], None, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _lstm_tbe(wx, wh, b, x_tbe, interpret):
    hs, _, _ = _run_fwd(wx, wh, b, x_tbe, interpret, stash=False)
    return hs


def _lstm_fwd(wx, wh, b, x_tbe, interpret):
    hs, cs, gates = _run_fwd(wx, wh, b, x_tbe, interpret, stash=True)
    return hs, (wx, wh, b, x_tbe, hs, cs, gates)


def _lstm_bwd(interpret, res, dhs):
    wx, wh, b, x_tbe, hs, cs, gates = res
    T, B, E = x_tbe.shape
    H = wh.shape[0]
    f32 = jnp.float32
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, T=T, H=H),
        grid=(T,),
        in_specs=[
            _rev_spec(B, H, T),          # dhs
            _rev_spec(B, E, T),          # x_s
            _rev_prev_spec(B, H, T),     # h_{s-1}
            _rev_spec(B, H, T),          # c_s
            _rev_prev_spec(B, H, T),     # c_{s-1}
            _rev_spec(B, 4 * H, T),      # gates_s
            _const_spec((E, 4 * H)),
            _const_spec((H, 4 * H)),
        ],
        out_specs=[
            _rev_spec(B, E, T),          # dx
            _const_spec((E, 4 * H)),
            _const_spec((H, 4 * H)),
            _const_spec((1, 4 * H)),
            _const_spec((B, H)),         # dh carry
            _const_spec((B, H)),         # dc carry
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, E), x_tbe.dtype),
            jax.ShapeDtypeStruct((E, 4 * H), f32),
            jax.ShapeDtypeStruct((H, 4 * H), f32),
            jax.ShapeDtypeStruct((1, 4 * H), f32),
            jax.ShapeDtypeStruct((B, H), f32),
            jax.ShapeDtypeStruct((B, H), f32),
        ],
        interpret=interpret,
        name="dk_lstm_bwd",
        **_vmem_kw(interpret),
    )
    with jax.named_scope("dk_lstm_bwd"):
        dx, dwx, dwh, db, _dh, _dc = call(
            dhs, x_tbe, hs, cs, cs, gates, wx, wh)
    return (dwx.astype(wx.dtype), dwh.astype(wh.dtype),
            db[0].astype(b.dtype), dx)


_lstm_tbe.defvjp(_lstm_fwd, _lstm_bwd)


def lstm_seq(wx, wh, b, x, interpret: bool | None = None):
    """Full-sequence LSTM: ``x [B, T, E] -> hs [B, T, H]`` (h0 = c0 = 0).

    One Pallas program for the whole recurrence; differentiable (custom VJP
    runs BPTT as a reversed-grid kernel). Batch is padded to a multiple of 8
    (f32 sublane tile) and sliced back. ``interpret=None`` compiles on TPU
    and interprets elsewhere (:mod:`distkeras_tpu.ops.pallas.mode`).
    """
    interpret = mode.interpret("lstm_seq", interpret)
    B = x.shape[0]
    pad = (-B) % 8
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], 0)
    x_tbe = jnp.transpose(x, (1, 0, 2))
    hs = _lstm_tbe(wx, wh, b, x_tbe, interpret)
    hs = jnp.transpose(hs, (1, 0, 2))
    return hs[:B] if pad else hs


def pack_lstm_params(cell_params) -> tuple:
    """flax ``OptimizedLSTMCell`` param tree -> packed (Wx [E,4H], Wh [H,4H],
    b [4H]) in i,f,g,o gate order (the layout ``lstm_seq`` consumes)."""
    wx = jnp.concatenate([cell_params["i" + g]["kernel"] for g in GATES], axis=1)
    wh = jnp.concatenate([cell_params["h" + g]["kernel"] for g in GATES], axis=1)
    b = jnp.concatenate([cell_params["h" + g]["bias"] for g in GATES], axis=0)
    return wx, wh, b


def _orthogonal_gates(key, shape, dtype=jnp.float32):
    """Per-gate orthogonal init for the packed recurrent kernel [H, 4H]."""
    H = shape[0]
    init = jax.nn.initializers.orthogonal()
    keys = jax.random.split(key, 4)
    return jnp.concatenate([init(k, (H, H), dtype) for k in keys], axis=1)
