"""Pipeline parallelism: GPipe microbatch scheduling over a ``pipe`` mesh axis.

Beyond-reference surface (SURVEY.md §2: pipeline parallel absent in dist-keras).
Layers are split into S contiguous stages, one per mesh slice along ``pipe``;
M microbatches stream through, with activations hopping stage-to-stage via
``ppermute`` (adjacent ICI links). The schedule is the classic GPipe ramp:
``M + S - 1`` ticks, stage ``s`` working on microbatch ``t - s`` at tick ``t``;
bubble fraction ``(S-1)/(M+S-1)``.

Everything is differentiable (``ppermute``/``scan`` have transposes), so one
``jax.grad`` through :func:`gpipe` trains the whole pipeline.
"""

from __future__ import annotations

from typing import Callable

import jax.numpy as jnp
from jax import lax


def gpipe(stage_fn: Callable, stage_params, microbatches, axis_name: str):
    """Run ``microbatches`` through the stage pipeline.

    Args:
      stage_fn: ``(stage_params, x) -> y`` — this slice's chunk of the network.
        Must map activations to activations of the same shape.
      stage_params: this slice's stage parameters (inside shard_map: the local
        shard of a ``P(pipe)``-stacked pytree).
      microbatches: ``[M, ...]`` — the microbatch queue. Only stage 0's queue is
        consumed; other stages receive activations over the ring.
      axis_name: the ``pipe`` mesh axis.

    Returns:
      ``[M, ...]`` outputs, valid on the **last** stage (zeros elsewhere —
      callers typically follow with a masked ``psum`` broadcast).
    """
    S = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    M = microbatches.shape[0]
    T = M + S - 1
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]

    zero_mb = jnp.zeros_like(microbatches[0])
    out0 = jnp.zeros_like(microbatches)

    def tick(carry, t):
        held, outputs = carry
        # Stage 0 ingests microbatch t (while t < M); other stages keep what the
        # ring delivered last tick.
        feed = lax.dynamic_index_in_dim(
            microbatches, jnp.minimum(t, M - 1), keepdims=False
        )
        x = jnp.where(idx == 0, feed, held)
        y = stage_fn(stage_params, x)
        # Last stage commits microbatch t - (S-1) once the ramp has filled.
        slot = t - (S - 1)
        committed = lax.cond(
            slot >= 0,
            lambda o: lax.dynamic_update_index_in_dim(o, y, jnp.maximum(slot, 0), 0),
            lambda o: o,
            outputs,
        )
        outputs = jnp.where(idx == S - 1, committed, outputs)
        # Ship activations to the next stage (last stage's send wraps to 0 and
        # is overwritten by the stage-0 feed next tick).
        held = lax.ppermute(y, axis_name, fwd_perm)
        return (held, outputs), None

    (_, outputs), _ = lax.scan(tick, (zero_mb, out0), jnp.arange(T))
    return outputs


def last_stage_broadcast(y, axis_name: str):
    """Broadcast the last stage's pipeline output to every stage (masked psum)."""
    S = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    return lax.psum(jnp.where(idx == S - 1, y, jnp.zeros_like(y)), axis_name)
