"""Pipelined training for the transformer family: dp x pp in one jitted step.

Stage layout for an N-layer :class:`~distkeras_tpu.models.transformer.TransformerLM`
on a ``(data, pipe)`` mesh with S pipeline stages:

* the N block param subtrees are stacked ``[S, N/S, ...]`` and sharded over
  ``pipe`` — each slice holds only its stage's layers (that is the point: HBM per
  chip scales as N/S);
* embedding / final-norm / head params stay replicated; embedding compute feeds
  stage 0, the head+loss run on the last stage, and the loss scalar is shared via
  a masked ``psum`` — so in backward, embed grads materialize only on stage 0 and
  head grads only on stage S-1, and one ``psum`` over ``pipe`` reassembles them
  with no double counting;
* gradients are additionally ``pmean``-ed over ``data`` (standard DP).
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distkeras_tpu.models.transformer import TransformerBlock, TransformerLM
from distkeras_tpu.ops.losses import get_loss
from distkeras_tpu.ops.precision import cast_floats
from distkeras_tpu.ops.optimizers import get_optimizer
from distkeras_tpu.parallel.pipeline import gpipe
from distkeras_tpu.runtime.mesh import DATA_AXIS, PIPE_AXIS, put_global


class PipeState(NamedTuple):
    params: Any  # (replicated_params, stage_params [S, nb, ...])
    opt_state: Any
    rng: jax.Array


def _layer_norm(p, x, eps=1e-6):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def split_transformer_params(params, num_stages: int):
    """(replicated, stage-stacked) split of TransformerLM params."""
    block_keys = sorted(
        (k for k in params if k.startswith("block_")),
        key=lambda s: int(s.split("_")[1]),
    )
    n = len(block_keys)
    if n % num_stages != 0:
        raise ValueError(f"{n} layers not divisible by {num_stages} stages")
    blocks = [params[k] for k in block_keys]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)
    stacked = jax.tree.map(
        lambda a: a.reshape((num_stages, n // num_stages) + a.shape[1:]), stacked
    )
    rep = {k: v for k, v in params.items() if not k.startswith("block_")}
    return rep, stacked


def merge_transformer_params(rep, stacked):
    """Inverse of :func:`split_transformer_params` (host-side, for export)."""
    leaves = jax.tree.leaves(stacked)
    S, nb = leaves[0].shape[0], leaves[0].shape[1]
    params = dict(rep)
    for s in range(S):
        for b in range(nb):
            params[f"block_{s * nb + b}"] = jax.tree.map(
                lambda a: a[s, b], stacked
            )
    return params


class PipelineEngine:
    """dp x pp training for TransformerLM-shaped models."""

    def __init__(
        self,
        model,
        optimizer,
        loss,
        mesh: Mesh,
        num_microbatches: int = 4,
        learning_rate: float = 0.01,
        seed: int = 0,
        compute_dtype=None,
        on_step=None,
    ):
        tl = model.module
        if not isinstance(tl, TransformerLM):
            raise TypeError("PipelineEngine requires a TransformerLM model")
        self.model = model
        self.mesh = mesh
        self.num_stages = mesh.shape[PIPE_AXIS]
        self.num_microbatches = num_microbatches
        self.tx = get_optimizer(optimizer, learning_rate)
        self.loss_fn = get_loss(loss)
        self.seed = seed
        self.block_module = TransformerBlock(
            tl.num_heads, tl.d_model, tl.d_ff, dropout_rate=tl.dropout_rate
        )
        self.tl = tl
        self.compute_dtype = compute_dtype
        # Optimizer-state specs: moments mirror the (rep, stage) param split
        # — stage moments sharded over ``pipe`` like the stage params, counts
        # replicated. A pytree-prefix spec cannot express this (the moments
        # are nested inside optax's chain tuple), and getting it wrong breaks
        # any stateful optimizer: a replicated spec hands every stage the
        # full moment stack while its update is stage-local, so the scan
        # carry types diverge (adam failed exactly this way).
        from distkeras_tpu.parallel.sharding import mirror_tree_specs

        # All abstract (eval_shape): no host copy / device stack is ever
        # materialized just to derive spec shapes.
        split = lambda p: split_transformer_params(p, self.num_stages)
        rep_a, stage_a = jax.eval_shape(split, model.params)
        param_specs = (jax.tree.map(lambda _: P(), rep_a),
                       jax.tree.map(lambda _: P(PIPE_AXIS), stage_a))
        self._opt_specs = mirror_tree_specs(
            jax.eval_shape(lambda p: self.tx.init(split(p)), model.params),
            (rep_a, stage_a), param_specs, P())
        #: optional ``on_step(step_idx, loss)`` — the engine's own observation
        #: point for direct ``step()`` use. The trainer path goes through
        #: WindowedStepEngine -> run_rounds, which carries ``on_round`` and
        #: the dispatch/retire telemetry; this hook covers callers driving
        #: the engine raw (the loss passed is the DEVICE value — fetching it
        #: fences the step, the caller's choice to pay).
        self.on_step = on_step
        self._step_count = 0
        self._step = self._build_step()

    # -- pure functions ----------------------------------------------------
    def _forward(self, rep, stage_params, tokens, rng):
        """Inside shard_map: embed -> gpipe(blocks) -> head. Loss-ready logits on
        the last stage (garbage elsewhere by construction)."""
        block_module = self.block_module
        M = self.num_microbatches
        B, L = tokens.shape
        x = rep["tok_embed"]["embedding"][tokens]
        x = x + rep["pos_embed"]["embedding"][jnp.arange(L)][None]
        x = x.astype(self.compute_dtype or jnp.float32)

        local_sp = jax.tree.map(lambda a: jnp.squeeze(a, 0), stage_params)

        def stage_fn(sp, h):
            def body(carry, p):
                return block_module.apply({"params": p}, carry, False), None

            h, _ = lax.scan(body, h, sp)
            return h

        micro = x.reshape((M, B // M, L, -1))
        y = gpipe(stage_fn, local_sp, micro, PIPE_AXIS)
        y = y.reshape((B, L, -1))
        y = _layer_norm(rep["ln_final"], y)
        return y @ rep["lm_head"]["kernel"] + rep["lm_head"]["bias"]

    def _build_step(self):
        loss_fn = self.loss_fn
        tx = self.tx
        S = self.num_stages

        def body(rep, stage, opt_state, rng, tokens, targets):
            idx = lax.axis_index(PIPE_AXIS)

            def loss_of(rep, stage):
                rep = cast_floats(rep, self.compute_dtype)
                stage = cast_floats(stage, self.compute_dtype)
                logits = self._forward(rep, stage, tokens, rng)
                per = loss_fn(logits.astype(jnp.float32), targets)
                # Only the last stage's logits are real. Mask LOCALLY and do NOT
                # psum here: grad-inside-shard_map effectively differentiates the
                # sum of per-rank outputs, so a psum inside the loss would scale
                # every gradient by the pipe axis size.
                return jnp.where(idx == S - 1, per, 0.0 * per)

            loss_local, (g_rep, g_stage) = jax.value_and_grad(loss_of, argnums=(0, 1))(
                rep, stage
            )
            loss = lax.psum(loss_local, PIPE_AXIS)  # reporting only
            # Reassemble replicated-param grads: embed grads live on stage 0,
            # head grads on stage S-1, zeros elsewhere -> psum is exact.
            g_rep = lax.psum(g_rep, PIPE_AXIS)
            g_rep = lax.pmean(g_rep, DATA_AXIS)
            g_stage = lax.pmean(g_stage, DATA_AXIS)
            loss = lax.pmean(loss, DATA_AXIS)

            updates, opt_state = tx.update((g_rep, g_stage), opt_state, (rep, stage))
            rep = jax.tree.map(jnp.add, rep, updates[0])
            stage = jax.tree.map(jnp.add, stage, updates[1])
            next_rng = jax.random.split(rng, 1)[0]
            return rep, stage, opt_state, next_rng, loss

        mapped = shard_map(
            body,
            mesh=self.mesh,
            in_specs=(P(), P(PIPE_AXIS), self._opt_specs, P(),
                      P(DATA_AXIS), P(DATA_AXIS)),
            out_specs=(P(), P(PIPE_AXIS), self._opt_specs, P(), P()),
            check_vma=False,
        )

        def step(state: PipeState, tokens, targets):
            rep, stage = state.params
            rep, stage, opt_state, rng, loss = mapped(
                rep, stage, state.opt_state, state.rng, tokens, targets
            )
            return PipeState((rep, stage), opt_state, rng), loss

        self._step_core = step  # unjitted: scannable by WindowedStepEngine
        return jax.jit(step, donate_argnums=(0,))

    # -- state -------------------------------------------------------------
    def init_state(self) -> PipeState:
        params = jax.tree.map(lambda a: np.array(a), self.model.params)
        rep, stage = split_transformer_params(params, self.num_stages)
        rep_sh = NamedSharding(self.mesh, P())
        stage_sh = NamedSharding(self.mesh, P(PIPE_AXIS))
        rep = put_global(rep, rep_sh)
        stage = put_global(stage, stage_sh)
        opt_sh = jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                              self._opt_specs,
                              is_leaf=lambda x: isinstance(x, P))
        opt_state = jax.jit(self.tx.init, out_shardings=opt_sh)((rep, stage))
        rng = put_global(jax.random.key(self.seed), rep_sh)
        return PipeState((rep, stage), opt_state, rng)

    def batch_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(DATA_AXIS))

    def step(self, state: PipeState, tokens, targets):
        from distkeras_tpu import telemetry

        # Host-side enqueue latency only (dispatch is async; no fence here).
        with telemetry.get().span("pipeline.dispatch"):
            state, loss = self._step(state, tokens, targets)
        if self.on_step is not None:
            self.on_step(self._step_count, loss)
        self._step_count += 1
        return state, loss

    def export_params(self, state: PipeState):
        rep, stage = jax.device_get(state.params)
        return merge_transformer_params(rep, stage)
