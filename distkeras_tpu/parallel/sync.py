"""Synchronous data parallelism: per-step gradient ``pmean``.

This is the reference's ``SynchronousDistributedTrainer`` path (and the "synchronous
DOWNPOUR" of BASELINE config #5), built the canonical TPU way: one replicated set of
params, batch sharded over the ``data`` axis, gradients all-reduced every step. No
center-variable bookkeeping — replicas never diverge, so the state is just
(params, opt_state) and the collective is a single fused psum riding ICI.

``window`` here means *steps per jitted program* (the scan length): folding many steps
into one XLA program amortizes dispatch overhead exactly like the async engine's
communication window, but with zero semantic effect.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distkeras_tpu.data.batching import BatchPlan
from distkeras_tpu.ops.losses import get_loss
from distkeras_tpu.ops.optimizers import get_optimizer
from distkeras_tpu.runtime.mesh import DATA_AXIS, put_global
from distkeras_tpu.workers import make_local_loop


class SyncState(NamedTuple):
    params: Any
    opt_state: Any
    rng: jax.Array
    #: mutable model collections (BatchNorm stats; None for pure models),
    #: replicated — re-synced by pmean after every round.
    model_state: Any = None


class SyncEngine:
    def __init__(
        self,
        model,
        optimizer,
        loss,
        mesh: Mesh,
        learning_rate: float = 0.01,
        compute_dtype=None,
        seed: int = 0,
        grad_accum: int = 1,
        workers_per_chip: int = 1,
        device_transform=None,
        nan_guard: "bool | None" = None,
    ):
        from distkeras_tpu.resilience.guard import nan_guard_enabled

        #: on-device NaN/Inf round skip (see AsyncEngine.nan_guard): a
        #: non-finite window keeps the previous (params, opt, stats) —
        #: replicas stay in lockstep because the skip decision is made on
        #: the pmean'd (replicated) losses.
        self.nan_guard = (nan_guard_enabled() if nan_guard is None
                          else bool(nan_guard))
        self.model = model
        self.mesh = mesh
        #: m logical workers per chip (reference parity: num_workers is a
        #: Spark-executor count, not a chip count). The multiplex folds the m
        #: workers into the per-chip batch ([m*B] per step) — gradient-exact
        #: for deterministic stateless models (mean over m*B == mean of m
        #: B-means), but dropout streams and BatchNorm batch statistics see
        #: the merged batch, not m per-worker batches.
        self.workers_per_chip = int(workers_per_chip)
        if self.workers_per_chip < 1:
            raise ValueError(f"workers_per_chip must be >= 1, got {workers_per_chip}")
        if self.workers_per_chip > 1:
            import warnings

            warnings.warn(
                "SyncEngine with workers_per_chip > 1 folds the m logical "
                "workers into one merged m*B per-chip batch: gradient-exact "
                "for deterministic stateless models, but batch statistics "
                "(BatchNorm) and stochastic-layer streams (dropout) see the "
                "merged batch — a slightly different trajectory than the "
                "same num_workers spread across chips",
                stacklevel=2)
        self.num_workers = mesh.shape[DATA_AXIS] * self.workers_per_chip
        #: physical chips (num_workers is logical under multiplexing).
        self.num_chips = int(mesh.devices.size)
        self.seed = seed
        self.tx = get_optimizer(optimizer, learning_rate)
        self.loss_fn = get_loss(loss)
        self.compute_dtype = compute_dtype
        self.grad_accum = int(grad_accum)
        self.device_transform = device_transform
        self._multi_fns = {}
        self._round_fn = self._build_round_fn()

    def _build_round_fn(self):
        def sync_grads(grads, loss):
            # The one collective: mean gradient across chips, fused by XLA.
            return lax.pmean(grads, DATA_AXIS), lax.pmean(loss, DATA_AXIS)

        local_loop = make_local_loop(
            self.model.module, self.loss_fn, self.tx,
            compute_dtype=self.compute_dtype, grad_transform=sync_grads,
            state_collections=self.model.state_collections,
            grad_accum=self.grad_accum,
            input_transform=self.device_transform,
            normalize_uint8=getattr(self.model, "normalize_uint8", True),
        )

        m = self.workers_per_chip
        nan_guard = self.nan_guard

        def body(params, opt_state, rng, model_state, xs, ys):
            # xs: [m, K, B, ...] on this slice — same worker-major layout as
            # the async engine, so one BatchPlan serves both engines. The m
            # multiplexed workers fold into the batch axis: [K, m*B, ...]
            # (gradient mean over m*B == mean of m workers' B-means). m == 1
            # keeps the plain slice (identical program to pre-multiplex).
            def merge(a):
                if m == 1:
                    return a[0]
                moved = jnp.swapaxes(a, 0, 1)  # [K, m, B, ...]
                return moved.reshape((moved.shape[0], m * moved.shape[2])
                                     + moved.shape[3:])

            xs0, ys0 = merge(xs), merge(ys)
            # Per-replica dropout stream; the *carried* rng stays replicated (the
            # divergent key never leaves the local loop).
            step_rng = jax.random.fold_in(rng, jax.lax.axis_index(DATA_AXIS))
            with jax.named_scope("dk_local_steps"):
                new_params, new_opt, new_model_state, losses = local_loop(
                    params, opt_state, xs0, ys0, step_rng, model_state)
            # Running statistics re-sync: each replica saw its own batch slice;
            # the mean is the canonical cross-replica estimate (params need no
            # such sync — the per-step gradient pmean keeps them identical).
            with jax.named_scope("dk_state_sync"):
                new_model_state = lax.pmean(new_model_state, DATA_AXIS)
            if nan_guard:
                # Resilience NaN/Inf skip: a non-finite window would leave
                # every replica's params poisoned through the gradient pmean
                # — discard the round instead. ``losses`` are the pmean'd
                # (replicated) per-step losses, so all replicas agree.
                with jax.named_scope("dk_nan_guard"):
                    ok = jnp.all(jnp.isfinite(losses))
                    new_params, new_opt, new_model_state = lax.cond(
                        ok,
                        lambda: (new_params, new_opt, new_model_state),
                        lambda: (params, opt_state, model_state))
            next_rng = jax.random.split(rng, 1)[0]
            return new_params, new_opt, next_rng, new_model_state, losses

        mapped = shard_map(
            body,
            mesh=self.mesh,
            in_specs=(P(), P(), P(), P(), P(DATA_AXIS), P(DATA_AXIS)),
            out_specs=(P(), P(), P(), P(), P()),
            check_vma=False,
        )

        def round_fn(state: SyncState, xs, ys):
            params, opt_state, rng, model_state, losses = mapped(
                state.params, state.opt_state, state.rng, state.model_state, xs, ys
            )
            return SyncState(params, opt_state, rng, model_state), jnp.mean(losses)

        self._round_core = round_fn
        return jax.jit(round_fn, donate_argnums=(0,))

    def multi_round_fn(self, rounds: int):
        """``rounds`` sync steps in one dispatched program (see
        ``AsyncEngine.multi_round_fn`` — identical semantics, scanned state)."""
        from distkeras_tpu.parallel.engine import make_multi_round_fn

        return make_multi_round_fn(self, rounds)

    def _put_batch(self, xs, ys):
        shard = NamedSharding(self.mesh, P(DATA_AXIS))
        return put_global(xs, shard), put_global(ys, shard)

    def init_state(self) -> SyncState:
        rep = NamedSharding(self.mesh, P())
        # Deep-copy: round_fn donates its input state; never alias the user's Model.
        params = jax.tree.map(lambda a: np.array(a), self.model.params)
        model_state = jax.tree.map(lambda a: np.array(a), self.model.state)
        return SyncState(
            params=put_global(params, rep),
            opt_state=put_global(self.tx.init(params), rep),
            rng=put_global(jax.random.key(self.seed), rep),
            model_state=put_global(model_state, rep),
        )

    def run(
        self,
        plan: BatchPlan,
        state: Optional[SyncState] = None,
        start_round: int = 0,
        on_round: Optional[Callable] = None,
        rounds_per_program: "int | str" = 1,
    ):
        """Execute rounds ``start_round..num_rounds``; ``on_round(r, loss, state)``
        (see AsyncEngine.run for the donation caveat).
        ``rounds_per_program``: int or ``"auto"`` (engine.run_rounds)."""
        if plan.num_workers != self.num_workers:
            raise ValueError(
                f"plan built for {plan.num_workers} workers, mesh has {self.num_workers}"
            )
        if state is None:
            state = self.init_state()
        from distkeras_tpu.parallel.engine import run_rounds

        return run_rounds(self, plan, state, start_round, on_round,
                          rounds_per_program)

    def run_stream(self, items, state=None, on_item=None, start_index=0,
                   max_items=None):
        """Train on an open-ended batch source (``(xs, ys)`` host batches
        shaped ``[W, K, B, ...]``) — same contract as
        :meth:`AsyncEngine.run_stream`; epoch bookkeeping stays with the
        caller."""
        from distkeras_tpu.parallel.engine import run_stream

        return run_stream(self, items, state=state, on_item=on_item,
                          start_index=start_index, max_items=max_items)
