"""The async-discipline engine: K local steps per replica + one collective fold.

This is the TPU replacement for the reference's *entire* L2–L4 stack (SURVEY.md §1):
socket transport, parameter-server thread, and executor worker loop become one
``shard_map``-wrapped, jit-compiled "fold round"::

    round(center, locals, opt_state, batch[W, K, B, ...]):
        per replica: K minibatch steps via lax.scan     (workers.py)
        fold: psum of per-replica deltas into center    (disciplines.py)

State layout on the mesh (axis ``data`` = one reference "worker" per slice):

* ``center``    — replicated (the parameter server's center variable)
* ``locals_``   — stacked ``[W, ...]``, sharded on the worker axis
* ``opt_state`` — stacked ``[W, ...]``, sharded likewise (each reference worker
  compiled its *own* optimizer — per-replica optimizer state is parity, not a bug)

The per-round batch arrives sharded the same way, so no sample ever leaves its chip;
the only cross-chip traffic is the O(model) psum per round — exactly the traffic the
reference pushed through pickle/TCP per commit, now on ICI.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distkeras_tpu.data.batching import BatchPlan
from distkeras_tpu.models.base import ROUND_COUNTERS
from distkeras_tpu.ops.losses import get_loss
from distkeras_tpu.ops.optimizers import get_optimizer
from distkeras_tpu.parallel.disciplines import Discipline
from distkeras_tpu.resilience import faults as _faults
from distkeras_tpu.resilience.guard import nan_guard_enabled
from distkeras_tpu.runtime.mesh import DATA_AXIS, put_global
from distkeras_tpu.workers import make_local_loop


class EngineState(NamedTuple):
    center: Any
    locals_: Any
    opt_state: Any
    fold_state: Any
    rng: jax.Array
    #: mutable model collections (BatchNorm stats; None for pure models),
    #: stacked ``[W, ...]`` and sharded on the worker axis like ``locals_``.
    #: Communicating disciplines pmean them at each fold (running statistics
    #: become a deterministic average, not the reference's raced socket
    #: overwrite); the no-comm ensemble fold keeps them per-member.
    model_state: Any = None


def _stack_for_workers(tree, num_workers: int):
    return jax.tree.map(lambda a: jnp.broadcast_to(a, (num_workers,) + a.shape), tree)


def _stack_on_host(tree, num_workers: int):
    """``[W, ...]`` zero-copy host views of ``tree``'s leaves, to be put under
    a worker-sharded layout: each chip then receives only its own workers'
    slices. Stacking on device first would materialize all W copies of the
    params and the optimizer state on the default device before the put
    spreads them — measured on four v5e chips with the flagship at W=4 (PR 21):
    13.1 GB peak on chip 0 against 3.4 GB on each of the others, and past a
    16 GB chip at W=8."""
    return jax.tree.map(
        lambda a: np.broadcast_to(a, (num_workers,) + np.shape(a)),
        jax.device_get(tree))  # one batched fetch; host leaves pass through


class AsyncEngine:
    """Runs a :class:`Discipline` over a 1-D ``data`` mesh.

    ``workers_per_chip`` (m) multiplexes m logical workers onto each chip —
    the reference ran ``num_workers=8`` Spark executors on a laptop, so the
    worker count must not be capped by physical chips. The worker axis stays
    worker-major ([W] = chips x m); per-chip the m replicas run under one
    vmap, their commits sum locally, and the cross-chip fold is the same
    single psum — for m=1 this is exactly the one-worker-per-chip program.
    """

    def __init__(
        self,
        model,
        optimizer,
        loss,
        discipline: Discipline,
        mesh: Mesh,
        window: int,
        learning_rate: float = 0.01,
        compute_dtype=None,
        seed: int = 0,
        per_worker_init: bool = False,
        grad_accum: int = 1,
        workers_per_chip: int = 1,
        device_transform=None,
        nan_guard: Optional[bool] = None,
        divergence_reset: Optional[float] = None,
    ):
        self.model = model
        self.mesh = mesh
        from distkeras_tpu.runtime.mesh import SEQ_AXIS

        if (getattr(model.module, "seq_axis", None) is not None
                and SEQ_AXIS not in mesh.axis_names):
            raise ValueError(
                f"model was built with seq_axis="
                f"{model.module.seq_axis!r} but this engine's mesh has no "
                f"'{SEQ_AXIS}' axis — the module's axis_index would be "
                "unbound. Pass parallel={'model': tp, 'seq': s} (AsyncTP"
                "Engine) or rebuild the model with seq_axis=None.")
        self.discipline = discipline
        self.window = window
        self.workers_per_chip = int(workers_per_chip)
        if self.workers_per_chip < 1:
            raise ValueError(f"workers_per_chip must be >= 1, got {workers_per_chip}")
        self.num_workers = mesh.shape[DATA_AXIS] * self.workers_per_chip
        #: physical chips — num_workers is LOGICAL under multiplexing, so
        #: samples/s/chip metrics must divide by this, not num_workers.
        self.num_chips = int(mesh.devices.size)
        self.seed = seed
        self.per_worker_init = per_worker_init
        #: on-device NaN/Inf round skip (resilience layer): when any worker's
        #: round loss goes non-finite, the round program keeps the previous
        #: state — one isfinite reduce + a where-select per leaf, no host
        #: round-trip. Default from DKTPU_NAN_GUARD (on unless "0").
        self.nan_guard = (nan_guard_enabled() if nan_guard is None
                          else bool(nan_guard))
        #: opt-in divergent-worker reset threshold (resilience.RoundGuard):
        #: |worker loss - mean| beyond it re-adopts the center. None = off.
        self.divergence_reset = divergence_reset
        self._reset_fn = None
        self.tx = get_optimizer(optimizer, learning_rate)
        self.loss_fn = get_loss(loss)
        self._local_loop = make_local_loop(
            model.module, self.loss_fn, self.tx, compute_dtype=compute_dtype,
            state_collections=model.state_collections, grad_accum=grad_accum,
            grad_transform=self._grad_transform(),
            input_transform=device_transform,
            normalize_uint8=getattr(model, "normalize_uint8", True),
        )
        self._multi_fns = {}
        self._round_fn = self._build_round_fn()

    # ------------------------------------------------------------------
    # Round-program hooks. The flat engine's shard_map binds every mesh axis
    # manually (its mesh is 1-D ``data``); AsyncTPEngine overrides these to
    # keep ``model`` a GSPMD (auto) axis — which is what lets non-auto-
    # partitionable code (the Mosaic flash kernel) self-manualize inside the
    # body — and to add a manual ``seq`` axis for sequence parallelism.
    def _manual_axes(self):
        """Axes shard_map binds manually; None = all mesh axes (flat engine)."""
        return None

    def _batch_spec(self) -> P:
        """shard_map spec for the [W, K, B, ...] round batches."""
        return P(DATA_AXIS)

    def _grad_transform(self):
        """Per-step (grads, loss) hook for the local loop (seq-axis pmean)."""
        return None

    def _fold_rng(self, rng, wid):
        """Per-worker rng derivation inside the round body."""
        return jax.random.fold_in(rng, wid)

    def _pin_state(self, state: "EngineState") -> "EngineState":
        """Pin output shardings (no-op for the all-manual flat engine, whose
        out_specs fully determine layout)."""
        return state

    # ------------------------------------------------------------------
    def _build_round_fn(self):
        disc = self.discipline
        window = self.window
        num_workers = self.num_workers
        m = self.workers_per_chip
        local_loop = self._local_loop
        fold_rng = self._fold_rng
        manual = self._manual_axes()
        from distkeras_tpu.runtime.mesh import SEQ_AXIS

        # A manual seq axis shards each worker's batch positions: mutable
        # state (running stats) updates from only L/S positions per shard,
        # so the cross-worker state fold must also mean over seq — the
        # out_spec claims seq-replication, and check_vma=False would let a
        # silent divergence through otherwise.
        seq_manual = bool(manual) and SEQ_AXIS in manual

        def _one_worker(center, locals_, opt_state, fold_state, rng,
                        model_state, xs, ys):
            """m == 1 fast path: the original one-worker-per-chip program.
            The vmap(1) generalization compiles to a measurably slower
            executable (A/B on-chip: -19% on the MNIST-CNN config), so the
            common case keeps the direct squeeze/expand body."""
            local = jax.tree.map(lambda a: jnp.squeeze(a, 0), locals_)
            opt = jax.tree.map(lambda a: jnp.squeeze(a, 0), opt_state)
            mstate = jax.tree.map(lambda a: jnp.squeeze(a, 0), model_state)
            xs0, ys0 = xs[0], ys[0]  # [K, B, ...]
            wid = jax.lax.axis_index(DATA_AXIS)
            start = center if disc.pulls_center else local
            worker_rng = fold_rng(rng, wid)
            with jax.named_scope("dk_local_steps"):
                new_local, new_opt, mstate, losses = local_loop(
                    start, opt, xs0, ys0, worker_rng, mstate)
            if disc.syncs_state:
                with jax.named_scope("dk_state_sync"):
                    mstate = lax.pmean(mstate, DATA_AXIS)
                    if seq_manual:
                        mstate = lax.pmean(mstate, SEQ_AXIS)
            # disc.fold = commit + psum + pulls_center + advance: the
            # single-worker reference semantics live in ONE place
            # (disciplines.py); only the m>1 path inlines the vmapped twin.
            with jax.named_scope("dk_fold"):
                new_center, new_local, new_fold_state = disc.fold(
                    center, new_local, fold_state, axis_name=DATA_AXIS,
                    window=window, num_workers=num_workers)
            with jax.named_scope("dk_loss_gather"):
                loss = lax.all_gather(jnp.mean(losses), DATA_AXIS)
            return (new_center,
                    jax.tree.map(lambda a: a[None], new_local),
                    jax.tree.map(lambda a: a[None], new_opt),
                    jax.tree.map(lambda a: a[None], mstate),
                    new_fold_state,
                    loss)

        def _multiplexed(center, locals_, opt_state, fold_state, rng,
                         model_state, xs, ys):
            """m > 1: vmap the m logical workers this chip carries, sum their
            commits locally, and fold with the same single psum."""
            wids = jax.lax.axis_index(DATA_AXIS) * m + jnp.arange(m)
            start = (jax.tree.map(
                lambda a: jnp.broadcast_to(a, (m,) + a.shape), center)
                if disc.pulls_center else locals_)
            worker_rngs = jax.vmap(lambda w: jax.random.fold_in(rng, w))(wids)
            with jax.named_scope("dk_local_steps"):
                new_local, new_opt, mstate, losses = jax.vmap(local_loop)(
                    start, opt_state, xs, ys, worker_rngs, model_state)
            if disc.syncs_state:
                # Stats fold: cross-worker mean (running statistics average;
                # they are not gradient-like deltas). Ensemble members keep
                # their own stats — each must match its own params.
                with jax.named_scope("dk_state_sync"):
                    mstate = jax.tree.map(
                        lambda a: jnp.broadcast_to(
                            a.mean(axis=0, keepdims=True), a.shape), mstate)
                    mstate = lax.pmean(mstate, DATA_AXIS)
            if disc.communicates:
                with jax.named_scope("dk_fold"):
                    commits, new_local = jax.vmap(
                        lambda loc, w: disc.commit(
                            center, loc, fold_state, worker_id=w,
                            window=window, num_workers=num_workers))(
                                new_local, wids)
                    total = lax.psum(
                        jax.tree.map(lambda a: a.sum(axis=0), commits),
                        DATA_AXIS)
                    new_center = jax.tree.map(jnp.add, center, total)
                    if disc.pulls_center:
                        new_local = jax.tree.map(
                            lambda a: jnp.broadcast_to(a, (m,) + a.shape),
                            new_center)
            else:
                new_center = center
            # all_gather gives [chips, m]; worker-major reshape -> [W].
            with jax.named_scope("dk_loss_gather"):
                loss = lax.all_gather(
                    jnp.mean(losses, axis=tuple(range(1, losses.ndim))),
                    DATA_AXIS).reshape(-1)
            return (new_center, new_local, new_opt, mstate,
                    disc.advance(fold_state), loss)

        nan_guard = self.nan_guard

        def body(center, locals_, opt_state, fold_state, rng, model_state, xs, ys):
            # Inside shard_map: this slice carries m logical workers.
            step = _one_worker if m == 1 else _multiplexed
            new_center, new_local, new_opt, new_model_state, new_fold_state, loss = step(
                center, locals_, opt_state, fold_state, rng, model_state,
                xs, ys)
            if nan_guard:
                # Resilience NaN/Inf skip: ONE worker's non-finite commit
                # contaminates the psum'd center for every replica, so the
                # whole round is discarded when any worker's loss went
                # non-finite — old state (params, opt, stats, fold counter)
                # carries forward; the reported loss keeps the NaN so host
                # accounting (resilience.nonfinite_rounds) still sees it.
                # ``loss`` is the replicated [W] all-gather, so every shard
                # takes the same branch. Cost when healthy: an isfinite
                # reduce + one cond select (measured cheaper than per-leaf
                # where) — below run-to-run noise next to the K-step loop.
                with jax.named_scope("dk_nan_guard"):
                    ok = jnp.all(jnp.isfinite(loss))
                    (new_center, new_local, new_opt, new_model_state,
                     new_fold_state) = lax.cond(
                        ok,
                        lambda: (new_center, new_local, new_opt,
                                 new_model_state, new_fold_state),
                        lambda: (center, locals_, opt_state, model_state,
                                 fold_state))
            model_state = new_model_state
            # Per-worker window-mean losses, all-gathered so the [W] history
            # vector is REPLICATED (fully addressable on every process of a
            # multi-host mesh — a data-sharded loss can't be fetched on the
            # driver). These are the per-worker training histories the
            # reference optionally collected (SURVEY.md §5 metrics row).
            next_rng = jax.random.split(rng, 1)[0]
            return (
                new_center,
                new_local,
                new_opt,
                new_fold_state,
                next_rng,
                model_state,
                loss,
            )  # loss: replicated [W]

        batch_spec = self._batch_spec()
        sm_kwargs = {} if manual is None else {"axis_names": frozenset(manual)}
        mapped = shard_map(
            body,
            mesh=self.mesh,
            in_specs=(P(), P(DATA_AXIS), P(DATA_AXIS), P(), P(), P(DATA_AXIS),
                      batch_spec, batch_spec),
            out_specs=(P(), P(DATA_AXIS), P(DATA_AXIS), P(), P(), P(DATA_AXIS),
                       P()),
            check_vma=False,
            **sm_kwargs,
        )

        def round_fn(state: EngineState, xs, ys):
            center, locals_, opt_state, fold_state, rng, model_state, loss = mapped(
                state.center, state.locals_, state.opt_state, state.fold_state,
                state.rng, state.model_state, xs, ys,
            )
            return self._pin_state(
                EngineState(center, locals_, opt_state, fold_state, rng,
                            model_state)), loss

        self._round_core = round_fn
        return jax.jit(round_fn, donate_argnums=(0,))

    def multi_round_fn(self, rounds: int):
        """A jitted program executing ``rounds`` consecutive fold rounds.

        Semantically identical to calling the per-round program ``rounds``
        times — the scan carries the exact same EngineState — but one host
        dispatch covers the whole block. Where host dispatch latency rivals
        a round's device time (small models) this is the difference between
        host-bound and device-bound throughput.
        Batches are ``[rounds, W, K, B, ...]``; returns losses ``[rounds, W]``.
        """
        return make_multi_round_fn(self, rounds)

    # ------------------------------------------------------------------
    # Sharding hooks: the center is replicated and per-worker state shards
    # on the worker axis. AsyncTPEngine overrides these (the ONLY layout
    # difference) to add tensor-parallel param dims, so init_state and
    # adopt_state are shared verbatim.
    def _center_shardings(self):
        return NamedSharding(self.mesh, P())

    def _stacked_shardings(self):
        return NamedSharding(self.mesh, P(DATA_AXIS))

    def _opt_shardings(self, opt_state, locals_):
        return self._stacked_shardings()

    def init_state(self) -> EngineState:
        W = self.num_workers
        # Deep-copy: round_fn donates its input state, and device_put may alias the
        # model's own buffers — donation must never delete the user's Model.
        center = jax.tree.map(lambda a: np.array(a), self.model.params)
        if self.per_worker_init:
            # Ensemble/averaging semantics: each replica starts from its OWN init
            # draw (reference: per-executor deserialization + uniform_weights),
            # not a broadcast of the driver's — init diversity is the point.
            per = [jax.device_get(
                self.model.reinit_params(self.seed * 1009 + 1 + i))
                for i in range(W)]
            locals_ = jax.tree.map(lambda *xs: np.stack(xs), *per)
        else:
            locals_ = _stack_on_host(center, W)
        opt_state = _stack_on_host(self.tx.init(center), W)
        fold_state = self.discipline.init_state(center)
        rng = jax.random.key(self.seed)

        rep = NamedSharding(self.mesh, P())
        wshard = NamedSharding(self.mesh, P(DATA_AXIS))
        model_state = _stack_on_host(self.model.state, W)
        return EngineState(
            center=put_global(center, self._center_shardings()),
            locals_=put_global(locals_, self._stacked_shardings()),
            opt_state=put_global(opt_state,
                                 self._opt_shardings(opt_state, locals_)),
            fold_state=put_global(fold_state, rep),
            rng=put_global(rng, rep),
            model_state=put_global(model_state, wshard),
        )

    def host_state(self, num_workers: int) -> EngineState:
        """An abstract EngineState template (ShapeDtypeStructs; real key for
        rng) with ``num_workers``-stacked per-worker arrays — the restore
        target for a checkpoint written at a different topology. Only shapes
        are allocated host-side; the restore itself still materializes the
        full saved tree (Orbax restores whole structures)."""
        W = num_workers

        def sds(a, lead=()):
            return jax.ShapeDtypeStruct(
                tuple(lead) + tuple(np.shape(a)), np.asarray(a).dtype)

        center = jax.tree.map(sds, self.model.params)
        locals_ = jax.tree.map(lambda a: sds(a, (W,)), self.model.params)
        zero_params = jax.tree.map(
            lambda a: jnp.zeros(a.shape, a.dtype), center)
        opt_state = jax.tree.map(
            lambda a: sds(a, (W,)), self.tx.init(zero_params))
        model_state = jax.tree.map(
            lambda a: sds(a, (W,)), self.model.state)
        return EngineState(
            center=center,
            locals_=locals_,
            opt_state=opt_state,
            fold_state=self.discipline.init_state(center),
            rng=jax.random.key(self.seed),
            model_state=model_state,
        )

    def adopt_state(self, host: EngineState) -> EngineState:
        """Re-topologize a restored host state onto THIS mesh (elastic
        resume after a pod resize). Reference semantics: a (re)joining worker
        pulls the center variable — so every replica restarts from the
        restored center with a fresh optimizer; running statistics are the
        cross-worker mean of the saved ones. Center, fold state, and rng
        carry over exactly."""
        W = self.num_workers
        rep = NamedSharding(self.mesh, P())
        wshard = NamedSharding(self.mesh, P(DATA_AXIS))
        center = jax.tree.map(np.asarray, host.center)
        model_state = jax.tree.map(
            lambda a: np.mean(np.asarray(a), axis=0), host.model_state)
        locals_ = _stack_on_host(center, W)
        opt_state = _stack_on_host(self.tx.init(center), W)
        return EngineState(
            center=put_global(center, self._center_shardings()),
            locals_=put_global(locals_, self._stacked_shardings()),
            opt_state=put_global(opt_state,
                                 self._opt_shardings(opt_state, locals_)),
            fold_state=put_global(host.fold_state, rep),
            rng=put_global(host.rng, rep),
            model_state=put_global(_stack_on_host(model_state, W), wshard),
        )

    def reset_workers(self, state: EngineState, worker_mask) -> EngineState:
        """Re-join the masked workers from the center (resilience layer: the
        divergent-worker reset). Reference semantics are the rejoining-worker
        PS pull: masked replicas take the center's params and a fresh
        optimizer; unmasked workers, the center, fold state, and rng are
        untouched. ``worker_mask`` is a host ``[W]`` bool array; the select
        runs as one jitted program (no donation — the caller's state stays
        valid until the new one is returned)."""
        mask = np.asarray(worker_mask, dtype=bool)
        if mask.shape != (self.num_workers,):
            raise ValueError(
                f"worker_mask must be [{self.num_workers}], got {mask.shape}")
        if self._reset_fn is None:
            W = self.num_workers

            def _select(fresh, old, m):
                def sel(f, o):
                    mm = m.reshape((W,) + (1,) * (f.ndim - 1))
                    return jnp.where(mm, f, o)

                return jax.tree.map(sel, fresh, old)

            def reset(st: EngineState, m):
                fresh_locals = _stack_for_workers(st.center, W)
                fresh_opt = _stack_for_workers(self.tx.init(st.center), W)
                return st._replace(
                    locals_=_select(fresh_locals, st.locals_, m),
                    opt_state=_select(fresh_opt, st.opt_state, m),
                )

            self._reset_fn = jax.jit(reset)
        return self._pin_state(self._reset_fn(state, mask))

    def _put_batch(self, xs: np.ndarray, ys: np.ndarray):
        shard = NamedSharding(self.mesh, self._batch_spec())
        return put_global(xs, shard), put_global(ys, shard)

    def run(
        self,
        plan: BatchPlan,
        state: Optional[EngineState] = None,
        start_round: int = 0,
        on_round: Optional[Callable] = None,
        rounds_per_program: "int | str" = 1,
    ):
        """Execute fold rounds ``start_round..num_rounds`` (resume-aware).

        Returns (state, losses) with ``losses`` shaped ``[rounds, W]`` — one
        loss curve per worker (reference parity: per-worker Keras history).
        ``on_round(r, loss, state)`` fires after each round — note ``state``
        buffers are donated into the *next* round, so callbacks that persist
        state must finish reading it before returning (the Checkpointer saves
        with ``wait=True`` for exactly this reason).
        """
        if plan.num_workers != self.num_workers:
            raise ValueError(
                f"plan built for {plan.num_workers} workers, mesh has {self.num_workers}"
            )
        if state is None:
            state = self.init_state()
        return run_rounds(self, plan, state, start_round, on_round,
                          rounds_per_program)

    def run_stream(self, items, state=None, on_item=None, start_index=0,
                   max_items=None):
        """Train on an open-ended batch source (``(xs, ys)`` host batches
        shaped ``[W, K, B, ...]`` like one BatchPlan round) — no epoch
        schedule, no round count; see :func:`run_stream`."""
        return run_stream(self, items, state=state, on_item=on_item,
                          start_index=start_index, max_items=max_items)


def local_worker_ids(mesh, workers_per_chip: int = 1) -> list[int]:
    """Global LOGICAL worker ids whose chips THIS process hosts (1-D data
    mesh). With multiplexing, chip c carries workers [c*m, (c+1)*m).

    The sharded data plane's unit of locality: a process stages rows for
    exactly these workers (``stage_round``), so per-host disk shards follow
    the device→process mapping with no extra bookkeeping."""
    pi = jax.process_index()
    m = workers_per_chip
    return [c * m + j
            for c, d in enumerate(mesh.devices.flat)
            if d.process_index == pi
            for j in range(m)]


def put_worker_local(local, mesh, num_workers: int, local_workers: list[int],
                     axis: int, spec):
    """Assemble a global batch array from rows this process holds.

    Replaces ``put_global``'s "every process holds the identical full host
    value" contract for batches: ``local`` carries only ``local_workers``'s
    slices along ``axis``; the callback answers each addressable device's
    shard request by translating its global worker range to local positions.
    Never sees (and so never requires) another host's rows."""
    global_shape = local.shape[:axis] + (num_workers,) + local.shape[axis + 1:]
    sharding = NamedSharding(mesh, spec)
    if jax.process_count() == 1 and len(local_workers) == num_workers:
        return jax.device_put(local, sharding)
    pos = {w: i for i, w in enumerate(local_workers)}
    def cb(idx):
        sl = idx[axis]
        start = 0 if sl.start is None else sl.start
        stop = global_shape[axis] if sl.stop is None else sl.stop
        li = [pos[w] for w in range(start, stop)]
        if li != list(range(li[0], li[0] + len(li))):
            raise ValueError(
                f"non-contiguous local worker placement {li} unsupported")
        key = list(idx)
        key[axis] = slice(li[0], li[0] + len(li))
        return local[tuple(key)]
    return jax.make_array_from_callback(tuple(global_shape), sharding, cb)


def _poison_rows(x, kind: str, idx: int):
    """Poison worker slice ``idx`` (leading axis) of a staged device array:
    multiply by NaN/Inf so the values — and everything backprop touches —
    go non-finite, without re-staging. Non-float batches (token ids) cannot
    carry a NaN; that misfire warns instead of silently consuming the
    one-shot fault."""
    if not jnp.issubdtype(x.dtype, jnp.floating):
        import warnings

        warnings.warn(
            f"{kind}@ batch fault scheduled on a non-float batch "
            f"(dtype {x.dtype}): cannot poison token ids — the fault is "
            "consumed with no effect", stacklevel=2)
        return x
    bad = x.dtype.type(float("nan") if kind == "nan" else float("inf"))
    return x.at[idx].mul(bad)


def _maybe_poison_round(r: int, xs):
    """Apply any scheduled nan/inf batch fault for round ``r`` (one-shot)."""
    fp = _faults.active_plan()
    if fp is None:
        return xs
    kind = fp.batch_fault(r)
    if kind is None:
        return xs
    return _poison_rows(xs, kind, fp.poison_worker(r, int(xs.shape[0])))


def _maybe_poison_block(rs, xs):
    """Block twin of :func:`_maybe_poison_round` over ``[R, W, ...]``."""
    fp = _faults.active_plan()
    if fp is None:
        return xs
    for j, r in enumerate(rs):
        kind = fp.batch_fault(r)
        if kind is None:
            continue
        if not jnp.issubdtype(xs.dtype, jnp.floating):
            _poison_rows(xs, kind, 0)  # shares the misfire warning
            continue
        w = fp.poison_worker(r, int(xs.shape[1]))
        bad = xs.dtype.type(float("nan") if kind == "nan" else float("inf"))
        xs = xs.at[j, w].mul(bad)
    return xs


def stage_round(engine, plan, r: int):
    """Gather + device-stage one round's batch, honouring plan locality.

    In-RAM plans go through the engine's full-batch path; sharded plans
    (``is_local``) on a multi-process mesh gather only this process's
    workers' rows from disk and assemble the global array from them.
    Single-process, the full ``round`` gather IS the local gather (every
    shard is addressable), so the plain path serves both. Any scheduled
    ``nan@r``/``inf@r`` fault poisons the staged features here — the single
    choke point every engine's staging passes through."""
    xs, ys = _stage_round_raw(engine, plan, r)
    return _maybe_poison_round(r, xs), ys


def _stage_round_raw(engine, plan, r: int):
    if getattr(plan, "is_local", False) and jax.process_count() > 1:
        hook = getattr(engine, "_stage_local_round", None)
        if hook is not None:  # step engines: locality by dp rank, own specs
            return hook(plan, r)
        lw = local_worker_ids(engine.mesh,
                              getattr(engine, "workers_per_chip", 1))
        xs, ys = plan.round_local(r, lw)
        put = lambda a: put_worker_local(
            a, engine.mesh, plan.num_workers, lw, 0, P(DATA_AXIS))
        return put(xs), put(ys)
    return engine._put_batch(*plan.round(r))


def stage_block(engine, plan, rs) -> tuple:
    """Stage a ``[R, W, K, B, ...]`` block of rounds (worker axis at dim 1)."""
    xs, ys = _stage_block_raw(engine, plan, rs)
    return _maybe_poison_block(rs, xs), ys


def _stage_block_raw(engine, plan, rs) -> tuple:
    # Engines with a batch-spec hook (seq-sharded AsyncTP) stage the block in
    # the round body's layout — otherwise XLA reshards the full block inside
    # every dispatched program.
    batch_spec = getattr(engine, "_batch_spec", None)
    spec = P(None, *batch_spec()) if batch_spec else P(None, DATA_AXIS)
    if (getattr(plan, "is_local", False) and jax.process_count() > 1
            and hasattr(engine, "_stage_local_block")):
        # Step engines: locality by dp rank, engine-owned specs.
        return engine._stage_local_block(plan, rs)
    if hasattr(engine, "_put_block"):
        # Step-engine adapters shard the batch axis, not a worker axis —
        # the engine owns its block spec (see parallel/runner.py).
        batches = [plan.round(r) for r in rs]
        return engine._put_block(np.stack([b[0] for b in batches]),
                                 np.stack([b[1] for b in batches]))
    if getattr(plan, "is_local", False) and jax.process_count() > 1:
        lw = local_worker_ids(engine.mesh,
                              getattr(engine, "workers_per_chip", 1))
        batches = [plan.round_local(r, lw) for r in rs]
        xs = np.stack([b[0] for b in batches])
        ys = np.stack([b[1] for b in batches])
        put = lambda a: put_worker_local(
            a, engine.mesh, plan.num_workers, lw, 1, spec)
        return put(xs), put(ys)
    batches = [plan.round(r) for r in rs]
    xs = np.stack([b[0] for b in batches])
    ys = np.stack([b[1] for b in batches])
    shard = NamedSharding(engine.mesh, spec)
    return put_global(xs, shard), put_global(ys, shard)


def run_rounds(engine, plan, state, start_round, on_round, rounds_per_program):
    """Dispatch to the per-round / blocked / auto-sized run loop (shared by the
    sync and async engines). ``rounds_per_program`` may be an int (fixed R) or
    ``"auto"`` — probe the per-round wall time and pick R to fill
    ``_AUTO_TARGET_S`` (~64 ms) of device work per dispatched program
    (semantics-preserving either way; see multi_round_fn)."""
    from distkeras_tpu import telemetry
    from distkeras_tpu.resilience.guard import note_losses

    # The run anchor span: every dispatch/retire/input_stall metric nests
    # logically under this wall-clock total (the report's share column).
    with telemetry.get().span("engine_run"):
        if rounds_per_program == "auto":
            state, losses = run_auto(engine, plan, state, start_round,
                                     on_round)
        elif int(rounds_per_program) > 1:
            state, losses = run_blocked(engine, plan, state, start_round,
                                        on_round, int(rounds_per_program))
        else:
            state, losses = run_per_round(engine, plan, state, start_round,
                                          on_round)
    # Post-hoc resilience accounting on the already-fetched history — the
    # rounds the on-device NaN guard skipped show up here as non-finite
    # loss rows (resilience.nonfinite_rounds), with no extra fences.
    note_losses(losses)
    return state, losses


def _observe_feed_wait(tele, feeder, r) -> None:
    """The run loop just popped round ``r``: record how long it sat blocked
    on the data plane, live, so that a window over the registry
    (``mark``/``delta``) and the timeline both see the stall with the round
    it delayed. ``input_stall`` is the compute-vs-data split every bench
    round needs."""
    wait = feeder.waits[-1]
    tele.histogram("input_stall").observe(wait)
    tele.counter("input_stall_seconds").add(wait)
    tele.observe_span("feed_wait", wait, id=r)


def _record_feed_waits(engine, feeder) -> None:
    """Keep the feeder's consumer-side wait times on the engine."""
    engine.feed_waits = list(feeder.waits)
    # The running sum, NOT sum(waits): the per-round deque is bounded
    # (prefetch.WAITS_KEEP) and an open-ended stream evicts old entries —
    # the total must keep counting them.
    engine.feed_wait_seconds = float(feeder.wait_seconds)


class _RoundCounters:
    """The model's ``ROUND_COUNTERS`` (``models/base.py``), out of the round
    program beside the loss: after each dispatch a copy of the collection is
    queued on the device (the next dispatch donates the state it lives in),
    and the copy of the round before, which has finished or is about to, is
    fetched and handed to the module's ``publish_round_counters``. The host
    therefore stays one round ahead of the device, never further, and the
    device's queue is never drained for it."""

    def __init__(self, engine):
        # Engines of other kinds (`parallel/runner.py`) share this run loop
        # and hold no `Model`: they have no counters to publish.
        model = getattr(engine, "model", None)
        self.publish = getattr(model.module, "publish_round_counters", None) \
            if ROUND_COUNTERS in (getattr(model, "state", None) or {}) else None
        self._copy = jax.jit(lambda tree: jax.tree.map(jnp.copy, tree))
        self._pending = None

    def after_dispatch(self, r: int, new_state) -> None:
        if self.publish is None:
            return
        queued = (r, self._copy(new_state.model_state[ROUND_COUNTERS]))
        self.flush()
        self._pending = queued

    def flush(self) -> None:
        if self._pending is not None:
            r, tree = self._pending
            self._pending = None
            # [W, ...] a leaf: the workers hold the same experts, and what
            # one of them was routed is the mean.
            self.publish(r, jax.tree.map(
                lambda a: np.asarray(a).mean(axis=0), tree))


def run_per_round(engine, plan, state, start_round, on_round):
    """One XLA dispatch per fold round, with background batch staging."""
    from distkeras_tpu import telemetry
    from distkeras_tpu.data.prefetch import RoundFeeder
    from distkeras_tpu.resilience.guard import RoundGuard

    tele = telemetry.get()
    guard = RoundGuard(engine)
    counters = _RoundCounters(engine)
    losses = []
    feeder = RoundFeeder(plan.num_rounds,
                         lambda r: stage_round(engine, plan, r),
                         start_round=start_round)
    try:
        for r, (xs, ys) in feeder:
            _observe_feed_wait(tele, feeder, r)
            guard.pre_round(r)  # crash/kill fault injection, if scheduled
            # Dispatch span: host-side enqueue only (jax dispatch is async);
            # the first round's entry absorbs compile time.
            with tele.span("dispatch[per-round]", id=r):
                new_state, loss = engine._round_fn(state, xs, ys)
            # Keep the device value: fetching here would fence every
            # dispatch; convert once at the end.
            losses.append(loss)
            counters.after_dispatch(r, new_state)
            if on_round is not None:
                with tele.span("on_round", id=r):
                    on_round(r, loss, new_state)
            # Divergent-worker reset (no-op — and no fence — unless enabled).
            state = guard.post_round(r, loss, new_state)
        counters.flush()
    except BaseException:
        # A crash mid-run still accounts the rounds already executed (the
        # supervised-recovery path reads resilience.nonfinite_rounds for
        # faults that landed BEFORE the crash).
        import contextlib

        with contextlib.suppress(Exception):
            from distkeras_tpu.resilience.guard import note_losses

            note_losses(np.asarray(jax.device_get(losses)))
        raise
    finally:
        # Deterministic shutdown even when the escaping exception (and its
        # traceback's frames) is retained by the caller — generator GC alone
        # would leave the feeder staging batches indefinitely.
        feeder.close()
        # Feed-overlap diagnostic (see RoundFeeder.waits): per-round consumer
        # block times; near-zero past round 0 = staging fully hidden behind
        # dispatch. docs/PERFORMANCE.md "Feed overlap" measures this in anger.
        _record_feed_waits(engine, feeder)
    # One batched fetch — per-item np.asarray would pay one D2H round-trip
    # per round. The retire span is this single fence: all
    # dispatched-but-unfinished device work drains here.
    with tele.span("retire[per-round]"):
        host = jax.device_get(losses)
    return state, np.asarray(host)


def run_stream(engine, items, state=None, on_item=None, start_index=0,
               max_items=None, stage=None, fetch_every=64):
    """Run an **open-ended** item source through an engine's round function.

    Where :func:`run_per_round` walks a BatchPlan's fixed epoch schedule,
    this loop has no epoch bookkeeping at all: ``items`` is any iterable of
    host batches ``(xs, ys)`` — including an unbounded live stream — staged
    through the same :class:`RoundFeeder` lookahead/backpressure (so stream
    stalls hit the stall watchdog and surface as ``FeederStalledError``,
    exactly like a dried-up BatchPlan gather). Both the sync and async
    engines run through here unchanged: each only needs its
    ``_round_fn(state, xs, ys)``.

    ``on_item(i, loss, state)`` sees the *device* loss (no fence).
    ``max_items`` bounds consumption of an endless source (tests, bounded
    sessions); losses are fetched to host in ``fetch_every`` chunks so an
    unbounded run holds O(fetch_every) device scalars, not O(items).
    Returns ``(state, host_losses)`` for the items actually consumed.
    """
    import itertools

    from distkeras_tpu import telemetry
    from distkeras_tpu.data.prefetch import RoundFeeder
    from distkeras_tpu.resilience.guard import RoundGuard, note_losses

    tele = telemetry.get()
    guard = RoundGuard(engine)
    if state is None:
        state = engine.init_state()
    if max_items is not None:
        items = itertools.islice(items, max_items)
    stage = stage or (lambda batch: engine._put_batch(*batch))
    host: list = []
    pending: list = []

    def _drain():
        if pending:
            host.extend(np.ravel(np.asarray(jax.device_get(pending))))
            pending.clear()

    feeder = RoundFeeder(items, stage, start_round=start_index)
    with tele.span("engine_run"):
        try:
            for i, (xs, ys) in feeder:
                _observe_feed_wait(tele, feeder, i)
                guard.pre_round(i)  # crash/kill fault injection
                with tele.span("dispatch[stream]", id=i):
                    new_state, loss = engine._round_fn(state, xs, ys)
                pending.append(loss)
                if on_item is not None:
                    with tele.span("on_round", id=i):
                        on_item(i, loss, new_state)
                state = guard.post_round(i, loss, new_state)
                if len(pending) >= fetch_every:
                    # Incremental fetch: bounds live device scalars AND is
                    # the only fence an endless run ever takes.
                    with tele.span("retire[stream]"):
                        _drain()
        except BaseException:
            import contextlib

            with contextlib.suppress(Exception):
                _drain()
                note_losses(np.asarray(host))
            raise
        finally:
            feeder.close()
            _record_feed_waits(engine, feeder)
    with tele.span("retire[stream]"):
        _drain()
    losses = np.asarray(host, np.float32)
    note_losses(losses)
    return state, losses


#: auto-R sizing. The probe must measure the STEADY-STATE per-round cost:
#: dispatch is async, and any single-round fence adds a fixed sync/fetch
#: round-trip — so the probe runs a batch of unfenced rounds and fences once.
#: R then targets ~64 ms of device work per program. The constants were
#: tuned in rounds 3-5 on an earlier single-chip setup whose dispatch and
#: fence latencies were far higher than a directly attached chip's
#: (MNIST-MLP: 4.8 ms/round at R=1 -> 2.0 ms at R=16; a 16-round scanned LSTM
#: program 16% slower per round than a 4-round one); they keep their values
#: until re-measured (ROADMAP S5). Block batches live in HBM — the byte cap
#: bounds the staged [R, W, K, B, ...] arrays.
_AUTO_MAX_R = 64
_AUTO_BLOCK_BYTES = 256e6
_AUTO_PROBE_ROUNDS = 15
_AUTO_TARGET_S = 0.064


def _auto_size_r(steady_s: float, round_bytes: int) -> int:
    """Rounds per program from a measured steady-state per-round time —
    run_auto's sizing rule.

    Multi-process: every process must run identical blocked programs
    (mismatched R means mismatched collectives -> distributed hang), but
    wall clocks differ per host — process 0's sizing is broadcast to all.
    Callers may further clamp by process-deterministic values (e.g. rounds
    remaining) without breaking agreement."""
    R = max(1, min(_AUTO_MAX_R,
                   max(1, int(_AUTO_BLOCK_BYTES / max(round_bytes, 1))),
                   int(np.ceil(_AUTO_TARGET_S / max(steady_s, 1e-6)))))
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        R = int(multihost_utils.broadcast_one_to_all(np.int32(R)))
    return R


def probe_steady(dispatch_round, n: int = _AUTO_PROBE_ROUNDS) -> float:
    """Steady-state per-round seconds: ``n`` unfenced dispatches, ONE fence
    (a per-round fence would add its sync round-trip to every sample). The
    measurement protocol for pre-staged probes
    (``examples/imagenet_disk.py``); run_auto inlines the same loop because
    it also collects losses and excludes staging time."""
    import time as _time

    t0 = _time.perf_counter()
    fence = None
    for _ in range(n):
        fence = dispatch_round()
    jax.block_until_ready(fence)
    return max((_time.perf_counter() - t0) / n, 1e-6)


def run_auto(engine, plan, state, start_round, on_round):
    """``rounds_per_program="auto"``: probe the steady-state per-round wall
    time on the first few (real) rounds, then execute the rest in blocks of
    ``R ≈ target/round_time`` rounds per dispatch. Loss history and final
    state are identical to any fixed-R run."""
    import time as _time

    from distkeras_tpu import telemetry
    from distkeras_tpu.resilience.guard import RoundGuard

    if start_round >= plan.num_rounds:  # resumed past the end: nothing to do
        return state, np.asarray([])
    tele = telemetry.get()
    guard = RoundGuard(engine)
    losses = []
    r = start_round
    round_bytes = 1

    # Round 1 fences compile (its callback runs inline — we're not timing yet).
    xs, ys = stage_round(engine, plan, r)
    guard.pre_round(r)
    with tele.span("dispatch[auto]"):
        state, loss = engine._round_fn(state, xs, ys)
    losses.append(loss)
    if on_round is not None:
        on_round(r, loss, state)
    state = guard.post_round(r, loss, state)
    r += 1
    jax.block_until_ready(loss)

    # Timed probe: unfenced rounds, one fence at the end. Callbacks are
    # DEFERRED out of the window entirely — a callback that fetches the loss
    # (MetricsLogger) or blocks on a checkpoint write would fence device
    # compute inside any "excluded" sub-window and corrupt the measurement
    # in either direction. Staging time is NOT subtracted: dispatch is async,
    # so host-side staging of round i+1 overlaps the device crunching round
    # i, and the wall clock already reads ~n*max(compute, staging) — which is
    # exactly the steady per-round cost the blocked phase (with RoundFeeder
    # lookahead) will see.
    pending = []
    n = 0
    t0 = _time.perf_counter()
    while r < plan.num_rounds and n < _AUTO_PROBE_ROUNDS:
        xs, ys = stage_round(engine, plan, r)
        round_bytes = sum(int(a.nbytes) for a in jax.tree.leaves((xs, ys)))
        guard.pre_round(r)
        with tele.span("dispatch[auto]"):  # ~µs span cost; rounds are ms
            state, loss = engine._round_fn(state, xs, ys)
        # NOTE: an enabled divergence reset fences each probe round (it must
        # read the loss) — the probe then measures the fenced per-round cost
        # and sizes R conservatively. Correctness is unaffected.
        state = guard.post_round(r, loss, state)
        losses.append(loss)
        pending.append((r, loss))
        r += 1
        n += 1
    head_done = r >= plan.num_rounds
    if n:
        jax.block_until_ready(loss)
        steady = max((_time.perf_counter() - t0) / n, 1e-6)
    host_all = None
    if on_round is not None and pending:
        # One batched fetch of ALL head losses (round 1 + probe rounds), then
        # callbacks see host arrays — per-callback np.asarray(loss)
        # (MetricsLogger) would otherwise issue up to 16 sequential D2H
        # round-trips before the blocked phase dispatches. The same host
        # copies serve as the returned head, so nothing is fetched twice.
        host_all = jax.device_get(losses)
        # Same contract as run_blocked: only the final call of the probe
        # "block" carries a state (interior states were donated onward).
        for i, (rr, _) in enumerate(pending):
            on_round(rr, host_all[1 + i],
                     state if i == len(pending) - 1 else None)
    if head_done:
        return state, np.asarray(
            host_all if host_all is not None else jax.device_get(losses))
    # num_rounds - r is process-deterministic, so the clamp preserves the
    # cross-process agreement _auto_size_r establishes.
    R = min(_auto_size_r(steady, round_bytes), plan.num_rounds - r)
    state, rest = run_blocked(engine, plan, state, r, on_round, R, mode="auto")
    # Without callbacks the head losses were never needed earlier — fetch
    # them only now, after the blocked phase dispatched, so the device never
    # idled on a D2H fetch between probe and blocked work.
    head = np.asarray(
        host_all if host_all is not None else jax.device_get(losses))
    return state, np.concatenate([head, np.asarray(rest)], axis=0)


def run_blocked(engine, plan, state, start_round, on_round, R, mode="blocked"):
    """Engine run loop with ``R`` rounds per compiled program (one dispatch per
    block; see ``multi_round_fn``). Loss histories are identical to the
    per-round path; ``on_round`` still fires once per round but only the
    block-final call carries a state (interior calls get ``None`` — their
    states never materialize on the host). Shared by the async and sync
    engines. ``mode`` tags the telemetry histograms ("blocked", or "auto"
    when run_auto sized R)."""
    from distkeras_tpu import telemetry
    from distkeras_tpu.data.prefetch import RoundFeeder
    from distkeras_tpu.resilience.guard import RoundGuard

    tele = telemetry.get()
    guard = RoundGuard(engine)
    dispatch_span = f"dispatch[{mode}]"
    retire_span = f"retire[{mode}]"
    starts = list(range(start_round, plan.num_rounds, R))

    def stage(i):
        # Blocked batches are [R, W, K, B, ...]: the worker axis moves to dim 1.
        rs = range(starts[i], min(starts[i] + R, plan.num_rounds))
        return stage_block(engine, plan, rs)

    losses = []
    feeder = RoundFeeder(len(starts), stage)
    try:
        for i, (xs, ys) in feeder:
            # Span ids here are the block's index, as the feeder's are.
            _observe_feed_wait(tele, feeder, i)
            n = xs.shape[0]
            # Crash/kill faults land at the block boundary containing their
            # round — interior rounds of a compiled program are indivisible.
            for rr in range(starts[i], starts[i] + n):
                guard.pre_round(rr)
            with tele.span(dispatch_span, id=i):
                new_state, block_losses = engine.multi_round_fn(n)(
                    state, xs, ys)
            if on_round is not None:
                # The block fence: np.asarray blocks until the whole
                # dispatched program retires — per-block retire latency.
                with tele.span(retire_span, id=i):
                    host_losses = np.asarray(block_losses)
                for j in range(n):
                    # Only the block-final call carries state: interior
                    # rounds' states never exist on the host, and handing out
                    # the block-final state under an interior round label
                    # would let a checkpoint resume re-apply rounds it
                    # already contains.
                    st = new_state if j == n - 1 else None
                    with tele.span("on_round", id=starts[i] + j):
                        on_round(starts[i] + j, host_losses[j], st)
                losses.extend(host_losses)
                state = guard.post_round(starts[i] + n - 1, block_losses[-1],
                                         new_state,
                                         host_loss=host_losses[-1])
            else:
                # No callbacks -> keep losses on device; a per-block D2H
                # fence would idle the device once every block. One batched
                # fetch at the end instead.
                losses.append(block_losses)
                state = guard.post_round(starts[i] + n - 1, block_losses[-1],
                                         new_state)
    except BaseException:
        import contextlib

        with contextlib.suppress(Exception):  # see run_per_round's twin
            from distkeras_tpu.resilience.guard import note_losses

            fetched = jax.device_get(losses)
            if fetched:
                note_losses(np.vstack(
                    [np.atleast_1d(np.asarray(f)) for f in fetched]))
        raise
    finally:
        feeder.close()  # deterministic even if the exception is retained
        _record_feed_waits(engine, feeder)
    if losses and on_round is None:  # device blocks: one batched fetch
        with tele.span(retire_span):
            fetched = jax.device_get(losses)
        losses = list(np.concatenate(fetched, axis=0))
    return state, np.asarray(losses)


def make_multi_round_fn(engine, rounds: int):
    """Build/cache a jitted ``rounds``-per-dispatch program from an engine's
    unjitted ``_round_core`` (see ``AsyncEngine.multi_round_fn``)."""
    fn = engine._multi_fns.get(rounds)
    if fn is None:
        core = engine._round_core

        def multi(state, xs_stack, ys_stack):
            def body(st, xy):
                st2, loss = core(st, *xy)
                return st2, loss

            state, losses = lax.scan(body, state, (xs_stack, ys_stack))
            return state, losses

        fn = jax.jit(multi, donate_argnums=(0,))
        engine._multi_fns[rounds] = fn
    return fn
