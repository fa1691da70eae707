"""Trainer taxonomy — class-for-class parity with ``distkeras/trainers.py``.

Same names, same constructor-kwargs surface, same ``train(dataframe) -> model`` entry
point (SURVEY.md §2, L5). What changed underneath: ``num_workers`` Spark partitions
become ``num_workers`` chips on a ``data`` mesh; the parameter-server thread becomes a
collective fold (``parallel/disciplines.py``); ``model.train_on_batch`` becomes a
jitted ``lax.scan`` window (``workers.py``).

Trainer -> engine mapping:

* ``SingleTrainer``                  -> SyncEngine on a 1-chip mesh
* ``SynchronousDistributedTrainer``  -> SyncEngine (per-step gradient pmean)
* ``DOWNPOUR/ADAG/DynSGD``           -> AsyncEngine, pull-based folds
* ``AEASGD/EAMSGD``                  -> AsyncEngine, elastic folds
* ``AveragingTrainer``               -> AsyncEngine, no-comm fold + final weight mean
* ``EnsembleTrainer``                -> AsyncEngine, no-comm fold, returns N models
"""

from __future__ import annotations

import time
import warnings
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu import telemetry
from distkeras_tpu.data.batching import make_batches
from distkeras_tpu.data.dataframe import DataFrame
from distkeras_tpu.models.base import Model
from distkeras_tpu.parallel.disciplines import (
    ADAGFold,
    AEASGDFold,
    Discipline,
    DownpourFold,
    DynSGDFold,
    EAMSGDFold,
    EnsembleFold,
)
from distkeras_tpu.parallel.engine import AsyncEngine
from distkeras_tpu.parallel.sync import SyncEngine
from distkeras_tpu.runtime import config as runtime_config
from distkeras_tpu.runtime.config import RunConfig
from distkeras_tpu.runtime.mesh import data_mesh

#: Discipline-fold class -> the wire name the netps server folds under
#: (subclass before base: EAMSGDFold is an AEASGDFold).
_FOLD_WIRE_NAMES = (
    (EAMSGDFold, "eamsgd"),
    (AEASGDFold, "aeasgd"),
    (DynSGDFold, "dynsgd"),
    (ADAGFold, "adag"),
    (DownpourFold, "downpour"),
)


def _fold_wire_name(disc: Discipline) -> str:
    for cls, name in _FOLD_WIRE_NAMES:
        if isinstance(disc, cls):
            return name
    raise ValueError(
        f"{type(disc).__name__} has no networked parameter-server "
        "equivalent (only the communicating PS disciplines do)")

#: Socket-era reference kwargs that have no TPU meaning: the parameter-server
#: transport is XLA collectives, so there is no master address/port to bind.
#: Accepted-and-ignored (with a warning) so 2016-era notebooks port by deleting
#: imports, not by editing every constructor call.
_LEGACY_SOCKET_KWARGS = frozenset({"master_port", "master_host", "master", "port"})


def _config_prop(name: str) -> property:
    """Trainer attribute backed by the :class:`RunConfig` (kwargs-first surface
    preserved; assignment rebuilds the frozen config)."""

    def _get(self):
        return getattr(self.config, name)

    def _set(self, value):
        self.config = self.config.replace(**{name: value})

    return property(_get, _set)


class Trainer:
    """Base trainer (reference ``Trainer``): owns model, optimizer, loss, timing.

    ``worker_optimizer`` and ``loss`` accept the reference's Keras-style strings or
    any optax transformation / callable. Hyperparameters normalize into
    ``self.config`` (:class:`RunConfig`); the reference's kwarg names stay
    readable/assignable as properties over it.
    """

    batch_size = _config_prop("batch_size")
    num_epoch = _config_prop("num_epoch")
    learning_rate = _config_prop("learning_rate")
    seed = _config_prop("seed")

    def __init__(
        self,
        model: Model,
        worker_optimizer="sgd",
        loss="categorical_crossentropy",
        features_col: str = "features",
        label_col: str = "label",
        batch_size: int = 32,
        num_epoch: int = 1,
        learning_rate: float = 0.01,
        compute_dtype: Optional[str] = None,
        seed: int = 0,
        metrics_path: Optional[str] = None,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: int = 0,
        resume: bool = False,
        rounds_per_program: Union[int, str] = 1,
        on_round=None,
        grad_accum: int = 1,
        transform=None,
        device_transform=None,
        normalize_uint8: bool = True,
        **kwargs,
    ):
        legacy = {k: kwargs.pop(k) for k in list(kwargs) if k in _LEGACY_SOCKET_KWARGS}
        if "parallel" in kwargs:
            # Targeted, not a bare TypeError: a user who learned parallel=
            # on ADAG will try it on the ensemble/averaging/sync trainers.
            raise ValueError(
                f"{type(self).__name__} does not host model-parallel "
                "submeshes. parallel={'model': tp, 'seq': sp} is supported "
                "by the communicating async trainers (DOWNPOUR/ADAG/DynSGD/"
                "AEASGD/EAMSGD — each worker becomes a tp[ x sp] submesh); "
                "for model-parallel synchronous training use "
                "ParallelTrainer(parallel={'data': ..., 'model': ...}). "
                "Averaging/Ensemble fold non-communicating replicas and "
                "have no submesh variant.")
        if kwargs:
            raise TypeError(
                f"{type(self).__name__} got unexpected kwargs: {sorted(kwargs)}"
            )
        if legacy:
            warnings.warn(
                f"ignoring socket-era kwargs {sorted(legacy)}: the parameter "
                "server is an XLA collective fold on TPU — there is no master "
                "address/port (kept for reference-notebook compatibility)",
                DeprecationWarning,
                stacklevel=2,
            )
        if not normalize_uint8 and getattr(model, "normalize_uint8", True):
            # The flag lives on the Model (engines, the remote worker loop,
            # and predictors all read it there — train and inference can
            # never disagree); the Trainer kwarg is the opt-out surface.
            import dataclasses as _dc

            model = _dc.replace(model, normalize_uint8=False)
        self.model = model
        self.worker_optimizer = worker_optimizer
        self.loss = loss
        self.features_col = features_col
        self.label_col = label_col
        if isinstance(compute_dtype, (str, type(None))):
            dtype_str, self._dtype_override = compute_dtype, None
        else:  # a concrete jnp dtype: bypasses the string-keyed config
            dtype_str, self._dtype_override = None, compute_dtype
        self.config = RunConfig(
            batch_size=batch_size, num_epoch=num_epoch,
            learning_rate=learning_rate, compute_dtype=dtype_str, seed=seed,
        )
        self.metrics_path = metrics_path
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_every = checkpoint_every
        self.resume = resume
        #: fold rounds per dispatched XLA program (1 = a program per round).
        #: Semantics-preserving dispatch amortization: raise it when host
        #: dispatch latency, not the device, bounds small-model throughput.
        #: Checkpoints then land on block boundaries (exact-resume-safe).
        #: ``"auto"`` probes the per-round wall time and sizes R to fill
        #: ~64 ms of device work per program (engine._AUTO_TARGET_S) — the
        #: right default for small models on dispatch-latency-heavy paths
        #: (no hand tuning).
        if rounds_per_program == "auto":
            self.rounds_per_program: Union[int, str] = "auto"
        elif (isinstance(rounds_per_program, str)
              or int(rounds_per_program) < 1):
            raise ValueError(
                f"rounds_per_program must be an int >= 1 or 'auto', got "
                f"{rounds_per_program!r}")
        else:
            self.rounds_per_program = int(rounds_per_program)
        #: optional ``f(round, loss)`` fired after every fold round (the
        #: Keras-callback-shaped progress hook; reference workers printed
        #: per-batch logs on executors — here the driver sees every round).
        self.on_round = on_round
        #: micro-batches per optimizer step (1/A the activation memory — for
        #: batches that don't fit HBM; see workers.make_local_loop for the
        #: BatchNorm/dropout semantics caveat).
        self.grad_accum = int(grad_accum)
        if self.grad_accum < 1:
            raise ValueError(f"grad_accum must be >= 1, got {grad_accum}")
        #: optional training-time row transform ``fn(features, labels, rng)
        #: -> (features, labels)`` applied to every staged round
        #: (deterministic per (seed, round, worker) — the lazy Spark-pipeline
        #: half: per-epoch randomized augmentation, train-time normalization;
        #: works for in-RAM and sharded dataframes alike). See
        #: ``data.batching.apply_round_transform``.
        self.transform = transform
        #: optional ON-DEVICE per-step transform ``fn(rng, x, y) -> (x, y)``
        #: applied inside the jitted round program (``ops/augment.py``) —
        #: image augmentation at VPU cost with raw uint8 staged over PCIe,
        #: vs ``transform``'s host-numpy cost. Deterministic per
        #: (seed, round, worker) like the host hook.
        self.device_transform = device_transform
        self.history: np.ndarray | None = None
        self.worker_histories: np.ndarray | None = None
        #: the engine the last ``train()`` ran on — for inspection after
        #: the fact (its compiled round program, ``feed_waits``); None
        #: before the first run and on the ``remote=`` path.
        self.engine = None
        self.training_time: float = 0.0
        self._t_start: float | None = None

    @property
    def compute_dtype(self):
        if self._dtype_override is not None:
            return self._dtype_override
        return self.config.dtype

    @compute_dtype.setter
    def compute_dtype(self, value):
        if isinstance(value, (str, type(None))):
            self._dtype_override = None
            self.config = self.config.replace(compute_dtype=value)
        else:
            self._dtype_override = value

    def _restore_candidate(self, engine, plan, ckpt, step, meta):
        """Restore checkpoint ``step`` (whose sidecar ``meta`` was already
        read) onto ``engine``, integrity-verified against the digest sidecar.
        Returns ``(state, start_round)``; raises on a missing/corrupt
        payload so :meth:`_resume_from_checkpoint` can fall back."""
        if not meta:
            # Orbax steps are offset from rounds across resumes; with
            # the sidecar gone the raw step is only an upper bound on
            # the true round. Resume conservatively from it, loudly.
            warnings.warn(
                f"checkpoint step {step} has no meta sidecar; "
                "treating the step as the round index — if this run "
                "chain was ever resumed or resized, data progress "
                "may be overestimated", stacklevel=2)
        true_round = int(meta.get("round", step))
        saved_w = meta.get("num_workers")
        cur_w = getattr(engine, "num_workers", None)
        saved_spr = meta.get("samples_per_round")
        resized = (saved_w is not None and cur_w is not None
                   and saved_w != cur_w)
        # Round indices are meaningless across schedules whose
        # per-round sample count changed — a worker-count resize,
        # OR a topology-dependent plan (e.g. a step engine's
        # per-dp-rank sharded schedule) whose spr moved while the
        # engine's logical worker count stayed 1.
        spr_changed = (saved_spr is not None
                       and saved_spr != plan.samples_per_round)
        start = 0
        if resized or spr_changed:
            # Carry over DATA progress (samples consumed), not the
            # raw counter. Old checkpoints without samples_per_round
            # meta fall back to the worker-count ratio (exact when
            # batch/window are unchanged, the common pod-resize
            # case).
            num = saved_spr if saved_spr else saved_w
            den = plan.samples_per_round if saved_spr else cur_w
            start = min(((true_round + 1) * num) // den,
                        plan.num_rounds)
        if resized and hasattr(engine, "host_state"):
            # Elastic resume: the checkpoint was written at a
            # different worker count (pod resize). Restore on the
            # host at the saved topology, then re-join every worker
            # from the center (the reference's PS pull semantics).
            host = ckpt.restore_host(engine.host_state(saved_w),
                                     step=step, verify=True)
            state = engine.adopt_state(host)
        else:
            state = ckpt.restore(engine.init_state(), step=step, verify=True)
            if resized:
                # W-independent state (e.g. SyncEngine) restores
                # exactly under a resize; data progress still
                # rescales so the resumed run neither replays nor
                # skips a topology-dependent slice of the data.
                warnings.warn(
                    f"resuming a checkpoint saved with num_workers="
                    f"{saved_w} on num_workers={cur_w}: state "
                    "restored exactly; data progress rescaled",
                    stacklevel=2)
            elif spr_changed:
                warnings.warn(
                    "resuming under a schedule whose samples/round "
                    f"changed ({saved_spr} -> "
                    f"{plan.samples_per_round}): state restored "
                    "exactly; data progress rescaled", stacklevel=2)
            else:
                start = min(true_round + 1, plan.num_rounds)
        return state, start

    def _resume_from_checkpoint(self, engine, plan, ckpt):
        """Resolve the resume point over ALL retained steps, newest first:
        steps with an intact meta sidecar are preferred (a missing/corrupt
        sidecar falls back to the most recent step that has one), and a step
        whose payload fails to restore or fails its integrity check falls
        back to the previous step. Returns ``(state, start, step_offset)``;
        ``state`` is None when nothing was restorable (fresh start)."""
        from distkeras_tpu.checkpoint import resume_candidates

        steps = ckpt.steps_desc()
        candidates = resume_candidates(
            steps, lambda s: ckpt.meta(s) is not None)
        if steps and candidates[0] != steps[0]:
            telemetry.counter("resilience.ckpt_fallback_steps").add(1)
            warnings.warn(
                f"latest checkpoint step {steps[0]} has a missing/corrupt "
                f"meta sidecar; falling back to step {candidates[0]}, the "
                "most recent step with an intact sidecar", stacklevel=2)
        last_err = None
        for step in candidates:
            meta = ckpt.meta(step) or {}
            saved_w = meta.get("num_workers")
            cur_w = getattr(engine, "num_workers", None)
            if (saved_w is not None and cur_w is not None
                    and saved_w != cur_w and hasattr(engine, "host_state")):
                disc = getattr(engine, "discipline", None)
                if disc is not None and not disc.center_is_trained:
                    # A configuration error, not corruption: falling back
                    # to an older step cannot fix a topology mismatch.
                    raise ValueError(
                        f"cannot elastically resume {type(disc).__name__}"
                        " (worker count changed): its training progress"
                        " lives in the per-worker replicas, not the"
                        " center. Resume with the original num_workers="
                        f"{saved_w}.")
            try:
                state, start = self._restore_candidate(
                    engine, plan, ckpt, step, meta)
            except Exception as e:  # corrupt/unreadable: try the next step
                last_err = e
                telemetry.counter("resilience.ckpt_fallback_steps").add(1)
                telemetry.event("ckpt_fallback", {
                    "step": step, "error": repr(e)})
                warnings.warn(
                    f"checkpoint step {step} failed to restore "
                    f"({type(e).__name__}: {e}); falling back to the "
                    "previous step", stacklevel=2)
                continue
            # Offset past the NEWEST retained step, not the restored one:
            # after a fallback the skipped (corrupt/sidecar-less) newer
            # steps are still on disk, and Orbax declines any save at a
            # step <= latest_step() — offsetting from the restored step
            # would get every periodic save until the counter passed them
            # silently declined.
            return state, start, (steps[0] + 1) - start
        warnings.warn(
            f"no restorable checkpoint in {self.checkpoint_dir} "
            f"(last error: {last_err!r}); starting fresh", stacklevel=2)
        return None, 0, (steps[0] + 1) if steps else 0

    def _execute(self, engine, plan):
        """Shared run harness: resume from checkpoint, per-round metrics/saves."""
        self.engine = engine
        state = None
        start = 0
        # Orbax step = round + step_offset. Orbax declines saves at any
        # step <= latest_step, and elastic resume can map the resume round
        # BELOW the saved step (scale-up: start = (r+1)*saved_w//cur_w < r) —
        # without an offset every post-resize checkpoint would be silently
        # dropped until the counter passed the old step. The offset keeps the
        # Orbax step sequence strictly increasing across any chain of resumes
        # while ``meta["round"]`` records the true (topology-local) round.
        step_offset = 0
        ckpt = logger = None
        if self.checkpoint_dir:
            from distkeras_tpu.checkpoint import Checkpointer

            ckpt = Checkpointer(self.checkpoint_dir)
            latest = ckpt.latest_step()
            if self.resume and latest is not None:
                with telemetry.span("setup.resume"):
                    state, start, step_offset = self._resume_from_checkpoint(
                        engine, plan, ckpt)
            elif latest is not None:
                # Fresh run (resume=False) into a dir with prior checkpoints:
                # rounds restart at 0, so without an offset every save would
                # land at a step Orbax has already seen and be declined.
                step_offset = latest + 1
        if state is None:
            with telemetry.span("setup.init_state"):
                state = engine.init_state()
        if self.metrics_path:
            from distkeras_tpu.metrics import MetricsLogger
            from distkeras_tpu.telemetry.training import DisciplineMonitor

            logger = MetricsLogger(
                self.metrics_path,
                samples_per_round=plan.samples_per_round,
                # Step engines run one logical plan-worker over many chips;
                # they expose the true chip count for samples/s/chip.
                num_chips=getattr(engine, "num_chips", plan.num_workers),
                extra={"trainer": type(self).__name__},
                # Discipline-aware round fields (staleness rotation, DynSGD
                # scales, per-worker loss divergence, straggler flags) for
                # engines that have a discipline; inert otherwise.
                monitor=DisciplineMonitor(
                    discipline=getattr(engine, "discipline", None),
                    num_workers=getattr(engine, "num_workers", 1)),
            )

        save_due = [False]  # a scheduled save passed while no state was out

        def _meta(r):
            return {"num_workers": getattr(engine, "num_workers", 1),
                    "round": r,
                    "samples_per_round": plan.samples_per_round}

        def on_round(r, loss, st):
            if logger is not None:
                # st=None marks interior rounds of a compiled block (the
                # engine contract) — the logger's authoritative burst-tail
                # signal for segmentation and straggler flagging.
                logger(r, loss, st)
            if self.on_round is not None:
                self.on_round(r, loss)
            if ckpt is None or not self.checkpoint_every:
                return
            if (r + 1) % self.checkpoint_every == 0 or r == plan.num_rounds - 1:
                save_due[0] = True
            # With rounds_per_program > 1 only block-final rounds carry a
            # state (interior states never exist on the host); a due save
            # waits for the next state-bearing call, whose label ``r`` is the
            # true round of that state — resume stays exact.
            if save_due[0] and st is not None:
                # wait=True: the engine donates state buffers into the next
                # round; the write must complete before training continues.
                # A declined save (e.g. another writer advanced the manager's
                # latest_step) keeps the save due, to retry at the next
                # state-bearing round instead of silently dropping it.
                if ckpt.save(r + step_offset, st, wait=True, meta=_meta(r)):
                    save_due[0] = False

        import contextlib

        done = False
        try:
            state, losses = engine.run(
                plan, state=state, start_round=start, on_round=on_round,
                rounds_per_program=self.rounds_per_program)
            if ckpt is not None and save_due[0] and plan.num_rounds > start:
                # The final scheduled save was declined (e.g. another writer
                # advanced the manager's latest_step past our sequence) and
                # there was no later round to retry at — persist the
                # terminal state at the next step the manager will accept.
                final_r = plan.num_rounds - 1
                latest_now = ckpt.latest_step()
                step = max(final_r + step_offset,
                           (-1 if latest_now is None else latest_now) + 1)
                ckpt.save(step, state, wait=True, meta=_meta(final_r))
            # Happy path closes UNsuppressed: a failed final checkpoint
            # flush must surface, not vanish into a finally.
            if ckpt is not None:
                ckpt.close()
            if logger is not None:
                logger.close()
            done = True
        finally:
            # Failure path (including a close that itself raised): orbax's
            # background threads and the metrics file handle must not leak
            # across in-process retries. Close errors are suppressed (an
            # in-flight async save can raise from wait_until_finished) so
            # the root-cause exception propagates; MetricsLogger.close is
            # idempotent, so the clean-exit double call is a no-op.
            if not done:
                if ckpt is not None:
                    with contextlib.suppress(Exception):
                        ckpt.close()
                if logger is not None:
                    with contextlib.suppress(Exception):
                        logger.close()
        losses = np.asarray(losses)
        if losses.ndim == 2:  # async engines: [rounds, W] per-worker curves
            self.worker_histories = losses.T
            self.history = losses.mean(axis=1)
        else:
            self.worker_histories = None
            self.history = losses
        return state

    def _finish_model(self, params, engine_state, worker: Optional[int] = None,
                      state_reduce=None) -> Model:
        """Model with trained params + (if the model is stateful) the trained
        mutable collections (BatchNorm running stats).

        Async engines stack state ``[W, ...]``: pass ``worker`` to take one
        member's copy (synced disciplines keep all copies equal, so 0 is
        canonical) or ``state_reduce`` to aggregate (AveragingTrainer)."""
        m = self.model.with_params(params)
        trained_state = getattr(engine_state, "model_state", None)
        if trained_state is not None:
            if state_reduce is not None:
                trained_state = jax.tree.map(state_reduce, trained_state)
            elif worker is not None:
                trained_state = jax.tree.map(lambda a: a[worker], trained_state)
            m = m.with_state(jax.tree.map(np.asarray, trained_state))
        return m

    # -- timing parity (reference Trainer.record_training_start/stop) -------
    def record_training_start(self):
        self._t_start = time.perf_counter()

    def record_training_stop(self):
        self.training_time = time.perf_counter() - self._t_start

    def get_training_time(self) -> float:
        return self.training_time

    def get_history(self) -> np.ndarray:
        return self.history

    def get_worker_histories(self) -> Optional[np.ndarray]:
        """Per-worker loss curves, shape ``[num_workers, rounds]`` (reference
        parity: per-worker Keras history collected on the driver; SURVEY.md §5
        metrics row). ``None`` for sync engines, whose replicas never diverge."""
        return self.worker_histories

    def train(self, dataframe: DataFrame, shuffle: bool = False) -> Model:
        raise NotImplementedError


class SingleTrainer(Trainer):
    """One-replica baseline (reference ``SingleTrainer``): coalesce to a single
    worker, plain minibatch SGD, no communication."""

    def __init__(self, *args, steps_per_program: int = 8, **kwargs):
        super().__init__(*args, **kwargs)
        self.steps_per_program = steps_per_program

    def train(self, dataframe: DataFrame, shuffle: bool = False) -> Model:
        self.record_training_start()
        mesh = data_mesh(num_workers=1)
        with telemetry.span("setup.build_engine"):
            engine = SyncEngine(
                self.model, self.worker_optimizer, self.loss, mesh,
                learning_rate=self.learning_rate, compute_dtype=self.compute_dtype,
                seed=self.seed, grad_accum=self.grad_accum,
                device_transform=self.device_transform,
            )
        with telemetry.span("setup.plan"):
            plan = make_batches(
                dataframe, self.features_col, self.label_col, self.batch_size,
                num_workers=1, window=self.steps_per_program, num_epoch=self.num_epoch,
                shuffle=shuffle, seed=self.seed, transform=self.transform,
            )
        state = self._execute(engine, plan)
        self.record_training_stop()
        return self._finish_model(state.params, state)


class DistributedTrainer(Trainer):
    """Base for multi-worker trainers (reference ``DistributedTrainer``)."""

    num_workers = _config_prop("num_workers")

    def __init__(self, *args, num_workers: Optional[int] = None, **kwargs):
        super().__init__(*args, **kwargs)
        self.config = self.config.replace(num_workers=num_workers)

    def _mesh(self):
        """(mesh, workers_per_chip): ``num_workers`` is a *logical* worker
        count (the reference's Spark-executor count — 8 workers on a laptop
        was normal), so counts beyond the chip count multiplex m workers
        onto each chip instead of erroring."""
        w = self.num_workers
        devices = jax.device_count()
        if w is None or w <= devices:
            return data_mesh(num_workers=w), 1
        if w % devices == 0:
            return data_mesh(), w // devices
        raise ValueError(
            f"num_workers={w} exceeds the {devices} available chips and "
            f"does not divide evenly onto them; use a multiple of {devices} "
            "(m workers per chip) or at most the chip count")


class SynchronousDistributedTrainer(DistributedTrainer):
    """Per-step gradient all-reduce (reference ``SynchronousDistributedTrainer``;
    BASELINE config #5's "synchronous DOWNPOUR" at scale)."""

    def __init__(self, *args, steps_per_program: int = 8, **kwargs):
        super().__init__(*args, **kwargs)
        self.steps_per_program = steps_per_program

    def train(self, dataframe: DataFrame, shuffle: bool = False) -> Model:
        self.record_training_start()
        mesh, m = self._mesh()
        with telemetry.span("setup.build_engine"):
            engine = SyncEngine(
                self.model, self.worker_optimizer, self.loss, mesh,
                learning_rate=self.learning_rate, compute_dtype=self.compute_dtype,
                seed=self.seed, grad_accum=self.grad_accum, workers_per_chip=m,
                device_transform=self.device_transform,
            )
        with telemetry.span("setup.plan"):
            plan = make_batches(
                dataframe, self.features_col, self.label_col, self.batch_size,
                num_workers=engine.num_workers, window=self.steps_per_program,
                num_epoch=self.num_epoch, shuffle=shuffle, seed=self.seed, transform=self.transform,
            )
        state = self._execute(engine, plan)
        self.record_training_stop()
        return self._finish_model(state.params, state)


class AsynchronousDistributedTrainer(DistributedTrainer):
    """Base for the discipline trainers (reference
    ``AsynchronousDistributedTrainer``): K local steps per worker per fold round."""

    communication_window = _config_prop("communication_window")

    def __init__(self, *args, communication_window: int = 5,
                 parallel: Optional[dict] = None, rules=None,
                 divergence_reset: Optional[float] = None,
                 remote: Optional[str] = None, **kwargs):
        super().__init__(*args, **kwargs)
        self.config = self.config.replace(communication_window=communication_window)
        #: ``"host:port"`` of a networked parameter server (netps): the
        #: worker loop becomes pull -> K local steps -> commit through the
        #: hardened TCP client instead of the in-process collective fold.
        #: Defaults from DKTPU_PS_ENDPOINT (set by Job for launched pods).
        self.remote = remote
        if remote and parallel:
            raise ValueError(
                "remote= (networked parameter server) and parallel= "
                "(model-parallel submeshes) cannot combine: the remote "
                "worker loop runs whole-model replicas")
        #: resilience: |worker loss − mean| beyond this threshold re-adopts
        #: the center for that worker (fresh optimizer, reference PS-pull
        #: semantics). None (default) = off; fetches the loss every round
        #: when on. Env override: DKTPU_DIVERGENCE_RESET.
        self.divergence_reset = divergence_reset
        #: each async worker as a model-parallel submesh:
        #: ``parallel={"model": 2}`` makes every logical worker a tp=2
        #: tensor-parallel replica (AsyncTPEngine over a (data, model)
        #: mesh); ``rules`` overrides the PartitionSpec rule set (default
        #: TRANSFORMER_TP_RULES).
        self.parallel = dict(parallel) if parallel else None
        self.rules = rules

    def _discipline(self) -> Discipline:
        raise NotImplementedError

    def _tp_engine(self):
        from distkeras_tpu.parallel.async_tp import AsyncTPEngine
        from distkeras_tpu.parallel.sharding import TRANSFORMER_TP_RULES
        from distkeras_tpu.runtime.mesh import hybrid_mesh

        axes = dict(self.parallel)
        tp = int(axes.pop("model", 1))
        sp = int(axes.pop("seq", 1))
        if axes:
            raise ValueError(
                f"async parallel supports only {{'model': n}} and "
                f"{{'seq': s}}, got extra axes {sorted(axes)}; pipeline/"
                "expert parallel compose via ParallelTrainer instead")
        devices = jax.device_count()
        W = self.num_workers or devices // (tp * sp)
        if W < 1 or W * tp * sp > devices:
            raise ValueError(
                f"parallel={{'model': {tp}, 'seq': {sp}}} with "
                f"num_workers={self.num_workers} needs num_workers*{tp * sp} "
                f"<= {devices} available devices (and at least one worker); "
                f"got W={W}")
        model = self.model
        layout = {"data": W, "model": tp}
        if sp > 1 or getattr(model.module, "seq_axis", None) is not None:
            # seq between data and model: ring ppermutes ride faster links
            # than the worker fold, TP all-reduces the fastest.
            layout = {"data": W, "seq": sp, "model": tp}
        if sp > 1 and getattr(model.module, "seq_axis", None) is None:
            # Same rebind ParallelTrainer does: a module built without
            # seq_axis would silently use local positions under sequence
            # sharding. Dense/flash attention falls back to gather-SP;
            # 'ring' must be requested at model construction.
            if not hasattr(model.module, "seq_axis"):
                raise ValueError(
                    f"parallel={self.parallel} has a 'seq' axis but "
                    f"{type(model.module).__name__} is not sequence-"
                    "shardable (no seq_axis attribute)")
            from distkeras_tpu.runtime.mesh import SEQ_AXIS

            model = model.with_module(model.module.clone(seq_axis=SEQ_AXIS))
        mesh = hybrid_mesh(layout)
        rules = self.rules if self.rules is not None else TRANSFORMER_TP_RULES
        return AsyncTPEngine(
            model, self.worker_optimizer, self.loss, self._discipline(),
            mesh, window=self.communication_window, rules=rules,
            learning_rate=self.learning_rate,
            compute_dtype=self.compute_dtype, seed=self.seed,
            grad_accum=self.grad_accum,
            device_transform=self.device_transform,
            divergence_reset=self.divergence_reset,
        )

    def _run(self, dataframe: DataFrame, shuffle: bool):
        if self.parallel:
            with telemetry.span("setup.build_engine"):
                engine = self._tp_engine()
        else:
            mesh, m = self._mesh()
            with telemetry.span("setup.build_engine"):
                engine = AsyncEngine(
                    self.model, self.worker_optimizer, self.loss,
                    self._discipline(), mesh,
                    window=self.communication_window,
                    learning_rate=self.learning_rate,
                    compute_dtype=self.compute_dtype, seed=self.seed,
                    grad_accum=self.grad_accum, workers_per_chip=m,
                    device_transform=self.device_transform,
                    divergence_reset=self.divergence_reset,
                )
        with telemetry.span("setup.plan"):
            plan = make_batches(
                dataframe, self.features_col, self.label_col, self.batch_size,
                num_workers=engine.num_workers, window=self.communication_window,
                num_epoch=self.num_epoch, shuffle=shuffle, seed=self.seed, transform=self.transform,
            )
        return self._execute(engine, plan)

    def _remote_endpoint(self) -> Optional[str]:
        return self.remote or runtime_config.env_str("DKTPU_PS_ENDPOINT") or None

    def _train_remote(self, dataframe: DataFrame, shuffle: bool,
                      endpoint: str) -> Model:
        """The networked-PS path: N worker threads, each pull -> K jitted
        local steps -> commit over TCP through the hardened client
        (``netps/remote.py``); returns the server's final center."""
        from distkeras_tpu.netps.remote import run_remote
        from distkeras_tpu.ops.losses import get_loss
        from distkeras_tpu.ops.optimizers import get_optimizer

        if self.checkpoint_dir or self.metrics_path:
            warnings.warn(
                "remote= training does not drive the checkpoint/metrics "
                "harness: the parameter-server process owns the center; "
                "checkpoint_dir/metrics_path are ignored on this path",
                stacklevel=2)
        W = self.num_workers or jax.device_count()
        with telemetry.span("setup.plan"):
            plan = make_batches(
                dataframe, self.features_col, self.label_col, self.batch_size,
                num_workers=W, window=self.communication_window,
                num_epoch=self.num_epoch, shuffle=shuffle, seed=self.seed,
                transform=self.transform,
            )
        disc = self._discipline()
        params, losses = run_remote(
            endpoint=endpoint, model=self.model,
            tx=get_optimizer(self.worker_optimizer, self.learning_rate),
            loss_fn=get_loss(self.loss), plan=plan,
            discipline=_fold_wire_name(disc),
            window=self.communication_window,
            alpha=getattr(disc, "alpha", 0.05), seed=self.seed,
            compute_dtype=self.compute_dtype, grad_accum=self.grad_accum,
        )
        self.worker_histories = losses.T
        self.history = np.nanmean(losses, axis=1)
        return self.model.with_params(params)

    def train(self, dataframe: DataFrame, shuffle: bool = False) -> Model:
        self.record_training_start()
        endpoint = self._remote_endpoint()
        if endpoint:
            # Re-check here, not only in __init__: the endpoint may arrive
            # via DKTPU_PS_ENDPOINT (a Job-launched pod sets it for every
            # worker), and silently dropping a requested model-parallel
            # layout would be far worse than refusing.
            if self.parallel:
                raise ValueError(
                    f"parameter-server endpoint {endpoint!r} (remote= or "
                    "DKTPU_PS_ENDPOINT) cannot combine with parallel=: the "
                    "remote worker loop runs whole-model replicas")
            model = self._train_remote(dataframe, shuffle, endpoint)
            self.record_training_stop()
            return model
        state = self._run(dataframe, shuffle)
        self.record_training_stop()
        return self._finish_model(state.center, state, worker=0)


class DOWNPOUR(AsynchronousDistributedTrainer):
    """DOWNPOUR (reference ``DOWNPOUR`` trainer + ``DeltaParameterServer``)."""

    def _discipline(self):
        return DownpourFold()


class ADAG(AsynchronousDistributedTrainer):
    """ADAG (reference ``ADAG`` trainer + ``ADAGParameterServer``): window-normalized
    accumulated-gradient commits."""

    def _discipline(self):
        return ADAGFold()


class DynSGD(AsynchronousDistributedTrainer):
    """DynSGD (reference ``DynSGD`` trainer + ``DynSGDParameterServer``):
    staleness-scaled folds."""

    def _discipline(self):
        return DynSGDFold()


class AEASGD(AsynchronousDistributedTrainer):
    """Elastic averaging (reference ``AEASGD``): exploration via persistent local
    replicas tethered to the center with elastic rate ``α = ρ·learning_rate``."""

    def __init__(self, *args, rho: float = 5.0, **kwargs):
        super().__init__(*args, **kwargs)
        self.rho = rho

    def _discipline(self):
        return AEASGDFold(alpha=self.rho * self.learning_rate)


class EAMSGD(AsynchronousDistributedTrainer):
    """EAMSGD (reference ``EAMSGD``): AEASGD with momentum local workers."""

    def __init__(self, *args, rho: float = 5.0, momentum: float = 0.9, **kwargs):
        super().__init__(*args, **kwargs)
        self.rho = rho
        self.momentum = momentum
        # Momentum lives in the *local* optimizer (reference EAMSGDWorker).
        if self.worker_optimizer in ("sgd", "momentum", "nesterov"):
            import optax

            self.worker_optimizer = optax.sgd(
                self.learning_rate, momentum=self.momentum,
                nesterov=self.worker_optimizer == "nesterov",
            )
        else:
            import warnings

            warnings.warn(
                "EAMSGD: momentum kwarg is embedded in the local optimizer; the "
                f"provided worker_optimizer={self.worker_optimizer!r} is used as-is "
                "and the momentum argument is ignored",
                stacklevel=2,
            )

    def _discipline(self):
        return EAMSGDFold(alpha=self.rho * self.learning_rate)


class ParallelTrainer(Trainer):
    """One-class trainer for the beyond-reference model-parallel engines —
    tensor/sequence/expert/pipeline parallelism with the reference's
    ``train(dataframe)`` UX and the full run harness (checkpoint/resume,
    metrics JSONL, ``rounds_per_program``) the data-parallel trainers get
    from :meth:`Trainer._execute`.

    ``parallel`` is the mesh layout, ``{axis: size}`` with at most one ``-1``
    (inferred): e.g. ``{'data': -1, 'model': 2}`` (dp×tp),
    ``{'data': 2, 'pipe': 4}`` (dp×pp), ``{'data': 2, 'expert': 4}``
    (dp×ep MoE), ``{'data': -1, 'seq': 2, 'model': 2}`` (dp×sp×tp).
    Put the most-communicating axis last — it lands on adjacent ICI links.

    ``strategy`` picks the engine; ``"auto"`` resolves from the mesh and
    model: a ``pipe`` axis → :class:`PipelineEngine` (GPipe microbatching),
    a ``seq`` axis / ring-sharded or flash-attention module →
    :class:`SPMDEngine` (shard_map dp×sp + GSPMD tp), anything else →
    :class:`GSPMDEngine` (pure sharding annotations; MoE all-to-alls and TP
    all-reduces are XLA-inserted).

    ``batch_size`` is the **global** per-step batch (the mesh is one logical
    worker), unlike the data-parallel trainers' per-worker batch; it must
    divide by the ``data`` axis (and ``num_microbatches`` for pipeline).
    """

    def __init__(
        self,
        model: Model,
        parallel: Optional[dict] = None,
        strategy: str = "auto",
        tp_rules=None,
        steps_per_program: int = 4,
        num_microbatches: int = 4,
        aux_loss_weight: float = 0.0,
        **kwargs,
    ):
        super().__init__(model, **kwargs)
        if self.grad_accum != 1:
            raise ValueError(
                "ParallelTrainer does not support grad_accum: the step "
                "engines have no accumulation path, so the kwarg would be "
                "silently ignored. Raise batch_size (the engines shard it "
                "over the data axis) or use a data-parallel trainer.")
        self.parallel = dict(parallel) if parallel else {"data": -1}
        if "data" not in self.parallel:
            self.parallel = {"data": 1, **self.parallel}
        if strategy not in ("auto", "spmd", "gspmd", "pipeline"):
            raise ValueError(
                f"strategy must be auto|spmd|gspmd|pipeline, got {strategy!r}")
        self.strategy = strategy
        self.tp_rules = tp_rules
        self.steps_per_program = int(steps_per_program)
        self.num_microbatches = int(num_microbatches)
        self.aux_loss_weight = float(aux_loss_weight)

    def _resolve_strategy(self) -> str:
        if self.strategy != "auto":
            return self.strategy
        if self.parallel.get("pipe", 1) != 1:
            return "pipeline"
        mod = self.model.module
        if (self.parallel.get("seq", 1) != 1
                or getattr(mod, "seq_axis", None) is not None
                or getattr(mod, "attn_impl", None) == "flash"):
            # flash/ring need a shard_map-bound mesh axis (GSPMDEngine
            # rejects them at construction by design).
            return "spmd"
        return "gspmd"

    def _default_rules(self):
        from distkeras_tpu.parallel.sharding import (
            MOE_RULES, TRANSFORMER_TP_RULES)

        if self.parallel.get("expert", 1) != 1:
            return MOE_RULES
        return TRANSFORMER_TP_RULES

    def _build_engine(self):
        from distkeras_tpu.parallel.runner import WindowedStepEngine
        from distkeras_tpu.runtime.mesh import SEQ_AXIS, hybrid_mesh

        strat = self._resolve_strategy()
        layout = dict(self.parallel)
        if strat == "spmd":
            # SPMDEngine always shard_maps over (data, seq); a dp×tp request
            # routed here (flash/ring models) still needs the axis present.
            layout.setdefault("seq", 1)
        mesh = hybrid_mesh(layout)
        model = self.model
        if (mesh.shape.get("seq", 1) > 1  # resolved size: -1 is inferred here
                and getattr(model.module, "seq_axis", None) is None):
            # Sequence sharding changes how the module computes positions and
            # attention; a module built without seq_axis would silently use
            # local positions. Rebind the same params under a seq-aware
            # module (dense/flash attention falls back to gather-SP; 'ring'
            # must be requested explicitly at model construction).
            if not hasattr(model.module, "seq_axis"):
                raise ValueError(
                    f"parallel={self.parallel} has a 'seq' axis but "
                    f"{type(model.module).__name__} is not sequence-"
                    "shardable (no seq_axis attribute)")
            model = model.with_module(
                model.module.clone(seq_axis=SEQ_AXIS))
        rules = self.tp_rules if self.tp_rules is not None else self._default_rules()
        common = dict(learning_rate=self.learning_rate, seed=self.seed,
                      compute_dtype=self.compute_dtype)
        if strat == "pipeline":
            from distkeras_tpu.parallel.pipeline_engine import PipelineEngine

            inner = PipelineEngine(
                model, self.worker_optimizer, self.loss, mesh,
                num_microbatches=self.num_microbatches, **common)
        elif strat == "spmd":
            from distkeras_tpu.parallel.spmd import SPMDEngine

            inner = SPMDEngine(
                model, self.worker_optimizer, self.loss, mesh, rules,
                aux_loss_weight=self.aux_loss_weight, **common)
        else:
            from distkeras_tpu.parallel.gspmd import GSPMDEngine

            inner = GSPMDEngine(
                model, self.worker_optimizer, self.loss, mesh, rules,
                aux_loss_weight=self.aux_loss_weight, **common)
        return WindowedStepEngine(inner, self.steps_per_program)

    def train(self, dataframe: DataFrame, shuffle: bool = False) -> Model:
        self.record_training_start()
        with telemetry.span("setup.build_engine"):
            engine = self._build_engine()
        # Multi-process sharded stores plan one "worker" per dp rank so each
        # host stages only its own ranks' rows (the engine merges the
        # rank-major stack back into the global batch — a sharding-preserving
        # reshape). Everything else uses the whole-mesh single-worker plan.
        plan_workers, per_worker_batch = 1, self.batch_size
        if (getattr(dataframe, "is_sharded", False)
                and jax.process_count() > 1):
            plan_workers = engine.dp_size
            if self.batch_size % plan_workers:
                raise ValueError(
                    f"batch_size={self.batch_size} must divide by the data-"
                    f"parallel size {plan_workers} for multi-process sharded "
                    "stores (rows are staged per dp rank)")
            per_worker_batch = self.batch_size // plan_workers
        with telemetry.span("setup.plan"):
            plan = make_batches(
                dataframe, self.features_col, self.label_col, per_worker_batch,
                num_workers=plan_workers, window=self.steps_per_program,
                num_epoch=self.num_epoch, shuffle=shuffle, seed=self.seed, transform=self.transform,
            )
        state = self._execute(engine, plan)
        self.record_training_stop()
        inner = engine.inner
        if hasattr(inner, "export_params"):  # pipeline: merge stage stacks
            params = inner.export_params(state)
        else:
            params = jax.device_get(state.params)
        return self.model.with_params(params)


#: The flagship-model spelling (VERDICT r2 next-round #3 names it this way).
TransformerTrainer = ParallelTrainer


class AveragingTrainer(DistributedTrainer):
    """Train independent replicas, average their weights (reference
    ``AveragingTrainer``): the fold is a single ``pmean`` at the end, here computed
    from the stacked local replicas."""

    communication_window = _config_prop("communication_window")

    def __init__(self, *args, communication_window: int = 8, **kwargs):
        super().__init__(*args, **kwargs)
        # steps per program only (no semantic effect: the fold is a no-op)
        self.config = self.config.replace(communication_window=communication_window)

    def train(self, dataframe: DataFrame, shuffle: bool = False) -> Model:
        self.record_training_start()
        mesh, m = self._mesh()
        # NOTE: replicas deliberately share one init (per_worker_init=False).
        # Post-hoc *weight* averaging is only meaningful when all replicas
        # descend within one loss basin; averaging independently-initialized
        # nets produces a point between basins (verified: accuracy collapses).
        # The reference likewise broadcast one serialized model to executors.
        with telemetry.span("setup.build_engine"):
            engine = AsyncEngine(
                self.model, self.worker_optimizer, self.loss, EnsembleFold(), mesh,
                window=self.communication_window, learning_rate=self.learning_rate,
                compute_dtype=self.compute_dtype, seed=self.seed,
                grad_accum=self.grad_accum, workers_per_chip=m,
            )
        with telemetry.span("setup.plan"):
            plan = make_batches(
                dataframe, self.features_col, self.label_col, self.batch_size,
                num_workers=engine.num_workers, window=self.communication_window,
                num_epoch=self.num_epoch, shuffle=shuffle, seed=self.seed, transform=self.transform,
            )
        state = self._execute(engine, plan)
        averaged = jax.tree.map(lambda a: jnp.mean(a, axis=0), state.locals_)
        self.record_training_stop()
        return self._finish_model(averaged, state,
                                  state_reduce=lambda a: jnp.mean(a, axis=0))


class EnsembleTrainer(DistributedTrainer):
    """Train N independent models, return all of them (reference
    ``EnsembleTrainer``)."""

    communication_window = _config_prop("communication_window")

    def __init__(self, *args, communication_window: int = 8, **kwargs):
        super().__init__(*args, **kwargs)
        self.config = self.config.replace(communication_window=communication_window)

    def train(self, dataframe: DataFrame, shuffle: bool = False) -> list[Model]:
        self.record_training_start()
        mesh, m = self._mesh()
        with telemetry.span("setup.build_engine"):
            engine = AsyncEngine(
                self.model, self.worker_optimizer, self.loss, EnsembleFold(), mesh,
                window=self.communication_window, learning_rate=self.learning_rate,
                compute_dtype=self.compute_dtype, seed=self.seed, per_worker_init=True,
                grad_accum=self.grad_accum, workers_per_chip=m,
            )
        with telemetry.span("setup.plan"):
            plan = make_batches(
                dataframe, self.features_col, self.label_col, self.batch_size,
                num_workers=engine.num_workers, window=self.communication_window,
                num_epoch=self.num_epoch, shuffle=shuffle, seed=self.seed, transform=self.transform,
            )
        state = self._execute(engine, plan)
        self.record_training_stop()
        stacked = jax.device_get(state.locals_)
        models = []
        for i in range(engine.num_workers):
            params_i = jax.tree.map(lambda a: a[i], stacked)
            models.append(self._finish_model(params_i, state, worker=i))
        return models
