"""Host-side per-round resilience hooks for the engine run loops.

Two layers of defense, split by cost:

* **On-device** (always on unless ``DKTPU_NAN_GUARD=0``): the round program
  itself checks ``isfinite`` over the replicated per-worker loss vector and,
  when any worker went non-finite, keeps the *previous* state — the poisoned
  round is skipped entirely, with zero host round-trips and one cheap
  ``where`` select per leaf. Lives in the engines' round bodies
  (``parallel/engine.py`` / ``parallel/sync.py``); this module only supplies
  the policy switch and the post-hoc accounting.

* **Host-side** (this module's :class:`RoundGuard`): fault injection
  (``crash@R`` / ``kill@R``) and the divergent-worker reset. The reset is
  opt-in (``divergence_reset=thr`` on the async trainers, or
  ``DKTPU_DIVERGENCE_RESET``) because it must fetch the loss every round —
  a fence the default path deliberately never pays, keeping the guards'
  no-fault overhead below run-to-run noise.
"""

from __future__ import annotations

import os
import signal
from typing import Optional

import numpy as np

from distkeras_tpu.resilience import faults
from distkeras_tpu.resilience.errors import InjectedFault
from distkeras_tpu.runtime import config


def nan_guard_enabled() -> bool:
    """Default for the engines' on-device NaN/Inf round skip."""
    return config.env_bool("DKTPU_NAN_GUARD")


class RoundGuard:
    """Per-run host-side guard, constructed by the engine run loops.

    Inactive (the common case: no faults configured, no divergence reset)
    every method is a branch-and-return — the run loop pays nothing.
    """

    def __init__(self, engine):
        self.engine = engine
        self.plan = faults.active_plan()
        thr = getattr(engine, "divergence_reset", None)
        if thr is None:
            thr = config.env_float("DKTPU_DIVERGENCE_RESET")
        disc = getattr(engine, "discipline", None)
        self.divergence_reset: Optional[float] = (
            float(thr)
            if thr is not None and disc is not None
            and getattr(disc, "communicates", False)
            and hasattr(engine, "reset_workers")
            else None)
        self._inject = self.plan is not None and bool(self.plan)

    def pre_round(self, round_idx: int) -> None:
        """Crash/kill injection, fired before the round is dispatched."""
        if not self._inject:
            return
        if self.plan.kill(round_idx):
            # The mid-run host kill: unmaskable, no cleanup — exactly what a
            # preempted/OOM-killed host looks like to Job.supervise.
            os.kill(os.getpid(), signal.SIGKILL)
        if self.plan.crash(round_idx):
            raise InjectedFault(
                f"crash injected at round {round_idx} (DKTPU_FAULTS)")

    def post_round(self, round_idx: int, loss, state,
                   host_loss=None):
        """Divergent-worker reset: when a worker's loss strays more than
        ``divergence_reset`` from the (finite) worker mean — or went
        non-finite while the round as a whole survived — re-adopt the
        center for that worker (the reference's rejoining-worker PS pull).
        Returns the (possibly replaced) state."""
        if self.divergence_reset is None:
            return state
        from distkeras_tpu import telemetry

        with telemetry.span("guard", id=round_idx):
            return self._reset_divergent(round_idx, loss, state, host_loss)

    def _reset_divergent(self, round_idx: int, loss, state, host_loss):
        host = np.asarray(host_loss if host_loss is not None
                          else __import__("jax").device_get(loss))
        host = host.reshape(-1).astype(np.float64)
        if host.size < 2:
            return state
        finite = host[np.isfinite(host)]
        if finite.size == 0:
            return state  # whole round poisoned — the NaN skip handles it
        mask = (~np.isfinite(host)
                | (np.abs(host - finite.mean()) > self.divergence_reset))
        if not mask.any() or mask.all():
            # All-divergent has no healthy center estimate to re-adopt
            # against; leave it to the NaN skip / supervisor.
            return state
        from distkeras_tpu import telemetry

        telemetry.counter("resilience.worker_resets").add(int(mask.sum()))
        telemetry.event("worker_reset", {
            "round": round_idx,
            "workers": [int(i) for i in np.flatnonzero(mask)]})
        return self.engine.reset_workers(state, mask)


def note_losses(losses) -> None:
    """Post-hoc accounting over a run's host loss history: count rounds any
    worker reported a non-finite loss (the rounds the on-device guard
    skipped) into ``resilience.nonfinite_rounds``. Runs once per run on the
    already-fetched array — no extra fences."""
    arr = np.asarray(losses, dtype=np.float64)
    if arr.size == 0:
        return
    rows = arr.reshape(arr.shape[0], -1)
    bad = int((~np.isfinite(rows)).any(axis=1).sum())
    if bad:
        from distkeras_tpu import telemetry

        telemetry.counter("resilience.nonfinite_rounds").add(bad)
