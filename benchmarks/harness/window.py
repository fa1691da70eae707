"""The run's clock: the trainer's ``on_round`` hook.

The program has no stop hook for time-bounded training and the benchmark may
not add one, so the hook keeps the ticks itself and, when the measured segment
is over, raises :class:`WindowClosed` (a ``BaseException``, so no ``except
Exception`` in the program swallows it). ``run.py`` catches it around
``train()``; ``run_per_round`` closes its feeder in ``finally``.

A **tick** is the host time at which a round's loss has been fetched. The
fetch lags by one round: during ``on_round(r)`` the hook fetches the loss of
round ``r - 1``, when round ``r`` is already in the device's queue, so the
benchmark itself never drains the queue.

Phases, each ending on a tick:

1. warm-up: the first round (which compiles) and two more;
2. traced runs only: the profiler runs for whole rounds, about 3 s and at
   least 4 rounds (at most 8 s), inside a ``TraceAnnotation``. The round in
   flight as the trace starts and the one in flight as it stops are cut, so
   four ticks leave a bracket of three whole rounds on the device. Then
   ``stop_trace`` (seconds, during which the host dispatches nothing) and two
   rounds to refill the queue;
3. the measured segment: ``seconds`` long in an untraced run. In a traced run
   it is what is left of ``seconds`` since the trace began, and never fewer
   than ``MIN_SEGMENT_ROUNDS`` rounds. Every host-clock metric is taken over
   this segment: all of its rounds, all of its time.
"""

from __future__ import annotations

import math
import time

import numpy as np

WARMUP_ROUNDS = 3
TRACE_MIN_S, TRACE_MAX_S, TRACE_MIN_ROUNDS = 3.0, 8.0, 4
SETTLE_ROUNDS = 2
MIN_SEGMENT_ROUNDS = 5


class WindowClosed(BaseException):
    """Raised by the hook when the measured segment is over."""


class Window:
    def __init__(self, seconds: float, *, t_start: float, trace_dir=None,
                 on_open=None, on_close=None, clock=time.perf_counter):
        self.seconds = float(seconds)
        self.t_start = t_start          # process start, on ``clock``
        self.t_train = None             # set by run.py as train() is called
        self.trace_dir = trace_dir      # None: untraced
        self.on_open, self.on_close = on_open, on_close
        self.clock = clock
        self.ticks: list = []           # ticks[i]: round i's loss fetched
        self.losses: list = []
        self._pending = None
        self.phase = "warmup"
        self.segment = None             # (first tick index, last tick index)
        self._seg_open = None
        self._settle_until = None
        self._trace_open = None         # tick index at which the trace began
        self.trace_rounds = 0           # rounds inside the annotation
        self.trace_cost_s = {}
        self._annotation = None

    # -- the hook ----------------------------------------------------------
    def __call__(self, r, loss):
        if self._pending is not None:
            self.losses.append(float(np.mean(np.asarray(self._pending))))
            self.ticks.append(self.clock())
            self._advance()
        self._pending = loss

    def _advance(self):
        i = len(self.ticks) - 1
        now = self.ticks[i]
        if self.phase == "warmup" and i + 1 >= WARMUP_ROUNDS:
            if self.trace_dir is None:
                self._open_segment(i)
            else:
                self._start_trace(i)
        elif self.phase == "trace":
            took, n = now - self.ticks[self._trace_open], i - self._trace_open
            if (took >= TRACE_MIN_S and n >= TRACE_MIN_ROUNDS) \
                    or (took >= TRACE_MAX_S and n >= 1):
                self._stop_trace(n)
                self._settle_until = i + SETTLE_ROUNDS
                self.phase = "settle"
        elif self.phase == "settle" and i >= self._settle_until:
            self._open_segment(i)
        elif self.phase == "segment":
            n = i - self._seg_open
            if now >= self._seg_end and n >= self._seg_min_rounds:
                self.segment = (self._seg_open, i)
                if self.on_close is not None:
                    self.on_close()
                raise WindowClosed

    def _open_segment(self, i):
        self.phase, self._seg_open = "segment", i
        if self._trace_open is None:
            self._seg_end, self._seg_min_rounds = self.ticks[i] + self.seconds, 1
        else:
            self._seg_end = self.ticks[self._trace_open] + self.seconds
            self._seg_min_rounds = MIN_SEGMENT_ROUNDS
        if self.on_open is not None:
            self.on_open()

    def _start_trace(self, i):
        import jax

        from benchmarks.harness.trace_reduce import (BRACKET_ANNOTATION,
                                                     SELECTORS)

        options = jax.profiler.ProfileOptions()
        # The device's ops are all the reduction reads. The Python tracer and
        # the runtime's host events slow the host they trace (trace_reduce.py
        # says by how much), so the platform's entry says how little to keep.
        options.python_tracer_level = 0
        options.host_tracer_level = SELECTORS[
            jax.devices()[0].platform]["host_tracer_level"]
        t0 = self.clock()
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self._annotation = jax.profiler.TraceAnnotation(BRACKET_ANNOTATION)
        self._annotation.__enter__()
        self.trace_cost_s["start"] = self.clock() - t0
        self.phase, self._trace_open = "trace", i

    def _stop_trace(self, rounds):
        import jax

        self._annotation.__exit__(None, None, None)
        self._annotation = None
        self.trace_rounds = rounds
        t0 = self.clock()
        jax.profiler.stop_trace()
        self.trace_cost_s["stop"] = self.clock() - t0

    def abort_trace(self):
        """Stop a profiler that a failing run left on."""
        if self.phase == "trace" and self._annotation is not None:
            self._stop_trace(0)

    # -- what the readers take ----------------------------------------------
    @property
    def segment_rounds(self) -> int:
        return self.segment[1] - self.segment[0]

    @property
    def segment_s(self) -> float:
        return self.ticks[self.segment[1]] - self.ticks[self.segment[0]]

    def round_times_s(self) -> list:
        i0, i1 = self.segment
        return [b - a for a, b in zip(self.ticks[i0:i1], self.ticks[i0 + 1:i1 + 1])]

    def segment_losses(self) -> list:
        i0, i1 = self.segment
        return self.losses[i0 + 1:i1 + 1]

    def failed_rounds(self) -> int:
        return sum(1 for x in self.segment_losses() if not math.isfinite(x))
