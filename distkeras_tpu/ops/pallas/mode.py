"""Compiled or interpreted: the one decision every Pallas call site shares.

A kernel compiles through Mosaic only when the computation is headed for a
TPU; everywhere else (the CPU test mesh) the same kernel body runs under the
Pallas interpreter. That choice used to be re-derived at each call site from
``jax.default_backend() != "tpu"`` — which on a chip host whose TPU client
failed to come up means JAX fell back to the CPU and every kernel interpreted
without a word. Here the decision is made once, and an interpreted call is
never silent: it logs (once per kernel per process) and counts in
``pallas.interpreted_calls``, so CPU tests keep working and a chip run can
assert the counter is zero.
"""

from __future__ import annotations

import logging

import jax

_log = logging.getLogger(__name__)
_announced: set = set()


def compiles() -> bool:
    """True when a kernel traced now is headed for a TPU. Honors a
    ``jax.default_device`` override (e.g. CPU-pinned param init in a TPU
    process), else follows the default backend."""
    dev = jax.config.jax_default_device  # None | platform name | Device
    if dev is None:
        dev = jax.default_backend()
    return (dev if isinstance(dev, str) else dev.platform) == "tpu"


def interpret(kernel: str, requested: bool | None = None) -> bool:
    """Whether ``kernel``, traced now, runs under the Pallas interpreter.

    ``requested`` is the caller's explicit choice (tests pin it); ``None``
    decides from :func:`compiles`. Called at trace time, so the counter
    counts traced kernel calls, not device executions."""
    chosen = (not compiles()) if requested is None else bool(requested)
    if chosen:
        from distkeras_tpu import telemetry

        telemetry.counter("pallas.interpreted_calls").add(1)
        if kernel not in _announced:
            _announced.add(kernel)
            _log.warning(
                "Pallas kernel %r is running under the interpreter "
                "(default backend %r): correct, but not the compiled "
                "Mosaic path a TPU runs", kernel, jax.default_backend())
    return chosen
