"""Where compiled programs persist between processes.

JAX's persistent compilation cache keys each entry on the cache directory's
path among other things, so a directory that moves (a temp name, a pid, a
timestamp) never hits. The rule here: the operator's choice wins — JAX fills
``jax_compilation_cache_dir`` from ``JAX_COMPILATION_CACHE_DIR`` at import —
and otherwise the cache sits at one fixed, git-ignored place beside the
package. Entry scripts (``chip_smoke.py``, ``benchmarks/run.py``,
``accuracy_gate.py``) call :func:`ensure_compile_cache` before their first
compile; importing the package never does.

Importing the package does call :func:`watch_compiles`, which puts what JAX
itself reports of every compilation (tracing, lowering, the backend's compile
or the cache's load) into the telemetry registry and onto its timeline, so an
operator sees which round recompiled.
"""

from __future__ import annotations

import os

import jax

#: ``<checkout>/.jax_cache`` — derived from this file's own location, so two
#: processes started from different working directories share it.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def ensure_compile_cache() -> str:
    """Make sure a persistent compile cache is configured; return its path.
    A directory that is already set (``JAX_COMPILATION_CACHE_DIR``, or an
    earlier ``jax.config.update``) is left alone."""
    path = jax.config.jax_compilation_cache_dir
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path


#: JAX's duration events -> the span each is recorded as. A cache hit is the
#: backend's event too (it wraps the lookup), so ``compile.backend`` takes
#: that event less the load reported just before it on the same thread.
_COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    "/jax/core/compile/backend_compile_duration": "compile.backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "compile.cache_load",
}
_watching = False


def watch_compiles() -> None:
    """Register, once per process, the one ``jax.monitoring`` listener that
    records each compilation's phases as flat timeline spans
    (``compile.trace``, ``compile.lower``, ``compile.backend``,
    ``compile.cache_load``) and counts ``compile.programs`` and
    ``compile.cache_hits``. Touches no backend."""
    global _watching
    if _watching:
        return
    _watching = True
    import threading

    from distkeras_tpu import telemetry

    loaded = threading.local()  # seconds of cache load not yet taken off

    def on_duration(event: str, seconds: float, **_) -> None:
        span = _COMPILE_SPANS.get(event)
        if span is None:
            return
        tele = telemetry.get()
        if span == "compile.cache_load":
            loaded.seconds = seconds
            tele.counter("compile.cache_hits").add(1)
        elif span == "compile.backend":
            seconds = max(seconds - getattr(loaded, "seconds", 0.0), 0.0)
            loaded.seconds = 0.0
            tele.counter("compile.programs").add(1)
        tele.observe_span(span, seconds, nest=False)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
