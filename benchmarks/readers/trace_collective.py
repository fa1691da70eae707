"""Collective time per round on device 0: the merged union of the trace
events of the round program's instructions with the given HLO opcode, over
the bracket's rounds.

The instructions are found by opcode in the compiled program's text, not by
name in the trace: XLA names an all-reduce after the JAX primitive that made
it (``psum_invariant.7``) unless a pass rebuilt it (``all-reduce-start.3``),
and a trace event carries the instruction's name. Both of the device's lines
are read: ``XLA Ops`` (a synchronous collective, and the ``-start`` and
``-done`` marks of an asynchronous one) and ``Async XLA Ops`` (its flight from
start to done). Whether compute hides the collective is ``fold.exposed_ms``,
not built.
"""

from __future__ import annotations

from benchmarks.harness.trace_reduce import busy_ns, instruction_names


def read(run, opcode: str):
    t = run.trace
    if not t or not t["rounds"]:
        return None
    names = instruction_names(run.hlo, opcode)
    matching = [ev for ev in t["ops0"] + t["async0"]
                if ev[2] in names]
    if not matching:
        return None
    return busy_ns(matching, t["lo"], t["hi"]) / 1e9 / t["rounds"] * 1e3
