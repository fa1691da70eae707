"""Device time per round by the program's own phases and kernels.

The round program names its phases with ``jax.named_scope`` (``dk_fwd_bwd``,
``dk_optimizer``, ``dk_fold`` ...) and its Pallas kernels by ``name=``
(``dk_flash_fwd`` ...). A compiled program's text keeps them per instruction
as ``metadata={op_name="jit(round_fn)/dk_local_steps/.../dk_fwd_bwd/..."}``:
the backward pass reads ``transpose(jvp(..))``, recomputation
``checkpoint/rematted_computation``. ``run.hlo`` holds that text and
``run.trace["ops0"]`` device 0's events by instruction name, so this reader
maps every instruction to a phase and sums the events' **self** time
(``trace_reduce.self_ns_by_name``) inside the bracket.

Phases, by the first that applies to an ``op_name``: ``remat``
(``rematted_computation``), ``backward`` (``transpose(`` within
``dk_fwd_bwd``), ``forward`` (the rest of ``dk_fwd_bwd``), ``optimizer``
(``dk_optimizer``), ``fold`` (``dk_fold``, ``dk_grad_sync``,
``dk_state_sync``), else ``other``. A fusion carries its own ``op_name``
(XLA names it after its root; ``;``-joined where it merged several) and
those of the instructions of the computation it calls. Names under two
different scopes (a weight-gradient convolution fused with the optimizer's
update) make it ``mixed``, and the ``[bench`` line says which pairs.
``other`` names beside a named phase do not: they are the compiler's own
converts and copies. Nor do forward, recomputed and backward names in one
fusion, all under ``dk_fwd_bwd``: XLA copies a forward cast or a cheap
activation into the backward fusion that consumes it, and the fusion runs in
the pass its own name says (else the latest pass among its names). An
instruction with no ``op_name`` at all (the copies, prefetching slices and
layout changes the compiler adds) takes the phase of the instructions that
use its result, if they agree. The phases partition the busy time. Kernels
are a cut across them: a ``dk_flash_fwd`` event counts in ``forward`` or
``remat`` and in the kernel's own total.

What a reader returns where there is nothing to read: ``None`` (which fails
the traced run, by name) when the program has ``dk_*`` scopes but not the
one this metric needs, because a refactor dropped it; ``0.0`` when the scope
is there and took no time; and ``0.0`` for every metric, said on a ``[bench``
line, when the program's text holds no ``dk_*`` scope at all: such a program
predates the scopes (the parent commit of the PR that added them), all of
its busy time is ``other``, and ``harness/result_line.py`` cannot print a
line that leaves a declared metric out.
"""

from __future__ import annotations

import json
import os
import re

from benchmarks.harness.trace_reduce import self_ns_by_name

PHASES = ("forward", "backward", "remat", "optimizer", "fold", "mixed",
          "other")
FOLD_SCOPES = ("dk_fold", "dk_grad_sync", "dk_state_sync")
#: the passes of ``dk_fwd_bwd``, latest first: what a fusion without a name
#: of its own runs in when it holds ops of several.
PASSES = ("remat", "backward", "forward")
#: the scopes of which one must be in the program for a phase's metric to read.
PHASE_NEEDS = {"forward": ("dk_fwd_bwd",), "backward": ("dk_fwd_bwd",),
               "remat": ("dk_fwd_bwd",), "optimizer": ("dk_optimizer",),
               "fold": FOLD_SCOPES, "mixed": ("dk_fwd_bwd",),
               "other": ("dk_fwd_bwd",)}

_KERNEL = re.compile(r"^dk_(?:flash|groupnorm|lstm|fold)_\w+$")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_FUSION_CALLS = re.compile(r" fusion\(.*calls=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_CONTROL = re.compile(r" (?:while|conditional|call)\(")


def label(op_name: str) -> tuple:
    """``(phase, kernel or None, dk scopes)`` of one ``op_name``."""
    parts = op_name.split("/")
    scopes = {p for p in parts if p.startswith("dk_")}
    kernel = next((p for p in parts if _KERNEL.match(p)), None)
    if "rematted_computation" in parts:
        phase = "remat"
    elif "dk_fwd_bwd" in scopes:
        phase = "backward" if "transpose(" in op_name else "forward"
    elif "dk_optimizer" in scopes:
        phase = "optimizer"
    elif scopes.intersection(FOLD_SCOPES):
        phase = "fold"
    else:
        phase = "other"
    return phase, kernel, frozenset(scopes)


def classify(hlo: str) -> tuple:
    """``({instruction: (phase, kernel or None)}, dk scopes of the program,
    control-flow instructions)`` from a compiled program's text; a mixed
    phase reads ``mixed:a+b``."""
    labels: dict = {}    # op_name -> label(op_name)
    own: dict = {}       # instruction -> [op_name, ...]
    calls: dict = {}     # fusion instruction -> called computation
    members: dict = {}   # computation -> [instruction, ...]
    users: dict = {}     # instruction -> [instruction that takes it, ...]
    control: set = set()  # while, conditional, call: their bodies' ops nest
    computation = None
    for line in hlo.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                computation = c.group(1)
            continue
        name = m.group(1)
        members.setdefault(computation, []).append(name)
        for operand in _OPERAND.findall(line, m.end()):
            users.setdefault(operand, []).append(name)
        n = _OP_NAME.search(line)
        own[name] = [p for p in n.group(1).split(";") if p] if n else []
        for op_name in own[name]:
            if op_name not in labels:
                labels[op_name] = label(op_name)
        f = _FUSION_CALLS.search(line)
        if f is not None:
            calls[name] = f.group(1)
        elif _CONTROL.search(line, m.end()):
            control.add(name)

    inside: dict = {}    # computation -> ({phase, ...}, {kernel, ...})

    def of_instruction(name):
        phases = {labels[n][0] for n in own[name]}
        kernels = {labels[n][1] for n in own[name]}
        if name in calls:
            p, k = of_computation(calls[name])
            phases, kernels = phases | p, kernels | k
        return phases, kernels

    def of_computation(comp):
        if comp not in inside:
            phases, kernels = set(), set()
            for name in members.get(comp, ()):
                p, k = of_instruction(name)
                phases, kernels = phases | p, kernels | k
            inside[comp] = (phases, kernels)
        return inside[comp]

    by_use: dict = {}    # unnamed instruction -> phases of its users

    def of_users(name):
        if name not in by_use:
            phases = set()
            for user in users.get(name, ()):
                if user in own:
                    phases |= (of_instruction(user)[0] if own[user]
                               else of_users(user))
            by_use[name] = phases - {"other"}
        return by_use[name]

    out = {}
    for name in own:
        phases, kernels = of_instruction(name)
        phases.discard("other")
        kernels.discard(None)
        if not own[name] and not phases and len(of_users(name)) == 1:
            phases = set(of_users(name))
        if len(phases) > 1 and phases <= set(PASSES):
            named = [labels[n][0] for n in own[name]
                     if labels[n][0] in PASSES]
            phases = {named[0] if named else
                      next(p for p in PASSES if p in phases)}
        phase = ("other" if not phases else phases.pop() if len(phases) == 1
                 else "mixed:" + "+".join(sorted(phases)))
        out[name] = (phase, kernels.pop() if len(kernels) == 1 else None)
    scopes = set().union(*(lab[2] for lab in labels.values())) \
        if labels else set()
    return out, scopes, control


def reduce(hlo: str, events, lo, hi) -> dict:
    """Self time of ``events`` (``(start_ns, dur_ns, instruction)``) inside
    ``[lo, hi]`` by phase and by kernel. ``sum(phases.values())`` is the
    events' busy time; an event of no instruction of ``hlo`` is ``other``."""
    classes, scopes, control = classify(hlo)
    phases = dict.fromkeys(PHASES, 0.0)
    kernels: dict = {}
    mixed: dict = {}
    control_ns = 0.0
    for name, ns in self_ns_by_name(events, lo, hi).items():
        phase, kernel = classes.get(name, ("other", None))
        if name in control:
            control_ns += ns
        if phase.startswith("mixed:"):
            mixed[phase[6:]] = mixed.get(phase[6:], 0.0) + ns
            phase = "mixed"
        phases[phase] += ns
        if kernel is not None:
            kernels[kernel] = kernels.get(kernel, 0.0) + ns
    return {"phases": phases, "kernels": kernels, "mixed": mixed,
            "scopes": scopes, "control_ns": control_ns,
            "backward_seen": any(p == "backward" for p, _ in classes.values())}


def flash_attention_floor(tokens: int, seq_len: int, d_model: int,
                          layers: int, peak: dict,
                          bytes_per_element: int = 2) -> dict:
    """The least time one chip could take for causal attention's forward and
    backward passes over ``tokens`` tokens in sequences of ``seq_len``.

    Operations: the two products ``QK^T`` and ``PV`` are ``2 * L * d`` each
    per token and layer in full and half of that under the causal mask, so
    ``2 * L * d`` forward and twice that backward: ``6 * L * d`` (what
    ``families/transformer_lm.train_flops_per_unit`` counts; a recomputed
    forward is not counted). Bytes: ``q``, ``k``, ``v``, ``o`` and their four
    gradients, each ``tokens * d`` elements per layer, moved once. The floor
    is the larger of operations over the bf16 peak and bytes over the HBM
    peak, and ``bound`` says which."""
    flops = 6.0 * seq_len * d_model * layers * tokens
    moved = 8.0 * tokens * d_model * bytes_per_element * layers
    by_flops = flops / peak["bf16_flops_per_s"]
    by_bytes = moved / peak["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": moved,
            "seconds": max(by_flops, by_bytes),
            "bound": "flops" if by_flops >= by_bytes else "bytes"}


def _say(msg: str) -> None:
    print(f"[bench] trace_scope: {msg}", flush=True)


def _reduced(run) -> dict:
    """The reduction of this run's trace, made once and kept on ``run`` (a
    cell declares a dozen metrics of it), and said on one ``[bench`` line."""
    if not hasattr(run, "trace_scope"):
        t = run.trace
        got = reduce(run.hlo, t["ops0"], t["lo"], t["hi"])
        busy = sum(got["phases"].values()) or 1.0
        per_round = 1e-6 / t["rounds"]
        _say("ms/round by phase: " + ", ".join(
            f"{p} {got['phases'][p] * per_round:.3f}" for p in PHASES)
            + f"; sum {busy * per_round:.3f}; mixed+other "
            f"{(got['phases']['mixed'] + got['phases']['other']) / busy:.2%}"
            " of busy; mixed: " + (", ".join(
                f"{k} {ns * per_round:.3f}"
                for k, ns in sorted(got["mixed"].items())) or "none")
            + "; by kernel: " + (", ".join(
                f"{k} {ns * per_round:.3f}"
                for k, ns in sorted(got["kernels"].items())) or "none"))
        if got["control_ns"] > 0.01 * busy:
            # A loop's own time is what its body's events leave uncovered:
            # microseconds in a whole trace. Seen once in five traced GPT-2
            # runs (PR 26): the profiler kept 81 % of the op events.
            _say(f"WARNING: {got['control_ns'] * per_round:.3f} ms/round is "
                 "the own time of while/conditional/call instructions, in "
                 "`other`: the trace lacks events of their bodies, and every "
                 "phase and kernel above reads short by its part of that")
        if not got["scopes"]:
            _say("the program's text holds no dk_* scope: it predates them, "
                 "so every phase and kernel reads 0 and all of it is `other`")
        run.trace_scope = got
    return run.trace_scope


def read(run, phase: str | None = None, kernels=None, floor=None):
    """ms/round of one ``phase``, or of the events under the ``kernels``
    scopes; with ``floor`` (``{"config": <configs/ file>}``), the share in
    percent that causal attention's least time is of the kernels' time."""
    t = run.trace
    if not t or not t["rounds"]:
        return None
    got = _reduced(run)
    if not got["scopes"]:
        return 0.0
    per_round = 1e-6 / t["rounds"]
    if phase is not None:
        if not got["scopes"].intersection(PHASE_NEEDS[phase]) \
                or (phase == "backward" and not got["backward_seen"]):
            return None
        return got["phases"][phase] * per_round
    if not set(kernels) <= got["scopes"]:
        return None
    ms = sum(got["kernels"].get(k, 0.0) for k in kernels) * per_round
    if floor is None:
        return ms
    if not ms:
        _say("the kernels are in the program and the trace holds no event of "
             "theirs: the share reads 0")
        return 0.0
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "configs", f"{floor['config']}.json"),
              encoding="utf-8") as f:
        config = json.load(f)
    least = flash_attention_floor(
        run.units_per_round // run.chips, config["seq_len"],
        config["module"]["d_model"], config["module"]["num_layers"], run.peak)
    _say(f"causal attention's floor a round: {least['seconds'] * 1e3:.3f} ms, "
         f"bound by {least['bound']} ({least['flops']:.4g} operations, "
         f"{least['bytes']:.4g} bytes), against {ms:.3f} ms of kernels")
    return least["seconds"] * 1e3 / ms * 100.0
