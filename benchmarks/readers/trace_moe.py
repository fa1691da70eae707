"""Device time of a routed expert layer's parts, and two shares of a roofline:
the grouped expert products against what the round **really routed here**, and
the flash kernels of a model that mixes windowed and full grouped-query
layers.

The program names the parts with ``jax.named_scope`` inside ``dk_fwd_bwd``:
``dk_moe_route`` (router, top-k, sort, gather into the sorted buffer),
``dk_moe_experts`` (the three grouped products and the gate between them),
``dk_moe_combine`` (back to token order, weighted sum). As in
``trace_scope``, an instruction belongs to the scopes of its own ``op_name``,
or, a fusion without one of ours, to those of the instructions it fused; an
event counts its **self** time, forward, recomputed and backward alike. An
instruction under two of the three counts once, for the first of
``PRIORITY``. The compiler expands ``jax.lax.ragged_dot`` on a TPU into
Mosaic calls whose ``op_name`` it writes itself (``ragged-dot-*``, no scope
of ours): those count as ``dk_moe_experts``, where the program makes them.

What the round routed comes from the program's own count: the expert layer
adds its assignments to a collection that leaves the round program with the
loss, and the program's ``publish_round_counters`` writes one ``moe.round``
telemetry event a round (``round``, ``assignments_held``, ``steps``,
``layers``). The share's floor uses the events of the traced rounds, not an
expectation.

Where there is nothing to read, ``read`` returns ``None`` (which fails the
traced run, by name) and says why on a ``[bench`` line: no trace, a program
without these scopes, no ``moe.round`` event for the traced rounds. As in
``trace_scope``, a scope that is in the program and took no time reads
``0.0`` (a rehearsal's CPU trace holds next to no event).
"""

from __future__ import annotations

import json
import os

from benchmarks.harness.trace_reduce import self_ns_by_name
from benchmarks.readers import trace_scope

PRIORITY = ("dk_moe_experts", "dk_moe_combine", "dk_moe_route")
FLASH = ("dk_flash_fwd", "dk_flash_dq", "dk_flash_dkv")
#: the compiler's own names for what it makes of ``jax.lax.ragged_dot``
RAGGED = "ragged-dot"


def _say(msg: str) -> None:
    print(f"[bench] trace_moe: {msg}", flush=True)


def scopes_by_instruction(hlo: str) -> dict:
    """``{instruction: frozenset of dk_* scopes}`` of a compiled program's
    text: those of its own ``op_name`` if it names any, else those of the
    instructions of the computation a fusion calls."""
    own, calls, members = {}, {}, {}
    computation = None
    for line in hlo.splitlines():
        m = trace_scope._INSTRUCTION.match(line)
        if m is None:
            c = trace_scope._COMPUTATION.match(line)
            if c is not None:
                computation = c.group(1)
            continue
        name = m.group(1)
        members.setdefault(computation, []).append(name)
        n = trace_scope._OP_NAME.search(line)
        own[name] = frozenset(
            part for op_name in (n.group(1).split(";") if n else ())
            for part in op_name.split("/") if part.startswith("dk_"))
        f = trace_scope._FUSION_CALLS.search(line)
        if f is not None:
            calls[name] = f.group(1)
    out = {}
    for name, scopes in own.items():
        if not scopes & set(PRIORITY) and name in calls:
            scopes = scopes.union(*(own[n] for n in members.get(calls[name], ())))
        if name.startswith(RAGGED):
            scopes = scopes | {"dk_moe_experts"}
        out[name] = scopes
    return out


def part_of(scopes) -> str | None:
    return next((p for p in PRIORITY if p in scopes), None)


def reduce(hlo: str, events, lo, hi) -> dict:
    """Self time in ns of ``events`` inside ``[lo, hi]`` by part of the expert
    layer: ``{"parts": {scope: ns}, "stems": {scope: {instruction name less
    its number: ns}}, "scopes": scopes of the program}``."""
    by = scopes_by_instruction(hlo)
    parts = dict.fromkeys(PRIORITY, 0.0)
    stems: dict = {p: {} for p in PRIORITY}  # part -> {instruction stem: ns}
    for name, ns in self_ns_by_name(events, lo, hi).items():
        part = part_of(by.get(name, ()))
        if part is not None:
            parts[part] += ns
            stem = name.rsplit(".", 1)[0] if name.rsplit(".", 1)[-1].isdigit() \
                else name
            stems[part][stem] = stems[part].get(stem, 0.0) + ns
    return {"parts": parts, "stems": stems,
            "scopes": frozenset().union(*by.values()) if by else frozenset()}


def experts_floor(assignments: float, layer_steps: float, held: int,
                  d_model: int, d_expert: int, peak: dict,
                  bytes_per_element: int = 2) -> dict:
    """The least time one chip could take for the gated experts' products,
    forward and backward, over ``assignments`` routed rows in ``layer_steps``
    (layers x steps) passes. Operations: three products of ``2 * d_model *
    d_expert`` a row forward and twice that backward, ``18 * d_model *
    d_expert`` a row. Bytes: the held experts' three matrices once a layer
    and step, and each gathered row of ``d_model`` once. The larger of
    operations over the bf16 peak and bytes over the HBM peak."""
    flops = 18.0 * d_model * d_expert * assignments
    moved = bytes_per_element * (layer_steps * held * 3.0 * d_model * d_expert
                                 + assignments * d_model)
    by_flops = flops / peak["bf16_flops_per_s"]
    by_bytes = moved / peak["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": moved,
            "seconds": max(by_flops, by_bytes),
            "bound": "flops" if by_flops >= by_bytes else "bytes"}


def flash_window_floor(tokens: int, keys_seen: list, num_heads: int,
                       num_kv_heads: int, head_dim: int, peak: dict,
                       bytes_per_element: int = 2) -> dict:
    """``trace_scope.flash_attention_floor``'s reasoning for grouped-query
    layers of which some are windowed: ``keys_seen`` holds a layer's mean
    number of keys a query sees (``families/smallthinker.mean_keys_seen``).
    Operations: ``QK^T`` and ``PV`` are ``2 * k * heads * head_dim`` each a
    token forward, twice that backward: ``12 * k * heads * head_dim`` a token
    and layer. Bytes: ``q, o, dq, do`` at ``num_heads`` and ``k, v, dk, dv``
    at ``num_kv_heads`` heads of ``head_dim``, each moved once."""
    width = num_heads * head_dim
    flops = sum(12.0 * k * width for k in keys_seen) * tokens
    moved = (4.0 * width + 4.0 * num_kv_heads * head_dim) \
        * bytes_per_element * tokens * len(keys_seen)
    by_flops = flops / peak["bf16_flops_per_s"]
    by_bytes = moved / peak["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": moved,
            "seconds": max(by_flops, by_bytes),
            "bound": "flops" if by_flops >= by_bytes else "bytes"}


def traced_round_events(run) -> list:
    """The program's ``moe.round`` events of the rounds the trace's bracket
    holds. The profiler starts on the tick of round ``i`` with round ``i + 1``
    in flight, which is cut; the bracket's whole rounds follow it."""
    from distkeras_tpu import telemetry

    first = getattr(run.window, "_trace_open", None)
    if first is None:
        return []
    rounds = range(first + 2, first + 2 + run.trace["rounds"])
    return [e for e in telemetry.get().events()
            if e.get("kind") == "moe.round" and e.get("round") in rounds]


def _config(name: str) -> dict:
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "configs", f"{name}.json"),
              encoding="utf-8") as f:
        return json.load(f)


def _reduced(run) -> dict:
    if not hasattr(run, "trace_moe"):
        t = run.trace
        got = reduce(run.hlo, t["ops0"], t["lo"], t["hi"])
        per_round = 1e-6 / t["rounds"]
        _say("ms/round by part (its longest instructions): " + "; ".join(
            f"{p} {got['parts'][p] * per_round:.3f} (" + ", ".join(
                f"{stem} {ns * per_round:.3f}" for stem, ns in sorted(
                    got["stems"][p].items(), key=lambda kv: -kv[1])[:4]) + ")"
            for p in PRIORITY))
        run.trace_moe = got
    return run.trace_moe


def read(run, scopes=None, floor=None, config=None):
    """ms/round under ``scopes``; or, with ``floor`` (``"experts"`` or
    ``"flash_window"``) and the ``config`` whose shapes it counts from, the
    share in percent that the floor is of the measured time."""
    t = run.trace
    if not t or not t["rounds"]:
        return None
    per_round = 1e-6 / t["rounds"]
    got = _reduced(run)
    if floor == "flash_window":
        if not set(FLASH) <= got["scopes"]:
            _say("the program has no flash kernel's scope")
            return None
        ms = trace_scope.read(run, kernels=list(FLASH))
        if not ms:
            _say("the flash kernels are in the program and the trace holds "
                 "no event of theirs: the share reads 0")
            return 0.0
        from benchmarks.families.smallthinker import mean_keys_seen

        module = _config(config)["module"]
        seq_len = _config(config)["seq_len"]
        keys = [mean_keys_seen(seq_len, module["window"] if w else None)
                for w in module["window_layout"][:module["num_layers"]]]
        least = flash_window_floor(
            run.units_per_round // run.chips, keys, module["num_heads"],
            module["num_kv_heads"], module["head_dim"], run.peak)
        from distkeras_tpu import telemetry

        _say("pallas.flash.visited_share, as the last flash call was traced: "
             f"{telemetry.gauge('pallas.flash.visited_share').value}")
        _say(f"windowed and full grouped attention's floor a round: "
             f"{least['seconds'] * 1e3:.3f} ms, bound by {least['bound']} "
             f"({least['flops']:.4g} operations, {least['bytes']:.4g} bytes; "
             f"keys seen a layer {keys}), against {ms:.3f} ms of kernels")
        return least["seconds"] * 1e3 / ms * 100.0
    wanted = ["dk_moe_experts"] if floor else list(scopes)
    if not set(wanted) <= got["scopes"]:
        _say(f"the program has no scope {sorted(set(wanted) - got['scopes'])}")
        return None
    ms = sum(got["parts"][s] for s in wanted) * per_round
    if floor is None:
        return ms
    events = traced_round_events(run)
    if not events:
        _say("the program wrote no moe.round event for the traced rounds: "
             "no share")
        return None
    if not ms:
        _say("the experts' scope is in the program and the trace holds no "
             "event of it: the share reads 0")
        return 0.0
    module = _config(config)["module"]
    assignments = sum(e["assignments_held"] for e in events) / len(events)
    layer_steps = sum(e["steps"] * e["layers"] for e in events) / len(events)
    least = experts_floor(assignments, layer_steps, module["experts_held"][1],
                          module["d_model"], module["d_expert"], run.peak)
    _say(f"the program counted {assignments:.1f} assignments to held experts "
         f"a round over rounds {[e['round'] for e in events]} "
         f"({layer_steps:g} layer-steps; load max/mean "
         f"{max(e['load_max_over_mean'] for e in events):.3f}, tokens "
         f"without a held expert "
         f"{events[-1]['tokens_without_held_expert_share']:.4f}); the "
         f"experts' floor a round: {least['seconds'] * 1e3:.3f} ms, bound by "
         f"{least['bound']} ({least['flops']:.4g} operations, "
         f"{least['bytes']:.4g} bytes), against {ms:.3f} ms")
    return least["seconds"] * 1e3 / ms * 100.0
