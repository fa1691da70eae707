"""Device time of the Mamba-2 state-space chain, its share of a floor of the
recurrence's own work, and the plain (not gated) experts' share of theirs
(``families/nemotron_h.py``).

The program names the chain with ``jax.named_scope`` inside ``dk_fwd_bwd``,
forward, recomputed and backward alike: ``dk_ssd_conv`` (the tap sums over
``x | B | C``, the convolution's bias and SiLU), ``dk_ssd`` (``dt``, the
decay, ``dt * x``, the in-chunk kernel pair ``dk_ssd_chunk_*``, the scan pair
``dk_kda_scan_*`` with no ``W``, the ``D`` skip, the gated norm). An
instruction counts for the scopes as ``trace_kda`` counts
(:func:`trace_kda.classify`, :func:`trace_kda.reduce`, imported, not copied):
its own ``op_name`` under one of them, or a fusion that fused such an
instruction and holds no matmul; a projection's product with some of the
chain fused into it is the matmul's, and a ``[bench`` line says how much that
was. An event counts its **self** time.

**The floors are of the work, whatever implements it**: :func:`ssd_floor`
(the recurrence between the projections: ``x``, ``y`` and their cotangents at
the held heads, ``B``, ``C`` and theirs at the held groups, ``dt`` and its,
each moved once) and :func:`plain_experts_floor` (two products an expert,
where ``trace_moe.experts_floor`` counts a gated expert's three). The expert
layer's time is ``trace_moe``'s reduction of ``dk_moe_experts``, and what the
round routed here is the program's ``moe.round`` events of the traced rounds
(``trace_moe.traced_round_events``).

Where there is nothing to read, ``read`` returns ``None`` and says why on a
``[bench`` line: no trace, a program without the scopes (the parent of the
PR that added them), no ``moe.round`` event of the traced rounds. A scope that
is in the program and took no time reads ``0.0`` (a rehearsal's CPU trace
holds next to no event).

The owner split and the shared expert's time, whose metrics' lists of cells
are pinned by ``test_trace_owner.py`` and ``test_kimi_linear_bench.py``, are
on this reader's ``[bench`` lines.
"""

from __future__ import annotations

from benchmarks.readers import trace_kda, trace_moe, trace_owner

SSD = ("dk_ssd", "dk_ssd_conv")


def _say(msg: str) -> None:
    print(f"[bench] trace_ssd: {msg}", flush=True)


def _least(flops: float, moved: float, peak: dict) -> dict:
    by_flops = flops / peak["bf16_flops_per_s"]
    by_bytes = moved / peak["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": moved,
            "seconds": max(by_flops, by_bytes),
            "bound": "flops" if by_flops >= by_bytes else "bytes"}


def ssd_floor(tokens: int, layers: int, heads: int, groups: int,
              head_dim: int, state_dim: int, peak: dict,
              bytes_per_element: int = 2) -> dict:
    """The least time one chip could take for the state-space recurrence
    between its projections, forward and backward, over ``tokens`` tokens in
    ``layers`` Mamba-2 layers of ``heads`` held heads in ``groups`` groups.
    Operations: 4 a state element and token forward (the decay, the rank-one
    write, ``h^T C``) and 8 backward: ``12 * state_dim * head_dim`` a token
    and head. Bytes, a token and layer, each moved once: ``x``, ``y``, ``dx``,
    ``dy`` at the heads (``4 * heads * head_dim``), ``B``, ``C``, ``dB``,
    ``dC`` at the groups (``4 * groups * state_dim``), ``dt`` and its
    cotangent (``2 * heads``). The recomputed forward of ``remat`` is not
    counted."""
    flops = 12.0 * state_dim * head_dim * heads * tokens * layers
    moved = (4.0 * heads * head_dim + 4.0 * groups * state_dim
             + 2.0 * heads) * bytes_per_element * tokens * layers
    return _least(flops, moved, peak)


def plain_experts_floor(assignments: float, layer_steps: float, held: int,
                        d_model: int, d_expert: int, peak: dict,
                        bytes_per_element: int = 2) -> dict:
    """The least time one chip could take for plain experts' products
    (``act(x W_up) W_down``), forward and backward, over ``assignments``
    routed rows in ``layer_steps`` (layers x steps) passes. Operations: two
    products of ``2 * d_model * d_expert`` a row forward and twice that
    backward, ``12 * d_model * d_expert`` a row. Bytes: the held experts' two
    matrices once a layer and step, and each gathered row of ``d_model``
    once."""
    flops = 12.0 * d_model * d_expert * assignments
    moved = bytes_per_element * (layer_steps * held * 2.0 * d_model * d_expert
                                 + assignments * d_model)
    return _least(flops, moved, peak)


def _reduced(run) -> dict:
    if not hasattr(run, "trace_ssd"):
        t = run.trace
        got = trace_kda.reduce(run.hlo, t["ops0"], t["lo"], t["hi"], SSD)
        per_round = 1e-6 / t["rounds"]
        _say(f"ms/round under {'+'.join(SSD)}: {got['ns'] * per_round:.3f} ("
             + ", ".join(f"{stem} {ns * per_round:.3f}" for stem, ns in sorted(
                 got["stems"].items(), key=lambda kv: -kv[1])[:8])
             + "); fusions that hold some of it and a projection's matmul, "
             f"not counted: {got['with_matmul_ns'] * per_round:.3f}")
        _say_what_ran(run)
        run.trace_ssd = got
    return run.trace_ssd


def _say_what_ran(run) -> None:
    """The program's own account, on ``[bench`` lines: the layer kinds it
    wrote as the model was built, the gauges and the counter it set as it was
    traced, the ``ssd.round`` and ``moe.round`` events of the traced rounds;
    and the shared expert's time and the owner split."""
    from distkeras_tpu import telemetry

    events = telemetry.get().events()
    kinds = [e for e in events if e.get("kind") == "model.layer_kinds"]
    if kinds:
        _say("model.layer_kinds: " + ",".join(kinds[-1]["operators"])
             + f"; experts held {kinds[-1]['experts_held']}, Mamba heads "
             f"held {kinds[-1].get('mamba_heads_held')}, attention heads held "
             f"{kinds[-1].get('heads_held')}, vocabulary rows "
             f"{kinds[-1]['vocab_size']}")
    _say("gauges: " + ", ".join(
        f"{g} {telemetry.gauge(g).value}" for g in (
            "ssd.chunk", "ssd.state_bytes", "remat.flash_residual_bytes",
            "pallas.flash.visited_share", "pallas.kda.heads_per_program"))
        + "; pallas.ssd.scan_calls "
        f"{telemetry.counter('pallas.ssd.scan_calls').value}")
    traced = {e["round"]: e for e in trace_moe.traced_round_events(run)}
    for e in events:
        if e.get("kind") == "ssd.round" and e.get("round") in traced:
            _say(f"ssd.round {e['round']}: min_chunk_decay by layer "
                 f"{[round(v, 2) for v in e['min_chunk_decay_by_layer']]}, "
                 f"mean dt {e['mean_dt']:.5f}")
    for r, e in sorted(traced.items()):
        _say(f"moe.round {r}: assignments held by layer "
             f"{e.get('assignments_held_by_layer')}, load max/mean by layer "
             f"{[round(v, 3) for v in e.get('load_max_over_mean_by_layer', [])]}"
             f", moe.bias_moved_share {e.get('bias_moved_share')}")
    t = run.trace
    shared = trace_kda.reduce(run.hlo, t["ops0"], t["lo"], t["hi"],
                              trace_kda.SHARED)
    _say(f"ms/round under {'+'.join(trace_kda.SHARED)} (the shared expert): "
         f"{shared['ns'] * 1e-6 / t['rounds']:.3f}")
    split = {o: trace_owner.read(run, owner=o) for o in (
        "embed", "norm", "mixer", "ffn", "head", "loss", "optimizer")}
    split["unowned + glue"] = trace_owner.read(run, owner=["unowned", "glue"])
    _say("owner split, ms/round: " + ", ".join(
        f"{o} {v:.3f}" for o, v in split.items() if v is not None))


def read(run, what: str = "ssd_ms", config=None):
    """``what="ssd_ms"``: ms/round under ``dk_ssd`` and ``dk_ssd_conv``.
    ``"ssd_roofline"``: the share in percent that :func:`ssd_floor` for
    ``config``'s ``mamba`` layers is of that time. ``"experts_roofline"``: the
    share that :func:`plain_experts_floor` over what the traced rounds routed
    here is of the time under ``dk_moe_experts``."""
    t = run.trace
    if not t or not t["rounds"]:
        return None
    if what == "experts_roofline":
        return _experts_share(run, config)
    got = _reduced(run)
    if not got["in_program"]:
        _say(f"the program has no scope {'/'.join(SSD)}")
        return None
    ms = got["ns"] * 1e-6 / t["rounds"]
    if what != "ssd_roofline":
        return ms
    if not ms:
        _say("the scope is in the program and the trace holds no event of "
             "it: the share reads 0")
        return 0.0
    from benchmarks.families.nemotron_h import held_heads, held_layers

    module = trace_moe._config(config)["module"]
    layers, held = held_layers(module).count("mamba"), held_heads(module)
    least = ssd_floor(run.units_per_round // run.chips, layers, held["mamba"],
                      held["groups"], module["mamba_head_dim"],
                      module["ssm_state_size"], run.peak)
    _say(f"the state-space recurrence's floor a round over {layers} layers: "
         f"{least['seconds'] * 1e3:.3f} ms, bound by {least['bound']} "
         f"({least['bytes']:.4g} bytes, {least['flops']:.4g} operations), "
         f"against {ms:.3f} ms")
    return least["seconds"] * 1e3 / ms * 100.0


def _experts_share(run, config):
    ms = trace_moe.read(run, scopes=["dk_moe_experts"])
    if ms is None:
        return None
    events = trace_moe.traced_round_events(run)
    if not events:
        _say("the program wrote no moe.round event for the traced rounds: "
             "no share")
        return None
    if not ms:
        _say("the experts' scope is in the program and the trace holds no "
             "event of it: the share reads 0")
        return 0.0
    module = trace_moe._config(config)["module"]
    assignments = sum(e["assignments_held"] for e in events) / len(events)
    layer_steps = sum(e["steps"] * e["layers"] for e in events) / len(events)
    least = plain_experts_floor(assignments, layer_steps,
                                module["experts_held"][1], module["d_model"],
                                module["d_expert"], run.peak)
    _say(f"{assignments:.1f} assignments to held experts a round over rounds "
         f"{[e['round'] for e in events]} ({layer_steps:g} layer-steps); the "
         f"plain experts' floor a round: {least['seconds'] * 1e3:.3f} ms, "
         f"bound by {least['bound']} ({least['flops']:.4g} operations, "
         f"{least['bytes']:.4g} bytes), against {ms:.3f} ms")
    return least["seconds"] * 1e3 / ms * 100.0
