"""Device time of Gated DeltaNet's chain, its share of a floor of Gated
DeltaNet's own work, and the flash kernels' share of the causal floor at head
width 256 (``families/qwen3_next.py``).

The program names the chain with ``jax.named_scope`` inside ``dk_fwd_bwd``,
forward, recomputed and backward alike: ``dk_gdn_conv`` (the tap sums over
``q | k | v`` and their SiLU), ``dk_gdn`` (norms, the decay, the kernels of
the chunked form, the gated norm). An instruction
counts for the scopes as ``trace_kda`` counts (:func:`trace_kda.classify`,
:func:`trace_kda.reduce`, imported, not copied): its own ``op_name`` under one
of them, or a fusion that fused such an instruction and holds no matmul; a
projection's product with some of the chain fused into it is the matmul's,
and a ``[bench`` line says how much that was. An event counts its **self**
time.

**The floor is of Gated DeltaNet's work, whatever implements it**
(:func:`gdn_floor`): one decay a value head, and ``q``, ``k`` at the key
heads. The chain's in-chunk pair ``dk_gdn_chunk_*`` reads the decay a value
head and ``q``, ``k`` at the key heads by group, with no repeat to the value
heads, and feeds the scan pair Kimi Delta Attention runs.

Where there is nothing to read, ``read`` returns ``None`` and says why on a
``[bench`` line: no trace, or a program without the scopes (the parent of the
PR that added them). A scope that is in the program and took no time reads
``0.0`` (a rehearsal's CPU trace holds next to no event).
"""

from __future__ import annotations

from benchmarks.readers import trace_kda, trace_moe, trace_owner, trace_scope

GDN = ("dk_gdn", "dk_gdn_conv")


def _say(msg: str) -> None:
    print(f"[bench] trace_gdn: {msg}", flush=True)


def gdn_floor(tokens: int, layers: int, key_heads: int, value_heads: int,
              head_dim: int, peak: dict, bytes_per_element: int = 2) -> dict:
    """The least time one chip could take for the delta rule between its
    projections, forward and backward, over ``tokens`` tokens in ``layers``
    Gated DeltaNet layers. Operations: 7 a state element and token forward
    (the decay, ``S'^T k``, the rank-one update, ``S^T q``) and twice that
    backward: ``21 * head_dim^2`` a token and value head. Bytes, a token and
    layer, each moved three times (``trace_kda.kda_floor``'s count: read
    forward, read backward, its gradient written; ``o`` written, read and
    its cotangent read): ``q`` and ``k`` at the **key** heads, ``v`` and
    ``o`` at the value heads, and **one** decay and one ``beta`` a value
    head: ``6 * (key_heads + value_heads) * head_dim + 6 * value_heads``
    elements. The recomputed forward of ``remat`` is not counted."""
    flops = 21.0 * head_dim ** 2 * value_heads * tokens * layers
    moved = (6.0 * (key_heads + value_heads) * head_dim + 6.0 * value_heads) \
        * bytes_per_element * tokens * layers
    by_flops = flops / peak["bf16_flops_per_s"]
    by_bytes = moved / peak["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": moved,
            "seconds": max(by_flops, by_bytes),
            "bound": "flops" if by_flops >= by_bytes else "bytes"}


def _reduced(run) -> dict:
    if not hasattr(run, "trace_gdn"):
        t = run.trace
        got = trace_kda.reduce(run.hlo, t["ops0"], t["lo"], t["hi"], GDN)
        per_round = 1e-6 / t["rounds"]
        _say(f"ms/round under {'+'.join(GDN)}: {got['ns'] * per_round:.3f} ("
             + ", ".join(f"{stem} {ns * per_round:.3f}" for stem, ns in sorted(
                 got["stems"].items(), key=lambda kv: -kv[1])[:8])
             + "); fusions that hold some of it and a projection's matmul, "
             f"not counted: {got['with_matmul_ns'] * per_round:.3f}")
        _say_what_ran(run)
        run.trace_gdn = got
    return run.trace_gdn


def _say_what_ran(run) -> None:
    """The program's own account, on ``[bench`` lines: the gauges it set as
    it was traced, and the ``gdn.round`` events of the traced rounds."""
    from distkeras_tpu import telemetry

    _say("gauges: " + ", ".join(
        f"{g} {telemetry.gauge(g).value}" for g in (
            "gdn.chunk", "gdn.state_bytes", "remat.flash_residual_bytes",
            "pallas.flash.visited_share")))
    t = run.trace
    shared = trace_kda.reduce(run.hlo, t["ops0"], t["lo"], t["hi"],
                              trace_kda.SHARED)
    _say(f"ms/round under {'+'.join(trace_kda.SHARED)} (the shared expert "
         f"and its gate; moe.shared_ms.lm's list of cells is pinned by "
         f"test_kimi_linear_bench.py): {shared['ns'] * 1e-6 / t['rounds']:.3f}")
    # the owner split on trace_owner's own [bench lines: owner.*'s lists of
    # cells are pinned by test_trace_owner.py
    unowned = trace_owner.read(run, owner=["unowned", "glue"])
    if unowned is not None:
        _say(f"owner unowned + glue: {unowned:.3f} ms/round")
    traced = {e["round"] for e in trace_moe.traced_round_events(run)}
    for e in telemetry.get().events():
        if e.get("kind") == "gdn.round" and e.get("round") in traced:
            _say(f"gdn.round {e['round']}: min_chunk_decay by layer "
                 f"{[round(v, 2) for v in e['min_chunk_decay_by_layer']]}, "
                 f"mean beta {e['mean_beta']:.4f}, shared expert's gate "
                 f"{e['shared_gate_mean']:.4f}")


def read(run, what: str = "gdn_ms", config=None):
    """``what="gdn_ms"``: ms/round under ``dk_gdn`` and ``dk_gdn_conv``.
    ``"gdn_roofline"``: the share in percent that :func:`gdn_floor` for
    ``config``'s ``gdn`` layers is of that time. ``"flash_roofline"``: the
    share that ``trace_moe.flash_window_floor`` over ``config``'s attention
    layers (full and causal, ``num_heads`` over ``num_kv_heads`` of
    ``head_dim``) is of the flash kernels' time."""
    t = run.trace
    if not t or not t["rounds"]:
        return None
    if what == "flash_roofline":
        return _flash_share(run, config)
    got = _reduced(run)
    if not got["in_program"]:
        _say(f"the program has no scope {'/'.join(GDN)}")
        return None
    ms = got["ns"] * 1e-6 / t["rounds"]
    if what != "gdn_roofline":
        return ms
    if not ms:
        _say("the scope is in the program and the trace holds no event of "
             "it: the share reads 0")
        return 0.0
    from benchmarks.families.qwen3_next import held_layers

    module = trace_moe._config(config)["module"]
    layers = held_layers(module).count("gdn")
    least = gdn_floor(run.units_per_round // run.chips, layers,
                      module["linear_key_heads"], module["linear_value_heads"],
                      module["linear_head_dim"], run.peak)
    _say(f"Gated DeltaNet's floor a round over {layers} layers: "
         f"{least['seconds'] * 1e3:.3f} ms, bound by {least['bound']} "
         f"({least['bytes']:.4g} bytes, {least['flops']:.4g} operations), "
         f"against {ms:.3f} ms")
    return least["seconds"] * 1e3 / ms * 100.0


def _flash_share(run, config):
    from benchmarks.families.qwen3_next import attention_keys_seen

    kernels = list(trace_moe.FLASH)
    ms = trace_scope.read(run, kernels=kernels)
    if ms is None or not set(kernels) <= trace_scope._reduced(run)["scopes"]:
        _say("the program has no flash kernel's scope")
        return None
    if not ms:
        _say("the flash kernels are in the program and the trace holds no "
             "event of theirs: the share reads 0")
        return 0.0
    cfg = trace_moe._config(config)
    module, keys = cfg["module"], attention_keys_seen(cfg)
    least = trace_moe.flash_window_floor(
        run.units_per_round // run.chips, keys, module["num_heads"],
        module["num_kv_heads"], module["head_dim"], run.peak)
    _say(f"gated attention's floor a round over {len(keys)} layer(s) "
         f"({module['num_heads']} / {module['num_kv_heads']} heads of "
         f"{module['head_dim']}): {least['seconds'] * 1e3:.3f} ms, bound by "
         f"{least['bound']} ({least['flops']:.4g} operations, "
         f"{least['bytes']:.4g} bytes; keys seen a layer {keys}), against "
         f"{ms:.3f} ms of kernels")
    return least["seconds"] * 1e3 / ms * 100.0
