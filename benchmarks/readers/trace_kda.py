"""Device time of Kimi Delta Attention's chain and of the shared expert, the
chain's share of a floor, and the flash kernels' share of theirs where keys
are wider than values and only some layers have attention
(``families/kimi_linear.py``).

The program names its parts with ``jax.named_scope`` inside ``dk_fwd_bwd``,
forward, recomputed and backward alike: ``dk_kda_conv`` (the three tap sums
and their SiLU), ``dk_kda`` (norms, decay, in-chunk products, the triangular
inverse, the scan over chunks, the gated norm), ``dk_moe_shared`` (the shared
expert's three products and gate). An instruction counts for a set of scopes
as in ``trace_shortconv.classify``: when its own ``op_name`` is under one of
them (a fusion carries the name of its root; a ``while`` and the
instructions of its body carry theirs), or when it is a fusion that has no
such name, fused an instruction that has, and holds no ``convolution`` (XLA
on a TPU writes a matmul as one). A fusion of the second sort that *does*
hold a matmul is a projection's product with some of the chain in its
prologue or epilogue (``W_q``'s with the first tap sum, ``W_o``'s with the
gated norm): that time is the matmul's, not the scope's, and a ``[bench``
line says how much it was. The floors count the whole chain, so a share
reads high by that part, never low. An event counts its **self** time.

The floors are of the work, whatever implements it (:func:`kda_floor`,
:func:`latent_flash_floor`).

Where there is nothing to read, ``read`` returns ``None`` and says why on a
``[bench`` line: no trace, or a program without the scope (the parent of the
PR that added it). A scope that is in the program and took no time reads
``0.0`` (a rehearsal's CPU trace holds next to no event).
"""

from __future__ import annotations

from benchmarks.harness.trace_reduce import self_ns_by_name
from benchmarks.readers import trace_moe, trace_scope

KDA = ("dk_kda", "dk_kda_conv")
SHARED = ("dk_moe_shared",)


def _say(msg: str) -> None:
    print(f"[bench] trace_kda: {msg}", flush=True)


def classify(hlo: str, scopes) -> tuple:
    """``(instructions that count as the scopes', fusions that hold both an
    instruction of the scopes and a matmul of another's)`` of a compiled
    program's text."""
    scopes = set(scopes)
    own, calls, members, convolution = {}, {}, {}, {}
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
        own[name] = n is not None and any(
            scopes & set(op_name.split("/"))
            for op_name in n.group(1).split(";"))
        convolution[name] = " convolution(" in line[m.end():]
        f = trace_scope._FUSION_CALLS.search(line)
        if f is not None:
            calls[name] = f.group(1)

    inside: dict = {}  # computation -> (holds the scopes, holds a convolution)

    def fused(name):
        comp = calls.get(name)
        if comp is None:
            return False, False
        if comp not in inside:
            held = [(own[n] or fused(n)[0], convolution[n] or fused(n)[1])
                    for n in members.get(comp, ())]
            inside[comp] = (any(s for s, _ in held), any(c for _, c in held))
        return inside[comp]

    fused_computations = set(calls.values())
    counted, with_matmul = set(), set()
    for comp, names in members.items():
        if comp in fused_computations:
            continue  # an instruction inside a fusion has no event of its own
        for name in names:
            if own[name]:
                counted.add(name)
                continue
            scope, conv = fused(name)
            if scope:
                (with_matmul if conv else counted).add(name)
    return counted, with_matmul


def reduce(hlo: str, events, lo, hi, scopes) -> dict:
    """Self time in ns of ``events`` inside ``[lo, hi]``: ``{"ns": under the
    scopes, "stems": {instruction name less its number: ns},
    "with_matmul_ns": of the fusions that hold some of the chain and another
    scope's matmul (not counted), "in_program": whether the program has the
    scopes at all}``."""
    counted, with_matmul = classify(hlo, scopes)
    total, mixed, stems = 0.0, 0.0, {}
    for name, ns in self_ns_by_name(events, lo, hi).items():
        if name in counted:
            total += ns
            stem = name.rsplit(".", 1)[0] if name.rsplit(".", 1)[-1].isdigit() \
                else name
            stems[stem] = stems.get(stem, 0.0) + ns
        elif name in with_matmul:
            mixed += ns
    return {"ns": total, "stems": stems, "with_matmul_ns": mixed,
            "in_program": bool(counted or with_matmul)}


def kda_floor(tokens: int, kda_layers: int, heads: int, head_dim: int,
              peak: dict, bytes_per_element: int = 2) -> dict:
    """The least time one chip could take for the delta rule between its
    projections, forward and backward, over ``tokens`` tokens in
    ``kda_layers`` layers of ``heads`` held heads. Operations: 7 a state
    element and token forward (the decay, ``S'^T k``, the rank-one update,
    ``S^T q``) and twice that backward: ``21 * head_dim^2`` a token and head.
    Bytes, a token and layer: ``q, k, v, g, o`` once forward and those with
    ``do`` and ``dq, dk, dv, dg`` once backward, ``15 * heads * head_dim``
    elements, and ``beta`` three times (read, read, its gradient). The
    recomputed forward of ``remat`` is not counted."""
    flops = 21.0 * head_dim ** 2 * heads * tokens * kda_layers
    moved = (15.0 * heads * head_dim + 3.0 * heads) * bytes_per_element \
        * tokens * kda_layers
    by_flops = flops / peak["bf16_flops_per_s"]
    by_bytes = moved / peak["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": moved,
            "seconds": max(by_flops, by_bytes),
            "bound": "flops" if by_flops >= by_bytes else "bytes"}


def latent_flash_floor(tokens: int, keys_seen: list, heads: int, qk_dim: int,
                       v_dim: int, peak: dict,
                       bytes_per_element: int = 2) -> dict:
    """``trace_moe.flash_window_floor``'s reasoning where keys and values
    have widths of their own: ``QK^T`` is ``2 * k * heads * qk_dim`` a token
    forward and ``PV`` ``2 * k * heads * v_dim``, twice that backward: ``6 * k
    * heads * (qk_dim + v_dim)`` a token and layer. Bytes: ``q, dq, k, dk`` at
    ``qk_dim`` and ``v, dv, o, do`` at ``v_dim`` for ``heads`` heads, each
    moved once. ``keys_seen`` holds the mean number of keys a query sees for
    each layer that has attention; the others count nothing."""
    flops = sum(6.0 * k * heads * (qk_dim + v_dim) for k in keys_seen) * tokens
    moved = 4.0 * heads * (qk_dim + v_dim) * bytes_per_element * tokens \
        * len(keys_seen)
    by_flops = flops / peak["bf16_flops_per_s"]
    by_bytes = moved / peak["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": moved,
            "seconds": max(by_flops, by_bytes),
            "bound": "flops" if by_flops >= by_bytes else "bytes"}


def _reduced(run, scopes) -> dict:
    cache = run.__dict__.setdefault("trace_kda", {})
    if scopes not in cache:
        t = run.trace
        got = reduce(run.hlo, t["ops0"], t["lo"], t["hi"], scopes)
        per_round = 1e-6 / t["rounds"]
        _say(f"ms/round under {'+'.join(scopes)}: {got['ns'] * per_round:.3f}"
             " (" + ", ".join(
                 f"{stem} {ns * per_round:.3f}" for stem, ns in sorted(
                     got["stems"].items(), key=lambda kv: -kv[1])[:8])
             + "); fusions that hold some of it and a projection's matmul, "
             f"not counted: {got['with_matmul_ns'] * per_round:.3f}")
        if scopes == KDA:
            _say_what_ran(run)
        cache[scopes] = got
    return cache[scopes]


def _say_what_ran(run) -> None:
    """The program's own account, on ``[bench`` lines: the layer kinds it
    wrote as the model was built, the gauges it set as it was traced, and the
    ``kda.round`` and ``moe.round`` events of the traced rounds."""
    from distkeras_tpu import telemetry

    events = telemetry.get().events()
    kinds = [e for e in events if e.get("kind") == "model.layer_kinds"]
    if kinds:
        _say("model.layer_kinds: operators " + ",".join(kinds[-1]["operators"])
             + "; feed-forward " + ",".join(kinds[-1]["feed_forward"])
             + f"; experts held {kinds[-1]['experts_held']}, heads held "
             f"{kinds[-1].get('heads_held')}, vocabulary rows "
             f"{kinds[-1]['vocab_size']}")
    _say("gauges: " + ", ".join(
        f"{g} {telemetry.gauge(g).value}" for g in (
            "kda.chunk", "kda.state_bytes", "remat.flash_residual_bytes",
            "pallas.flash.visited_share")))
    traced = {e["round"]: e for e in trace_moe.traced_round_events(run)}
    for e in events:
        if e.get("kind") == "kda.round" and e.get("round") in traced:
            _say(f"kda.round {e['round']}: min_chunk_decay by layer "
                 f"{[round(v, 2) for v in e['min_chunk_decay_by_layer']]}, "
                 f"mean beta {e['mean_beta']:.4f}")
    for r, e in sorted(traced.items()):
        _say(f"moe.round {r}: assignments held by layer "
             f"{e.get('assignments_held_by_layer')}, load max/mean by layer "
             f"{[round(v, 3) for v in e.get('load_max_over_mean_by_layer', [])]}"
             f", moe.bias_moved_share {e.get('bias_moved_share')}")


def read(run, what: str = "kda_ms", config=None):
    """``what="kda_ms"``: ms/round under ``dk_kda`` and ``dk_kda_conv``.
    ``"kda_roofline"``: the share in percent that :func:`kda_floor` for
    ``config``'s ``kda`` layers is of that time. ``"shared_ms"``: ms/round
    under ``dk_moe_shared``. ``"flash_latent"``: the share that
    :func:`latent_flash_floor` over ``config``'s layers *that have attention*
    is of the flash kernels' time."""
    t = run.trace
    if not t or not t["rounds"]:
        return None
    if what == "flash_latent":
        return _flash_share(run, config)
    scopes = SHARED if what == "shared_ms" else KDA
    got = _reduced(run, scopes)
    if not got["in_program"]:
        _say(f"the program has no scope {'/'.join(scopes)}")
        return None
    ms = got["ns"] * 1e-6 / t["rounds"]
    if what != "kda_roofline":
        return ms
    if not ms:
        _say("the scope is in the program and the trace holds no event of "
             "it: the share reads 0")
        return 0.0
    from benchmarks.families.kimi_linear import held_layers

    module = trace_moe._config(config)["module"]
    layers = sum(op == "kda" for op, _ in held_layers(module))
    least = kda_floor(run.units_per_round // run.chips, layers,
                      module["heads_held"][1], module["kda_head_dim"],
                      run.peak)
    _say(f"the delta rule's floor a round over {layers} layers: "
         f"{least['seconds'] * 1e3:.3f} ms, bound by {least['bound']} "
         f"({least['bytes']:.4g} bytes, {least['flops']:.4g} operations), "
         f"against {ms:.3f} ms")
    return least["seconds"] * 1e3 / ms * 100.0


def _flash_share(run, config):
    from benchmarks.families.kimi_linear import attention_keys_seen

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
    qk = module["qk_nope_head_dim"] + module["qk_rope_head_dim"]
    least = latent_flash_floor(
        run.units_per_round // run.chips, keys, module["heads_held"][1], qk,
        module["v_head_dim"], run.peak)
    _say(f"latent attention's floor a round over the {len(keys)} layer(s) "
         f"that have attention (keys {qk}, values {module['v_head_dim']}): "
         f"{least['seconds'] * 1e3:.3f} ms, bound by {least['bound']} "
         f"({least['flops']:.4g} operations, {least['bytes']:.4g} bytes; "
         f"keys seen a layer {keys}), against {ms:.3f} ms of kernels")
    return least["seconds"] * 1e3 / ms * 100.0
