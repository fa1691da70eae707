"""Device time of the gated short convolution's elementwise chain, its share
of a floor, and the flash kernels' share of theirs in a model only some of
whose layers have attention (``families/lfm2.py``).

The program names the chain between the convolution operator's two
projections (``Bg * u``, the taps, ``Cg *``) with ``jax.named_scope``:
``dk_shortconv``, inside ``dk_fwd_bwd``, forward, recomputed and backward
alike. An instruction counts when its own ``op_name`` is under the scope (a
fusion carries the name of its root), or when it is a fusion that has no
such name, fused an instruction that has, and holds no ``convolution``. XLA
on a TPU writes a matmul as a ``convolution`` and fuses elementwise
producers and consumers into it: what of the chain it fused into ``W_in``'s
or ``W_out``'s product runs inside that product and is the matmul's time,
not this scope's (``W_in`` and ``W_out`` are matmuls of the step). The
``[bench`` line says how much time such fusions took, so that a reader knows
what the scope's time leaves out; the floor below counts the whole chain, so
the share reads high by that part, never low. An event counts its **self**
time, as in ``trace_scope``.

The floor is of the work, whatever implements it (:func:`shortconv_floor`).

Where there is nothing to read, ``read`` returns ``None`` and says why on a
``[bench`` line: no trace, or a program without the scope (the parent of the
PR that added it). A scope that is in the program and took no time reads
``0.0`` (a rehearsal's CPU trace holds next to no event).
"""

from __future__ import annotations

from benchmarks.harness.trace_reduce import self_ns_by_name
from benchmarks.readers import trace_moe, trace_scope

SCOPE = "dk_shortconv"


def _say(msg: str) -> None:
    print(f"[bench] trace_shortconv: {msg}", flush=True)


def classify(hlo: str) -> tuple:
    """``(instructions that count as the scope's, fusions that hold both an
    instruction of the scope and a convolution)`` of a compiled program's
    text."""
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
            SCOPE in op_name.split("/") for op_name in n.group(1).split(";"))
        convolution[name] = " convolution(" in line[m.end():]
        f = trace_scope._FUSION_CALLS.search(line)
        if f is not None:
            calls[name] = f.group(1)

    inside: dict = {}  # computation -> (holds the scope, holds a convolution)

    def fused(name):
        """What the computation that fusion ``name`` calls holds."""
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
            scope, conv = fused(name)
            if conv and (scope or own[name]):
                with_matmul.add(name)
            elif own[name] or scope:
                counted.add(name)
    return counted, with_matmul


def reduce(hlo: str, events, lo, hi) -> dict:
    """Self time in ns of ``events`` inside ``[lo, hi]``: ``{"ns": under the
    scope, "stems": {instruction name less its number: ns}, "with_matmul_ns":
    of the fusions that hold some of the chain and a matmul (not counted),
    "in_program": whether the program has the scope at all}``."""
    counted, with_matmul = classify(hlo)
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


def shortconv_floor(tokens: int, conv_layers: int, d_model: int, peak: dict,
                    bytes_per_element: int = 2) -> dict:
    """The least time one chip could take for the gated short convolution's
    elementwise chain between its two projections, forward and backward,
    over ``tokens`` tokens in ``conv_layers`` layers. Bytes, a token and
    layer: forward reads ``[Bg, Cg, u]`` (``3 d``) and writes ``y`` (``d``);
    backward reads them and ``dy`` (``4 d``) and writes their three
    gradients (``3 d``): ``11 d`` elements, each moved once. Operations: 7 a
    channel forward (``Bg * u``, three taps and two sums, ``Cg *``) and 21
    backward; beside the bytes they bound nothing. The recomputed forward of
    ``remat`` is not counted."""
    moved = 11.0 * d_model * bytes_per_element * tokens * conv_layers
    flops = 28.0 * d_model * tokens * conv_layers
    by_flops = flops / peak["bf16_flops_per_s"]
    by_bytes = moved / peak["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": moved,
            "seconds": max(by_flops, by_bytes),
            "bound": "flops" if by_flops >= by_bytes else "bytes"}


def _reduced(run) -> dict:
    if not hasattr(run, "trace_shortconv"):
        t = run.trace
        got = reduce(run.hlo, t["ops0"], t["lo"], t["hi"])
        per_round = 1e-6 / t["rounds"]
        _say(f"ms/round under {SCOPE}: {got['ns'] * per_round:.3f} ("
             + ", ".join(f"{stem} {ns * per_round:.3f}" for stem, ns in sorted(
                 got["stems"].items(), key=lambda kv: -kv[1])[:6])
             + f"); fusions that hold some of the chain and a matmul, not "
             f"counted: {got['with_matmul_ns'] * per_round:.3f}")
        _say_what_ran(run)
        run.trace_shortconv = got
    return run.trace_shortconv


def _say_what_ran(run) -> None:
    """The program's own account of the traced rounds, on ``[bench`` lines:
    the layer kinds it wrote as the model was built (``model.layer_kinds``)
    and, from the ``moe.round`` events of the traced rounds, each routed
    layer's load and what the router's bias moved."""
    from distkeras_tpu import telemetry

    kinds = [e for e in telemetry.get().events()
             if e.get("kind") == "model.layer_kinds"]
    if kinds:
        _say("model.layer_kinds: operators " + ",".join(kinds[-1]["operators"])
             + "; feed-forward " + ",".join(kinds[-1]["feed_forward"])
             + f"; experts held {kinds[-1]['experts_held']}, vocabulary "
             f"rows {kinds[-1]['vocab_size']}")
    for e in trace_moe.traced_round_events(run):
        _say(f"moe.round {e['round']}: assignments held by layer "
             f"{e.get('assignments_held_by_layer')}, load max/mean by layer "
             f"{[round(v, 3) for v in e.get('load_max_over_mean_by_layer', [])]}"
             f", moe.bias_moved_share {e.get('bias_moved_share')}")


def read(run, what: str = "ms", config=None):
    """``what="ms"``: ms/round under ``dk_shortconv``. ``"roofline"``: the
    share in percent that :func:`shortconv_floor` for ``config``'s
    convolution layers is of that time. ``"flash_attention_layers"``: the
    share that ``trace_moe.flash_window_floor`` over ``config``'s layers
    *that have attention* is of the flash kernels' time."""
    t = run.trace
    if not t or not t["rounds"]:
        return None
    if what == "flash_attention_layers":
        return _flash_share(run, config)
    got = _reduced(run)
    if not got["in_program"]:
        _say(f"the program has no scope {SCOPE}")
        return None
    ms = got["ns"] * 1e-6 / t["rounds"]
    if what == "ms":
        return ms
    if not ms:
        _say("the scope is in the program and the trace holds no event of "
             "it: the share reads 0")
        return 0.0
    from benchmarks.families.lfm2 import held_layers

    module = trace_moe._config(config)["module"]
    layers = sum(op == "conv" for op, _ in held_layers(module))
    least = shortconv_floor(run.units_per_round // run.chips, layers,
                            module["d_model"], run.peak)
    _say(f"the convolution chain's floor a round over {layers} layers: "
         f"{least['seconds'] * 1e3:.3f} ms, bound by {least['bound']} "
         f"({least['bytes']:.4g} bytes, {least['flops']:.4g} operations), "
         f"against {ms:.3f} ms")
    return least["seconds"] * 1e3 / ms * 100.0


def _flash_share(run, config):
    from benchmarks.families.lfm2 import attention_keys_seen

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
    _say(f"grouped attention's floor a round over the {len(keys)} layer(s) "
         f"that have attention: {least['seconds'] * 1e3:.3f} ms, bound by "
         f"{least['bound']} ({least['flops']:.4g} operations, "
         f"{least['bytes']:.4g} bytes; keys seen a layer {keys}), against "
         f"{ms:.3f} ms of kernels")
    return least["seconds"] * 1e3 / ms * 100.0
