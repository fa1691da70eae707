"""Device time per round by the sublayer that owns each instruction.

``trace_scope`` says *when* an instruction of the round program runs
(forward, recomputed, backward, optimizer, fold); this reader says *whose* it
is. The program opens an **owner** scope around each sublayer of its models
(``distkeras_tpu/scopes.py``: ``with owner("mixer"):`` is
``jax.named_scope("dk_own_mixer")``), so a compiled instruction's ``op_name``
reads ``.../dk_fwd_bwd/transpose(jvp(..))/block_3/attn/dk_own_mixer/...``. Out
of ``run.hlo`` and ``run.trace["ops0"]`` every instruction gets one owner and
one pass, and device 0's **self** time inside the bracket
(``trace_reduce.self_ns_by_name``) is summed by both. The owners partition
the busy time: their sum is ``round.device_ms.*``.

**Owner**, by the first rule that gives one:

1. the innermost ``dk_own_*`` of the instruction's own ``op_name`` (XLA names
   a fusion after its root; of ``;``-joined names, the first that has one);
2. a fusion whose own name has none: the one owner the instructions it calls
   agree on. **Where they hold two owners' instructions** (rule 1 failing):
   the owner of the ``convolution`` s it holds (XLA on a TPU writes a matmul
   as one, and a matmul is where such a fusion's time goes) if those agree,
   else the owner most of its instructions carry, the earlier of ``OWNERS``
   on a tie. ``cast`` is asked last, after rule 3: a convert fused into an
   instruction is on the way into or out of that instruction's work (XLA
   fuses the next step's cast of a parameter into Adam's update of it).
   How much hangs on rules 1 and 2 is printed: ``owner<-other`` is
   the time of the instructions counted for ``owner`` that also hold an
   instruction of ``other`` (a norm fused into the matmul that reads it, a
   cast into the product that takes the weight);
3. from the scopes that were always there, an instruction's own name before
   those it calls: ``optimizer`` (``dk_optimizer``), ``fold``
   (``trace_scope.FOLD_SCOPES``), ``guard`` (``dk_nan_guard``), ``io``
   (``dk_loss_gather``, ``dk_input_transform``), ``glue`` (under
   ``dk_fwd_bwd`` and no owner: residual adds, reshapes);
4. an instruction with no ``op_name`` at all (the compiler's copies and
   prefetching slices) and the compiler's ``ragged-dot-*`` calls (it writes
   their ``op_name`` itself, with no scope of ours): the one owner its users
   have, else the one its operands have (a copy that two owners read is the
   owner's whose result it moves; an expert's weight-gradient product, which
   only the update reads, is the expert layer's), else the derived group
   they agree on. Such an instruction among the users or operands stands for
   its own, and a value that a ``while`` carries is followed from the
   instruction that writes element ``i`` to those that read it;
5. ``unowned``: no scope of ours, or an event of no instruction of the text.

So a weight-gradient product fused with Adam's update, which XLA names after
the update, is the owner's whose matmul it is. **Pass**: ``forward``,
``recomputed`` (``trace_scope``'s ``remat``) or ``backward`` as
``trace_scope.classify`` says; ``wgrad_update`` where it says ``mixed:`` with
``optimizer`` in it; else ``none``.

What ``read`` returns where there is nothing to read, in this order. No
trace or no whole round: ``None``. The program's telemetry registry declares
no counter ``trace.owner_scopes``: the program predates the scopes (the
parent commit of the PR that added them, under this benchmark); every metric
reads ``0.0`` and one ``[bench`` line says so. The counter is declared and
reads 0 in this process: ``None`` (the cell's model opened no owner scope: a
refactor dropped them). The counter is above 0 and the text holds no
``dk_own_*``: ``None``, because the executable then came from a compile
cache that a tree without the scopes filled: a named scope is metadata, and
JAX's compile-cache key leaves metadata out. A metric's own owner absent from
a text that holds others: ``None``. The owner is there and took no time:
``0.0``. ``None`` fails the traced run, by name.
"""

from __future__ import annotations

import re
import time

from benchmarks.harness.trace_reduce import kind, self_ns_by_name
from benchmarks.readers import trace_scope
from benchmarks.readers.trace_moe import RAGGED

#: ``distkeras_tpu/scopes.py``'s, said again: this file also reads a program
#: that has no such module (``tests/benchmarks/test_trace_owner.py`` holds
#: the two equal).
PREFIX = "dk_own_"
OWNERS = ("cast", "embed", "norm", "mixer", "ffn", "conv", "head", "loss")
#: the groups derived from the scopes that were always there, in the order in
#: which a fusion that holds several takes one
DERIVED = ("optimizer", "fold", "guard", "io", "glue")
GROUPS = OWNERS + DERIVED + ("unowned",)
PASSES = ("forward", "recomputed", "backward", "wgrad_update", "none")
COUNTER = "trace.owner_scopes"
#: the scope of which one must be in the text for a derived group to read
NEEDS = {"optimizer": ("dk_optimizer",), "fold": trace_scope.FOLD_SCOPES,
         "guard": ("dk_nan_guard",),
         "io": ("dk_loss_gather", "dk_input_transform"),
         "glue": ("dk_fwd_bwd",)}

#: a group as a bit, to hold what a fusion holds in one integer
_BIT = {g: 1 << i for i, g in enumerate(GROUPS)}
_BIT[None] = 0
_GROUP_OF_BIT = {bit: g for g, bit in _BIT.items() if g}
_OWNER_BITS = (1 << len(OWNERS)) - 1
_OWNER = re.compile(PREFIX + r"([a-z]+)")
_OPCODE = re.compile(r" (get-tuple-element|tuple|parameter|while)\(")
_NAME = re.compile(r"%?([\w.\-]+)")
_INDEX = re.compile(r"index=(\d+)")
_BODY = re.compile(r"body=%?([\w.\-]+)")
_PASS = {"forward": "forward", "remat": "recomputed", "backward": "backward"}


def group_of(op_name: str) -> str | None:
    """The owner or derived group of one ``op_name``; ``None``: no scope of
    ours."""
    found = [o for o in _OWNER.findall(op_name) if o in OWNERS]
    if found:
        return found[-1]
    scopes = trace_scope.label(op_name)[2]
    return next((g for g in DERIVED if scopes.intersection(NEEDS[g])), None)


def pass_of(phase: str) -> str:
    if phase.startswith("mixed:"):
        return "wgrad_update" if "optimizer" in phase[6:].split("+") \
            else "none"
    return _PASS.get(phase, "none")


def classify(hlo: str, names=None, phases=None) -> tuple:
    """``({instruction: (group, pass, other groups it also holds)}, the
    groups the text could hold time of, its lines)`` of a compiled program's
    text, in one pass over it and one call of ``trace_scope.classify``
    (``phases``: that call's result, where the caller has made it), for the
    instructions ``names`` (a trace's: a quarter of the text's) or for all.
    A derived group is one the text could hold time of when its scope is in
    the text; ``unowned`` always is."""
    classes, scopes, _ = phases or trace_scope.classify(hlo)
    groups: dict = {}    # an instruction's op_name(s) -> their groups
    own: dict = {}       # instruction -> (group of each own op_name)
    calls: dict = {}     # fusion instruction -> called computation
    members: dict = {}   # computation -> [(instruction, line, from, to)]
    matmul: set = set()  # the convolutions
    unnamed: set = set()
    computation, lines = None, 0
    instruction, op_name_of = (trace_scope._INSTRUCTION.match,
                               trace_scope._OP_NAME.search)
    for line in hlo.splitlines():
        lines += 1
        m = instruction(line)
        if m is None:
            c = trace_scope._COMPUTATION.match(line)
            if c is not None:
                computation = c.group(1)
                members[computation] = body = []
            continue
        name, at = m.group(1), m.end()
        # what follows the metadata (a Mosaic call's body: 100 kB) names no
        # operand, and nothing before it an op_name
        end = line.find(", metadata={", at)
        n = op_name_of(line, end) if end >= 0 else None
        if end < 0:
            end = len(line)
        if n is None:
            unnamed.add(name)
            own[name] = ()
        else:
            named = n.group(1)
            if named not in groups:  # one name, or several joined by ";"
                groups[named] = tuple(group_of(o) for o in named.split(";"))
            own[name] = groups[named]
        body.append((name, line, at, end))
        called = line.find(", calls=", at, end)
        if called >= 0:
            calls[name] = _NAME.match(line, called + 8).group(1)
        elif line.find(" convolution(", at, end) >= 0:
            matmul.add(name)

    # Who takes what, among the instructions that can have an event of their
    # own (those of a fused computation have none).
    users: dict = {}     # instruction -> [instruction that takes it, ...]
    operands: dict = {}  # instruction -> [instruction it takes, ...]
    loops: list = []     # (while, the tuple it takes, its body)
    roots: dict = {}     # computation -> its ROOT, where that is a tuple
    arguments: dict = {}  # computation -> its parameter(0)
    elements: dict = {}  # (tuple-shaped instruction, i) -> [its element i]
    fused = set(calls.values())
    for computation, body in members.items():
        if computation in fused:
            continue
        for name, line, at, end in body:
            operands[name] = taken = trace_scope._OPERAND.findall(
                line, at, end)
            for operand in taken:
                users.setdefault(operand, []).append(name)
            o = _OPCODE.search(line, at, end)
            if o is None:
                continue
            opcode = o.group(1)
            if opcode == "get-tuple-element":
                index = int(_INDEX.search(line, o.end(), end).group(1))
                elements.setdefault((taken[0], index), []).append(name)
            elif opcode == "tuple":
                if line.startswith("  ROOT "):
                    roots[computation] = name
            elif opcode == "parameter":
                if line.startswith("0)", o.end()):
                    arguments[computation] = name
            else:
                loops.append((name, taken[0], _BODY.search(
                    line, o.end(), end).group(1)))
    # A value a loop carries: what writes element i (of the tuple the loop
    # takes, of its body's ROOT) is read by the get-tuple-elements of index i
    # (of the body's parameter, of the loop).
    for loop, taken, body in loops:
        for source in (taken, roots.get(body)):
            for i, writer in enumerate(operands.get(source, ())):
                readers = elements.get((arguments.get(body), i), []) \
                    + elements.get((loop, i), [])
                users.setdefault(writer, []).extend(readers)
                for reader in readers:
                    operands[reader].append(writer)

    # What an instruction holds, as bits (_BIT): of its own names, and of
    # the instructions of the computation it calls.
    inside: dict = {}  # computation -> (bits held, bits of its convolutions)

    def held(name):
        bits = 0
        for g in own[name]:
            bits |= _BIT[g]
        products = _BIT[own[name][0]] if name in matmul and own[name] else 0
        called = calls.get(name)
        if called is not None:
            if called not in inside:
                inner, inner_products = 0, 0
                for member, _, _, _ in members.get(called, ()):
                    got = held(member)
                    inner |= got[0]
                    inner_products |= got[1]
                inside[called] = (inner, inner_products)
            bits |= inside[called][0]
            products |= inside[called][1]
        return bits, products

    def most(name, owners):
        """The owner of ``owners`` (bits) that most instructions of ``name``
        and of what it calls carry; the earlier of ``OWNERS`` on a tie."""
        count = dict.fromkeys((g for g in OWNERS if _BIT[g] & owners), 0)
        todo = [name]
        while todo:
            at = todo.pop()
            for g in own[at]:
                if g in count:
                    count[g] += 1
            todo.extend(m for m, _, _, _ in members.get(calls.get(at), ()))
        return max(count, key=count.get)  # max keeps the first on a tie

    settled: dict = {}   # instruction -> (group or None, bits of the others)

    def of(name):
        if name not in settled:
            bits, products = held(name)
            group = next((g for g in own[name] if g in OWNERS), None)
            if group is None:
                owners = bits & _OWNER_BITS & ~_BIT["cast"]
                products &= owners
                if owners & (owners - 1) == 0:      # one owner, or none
                    group = _GROUP_OF_BIT.get(owners)
                elif products & (products - 1) == 0 and products:
                    group = _GROUP_OF_BIT[products]
                else:
                    group = most(name, owners)
            if group is None:
                group = next((g for g in own[name] if g is not None), None) \
                    or next((g for g in DERIVED if _BIT[g] & bits), None) \
                    or ("cast" if bits & _BIT["cast"] else None)
            settled[name] = (group, bits & ~_BIT[group])
        return settled[name]

    def passed(name):
        """Whether ``name`` stands for its neighbours: it has no ``op_name``
        or is a ``ragged-dot-*`` call, and holds no group's instruction."""
        return (name in unnamed or name.startswith(RAGGED)) \
            and of(name)[0] is None

    def through(name, edges, seen):
        """The one owner, else the one derived group, that the instructions
        along ``edges`` from ``name`` have, one that :func:`passed` standing
        for those beyond it; ``None`` where they have none or several."""
        if name not in seen:
            seen[name] = None
            found = 0
            for n in edges.get(name, ()):
                if n in own:
                    found |= _BIT[through(n, edges, seen) if passed(n)
                                  else of(n)[0]]
            found = found & _OWNER_BITS or found
            if found & (found - 1) == 0:
                seen[name] = _GROUP_OF_BIT.get(found)
        return seen[name]

    by_use, by_operand = {}, {}
    others_of: dict = {}  # bits -> the groups, as a frozenset
    passes: dict = {}     # a phase of trace_scope's -> pass_of(it)
    out = {}
    for name in own if names is None else names:
        if name not in own:
            continue
        group, others = of(name)
        if passed(name):
            ways = (through(name, users, by_use),
                    through(name, operands, by_operand))
            group = next((g for g in ways if g in OWNERS), ways[0] or ways[1])
        if others not in others_of:
            others_of[others] = frozenset(
                g for g in GROUPS if _BIT[g] & others)
        phase = classes.get(name, ("other",))[0]
        if phase not in passes:
            passes[phase] = pass_of(phase)
        out[name] = (group or "unowned", passes[phase], others_of[others])
    present = {g for named in groups.values() for g in named
               if g in OWNERS} | {
        g for g in DERIVED if scopes.intersection(NEEDS[g])} | {"unowned"}
    return out, present, lines


def reduce(hlo: str, events, lo, hi) -> dict:
    """Self time in ns of ``events`` (``(start_ns, dur_ns, instruction)``)
    inside ``[lo, hi]``: ``{"ns": {group: {pass: ns}}, "pairs": {(group,
    other group the instruction also holds): ns}, "stems": {"unowned" and
    "glue": {instruction name less its number: ns}}, "present": the groups
    the text holds, "lines": its lines, "seconds": what reading took, by
    part}``. The sum over ``ns`` is the events' busy time."""
    t0 = time.perf_counter()
    self_ns = self_ns_by_name(events, lo, hi)
    t1 = time.perf_counter()
    phases = trace_scope.classify(hlo)
    t2 = time.perf_counter()
    by, present, lines = classify(hlo, self_ns, phases)
    seconds = {"self time": t1 - t0, "trace_scope.classify": t2 - t1,
               "own pass": time.perf_counter() - t2}
    ns = {g: dict.fromkeys(PASSES, 0.0) for g in GROUPS}
    pairs: dict = {}
    stems: dict = {"unowned": {}, "glue": {}}
    for name, t in self_ns.items():
        group, which, others = by.get(name, ("unowned", "none", ()))
        ns[group][which] += t
        for other in others:
            pairs[group, other] = pairs.get((group, other), 0.0) + t
        if group in stems:
            stems[group][kind(name)] = stems[group].get(kind(name), 0.0) + t
    return {"ns": ns, "pairs": pairs, "stems": stems, "present": present,
            "lines": lines, "seconds": seconds}


def _say(msg: str) -> None:
    print(f"[bench] trace_owner: {msg}", flush=True)


def _program_state() -> str:
    """``"predates"``: the program's registry declares no counter of owner
    scopes; ``"none opened"``: declared, and this process opened none;
    ``"opened"``."""
    from distkeras_tpu import telemetry
    from distkeras_tpu.telemetry import registry

    if not registry.declared("counter", COUNTER):
        return "predates"
    return "opened" if telemetry.counter(COUNTER).value > 0 else "none opened"


def _reduced(run):
    """The reduction of this run's trace, made once and kept on ``run``
    (eleven metrics, one parse) and said on three ``[bench`` lines; ``0.0``
    where the program predates the scopes, ``None`` where it opened them and
    the text does not show them."""
    if hasattr(run, "trace_owner"):
        return run.trace_owner
    state = _program_state()
    if state == "predates":
        _say(f"the program's telemetry registry declares no counter "
             f"{COUNTER}: it predates the owner scopes, so every owner.* "
             "metric reads 0")
        run.trace_owner = 0.0
        return 0.0
    if state == "none opened":
        _say(f"the counter {COUNTER} is declared and reads 0: this cell's "
             "model opened no owner scope as it was traced")
        run.trace_owner = None
        return None
    t, t0 = run.trace, time.perf_counter()
    got = reduce(run.hlo, t["ops0"], t["lo"], t["hi"])
    took = time.perf_counter() - t0
    if not got["present"].intersection(OWNERS):
        _say(f"the counter {COUNTER} reads above 0 and the compiled text "
             f"holds no {PREFIX}* scope: the executable came from a compile "
             "cache filled by a tree without the owner scopes; run with an "
             "empty JAX_COMPILATION_CACHE_DIR")
        run.trace_owner = None
        return None
    per_round = 1e-6 / t["rounds"]
    totals = {g: sum(got["ns"][g].values()) for g in GROUPS}
    busy = sum(totals.values()) or 1.0
    _say("ms/round by owner, " + "/".join(PASSES) + ": " + "; ".join(
        f"{g} " + "/".join(f"{got['ns'][g][p] * per_round:.3f}"
                           for p in PASSES) + f" = {totals[g] * per_round:.3f}"
        for g in GROUPS if g in got["present"] or totals[g])
        + "; by pass " + "/".join(
            f"{sum(got['ns'][g][p] for g in GROUPS) * per_round:.3f}"
            for p in PASSES)
        + f"; sum {busy * per_round:.3f}; unowned+glue "
        f"{(totals['unowned'] + totals['glue']) / busy:.2%} of busy; read in "
        f"{took:.2f} s from {got['lines']} lines (" + ", ".join(
            f"{part} {s:.2f}" for part, s in got["seconds"].items()) + ")")
    pairs = sorted(got["pairs"].items(), key=lambda kv: -kv[1])[:24]
    _say("ms/round of instructions that also hold another's, owner<-other: "
         + (", ".join(f"{g}<-{o} {ns * per_round:.3f}"
                      for (g, o), ns in pairs) or "none"))
    _say("; ".join(
        f"{g} by stem: " + (", ".join(
            f"{stem} {ns * per_round:.3f}" for stem, ns in sorted(
                got["stems"][g].items(), key=lambda kv: -kv[1])[:8]) or "none")
        for g in ("unowned", "glue")))
    run.trace_owner = got
    return got


def read(run, owner):
    """ms/round of device 0's self time that ``owner`` owns: one of
    ``GROUPS``, or a list of them, summed (``["unowned", "glue"]``)."""
    t = run.trace
    if not t or not t["rounds"]:
        return None
    got = _reduced(run)
    if not got:
        return got  # 0.0: the program predates the scopes; None: said above
    names = [owner] if isinstance(owner, str) else list(owner)
    if not set(names) <= got["present"]:
        return None
    return sum(sum(got["ns"][g].values()) for g in names) * 1e-6 / t["rounds"]
