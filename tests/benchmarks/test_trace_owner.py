"""The reader ``trace_owner`` (device time by the sublayer that owns each
instruction) on hand-made text and events, as ``test_trace_scope.py`` does for
the phases; and the eleven ``owner.*`` entries of the manifest, by name."""

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness import result_line, trace_reduce  # noqa: E402
from benchmarks.readers import trace_owner, trace_scope  # noqa: E402

STEP = "jit(round_fn)/dk_local_steps/while/body/closed_call"
FWD = f"{STEP}/dk_fwd_bwd/jvp(LM)"
BWD = f"{STEP}/dk_fwd_bwd/transpose(jvp(LM))"
REMAT = f"{BWD}/checkpoint/rematted_computation"
CAST = f"{STEP}/dk_fwd_bwd/jvp(dk_own_cast)/convert_element_type"
UPDATE = f"{STEP}/dk_optimizer/add"

#: A round program in miniature, as the TPU compiler prints one: fused
#: computations first, then the body of the loop over steps, then the entry.
HLO = f"""HloModule jit_round_fn, is_scheduled=true

%fused_computation.1 (p0: bf16[8,64], p1: f32[64,64]) -> bf16[8,64] {{
  %p0 = bf16[8,64]{{1,0}} parameter(0)
  %p1 = f32[64,64]{{1,0}} parameter(1)
  %convert.1 = bf16[64,64]{{1,0}} convert(%p1), metadata={{op_name="{CAST}"}}
  ROOT %dot.1 = bf16[8,64]{{1,0}} convolution(%p0, %convert.1), metadata={{op_name="{FWD}/block_0/mlp/dk_own_ffn/up/dot_general"}}
}}

%fused_computation.2 (p0: bf16[8,64], p1: f32[64,64]) -> f32[64,64] {{
  %p0.1 = bf16[8,64]{{1,0}} parameter(0)
  %p1.1 = f32[64,64]{{1,0}} parameter(1)
  %dot.2 = f32[64,64]{{1,0}} convolution(%p0.1, %p0.1), metadata={{op_name="{BWD}/block_0/mlp/dk_own_ffn/up/transpose/dot_general"}}
  %convert.2 = bf16[64,64]{{1,0}} convert(%p1.1), metadata={{op_name="{CAST}"}}
  ROOT %add.1 = f32[64,64]{{1,0}} add(%dot.2, %p1.1), metadata={{op_name="{UPDATE}"}}
}}

%fused_computation.3 (p0: bf16[8,64]) -> bf16[8,64] {{
  %p0.2 = bf16[8,64]{{1,0}} parameter(0)
  %mul.1 = bf16[8,64]{{1,0}} multiply(%p0.2, %p0.2), metadata={{op_name="{FWD}/block_0/ln/dk_own_norm/mul"}}
  ROOT %add.2 = bf16[8,64]{{1,0}} add(%mul.1, %p0.2), metadata={{op_name="{FWD}/block_0/add"}}
}}

%fused_computation.4 (p0: bf16[8,64], p1: bf16[64,512]) -> f32[8,512] {{
  %p0.3 = bf16[8,64]{{1,0}} parameter(0)
  %p1.3 = bf16[64,512]{{1,0}} parameter(1)
  %mul.2 = bf16[8,64]{{1,0}} multiply(%p0.3, %p0.3), metadata={{op_name="{FWD}/ln_final/dk_own_norm/mul"}}
  %rsqrt.1 = bf16[8,64]{{1,0}} rsqrt(%mul.2), metadata={{op_name="{FWD}/ln_final/dk_own_norm/rsqrt"}}
  %dot.3 = f32[8,512]{{1,0}} convolution(%rsqrt.1, %p1.3), metadata={{op_name="{FWD}/dk_own_head/lm_head/dot_general"}}
  ROOT %sub.1 = f32[8,512]{{1,0}} subtract(%dot.3, %dot.3), metadata={{op_name="{STEP}/dk_fwd_bwd/jvp(dk_own_loss)/sub"}}
}}

%fused_computation.5 (p0: f32[64,64]) -> f32[64,64] {{
  %p0.4 = f32[64,64]{{1,0}} parameter(0)
  %convert.3 = bf16[64,64]{{1,0}} convert(%p0.4), metadata={{op_name="{CAST}"}}
  ROOT %add.3 = f32[64,64]{{1,0}} add(%p0.4, %p0.4), metadata={{op_name="{UPDATE}"}}
}}

%fused_computation.6 (p0: bf16[8,64]) -> bf16[8,64] {{
  %p0.5 = bf16[8,64]{{1,0}} parameter(0)
  %gather.1 = bf16[8,64]{{1,0}} negate(%p0.5), metadata={{op_name="{BWD}/dk_own_embed/tok_embed/scatter-add"}}
  %mul.3 = bf16[8,64]{{1,0}} multiply(%gather.1, %p0.5), metadata={{op_name="{BWD}/block_0/ln/dk_own_norm/mul"}}
  ROOT %mul.4 = bf16[8,64]{{1,0}} multiply(%mul.3, %p0.5), metadata={{op_name="{BWD}/block_0/ln/dk_own_norm/mul"}}
}}

%body (arg: (f32[64,64], f32[64,64])) -> (f32[64,64], f32[64,64]) {{
  %arg = (f32[64,64]{{1,0}}, f32[64,64]{{1,0}}) parameter(0)
  %get-tuple-element.1 = f32[64,64]{{1,0}} get-tuple-element(%arg), index=0
  %get-tuple-element.2 = f32[64,64]{{1,0}} get-tuple-element(%arg), index=1
  %fusion.1 = bf16[8,64]{{1,0}} fusion(%x, %get-tuple-element.1), kind=kOutput, calls=%fused_computation.1, metadata={{op_name="{FWD}/block_0/mlp/dk_own_ffn/up/dot_general"}}
  %copy.1 = bf16[8,64]{{1,0}} copy(%fusion.1)
  %dk_flash_fwd.1 = bf16[16,128,32]{{2,1,0}} custom-call(%copy.1), custom_call_target="tpu_custom_call", metadata={{op_name="{REMAT}/block_0/attn/dk_own_mixer/dk_flash_fwd/dk_flash_fwd/pallas_call"}}, backend_config={{"body":"%not-an-operand"}}
  %fusion.3 = bf16[8,64]{{1,0}} fusion(%dk_flash_fwd.1), kind=kLoop, calls=%fused_computation.3, metadata={{op_name="{FWD}/block_0/add"}}
  %copy.2 = bf16[8,64]{{1,0}} copy(%fusion.3)
  %fusion.4 = f32[8,512]{{1,0}} fusion(%copy.2, %w), kind=kOutput, calls=%fused_computation.4, metadata={{op_name="{STEP}/dk_fwd_bwd/jvp(dk_own_loss)/sub"}}
  %fusion.6 = bf16[8,64]{{1,0}} fusion(%copy.2), kind=kLoop, calls=%fused_computation.6
  %add.4 = bf16[8,64]{{1,0}} add(%fusion.6, %fusion.6), metadata={{op_name="{BWD}/block_0/add"}}
  %ragged-dot-none.1 = f32[64,64]{{1,0}} custom-call(%fusion.1, %add.4), custom_call_target="tpu_custom_call", metadata={{op_name="ragged-dot-none"}}
  %fusion.2 = f32[64,64]{{1,0}} fusion(%fusion.1, %ragged-dot-none.1), kind=kOutput, calls=%fused_computation.2, metadata={{op_name="{UPDATE}"}}
  %fusion.5 = f32[64,64]{{1,0}} fusion(%get-tuple-element.2), kind=kLoop, calls=%fused_computation.5, metadata={{op_name="{UPDATE}"}}
  %copy.3 = f32[64,64]{{0,1}} copy(%fusion.5)
  ROOT %tuple.1 = (f32[64,64]{{1,0}}, f32[64,64]{{0,1}}) tuple(%copy.3, %fusion.2)
}}

ENTRY %main (Arg_0: f32[64,64]) -> (f32[64,64]) {{
  %squeeze.1 = f32[64,64]{{1,0}} bitcast(%Arg_0), metadata={{op_name="jit(round_fn)/squeeze"}}
  %copy.4 = f32[64,64]{{0,1}} copy(%squeeze.1)
  %tuple.2 = (f32[64,64]{{1,0}}, f32[64,64]{{0,1}}) tuple(%squeeze.1, %copy.4)
  %while.1 = (f32[64,64]{{1,0}}, f32[64,64]{{0,1}}) while(%tuple.2), condition=%cond, body=%body, metadata={{op_name="jit(round_fn)/dk_local_steps/while"}}
  %get-tuple-element.3 = f32[64,64]{{1,0}} get-tuple-element(%while.1), index=0
  %fusion.7 = f32[64,64]{{1,0}} fusion(%get-tuple-element.3), kind=kLoop, calls=%fused_computation.7, metadata={{op_name="jit(round_fn)/dk_fold/sub"}}
  %fusion.8 = pred[] fusion(%fusion.7), kind=kLoop, calls=%fused_computation.8, metadata={{op_name="jit(round_fn)/dk_nan_guard/is_finite"}}
  ROOT %fusion.9 = f32[1]{{0}} fusion(%fusion.8), kind=kLoop, calls=%fused_computation.9, metadata={{op_name="jit(round_fn)/dk_loss_gather/all_gather"}}
}}
"""

#: instruction -> (owner, pass, the rule that gives it)
EXPECTED = {
    "fusion.1": ("ffn", "forward"),          # its own name; a cast inside
    "copy.1": ("mixer", "recomputed"),       # unnamed: its one user's
    "dk_flash_fwd.1": ("mixer", "recomputed"),
    "fusion.3": ("norm", "forward"),         # named glue; calls agree on norm
    "copy.2": ("norm", "none"),              # users disagree: its operand's
    "fusion.4": ("loss", "forward"),         # its own name, whatever it holds
    "fusion.6": ("norm", "backward"),        # no name, two owners: the most
    "add.4": ("glue", "backward"),           # under dk_fwd_bwd, no owner
    "ragged-dot-none.1": ("ffn", "none"),    # user optimizer; operand ffn
    "fusion.2": ("ffn", "wgrad_update"),     # named for the update: matmul's
    "fusion.5": ("optimizer", "wgrad_update"),  # a cast claims no update
    "copy.3": ("ffn", "none"),               # carried: next step's reader
    "copy.4": ("optimizer", "none"),         # carried into the loop
    "squeeze.1": ("unowned", "none"),        # no scope of ours
    "while.1": ("unowned", "none"),
    "fusion.7": ("fold", "none"),
    "fusion.8": ("guard", "none"),
    "fusion.9": ("io", "none"),
}

#: ``(start_ns, dur_ns, instruction)``: the while encloses its body's ops.
EVENTS = [(0, 5, "squeeze.1"), (5, 15, "copy.4"), (20, 1000, "while.1")] + [
    (20 + 70 * i, 60 + i, name) for i, name in enumerate((
        "fusion.1", "copy.1", "dk_flash_fwd.1", "fusion.3", "copy.2",
        "fusion.4", "fusion.6", "add.4", "ragged-dot-none.1", "fusion.2",
        "fusion.5", "copy.3"))] + [
    (1020, 70, "fusion.7"), (1090, 20, "fusion.8"), (1110, 10, "fusion.9"),
    (1120, 5, "not-in-the-text")]


def test_each_instruction_gets_its_owner_and_pass():
    by, present, lines = trace_owner.classify(HLO)
    assert {n: by[n][:2] for n in EXPECTED} == EXPECTED
    assert lines == len(HLO.splitlines())
    assert present == {"cast", "embed", "norm", "mixer", "ffn", "head",
                       "loss", "optimizer", "fold", "guard", "io", "glue",
                       "unowned"}
    # what else an instruction holds: how much hangs on the rule
    assert by["fusion.1"][2] == {"cast"}
    assert by["fusion.2"][2] == {"optimizer", "cast"}
    assert by["fusion.4"][2] == {"norm", "head"}
    assert by["fusion.6"][2] == {"embed"}
    # asked for the names of a trace alone, it answers the same for them
    some, _, _ = trace_owner.classify(HLO, names=["copy.3", "gone"])
    assert some == {"copy.3": by["copy.3"]}


def test_a_fusion_of_two_owners_goes_to_its_matmuls():
    """No name of its own, a norm's two instructions and the head's one: the
    convolution decides, not the count."""
    text = HLO.replace(
        ', metadata={op_name="' + f"{STEP}/dk_fwd_bwd/jvp(dk_own_loss)/sub"
        + '"}\n  %fusion.6', "\n  %fusion.6").replace(
        "ROOT %sub.1 = f32[8,512]{1,0} subtract(%dot.3, %dot.3), metadata="
        '{op_name="' + f"{STEP}/dk_fwd_bwd/jvp(dk_own_loss)/sub" + '"}',
        "ROOT %sub.1 = f32[8,512]{1,0} subtract(%dot.3, %dot.3)")
    assert text != HLO
    by, _, _ = trace_owner.classify(text)
    assert by["fusion.4"] == ("head", "forward", frozenset({"norm"}))


def test_owners_partition_the_busy_time_as_the_phases_do():
    got = trace_owner.reduce(HLO, EVENTS, 0, 2000)
    totals = {g: sum(p.values()) for g, p in got["ns"].items()}
    busy = trace_reduce.busy_ns(EVENTS, 0, 2000)
    assert sum(totals.values()) == busy
    phases = trace_scope.reduce(HLO, EVENTS, 0, 2000)["phases"]
    assert sum(phases.values()) == busy
    by_pass = {p: sum(got["ns"][g][p] for g in trace_owner.GROUPS)
               for p in trace_owner.PASSES}
    assert by_pass["forward"] == phases["forward"]
    assert by_pass["recomputed"] == phases["remat"]
    assert by_pass["backward"] == phases["backward"]
    assert by_pass["wgrad_update"] == phases["mixed"] == 69.0 + 70.0
    durations = {name: dur for _, dur, name in EVENTS}
    assert totals["ffn"] == sum(
        durations[n] for n in ("fusion.1", "ragged-dot-none.1", "fusion.2",
                               "copy.3"))
    # the squeeze, the stray event, and the while's own time: what the
    # twelve ops of its body leave uncovered
    assert totals["unowned"] == 5 + 5 + (1000 - sum(60 + i for i in range(12)))
    assert got["pairs"]["ffn", "optimizer"] == durations["fusion.2"]
    assert got["pairs"]["ffn", "cast"] == durations["fusion.1"] \
        + durations["fusion.2"]
    assert got["stems"]["glue"] == {"add": durations["add.4"]}
    assert set(got["stems"]["unowned"]) == {"squeeze", "while",
                                            "not-in-the-text"}
    # clipped to the bracket like every other reduction
    clipped = trace_owner.reduce(HLO, EVENTS, 50, 400)
    assert sum(sum(p.values()) for p in clipped["ns"].values()) \
        == trace_reduce.busy_ns(EVENTS, 50, 400)


def _run(hlo=HLO, events=EVENTS, rounds=2):
    return types.SimpleNamespace(
        trace={"ops0": events, "lo": 0, "hi": 2000, "rounds": rounds},
        hlo=hlo)


def _program(monkeypatch, declared=True, opened=3.0):
    """The program's side of the reader: whether its registry declares the
    counter of owner scopes, and what the counter reads in this process."""
    from distkeras_tpu import telemetry
    from distkeras_tpu.telemetry import registry

    real = registry.declared
    monkeypatch.setattr(
        registry, "declared", lambda kind, name: declared
        if (kind, name) == ("counter", trace_owner.COUNTER)
        else real(kind, name))
    monkeypatch.setattr(
        telemetry, "counter", lambda name: types.SimpleNamespace(value=opened))


def test_read_gives_ms_per_round_and_sums_a_list(monkeypatch, capsys):
    _program(monkeypatch)
    run = _run()
    durations = {name: dur for _, dur, name in EVENTS}
    assert trace_owner.read(run, "loss") \
        == pytest.approx(durations["fusion.4"] * 1e-6 / 2)
    assert trace_owner.read(run, ["unowned", "glue"]) == pytest.approx(
        trace_owner.read(run, "unowned") + trace_owner.read(run, "glue"))
    said = [line for line in capsys.readouterr().out.splitlines()
            if line.startswith("[bench] trace_owner:")]
    assert len(said) == 3  # the table, the pairs, the stems: once a run
    assert "read in" in said[0] and "unowned+glue" in said[0]
    assert "ffn<-optimizer" in said[1] and "glue by stem: add" in said[2]


def test_a_program_that_predates_the_scopes_reads_zero(monkeypatch, capsys):
    _program(monkeypatch, declared=False)
    run = _run(hlo=HLO.replace("dk_own_", "x_"))
    assert [trace_owner.read(run, o) for o in ("mixer", ["unowned", "glue"])] \
        == [0.0, 0.0]
    said = capsys.readouterr().out
    assert said.count("predates the owner scopes") == 1
    # without a trace, or without a whole round, there is nothing to read
    assert trace_owner.read(_run(rounds=0), "mixer") is None
    assert trace_owner.read(types.SimpleNamespace(trace=None), "mixer") is None


def test_a_stale_executable_is_told_from_a_program_without_scopes(
        monkeypatch, capsys):
    """The lowering this process made opened the scopes, the text shows none:
    the compile cache served an executable of a tree without them."""
    _program(monkeypatch, declared=True, opened=41.0)
    run = _run(hlo=HLO.replace("dk_own_", "x_"))
    assert trace_owner.read(run, "mixer") is None
    assert trace_owner.read(run, ["unowned", "glue"]) is None
    said = capsys.readouterr().out
    assert said.count("came from a compile cache filled by a tree without "
                      "the owner scopes") == 1
    assert "empty JAX_COMPILATION_CACHE_DIR" in said
    # declared and never opened: the cell's model dropped its scopes
    _program(monkeypatch, declared=True, opened=0.0)
    assert trace_owner.read(_run(), "mixer") is None
    assert "opened no owner scope" in capsys.readouterr().out


def test_an_owner_absent_is_none_and_an_idle_one_is_zero(monkeypatch):
    _program(monkeypatch)
    assert trace_owner.read(_run(), "conv") is None  # others are there
    dropped = _run(hlo=HLO.replace("dk_own_mixer", "mixer"))
    assert trace_owner.read(dropped, "mixer") is None
    assert trace_owner.read(dropped, "ffn") > 0.0
    no_update = _run(hlo=HLO.replace("dk_optimizer", "optimizer"))
    assert trace_owner.read(no_update, "optimizer") is None
    idle = _run(events=[e for e in EVENTS if e[2] != "fusion.4"])
    assert trace_owner.read(idle, "loss") == 0.0


def test_many_reads_make_one_parse(monkeypatch):
    _program(monkeypatch)
    calls = []
    real = trace_owner.reduce
    monkeypatch.setattr(trace_owner, "reduce", lambda *a, **k: (
        calls.append(1), real(*a, **k))[1])
    classified = []
    real_classify = trace_scope.classify
    monkeypatch.setattr(trace_scope, "classify", lambda hlo: (
        classified.append(1), real_classify(hlo))[1])
    run = _run()
    for owner in trace_owner.GROUPS:
        trace_owner.read(run, owner)
    assert calls == [1] and classified == [1]


def test_the_reader_and_the_program_share_one_vocabulary():
    from distkeras_tpu import scopes

    assert (trace_owner.PREFIX, trace_owner.OWNERS) \
        == (scopes.PREFIX, scopes.OWNERS)
    assert not set(trace_owner.OWNERS) & set(trace_owner.DERIVED)


def _json(*parts):
    with open(os.path.join(ROOT, *parts), encoding="utf-8") as f:
        return json.load(f)


LM = ["gpt2m_aeasgd_w1", "smallthinker_aeasgd_w1", "lfm2_aeasgd_w1",
      "kimi_linear_aeasgd_w1"]
IMG = ["resnet50_sync_mem"]
METRICS = {
    "owner.mixer_ms.lm": ("Models", "mixer", LM),
    "owner.ffn_ms.lm": ("Models", "ffn", LM),
    "owner.head_ms.lm": ("Models", "head", LM),
    "owner.loss_ms.lm": ("Models", "loss", LM),
    "owner.embed_ms.lm": ("Models", "embed", LM),
    "owner.norm_ms.lm": ("Models", "norm", LM),
    "owner.optimizer_ms.lm": ("Local steps", "optimizer", LM),
    "owner.unowned_ms.lm": ("Local steps", ["unowned", "glue"], LM),
    "owner.conv_ms.img": ("Models", "conv", IMG),
    "owner.norm_ms.img": ("Models", "norm", IMG),
    "owner.unowned_ms.img": ("Local steps", ["unowned", "glue"], IMG),
}


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_metric_is_in_the_manifest_with_its_file(name):
    layer, owner, cells = METRICS[name]
    entry = next(m for m in _json("BENCHMARK.json")["per_layer"]
                 if m["name"] == name)
    assert entry == {
        "name": name, "unit": "ms/round", "better": "lower",
        "source": "device_trace", "layer": layer, "workloads": cells,
        "moves": "tokens_per_s_chip" if cells is LM else "samples_per_s_chip"}
    assert _json("benchmarks", "layer_metrics", f"{name}.json") == {
        "layer": layer, "reader": "trace_owner", "arguments": {"owner": owner}}
    groups = [owner] if isinstance(owner, str) else owner
    assert set(groups) <= set(trace_owner.GROUPS)


@pytest.mark.parametrize("cell", LM + IMG)
def test_a_traced_run_of_the_cell_declares_its_owner_metrics(cell):
    """``test_benchmark.py`` rehearses every cell with ``--trace 1`` and holds
    the line to what the cell declares: these names are among that."""
    declared = result_line.declared_metrics(_json("BENCHMARK.json"), cell,
                                            traced=True)
    assert {n for n in declared if n.startswith("owner.")} \
        == {n for n, (_, _, cells) in METRICS.items() if cell in cells}
    assert all(declared[n] == "ms/round" for n in METRICS if n in declared)
