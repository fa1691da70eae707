"""The readers this PR's per-layer metrics rest on: ``trace_scope`` (device
time by the program's phases and kernels, the flash roofline) and
``timeline`` (set-up by the program's own spans), on hand-made inputs."""

import glob
import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness import peaks, trace_reduce  # noqa: E402
from benchmarks.readers import timeline, trace_busy, trace_scope  # noqa: E402

STEP = "jit(round_fn)/dk_local_steps/while/body/closed_call"
FWD = f"{STEP}/dk_fwd_bwd/jvp(TransformerLM)"
BWD = f"{STEP}/dk_fwd_bwd/transpose(jvp(TransformerLM))/dk_fwd_bwd/jvp(TransformerLM)"

#: A round program in miniature, as the TPU compiler prints one: fused
#: computations first, each instruction with its own ``op_name``.
HLO = f"""HloModule jit_round_fn, is_scheduled=true

%fused_computation.1 (p0: bf16[8,64], p1: bf16[64,64]) -> bf16[8,64] {{
  %p0 = bf16[8,64]{{1,0}} parameter(0)
  %p1 = bf16[64,64]{{1,0}} parameter(1)
  %convert.1 = bf16[64,64]{{1,0}} convert(%p1), metadata={{op_name="{STEP}/dk_fwd_bwd/jvp()/convert_element_type"}}
  ROOT %dot.1 = bf16[8,64]{{1,0}} convolution(%p0, %convert.1), metadata={{op_name="{FWD}/block_0/mlp_up/dot_general"}}
}}

%fused_computation.2 (p0: f32[64,64], p1: f32[64,64]) -> f32[64,64] {{
  %p0.1 = f32[64,64]{{1,0}} parameter(0)
  %p1.1 = f32[64,64]{{1,0}} parameter(1)
  %mul.1 = f32[64,64]{{1,0}} multiply(%p0.1, %p1.1), metadata={{op_name="{BWD}/block_0/mlp_up/transpose/mul"}}
  ROOT %add.1 = f32[64,64]{{1,0}} add(%mul.1, %p1.1), metadata={{op_name="{STEP}/dk_optimizer/add"}}
}}

%fused_computation.3 (p0: f32[64,64], p1: f32[64,64]) -> f32[64,64] {{
  %p0.2 = f32[64,64]{{1,0}} parameter(0)
  %convert.2 = bf16[64,64]{{1,0}} convert(%p0.2), metadata={{op_name="{STEP}/dk_fwd_bwd/jvp()/convert_element_type"}}
  ROOT %dot.2 = f32[64,64]{{1,0}} convolution(%convert.2, %convert.2), metadata={{op_name="{BWD}/block_0/mlp_up/transpose/dot_general"}}
}}

%body (arg: (f32[64,64])) -> (f32[64,64]) {{
  %fusion.1 = bf16[8,64]{{1,0}} fusion(%a, %b), kind=kOutput, calls=%fused_computation.1, metadata={{op_name="{FWD}/block_0/mlp_up/dot_general"}}
  %dk_flash_fwd.1 = bf16[16,128,32]{{2,1,0}} custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", metadata={{op_name="{FWD}/block_0/attn/dk_flash_fwd/dk_flash_fwd/pallas_call"}}
  %dk_flash_fwd.2 = bf16[16,128,32]{{2,1,0}} custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", metadata={{op_name="{BWD}/checkpoint/rematted_computation/block_0/attn/dk_flash_fwd/dk_flash_fwd/pallas_call"}}
  %fusion.2 = bf16[8,64]{{1,0}} fusion(%a, %b), kind=kLoop, calls=%fused_computation.9, metadata={{op_name="{BWD}/checkpoint/rematted_computation/block_0/mlp_up/dot_general"}}
  %dk_flash_dq.1 = bf16[16,128,32]{{2,1,0}} custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", metadata={{op_name="{BWD}/checkpoint/block_0/attn/dk_flash_dq/dk_flash_dq/pallas_call"}}
  %fusion.3 = f32[64,64]{{1,0}} fusion(%g, %h), kind=kLoop, calls=%fused_computation.3, metadata={{op_name="{BWD}/block_0/mlp_up/transpose/dot_general"}}
  %fusion.4 = f32[64,64]{{1,0}} fusion(%g, %h), kind=kLoop, calls=%fused_computation.7, metadata={{op_name="{STEP}/dk_optimizer/mul"}}
  %fusion.5 = f32[64,64]{{1,0}} fusion(%g, %h), kind=kLoop, calls=%fused_computation.2, metadata={{op_name="{STEP}/dk_optimizer/add"}}
  %fusion.6 = f32[64,64]{{1,0}} fusion(%g, %h), kind=kLoop, calls=%fused_computation.6, metadata={{op_name="{BWD}/x/mul;{STEP}/dk_optimizer/sub"}}
  ROOT %tuple.1 = (f32[64,64]{{1,0}}) tuple(%fusion.5)
}}

ENTRY %main (Arg_0: f32[64,64]) -> (f32[64,64]) {{
  %while.1 = (f32[64,64]{{1,0}}) while(%arg), condition=%cond, body=%body, metadata={{op_name="jit(round_fn)/dk_local_steps/while"}}
  %fusion.7 = f32[64,64]{{1,0}} fusion(%c, %l), kind=kLoop, calls=%fused_computation.5, metadata={{op_name="jit(round_fn)/dk_fold/sub"}}
  %copy.1 = f32[64,64]{{1,0}} copy(%fusion.7)
  ROOT %fusion.8 = f32[1]{{0}} fusion(%x), kind=kLoop, calls=%fused_computation.4, metadata={{op_name="jit(round_fn)/dk_loss_gather/all_gather"}}
}}
"""

#: ``(start_ns, dur_ns, instruction)``: the while encloses its body's ops.
EVENTS = [
    (0, 1000, "while.1"),
    (0, 100, "fusion.1"),          # forward
    (100, 200, "dk_flash_fwd.1"),  # forward, kernel
    (300, 150, "dk_flash_fwd.2"),  # remat, kernel
    (450, 50, "fusion.2"),         # remat
    (500, 250, "dk_flash_dq.1"),   # backward, kernel
    (750, 100, "fusion.3"),        # backward, a forward cast copied in
    (850, 60, "fusion.4"),         # optimizer
    (910, 40, "fusion.5"),         # backward + optimizer inside: mixed
    (950, 30, "fusion.6"),         # ';'-joined backward + optimizer: mixed
    (1000, 70, "fusion.7"),        # fold
    (1070, 20, "copy.1"),          # no metadata: other
    (1090, 10, "fusion.8"),        # dk_loss_gather: other
    (1100, 5, "not-in-the-text"),  # other
]


def test_each_instruction_gets_its_phase_and_kernel():
    classes, scopes, control = trace_scope.classify(HLO)
    assert control == {"while.1"}
    assert classes["fusion.1"] == ("forward", None)
    assert classes["dk_flash_fwd.1"] == ("forward", "dk_flash_fwd")
    assert classes["dk_flash_fwd.2"] == ("remat", "dk_flash_fwd")
    assert classes["fusion.2"] == ("remat", None)
    assert classes["dk_flash_dq.1"] == ("backward", "dk_flash_dq")
    assert classes["fusion.3"] == ("backward", None)
    assert classes["fusion.4"] == ("optimizer", None)
    assert classes["fusion.5"] == ("mixed:backward+optimizer", None)
    assert classes["fusion.6"] == ("mixed:backward+optimizer", None)
    assert classes["fusion.7"] == ("fold", None)
    assert classes["copy.1"] == ("other", None)
    assert classes["fusion.8"] == ("other", None)
    assert classes["while.1"] == ("other", None)
    assert {"dk_local_steps", "dk_fwd_bwd", "dk_optimizer", "dk_fold",
            "dk_loss_gather", "dk_flash_fwd", "dk_flash_dq"} == scopes


def test_phases_partition_the_busy_time_and_kernels_cut_across():
    got = trace_scope.reduce(HLO, EVENTS, 0, 2000)
    assert got["phases"] == {
        "forward": 300.0, "backward": 350.0, "remat": 200.0,
        "optimizer": 60.0, "fold": 70.0, "mixed": 70.0,
        # copy, loss gather, the stray event, and the while's own 20 ns
        # (980..1000), which none of its body's ops covers.
        "other": 20.0 + 10.0 + 5.0 + 20.0}
    assert sum(got["phases"].values()) == trace_reduce.busy_ns(EVENTS, 0, 2000)
    assert got["kernels"] == {"dk_flash_fwd": 350.0, "dk_flash_dq": 250.0}
    assert got["mixed"] == {"backward+optimizer": 70.0}
    assert got["control_ns"] == 20.0
    # A trace that lost the events of a loop's body shows it as the loop's own.
    lost = trace_scope.reduce(HLO, [e for e in EVENTS if e[2] != "fusion.3"],
                              0, 2000)
    assert lost["control_ns"] == 120.0 and lost["phases"]["other"] == 155.0
    # Clipped to the bracket like every other reduction.
    clipped = trace_scope.reduce(HLO, EVENTS, 50, 400)
    assert sum(clipped["phases"].values()) == trace_reduce.busy_ns(
        EVENTS, 50, 400) == 350


def _run(hlo=HLO, events=EVENTS, rounds=2, units=32768):
    return types.SimpleNamespace(
        trace={"ops0": events, "lo": 0, "hi": 2000, "rounds": rounds},
        hlo=hlo, chips=1, units_per_round=units,
        peak=peaks.PEAKS["TPU v5 lite"])


def test_read_gives_ms_per_round_none_for_a_dropped_scope_zero_for_idle():
    run = _run()
    assert trace_scope.read(run, phase="forward") == pytest.approx(300e-6 / 2)
    assert trace_scope.read(run, phase="remat") == pytest.approx(200e-6 / 2)
    assert trace_scope.read(run, kernels=["dk_flash_fwd", "dk_flash_dq"]) \
        == pytest.approx(600e-6 / 2)
    # A kernel the program does not hold at all: the run must fail, by name.
    assert trace_scope.read(run, kernels=["dk_flash_dkv"]) is None
    # A refactor dropped dk_optimizer but kept the others: None, not 0.
    dropped = _run(hlo=HLO.replace("dk_optimizer", "optimizer"))
    assert trace_scope.read(dropped, phase="optimizer") is None
    assert trace_scope.read(dropped, phase="forward") is not None
    # No remat in the configuration: the scope it rests on is there, 0.0.
    plain = _run(hlo=HLO.replace("/checkpoint/rematted_computation", ""))
    assert trace_scope.read(plain, phase="remat") == 0.0
    # A program from before the scopes: every metric reads 0, none fails.
    old = _run(hlo=HLO.replace("dk_", "x_"))
    assert trace_scope.read(old, phase="forward") == 0.0
    assert trace_scope.read(old, kernels=["dk_flash_fwd"],
                            floor={"config": "gpt2-medium"}) == 0.0
    assert trace_scope.read(_run(rounds=0), phase="forward") is None


@pytest.mark.parametrize("phase, ns", [("mixed", 70.0), ("other", 55.0)])
def test_mixed_and_other_read_under_the_conditions_forward_does(phase, ns):
    assert trace_scope.read(_run(), phase=phase) == pytest.approx(ns * 1e-6 / 2)
    # A refactor dropped dk_fwd_bwd but kept the others: None, as forward.
    dropped = _run(hlo=HLO.replace("dk_fwd_bwd", "fwd_bwd"))
    assert trace_scope.read(dropped, phase="forward") is None
    assert trace_scope.read(dropped, phase=phase) is None
    # The scope is there and the trace holds no such event: 0.0.
    named = {"mixed": ("fusion.5", "fusion.6"),
             # the while's own 20 ns, which no op of its body covers, too
             "other": ("copy.1", "fusion.8", "not-in-the-text", "while.1")}
    idle = _run(events=[e for e in EVENTS if e[2] not in named[phase]])
    assert trace_scope.read(idle, phase=phase) == 0.0
    assert trace_scope.read(idle, phase="forward") > 0.0
    # A program from before the scopes, and a trace without whole rounds.
    assert trace_scope.read(_run(hlo=HLO.replace("dk_", "x_")),
                            phase=phase) == 0.0
    assert trace_scope.read(_run(rounds=0), phase=phase) is None


@pytest.mark.parametrize("family, absent", [("lm", set()),
                                            ("img", {"remat", "fold"})])
def test_the_phase_metrics_add_up_to_the_rounds_device_time(family, absent):
    """forward + backward + remat + optimizer + mixed + other + fold =
    ``round.device_ms``, through the metrics' own files: every phase of the
    reduction has one (``.img``: but the two ResNet's program never enters),
    so no part of the busy time stands on a ``[bench`` line alone."""
    phases = {}
    for path in glob.glob(os.path.join(ROOT, "benchmarks", "layer_metrics",
                                       f"*.{family}.json")):
        with open(path, encoding="utf-8") as f:
            spec = json.load(f)
        if spec["reader"] == "trace_scope" and "phase" in spec["arguments"]:
            assert spec["arguments"]["phase"] not in phases, path
            phases[spec["arguments"]["phase"]] = spec["arguments"]
    assert set(trace_scope.PHASES) - set(phases) == absent
    classes, _, _ = trace_scope.classify(HLO)
    events = [e for e in EVENTS
              if classes.get(e[2], ("other",))[0] not in absent]
    run = _run(events=events)
    run.trace["busy_s"] = trace_reduce.busy_ns(events, 0, 2000) * 1e-9
    assert all(trace_scope.reduce(HLO, events, 0, 2000)["phases"][p] == 0.0
               for p in absent)
    parts = {p: trace_scope.read(run, **phases[p]) for p in phases}
    assert None not in parts.values() and parts["mixed"] > 0 < parts["other"]
    assert sum(parts.values()) == pytest.approx(trace_busy.read(run), rel=1e-12)


def test_flash_floor_for_gpt2_mediums_shapes():
    """32,768 tokens a round in sequences of 1024, d_model 1024, 24 layers:
    6 * 1024 * 1024 * 24 = 150,994,944 operations a token (the 0.151 G of
    PERF.md), 8 tensors of 32768 x 1024 bf16 a layer."""
    peak = peaks.PEAKS["TPU v5 lite"]
    least = trace_scope.flash_attention_floor(32768, 1024, 1024, 24, peak)
    assert least["flops"] == 150_994_944 * 32768 == 4_947_802_324_992
    assert least["bytes"] == 8 * 32768 * 1024 * 2 * 24 == 12_884_901_888
    assert least["bound"] == "flops"
    assert least["seconds"] == pytest.approx(4_947_802_324_992 / 197e12)
    assert least["seconds"] * 1e3 == pytest.approx(25.1157, abs=1e-3)
    assert 12_884_901_888 / 819e9 * 1e3 == pytest.approx(15.7325, abs=1e-3)
    # Short sequences turn it around: the bytes bound.
    short = trace_scope.flash_attention_floor(32768, 64, 1024, 24, peak)
    assert short["bound"] == "bytes"
    assert short["seconds"] == pytest.approx(12_884_901_888 / 819e9)


def test_roofline_share_is_the_floor_over_the_kernels_time():
    # 600 ns of kernels in 2 rounds = 300e-6 ms a round against a floor of
    # 25.1 ms: a share far above 100, which only says the arithmetic is plain.
    share = trace_scope.read(_run(), kernels=["dk_flash_fwd", "dk_flash_dq"],
                             floor={"config": "gpt2-medium"})
    assert share == pytest.approx(25.11574 / 300e-6 * 100, rel=1e-5)


# ---------------------------------------------------------------------------
# timeline
# ---------------------------------------------------------------------------

def _entry(path, t0, dur):
    return {"path": path, "t0_ns": t0, "dur_ns": dur, "id": None,
            "thread": "MainThread", "wall_ns": t0}


def test_covered_seconds_stop_at_the_first_tick_and_count_no_second_twice():
    entries = [
        _entry("model_build", 0, 5_000_000_000),
        _entry("compile.trace", 6_000_000_000, 1_000_000_000),
        _entry("a/compile.trace", 7_000_000_000, 500_000_000),
        _entry("compile.lower", 8_000_000_000, 250_000_000),
        _entry("compile.trace", 20_000_000_000, 9_000_000_000),  # after
        _entry("compile.lower", 9_900_000_000, 200_000_000),     # straddles
    ]
    tick = 10_000_000_000
    assert timeline.covered_seconds(entries, ["model_build"], tick) == 5.0
    assert timeline.covered_seconds(
        entries, ["compile.trace", "compile.lower"], tick) == 1.75
    assert timeline.covered_seconds(entries, ["compile.cache_load"], tick) == 0.0
    # JAX reports a function's tracing and that of each jitted function it
    # calls, one inside the other: the union, not the sum.
    nested = [_entry("compile.trace", 0, 1_000_000_000),
              _entry("compile.trace", 100_000_000, 300_000_000),
              _entry("compile.trace", 900_000_000, 300_000_000)]
    assert timeline.covered_seconds(nested, ["compile.trace"], tick) == 1.2
    # Model.build's own programs compile inside its span: counted there only.
    entries += [_entry("compile.trace", 1_000_000_000, 2_000_000_000),
                {**_entry("compile.trace", 2_000_000_000, 1_000_000_000),
                 "thread": "dk-feeder"}]
    assert timeline.covered_seconds(entries, ["compile.trace"], tick) == 4.5
    assert timeline.covered_seconds(entries, ["compile.trace"], tick,
                                  outside=["model_build"]) == 2.5


def test_timeline_reads_the_programs_own_registry():
    from distkeras_tpu import telemetry

    telemetry.reset()
    with telemetry.span("model_build"):
        pass
    import time

    run = types.SimpleNamespace(
        window=types.SimpleNamespace(ticks=[time.perf_counter() + 1.0]))
    assert 0.0 <= timeline.read(run, paths=["model_build"]) < 1.0
    assert timeline.read(run, paths=["compile.cache_load"]) == 0.0
    # A span no registry declares: a refactor dropped it, the run must fail.
    assert timeline.read(run, paths=["model_build", "no.such.span"]) is None
    # A full ring may have dropped set-up's spans: fail rather than read less.
    from distkeras_tpu.telemetry import core

    for _ in range(core.TIMELINE_CAPACITY):
        telemetry.get().observe_span("feed_wait", 0.0)
    assert timeline.read(run, paths=["model_build"]) is None
    telemetry.reset()


def test_timeline_reads_zero_from_a_program_without_one(monkeypatch):
    from distkeras_tpu import telemetry

    class Before:  # the parent commit's registry: spans, no timeline
        pass

    monkeypatch.setattr(telemetry, "get", lambda: Before())
    run = types.SimpleNamespace(window=types.SimpleNamespace(ticks=[1.0]))
    assert timeline.read(run, paths=["model_build"]) == 0.0
