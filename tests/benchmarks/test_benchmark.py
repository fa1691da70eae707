"""The benchmark's own tests (BENCHMARK.json's second path): the manifest and
its files agree, the result line's check refuses what the driver would, the
trace reduction does its arithmetic on hand-made intervals, the FLOPs come out
of the shapes, each plain reference agrees with its family's tiny preset and
notices a skipped block, the window traces by its rule, and every cell has a
file of its own that rehearses ``run.py --rehearse`` (``rehearsal.py``). All
on the CPU; nothing here is a measurement."""

from __future__ import annotations

import argparse
import copy
import glob
import importlib
import json
import os
import re
import sys

import pytest
import rehearsal
from test_manifest_appends import appended

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
BENCH = os.path.join(ROOT, "benchmarks")

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import result_line, trace_reduce  # noqa: E402
from benchmarks.harness.window import Window, WindowClosed  # noqa: E402
from benchmarks.readers import trace_collective  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _json(*parts):
    with open(os.path.join(ROOT, *parts), encoding="utf-8") as f:
        return json.load(f)


MANIFEST = _json("BENCHMARK.json")
CELLS = [w["name"] for w in MANIFEST["workloads"]]


# ---------------------------------------------------------------------------
# manifest
# ---------------------------------------------------------------------------

def test_manifest_names_files_that_exist():
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    for w in MANIFEST["workloads"]:
        wl = _json("benchmarks", "workloads", f"{w['name']}.json")
        assert wl["config"] == w["config"] and wl["chips"] == w["chips"]
        cfg = _json(configs[w["config"]]["file"])
        assert cfg["reduced"] == configs[w["config"]]["reduced"]
        assert cfg["source"] == configs[w["config"]]["source"]
        for kind in ("families", "references"):
            assert os.path.exists(os.path.join(BENCH, kind,
                                               f"{cfg['family']}.py"))
    for group, folder in (("end_to_end", "end_to_end"),
                          ("per_layer", "layer_metrics")):
        for m in MANIFEST[group]:
            spec = _json("benchmarks", folder, f"{m['name']}.json")
            assert os.path.exists(os.path.join(BENCH, "readers",
                                               f"{spec['reader']}.py"))
            if group == "per_layer":
                assert spec["layer"] == m["layer"]
    assert {c["name"] for c in MANIFEST["configs"]} \
        == {w["config"] for w in MANIFEST["workloads"]}


def test_manifest_names_units_and_lengths():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in MANIFEST[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
            for key in ("why", "layer", "source"):
                if key in entry:
                    assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key]
            if "unit" in entry:
                assert UNIT.match(entry["unit"]), entry["unit"]
                assert entry["better"] in ("lower", "higher")
    assert len(names) == len(set(names))
    for w in MANIFEST["workloads"]:
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert 1 <= MANIFEST["run_seconds"] <= 51
    for path in glob.glob(os.path.join(BENCH, "**", "*"), recursive=True):
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", os.path.relpath(path, ROOT))
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_manifest_metrics_move_what_their_cells_report():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in MANIFEST["end_to_end"]:
        assert 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in MANIFEST["per_layer"]:
        assert "bound" not in m
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS
            assert cell in moved.get("workloads", CELLS), (m["name"], cell)
    for cell in CELLS:
        assert len(result_line.declared_metrics(MANIFEST, cell, False)) >= 2
        assert result_line.declared_metrics(MANIFEST, cell, True)


def test_manifest_four_chip_cells_are_a_quarter_or_one():
    four = sum(1 for w in MANIFEST["workloads"] if w["chips"] == 4)
    assert four <= max(len(CELLS) // 4, 1)


# ---------------------------------------------------------------------------
# the result line
# ---------------------------------------------------------------------------

DECLARED = {"round.device_ms.lm": "ms/round", "model.mfu.lm": "%"}


def _good_line():
    return {"correct": True, "attempted": 28, "failed": 0,
            "metrics": {"round.device_ms.lm": {"value": 1071.5, "unit": "ms/round"},
                        "model.mfu.lm": {"value": 35.2, "unit": "%"}},
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 4,
                       "memory_peak_bytes": 16588567040, "busy_s": 3.21,
                       "window_s": 3.25},
            "breakdown": {"device_ops": [["fusion.1", 1.5]], "idle_gaps": []}}


def test_result_line_accepts_a_good_line():
    result_line.validate(_good_line(), DECLARED, 4, True)
    json.loads(result_line.dumps(_good_line()))


def _set(path, value):
    def change(line):
        node = line
        for key in path[:-1]:
            node = node[key]
        if value is KeyError:
            del node[path[-1]]
        else:
            node[path[-1]] = value
    return change


@pytest.mark.parametrize("change, reason", [
    (_set(("device", "busy_s"), 3.26), "busy_s"),           # a sum of lines
    (_set(("device", "busy_s"), 0.0), "busy_s"),            # caught no event
    (_set(("metrics", "model.mfu.lm", "value"), float("nan")), "finite"),
    (_set(("metrics", "model.mfu.lm", "value"), None), "finite"),
    (_set(("metrics", "model.mfu.lm"), KeyError), "missing"),
    (_set(("metrics", "extra"), {"value": 1.0, "unit": "s"}), "not declared"),
    (_set(("metrics", "model.mfu.lm", "unit"), "percent"), "unit"),
    (_set(("device", "count"), 1), "count"),
    (_set(("device", "platform"), "cpu"), "platform"),
    (_set(("device", "memory_peak_bytes"), 0), "memory_peak_bytes"),
    (_set(("device", "window_s"), KeyError), "device keys"),
    (_set(("notes",), {}), "keys"),
    (_set(("failed",), 29), "failed"),
])
def test_result_line_refuses(change, reason):
    line = copy.deepcopy(_good_line())
    change(line)
    with pytest.raises(result_line.LineRefused, match=reason):
        result_line.validate(line, DECLARED, 4, True)


# ---------------------------------------------------------------------------
# the trace reduction, on hand-made intervals (nanoseconds)
# ---------------------------------------------------------------------------

def _modules(starts, dur=90):
    return [(s, dur, "jit_round_fn(1)") for s in starts]


def test_trace_overlap_is_merged_and_edges_are_clipped():
    ops = [(90, 20, "cut at the start"),      # 100..110 inside
           (120, 30, "while"), (125, 10, "child"),   # nested: counted once
           (160, 20, "a"), (170, 20, "overlaps a"),  # 160..190
           (290, 20, "cut at the end")]       # 290..300 inside
    assert trace_reduce.busy_ns(ops, 100, 300) == 10 + 30 + 30 + 10
    out = trace_reduce.reduce_planes(
        [{"ops": ops, "modules": _modules([0, 100, 200, 300])}])
    assert out["rounds"] == 2
    assert out["window_s"] == pytest.approx(200e-9)
    assert out["busy_s"] == pytest.approx(80e-9)
    own = dict(out["breakdown"]["device_ops"])
    assert own["while"] == pytest.approx(20e-9)  # 30 less its child's 10
    assert own["child"] == pytest.approx(10e-9)
    gaps = dict(out["breakdown"]["idle_gaps"])
    # 190..290 holds the start of the third program: the host's gap.
    assert gaps["between round programs: host run loop"] == pytest.approx(100e-9)
    assert gaps["inside a program, after cut at the start"] == pytest.approx(10e-9)
    assert gaps["inside a program, after while"] == pytest.approx(10e-9)


def test_trace_four_planes_are_averaged_and_lines_are_not_added():
    def plane(busy):
        return {"ops": [(100, busy, "fusion.1")],
                "modules": _modules([0, 100, 200, 300])}
    out = trace_reduce.reduce_planes([plane(b) for b in (40, 80, 120, 160)])
    assert out["busy_s"] == pytest.approx(100e-9)          # the mean
    assert out["busy_s"] <= out["window_s"]
    assert out["busy_s_per_plane"] == pytest.approx([40e-9, 80e-9, 120e-9, 160e-9])
    # The program line covers the whole round; were it added to the op line,
    # busy would pass the window. Only "ops" is summed.
    full = {"ops": [(100, 200, "fusion.1")],
            "modules": _modules([0, 100, 200, 300], dur=100)}
    out = trace_reduce.reduce_planes([full])
    assert out["busy_s"] == pytest.approx(200e-9)
    # The breakdown adds up the instructions of one stem.
    assert out["breakdown"]["device_ops"] == [["fusion", pytest.approx(200e-9)]]
    assert trace_reduce.instruction_name(
        "%attn.755 = (bf16[128,1024,64]{2,1,0}) custom-call(%x), kind=k") == "attn.755"


def test_trace_without_whole_rounds_is_unreadable():
    with pytest.raises(trace_reduce.TraceUnreadable, match="needs three"):
        trace_reduce.reduce_planes(
            [{"ops": [(0, 5, "x")], "modules": _modules([0, 100])}])
    with pytest.raises(trace_reduce.TraceUnreadable):
        trace_reduce.reduce_planes([])
    # A rehearsal's plane has no program line: the harness's annotation and
    # its count of rounds stand in.
    out = trace_reduce.reduce_planes([{"ops": [(10, 5, "x")], "modules": []}],
                                     annotation=(0, 100),
                                     rounds_in_annotation=4)
    assert (out["rounds"], out["busy_s"]) == (4, pytest.approx(5e-9))


def test_collective_reader_finds_instructions_by_opcode():
    hlo = "\n".join([
        "  %psum_invariant.7 = f32[8,8]{1,0:T(8,128)S(1)} all-reduce(%fusion), channel_id=1",
        "  ROOT %fusion.2 = f32[8,8]{1,0} fusion(%psum_invariant.7), kind=kLoop",
        "  %all-reduce-start.3 = (f32[8]{0}, f32[8]{0}) all-reduce-start(%x)",
        "  all-reduce-done.3 = f32[8]{0} all-reduce-done(%all-reduce-start.3)"])
    assert trace_reduce.instruction_names(hlo, "all-reduce") == {
        "psum_invariant.7", "all-reduce-start.3", "all-reduce-done.3"}
    assert len(trace_reduce.instruction_names(hlo)) == 4

    class Run:
        pass
    run = Run()
    run.hlo = hlo
    run.trace = {"rounds": 2, "lo": 0, "hi": 1000, "async0": [],
                 "ops0": [(100, 50, "psum_invariant.7"), (120, 50, "fusion.2"),
                          (400, 10, "all-reduce-start.3"),
                          (405, 20, "all-reduce-done.3")]}
    assert trace_collective.read(run, "all-reduce") \
        == pytest.approx((50 + 25) / 1e9 / 2 * 1e3)
    run.trace["ops0"] = [(120, 50, "fusion.2")]
    assert trace_collective.read(run, "all-reduce") is None


# ---------------------------------------------------------------------------
# the window's clock, on a hand-made clock
# ---------------------------------------------------------------------------

def test_window_opens_after_warmup_and_closes_on_a_tick():
    now = [0.0]
    w = Window(2.0, t_start=0.0, clock=lambda: now[0])
    w.t_train = 0.5
    with pytest.raises(WindowClosed):
        for r in range(100):
            now[0] = 10.0 + 0.3 * r   # round r-1's loss arrives
            w(r, float(r))            # a loss to fetch next time
    # ticks 0, 1, 2 are warm-up; the segment opens on tick 2 (10.6 s on this
    # clock) and closes on the first tick 2 s later.
    assert w.segment[0] == 2
    assert w.segment_s == pytest.approx(2.1) and w.segment_rounds == 7
    assert len(w.round_times_s()) == 7 and w.failed_rounds() == 0
    assert w.segment_losses() == [3.0, 4.0, 5.0, 6.0, 7.0, 8.0, 9.0]


@pytest.mark.parametrize("rehearse, round_s, rounds", [
    (False, 0.25, 12),  # on the chip: about 3 s ...
    (False, 1.5, 4),    # ... and at least 4 rounds ...
    (False, 5.0, 2),    # ... but at most 8 s
    (True, 0.25, 4),    # a rehearsal: the 4 rounds alone
    (True, 5.0, 2),
])
def test_the_trace_spans_the_rounds_run_py_asks_for(
        monkeypatch, rehearse, round_s, rounds):
    """The window as ``run.py`` builds it, with and without ``--rehearse``,
    on a hand-made clock; the profiler's calls are stubbed."""
    import jax

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **k: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    args = argparse.Namespace(seconds=30.0, rehearse=rehearse)
    now = [0.0]
    w = bench_run.make_window(args, "unused", clock=lambda: now[0])
    for r in range(40):
        now[0] = round_s * r
        w(r, float(r))
        if w.phase != "trace" and w.trace_rounds:
            break
    assert calls == ["start", "stop"]
    assert w.trace_rounds == rounds


# ---------------------------------------------------------------------------
# operations from shapes
# ---------------------------------------------------------------------------

def test_flops_from_the_configurations_shapes():
    import jax
    import jax.numpy as jnp

    from benchmarks.families import resnet, transformer_lm
    from distkeras_tpu.models.transformer import TransformerLM

    gpt = _json("benchmarks", "configs", "gpt2-medium.json")
    assert transformer_lm.matmul_params(gpt["module"]) \
        == pytest.approx(353.5e6, rel=1e-3)
    assert transformer_lm.train_flops_per_unit(gpt) \
        == pytest.approx(2.272e9, rel=2e-3)
    shapes = jax.eval_shape(
        lambda: TransformerLM(**gpt["module"]).init(
            jax.random.key(0), jnp.zeros((1, 128), jnp.int32), train=False))
    assert sum(a.size for a in jax.tree.leaves(shapes)) \
        == pytest.approx(406.3e6, rel=1e-3)
    rn = _json("benchmarks", "configs", "resnet50-gn.json")
    assert 7.7e9 <= resnet.forward_flops(rn) <= 8.6e9
    assert resnet.train_flops_per_unit(rn) == 3 * resnet.forward_flops(rn)


# ---------------------------------------------------------------------------
# references against the tiny presets
# ---------------------------------------------------------------------------

def _tiny(config_name, **module):
    config = _json("benchmarks", "configs", f"{config_name}.json")
    family = importlib.import_module(f"benchmarks.families.{config['family']}")
    config = {**config, **family.TINY,
              "module": {**config["module"], **family.TINY["module"], **module}}
    return family, config


@pytest.mark.parametrize("config_name, skip", [
    ("gpt2-medium", lambda c: c["module"].update(num_layers=1)),
    ("resnet50-gn", lambda c: c["module"].update(stage_sizes=[1, 1])),
])
def test_reference_agrees_with_the_model_and_notices_a_skipped_block(
        config_name, skip):
    family, config = _tiny(config_name)
    model = family.build_model(config, seed=3)
    # In float32 the wiring must agree to rounding. The flash kernel rounds
    # its operands to bfloat16 by design (2e-3 here), so the exact comparison
    # runs the same parameters through the module's dense attention.
    plain = _tiny(config_name, attn_impl="dense")[1] \
        if "attn_impl" in config["module"] else config
    exact = family.reference_check(
        family.build_model(plain, seed=3), plain, 7, None)
    assert exact["ok"] and exact["rel_l2"] < 1e-5, exact
    lowp = family.reference_check(model, config, 7, "bfloat16")
    assert lowp["ok"] and lowp["rel_l2"] > exact["rel_l2"], lowp
    # The reference told of one block fewer (it reads blocks by name, so the
    # parameters of the skipped one are simply not used) must disagree, by
    # far more than the bfloat16 tolerance.
    fewer = copy.deepcopy(config)
    skip(fewer)
    wrong = family.reference_check(model, fewer, 7, "bfloat16")
    assert not wrong["ok"] and wrong["rel_l2"] > 3 * wrong["tolerance"], wrong


def test_reference_pins_the_form_of_gelu():
    """The two forms of GELU differ by less than bfloat16's noise, so float32
    on the CPU is where the form is pinned."""
    from benchmarks.references import transformer_lm as reference

    family, config = _tiny("gpt2-medium", attn_impl="dense")
    model = family.build_model(config, seed=3)
    import jax

    original = reference._gelu_tanh
    reference._gelu_tanh = lambda x: jax.nn.gelu(x, approximate=False)
    try:
        wrong = family.reference_check(model, config, 7, None)
    finally:
        reference._gelu_tanh = original
    assert not wrong["ok"], wrong


# ---------------------------------------------------------------------------
# the command, rehearsed: each cell in ``test_rehearse_<cell>.py``
# ---------------------------------------------------------------------------

def check_every_cell_is_rehearsed(manifest):
    """Each cell of ``manifest`` has its ``test_rehearse_<cell>.py``, naming
    it, and every such file rehearses a cell of ``manifest``: as sets."""
    files = rehearsal.rehearsed_cells()
    cells = {w["name"] for w in manifest["workloads"]}
    missing = sorted(cells - set(files))
    assert not missing, "no rehearsal for cell(s) " + ", ".join(
        f"{c} (add tests/benchmarks/{rehearsal.file_name(c)})" for c in missing)
    stray = {files[c] for c in set(files) - cells}
    assert not stray, f"rehearsal files of no cell: {sorted(stray)}"
    misnamed = {c: f for c, f in files.items() if f != rehearsal.file_name(c)}
    assert not misnamed, f"a rehearsal file under another name: {misnamed}"


def test_every_cell_has_a_rehearsal_file_of_its_own():
    check_every_cell_is_rehearsed(MANIFEST)


def test_a_cell_appended_without_its_rehearsal_file_is_refused_by_name():
    """The manifest as a later PR leaves it (``test_manifest_appends.py``):
    its cell has no file, and the guard names it."""
    with pytest.raises(AssertionError, match="a_later_cell"):
        check_every_cell_is_rehearsed(appended(MANIFEST))


def test_without_rehearse_a_cpu_is_refused_by_name():
    done = rehearsal.run(CELLS[0], 0)
    assert done.returncode != 0
    assert "'cpu'" in done.stderr
    assert not any(ln.startswith("{") for ln in done.stdout.splitlines())


def test_wrong_number_of_chips_is_refused():
    done = rehearsal.run(CELLS[0], 0, "--rehearse", devices=2)
    assert done.returncode != 0 and "chip" in done.stderr
    assert not any(ln.startswith("{") for ln in done.stdout.splitlines())
