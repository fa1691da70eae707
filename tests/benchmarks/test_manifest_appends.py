"""A family's test of the manifest holds what its PR added by name, never by
a place in, or the length of, a list that later PRs append to.

A PR puts its new entries at the end of ``BENCHMARK.json``'s lists, and only
a ``benchmark`` PR may edit a test under ``tests/benchmarks``. So a test that
pins ``[-1]``, ``[-5:]`` or a length refuses every later cell, configuration
and per-layer metric, whatever that PR contains (PR 36 was refused so). This
file runs every family's ``test_the_cell_and_its_metrics_are_in_the_manifest``
against the manifest as a later PR would leave it; the next family's test is
a case here without an edit. All on the CPU; nothing here is a measurement."""

from __future__ import annotations

import copy
import glob
import importlib.util
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
FAMILY_TEST = "test_the_cell_and_its_metrics_are_in_the_manifest"


def _families():
    """The modules ``test_*_bench.py`` that test the manifest, by their text:
    nothing of a family is imported while this file is collected."""
    found = []
    for path in sorted(glob.glob(os.path.join(HERE, "test_*_bench.py"))):
        with open(path, encoding="utf-8") as f:
            if f"def {FAMILY_TEST}(" in f.read():
                found.append(os.path.basename(path)[:-3])
    return found


def _json(*parts):
    with open(os.path.join(ROOT, *parts), encoding="utf-8") as f:
        return json.load(f)


def appended(manifest: dict) -> dict:
    """``manifest`` as a later PR leaves it: at the end of each list one
    configuration, one one-chip cell on it, a per-layer metric of that cell
    alone and one of every cell; the cell joins ``tokens_per_s_chip``."""
    later = copy.deepcopy(manifest)
    later["configs"].append({
        "name": "a-later-config", "source": "https://example.org/a-later-config",
        "file": "benchmarks/configs/a-later-config.json", "reduced": [],
        "why": "what a later model_config PR appends"})
    later["workloads"].append({
        "name": "a_later_cell", "config": "a-later-config",
        "traffic": "a_later_traffic", "chips": 1,
        "why": "what a later PR with a cell of its own appends"})
    next(m for m in later["end_to_end"]
         if m["name"] == "tokens_per_s_chip")["workloads"].append("a_later_cell")
    later["per_layer"] += [
        {"name": "later.own_ms.lm", "unit": "ms/round", "better": "lower",
         "source": "device_trace", "layer": "Local steps",
         "moves": "tokens_per_s_chip", "workloads": ["a_later_cell"]},
        {"name": "later.every_cell_s", "unit": "s", "better": "lower",
         "source": "program_span", "layer": "Entry", "moves": "setup_s"}]
    return later


def reads_appended(read):
    """``read`` (a module's ``_json``) with ``BENCHMARK.json`` as
    :func:`appended` leaves it and every other path from disk as before."""
    def _json_appended(*parts):
        got = read(*parts)
        return appended(got) if parts == ("BENCHMARK.json",) else got

    return _json_appended


@pytest.mark.parametrize("family", _families())
def test_a_familys_test_of_the_manifest_holds_after_an_append(family):
    # a copy of the module under a name of its own: the family's own module,
    # which pytest imports and runs, keeps its ``_json``
    spec = importlib.util.spec_from_file_location(
        f"_appended_{family}", os.path.join(HERE, f"{family}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module._json = reads_appended(module._json)
    getattr(module, FAMILY_TEST)()


def test_the_glob_finds_the_families_that_test_the_manifest():
    assert {"test_lfm2_bench", "test_kimi_linear_bench"} <= set(_families())
    assert "test_smallthinker_bench" not in _families()  # it has no such test


def _last_place(read, name):
    manifest = read("BENCHMARK.json")
    cell = next(w for w in manifest["workloads"] if w["name"] == name)
    assert manifest["workloads"][-1] == cell


def _last_slice(read, name):
    manifest = read("BENCHMARK.json")
    assert [m["name"] for m in manifest["per_layer"][-1:]] == [name]


def _length(read, name):
    manifest = read("BENCHMARK.json")
    assert len(manifest["configs"]) == len(_json("BENCHMARK.json")["configs"])
    assert any(c["name"] == name for c in manifest["configs"])


def _all_but_the_last(read, name):
    manifest = read("BENCHMARK.json")
    assert name not in [w["name"] for w in manifest["workloads"][:-1]]


@pytest.mark.parametrize("pin, group", [
    (_last_place, "workloads"), (_last_slice, "per_layer"),
    (_length, "configs"), (_all_but_the_last, "workloads")])
def test_the_guard_fails_a_test_that_pins_a_place_or_a_length(pin, group):
    """The control: each pin as PR 34's test made it, of today's last entry.
    It holds on the manifest on disk and fails once a PR has appended."""
    name = _json("BENCHMARK.json")[group][-1]["name"]
    pin(_json, name)
    with pytest.raises(AssertionError):
        pin(reads_appended(_json), name)
