"""The repo keeps one record of speed, and says only what its tree bears out.

``benchmarks/run.py`` measures, ``PERF_LEDGER.jsonl`` records, ``PERF.md``
tells. Nothing in the package, the examples, the tests or CI reads or writes
a benchmark file at the repo's root (the retired ``bench.py`` and the
``BENCH_SUMMARY.json`` / ``BENCH_PIN.json`` / ``BENCH_TELEMETRY.jsonl`` it
kept there), and ``README.md``'s layout and cells are those of the tree and
of ``BENCHMARK.json``.
"""

import ast
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the retired benchmark, its module and the files it kept at the root
RETIRED = re.compile(
    r"\bimport bench\b|\bfrom bench\b|(?<![\w/.])bench\.py\b"
    r"|BENCH_SUMMARY|BENCH_PIN|BENCH_TELEMETRY")

#: the tests that plant such a file to show that nothing picks it up
NEGATIVE_TESTS = {
    "tests/test_health.py": "test_sentinels_read_no_file_from_cwd",
    "tests/test_sim.py": "test_hier_crossover_requires_its_curve",
}
#: this file names the pattern; ``tests/benchmarks/`` is the benchmark's own
SKIPPED = ("tests/test_repo_records.py", "tests/benchmarks/")


def _files(rel: str):
    top = os.path.join(ROOT, rel)
    if os.path.isfile(top):
        yield rel
        return
    for folder, dirs, names in os.walk(top):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in names:
            yield os.path.relpath(os.path.join(folder, name), ROOT)


def _allowed_lines(rel: str, source: str) -> range:
    """The lines of the negative test that ``rel`` holds, if it holds one."""
    name = NEGATIVE_TESTS.get(rel)
    if name is None:
        return range(0)
    (fn,) = [n for n in ast.parse(source).body
             if isinstance(n, ast.FunctionDef) and n.name == name]
    return range(fn.lineno, fn.end_lineno + 1)


@pytest.mark.parametrize("where", [
    "distkeras_tpu", "examples", "tests", "chip_smoke.py",
    "accuracy_gate.py", ".github/workflows/tier1.yml"])
def test_nothing_reads_a_root_benchmark_file(where):
    hits, scanned = [], 0
    for rel in _files(where):
        if rel.startswith(SKIPPED):
            continue
        try:
            with open(os.path.join(ROOT, rel), encoding="utf-8") as f:
                source = f.read()
        except UnicodeDecodeError:  # a built extension
            continue
        scanned += 1
        allowed = _allowed_lines(rel, source)
        hits += [f"{rel}:{n}: {line.strip()}"
                 for n, line in enumerate(source.splitlines(), 1)
                 if RETIRED.search(line) and n not in allowed]
    assert scanned, f"nothing to scan under {where}"
    assert not hits, "\n".join(hits)


def _readme_section(title: str) -> str:
    with open(os.path.join(ROOT, "README.md"), encoding="utf-8") as f:
        text = f.read()
    start = text.index(f"## {title}\n")
    end = text.find("\n## ", start + 1)
    return text[start:end if end != -1 else None]


def _layout_paths():
    """Paths the layout block names: a line at the margin opens a top-level
    directory, a line indented by two names a directory or the files in it.
    Deeper lines are prose."""
    block = _readme_section("Layout").split("```")[1]
    top = None
    for line in block.splitlines():
        words = line.split()
        if not words:
            continue
        if not line.startswith(" ") and words[0].endswith("/"):
            top = words[0]
            yield top
        elif line.startswith("  ") and not line.startswith("   "):
            if words[0].endswith("/"):
                yield top + words[0]
            else:
                assert all(w.endswith(".py") for w in words), line
                yield from (top + w for w in words)


def test_readme_layout_matches_the_tree():
    paths = list(_layout_paths())
    assert len(paths) > 20, paths
    missing = [p for p in paths if not os.path.exists(os.path.join(ROOT, p))]
    assert not missing, f"README.md's layout names what is not there: {missing}"
    package = os.path.join(ROOT, "distkeras_tpu")
    packages = sorted(
        f"distkeras_tpu/{d}/" for d in os.listdir(package)
        if os.path.isfile(os.path.join(package, d, "__init__.py")))
    unlisted = [p for p in packages if p not in paths]
    assert not unlisted, f"README.md's layout leaves out {unlisted}"


def test_readme_cells_are_the_benchmarks():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        manifest = json.load(f)
    cells = {w["name"] for w in manifest["workloads"]}
    metrics = {m["name"] for m in manifest["end_to_end"]}
    rows = [[c.strip().strip("`") for c in line.strip("|").split("|")]
            for line in _readme_section("Measured performance").splitlines()
            if line.startswith("| `")]
    assert rows, "README.md names no cell under Measured performance"
    assert {r[0] for r in rows} <= cells, (rows, cells)
    assert {r[1] for r in rows} <= metrics, (rows, metrics)
