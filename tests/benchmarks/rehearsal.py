"""``run.py --rehearse`` as the tests drive it: the command a check runs, on the
CPU at the family's tiny preset, and the line it prints held to
``harness/result_line.py``'s check. A helper, not a test module: nothing here
is collected.

Each cell of ``BENCHMARK.json`` is rehearsed by a file of its own,
``test_rehearse_<cell>.py`` with ``.`` and ``-`` of the cell's name as ``_``
(:func:`file_name`), which names its cell on a line ``CELL = "<cell>"`` and
parametrises ``trace`` over ``[0, 1]``. Under ``--dist loadfile`` the cells
then go to different workers; a PR that appends a cell adds its file, and
``test_benchmark.py`` refuses a cell without one by name.

Both trace settings of a cell share one persistent compile cache, fresh for
the cell in each pytest run (:func:`rehearse`): the traced case loads what the
untraced one compiled. A compiled program's key leaves the scopes' metadata
out, which is harmless here because both cases run one tree."""

from __future__ import annotations

import glob
import json
import os
import re
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import result_line  # noqa: E402

SEED = 3000000019
_CELL_LINE = re.compile(r'^CELL = "([^"]+)"$', re.M)


def _manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def chips(cell: str, manifest=None) -> int:
    return next(w["chips"] for w in (manifest or _manifest())["workloads"]
                if w["name"] == cell)


def file_name(cell: str) -> str:
    return f"test_rehearse_{re.sub(r'[.-]', '_', cell)}.py"


def rehearsed_cells() -> dict:
    """Each ``test_rehearse_*.py`` beside this file, by the cell its
    ``CELL = "..."`` line names (``None`` where it names none), read as text:
    nothing is imported."""
    found = {}
    for path in sorted(glob.glob(os.path.join(HERE, "test_rehearse_*.py"))):
        with open(path, encoding="utf-8") as f:
            m = _CELL_LINE.search(f.read())
        found[m.group(1) if m else None] = os.path.basename(path)
    return found


def run(cell, trace, *extra, devices=None, cache_dir=None):
    """``run.py`` for ``cell`` on ``devices`` CPU devices (the cell's chips
    unless said), with the suite's other XLA flags, in a process of its own."""
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    count = devices or chips(cell)
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=" ".join(
        [f"--xla_force_host_platform_device_count={count}", *flags]))
    if cache_dir is not None:
        env.update(JAX_COMPILATION_CACHE_DIR=str(cache_dir),
                   JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
                   JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", cell, "--seed", str(SEED), "--seconds", "2",
         "--trace", str(trace), *extra], env=env, capture_output=True,
        text=True, timeout=600, cwd=ROOT)


def rehearse(cell, trace, tmp_path_factory):
    """The rehearsal prints a line the check accepts, with every metric the
    cell declares for ``trace``. The compile cache is the cell's own
    directory under the pytest run's base temporary directory, which both
    cases find."""
    manifest = _manifest()
    n_chips = chips(cell, manifest)
    cache_dir = tmp_path_factory.getbasetemp() / f"compile_cache-{cell}"
    cache_dir.mkdir(exist_ok=True)
    done = run(cell, trace, "--rehearse", cache_dir=cache_dir)
    if n_chips > 1 and trace:
        # The CPU backend's trace holds no event for the collective (its
        # thunks run untraced), so the one reader that needs it finds nothing
        # and the run must fail, naming the metric: never a line without it.
        assert done.returncode == 2, done.stderr[-2000:]
        assert "fold.allreduce_ms.lm" in done.stderr
        assert not done.stdout.strip().splitlines()[-1].startswith("{")
        return
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    declared = result_line.declared_metrics(manifest, cell, bool(trace))
    result_line.validate(line, declared, n_chips, bool(trace), platform="cpu")
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert ("breakdown" in line) == bool(trace)
