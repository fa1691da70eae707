"""What a CPU can check about ``chip_smoke.py`` and the compile-cache rule:
the smoke refuses to run without a TPU (it never quietly measures a CPU), and
the persistent compile cache sits where the operator put it, or else at one
fixed place that does not depend on the working directory."""

import importlib.util
import json
import os
import subprocess
import sys

import jax
import pytest

from distkeras_tpu.runtime import compile_cache

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_smoke_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=_REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "found platform 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout, "a CPU run must print no result"


def test_result_line_holds_exactly_the_keys_the_chip_check_reads():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(_REPO, "chip_smoke.py"))
    chip_smoke = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = chip_smoke  # dataclasses looks its module up
    try:
        spec.loader.exec_module(chip_smoke)
    finally:
        del sys.modules["chip_smoke"]
    line = chip_smoke.result_line(
        True, {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert json.loads(chip_smoke.result_line(
        False, {"platform": "tpu", "kind": "k", "count": 4}))["ok"] is False


@pytest.fixture
def cache_dir_config():
    before = jax.config.jax_compilation_cache_dir
    yield lambda value: jax.config.update("jax_compilation_cache_dir", value)
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_leaves_a_preset_directory_alone(cache_dir_config):
    # JAX fills this option from JAX_COMPILATION_CACHE_DIR at import.
    cache_dir_config("/operator/chose/this")
    assert compile_cache.ensure_compile_cache() == "/operator/chose/this"
    assert jax.config.jax_compilation_cache_dir == "/operator/chose/this"


def test_compile_cache_default_ignores_the_working_directory(
        cache_dir_config, tmp_path, monkeypatch):
    paths = []
    for name in ("a", "b"):
        (tmp_path / name).mkdir()
        monkeypatch.chdir(tmp_path / name)
        cache_dir_config(None)
        paths.append(compile_cache.ensure_compile_cache())
        assert jax.config.jax_compilation_cache_dir == paths[-1]
    assert paths[0] == paths[1] == os.path.join(_REPO, ".jax_cache")
