"""Driver-contract tests: entry() jits; dryrun_multichip runs dp x sp x tp."""

import importlib.util
import os

import jax


def _load_entry():
    path = os.path.join(os.path.dirname(__file__), "..", "__graft_entry__.py")
    spec = importlib.util.spec_from_file_location("graft_entry", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_entry_forward_jits():
    mod = _load_entry()
    fn, args = mod.entry()
    out = jax.jit(fn)(*args)
    assert out.shape == (8, 128, 8192)


def test_dryrun_multichip_8():
    _load_entry().dryrun_multichip(8)


def test_dryrun_multichip_2():
    _load_entry().dryrun_multichip(2)
