"""Compiled-HLO regression tests: the collective structure the design promises.

The whole point of the rebuild is that the reference's parameter-server
traffic becomes ONE fused collective per fold round riding ICI (SURVEY.md §7).
These tests pin that property in the compiled executable so a refactor that
silently splits or multiplies the collectives fails CI, not a pod run.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from distkeras_tpu.models import Model
from distkeras_tpu.models.mlp import MLP
from distkeras_tpu.parallel.disciplines import get_discipline
from distkeras_tpu.parallel.engine import AsyncEngine
from distkeras_tpu.parallel.sync import SyncEngine
from distkeras_tpu.runtime.mesh import data_mesh


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _count(hlo, op):
    return len(re.findall(rf"{op}[-.\w]*\(", hlo))


@pytest.fixture(scope="module")
def setup():
    mesh = data_mesh()
    model = Model.build(MLP(hidden=(32,), num_outputs=3),
                        jnp.zeros((1, 6), jnp.float32))
    xs = jnp.zeros((8, 4, 16, 6), jnp.float32)
    ys = jnp.zeros((8, 4, 16), jnp.int32)
    return mesh, model, xs, ys


@pytest.mark.parametrize("disc", ["downpour", "adag", "dynsgd", "aeasgd"])
def test_async_round_is_one_fused_all_reduce(setup, disc, request):
    mesh, model, xs, ys = setup
    fold = get_discipline(disc) if disc != "aeasgd" else get_discipline(
        "aeasgd", alpha=0.1)
    eng = AsyncEngine(model, "sgd", "sparse_categorical_crossentropy", fold,
                      mesh, window=4, learning_rate=0.1)
    hlo = _compiled_text(eng._round_core, eng.init_state(), xs, ys)
    n = _count(hlo, "all-reduce")
    # one fused all-reduce for the param fold (the loss gather may fuse into
    # it or add one more op at most — never one per parameter tensor)
    assert 1 <= n <= 2, f"{disc}: expected one fused fold, got {n} all-reduces"


def test_sync_round_is_one_fused_all_reduce_per_step(setup):
    mesh, model, xs, ys = setup
    eng = SyncEngine(model, "sgd", "sparse_categorical_crossentropy", mesh,
                     learning_rate=0.1)
    hlo = _compiled_text(eng._round_core, eng.init_state(), xs, ys)
    # the window scan contains the per-step gradient pmean: the loop body
    # must carry a single fused all-reduce, not one per layer
    n = _count(hlo, "all-reduce")
    assert 1 <= n <= 3, f"expected fused per-step pmean, got {n} all-reduces"


def test_async_round_has_no_host_transfers(setup):
    """The round program must not bounce through the host (infeed/outfeed
    beyond the obvious arg/result transfers)."""
    mesh, model, xs, ys = setup
    eng = AsyncEngine(model, "sgd", "sparse_categorical_crossentropy",
                      get_discipline("adag"), mesh, window=4, learning_rate=0.1)
    hlo = _compiled_text(eng._round_core, eng.init_state(), xs, ys)
    assert _count(hlo, "infeed") == 0
    assert _count(hlo, "outfeed") == 0
