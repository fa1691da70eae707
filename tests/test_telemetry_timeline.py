"""The telemetry timeline (``Telemetry.span(name, id=)``, ``timeline()``),
the compile listener, and the ``dk_*`` scopes of the round programs."""

import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import distkeras_tpu as dk
from distkeras_tpu import telemetry
from distkeras_tpu.models.base import Model
from distkeras_tpu.telemetry import core
from distkeras_tpu.telemetry.core import Telemetry


# ---------------------------------------------------------------------------
# the ring
# ---------------------------------------------------------------------------

def test_entries_carry_path_id_and_thread_and_nest():
    tele = Telemetry()
    with tele.span("round", id=7):
        with tele.span("dispatch", id=7):
            time.sleep(0.002)
    inner, outer = tele.timeline()
    assert (inner["path"], outer["path"]) == ("round/dispatch", "round")
    assert inner["id"] == outer["id"] == 7
    assert inner["thread"] == threading.current_thread().name
    assert outer["t0_ns"] <= inner["t0_ns"]
    assert inner["t0_ns"] + inner["dur_ns"] <= outer["t0_ns"] + outer["dur_ns"]
    assert inner["dur_ns"] >= 2_000_000
    # The histogram of the same path still fills: mark()/delta() read it.
    assert tele.snapshot()["spans"]["round/dispatch"]["count"] == 1


def test_observe_span_ends_now_and_can_stay_flat():
    tele = Telemetry()
    with tele.span("engine_run"):
        tele.observe_span("feed_wait", 0.25, id=3)
        tele.observe_span("compile.backend", 0.5, nest=False)
    wait, compiled, _ = tele.timeline()
    assert (wait["path"], wait["id"]) == ("engine_run/feed_wait", 3)
    assert compiled["path"] == "compile.backend"
    now = time.perf_counter_ns()
    assert 0 <= now - (wait["t0_ns"] + wait["dur_ns"]) < 50_000_000
    assert wait["dur_ns"] == 250_000_000


def test_the_ring_is_bounded(monkeypatch):
    monkeypatch.setattr(core, "TIMELINE_CAPACITY", 8)
    tele = Telemetry()
    for i in range(20):
        with tele.span("s", id=i):
            pass
    assert [e["id"] for e in tele.timeline()] == list(range(12, 20))
    assert tele.snapshot()["spans"]["s"]["count"] == 20


def test_since_cuts_and_reset_clears():
    tele = Telemetry()
    with tele.span("before"):
        pass
    cut = time.perf_counter_ns()
    with tele.span("after"):
        pass
    assert [e["path"] for e in tele.timeline(since=cut)] == ["after"]
    assert len(tele.timeline()) == 2
    tele.reset()
    assert tele.timeline() == []


def test_wall_clock_agrees_with_time_ns_to_a_millisecond():
    tele = Telemetry()
    wall = time.time_ns()
    with tele.span("now"):
        pass
    (entry,) = tele.timeline()
    assert abs(entry["wall_ns"] - wall) < 1_000_000


def test_disabled_telemetry_records_nothing():
    tele = Telemetry(enabled=False)
    with tele.span("x", id=1):
        pass
    tele.observe_span("y", 0.1)
    assert tele.timeline() == []
    assert tele.span("x") is tele.span("y", id=2)  # the no-op singleton


# ---------------------------------------------------------------------------
# the run loop, the feeder's thread, set-up
# ---------------------------------------------------------------------------

def _train_tiny(on_round=None, **kwargs):
    from distkeras_tpu.models.mlp import MLP

    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 8)).astype(np.float32)
    df = dk.DataFrame({"features": x,
                       "label": (x[:, 0] > 0).astype(np.int32)})
    model = Model.build(MLP(hidden=(8,), num_outputs=2), jnp.zeros((1, 8)))
    trainer = dk.SynchronousDistributedTrainer(
        model, worker_optimizer="sgd",
        loss="sparse_categorical_crossentropy", num_workers=1, batch_size=8,
        steps_per_program=2, on_round=on_round, **kwargs)
    trainer.train(df)
    return trainer


def test_one_rounds_spans_share_an_id_across_threads():
    telemetry.reset()
    seen = []
    _train_tiny(on_round=lambda r, loss: seen.append(r))
    by_round: dict = {}
    for e in telemetry.get().timeline():
        if e["id"] is not None:
            by_round.setdefault(e["id"], {})[e["path"]] = e
    assert sorted(by_round) == seen == [0, 1, 2, 3]
    for r, spans in by_round.items():
        assert set(spans) == {
            "feeder.stage", "engine_run/feed_wait",
            "engine_run/dispatch[per-round]", "engine_run/on_round"}, r
        assert spans["feeder.stage"]["thread"] == "dk-feeder"
        assert spans["engine_run/on_round"]["thread"] \
            == spans["engine_run/dispatch[per-round]"]["thread"] \
            == threading.current_thread().name
        # Staged, then waited for, then dispatched, then the hook.
        assert spans["feeder.stage"]["t0_ns"] \
            <= spans["engine_run/dispatch[per-round]"]["t0_ns"] \
            <= spans["engine_run/on_round"]["t0_ns"]


def test_input_stall_is_observed_live_with_each_round():
    telemetry.reset()
    tele = telemetry.get()
    counts = []
    _train_tiny(on_round=lambda r, loss: counts.append(
        tele.histogram("input_stall").count))
    assert counts == [1, 2, 3, 4]  # not replayed at the end of the run
    assert tele.counter("input_stall_seconds").value == pytest.approx(
        tele.histogram("input_stall").total)


def test_setup_spans_close_before_the_run_loop_opens():
    telemetry.reset()
    _train_tiny()
    spans = {e["path"]: e for e in telemetry.get().timeline()}
    run = spans["engine_run"]
    for name in ("model_build", "setup.build_engine", "setup.plan",
                 "setup.init_state"):
        assert spans[name]["t0_ns"] + spans[name]["dur_ns"] <= run["t0_ns"], name
    assert "setup.resume" not in spans  # no checkpoint to resume from
    # What the benchmark's loop.dispatch_ms reads, letter for letter.
    assert "engine_run/dispatch[per-round]" in spans


def test_resume_is_a_setup_span(tmp_path):
    telemetry.reset()
    _train_tiny(checkpoint_dir=str(tmp_path), checkpoint_every=2)
    telemetry.reset()
    _train_tiny(checkpoint_dir=str(tmp_path), checkpoint_every=2, resume=True)
    assert "setup.resume" in {e["path"] for e in telemetry.get().timeline()}


# ---------------------------------------------------------------------------
# the compile listener
# ---------------------------------------------------------------------------

def test_compile_listener_counts_a_new_program_once():
    tele = telemetry.get()
    programs = tele.counter("compile.programs")

    @jax.jit
    def fresh(x):
        return jnp.tanh(x) * 3.0 + 0.125

    x = jnp.ones((3, 5))
    x.block_until_ready()
    before, mark = programs.value, time.perf_counter_ns()
    fresh(x).block_until_ready()
    assert programs.value == before + 1
    paths = [e["path"] for e in tele.timeline(since=mark - 10_000_000_000)
             if e["t0_ns"] + e["dur_ns"] >= mark]
    for name in ("compile.trace", "compile.lower", "compile.backend"):
        assert name in paths, paths
    fresh(x).block_until_ready()
    assert programs.value == before + 1  # the second call compiles nothing


# ---------------------------------------------------------------------------
# the scopes in the compiled round programs
# ---------------------------------------------------------------------------

def _scopes(text: str) -> set:
    return {part for name in re.findall(r'op_name="([^"]*)"', text)
            for part in re.split(r"[/;]", name) if part.startswith("dk_")}


def _round_text(engine, x, y):
    lead = (engine.num_workers, 2, 2)
    xs, ys = engine._put_batch(np.zeros(lead + x[0], x[1]),
                               np.zeros(lead + y[0], y[1]))
    return engine._round_fn.lower(
        engine.init_state(), xs, ys).compile().as_text()


def _tiny_gpt2():
    from distkeras_tpu.models.transformer import TransformerLM

    return Model.build(
        TransformerLM(vocab_size=64, num_layers=2, d_model=32, num_heads=2,
                      d_ff=64, max_seq_len=128, attn_impl="flash",
                      remat=True),
        jnp.zeros((1, 128), jnp.int32))


def _tiny_smallthinker():
    from distkeras_tpu.models import small_smallthinker_lm

    return small_smallthinker_lm(seq_len=128, attn_impl="flash", remat=True)


@pytest.mark.parametrize("build,recomputed", [
    (_tiny_gpt2, set()),
    (_tiny_smallthinker, {"dk_moe_route", "dk_moe_experts"}),
], ids=["gpt2", "smallthinker"])
def test_tiny_lm_round_program_carries_every_scope(build, recomputed):
    from distkeras_tpu.parallel.disciplines import AEASGDFold
    from distkeras_tpu.parallel.engine import AsyncEngine
    from distkeras_tpu.runtime.mesh import data_mesh

    engine = AsyncEngine(
        build(), "adam", "sparse_categorical_crossentropy", AEASGDFold(),
        data_mesh(num_workers=1), window=2, compute_dtype=jnp.bfloat16)
    text = _round_text(engine, ((128,), np.int32), ((128,), np.int32))
    assert {"dk_local_steps", "dk_fwd_bwd", "dk_optimizer", "dk_fold",
            "dk_loss_gather", "dk_nan_guard", "dk_flash_fwd", "dk_flash_dq",
            "dk_flash_dkv"} | recomputed <= _scopes(text)
    names = re.findall(r'op_name="([^"]*)"', text)
    backward = [n for n in names if "dk_fwd_bwd" in n and "transpose(" in n]
    remat = [n for n in names if "rematted_computation" in n]
    assert backward and remat
    # The block is recomputed, all but the flash forward: it runs in the first
    # pass alone, its out and lse kept (`flash_attention.FLASH_RESIDUALS`).
    assert any("dk_flash_fwd" in n and "transpose(" not in n for n in names)
    assert not any("dk_flash_fwd" in n for n in remat)
    assert recomputed <= {part for n in remat for part in n.split("/")}
    assert any("dk_flash_dq" in n for n in backward)
    assert any("dk_flash_dkv" in n for n in backward)


def test_tiny_resnet_round_program_carries_every_scope():
    from distkeras_tpu.models.resnet import ResNet
    from distkeras_tpu.parallel.sync import SyncEngine
    from distkeras_tpu.runtime.mesh import data_mesh

    model = Model.build(
        ResNet(stage_sizes=(1, 1), base_features=8, num_outputs=10,
               stem_kernel=3, groups=4, norm_impl="pallas"),
        jnp.zeros((1, 16, 16, 3), jnp.float32))
    engine = SyncEngine(model, "sgd", "sparse_categorical_crossentropy",
                        data_mesh(num_workers=2),
                        compute_dtype=jnp.bfloat16)
    text = _round_text(engine, ((16, 16, 3), np.uint8), ((), np.int32))
    assert {"dk_local_steps", "dk_fwd_bwd", "dk_grad_sync", "dk_optimizer",
            "dk_nan_guard", "dk_groupnorm_fwd",
            "dk_groupnorm_bwd"} <= _scopes(text)
    names = re.findall(r'op_name="([^"]*)"', text)
    assert any("dk_fwd_bwd" in n and "transpose(" in n for n in names)
    assert not any("rematted_computation" in n for n in names)


def test_stateful_model_with_a_device_transform_carries_the_other_scopes():
    import flax.linen as nn

    from distkeras_tpu.parallel.sync import SyncEngine
    from distkeras_tpu.runtime.mesh import data_mesh

    class BNMLP(nn.Module):
        @nn.compact
        def __call__(self, x, train: bool = False):
            x = nn.Dense(8)(x)
            x = nn.BatchNorm(use_running_average=not train)(x)
            return nn.Dense(3)(nn.relu(x))

    model = Model.build(BNMLP(), jnp.zeros((1, 4), jnp.float32))
    engine = SyncEngine(model, "sgd", "sparse_categorical_crossentropy",
                        data_mesh(num_workers=2),
                        device_transform=lambda rng, x, y: (x * 2.0, y))
    text = _round_text(engine, ((4,), np.float32), ((), np.int32))
    assert {"dk_input_transform", "dk_state_sync", "dk_grad_sync",
            "dk_fwd_bwd", "dk_optimizer"} <= _scopes(text)
