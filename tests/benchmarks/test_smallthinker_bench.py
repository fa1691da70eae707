"""The SmallThinker family's part of the benchmark: its FLOP count and the
two floors of ``readers/trace_moe.py`` against hand-computed numbers, the
reader's reduction on a hand-made program and trace, and the reference's
limits at the tiny preset. All on the CPU; nothing here is a measurement."""

from __future__ import annotations

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.families import smallthinker as family  # noqa: E402
from benchmarks.harness import peaks  # noqa: E402
from benchmarks.readers import trace_moe  # noqa: E402

V5E = peaks.PEAKS["TPU v5 lite"]


def _config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "smallthinker-21b-a3b.json"), encoding="utf-8") as f:
        return json.load(f)


def test_configuration_states_the_cut_and_the_published_counts():
    cfg = _config()
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["moe_num_primary_experts"],
            pub["vocab_size"]) == (52, 64, 151936)
    assert cfg["reduced"] == ["num_hidden_layers", "moe_num_primary_experts",
                              "vocab_size"]
    for key, value in pub.items():  # every other key as published
        assert cfg[key] == (value if key not in cfg["reduced"] else
                            {"num_hidden_layers": 4,
                             "moe_num_primary_experts": 8,
                             "vocab_size": 18992}[key]), key
    m = cfg["module"]
    assert (m["d_model"], m["num_heads"], m["num_kv_heads"], m["head_dim"],
            m["d_expert"], m["num_experts"], m["experts_per_token"],
            m["window"], m["rope_theta"], m["rms_eps"]) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["head_dim"],
        pub["moe_ffn_hidden_size"], pub["moe_num_primary_experts"],
        pub["moe_num_active_primary_experts"], pub["sliding_window_size"],
        pub["rope_theta"], pub["rms_norm_eps"])
    assert m["rope_layout"] == pub["rope_layout"][:4]
    assert m["window_layout"] == pub["sliding_window_layout"][:4]
    assert (m["num_layers"], m["experts_held"], m["vocab_size"]) \
        == (4, [0, 8], 18992) and 8 * 18992 == 151936
    assert "8 chips share each layer" in cfg["deployment"]


def test_flops_and_parameters_from_the_shapes():
    import jax
    import jax.numpy as jnp

    from distkeras_tpu.models.smallthinker import SmallThinkerLM

    cfg = _config()
    m = cfg["module"]
    assert family.mean_keys_seen(8192, None) == 4096.5
    assert family.mean_keys_seen(8192, 4096) == 3072.25
    assert family.mean_keys_seen(2048, 4096) == 1024.5
    # a layer: attention 20,971,520, router 163,840, 6 * 8/64 experts of
    # 5,898,240; the head 18,992 x 2560
    per_token = 4 * (20_971_520 + 163_840 + 0.75 * 5_898_240) + 48_619_520
    assert family.matmul_params_per_token(m) == per_token
    scores = 12 * 3584 * (4096.5 + 3 * 3072.25)
    assert family.train_flops_per_unit(cfg) == 6 * per_token + scores
    # about 24 TFLOP a step of 16,384 tokens, 39 % of it the scores
    step = family.train_flops_per_unit(cfg) * 16384
    assert step == pytest.approx(24.0e12, rel=0.02)
    assert scores * 16384 / step == pytest.approx(0.39, abs=0.01)
    shapes = jax.eval_shape(lambda: SmallThinkerLM.from_config(m).init(
        jax.random.key(0), jnp.zeros((1, 128), jnp.int32), train=False))
    assert sum(a.size for a in jax.tree.leaves(shapes["params"])) \
        == 370_547_200


def test_experts_floor_by_hand():
    # 49,152 assignments a round in 16 layer-steps: operations
    # 18 * 2560 * 768 * 49,152 = 1.7395e12 -> 8.830 ms at 197 TFLOP/s;
    # bytes 2 * (16 * 8 * 3 * 2560 * 768 + 49,152 * 2560) = 1.7616e9
    # -> 2.151 ms at 819 GB/s. Bound by operations.
    got = trace_moe.experts_floor(49152, 16, 8, 2560, 768, V5E)
    assert got["flops"] == 18 * 2560 * 768 * 49152
    assert got["bytes"] == 2 * (16 * 8 * 3 * 2560 * 768 + 49152 * 2560)
    assert got["bound"] == "flops"
    assert got["seconds"] == pytest.approx(8.830e-3, rel=1e-3)
    # few rows: the weights' traffic bounds it
    few = trace_moe.experts_floor(1024, 16, 8, 2560, 768, V5E)
    assert few["bound"] == "bytes"
    assert few["seconds"] == pytest.approx(
        2 * (16 * 8 * 3 * 2560 * 768 + 1024 * 2560) / 819e9)


def test_flash_window_floor_by_hand():
    # 65,536 tokens a round, one full and three windowed layers of 8,192:
    # operations 12 * 3584 * (4096.5 + 3 * 3072.25) * 65,536 = 3.7525e13
    # -> 190.48 ms; bytes (4 * 3584 + 4 * 512) * 2 * 65,536 * 4 = 8.59e9
    # -> 10.49 ms. Bound by operations.
    keys = [4096.5, 3072.25, 3072.25, 3072.25]
    got = trace_moe.flash_window_floor(65536, keys, 28, 4, 128, V5E)
    assert got["flops"] == 12 * 3584 * sum(keys) * 65536
    assert got["bytes"] == (4 * 3584 + 4 * 512) * 2 * 65536 * 4
    assert got["bound"] == "flops"
    assert got["seconds"] == pytest.approx(0.19048, rel=1e-3)


HLO = """\
%fused_gate (p: f32[8]) -> f32[8] {
  %mul.1 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(round_fn)/dk_local_steps/dk_fwd_bwd/block_0/moe/dk_moe_experts/experts/mul"}
}

%fused_two (p: f32[8]) -> f32[8] {
  %a.1 = f32[8]{0} add(%p, %p), metadata={op_name="jit(round_fn)/dk_fwd_bwd/dk_moe_combine/add"}
  %b.1 = f32[8]{0} add(%a.1, %p), metadata={op_name="jit(round_fn)/dk_fwd_bwd/dk_moe_experts/add"}
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %sort.3 = f32[8]{0} sort(%x), metadata={op_name="jit(round_fn)/dk_local_steps/dk_fwd_bwd/block_0/moe/dk_moe_route/sort"}
  %gather.2 = f32[8]{0} gather(%sort.3), metadata={op_name="jit(round_fn)/dk_local_steps/dk_fwd_bwd/transpose(jvp(block_0))/moe/dk_moe_combine/gather"}
  %ragged-dot-none.4 = f32[8]{0} custom-call(%gather.2), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %gate_fusion = f32[8]{0} fusion(%ragged-dot-none.4), kind=kLoop, calls=%fused_gate
  %both_fusion = f32[8]{0} fusion(%gate_fusion), kind=kLoop, calls=%fused_two
  %attn.5 = f32[8]{0} custom-call(%both_fusion), metadata={op_name="jit(round_fn)/dk_local_steps/dk_fwd_bwd/block_0/attn/dk_flash_fwd"}
  ROOT %copy.9 = f32[8]{0} copy(%attn.5)
}
"""


def test_reader_sums_self_time_by_part():
    by = trace_moe.scopes_by_instruction(HLO)
    assert trace_moe.part_of(by["sort.3"]) == "dk_moe_route"
    assert trace_moe.part_of(by["gather.2"]) == "dk_moe_combine"
    assert trace_moe.part_of(by["ragged-dot-none.4"]) == "dk_moe_experts"
    assert trace_moe.part_of(by["gate_fusion"]) == "dk_moe_experts"
    assert trace_moe.part_of(by["both_fusion"]) == "dk_moe_experts"  # once
    assert trace_moe.part_of(by["attn.5"]) is None
    assert trace_moe.part_of(by["copy.9"]) is None
    events = [(0, 10, "sort.3"), (10, 30, "gather.2"),
              (40, 100, "ragged-dot-none.4"), (140, 20, "gate_fusion"),
              (160, 5, "both_fusion"), (165, 50, "attn.5"),
              (990, 40, "sort.3")]                       # cut at the bracket
    got = trace_moe.reduce(HLO, events, 0, 1000)
    assert got["parts"] == {"dk_moe_experts": 125.0, "dk_moe_combine": 30.0,
                            "dk_moe_route": 20.0}
    assert got["stems"]["dk_moe_experts"] == {
        "ragged-dot-none": 100.0, "gate_fusion": 20.0, "both_fusion": 5.0}
    assert {"dk_moe_route", "dk_moe_experts", "dk_moe_combine",
            "dk_flash_fwd"} <= got["scopes"]

    run = types.SimpleNamespace(
        hlo=HLO, trace={"ops0": events, "lo": 0, "hi": 1000, "rounds": 2},
        window=types.SimpleNamespace(), peak=V5E, units_per_round=8, chips=1)
    assert trace_moe.read(run, scopes=["dk_moe_route", "dk_moe_combine"]) \
        == pytest.approx(50.0 / 2 * 1e-6)
    assert trace_moe.read(run, scopes=["dk_moe_experts"]) \
        == pytest.approx(125.0 / 2 * 1e-6)
    # no trace bracket on the window, so no event of a traced round: no share
    assert trace_moe.read(run, floor="experts",
                          config="smallthinker-21b-a3b") is None
    # a program without the scopes (the parent's): nothing, and no raise
    run = types.SimpleNamespace(
        hlo="ENTRY %main () -> f32[] {\n  %c.1 = f32[] constant(0)\n}\n",
        trace={"ops0": [(0, 5, "c.1")], "lo": 0, "hi": 10, "rounds": 1},
        window=types.SimpleNamespace(), peak=V5E, units_per_round=8, chips=1)
    assert trace_moe.read(run, scopes=["dk_moe_experts"]) is None
    assert trace_moe.read(run, floor="flash_window",
                          config="smallthinker-21b-a3b") is None
    # the scopes in the program and no event of theirs: 0.0, as trace_scope
    run = types.SimpleNamespace(
        hlo=HLO, trace={"ops0": [(0, 5, "copy.9")], "lo": 0, "hi": 10,
                        "rounds": 1},
        window=types.SimpleNamespace(), peak=V5E, units_per_round=8, chips=1)
    assert trace_moe.read(run, scopes=["dk_moe_experts"]) == 0.0
    assert trace_moe.read(types.SimpleNamespace(trace=None),
                          scopes=["dk_moe_experts"]) is None


def test_experts_share_reads_the_programs_count_of_the_traced_rounds():
    from distkeras_tpu import telemetry

    tele = telemetry.get()
    for r, n in ((40, 1.0), (41, 40000.0), (42, 50000.0), (43, 60000.0),
                 (44, 2.0)):
        tele.event("moe.round", {
            "round": r, "layers": 4, "steps": 4.0, "assignments_held": n,
            "load_max_over_mean": 1.1,
            "tokens_without_held_expert_share": 0.45})
    events = [(0, 500_000_000, "ragged-dot-none.4")]
    run = types.SimpleNamespace(
        hlo=HLO, peak=V5E, units_per_round=65536, chips=1,
        trace={"ops0": events, "lo": 0, "hi": 600_000_000, "rounds": 3},
        window=types.SimpleNamespace(_trace_open=39))
    assert [e["round"] for e in trace_moe.traced_round_events(run)] \
        == [41, 42, 43]
    floor = trace_moe.experts_floor(50000.0, 16, 8, 2560, 768, V5E)
    ms = 500.0 / 3
    assert trace_moe.read(run, floor="experts",
                          config="smallthinker-21b-a3b") \
        == pytest.approx(floor["seconds"] * 1e3 / ms * 100.0)


def test_reference_limits_at_the_tiny_preset():
    """In float32 with the module's dense attention the wiring agrees to
    rounding and the routing is the same; in bfloat16 both limits hold; the
    reference in float8 fails the logits' limit, a skipped block fails it by
    far, and a model that chooses other experts fails the routing's."""
    import copy
    import functools

    import jax.numpy as jnp

    from benchmarks.references import smallthinker as reference

    cfg = _config()
    cfg = {**cfg, **family.TINY,
           "module": {**cfg["module"], **family.TINY["module"]}}
    plain = copy.deepcopy(cfg)
    plain["module"]["attn_impl"] = "dense"
    exact = family.reference_check(family.build_model(plain, 3), plain, 7, None)
    assert exact["ok"] and exact["rel_l2"] < 1e-5, exact
    assert exact["routing_agreement"] == 1.0
    model = family.build_model(cfg, 3)
    lowp = family.reference_check(model, cfg, 7, "bfloat16")
    assert lowp["ok"] and lowp["rel_l2"] > exact["rel_l2"], lowp
    assert lowp["routing_agreement"] > 0.9
    coarse = family.reference_check(
        model, cfg, 7, "bfloat16", forward=functools.partial(
            reference.forward, round_to=jnp.float8_e4m3fn))
    assert not coarse["ok"] and coarse["rel_l2"] > coarse["tolerance"], coarse
    fewer = copy.deepcopy(cfg)
    fewer["module"]["num_layers"] = 1
    wrong = family.reference_check(model, fewer, 7, "bfloat16")
    assert not wrong["ok"] and wrong["rel_l2"] > 3 * wrong["tolerance"], wrong

    # The reference follows the model's choice of experts, so a model that
    # chooses wrongly (here: the least likely) agrees on the logits still;
    # the routing's limit is what refuses it.
    import jax

    from distkeras_tpu.models import smallthinker as program

    def least_likely(logits, k):
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        w, e = jax.lax.top_k(-probs, k)
        return w / jnp.sum(w, axis=-1, keepdims=True), e

    route_top_k = program.route_top_k
    program.route_top_k = least_likely
    try:
        other = family.reference_check(model, cfg, 7, "bfloat16")
    finally:
        program.route_top_k = route_top_k
    assert not other["ok"] and other["rel_l2"] <= other["tolerance"], other
    assert other["routing_agreement"] < 0.1, other


def test_the_cell_draws_its_ids_evenly_and_the_feed_passes_the_law_on():
    """The cell's workload names the even law; ``make_dataframe`` takes it
    from the feed; the stream stays in the slice and learnable (three
    quarters of the transitions follow the successor map); an unknown law
    is refused."""
    import numpy as np

    from benchmarks.families.transformer_lm import learnable_tokens

    with open(os.path.join(ROOT, "benchmarks", "workloads",
                           "smallthinker_aeasgd_w1.json"),
              encoding="utf-8") as f:
        feed = json.load(f)["feed"]
    assert feed["ids"] == "uniform"
    config = {"seq_len": 256, "module": {"vocab_size": 4096}}
    extra = {k: v for k, v in feed.items() if k not in ("rows", "num_epoch")}
    df = family.make_dataframe(config, 16, 7, **extra)
    x, y = np.asarray(df["features"]), np.asarray(df["label"])
    assert x.shape == y.shape == (16, 256)
    assert 0 <= x.min() and x.max() < 4096
    assert np.array_equal(x[:, 1:], y[:, :-1])
    followed = np.mean(y == (x * 31 + 7) % 4096)
    assert 0.7 < followed < 0.8
    zipf, _ = learnable_tokens(16, 256, 4096, 7)
    even, _ = learnable_tokens(16, 256, 4096, 7, ids="uniform")
    assert np.array_equal(even, x)
    # Zipf puts a tenth of the fresh draws on id 0; the even law 1/4096
    assert np.mean(zipf == 0) > 20 * np.mean(even == 0)
    with pytest.raises(ValueError, match="ids"):
        learnable_tokens(1, 8, 16, 0, ids="zipfian")


@pytest.mark.parametrize("ids, widest", [("zipf", None), ("uniform", 0.03)])
def test_an_untrained_router_gives_every_seed_one_share_under_even_ids(
        ids, widest):
    """Why the cell draws its ids evenly: a router at initialisation sends an
    id to the same experts every time, so the held experts' share of the
    assignments is a sum over the stream's ids. Under Zipf's law a few
    hundred ids carry half of it and the share moves with the seed; under
    the even law it holds. A first layer's routing (embeddings N(0, 1), a
    lecun-normal router, top 6 of 64, experts 0-7 held) over eight seeds, at
    a vocabulary and width a test can hold: the even law's relative spread
    stays under ``widest``, Zipf's is over twice the even law's. (At the
    cell's 18,992 ids and width 2,560, over its 256 rows: 3.7 % and 0.87 %,
    PERF.md §6.)"""
    import numpy as np

    from benchmarks.families.transformer_lm import learnable_tokens

    V, D, E, K, HELD = 4096, 256, 64, 6, 8

    def shares(law):
        out = []
        for seed in range(8):
            rng = np.random.default_rng(100 + seed)
            emb = rng.standard_normal((V, D), dtype=np.float32)
            router = rng.standard_normal((D, E), dtype=np.float32) / D ** 0.5
            top = np.argpartition(-(emb @ router), K, axis=1)[:, :K]
            x, _ = learnable_tokens(32, 2048, V, seed, ids=law)
            count = np.bincount(x.ravel(), minlength=V)
            out.append((count * (top < HELD).sum(1)).sum()
                       / (count.sum() * K))
        out = np.asarray(out)
        return out.std() / out.mean()

    spread = shares(ids)
    if widest is not None:
        assert spread < widest
    else:
        assert spread > 2 * shares("uniform")
