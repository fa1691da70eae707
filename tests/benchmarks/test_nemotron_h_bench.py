"""The Nemotron-H family's part of the benchmark: the configuration states its
cut and every other key as published; its parameter and FLOP counts against
the deployment's arithmetic; the two new floors against
hand-computed values; the reader's reduction on a hand-made program and
trace. All on the CPU; nothing here is a measurement. (The rehearsal of
``nemotron3nano_aeasgd_w1``, traced and untraced, through ``run.py
--rehearse`` and ``harness/result_line.py``'s check, is
``test_rehearse_nemotron3nano_aeasgd_w1.py``.)"""

from __future__ import annotations

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.families import nemotron_h as family  # noqa: E402
from benchmarks.harness import peaks, result_line  # noqa: E402
from benchmarks.readers import trace_moe, trace_ssd  # noqa: E402

V5E = peaks.PEAKS["TPU v5 lite"]
CELL = "nemotron3nano_aeasgd_w1"
NAME = "nemotron-3-nano-30b-a3b"
HELD = {"num_hidden_layers": 7, "n_routed_experts": 8, "vocab_size": 16384,
        "mamba_num_heads": 32, "n_groups": 4, "num_attention_heads": 16,
        "num_key_value_heads": 1}
NEW = ("kernel.ssd_ms.lm", "kernel.ssd_roofline.lm",
       "moe.experts_roofline.nemotron")


def _json(*parts):
    with open(os.path.join(ROOT, *parts), encoding="utf-8") as f:
        return json.load(f)


def _config():
    return _json("benchmarks", "configs", f"{NAME}.json")


def test_configuration_states_the_cut_and_the_published_counts():
    cfg = _config()
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["n_routed_experts"],
            pub["vocab_size"], pub["mamba_num_heads"], pub["n_groups"],
            pub["num_attention_heads"], pub["num_key_value_heads"]) \
        == (52, 128, 131072, 64, 8, 32, 2)
    assert cfg["reduced"] == list(HELD)
    assert set(cfg["held_here"]) == set(cfg["reduced"])
    for key, value in pub.items():  # every other key as published
        assert cfg[key] == HELD.get(key, value), key
    m = cfg["module"]
    assert (m["d_model"], m["num_heads"], m["num_kv_heads"], m["head_dim"],
            m["mamba_num_heads"], m["mamba_head_dim"], m["n_groups"],
            m["ssm_state_size"], m["conv_kernel"], m["d_expert"],
            m["d_shared"], m["num_experts"], m["experts_per_token"],
            m["routed_scaling_factor"], m["rms_eps"]) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["head_dim"], pub["mamba_num_heads"],
        pub["mamba_head_dim"], pub["n_groups"], pub["ssm_state_size"],
        pub["conv_kernel"], pub["moe_intermediate_size"],
        pub["moe_shared_expert_intermediate_size"], pub["n_routed_experts"],
        pub["num_experts_per_tok"], pub["routed_scaling_factor"],
        pub["layer_norm_epsilon"])
    assert pub["mlp_hidden_act"] == "relu2" and pub["n_shared_experts"] == 1
    assert pub["n_group"] == pub["topk_group"] == 1
    assert pub["use_conv_bias"] is True and pub["mamba_proj_bias"] is False
    # the held pattern: published layers 6-12, the period that repeats
    pattern = pub["hybrid_override_pattern"]
    assert pattern[6:13] == "EMEMEM*"
    letters = {"M": "mamba", "E": "moe", "*": "attention"}
    assert m["layer_types"] == [letters[c] for c in pattern[6:13]]
    assert (m["num_layers"], m["experts_held"], m["vocab_size"],
            m["mamba_heads_held"], m["heads_held"]) \
        == (7, [0, 8], 16384, [0, 32], [0, 16])
    held = family.held_heads(m)
    assert (held["mamba"], held["groups"], held["query"], held["kv"]) \
        == (HELD["mamba_num_heads"], HELD["n_groups"],
            HELD["num_attention_heads"], HELD["num_key_value_heads"])
    assert 16 * 8 == 128 and 8 * 16384 == 131072
    for said in ("16 chips share each layer", "the experts in 16 parts",
                 "the vocabulary in 8 slices", "458,281,248",
                 "528,092,736"):
        assert said in cfg["deployment"], said
    assert len(cfg["departures"]) >= 5 and set(cfg["assumed"]) >= {
        "mamba_inner_width", "short_conv", "ssd", "gated_norm",
        "attention_positions", "experts", "router", "correction_bias",
        "initialization", "sequence"}
    assert "9-layer" in cfg["held_here"]["num_hidden_layers"]
    assert m["attn_impl"] == "flash" and m["remat"] is True
    entry = next(c for c in _json("BENCHMARK.json")["configs"]
                 if c["name"] == NAME)
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    assert entry["file"] == f"benchmarks/configs/{NAME}.json"


def _count(module: dict) -> tuple:
    import jax
    import jax.numpy as jnp

    from distkeras_tpu.models.nemotron_h import NemotronHLM

    shapes = jax.eval_shape(lambda: NemotronHLM.from_config(module).init(
        jax.random.key(0), jnp.zeros((1, 128), jnp.int32), train=False))
    count = lambda tree: sum(a.size for a in jax.tree.leaves(tree))  # noqa: E731
    return shapes, count


def test_flops_and_parameters_from_the_shapes():
    cfg = _config()
    m = cfg["module"]
    assert family.held_layers(m) == ["moe", "mamba"] * 3 + ["attention"]
    assert family.attention_keys_seen(cfg) == [4096.5]
    # matmul parameters a token: a mamba layer's W_in and W_out 19,353,600;
    # attention 11,698,176; a moe layer's router, shared expert and 6 * 8/128
    # experts 24,041,472; the head 44,040,192
    mamba, attention, moe, head = (19_353_600, 11_698_176, 24_041_472,
                                   44_040_192)
    assert mamba == 2688 * (2 * 2048 + 2 * 512 + 32) + 2048 * 2688
    assert moe == 2688 * 128 + 2 * 2688 * 3712 + 6 * 8 * 2 * 2688 * 1856 // 128
    per_token = 3 * mamba + attention + 3 * moe + head
    assert per_token == 185_923_584
    assert family.matmul_params_per_token(m) == per_token
    scores = 12 * 4096.5 * 16 * 128
    recurrence = 12 * 128 * 64 * 32 * 3
    assert family.recurrence_flops_per_token(m) == recurrence
    assert family.train_flops_per_unit(cfg) \
        == 6 * per_token + scores + recurrence
    assert family.train_flops_per_unit(cfg) == pytest.approx(1.2257e9,
                                                             rel=1e-4)
    shapes, count = _count(m)
    p = shapes["params"]
    # the deployment's count: an expert layer 100,125,312 (experts 79,822,848,
    # shared 19,955,712, router 344,064, norm), a mamba layer 19,373,792 and
    # attention 11,700,864 with their heads in 2, 88,080,384 embedding and
    # head rows
    assert count(p["block_0"]) == 100_125_312
    assert count(p["block_0"]["moe"]) == 79_822_848
    assert count(p["block_0"]["shared"]) == 19_955_712
    assert count(p["block_0"]["router"]) == 344_064
    assert count(p["block_1"]) == 19_373_792
    assert count(p["block_6"]) == 11_700_864
    assert count(p["tok_embed"]) + count(p["head"]) == 88_080_384
    assert count(p) == 458_281_248
    assert set(shapes) == {"params", "round_counters", "router_bias"}
    # whole heads: what does not fit; heads in 4: the stated fall-back
    whole = _count({**m, "mamba_heads_held": [0, 64],
                    "heads_held": [0, 32]})[0]["params"]
    assert count(whole["block_1"]) == 38_744_896
    assert count(whole["block_6"]) == 23_399_040
    assert count(whole) == 528_092_736
    assert count(_count({**m, "mamba_heads_held": [0, 16],
                         "heads_held": [0, 8]})[0]["params"]) == 423_719_568


def test_ssd_floor_by_hand():
    # 65,536 tokens a round, 3 mamba layers of 32 heads of 64 over a state of
    # 128 in 4 groups: x, y, dx, dy (4 x 32 x 64), B, C, dB, dC (4 x 4 x 128)
    # and dt with its cotangent (2 x 32) a token and layer, 10,304 elements
    # of 2 B: 4.052e9 B a round -> 4.947 ms at 819 GB/s. Operations 12 * 128 *
    # 64 * 32 * 65,536 * 3 = 6.185e11 -> 3.14 ms. Bound by bytes.
    got = trace_ssd.ssd_floor(65536, 3, 32, 4, 64, 128, V5E)
    assert got["bytes"] == (4 * 32 * 64 + 4 * 4 * 128 + 2 * 32) * 2 \
        * 65536 * 3 == 4_051_697_664
    assert got["flops"] == 12 * 128 * 64 * 32 * 65536 * 3
    assert got["bound"] == "bytes"
    assert got["seconds"] == pytest.approx(4.947e-3, rel=1e-3)
    assert got["flops"] / V5E["bf16_flops_per_s"] == pytest.approx(3.14e-3,
                                                                   rel=1e-3)
    assert trace_ssd.ssd_floor(65536, 0, 32, 4, 64, 128, V5E)["seconds"] == 0


def test_plain_experts_floor_by_hand():
    # the even load: 8,192 tokens a step x 6 / 128 x 8 held = 3,072
    # assignments a layer and step, 73,728 over 3 layers x 8 steps:
    # 12 * 2688 * 1856 * 73,728 = 4.414e12 -> 22.40 ms; bytes 2 * (24 * 8 * 2
    # * 2688 * 1856 + 73,728 * 2688) = 4.227e9 -> 5.16 ms. Bound by
    # operations.
    got = trace_ssd.plain_experts_floor(73728, 24, 8, 2688, 1856, V5E)
    assert got["flops"] == 12 * 2688 * 1856 * 73728
    assert got["bytes"] == 2 * (24 * 8 * 2 * 2688 * 1856 + 73728 * 2688)
    assert got["bound"] == "flops"
    assert got["seconds"] == pytest.approx(22.40e-3, rel=1e-3)
    # the gated experts' floor would count three products: 3/2 of this
    gated = trace_moe.experts_floor(73728, 24, 8, 2688, 1856, V5E)
    assert gated["flops"] == pytest.approx(1.5 * got["flops"])


_SSD = "jit(round_fn)/dk_local_steps/dk_fwd_bwd/block_1/mixer"
_MOE = "jit(round_fn)/dk_local_steps/dk_fwd_bwd/block_0/moe"
HLO = f"""\
%fused_taps (p: bf16[8]) -> bf16[8] {{
  %add.2 = bf16[8]{{0}} add(%p, %p), metadata={{op_name="{_SSD}/dk_ssd_conv/add"}}
}}

%fused_in (p: bf16[8], w: bf16[8,8]) -> bf16[8] {{
  %convolution.3 = bf16[8]{{0}} convolution(%p, %w), metadata={{op_name="{_SSD}/in_proj/dot_general"}}
  %taps_fusion = bf16[8]{{0}} fusion(%convolution.3), kind=kLoop, calls=%fused_taps
}}

%fused_gamma (p: f32[8]) -> f32[8] {{
  %exp.7 = f32[8]{{0}} exponential(%p), metadata={{op_name="{_SSD}/dk_ssd/exp"}}
}}

ENTRY %main (x: bf16[8], w: bf16[8,8]) -> bf16[8] {{
  %fusion.1 = bf16[8]{{0}} fusion(%x, %w), kind=kOutput, calls=%fused_in, metadata={{op_name="{_SSD}/in_proj/dot_general"}}
  %fusion.4 = f32[8]{{0}} fusion(%fusion.1), kind=kLoop, calls=%fused_gamma
  %scan.5 = bf16[8]{{0}} custom-call(%fusion.4), metadata={{op_name="{_SSD}/dk_ssd/dk_kda_scan_fwd"}}
  %ragged.6 = bf16[8]{{0}} custom-call(%scan.5), metadata={{op_name="{_MOE}/dk_moe_experts/ragged_dot"}}
  ROOT %copy.13 = bf16[8]{{0}} copy(%ragged.6)
}}
"""


def _run(events, hlo=HLO, rounds=2, units=65536, trace_open=None):
    return types.SimpleNamespace(
        hlo=hlo, trace={"ops0": events, "lo": 0, "hi": 10**9,
                        "rounds": rounds},
        window=types.SimpleNamespace(_trace_open=trace_open), peak=V5E,
        units_per_round=units, chips=1)


def test_reader_counts_the_scopes_and_leaves_the_projections_out():
    events = [(0, 50_000_000, "fusion.1"),           # W_in with the taps
              (50_000_000, 30_000_000, "fusion.4"),  # Gamma, fused
              (80_000_000, 90_000_000, "scan.5"),    # the scan, no W
              (170_000_000, 80_000_000, "ragged.6")]
    run = _run(events)
    assert trace_ssd.read(run, what="ssd_ms") == pytest.approx(60.0)
    floor = trace_ssd.ssd_floor(65536, 3, 32, 4, 64, 128, V5E)
    assert trace_ssd.read(run, what="ssd_roofline", config=NAME) \
        == pytest.approx(floor["seconds"] * 1e3 / 60.0 * 100.0)
    # the experts' share needs the program's moe.round events of the traced
    # rounds: none here
    assert trace_ssd.read(run, what="experts_roofline", config=NAME) is None


def test_reader_takes_the_experts_share_from_the_rounds_the_program_counted():
    from distkeras_tpu import telemetry

    first = 10_000 + len(telemetry.get().events())  # rounds of their own
    for r in (first + 2, first + 3):
        telemetry.event("moe.round", {
            "round": r, "layers": 3, "steps": 8.0, "assignments_held": 73728.0,
            "load_max_over_mean": 1.0, "tokens_without_held_expert_share": 0.7})
    run = _run([(0, 80_000_000, "ragged.6")], trace_open=first)
    floor = trace_ssd.plain_experts_floor(73728, 24, 8, 2688, 1856, V5E)
    assert trace_ssd.read(run, what="experts_roofline", config=NAME) \
        == pytest.approx(floor["seconds"] * 1e3 / 40.0 * 100.0)


def test_reader_returns_nothing_where_there_is_nothing_to_read():
    # a program without the scopes (the parent's): None, and no raise
    old = "ENTRY %main () -> f32[] {\n  %c.1 = f32[] constant(0)\n}\n"
    run = _run([(0, 5, "c.1")], hlo=old, rounds=1)
    for what in ("ssd_ms", "ssd_roofline", "experts_roofline"):
        assert trace_ssd.read(run, what=what, config=NAME) is None
    # the scopes in the program and no event of them: 0.0
    run = _run([(0, 5, "copy.13")], rounds=1)
    for what in ("ssd_ms", "ssd_roofline"):
        assert trace_ssd.read(run, what=what, config=NAME) == 0.0
    assert trace_ssd.read(types.SimpleNamespace(trace=None)) is None


def test_the_cell_and_its_metrics_are_in_the_manifest():
    manifest = _json("BENCHMARK.json")
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["chips"], cell["traffic"]) \
        == (NAME, 1, "aeasgd_w1_8k_b1w8")
    assert [w for w in manifest["workloads"] if w["name"] == CELL] == [cell] \
        and [c["name"] for c in manifest["configs"]].count(NAME) == 1
    workload = _json("benchmarks", "workloads", f"{CELL}.json")
    twin = _json("benchmarks", "workloads", "kimi_linear_aeasgd_w1.json")
    # Kimi Linear's trainer and feed: 1 x 8,192 tokens a step, window 8
    assert workload["trainer"] == twin["trainer"]
    assert workload["feed"] == twin["feed"]
    untraced = result_line.declared_metrics(manifest, CELL, False)
    assert set(untraced) == {"tokens_per_s_chip", "setup_s"}
    traced = result_line.declared_metrics(manifest, CELL, True)
    joined = {"data.stall_ms.lm", "loop.dispatch_ms.lm", "round.device_ms.lm",
              "model.mfu.lm", "step.forward_ms.lm", "step.backward_ms.lm",
              "step.optimizer_ms.lm", "step.remat_ms.lm", "step.mixed_ms.lm",
              "step.other_ms.lm", "fold.device_ms.lm", "kernel.flash_ms.lm",
              "moe.route_ms.lm", "moe.experts_ms.lm"}
    assert set(traced) >= set(NEW) | joined
    # the lists of cells that test_kimi_linear_bench.py and
    # test_trace_owner.py pin, which only a benchmark PR may edit: the owner
    # split and the shared expert's time are on trace_ssd's [bench lines
    assert not any(n.startswith("owner.") or n == "moe.shared_ms.lm"
                   for n in traced)
    # the shares whose files name another configuration's shapes stay out
    assert not any(n.startswith(("kernel.flash_roofline.",
                                 "moe.experts_roofline.", "kernel.kda_",
                                 "kernel.gdn_", "kernel.shortconv_"))
                   for n in traced if n not in NEW)
    assert [n for n in (m["name"] for m in manifest["per_layer"])
            if n in NEW] == list(NEW)
    for name in NEW:
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "tokens_per_s_chip"
        spec = _json("benchmarks", "layer_metrics", f"{name}.json")
        assert spec["layer"] == entry["layer"]
        assert spec["reader"] == "trace_ssd"
        assert spec["arguments"].get("config", NAME) == NAME
    # no other cell reports the new metrics
    for other in (w["name"] for w in manifest["workloads"]
                  if w["name"] != CELL):
        assert not set(NEW) & set(
            result_line.declared_metrics(manifest, other, True))
