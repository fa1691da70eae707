"""The LFM2 family's part of the benchmark: the configuration states its cut
and every other key as published; its parameter and FLOP counts against the
numbers of the issue that added it; the two new floors against hand-computed
values; the reader's reduction on a hand-made program and trace; and the
reference's limits at the tiny preset. All on the CPU; nothing here is a
measurement. (The rehearsal of ``lfm2_aeasgd_w1``, traced and untraced, is
``test_rehearse_lfm2_aeasgd_w1.py``.)"""

from __future__ import annotations

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.families import lfm2 as family  # noqa: E402
from benchmarks.harness import peaks, result_line  # noqa: E402
from benchmarks.readers import trace_moe, trace_shortconv  # noqa: E402

V5E = peaks.PEAKS["TPU v5 lite"]
CELL = "lfm2_aeasgd_w1"
HELD = {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 8,
        "vocab_size": 8192}


def _json(*parts):
    with open(os.path.join(ROOT, *parts), encoding="utf-8") as f:
        return json.load(f)


def _config():
    return _json("benchmarks", "configs", "lfm2-24b-a2b.json")


def test_configuration_states_the_cut_and_the_published_counts():
    cfg = _config()
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["num_dense_layers"],
            pub["num_experts"], pub["vocab_size"]) == (40, 2, 64, 65536)
    assert cfg["reduced"] == list(HELD)
    assert set(cfg["held_here"]) == set(HELD)
    for key, value in pub.items():  # every other key as published
        assert cfg[key] == HELD.get(key, value), key
    m = cfg["module"]
    assert (m["d_model"], m["d_ff"], m["d_expert"], m["num_heads"],
            m["num_kv_heads"], m["num_experts"], m["experts_per_token"],
            m["conv_kernel"], m["rope_theta"], m["rms_eps"],
            m["routed_scaling_factor"]) == (
        pub["hidden_size"], pub["intermediate_size"],
        pub["moe_intermediate_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["num_experts"],
        pub["num_experts_per_tok"], pub["conv_L_cache"],
        pub["rope_parameters"]["rope_theta"], pub["norm_eps"],
        pub["routed_scaling_factor"])
    assert m["head_dim"] * m["num_heads"] == m["d_model"]
    # the held pattern: published layers 0, 2, 3, 4, 5
    assert m["layer_types"] == [pub["layer_types"][l] for l in (0, 2, 3, 4, 5)]
    assert m["layer_types"][1:] == pub["layer_types"][2:6] \
        == ["full_attention", "conv", "conv", "conv"]  # one whole period
    assert (m["num_layers"], m["num_dense_layers"], m["experts_held"],
            m["vocab_size"]) == (5, 1, [0, 8], 8192) and 8 * 8192 == 65536
    assert "8 chips share each layer" in cfg["deployment"]
    assert len(cfg["departures"]) >= 4 and set(cfg["assumed"]) >= {
        "head_dim", "tie_word_embeddings", "qk_norm", "initialization",
        "expert_bias_update"}
    assert m["expert_bias_update"] == 5e-3
    # and the manifest's entry says the same
    entry = next(c for c in _json("BENCHMARK.json")["configs"]
                 if c["name"] == "lfm2-24b-a2b")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    # the catalog the source was read from: every number under its key
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog, encoding="utf-8") as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "LFM2-24B-A2B")
        assert row["config"] == pub and row["source_url"] == cfg["source"]


def test_flops_and_parameters_from_the_shapes():
    import jax
    import jax.numpy as jnp

    from distkeras_tpu.models.lfm2 import Lfm2MoeLM

    cfg = _config()
    m = cfg["module"]
    assert family.held_layers(m) == [
        ("conv", False), ("full_attention", True), ("conv", True),
        ("conv", True), ("conv", True)]
    assert family.attention_keys_seen(cfg) == [4096.5]
    # matmul parameters a token: the conv operator 2048 x 6144 + 2048 x 2048
    # = 16,777,216, attention 10,485,760, the dense feed-forward 72,351,744,
    # a router 131,072, 4 * 8/64 experts of 9,437,184, the head 16,777,216
    conv, attention, dense, router, expert, head = (
        16_777_216, 10_485_760, 72_351_744, 131_072, 9_437_184, 16_777_216)
    per_token = (conv + dense) + (attention + router + 0.5 * expert) \
        + 3 * (conv + router + 0.5 * expert) + head
    assert per_token == 186_122_240
    assert family.matmul_params_per_token(m) == per_token
    scores = 12 * 2048 * 4096.5
    assert family.train_flops_per_unit(cfg) == 6 * per_token + scores
    # about 1.22 GFLOP a token, 80 TFLOP a round of 65,536
    assert family.train_flops_per_unit(cfg) == pytest.approx(1.22e9, rel=0.01)
    assert family.train_flops_per_unit(cfg) * 65536 \
        == pytest.approx(79.8e12, rel=0.01)
    shapes = jax.eval_shape(lambda: Lfm2MoeLM.from_config(m).init(
        jax.random.key(0), jnp.zeros((1, 128), jnp.int32), train=False))
    count = lambda tree: sum(a.size for a in jax.tree.leaves(tree))  # noqa: E731
    p = shapes["params"]
    assert count(p["block_0"]["conv"]) == 16_783_360      # with the taps
    assert count(p["block_0"]["mlp"]) == 72_351_744
    assert count(p["block_1"]["attn"]) == 10_485_888      # with q/k norm
    assert count(p["block_1"]["moe"]) == 8 * 9_437_184 == 75_497_472
    # the router's 64 biases are state beside the parameters
    bias = shapes["router_bias"]
    assert count(p["block_1"]["router"]) \
        + count(bias["block_1"]["expert_bias"]) == 131_136
    assert count(p["tok_embed"]) == 16_777_216 and "lm_head" not in p
    assert count(p) + count(bias) == 469_285_248 and count(bias) == 4 * 64


def test_shortconv_floor_by_hand():
    # 65,536 tokens a round, 4 conv layers, d 2048: 11 * 2048 elements of 2 B
    # a token and layer = 45,056 B; 11.811e9 B a round -> 14.421 ms at 819
    # GB/s. Operations 28 * 2048 * 65,536 * 4 = 1.5e10 -> 0.076 ms. Bound by
    # bytes.
    got = trace_shortconv.shortconv_floor(65536, 4, 2048, V5E)
    assert got["bytes"] == 45056 * 65536 * 4 == 11_811_160_064
    assert got["flops"] == 28 * 2048 * 65536 * 4
    assert got["bound"] == "bytes"
    assert got["seconds"] == pytest.approx(14.421e-3, rel=1e-3)
    # a layer without the convolution counts nothing
    assert trace_shortconv.shortconv_floor(65536, 0, 2048, V5E)["seconds"] == 0


def test_flash_floor_of_the_one_attention_layer_by_hand():
    # 65,536 tokens a round through one full causal layer of 8,192, 32 query
    # and 8 K/V heads of 64: operations 12 * 4096.5 * 2048 * 65,536 =
    # 6.598e12 -> 33.49 ms at 197 TFLOP/s; bytes (4 * 2048 + 4 * 512) * 2 *
    # 65,536 = 1.342e9 -> 1.64 ms. Bound by operations.
    keys = family.attention_keys_seen(_config())
    got = trace_moe.flash_window_floor(65536, keys, 32, 8, 64, V5E)
    assert got["flops"] == 12 * 4096.5 * 2048 * 65536
    assert got["bytes"] == (4 * 2048 + 4 * 512) * 2 * 65536
    assert got["bound"] == "flops"
    assert got["seconds"] == pytest.approx(33.49e-3, rel=1e-3)


def test_experts_floor_at_this_configurations_shapes():
    # the even load: 65,536 tokens x 4 / 64 x 8 held = 32,768 assignments a
    # layer and round, 131,072 over the 4 routed layers, in 16 layer-steps:
    # 18 * 2048 * 1536 * 131,072 = 7.422e12 -> 37.67 ms; bytes 2 * (16 * 8 *
    # 3 * 2048 * 1536 + 131,072 * 2048) = 2.953e9 -> 3.61 ms.
    got = trace_moe.experts_floor(131072, 16, 8, 2048, 1536, V5E)
    assert got["flops"] == 18 * 2048 * 1536 * 131072
    assert got["bytes"] == 2 * (16 * 8 * 3 * 2048 * 1536 + 131072 * 2048)
    assert got["bound"] == "flops"
    assert got["seconds"] == pytest.approx(37.67e-3, rel=1e-3)


HLO = """\
%fused_gate (p: bf16[8]) -> bf16[8] {
  %slice.1 = bf16[8]{0} slice(%p), metadata={op_name="jit(round_fn)/dk_local_steps/dk_fwd_bwd/block_0/conv/dk_shortconv/slice"}
  %mul.1 = bf16[8]{0} multiply(%slice.1, %p), metadata={op_name="jit(round_fn)/dk_local_steps/dk_fwd_bwd/block_0/conv/dk_shortconv/mul"}
}

%fused_taps (p: bf16[8]) -> bf16[8] {
  %add.2 = bf16[8]{0} add(%p, %p), metadata={op_name="jit(round_fn)/dk_local_steps/dk_fwd_bwd/block_0/conv/dk_shortconv/add"}
}

%fused_out_proj (p: bf16[8], w: bf16[8,8]) -> bf16[8] {
  %taps_fusion = bf16[8]{0} fusion(%p), kind=kLoop, calls=%fused_taps
  %convolution.3 = bf16[8]{0} convolution(%taps_fusion, %w), metadata={op_name="jit(round_fn)/dk_local_steps/dk_fwd_bwd/block_0/conv/out_proj/dot_general"}
}

%fused_unnamed (p: bf16[8]) -> bf16[8] {
  %mul.7 = bf16[8]{0} multiply(%p, %p), metadata={op_name="jit(round_fn)/dk_local_steps/dk_fwd_bwd/transpose(jvp(block_0))/conv/dk_shortconv/mul"}
  %convert.7 = f32[8]{0} convert(%mul.7)
}

ENTRY %main (x: bf16[8], w: bf16[8,8]) -> bf16[8] {
  %slice_multiply_fusion.4 = bf16[8]{0} fusion(%x), kind=kLoop, calls=%fused_gate, metadata={op_name="jit(round_fn)/dk_local_steps/dk_fwd_bwd/block_0/conv/dk_shortconv/mul"}
  %fusion.5 = bf16[8]{0} fusion(%slice_multiply_fusion.4, %w), kind=kOutput, calls=%fused_out_proj, metadata={op_name="jit(round_fn)/dk_local_steps/dk_fwd_bwd/block_0/conv/out_proj/dot_general"}
  %fusion.6 = f32[8]{0} fusion(%fusion.5), kind=kLoop, calls=%fused_unnamed
  %attn.8 = bf16[8]{0} custom-call(%fusion.6), metadata={op_name="jit(round_fn)/dk_local_steps/dk_fwd_bwd/block_1/attn/dk_flash_fwd"}
  %dq.9 = bf16[8]{0} custom-call(%attn.8), metadata={op_name="jit(round_fn)/dk_local_steps/dk_fwd_bwd/transpose(jvp(block_1))/attn/dk_flash_dq"}
  %dkv.10 = bf16[8]{0} custom-call(%dq.9), metadata={op_name="jit(round_fn)/dk_local_steps/dk_fwd_bwd/transpose(jvp(block_1))/attn/dk_flash_dkv"}
  ROOT %copy.11 = bf16[8]{0} copy(%dkv.10)
}
"""


def _run(events, hlo=HLO, rounds=2, units=65536):
    return types.SimpleNamespace(
        hlo=hlo, trace={"ops0": events, "lo": 0, "hi": 10**9,
                        "rounds": rounds},
        window=types.SimpleNamespace(), peak=V5E, units_per_round=units,
        chips=1)


def test_reader_counts_the_scope_and_leaves_the_matmuls_out():
    counted, with_matmul = trace_shortconv.classify(HLO)
    # its own name; a fusion without one that fused the chain and no matmul
    assert counted == {"slice_multiply_fusion.4", "fusion.6"}
    # W_out's product with the taps fused into it: the matmul's time
    assert with_matmul == {"fusion.5"}
    events = [(0, 30_000_000, "slice_multiply_fusion.4"),
              (30_000_000, 100_000_000, "fusion.5"),
              (130_000_000, 10_000_000, "fusion.6"),
              (140_000_000, 40_000_000, "attn.8"),
              (180_000_000, 30_000_000, "dq.9"),
              (210_000_000, 50_000_000, "dkv.10")]
    got = trace_shortconv.reduce(HLO, events, 0, 10**9)
    assert got["ns"] == 40_000_000 and got["with_matmul_ns"] == 100_000_000
    assert got["stems"] == {"slice_multiply_fusion": 30_000_000,
                            "fusion": 10_000_000}
    run = _run(events)
    assert trace_shortconv.read(run, what="ms") == pytest.approx(20.0)
    floor = trace_shortconv.shortconv_floor(65536, 4, 2048, V5E)
    assert trace_shortconv.read(run, what="roofline", config="lfm2-24b-a2b") \
        == pytest.approx(floor["seconds"] * 1e3 / 20.0 * 100.0)
    # the flash kernels against the one layer that has attention: 33.49 ms
    # over 60 ms of kernels a round
    assert trace_shortconv.read(run, what="flash_attention_layers",
                                config="lfm2-24b-a2b") \
        == pytest.approx(33.49 / 60.0 * 100.0, rel=1e-3)


def test_reader_returns_nothing_where_there_is_nothing_to_read():
    # a program without the scope (the parent's): None, and no raise
    old = "ENTRY %main () -> f32[] {\n  %c.1 = f32[] constant(0)\n}\n"
    run = _run([(0, 5, "c.1")], hlo=old, rounds=1)
    for what in ("ms", "roofline"):
        assert trace_shortconv.read(run, what=what,
                                    config="lfm2-24b-a2b") is None
    # a program with scopes of ours but no flash kernel: no share of theirs
    no_flash = HLO[:HLO.index("  %attn.8")] + "}\n"
    assert trace_shortconv.read(
        _run([(0, 5, "fusion.6")], hlo=no_flash),
        what="flash_attention_layers", config="lfm2-24b-a2b") is None
    # the scope in the program and no event of it: 0.0, as trace_scope
    run = _run([(0, 5, "copy.11")], rounds=1)
    assert trace_shortconv.read(run, what="ms") == 0.0
    assert trace_shortconv.read(run, what="roofline",
                                config="lfm2-24b-a2b") == 0.0
    assert trace_shortconv.read(run, what="flash_attention_layers",
                                config="lfm2-24b-a2b") == 0.0
    # no trace at all
    assert trace_shortconv.read(types.SimpleNamespace(trace=None)) is None


def test_experts_share_reads_this_configurations_shapes():
    from distkeras_tpu import telemetry

    tele = telemetry.get()
    for r, n in ((70, 1.0), (71, 60000.0), (72, 65536.0), (73, 70000.0)):
        tele.event("moe.round", {
            "round": r, "layers": 4, "steps": 4.0, "assignments_held": n,
            "load_max_over_mean": 1.2, "bias_moved_share": 0.1,
            "tokens_without_held_expert_share": 0.58})
    hlo = HLO.replace("%copy.11 = bf16[8]{0} copy(%dkv.10)",
                      '%ragged-dot-none.11 = bf16[8]{0} custom-call(%dkv.10)')
    run = types.SimpleNamespace(
        hlo=hlo, peak=V5E, units_per_round=65536, chips=1,
        trace={"ops0": [(0, 300_000_000, "ragged-dot-none.11")], "lo": 0,
               "hi": 400_000_000, "rounds": 3},
        window=types.SimpleNamespace(_trace_open=69))
    floor = trace_moe.experts_floor((60000 + 65536 + 70000) / 3, 16, 8,
                                    2048, 1536, V5E)
    assert trace_moe.read(run, floor="experts", config="lfm2-24b-a2b") \
        == pytest.approx(floor["seconds"] * 1e3 / 100.0 * 100.0)


def test_the_cell_and_its_metrics_are_in_the_manifest():
    manifest = _json("BENCHMARK.json")
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("lfm2-24b-a2b", "aeasgd_w1_8k", 1)
    workload = _json("benchmarks", "workloads", f"{CELL}.json")
    twin = _json("benchmarks", "workloads", "smallthinker_aeasgd_w1.json")
    assert workload["trainer"] == twin["trainer"]   # the same traffic
    # but for the law of the fresh ids: Zipf's here, even there (LFM2's
    # bias balances its load; SmallThinker's router does not, PERF.md §6)
    assert workload["feed"] == {k: v for k, v in twin["feed"].items()
                                if k != "ids"}
    assert twin["feed"]["ids"] == "uniform" and "ids" not in workload["feed"]
    untraced = result_line.declared_metrics(manifest, CELL, False)
    assert set(untraced) == {"tokens_per_s_chip", "setup_s"}
    traced = result_line.declared_metrics(manifest, CELL, True)
    assert set(traced) >= {
        "kernel.shortconv_ms.lm", "kernel.shortconv_roofline.lm",
        "moe.experts_roofline.lfm2", "kernel.flash_roofline.gqa64",
        "data.stall_ms.lm", "loop.dispatch_ms.lm", "round.device_ms.lm",
        "model.mfu.lm", "step.forward_ms.lm", "step.backward_ms.lm",
        "step.optimizer_ms.lm", "step.remat_ms.lm", "fold.device_ms.lm",
        "kernel.flash_ms.lm", "moe.route_ms.lm", "moe.experts_ms.lm"}
    # the shares whose files name another configuration's shapes stay out
    assert not set(traced) & {"kernel.flash_roofline.lm",
                              "kernel.flash_roofline.window",
                              "moe.experts_roofline.lm"}
    for name in ("kernel.shortconv_ms.lm", "kernel.shortconv_roofline.lm",
                 "moe.experts_roofline.lfm2", "kernel.flash_roofline.gqa64"):
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "tokens_per_s_chip"
        spec = _json("benchmarks", "layer_metrics", f"{name}.json")
        assert spec["layer"] == entry["layer"]
        assert spec["arguments"].get("config", "lfm2-24b-a2b") \
            == "lfm2-24b-a2b"


def test_reference_limits_at_the_tiny_preset():
    """In float32 with the module's dense attention the wiring agrees to
    rounding and the routing is the same; in bfloat16 both limits hold; the
    reference in float8 fails the logits' limit, a skipped layer fails it by
    far, and a model that chooses by another rule fails the routing's."""
    import copy
    import functools

    import jax
    import jax.numpy as jnp

    from benchmarks.references import lfm2 as reference
    from distkeras_tpu.models import lfm2 as program

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
    fewer["module"]["num_layers"] = 2
    wrong = family.reference_check(model, fewer, 7, "bfloat16")
    assert not wrong["ok"] and wrong["rel_l2"] > 3 * wrong["tolerance"], wrong

    # The reference follows the model's choice of experts, so a model that
    # chooses wrongly (here: the least likely) agrees on the logits still;
    # the routing's limit is what refuses it.
    def least_likely(logits, bias, k, scale=1.0):
        probs = jax.nn.sigmoid(logits.astype(jnp.float32))
        _, e = jax.lax.top_k(-(probs + bias.astype(jnp.float32)), k)
        w = jnp.take_along_axis(probs, e, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6) * scale
        return w, e, jnp.zeros(e.shape, bool)

    route = program.route_sigmoid_bias_top_k
    program.route_sigmoid_bias_top_k = least_likely
    try:
        other = family.reference_check(model, cfg, 7, "bfloat16")
    finally:
        program.route_sigmoid_bias_top_k = route
    assert not other["ok"] and other["rel_l2"] <= other["tolerance"], other
    assert other["routing_agreement"] < 0.1, other
