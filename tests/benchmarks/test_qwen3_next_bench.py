"""The Qwen3-Next family's part of the benchmark: the configuration states its
cut and every other key as published; its parameter and FLOP counts against
the numbers of the issue that added it; the new floors against hand-computed
values; the reader's reduction on a hand-made program and trace; the flash
kernels' tiling pinned at every shape the cells before this one run. All on
the CPU; nothing here is a measurement. (The rehearsal of
``qwen3next_aeasgd_w1``, traced and untraced, through ``run.py --rehearse``
and ``harness/result_line.py``'s check, is
``test_rehearse_qwen3next_aeasgd_w1.py``.)"""

from __future__ import annotations

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.families import qwen3_next as family  # noqa: E402
from benchmarks.harness import peaks, result_line  # noqa: E402
from benchmarks.readers import trace_gdn, trace_moe  # noqa: E402

V5E = peaks.PEAKS["TPU v5 lite"]
CELL = "qwen3next_aeasgd_w1"
NAME = "qwen3-next-80b-a3b"
HELD = {"num_hidden_layers": 4, "num_experts": 8, "vocab_size": 18992}
NEW = ("kernel.gdn_ms.lm", "kernel.gdn_roofline.lm",
       "kernel.flash_roofline.hd256", "moe.experts_roofline.qwen3next")


def _json(*parts):
    with open(os.path.join(ROOT, *parts), encoding="utf-8") as f:
        return json.load(f)


def _config():
    return _json("benchmarks", "configs", f"{NAME}.json")


def test_configuration_states_the_cut_and_the_published_counts():
    cfg = _config()
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["num_experts"], pub["vocab_size"]) \
        == (48, 512, 151936)
    assert cfg["reduced"] == list(HELD)
    assert set(cfg["held_here"]) == set(cfg["reduced"])
    for key, value in pub.items():  # every other key as published
        assert cfg[key] == HELD.get(key, value), key
    m = cfg["module"]
    assert (m["d_model"], m["num_heads"], m["num_kv_heads"], m["head_dim"],
            m["linear_key_heads"], m["linear_value_heads"],
            m["linear_head_dim"], m["conv_kernel"], m["d_expert"],
            m["d_shared"], m["num_experts"], m["experts_per_token"],
            m["rms_eps"], m["rope_theta"]) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["head_dim"],
        pub["linear_num_key_heads"], pub["linear_num_value_heads"],
        pub["linear_key_head_dim"], pub["linear_conv_kernel_dim"],
        pub["moe_intermediate_size"], pub["shared_expert_intermediate_size"],
        pub["num_experts"], pub["num_experts_per_tok"], pub["rms_norm_eps"],
        pub["rope_theta"])
    assert pub["linear_value_head_dim"] == pub["linear_key_head_dim"] == 128
    assert m["rotary_dim"] == pub["head_dim"] * pub["partial_rotary_factor"] \
        == 64
    # the held pattern: published layers 1-4, every fourth full attention
    interval = pub["full_attention_interval"]
    assert m["layer_types"] == ["attention" if (l + 1) % interval == 0
                                else "gdn" for l in range(4)]
    assert pub["decoder_sparse_step"] == 1 and pub["mlp_only_layers"] == []
    assert (m["num_layers"], m["experts_held"], m["vocab_size"]) \
        == (4, [0, 8], 18992)
    assert 64 * 8 == 512 and 8 * 18992 == 151936
    for said in ("64 chips share each layer", "the experts in 64 parts",
                 "the vocabulary in 8 slices", "323,677,248"):
        assert said in cfg["deployment"], said
    assert len(cfg["departures"]) >= 5 and set(cfg["assumed"]) >= {
        "bias", "short_conv", "qk_l2norm", "decay", "beta", "gdn_output",
        "gated_attention", "router", "shared_expert", "zero_centred_norms",
        "initialization"}
    assert m["attn_impl"] == "flash" and m["remat"] is True
    entry = next(c for c in _json("BENCHMARK.json")["configs"]
                 if c["name"] == NAME)
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    assert entry["file"] == f"benchmarks/configs/{NAME}.json"
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog, encoding="utf-8") as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
        assert row["config"] == pub and row["source_url"] == cfg["source"]


def test_flops_and_parameters_from_the_shapes():
    import jax
    import jax.numpy as jnp

    from distkeras_tpu.models.qwen3_next import Qwen3NextLM

    cfg = _config()
    m = cfg["module"]
    assert family.held_layers(m) == ["gdn", "gdn", "gdn", "attention"]
    assert family.attention_keys_seen(cfg) == [4096.5]
    # matmul parameters a token: the gdn operator 33,685,504, attention
    # 27,262,976, a layer's router + shared expert + its gate + 10 * 8/512
    # experts 4,687,872, the head 38,895,616
    gdn, attention, routed, head = (33_685_504, 27_262_976, 4_687_872,
                                    38_895_616)
    assert routed == 1_048_576 + 3_145_728 + 2_048 + 3_145_728 * 10 * 8 // 512
    per_token = 3 * gdn + attention + 4 * routed + head
    assert per_token == 185_966_592
    assert family.matmul_params_per_token(m) == per_token
    scores = 12 * 4096.5 * 16 * 256
    recurrence = 21 * 128 * 128 * 32 * 3
    assert recurrence == 33_030_144
    assert family.recurrence_flops_per_token(m) == recurrence
    assert family.train_flops_per_unit(cfg) \
        == 6 * per_token + scores + recurrence
    assert family.train_flops_per_unit(cfg) == pytest.approx(1.3502e9,
                                                             rel=1e-4)
    shapes = jax.eval_shape(lambda: Qwen3NextLM.from_config(m).init(
        jax.random.key(0), jnp.zeros((1, 128), jnp.int32), train=False))
    count = lambda tree: sum(a.size for a in jax.tree.leaves(tree))  # noqa: E731
    p = shapes["params"]
    # the issue's count: a gdn operator 33,718,464 (taps, A_log, dt_bias and
    # its norm too), attention 27,263,488 (its q/k norms), router + shared
    # expert + gate + norms 4,200,448, the 8 experts 25,165,824
    assert count(p["block_0"]["gdn"]) == 33_718_464
    assert count(p["block_3"]["attn"]) == 27_263_488
    for block in ("block_0", "block_3"):
        outside = count(p[block]) - count(p[block]["moe"]) \
            - count(p[block][{"block_0": "gdn", "block_3": "attn"}[block]])
        assert outside == 4_200_448
        assert count(p[block]["moe"]) == 25_165_824
    assert count(p["block_0"]) == count(p["block_1"]) == count(p["block_2"]) \
        == 37_918_912 + 25_165_824
    assert count(p["block_3"]) == 31_463_936 + 25_165_824
    assert count(p["tok_embed"]) + count(p["head"]) == 77_791_232
    assert count(p) == 323_677_248
    assert set(shapes) == {"params", "round_counters"}
    # 16 experts a layer: what leaves no room
    wider = jax.eval_shape(lambda: Qwen3NextLM.from_config(
        {**m, "experts_held": [0, 16]}).init(
            jax.random.key(0), jnp.zeros((1, 128), jnp.int32), train=False))
    assert count(wider["params"]) == 424_340_544


def test_gdn_floor_by_hand():
    # 65,536 tokens a round, 3 gdn layers, 16 key heads and 32 value heads of
    # 128: each of q, k (16 heads), v, o (32 heads), the decay and beta (one a
    # value head) moved three times a token and layer: 6 * 48 * 128 + 6 * 32
    # = 37,056 elements of 2 B; 1.457e10 B a round -> 17.79 ms at 819 GB/s.
    # Operations 21 * 128^2 * 32 * 65,536 * 3 = 2.165e12 -> 10.99 ms. Bound
    # by bytes.
    got = trace_gdn.gdn_floor(65536, 3, 16, 32, 128, V5E)
    assert got["bytes"] == (6 * 48 * 128 + 6 * 32) * 2 * 65536 * 3 \
        == 14_571_012_096
    assert got["flops"] == 21 * 128 * 128 * 32 * 65536 * 3
    assert got["bound"] == "bytes"
    assert got["seconds"] == pytest.approx(17.79e-3, rel=1e-3)
    # the floor of the work: what the chain moves today (q, k repeated to
    # 32 heads, the decay broadcast over 128 channels) is more
    today = (6 * 64 * 128 + 3 * 32 * 128 + 3 * 32) * 2 * 65536 * 3
    assert today > 1.4 * got["bytes"]
    assert trace_gdn.gdn_floor(65536, 0, 16, 32, 128, V5E)["seconds"] == 0


def test_flash_floor_at_head_width_256_by_hand():
    # one full causal layer of 8,192, 16 query heads over 2 K/V heads of 256:
    # operations 12 * 4096.5 * 16 * 256 * 65,536 = 1.320e13 -> 66.99 ms at
    # 197 TFLOP/s; bytes (4 * 16 + 4 * 2) * 256 * 2 * 65,536 = 2.416e9 -> 2.95
    # ms. Bound by operations.
    keys = family.attention_keys_seen(_config())
    got = trace_moe.flash_window_floor(65536, keys, 16, 2, 256, V5E)
    assert got["flops"] == 12 * 4096.5 * 16 * 256 * 65536
    assert got["bytes"] == (4 * 16 + 4 * 2) * 256 * 2 * 65536
    assert got["bound"] == "flops"
    assert got["seconds"] == pytest.approx(66.99e-3, rel=1e-3)


def test_experts_floor_at_this_configurations_shapes():
    # the even load: 16,384 tokens a step x 10 / 512 x 8 held = 2,560
    # assignments a layer and step, 40,960 over 4 layers x 4 steps:
    # 18 * 2048 * 512 * 40,960 = 7.731e11 -> 3.92 ms; bytes 2 * (16 * 8 * 3 *
    # 2048 * 512 + 40,960 * 2048) = 9.731e8 -> 1.19 ms. Bound by operations.
    got = trace_moe.experts_floor(40960, 16, 8, 2048, 512, V5E)
    assert got["flops"] == 18 * 2048 * 512 * 40960
    assert got["bytes"] == 2 * (16 * 8 * 3 * 2048 * 512 + 40960 * 2048)
    assert got["bound"] == "flops"
    assert got["seconds"] == pytest.approx(3.924e-3, rel=1e-3)


_GDN = "jit(round_fn)/dk_local_steps/dk_fwd_bwd/block_1/gdn"
HLO = f"""\
%fused_taps (p: bf16[8]) -> bf16[8] {{
  %add.2 = bf16[8]{{0}} add(%p, %p), metadata={{op_name="{_GDN}/dk_gdn_conv/add"}}
}}

%fused_qkvz (p: bf16[8], w: bf16[8,8]) -> bf16[8] {{
  %convolution.3 = bf16[8]{{0}} convolution(%p, %w), metadata={{op_name="{_GDN}/in_proj_qkvz/dot_general"}}
  %taps_fusion = bf16[8]{{0}} fusion(%convolution.3), kind=kLoop, calls=%fused_taps
}}

%fused_norm (p: f32[8]) -> f32[8] {{
  %rsqrt.7 = f32[8]{{0}} rsqrt(%p), metadata={{op_name="{_GDN}/dk_gdn/rsqrt"}}
}}

ENTRY %main (x: bf16[8], w: bf16[8,8]) -> bf16[8] {{
  %fusion.1 = bf16[8]{{0}} fusion(%x, %w), kind=kOutput, calls=%fused_qkvz, metadata={{op_name="{_GDN}/in_proj_qkvz/dot_general"}}
  %fusion.4 = f32[8]{{0}} fusion(%fusion.1), kind=kLoop, calls=%fused_norm
  %chunk.5 = bf16[8]{{0}} custom-call(%fusion.4), metadata={{op_name="{_GDN}/dk_gdn/dk_kda_chunk_fwd"}}
  %attn.10 = bf16[8]{{0}} custom-call(%chunk.5), metadata={{op_name="jit(round_fn)/dk_local_steps/dk_fwd_bwd/block_3/attn/dk_flash_fwd"}}
  %dq.11 = bf16[8]{{0}} custom-call(%attn.10), metadata={{op_name="jit(round_fn)/dk_local_steps/dk_fwd_bwd/transpose(jvp(block_3))/attn/dk_flash_dq"}}
  %dkv.12 = bf16[8]{{0}} custom-call(%dq.11), metadata={{op_name="jit(round_fn)/dk_local_steps/dk_fwd_bwd/transpose(jvp(block_3))/attn/dk_flash_dkv"}}
  ROOT %copy.13 = bf16[8]{{0}} copy(%dkv.12)
}}
"""


def _run(events, hlo=HLO, rounds=2, units=65536):
    return types.SimpleNamespace(
        hlo=hlo, trace={"ops0": events, "lo": 0, "hi": 10**9,
                        "rounds": rounds},
        window=types.SimpleNamespace(), peak=V5E, units_per_round=units,
        chips=1)


def test_reader_counts_the_scopes_and_leaves_the_projections_out():
    events = [(0, 50_000_000, "fusion.1"),          # W_qkvz with the taps
              (50_000_000, 30_000_000, "fusion.4"),  # the norm, fused
              (80_000_000, 90_000_000, "chunk.5"),   # a kernel of the chain
              (170_000_000, 60_000_000, "attn.10"),
              (230_000_000, 40_000_000, "dq.11"),
              (270_000_000, 100_000_000, "dkv.12")]
    run = _run(events)
    assert trace_gdn.read(run, what="gdn_ms") == pytest.approx(60.0)
    floor = trace_gdn.gdn_floor(65536, 3, 16, 32, 128, V5E)
    assert trace_gdn.read(run, what="gdn_roofline", config=NAME) \
        == pytest.approx(floor["seconds"] * 1e3 / 60.0 * 100.0)
    # the flash kernels: 66.99 ms of floor over 100 ms of kernels a round
    assert trace_gdn.read(run, what="flash_roofline", config=NAME) \
        == pytest.approx(66.99 / 100.0 * 100.0, rel=1e-3)


def test_reader_returns_nothing_where_there_is_nothing_to_read():
    # a program without the scopes (the parent's): None, and no raise
    old = "ENTRY %main () -> f32[] {\n  %c.1 = f32[] constant(0)\n}\n"
    run = _run([(0, 5, "c.1")], hlo=old, rounds=1)
    for what in ("gdn_ms", "gdn_roofline", "flash_roofline"):
        assert trace_gdn.read(run, what=what, config=NAME) is None
    # the scopes in the program and no event of them: 0.0
    run = _run([(0, 5, "copy.13")], rounds=1)
    for what in ("gdn_ms", "gdn_roofline", "flash_roofline"):
        assert trace_gdn.read(run, what=what, config=NAME) == 0.0
    assert trace_gdn.read(types.SimpleNamespace(trace=None)) is None


def test_the_cell_and_its_metrics_are_in_the_manifest():
    manifest = _json("BENCHMARK.json")
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["chips"]) == (NAME, 1)
    assert [w for w in manifest["workloads"] if w["name"] == CELL] == [cell] \
        and [c["name"] for c in manifest["configs"]].count(NAME) == 1
    workload = _json("benchmarks", "workloads", f"{CELL}.json")
    twin = _json("benchmarks", "workloads", "lfm2_aeasgd_w1.json")
    mine, theirs = workload["trainer"]["kwargs"], twin["trainer"]["kwargs"]
    # the same trainer and feed as the other 8k cells, 65,536 tokens a round
    assert {k: v for k, v in mine.items()
            if k not in ("batch_size", "communication_window")} \
        == {k: v for k, v in theirs.items()
            if k not in ("batch_size", "communication_window")}
    assert mine["batch_size"] * mine["communication_window"] == 8
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
    # split and the shared expert's time are on trace_gdn's [bench lines
    assert not any(n.startswith("owner.") or n == "moe.shared_ms.lm"
                   for n in traced)
    # the shares whose files name another configuration's shapes stay out
    assert not set(traced) & {
        "kernel.flash_roofline.lm", "kernel.flash_roofline.window",
        "kernel.flash_roofline.gqa64", "kernel.flash_roofline.mla",
        "moe.experts_roofline.lm", "moe.experts_roofline.lfm2",
        "moe.experts_roofline.kimi", "kernel.shortconv_ms.lm",
        "kernel.shortconv_roofline.lm", "kernel.kda_ms.lm",
        "kernel.kda_roofline.lm"}
    assert [n for n in (m["name"] for m in manifest["per_layer"])
            if n in NEW] == list(NEW)
    for name in NEW:
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "tokens_per_s_chip"
        spec = _json("benchmarks", "layer_metrics", f"{name}.json")
        assert spec["layer"] == entry["layer"]
        assert spec["arguments"].get("config", NAME) == NAME
    # no other cell reports the new metrics
    for other in (w["name"] for w in manifest["workloads"]
                  if w["name"] != CELL):
        assert not set(NEW) & set(
            result_line.declared_metrics(manifest, other, True))


#: ``(L, D, Dv, window) -> (block_q, block_k, cut)`` at every shape the cells
#: before this one run and ``chip_smoke.py`` checks: GPT-2 (1024, 64),
#: SmallThinker (8192, 128) windowed and full, LFM2 (8192, 64), Kimi Linear's
#: latent attention (8192, keys 192, values 128), the smoke's (2048, 64). At
#: each the kernels ask for Mosaic's default scoped VMEM, as they did.
TILINGS = {(1024, 64, None, None): (1024, 1024, 256),
           (8192, 128, None, 4096): (512, 512, 256),
           (8192, 128, None, None): (512, 512, 256),
           (8192, 64, None, None): (512, 512, 256),
           (8192, 192, 128, None): (256, 256, 128),
           (2048, 64, None, None): (2048, 2048, 512)}


@pytest.mark.parametrize("shape", list(TILINGS), ids=str)
def test_the_tiling_of_every_earlier_cell_is_pinned(shape):
    from distkeras_tpu.ops.pallas.flash_attention import (default_tiling,
                                                          scoped_vmem)

    L, D, Dv, window = shape
    assert default_tiling(L, D, 128, window, Dv) == TILINGS[shape]
    assert scoped_vmem(L, D, 128, Dv) is None


def test_heads_of_256_ask_for_more_scoped_vmem_and_tile_at_1024():
    """At L = 8,192 K and V of 256 whole take the default 16 MiB: the
    kernels ask for 48 MiB and tile by 1,024 (``dk_flash_dkv`` needs 34.4
    MiB there, measured by a compile for a described v5e)."""
    from distkeras_tpu.ops.pallas.flash_attention import (default_tiling,
                                                          scoped_vmem)

    assert scoped_vmem(8192, 256) == 48 * 2 ** 20
    assert default_tiling(8192, 256) == (1024, 1024, 512)
