"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py                  # on a TPU host: the real sizes
    python chip_smoke.py --rehearse-cpu   # anywhere: toy sizes, control flow only

One process (a chip belongs to one process) drives the main path through the
entry points a user calls, in this order, and fails if any phase failed:

* **device**   — platform, device_kind, count, package versions. Anything but
  a TPU is an error; the CPU rehearsal is asked for by name, never detected.
* **train-1**  — ``dk.AEASGD(...).train(df)`` on the flagship ``TransformerLM``
  at full width (8 layers, d_model 1024, vocab 32768, 8 x 2048 tokens a step,
  flash attention, remat, bf16) on a seeded learnable token stream. Every loss
  finite, the last round below the first, the compiled round program holds
  the Mosaic custom call. Set-up (``setup_s``: init + compile + the first
  round, to the first fetched loss; ``init_s`` is its model-and-data share)
  is reported apart from the steady round seconds (``round_s``, fetched loss
  to fetched loss).
* **kernels**  — every Pallas kernel a trainer can select, compiled at its
  benchmark cell's shape, forward and backward, against an XLA reference.
* **four-chip** (only where >= 4 devices are visible) — AEASGD at W=4 on
  ``cifar10_cnn`` and on the flagship; ADAG at W=2 x tp=2 through the
  partially-auto ``AsyncTPEngine``; all four devices busy, one fold all-reduce.
* **remote**   — a parameter-server *subprocess* beside the trainer that holds
  the chip, int8 commits; the child must never open a TPU client.

The last line of a run on a TPU is one JSON object with exactly two keys,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``
(``"ok": false`` and exit code 1 if a phase failed). The line before it,
``[summary] {...}``, holds everything measured and makes no performance claim
(it ends with ``"claim": null``): its seconds are set-up and smoke-length
rounds, not a benchmark. Without a TPU the script exits 2 and prints no
result; a rehearsal prints ``{"rehearsal": true, ...}`` instead of a result.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.metadata
import json
import os
import subprocess
import sys
import time
import traceback

import numpy as np


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Every size the smoke uses. ``FULL`` is the width the benchmark cells
    run at; ``TOY`` only rehearses control flow on a CPU."""

    layers: int
    d_model: int
    heads: int
    d_ff: int
    vocab: int
    seq: int
    lm_batch: int
    lm_window: int
    lm_rounds: int
    tp_layers: int
    cnn_batch: int
    cnn_window: int
    flash: tuple      # (B, L, H, Dh), one per sequence length checked
    lstm: tuple       # (B, T, E, H)
    groupnorm: tuple  # (B, H, W, C, groups)
    fold: tuple       # tensor shape
    rows: tuple       # (tokens, k, D, held of 64 experts) of an expert layer
    kda: tuple        # (B, L, H, Dh) of a Kimi Delta Attention layer
    mlp_rows: int


FULL = Sizes(layers=8, d_model=1024, heads=16, d_ff=4096, vocab=32768,
             seq=2048, lm_batch=8, lm_window=8, lm_rounds=4, tp_layers=2,
             cnn_batch=2048, cnn_window=8,
             flash=((8, 2048, 16, 64), (8, 1024, 16, 64)),  # flagship; gpt2m cell
             lstm=(2048, 200, 64, 128),
             groupnorm=(128, 112, 112, 64, 32), fold=(8192, 512),
             rows=(16384, 6, 2560, 8),  # the smallthinker cell's step
             kda=(1, 8192, 8, 128),     # the kimi_linear cell's step
             mlp_rows=8192)
TOY = Sizes(layers=1, d_model=64, heads=2, d_ff=128, vocab=256,
            seq=128, lm_batch=2, lm_window=2, lm_rounds=3, tp_layers=1,
            cnn_batch=16, cnn_window=2,
            flash=((1, 128, 2, 16), (1, 64, 2, 16)), lstm=(8, 6, 8, 128),
            groupnorm=(2, 8, 8, 64, 32), fold=(70, 33), rows=(64, 2, 32, 16),
            kda=(2, 128, 2, 16),
            mlp_rows=2048)


class SmokeFailure(AssertionError):
    """A phase ran but its result is wrong."""


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def device_facts(rehearse: bool) -> dict:
    import jax
    import jaxlib

    dev = jax.devices()[0]
    facts = {"platform": dev.platform, "kind": dev.device_kind,
             "count": len(jax.devices())}
    versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "libtpu": importlib.metadata.version("libtpu"),
                "python": sys.version.split()[0]}
    print(f"[device] platform={facts['platform']} device_kind={facts['kind']!r} "
          f"count={facts['count']} " +
          " ".join(f"{k}={v}" for k, v in versions.items()), flush=True)
    if facts["platform"] != "tpu" and not rehearse:
        print(f"[device] FAILED: found platform {facts['platform']!r}, not a "
              "TPU. This script proves the chip path; on a CPU pass "
              "--rehearse-cpu to walk the control flow at toy size.",
              file=sys.stderr, flush=True)
        raise SystemExit(2)
    return {"device": facts, "versions": versions}


def peak_bytes() -> list:
    import jax

    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in jax.devices()]


# ---------------------------------------------------------------------------
# training phases
# ---------------------------------------------------------------------------

def learnable_tokens(n: int, seq: int, vocab: int, seed: int):
    """A seeded token stream a language model can learn: Zipf unigrams, and
    three quarters of the transitions follow one fixed successor map. Uniform
    noise would pin the loss at ln V and a falling loss would prove nothing."""
    import distkeras_tpu as dk

    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, vocab + 1)
    draws = rng.choice(vocab, size=(n, seq + 1), p=p / p.sum())
    follow = rng.random((n, seq + 1)) < 0.75
    x = draws.copy()
    for t in range(1, seq + 1):
        x[:, t] = np.where(follow[:, t], (x[:, t - 1] * 31 + 7) % vocab,
                           draws[:, t])
    return dk.DataFrame({"features": x[:, :-1].astype(np.int32),
                         "label": x[:, 1:].astype(np.int32)})


class RoundClock:
    """The trainer's ``on_round`` hook: fetch the round's loss (the fetch is
    what ends each timing) and note when it arrived."""

    def __init__(self):
        self.t0 = time.perf_counter()
        self.t_built = self.t0  # model and data exist; train() starts
        self.ticks: list = []
        self.losses: list = []

    def __call__(self, r, loss):
        self.losses.append(float(np.mean(np.asarray(loss))))
        self.ticks.append(time.perf_counter())

    def facts(self) -> dict:
        later = np.diff(self.ticks)
        return {"init_s": round(self.t_built - self.t0, 2),
                "setup_s": round(self.ticks[0] - self.t0, 2),
                "round_s": (round(float(np.median(later)), 4)
                            if len(later) else None),
                "losses": [round(x, 4) for x in self.losses]}


def _check_losses(losses, what: str) -> None:
    _require(len(losses) >= 3, f"{what}: only {len(losses)} fold rounds ran")
    _require(bool(np.all(np.isfinite(losses))),
             f"{what}: non-finite loss in {losses}")
    _require(losses[-1] < losses[0],
             f"{what}: loss did not fall ({losses[0]} -> {losses[-1]})")


def round_program_text(engine, x_shape, x_dtype, y_shape, y_dtype) -> str:
    """The optimized HLO of the engine's round program, compiled for the
    same argument shapes and shardings ``train()`` ran it with."""
    lead = (engine.num_workers, engine.window)
    xs, ys = engine._put_batch(np.zeros(lead + x_shape, x_dtype),
                               np.zeros(lead + y_shape, y_dtype))
    return engine._round_fn.lower(
        engine.init_state(), xs, ys).compile().as_text()


def _count_all_reduce(hlo: str) -> int:
    import re

    return len(re.findall(r"all-reduce(?:-start)?\(", hlo))


def _dk_scopes(hlo: str) -> list:
    """The ``dk_*`` named scopes and kernel names that the compiled program's
    ``op_name`` metadata carries (what a device trace finds its phases by)."""
    import re

    return sorted({part for name in re.findall(r'op_name="([^"]*)"', hlo)
                   for part in re.split(r"[/;]", name)
                   if part.startswith("dk_")})


def train_lm(sz: Sizes, *, discipline: str, layers: int, num_workers: int,
             parallel=None, seed: int, on_tpu: bool) -> dict:
    """Train the flagship TransformerLM through ``dk.<discipline>(...)
    .train(df)`` and check what came out."""
    import jax
    import jax.numpy as jnp

    import distkeras_tpu as dk
    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.transformer import TransformerLM

    clock = RoundClock()
    model = Model.build(
        TransformerLM(num_layers=layers, d_model=sz.d_model,
                      num_heads=sz.heads, d_ff=sz.d_ff, vocab_size=sz.vocab,
                      max_seq_len=sz.seq, attn_impl="flash", remat=True),
        jnp.zeros((1, sz.seq), jnp.int32), seed=seed)
    df = learnable_tokens(
        num_workers * sz.lm_window * sz.lm_batch * sz.lm_rounds, sz.seq,
        sz.vocab, seed)
    kwargs = dict(worker_optimizer="adam",
                  loss="sparse_categorical_crossentropy",
                  num_workers=num_workers, batch_size=sz.lm_batch,
                  communication_window=sz.lm_window, learning_rate=3e-4,
                  compute_dtype="bfloat16", seed=seed, on_round=clock)
    if parallel:
        kwargs["parallel"] = parallel
    trainer = getattr(dk, discipline)(model, **kwargs)
    clock.t_built = time.perf_counter()
    trained = trainer.train(df)
    _check_losses(clock.losses, f"{discipline} W={num_workers}")
    leaves = [np.asarray(a) for a in jax.tree.leaves(trained.params)]
    _require(all(np.all(np.isfinite(a)) for a in leaves),
             "trained parameters are not finite")
    _require(sum(a.size for a in leaves) == model.num_params,
             "trained model lost parameters")
    t0 = time.perf_counter()
    hlo = round_program_text(trainer.engine, (sz.lm_batch, sz.seq), np.int32,
                             (sz.lm_batch, sz.seq), np.int32)
    facts = dict(clock.facts(), params=model.num_params,
                 tokens_per_round=num_workers * sz.lm_window * sz.lm_batch
                 * sz.seq,
                 hlo_text_s=round(time.perf_counter() - t0, 2),
                 mosaic_calls=hlo.count("tpu_custom_call"),
                 all_reduces=_count_all_reduce(hlo),
                 dk_scopes=_dk_scopes(hlo))
    if on_tpu:
        _require(facts["mosaic_calls"] > 0,
                 "the compiled round program holds no Mosaic custom call: "
                 "flash attention interpreted or gave way to dense")
    _require("dk_flash_fwd" in facts["dk_scopes"],
             "the round program runs flash attention and nothing in it is "
             f"named dk_flash_fwd (scopes found: {facts['dk_scopes']}): the "
             "benchmark's readers would not find the kernel")
    if num_workers > 1:
        _require(facts["all_reduces"] >= 1,
                 "the W>1 round program holds no all-reduce: nothing folds")
    return facts


def train_cifar(sz: Sizes, num_workers: int) -> dict:
    """The north-star cell: AEASGD on cifar10_cnn, one fold per window."""
    import distkeras_tpu as dk
    from distkeras_tpu.datasets import cifar10
    from distkeras_tpu.models.cnn import cifar10_cnn

    clock = RoundClock()
    model = cifar10_cnn(seed=0)
    df = cifar10(n=num_workers * sz.cnn_window * sz.cnn_batch, seed=0)
    # alpha = rho * lr = 0.05, so W * alpha stays well under 1 (stable fold).
    trainer = dk.AEASGD(
        model, worker_optimizer="sgd", loss="sparse_categorical_crossentropy",
        num_workers=num_workers, batch_size=sz.cnn_batch,
        communication_window=sz.cnn_window, learning_rate=0.05, rho=1.0,
        num_epoch=3, compute_dtype="bfloat16", on_round=clock)
    clock.t_built = time.perf_counter()
    trainer.train(df, shuffle=True)
    _check_losses(clock.losses, f"AEASGD cifar10_cnn W={num_workers}")
    hlo = round_program_text(trainer.engine, (sz.cnn_batch, 32, 32, 3),
                             np.float32, (sz.cnn_batch,), np.int32)
    n = _count_all_reduce(hlo)
    # One fused all-reduce for the fold; the loss gather may add one more op
    # at most — never one per parameter tensor (tests/test_hlo_properties.py).
    _require(1 <= n <= 2, f"expected one fused fold all-reduce, found {n}")
    return dict(clock.facts(), all_reduces=n, dk_scopes=_dk_scopes(hlo))


def phase_four_chip(sz: Sizes, on_tpu: bool) -> dict:
    out = {"cifar10_cnn_aeasgd_w4": train_cifar(sz, 4),
           "flagship_aeasgd_w4": train_lm(
               sz, discipline="AEASGD", layers=sz.layers, num_workers=4,
               seed=1, on_tpu=on_tpu),
           "flagship_adag_w2_tp2": train_lm(
               sz, discipline="ADAG", layers=sz.tp_layers, num_workers=2,
               parallel={"model": 2}, seed=2, on_tpu=on_tpu)}
    peaks = peak_bytes()[:4]
    out["peak_bytes_in_use"] = peaks
    if on_tpu:  # the CPU backend reports no memory statistics
        _require(min(peaks) > 0 and max(peaks) <= 2 * min(peaks),
                 f"device memory is lopsided, {peaks}: work that should "
                 "spread over four chips stayed on some of them")
    return out


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _rel_l2(got, ref) -> float:
    """Relative L2 error. Robust where a max-abs test is not: a ReLU mask
    flips on the handful of pre-activations that round across zero."""
    got = np.asarray(got, np.float32).ravel()
    ref = np.asarray(ref, np.float32).ravel()
    return float(np.linalg.norm(got - ref) / (np.linalg.norm(ref) + 1e-30))


def _compare(name: str, got, ref, tol: float) -> dict:
    import jax

    errs = {}
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(ref)):
        _require(g.shape == r.shape, f"{name}{path}: shape {g.shape} vs "
                                     f"{r.shape}")
        _require(bool(np.all(np.isfinite(np.asarray(g, np.float32)))),
                 f"{name}{path}: non-finite values")
        errs[jax.tree_util.keystr(path)] = round(_rel_l2(g, r), 6)
    worst = max(errs.values())
    print(f"[kernels] {name}: rel-L2 error vs XLA reference {errs}",
          flush=True)
    _require(worst <= tol, f"{name}: error {worst} above {tol}: {errs}")
    return {"rel_l2_max": worst}


def _fwd_and_grads(fn, cotangent):
    """``args -> {"out": fn(*args), "grads": vjp(cotangent)}``, jitted;
    ``cotangent`` an array or a tuple of them, as ``fn`` returns."""
    import jax

    def run(*args):
        out, vjp = jax.vjp(fn, *args)
        return {"out": out, "grads": vjp(jax.tree.map(
            lambda c, o: c.astype(o.dtype), cotangent, out))}

    return jax.jit(run)


def check_flash(shape) -> dict:
    import jax
    import jax.numpy as jnp

    from distkeras_tpu.ops.pallas import flash_attention

    B, L, H, D = shape
    ks = jax.random.split(jax.random.key(0), 4)
    q, k, v, w = (jax.random.normal(kk, shape, jnp.bfloat16) for kk in ks)
    q = q * (D ** -0.5)

    def dense_one(q, k, v):  # one batch row, float32, exact matmuls
        q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
        s = jnp.einsum("qhd,khd->hqk", q, k, precision="highest")
        s = jnp.where(jnp.tril(jnp.ones((L, L), bool))[None], s, -1e30)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                          precision="highest")

    def reference(q, k, v, w):  # row by row: the O(L^2) scores stay small
        def one(args):
            qi, ki, vi, wi = args
            out, vjp = jax.vjp(dense_one, qi, ki, vi)
            return {"out": out, "grads": vjp(wi.astype(jnp.float32))}

        return jax.lax.map(one, (q, k, v, w))

    got = _fwd_and_grads(
        lambda q, k, v: flash_attention(q, k, v, block_size=min(128, L)),
        w)(q, k, v)
    return _compare("flash_attention", got, jax.jit(reference)(q, k, v, w),
                    tol=3e-2)


def check_lstm(shape) -> dict:
    import jax
    import jax.numpy as jnp
    from jax import lax

    from distkeras_tpu.ops.pallas.lstm import lstm_seq

    B, T, E, H = shape
    ks = jax.random.split(jax.random.key(1), 5)
    bf = jnp.bfloat16
    wx = (jax.random.normal(ks[0], (E, 4 * H)) * E ** -0.5).astype(bf)
    wh = (jax.random.normal(ks[1], (H, 4 * H)) * H ** -0.5).astype(bf)
    b = (jax.random.normal(ks[2], (4 * H,)) * 0.1).astype(bf)
    x = jax.random.normal(ks[3], (B, T, E), bf)
    w = jax.random.normal(ks[4], (B, T, H), bf)

    def reference(wx, wh, b, x):  # lax.scan, float32, exact matmuls
        wx, wh, b, x = (a.astype(jnp.float32) for a in (wx, wh, b, x))

        def step(carry, x_t):
            h, c = carry
            pre = (jnp.dot(x_t, wx, precision="highest")
                   + jnp.dot(h, wh, precision="highest") + b)
            i, f, g, o = jnp.split(pre, 4, axis=1)
            c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
            h = jax.nn.sigmoid(o) * jnp.tanh(c)
            return (h, c), h

        zero = jnp.zeros((B, H), jnp.float32)
        _, hs = lax.scan(step, (zero, zero), jnp.swapaxes(x, 0, 1))
        return jnp.swapaxes(hs, 0, 1)

    got = _fwd_and_grads(lstm_seq, w)(wx, wh, b, x)
    ref = _fwd_and_grads(reference, w)(wx, wh, b, x)
    return _compare("lstm_seq", got, ref, tol=3e-2)


def check_groupnorm(shape) -> dict:
    import jax
    import jax.numpy as jnp

    from distkeras_tpu.ops.pallas.groupnorm import _xla_group_norm, group_norm

    B, Hh, Ww, C, G = shape
    ks = jax.random.split(jax.random.key(2), 4)
    x = jax.random.normal(ks[0], (B, Hh, Ww, C), jnp.bfloat16)
    gamma = 1.0 + 0.1 * jax.random.normal(ks[1], (C,))
    beta = 0.1 * jax.random.normal(ks[2], (C,))
    w = jax.random.normal(ks[3], x.shape, jnp.bfloat16)

    def reference(x, gamma, beta):
        return _xla_group_norm(x.reshape(B, -1, C), gamma, beta, G,
                               relu=True).reshape(x.shape)

    got = _fwd_and_grads(
        lambda x, g, b: group_norm(x, g, b, groups=G, relu=True),
        w)(x, gamma, beta)
    ref = _fwd_and_grads(reference, w)(x, gamma, beta)
    return _compare("group_norm", got, ref, tol=3e-2)


def check_fold(shape) -> dict:
    from distkeras_tpu.netps import wire
    from distkeras_tpu.netps.fold import fold_compressed_numpy
    from distkeras_tpu.ops.pallas.fold import fold_compressed

    rng = np.random.default_rng(3)
    center = rng.normal(size=shape).astype(np.float32)
    delta = (rng.normal(size=shape) * 0.01).astype(np.float32)
    out = {}
    for codec in ("int8", "bf16"):
        enc, spec = wire.codec_encode(delta, codec)
        ref = center.copy()
        fold_compressed_numpy(ref, enc, spec, 0.5)
        got = fold_compressed(center, enc, spec, 0.5)
        out[codec] = _compare(f"fold[{codec}]", got, ref, tol=1e-6)
    return out


def check_rows(shape) -> dict:
    """The expert layer's row kernels (``ops/pallas/rows.py``) against XLA's
    gather and segment sum, on the live rows of a seeded routing."""
    import jax
    import jax.numpy as jnp

    from distkeras_tpu.ops.pallas import rows

    tokens, k, width, held = shape
    rng = np.random.default_rng(4)
    experts = np.argsort(rng.random((tokens, 64)), axis=1)[:, :k].reshape(-1)
    order = np.argsort(np.where(experts < held, experts, held), kind="stable")
    slot = np.empty(tokens * k, np.int32)
    slot[order] = np.arange(tokens * k, dtype=np.int32)
    live = int(np.sum(experts < held))
    token = jnp.asarray(order // k, jnp.int32)
    slot = jnp.asarray(slot.reshape(tokens, k))
    x = jnp.asarray(rng.normal(size=(tokens, width)), jnp.bfloat16)
    buffer = jnp.asarray(rng.normal(size=(tokens * k, width)), jnp.bfloat16)
    weights = jnp.asarray(rng.random((tokens, k)), jnp.float32)
    by_row = weights.reshape(-1)[order]
    got = jax.jit(lambda: {
        "gather": rows.gather(x, token, live, by_row)[:live],
        "combine": rows.combine(buffer, slot, live, weights)})()
    ref = jax.jit(lambda: {
        "gather": (x[token[:live]].astype(jnp.float32)
                   * by_row[:live, None]).astype(jnp.bfloat16),
        "combine": jax.ops.segment_sum(
            buffer[:live].astype(jnp.float32)
            * by_row[:live, None].astype(jnp.bfloat16).astype(jnp.float32),
            token[:live], tokens)})()
    return dict(_compare("rows", got, ref, tol=1e-6), live=live)


def check_kda_scan(shape) -> dict:
    """The delta rule's scan over chunks (``ops/pallas/delta_rule.py``: the
    kernels ``dk_kda_scan_fwd`` and ``dk_kda_scan_bwd``) against the same
    three lines as a ``lax.scan`` in ``jax.numpy`` with JAX's derivative,
    ``O`` and all six cotangents, on seeded chunks whose state stays bounded
    (a chunk's decay in (0.1, 0.5), ``|Kd^T W|`` about 0.3)."""
    import jax
    import jax.numpy as jnp

    from distkeras_tpu.ops.delta_rule import chunk_for
    from distkeras_tpu.ops.pallas.delta_rule import scan_chunks

    B, L, H, D = shape
    C = chunk_for(L)
    lead, dt, f32 = (B, H, L // C), jnp.bfloat16, jnp.float32
    ks = jax.random.split(jax.random.key(0), 7)
    small = 0.04 * (128 / D) ** 0.5 * (64 / C) ** 0.5
    U = jax.random.normal(ks[0], lead + (C, D), f32)
    W, Kd = (small * jax.random.normal(k, lead + (C, D), f32).astype(dt)
             for k in ks[1:3])
    Qg = jax.random.normal(ks[3], lead + (C, D), dt) * D ** -0.5
    Bq = jnp.tril(jax.random.normal(ks[4], lead + (C, C), dt)) * C ** -0.5
    shrink = jax.random.uniform(ks[5], lead + (D,), f32, 0.1, 0.5)
    cotangent = jax.random.normal(ks[6], lead + (C, D), dt)

    def dot(spec, a, b):
        return jnp.einsum(spec, a.astype(dt), b.astype(dt),
                          preferred_element_type=f32)

    def reference(*chunks):
        def step(S, xs):
            U, W, Qg, Bq, Kd, shrink = xs
            pseudo = U - dot("bhrk,bhkv->bhrv", W, S)
            out = dot("bhrk,bhkv->bhrv", Qg, S) \
                + dot("bhrc,bhcv->bhrv", Bq, pseudo)
            S = shrink[..., None] * S + dot("bhck,bhcv->bhkv", Kd, pseudo)
            return S, out.astype(dt)

        _, out = jax.lax.scan(step, jnp.zeros((B, H, D, D), f32), tuple(
            jnp.moveaxis(x, 2, 0) for x in chunks))
        return jnp.moveaxis(out, 0, 2)

    args = (U, W, Qg, Bq, Kd, shrink)
    return _compare("kda_scan", _fwd_and_grads(scan_chunks, cotangent)(*args),
                    _fwd_and_grads(reference, cotangent)(*args), tol=2e-2)


def check_kda_chunk(shape) -> dict:
    """The delta rule's in-chunk half (``ops/pallas/delta_rule.py``: the
    kernels ``dk_kda_chunk_fwd`` and ``dk_kda_chunk_bwd``) against the kept
    ``jax.numpy`` form (``ops/delta_rule.py::in_chunk_by_jax_numpy``) with
    JAX's derivative, the six outputs and all five cotangents, on unit keys,
    scaled unit queries, a decay from ``e^-6`` to ``e^0.5`` a step and
    channel, ``beta`` in (0, 1)."""
    import jax
    import jax.numpy as jnp

    from distkeras_tpu.ops.delta_rule import (SUB, chunk_for,
                                              in_chunk_by_jax_numpy)
    from distkeras_tpu.ops.pallas.delta_rule import chunk_products

    B, L, H, D = shape
    C, dt = chunk_for(L), jnp.bfloat16
    ks = jax.random.split(jax.random.key(0), 11)

    def unit(key):
        x = jax.random.normal(key, shape)
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    args = ((unit(ks[0]) * D ** -0.5).astype(dt), unit(ks[1]).astype(dt),
            jax.random.normal(ks[2], shape, dt),
            -jnp.exp(jax.random.uniform(ks[3], shape, minval=-6, maxval=0.5)),
            jax.nn.sigmoid(jax.random.normal(ks[4], shape[:3])))

    def form(*a):
        return in_chunk_by_jax_numpy(*a, C)[0]

    cotangents = tuple(jax.random.normal(k, o.shape) for k, o in zip(
        ks[5:], jax.eval_shape(form, *args)))
    return _compare(
        "kda_chunk",
        _fwd_and_grads(lambda *a: chunk_products(*a, C, min(SUB, C))[:6],
                       cotangents)(*args),
        _fwd_and_grads(form, cotangents)(*args), tol=2e-2)


def phase_kernels(sz: Sizes) -> dict:
    out = {}
    flash = [(f"flash_attention_L{shape[1]}", check_flash, shape)
             for shape in sz.flash]
    for name, check, shape in (*flash,
                               ("lstm_seq", check_lstm, sz.lstm),
                               ("group_norm", check_groupnorm, sz.groupnorm),
                               ("fold", check_fold, sz.fold),
                               ("rows", check_rows, sz.rows),
                               ("kda_scan", check_kda_scan, sz.kda),
                               ("kda_chunk", check_kda_chunk, sz.kda)):
        t0 = time.perf_counter()
        out[name] = dict(check(shape), shape=list(shape),
                         seconds=round(time.perf_counter() - t0, 2))
    return out


# ---------------------------------------------------------------------------
# remote
# ---------------------------------------------------------------------------

def _opened_tpu_client(pid: int) -> list:
    """Evidence that process ``pid`` opened a TPU client: libtpu mapped into
    it, or an accelerator device node among its open files."""
    with open(f"/proc/{pid}/maps", encoding="utf-8", errors="replace") as f:
        found = sorted({ln.split()[-1] for ln in f if "libtpu" in ln})
    fd_dir = f"/proc/{pid}/fd"
    for fd in os.listdir(fd_dir):
        try:
            target = os.readlink(os.path.join(fd_dir, fd))
        except OSError:  # the fd closed while we were looking
            continue
        if target.startswith(("/dev/accel", "/dev/vfio")):
            found.append(target)
    return found


def phase_remote(sz: Sizes) -> dict:
    import jax

    import distkeras_tpu as dk
    from distkeras_tpu.datasets import mnist
    from distkeras_tpu.models.mlp import mnist_mlp
    from distkeras_tpu.netps.shards.client import make_ps_client
    from distkeras_tpu.runtime import config

    child = subprocess.Popen(
        [sys.executable, "-m", "distkeras_tpu.netps", "--host", "127.0.0.1",
         "--port", "0", "--discipline", "adag"],
        stdout=subprocess.PIPE, text=True,
        cwd=os.path.dirname(os.path.abspath(__file__)))
    try:
        ready = child.stdout.readline().split()
        _require(len(ready) == 2 and ready[0] == "NETPS_READY",
                 f"parameter server did not come up: {ready}")
        endpoint = ready[1]
        # The codec'd commit is what used to make the server resolve a jax
        # backend inside its own process.
        config.env_set("DKTPU_NET_COMPRESS", "int8")
        t0 = time.perf_counter()
        trainer = dk.ADAG(
            mnist_mlp(seed=0), loss="sparse_categorical_crossentropy",
            num_workers=2, batch_size=64, communication_window=4,
            learning_rate=0.1, remote=endpoint)
        trained = trainer.train(mnist(n=sz.mlp_rows, flat=True, seed=0))
        losses = [float(x) for x in trainer.get_history()]
        _check_losses(losses, "ADAG remote")
        _require(all(np.all(np.isfinite(np.asarray(a))) for a in
                     jax.tree.leaves(trained.params)),
                 "remote-trained parameters are not finite")
        with make_ps_client(endpoint, worker_id=99) as probe:
            stats = probe.stats(ring=0)
        tpu_evidence = _opened_tpu_client(child.pid)
        _require(stats["commits_total"] > 0, "the server folded no commit")
        _require(stats["fold_backend"] == "numpy",
                 f"server folds through {stats['fold_backend']!r}")
        _require(not tpu_evidence,
                 f"the parameter-server child opened a TPU client: "
                 f"{tpu_evidence}")
        return {"seconds": round(time.perf_counter() - t0, 2),
                "commits": stats["commits_total"],
                "fold_backend": stats["fold_backend"],
                "loss_first_last": [round(losses[0], 4),
                                    round(losses[-1], 4)],
                "child_tpu_client": False}
    finally:
        child.terminate()
        try:
            child.wait(timeout=30)
        except subprocess.TimeoutExpired:
            child.kill()
            child.wait(timeout=30)
        child.stdout.close()


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def result_line(ok: bool, device: dict) -> str:
    """The last line of a chip run: these two keys and nothing else, which is
    what the driver that checks the chip reads. Everything measured is on the
    ``[summary]`` line before it."""
    return json.dumps({"ok": bool(ok),
                       "device": {"platform": str(device["platform"]),
                                  "kind": str(device["kind"]),
                                  "count": int(device["count"])}})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="walk every phase at toy size on whatever backend "
                         "JAX has (kernels interpret); proves control flow, "
                         "not the chip")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    summary = device_facts(args.rehearse_cpu)
    on_tpu = summary["device"]["platform"] == "tpu"
    sz = TOY if args.rehearse_cpu else FULL

    from distkeras_tpu import telemetry
    from distkeras_tpu.data import native_loader
    from distkeras_tpu.runtime.compile_cache import ensure_compile_cache

    summary["compile_cache"] = ensure_compile_cache()
    path, why = native_loader.served_by()
    summary["native_loader"] = path
    print(f"[device] compile cache {summary['compile_cache']}; native loader "
          f"served by {path}" + (f" ({why})" if why else ""), flush=True)

    phases = [("train-1", lambda: train_lm(
                  sz, discipline="AEASGD", layers=sz.layers, num_workers=1,
                  seed=0, on_tpu=on_tpu)),
              ("kernels", lambda: phase_kernels(sz))]
    if summary["device"]["count"] >= 4:
        phases.append(("four-chip", lambda: phase_four_chip(sz, on_tpu)))
    phases.append(("remote", lambda: phase_remote(sz)))

    failed = []
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            summary[name] = run()
        except Exception:  # noqa: BLE001 - phase boundary: report, go on, fail
            traceback.print_exc()
            failed.append(name)
            summary[name] = {"failed": True}
        summary[name]["phase_s"] = round(time.perf_counter() - t0, 2)
        print(f"[{name}] {'FAILED' if name in failed else 'ok'} "
              f"{json.dumps(summary[name])}", flush=True)

    summary["peak_bytes_in_use"] = peak_bytes()
    summary["interpreted_pallas_calls"] = int(
        telemetry.counter("pallas.interpreted_calls").value)
    if on_tpu and summary["interpreted_pallas_calls"]:
        print(f"[kernels] FAILED: {summary['interpreted_pallas_calls']} "
              "Pallas kernel calls ran under the interpreter on a TPU",
              file=sys.stderr, flush=True)
        failed.append("interpreted-kernels")
    summary["total_s"] = round(time.perf_counter() - t_start, 2)
    summary["claim"] = None
    sys.stderr.flush()
    print(f"[summary] {json.dumps(summary)}", flush=True)
    if failed:
        print(f"chip_smoke FAILED: {failed}", flush=True)
    if args.rehearse_cpu:
        # Control flow only; the seconds above are a CPU's and go no further.
        print(json.dumps({"rehearsal": True, "device": summary["device"],
                          "phases_walked": [name for name, _ in phases],
                          "failed": failed}), flush=True)
        return 1 if failed else 0
    print(result_line(not failed, summary["device"]), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
