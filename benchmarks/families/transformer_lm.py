"""Decoder-only language models through ``models/transformer.py::TransformerLM``."""

from __future__ import annotations

import numpy as np

from benchmarks.families import compare_with_reference
from benchmarks.references import transformer_lm as reference

#: What ``--rehearse`` swaps in for the configuration's sizes: control flow on
#: a CPU in seconds (the flash kernel interprets there). Never measured.
TINY = {"module": {"vocab_size": 256, "num_layers": 2, "d_model": 64,
                   "num_heads": 2, "d_ff": 128, "max_seq_len": 128},
        "seq_len": 128}


def build_model(config: dict, seed: int):
    import jax.numpy as jnp

    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.transformer import TransformerLM

    return Model.build(TransformerLM(**config["module"]),
                       jnp.zeros((1, config["seq_len"]), jnp.int32), seed=seed)


def learnable_tokens(n: int, seq: int, vocab: int, seed: int):
    """A seeded token stream a language model can learn (a copy of
    ``chip_smoke.learnable_tokens``): Zipf unigrams, and three quarters of the
    transitions follow one fixed successor map. Uniform noise would pin the
    loss at ln V, and a falling loss would prove nothing."""
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, vocab + 1)
    draws = rng.choice(vocab, size=(n, seq + 1), p=p / p.sum())
    follow = rng.random((n, seq + 1)) < 0.75
    x = draws.copy()
    for t in range(1, seq + 1):
        x[:, t] = np.where(follow[:, t], (x[:, t - 1] * 31 + 7) % vocab,
                           draws[:, t])
    return x[:, :-1].astype(np.int32), x[:, 1:].astype(np.int32)


def make_dataframe(config: dict, rows: int, seed: int):
    import distkeras_tpu as dk

    x, y = learnable_tokens(rows, config["seq_len"],
                            config["module"]["vocab_size"], seed)
    return dk.DataFrame({"features": x, "label": y})


def sample_shapes(config: dict):
    """``(x shape, x dtype, y shape, y dtype)`` of one sample."""
    L = config["seq_len"]
    return (L,), np.int32, (L,), np.int32


def units_per_sample(config: dict) -> int:
    return config["seq_len"]  # tokens


def matmul_params(module: dict) -> int:
    """Parameters that multiply activations: the blocks' four attention
    projections and two MLP matrices, and the head. Embeddings are looked up,
    LayerNorms and biases are elementwise."""
    d, f = module["d_model"], module["d_ff"]
    return (module["num_layers"] * (4 * d * d + 2 * d * f)
            + d * module["vocab_size"])


def train_flops_per_unit(config: dict) -> float:
    """Forward and backward operations per token: 6 per matmul parameter (2
    forward, 4 backward), and causal attention's two products, 2*L*d each in
    full and half of that under the mask, so 2*L*d forward and 3 times that
    with the backward pass. The recomputed forward of ``remat`` is not
    counted."""
    m = config["module"]
    attention = 6 * config["seq_len"] * m["d_model"] * m["num_layers"]
    return 6.0 * matmul_params(m) + attention


def expects_mosaic(config: dict) -> bool:
    return config["module"].get("attn_impl") == "flash"


def reference_check(model, config: dict, seed: int, compute_dtype) -> dict:
    x, _ = learnable_tokens(1, config["seq_len"],
                            config["module"]["vocab_size"], seed + 1)
    tol = reference.TOLERANCE if compute_dtype else reference.TOLERANCE_FLOAT32
    return compare_with_reference(model, reference.forward, x, x,
                                  config["module"], compute_dtype, tol)
