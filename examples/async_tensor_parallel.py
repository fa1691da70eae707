"""Async disciplines x tensor parallelism: every worker is a tp submesh.

The reference's async workers were single-GPU processes; here an AEASGD
"worker" can itself be a tensor-parallel transformer replica. This example
trains a small TransformerLM with elastic averaging over W workers, each
tp-sharded over 2 chips of a (data, model) mesh — the same
``trainer.train(dataframe)`` call as every other trainer.

    # CPU virtual mesh (4 workers x tp=2 on 8 virtual devices):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/async_tensor_parallel.py
"""

import numpy as np


def main():
    import jax
    import jax.numpy as jnp

    import distkeras_tpu as dk
    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.transformer import TransformerLM

    tp = 2
    workers = max(1, jax.device_count() // tp)
    L, V = 32, 256
    rng = np.random.default_rng(0)
    toks = rng.integers(0, V, size=(workers * 512, L))
    df = dk.DataFrame({"features": toks.astype(np.int32),
                       "label": np.roll(toks, -1, 1).astype(np.int32)})

    model = Model.build(
        TransformerLM(vocab_size=V, num_layers=2, d_model=64, num_heads=4,
                      d_ff=128, max_seq_len=L),
        jnp.zeros((1, L), jnp.int32))

    trainer = dk.AEASGD(
        model, num_workers=workers, parallel={"model": tp},
        worker_optimizer="adam", loss="sparse_categorical_crossentropy",
        batch_size=8, communication_window=4, num_epoch=2,
        learning_rate=1e-3, rho=5.0)
    print(f"AEASGD over {workers} workers, each a tp={tp} replica "
          f"({jax.device_count()} devices total) ...")
    trainer.train(df, shuffle=True)
    h = trainer.get_history()
    print(f"done: {len(h)} fold rounds, loss {h[0]:.4f} -> {h[-1]:.4f}")
    assert h[-1] < h[0]


if __name__ == "__main__":
    main()
