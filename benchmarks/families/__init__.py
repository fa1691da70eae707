"""One module per model family. Each builds the model through
``distkeras_tpu.models`` as a user would, makes the seeded DataFrame, counts
the operations from the configuration's shapes, checks the built model against
``references/<family>.py``, and holds the tiny preset a rehearsal swaps in.

A family module offers: ``TINY``, ``build_model``, ``make_dataframe``,
``sample_shapes``, ``units_per_sample``, ``train_flops_per_unit``,
``expects_mosaic`` and ``reference_check``."""

from __future__ import annotations

import numpy as np


def rel_l2(got, ref) -> float:
    got = np.asarray(got, np.float32).ravel()
    ref = np.asarray(ref, np.float32).ravel()
    return float(np.linalg.norm(got - ref) / (np.linalg.norm(ref) + 1e-30))


def compare_with_reference(model, forward, x, x_model, module_kwargs,
                           compute_dtype, tolerance) -> dict:
    """The built model's own ``apply`` (parameters and inputs cast to the
    trainer's compute dtype, as ``workers.make_local_loop`` casts them) against
    the plain ``forward`` on the same parameters: relative L2 on the logits."""
    import jax
    import jax.numpy as jnp

    def cast(a):
        if compute_dtype and jnp.issubdtype(a.dtype, jnp.floating):
            return a.astype(compute_dtype)
        return a

    got = jax.jit(lambda p, x: model.apply(jax.tree.map(cast, p), x)
                  .astype(jnp.float32))(model.params, x_model)
    ref = jax.jit(lambda p, x: forward(p, x, **module_kwargs))(model.params, x)
    err = rel_l2(got, ref)
    finite = bool(np.all(np.isfinite(np.asarray(got, np.float32))))
    return {"rel_l2": err, "tolerance": tolerance,
            "ok": finite and err <= tolerance}
