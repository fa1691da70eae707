"""Plain float32 ``jax.numpy`` forward passes, one per family: no kernels, no
flax, no batching tricks. The run compares the built model against them in
set-up, at the published widths on the chip; the tests do at a tiny size."""
