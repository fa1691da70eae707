"""Worker local-step loops.

Parity with ``distkeras/workers.py``: the reference ships a ``Worker.train`` closure
to each Spark executor, which deserializes the model, compiles it with the worker
optimizer, and calls ``model.train_on_batch`` per minibatch (SURVEY.md §3.1 hot loop).

Here the "worker" is a pure jitted function: ``communication_window`` minibatch steps
expressed as one ``lax.scan`` so the whole window is a single XLA program — no Python
between steps, params stay in HBM/vregs, and XLA can pipeline weight updates against
the next batch's gradients. Replica divergence (each worker trains on its own slice)
comes from running this under ``shard_map``, not from separate processes.

The same loop serves both engines: the async engine uses it as-is (grads stay local);
the sync engine injects a per-step gradient ``pmean`` via ``grad_transform``.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import optax
from jax import lax

from distkeras_tpu.models.base import ROUND_COUNTERS, _warn_uint8_rescale
from distkeras_tpu.scopes import owner


def make_local_loop(
    module,
    loss_fn: Callable,
    tx: optax.GradientTransformation,
    compute_dtype=None,
    grad_transform: Optional[Callable] = None,
    state_collections: Sequence[str] = (),
    grad_accum: int = 1,
    input_transform: Optional[Callable] = None,
    normalize_uint8: bool = True,
):
    """Build ``local_steps(params, opt_state, xs, ys, rng, state) ->
    (params, opt_state, state, losses)``.

    ``xs``/``ys`` are ``[window, batch, ...]``; the scan carries (params, opt_state,
    state) across the window — the executor minibatch loop with zero host
    round-trips. With a ``compute_dtype``, both inputs *and* params are cast to it
    inside the loss (canonical mixed precision: fwd/bwd run entirely at the MXU's
    bf16 rate, while the carried master params, gradients, and optimizer state stay
    float32 — the cast's cotangent upcasts the grads). Casting inputs alone promotes
    every matmul/conv back to float32 and halves MXU throughput (measured: CIFAR-10
    CNN 30 -> 46 TFLOPS/chip on v5e from casting params too). ``grad_transform(grads,
    loss) -> (grads, loss)`` runs after each backward pass — the sync engine's
    gradient all-reduce hook.

    ``state_collections`` names the model's mutable variable collections
    (BatchNorm running stats: flax ``batch_stats`` / the Keras adapter's
    ``keras_state``); ``state`` is the matching ``{collection: tree}`` dict (or
    None for stateless models). The forward runs with those collections mutable
    and the updated state is carried across the window — the engines
    cross-replica-mean it at each fold (see AsyncEngine/SyncEngine). State is
    deliberately NOT cast to ``compute_dtype`` — running statistics stay in
    their stored precision. The collection ``ROUND_COUNTERS``
    (``models/base.py``) is zeroed here as the window begins.

    ``grad_accum=A`` splits every step's batch into A sequential micro-batches
    and applies ONE optimizer update on their mean gradient at 1/A the
    activation memory — the standard trick for batches that don't fit HBM.
    For stateless, dropout-free models this is numerically the identical step
    (the same mean gradient reaches ``tx.update``; equivalence-tested).
    Caveats: BatchNorm statistics are computed per micro-batch (B/A samples,
    momentum applied A times per step) and dropout masks take a per-micro rng
    path — both standard accumulation semantics, but not bitwise equal to the
    unaccumulated step. Mutable state threads through the micro-batches in
    order.

    ``input_transform(rng, x, y) -> (x, y)`` runs ON DEVICE on each step's
    minibatch before the forward (``ops/augment.py``: jitted crop/flip —
    augmentation at VPU cost instead of host-numpy cost). It draws a
    dedicated per-step key from the carried chain (a 3-way split instead of
    2-way, so a transform-free run's rng stream is untouched when the hook
    is None; enabling it yields a different — equally deterministic —
    stream).

    The rng handed in must be identical across replicas if determinism across
    restarts matters; per-step dropout keys are derived inside the scan.
    """
    if grad_accum < 1:
        raise ValueError(f"grad_accum must be >= 1, got {grad_accum}")
    cols = tuple(state_collections or ())

    def cast(x):
        if compute_dtype is not None and jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(compute_dtype)
        return x

    def cast_input(x):
        if x.dtype == jnp.uint8 and normalize_uint8:
            # Raw image bytes: normalize to the compute dtype ON DEVICE.
            # Shipping uint8 and dividing in-graph is 4x less host->device
            # traffic than staging float32 — the difference between a feed-
            # bound and a compute-bound out-of-core run (docs/PERFORMANCE.md
            # "Feed overlap"). The common case is image bytes (integer
            # token/label inputs are int32/int64, never uint8), but the rule
            # is opt-out-able for byte-valued non-image features:
            # ``normalize_uint8=False`` (threaded from Model/Trainer).
            _warn_uint8_rescale()
            return x.astype(compute_dtype or jnp.float32) / 255.0
        return cast(x)

    def loss_on_batch(params, state, x, y, rng):
        with owner("cast"):
            if compute_dtype is not None:
                params = jax.tree.map(cast, params)
            x = cast_input(x)
        # Always provide a dropout rng: harmless for dropout-free modules, required
        # for any module that samples (flax raises at trace time otherwise).
        if cols:
            out, mut = module.apply(
                {"params": params, **state}, x, train=True,
                rngs={"dropout": rng}, mutable=list(cols),
            )
            state = {k: mut[k] for k in cols}
        else:
            out = module.apply({"params": params}, x, train=True,
                               rngs={"dropout": rng})
        with owner("loss"):
            return loss_fn(out.astype(jnp.float32), y), state

    def local_steps(params, opt_state, xs, ys, rng: Optional[jax.Array] = None,
                    state=None):
        if rng is None:
            rng = jax.random.key(0)
        if ROUND_COUNTERS in cols:
            # What leaves the round is the round's own sums.
            state = {**state, ROUND_COUNTERS: jax.tree.map(
                jnp.zeros_like, state[ROUND_COUNTERS])}

        def grad_of_step(p, st, x, y, sub):
            if grad_accum == 1:
                (loss, st), grads = jax.value_and_grad(loss_on_batch, has_aux=True)(
                    p, st, x, y, sub)
                return loss, st, grads
            B = x.shape[0]
            if B % grad_accum:
                raise ValueError(
                    f"batch size {B} not divisible by grad_accum={grad_accum}")
            xm = x.reshape((grad_accum, B // grad_accum) + x.shape[1:])
            ym = y.reshape((grad_accum, B // grad_accum) + y.shape[1:])

            def micro(carry, i):
                st_c, g_sum, l_sum = carry
                (l, st_c), g = jax.value_and_grad(loss_on_batch, has_aux=True)(
                    p, st_c, xm[i], ym[i], jax.random.fold_in(sub, i))
                g_sum = jax.tree.map(jnp.add, g_sum, g)
                return (st_c, g_sum, l_sum + l), None

            g0 = jax.tree.map(jnp.zeros_like, p)
            (st, g_sum, l_sum), _ = lax.scan(
                micro, (st, g0, jnp.float32(0)), jnp.arange(grad_accum))
            inv = 1.0 / grad_accum
            return l_sum * inv, st, jax.tree.map(lambda g: g * inv, g_sum)

        # The scopes name the step's phases in the compiled program's
        # ``op_name`` metadata (and so in a device trace); they add no op.
        def step(carry, batch):
            p, s, st, key = carry
            x, y = batch
            if input_transform is not None:
                key, sub, akey = jax.random.split(key, 3)
                with jax.named_scope("dk_input_transform"):
                    x, y = input_transform(akey, x, y)
            else:
                key, sub = jax.random.split(key)
            with jax.named_scope("dk_fwd_bwd"):
                loss, st, grads = grad_of_step(p, st, x, y, sub)
            if grad_transform is not None:
                with jax.named_scope("dk_grad_sync"):
                    grads, loss = grad_transform(grads, loss)
            with jax.named_scope("dk_optimizer"):
                updates, s = tx.update(grads, s, p)
                p = optax.apply_updates(p, updates)
            return (p, s, st, key), loss

        (params, opt_state, state, _), losses = lax.scan(
            step, (params, opt_state, state, rng), (xs, ys))
        return params, opt_state, state, losses

    return local_steps
