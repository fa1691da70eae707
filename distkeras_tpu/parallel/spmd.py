"""Multi-axis SPMD training: data + sequence + tensor parallelism in one step.

Beyond-reference surface (the reference is data-parallel only; SURVEY.md §2): this is
the engine for models too large or too long for pure DP. Axis split of labor:

* ``data``  — manual (shard_map): batch sharded, gradient ``pmean``.
* ``seq``   — manual (shard_map): activations sequence-sharded; ring attention
  ``ppermute``s K/V blocks around the ICI ring (``ops/ring_attention.py``).
* ``model`` — **auto** (GSPMD): params/optimizer state sharded by the PartitionSpec
  rules in ``parallel/sharding.py``; XLA inserts the tensor-parallel collectives.

shard_map's ``axis_names`` lets the two manual axes coexist with GSPMD on ``model`` —
one jitted program, no hand-written all-reduces for TP.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distkeras_tpu.ops.losses import get_loss
from distkeras_tpu.ops.precision import cast_floats
from distkeras_tpu.ops.optimizers import get_optimizer
from distkeras_tpu.parallel.sharding import param_shardings
from distkeras_tpu.runtime.mesh import DATA_AXIS, MODEL_AXIS, SEQ_AXIS, put_global


class SPMDState(NamedTuple):
    params: Any
    opt_state: Any
    rng: jax.Array


def spmd_mesh_for(n_devices: int, devices: Optional[Sequence] = None) -> Mesh:
    """Factor ``n_devices`` into a (data, seq, model) mesh.

    Greedy powers-of-two split, favoring data first (throughput), then model and
    seq. Axis order puts ``model`` innermost so TP collectives ride the
    fastest/adjacent ICI links.
    """
    devs = list(devices) if devices is not None else jax.devices()[:n_devices]
    n = len(devs)
    sizes = {"data": 1, "seq": 1, "model": 1}
    order = ["data", "model", "seq"]
    i = 0
    while n % 2 == 0 and n > 1:
        sizes[order[i % len(order)]] *= 2
        n //= 2
        i += 1
    sizes["data"] *= n  # odd remainder goes to data
    grid = np.asarray(devs).reshape(sizes["data"], sizes["seq"], sizes["model"])
    return Mesh(grid, (DATA_AXIS, SEQ_AXIS, MODEL_AXIS))


class SPMDEngine:
    """jit-compiled dp x sp x tp training step for sequence models.

    ``module`` must accept ``[B_local, L_local]`` token blocks and, when the mesh has
    a ``seq`` axis, be constructed with ``seq_axis='seq'`` (the transformer zoo model
    handles global positions/causality itself).
    """

    def __init__(
        self,
        model,
        optimizer,
        loss,
        mesh: Mesh,
        tp_rules,
        learning_rate: float = 0.01,
        seed: int = 0,
        aux_loss_weight: float = 0.0,
        compute_dtype=None,
    ):
        self.model = model
        self.mesh = mesh
        self.tx = get_optimizer(optimizer, learning_rate)
        self.loss_fn = get_loss(loss)
        self.tp_rules = tp_rules
        self.seed = seed
        self.aux_loss_weight = float(aux_loss_weight)
        self.compute_dtype = compute_dtype
        self.manual_axes = frozenset(
            a for a in (DATA_AXIS, SEQ_AXIS) if mesh.shape.get(a, 1) >= 1
        )
        self._step = self._build_step()

    def _build_step(self):
        module = self.model.module
        loss_fn = self.loss_fn
        tx = self.tx
        manual = self.manual_axes
        aux_w = self.aux_loss_weight
        dtype = self.compute_dtype

        def body(params, opt_state, rng, tokens, targets):
            step_rng = jax.random.fold_in(
                jax.random.fold_in(rng, lax.axis_index(DATA_AXIS)),
                lax.axis_index(SEQ_AXIS),
            )

            def loss_of(p):
                p = cast_floats(p, dtype)
                if aux_w:
                    logits, mut = module.apply(
                        {"params": p}, tokens, train=True,
                        rngs={"dropout": step_rng}, mutable=["intermediates"],
                    )
                    from distkeras_tpu.ops.losses import collect_aux_loss

                    return (loss_fn(logits.astype(jnp.float32), targets)
                            + aux_w * collect_aux_loss(mut))
                logits = module.apply(
                    {"params": p}, tokens, train=True, rngs={"dropout": step_rng}
                )
                return loss_fn(logits.astype(jnp.float32), targets)

            loss, grads = jax.value_and_grad(loss_of)(params)
            # Full gradient = mean over both manual shard axes (model-axis
            # collectives are GSPMD's job).
            grads = lax.pmean(lax.pmean(grads, DATA_AXIS), SEQ_AXIS)
            loss = lax.pmean(lax.pmean(loss, DATA_AXIS), SEQ_AXIS)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = jax.tree.map(jnp.add, params, updates)
            next_rng = jax.random.split(rng, 1)[0]
            return params, opt_state, next_rng, loss

        mapped = shard_map(
            body,
            mesh=self.mesh,
            in_specs=(P(), P(), P(), P(DATA_AXIS, SEQ_AXIS), P(DATA_AXIS, SEQ_AXIS)),
            out_specs=(P(), P(), P(), P()),
            axis_names=manual,
            check_vma=False,
        )

        def step(state: SPMDState, tokens, targets):
            params, opt_state, rng, loss = mapped(
                state.params, state.opt_state, state.rng, tokens, targets
            )
            return SPMDState(params, opt_state, rng), loss

        self._step_core = step  # unjitted: scannable by WindowedStepEngine
        return jax.jit(step, donate_argnums=(0,))

    def init_state(self) -> SPMDState:
        from distkeras_tpu.parallel.sharding import mirror_tree_specs

        params = jax.tree.map(lambda a: np.array(a), self.model.params)
        shardings = param_shardings(params, self.mesh, self.tp_rules)
        params = put_global(params, shardings)
        # Moments inherit param shardings, scalars replicate (see
        # GSPMDEngine.init_state for why this must be explicit).
        opt_sh = mirror_tree_specs(
            jax.eval_shape(self.tx.init, params), params, shardings,
            NamedSharding(self.mesh, P()))
        opt_state = jax.jit(self.tx.init, out_shardings=opt_sh)(params)
        rng = put_global(
            jax.random.key(self.seed), NamedSharding(self.mesh, P())
        )
        return SPMDState(params=params, opt_state=opt_state, rng=rng)

    def batch_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(DATA_AXIS, SEQ_AXIS))

    def step(self, state: SPMDState, tokens, targets):
        return self._step(state, tokens, targets)
