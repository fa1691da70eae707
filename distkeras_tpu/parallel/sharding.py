"""PartitionSpec rules: mapping parameter pytrees onto multi-axis meshes.

The reference has no model parallelism (SURVEY.md §2 parallelism inventory) — this is
new surface for the TPU rebuild. Rules are (regex over the param path, PartitionSpec)
pairs; first match wins, default replicated. The transformer rules implement standard
Megatron-style tensor parallelism: attention heads and MLP hidden dim sharded over
``model``, with XLA/GSPMD inserting the all-reduces at ``out``/``mlp_down``.
"""

from __future__ import annotations

import re
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distkeras_tpu.runtime.mesh import EXPERT_AXIS, MODEL_AXIS

# (path regex, spec). Paths are '/'-joined flax param paths, e.g.
# "block_0/attn/query/kernel".
TRANSFORMER_TP_RULES: list[tuple[str, P]] = [
    (r".*/attn/(query|key|value)/kernel$", P(None, MODEL_AXIS, None)),
    (r".*/attn/(query|key|value)/bias$", P(MODEL_AXIS, None)),
    (r".*/attn/out/kernel$", P(MODEL_AXIS, None, None)),
    (r".*/mlp_up/kernel$", P(None, MODEL_AXIS)),
    (r".*/mlp_up/bias$", P(MODEL_AXIS)),
    (r".*/mlp_down/kernel$", P(MODEL_AXIS, None)),
    (r"tok_embed/embedding$", P(None, MODEL_AXIS)),
    (r"pos_embed/embedding$", P(None, MODEL_AXIS)),
    (r"lm_head/kernel$", P(None, MODEL_AXIS)),
    (r"lm_head/bias$", P(MODEL_AXIS)),
]

# Mixture-of-Experts: the stacked expert bank's leading axis is the expert id —
# shard it over the ``expert`` mesh axis (GSPMD turns the dispatch/combine
# einsums into all-to-alls). Router stays replicated.
MOE_RULES: list[tuple[str, P]] = [
    # `gate` is the gated experts' third matrix (models/blocks.py).
    (r".*/moe/experts/(up|gate)/kernel$", P(EXPERT_AXIS, None, None)),
    (r".*/moe/experts/up/bias$", P(EXPERT_AXIS, None)),
    (r".*/moe/experts/down/kernel$", P(EXPERT_AXIS, None, None)),
    (r".*/moe/experts/down/bias$", P(EXPERT_AXIS, None)),
] + TRANSFORMER_TP_RULES


def param_path_specs(params, rules: Sequence[tuple[str, P]]):
    """Pytree of PartitionSpecs: first rule whose regex matches the param path."""
    compiled = [(re.compile(pat), spec) for pat, spec in rules]

    def spec_for(path, leaf):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        for pat, spec in compiled:
            if pat.search(name):
                if len(spec) > leaf.ndim:
                    raise ValueError(
                        f"rule {pat.pattern!r} spec {spec} has more axes than "
                        f"param {name} (shape {leaf.shape})"
                    )
                return spec
        return P()

    return jax.tree_util.tree_map_with_path(spec_for, params)


def mirror_tree_specs(opt_tree, params, like, default):
    """Per-leaf specs for an optimizer state: sub-trees that mirror ``params``
    (adam moments, momentum traces) inherit ``like`` (a params-shaped tree of
    specs/shardings); everything else (step counts, scalars) gets ``default``.

    Matching is structural (treedef equality) plus shape agreement, so it is
    optimizer-agnostic — no assumptions about optax's chain layout. Needed
    because ``jax.jit(tx.init)`` alone leaves the state committed to one
    device (restore-template mismatch) and because pytree-prefix specs cannot
    address moments nested inside an optax chain tuple."""
    import jax.tree_util as jtu

    pdef = jtu.tree_structure(params)
    pshapes = [np.shape(l) for l in jtu.tree_leaves(params)]

    def rec(node):
        if jtu.tree_structure(node) == pdef and [
            np.shape(l) for l in jtu.tree_leaves(node)
        ] == pshapes:
            return like
        not_self = lambda x: x is not node  # one-level flatten
        onelevel = jtu.tree_structure(node, is_leaf=not_self)
        children = jtu.tree_leaves(node, is_leaf=not_self)
        if children == [node]:  # node is itself a leaf
            return default
        return jtu.tree_unflatten(onelevel, [rec(c) for c in children])

    return rec(opt_tree)


def restrict_spec(spec: P, mesh: Mesh, shape=None) -> P:
    """Degrade ``spec`` onto what ``mesh`` (and optionally ``shape``) can
    carry: spec axes not present in the mesh become replicated, and — when
    a concrete ``shape`` is given — so does any dimension the mesh axis
    does not divide evenly (jax rejects ragged shards; replication is the
    correct degradation because rules are declarative over shape families).
    Shared by :func:`param_shardings` and the netps mesh dialect's
    device-resident center (``netps.mesh.MeshFolder``)."""

    def keep(d, axis):
        if axis is None:
            return None
        if isinstance(axis, (tuple, list)):
            kept = tuple(a for a in axis if a in mesh.axis_names)
            axis = kept if kept else None
        elif axis not in mesh.axis_names:
            axis = None
        if axis is None or shape is None:
            return axis
        names = axis if isinstance(axis, tuple) else (axis,)
        size = int(np.prod([mesh.shape[a] for a in names], dtype=np.int64))
        if d >= len(shape) or size < 1 or int(shape[d]) % size != 0:
            return None
        return axis

    return P(*(keep(d, a) for d, a in enumerate(spec)))


def param_shardings(params, mesh: Mesh, rules: Sequence[tuple[str, P]]):
    """Pytree of NamedShardings for ``params`` on ``mesh`` under ``rules``.

    Spec axes not present in ``mesh`` degrade to replicated, so one rule set
    (e.g. MOE_RULES, which mentions both ``expert`` and ``model``) serves every
    mesh shape.
    """
    specs = param_path_specs(params, rules)
    return jax.tree.map(lambda s: NamedSharding(mesh, restrict_spec(s, mesh)),
                        specs, is_leaf=lambda x: isinstance(x, P))
