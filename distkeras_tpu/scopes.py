"""Whose instruction is it: the sublayer scopes of the round program.

The phase scopes (``dk_fwd_bwd``, ``dk_optimizer``, ``dk_fold`` ...) say *when*
an instruction runs and the kernel scopes (``dk_flash_fwd``, ``dk_moe_route``
...) cut single parts out. An **owner** scope says which sublayer of the model
an instruction belongs to, forward, recomputed and backward alike: a cast of
the parameters or the input, an embedding, a norm, the mixer (attention, the
delta rule, a short convolution, each with its projections), the feed-forward
or expert layer, a convolution, the head, the loss. Whatever lies under
``dk_fwd_bwd`` and under no owner (residual adds, reshapes) is glue, by
derivation; ``benchmarks/readers/trace_owner.py`` reads the scopes back from
the compiled program's text.

One vocabulary, one idiom: every owner scope in the tree is opened by ``with
owner("..."):`` and by nothing else. The prefix keeps the vocabulary apart
from the phases' and the kernels' (``dk_fold`` is a phase).

A scope is metadata. It adds no instruction, and JAX's compile-cache key
leaves metadata out: after adding or moving a scope, a compiled program's
text comes from an empty cache or it shows the old names. The counter
``trace.owner_scopes`` says that *this process's* lowering opened them, so a
reader can tell such a stale executable (counter above 0, no owner in the
text) from a program that predates the scopes (no such counter declared).
"""

from __future__ import annotations

import contextlib

import jax

OWNERS = ("cast", "embed", "norm", "mixer", "ffn", "conv", "head", "loss")
PREFIX = "dk_own_"


@contextlib.contextmanager
def owner(name: str):
    """``jax.named_scope`` of the sublayer ``name`` (one of :data:`OWNERS`),
    counted in ``trace.owner_scopes`` as it is opened, at trace time."""
    if name not in OWNERS:
        raise ValueError(f"no owner {name!r}: the vocabulary is {OWNERS}")
    from distkeras_tpu import telemetry

    telemetry.counter("trace.owner_scopes").add(1)
    with jax.named_scope(PREFIX + name):
        yield
