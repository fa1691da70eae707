"""Ring attention: causal attention over a sequence-sharded axis via ``ppermute``.

Long-context support the 2016-era reference never had (SURVEY.md §5 marks it absent),
built the TPU way: each chip holds a ``[B, L/S, H, D]`` block of Q/K/V; K/V blocks hop
around the ring one neighbor per step (``ppermute`` rides adjacent ICI links) while
each chip folds the arriving block into a streaming-softmax accumulator. Peak memory
is O(L/S · L/S) per score block instead of O(L²), and the permute of the *next* block
overlaps with the matmul of the current one (XLA schedules the collective-permute
async).

Must be called inside ``shard_map`` with ``axis_name`` in the mesh (the transformer's
``seq`` axis). Accumulation is float32 regardless of input dtype; output returns in
the input dtype.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


_NEG = -1e30


def ring_attention(q, k, v, axis_name: str):
    """Causal multi-head attention with sequence sharded over ``axis_name``.

    Args:
      q, k, v: ``[batch, local_len, heads, head_dim]`` — this chip's sequence block.
        ``q`` is expected pre-scaled (by 1/sqrt(head_dim)).
      axis_name: mesh axis carrying the sequence shards.

    Returns:
      ``[batch, local_len, heads, head_dim]`` attention output for the local block.
    """
    B, L, H, D = q.shape
    out_dtype = q.dtype
    S = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)

    qf = q.astype(jnp.float32)
    q_pos = my * L + jnp.arange(L)

    # Streaming-softmax accumulators (m: running max, l: running denominator).
    m0 = jnp.full((B, H, L), _NEG, jnp.float32)
    l0 = jnp.zeros((B, H, L), jnp.float32)
    acc0 = jnp.zeros((B, H, L, D), jnp.float32)
    perm = [(j, (j + 1) % S) for j in range(S)]

    def step(carry, i):
        k_blk, v_blk, m, l, acc = carry
        src = (my - i) % S  # ring rank the current K/V block originated from
        k_pos = src * L + jnp.arange(L)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, k_blk.astype(jnp.float32))
        mask = (q_pos[:, None] >= k_pos[None, :])[None, None]
        s = jnp.where(mask, s, _NEG)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # exp(NEG - NEG) would be 1 for fully-masked rows; mask the probabilities,
        # not just the scores.
        p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32)
        )
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return (k_blk, v_blk, m_new, l, acc), None

    (_, _, _, l, acc), _ = lax.scan(step, (k, v, m0, l0, acc0), jnp.arange(S))
    # Every q position attends at least to itself (own block, i=0), so l > 0.
    out = acc / l[..., None]
    return out.transpose(0, 2, 1, 3).astype(out_dtype)
