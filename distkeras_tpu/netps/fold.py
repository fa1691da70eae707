"""The one shared server-side fold: commit discipline semantics.

Both parameter-server stand-ins — the in-process raced twin
(:class:`distkeras_tpu.racelab.RacedParameterServer`) and the networked
:class:`distkeras_tpu.netps.server.PSServer` — fold a worker's commit into
the center through THIS function, so the raced-parity evidence
(``tests/test_raced_ps.py``: raced PS vs deterministic window folds agree)
transfers to the network server by construction: same fold, different
transport.

Division of labor mirrors the reference exactly (SURVEY.md §3.3/§3.4): the
*worker* pre-normalizes its commit (ADAG divides by the window, the elastic
disciplines send ``e = α·(w − center)``), and the *server* applies one
scale — ``1/(staleness+1)`` for DynSGD, identity for everything else — and
adds. Staleness is the server's update counter minus the committer's
pull-time counter.

**Compressed-domain folds.** A delta tensor may arrive as an ``(array,
spec)`` pair in its *wire* dtype (the netps handlers read frames with
``decode=False``): int8 with a per-tensor scale, or bf16 bit-truncated.
Those fold without a decode-to-f32 pass — the dequantization is fused
into the accumulate: ``center += (commit_scale · tensor_scale) · q`` in one
numpy expression. This host fold never touches a jax backend: a parameter-
server process runs beside trainers that own the chips, and a chip belongs
to one process. The device-side twin of the same arithmetic is the Pallas
kernel (``distkeras_tpu.ops.pallas.fold``), reached only through a server
constructed with ``transport="mesh"`` (``netps/mesh.py``), whose process owns
its devices by construction; kernel-vs-numpy parity is pinned by
``tests/test_pallas_fold.py``.

Fold throughput is exported by the netps server as the
``netps.fold.tensors_per_sec`` gauge (docs/OBSERVABILITY.md) so the
report CLI can tell a fold-bound server from a wire-bound one.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

#: every discipline either PS stand-in accepts (the reference routed both
#: elastic trainers through the plain DeltaParameterServer — the fold is
#: identical; elasticity lives worker-side).
SUPPORTED_DISCIPLINES = ("downpour", "adag", "dynsgd", "aeasgd", "eamsgd")


def check_discipline(discipline: str) -> str:
    if discipline not in SUPPORTED_DISCIPLINES:
        raise ValueError(
            f"unsupported PS discipline {discipline!r}; "
            f"known: {list(SUPPORTED_DISCIPLINES)}")
    return discipline


def counter_scalar(counter) -> int:
    """One scalar from a possibly per-shard counter: a sharded center's
    pull/join returns one update counter PER SHARD; consumers mirroring a
    single lineage counter (the hier aggregator, the simulator's
    SimCenter) take the MIN — staleness charged from it can only be
    overstated (DynSGD then downweights, which is safe), never
    negative."""
    if isinstance(counter, (tuple, list)):
        return min(int(u) for u in counter)
    return int(counter)


def counter_staleness(updates, pulled) -> int:
    """THE staleness counter rule, shared by every center implementation
    — ``PSServer._fold_locked``, and the fleet simulator's stand-in
    center — so simulation exercises the same arithmetic production
    folds use: staleness is the server's update counter at fold time
    minus the committer's pull-time counter. Either side may arrive as a
    per-shard tuple (reduced by :func:`counter_scalar`'s MIN rule)."""
    return counter_scalar(updates) - counter_scalar(pulled)


def commit_scale(discipline: str, staleness: int) -> float:
    """The server-side scale applied to a commit folded ``staleness``
    updates after its pull (DynSGD's counter semantics; 1.0 otherwise)."""
    if discipline == "dynsgd":
        return 1.0 / (float(staleness) + 1.0)
    return 1.0


def split_entry(entry) -> tuple[np.ndarray, Optional[dict]]:
    """A delta entry is a plain ndarray (in-process callers) or an
    ``(array, spec)`` wire pair (the netps raw-decode path)."""
    if isinstance(entry, tuple):
        a, spec = entry
        return a, (spec or None)
    return entry, None


def decode_entry(entry) -> np.ndarray:
    """One delta entry -> a plain f32-domain array (the non-fold consumers:
    join inits, the hierarchical aggregator's pre-combine)."""
    from distkeras_tpu.netps import wire

    a, spec = split_entry(entry)
    return wire.codec_decode(a, spec) if spec else np.asarray(a)


def validate_delta(delta) -> None:
    """Up-front spec validation for a commit's wire entries — the rules
    ``codec_decode`` enforced before the ``decode=False`` path existed
    (unknown codec, int8 without a scale), applied BEFORE any fold or
    bookkeeping: a spec that failed mid-:func:`fold_delta` would leave
    the already-folded prefix tensors in the center with no commit_log
    entry, and the retransmit would fold them AGAIN. Raises
    ``ProtocolError``."""
    from distkeras_tpu.netps import wire
    from distkeras_tpu.netps.errors import ProtocolError

    for entry in delta:
        _a, spec = split_entry(entry)
        codec = spec.get("codec") if spec else None
        if not codec:
            continue
        if codec == wire.CODEC_INT8:
            try:
                float(spec["scale"])
            except (KeyError, TypeError, ValueError) as e:
                raise ProtocolError(f"int8 array spec without a scale: {e}")
        elif codec != wire.CODEC_BF16:
            raise ProtocolError(f"unknown codec {codec!r} in array spec")


# -- compressed-domain fold ------------------------------------------------

def fold_compressed_numpy(center: np.ndarray, a: np.ndarray, spec: dict,
                          scale: float) -> None:
    """Accumulate a wire-dtype tensor into the f32 ``center`` in place,
    dequantization fused into the add (the oracle the Pallas kernel and the
    mesh dialect's collective body are held to). Specs are assumed valid
    (:func:`validate_delta` runs before any fold): a missing int8 scale
    raises rather than silently folding zero."""
    from distkeras_tpu.netps import wire

    codec = spec.get("codec")
    if codec == wire.CODEC_INT8:
        s = np.float32(scale * float(spec["scale"]))
        if s:
            np.add(center, a.astype(np.float32) * s, out=center)
        return
    if codec == wire.CODEC_BF16:
        # Not compressed-domain in any meaningful sense on CPU (the f32
        # temp materializes either way) — reuse the ONE bf16 dequant.
        np.add(center, np.float32(scale) * wire.codec_decode(a, spec),
               out=center)
        return
    raise ValueError(f"unknown codec {codec!r} in delta spec")


def _fold_entry(c: np.ndarray, entry, scale: float) -> None:
    a, spec = split_entry(entry)
    codec = spec.get("codec") if spec else None
    if not codec:
        c += scale * np.asarray(a, c.dtype)
        return
    fold_compressed_numpy(c, np.asarray(a), spec, float(scale))


def fold_delta(center: Sequence[np.ndarray], delta: Sequence,
               discipline: str, staleness: int) -> None:
    """Fold one worker-normalized commit into ``center`` **in place** —
    the body of the reference's ``handle_commit`` under the lock. Delta
    entries may be plain arrays or ``(array, spec)`` wire pairs; codec'd
    pairs fold in the compressed domain.

    Deliberately telemetry-free: callers hold their center lock across
    this, and metrics must not nest a telemetry lock under it (DK201).
    The netps server times the call and exports
    ``netps.fold.tensors_per_sec`` after releasing its lock."""
    scale = commit_scale(discipline, staleness)
    for c, d in zip(center, delta):
        _fold_entry(c, d, scale)
