"""The ``mesh`` transport dialect: a device-resident center.

Every dialect before this one (TCP frames, the shm ring) bottoms out in a
host-side fold — even the in-process raced twin round-trips host memory on
every commit. This dialect is the paper's stated north star (replace the
socket parameter server with ICI collectives) grafted onto the netps
contract instead of replacing it:

* **The center lives on device.** :class:`MeshFolder` holds the center as
  jax buffers laid out over a one-axis device mesh (``("fold",)``), each
  tensor's :class:`~jax.sharding.PartitionSpec` derived from the SAME
  :class:`~distkeras_tpu.netps.shards.PartitionPlan` the sharded wire
  plane uses (``plan.to_partition_specs()`` — one plan, two fabrics) and
  clamped by :func:`distkeras_tpu.parallel.sharding.restrict_spec`.
* **Folds are collectives.** One ``jax.jit(donate_argnums=0)`` program
  per codec signature folds the whole delta: a ``shard_map`` body adds
  each device's rows in place (donation means the old center buffers are
  consumed, not copied — the zero-copy fold), dequantization fused via
  the SAME Pallas kernel the host path uses
  (:func:`distkeras_tpu.ops.pallas.fold.fold_traced` — on TPU compiled,
  in tests interpreted), and a ``psum`` over per-device element counts is
  the cross-shard conservation check.
* **The dialect is negotiated, not assumed.** A mesh server advertises
  ``caps["mesh"] = {"proc": <boot_id:pid>, "token": ...}`` in its join
  reply; a client requesting ``DKTPU_NET_TRANSPORT=mesh`` upgrades only
  when the proc token matches :func:`local_mesh_id` — devices are
  shareable only within ONE jax runtime, so the same-runtime check is the
  shm boot-id check one level up. Everyone else stays on the wire.
* **Every durability guarantee is host-authoritative and rides through.**
  The request still crosses :meth:`PSServer._serve_frame` (dedup, epoch
  fence, lease, membership — unchanged), and every device fold's
  ``(wid, seq, staleness, epoch)`` record still enqueues into the bounded
  background journal writer. Recovery replays the journal host-side and
  re-seats the recovered center on device — bit-identical, because the
  collective body mirrors ``fold_compressed_numpy`` term for term.
* **Demotion, not failure.** A lost mesh (device loss, closed server,
  injected ``mesh_down``) demotes the client to its negotiated shm/TCP
  dialect without dropping the in-flight window — the retransmit keeps
  its seq and the dedup table makes it exactly-once; a mesh server serves
  the shm ring and TCP concurrently precisely so the demotion has
  somewhere to land. The shm->TCP fallback pattern, one level up.

Dispatch itself is a direct in-process call (no frames, no sockets, no
copies): the client hands its wire-form delta — the same ``(array, spec)``
pairs a frame would carry — straight to the server's dispatch under the
server's own lock discipline. That handoff is what lets the ``mesh``
dialect meet the in-process engine's rate (2-core CPU box, PR 20: two
repetitions, not a chip's number) while keeping journal + dedup + fence
semantics identical to the socket dialects.
"""

from __future__ import annotations

import os
import threading
import uuid
import warnings
from typing import Optional, Sequence

import numpy as np

from distkeras_tpu.netps import shm, wire
from distkeras_tpu.resilience import faults as _faults

#: the one mesh axis every center tensor folds over.
MESH_AXIS = "fold"


def local_mesh_id() -> str:
    """The same-runtime identity for mesh negotiation: device buffers are
    shareable only within one jax runtime, i.e. one process on one kernel
    — so the token is the shm boot-id check narrowed by pid."""
    return f"{shm.local_boot_id()}:{os.getpid()}"


# ---------------------------------------------------------------------------
# The in-process dispatch registry
# ---------------------------------------------------------------------------
#
# A mesh server registers its serve function under an opaque token and
# advertises the token in its join reply. Dispatch is the whole data path:
# the client's handler thread calls the server's transport-independent
# dispatch directly (the server's center lock serializes folds exactly as
# it does for socket handler threads). A token that is gone — server
# closed, process restarted — raises ConnectionError, which is precisely
# the failure class the client's demotion sweep catches.

_REG_LOCK = threading.Lock()
_SERVERS: dict = {}


def register(serve_fn) -> str:
    """Register a mesh server's serve function; returns its token."""
    token = uuid.uuid4().hex
    with _REG_LOCK:
        _SERVERS[token] = serve_fn
    return token


def unregister(token: Optional[str]) -> None:
    with _REG_LOCK:
        _SERVERS.pop(token, None)


def dispatch(token: str, header: dict, arrays: list):
    """One direct request against a registered mesh server: returns the
    ``(reply_header, reply_arrays)`` pair a wire frame would have carried.
    Raises ``ConnectionError`` when the peer is gone or when the
    ``mesh_down`` fault drill fires — both look like device loss to the
    caller, and both must trigger demotion, not an error reply."""
    with _REG_LOCK:
        fn = _SERVERS.get(token)
    if fn is None:
        raise ConnectionError("mesh peer is gone (server closed)")
    plan = _faults.active_net_plan()
    if plan is not None and header.get("op") == wire.OP_COMMIT:
        if plan.fire("mesh_down", int(header.get("seq", 0))) is not None:
            raise ConnectionError("injected mesh_down: device mesh lost")
    served = fn(dict(header), list(arrays))
    if served is None:
        raise ConnectionError("mesh peer refused the request")
    return served


# ---------------------------------------------------------------------------
# The device-resident center
# ---------------------------------------------------------------------------

class MeshFolder:
    """The center as donated device buffers, folded by collectives.

    Construction seats ``center`` (host f32 arrays) on the process's
    devices under per-tensor shardings; :meth:`fold` consumes a wire-form
    delta (plain arrays or ``(array, spec)`` codec pairs) through one
    jitted, buffer-donating collective program; :meth:`center_host` is
    the lazily-synced host mirror every read path (pull replies, join
    inits, snapshots, replication) goes through. NOT thread-safe — the
    server's center lock already serializes every caller.
    """

    def __init__(self, center: Sequence[np.ndarray], *, plan=None,
                 interpret: Optional[bool] = None):
        import jax
        from jax.sharding import Mesh, NamedSharding

        devices = jax.devices()
        if not devices:  # pragma: no cover - jax without devices
            raise RuntimeError("no jax devices for a mesh center")
        self.backend = devices[0].platform
        self.num_devices = len(devices)
        #: interpret=True forces the fused Pallas-kernel body under the
        #: interpreter off-TPU — the CI fold-parity hook (same kernel,
        #: same collective body a real chip runs). The default off-TPU is
        #: the exact two-program formulation instead (see the fold
        #: section below), which is bit-identical to the numpy oracle.
        self.interpret = bool(interpret)
        self._mesh = Mesh(np.asarray(devices), (MESH_AXIS,))
        self._shapes = [tuple(np.shape(a)) for a in center]
        specs = self._tensor_specs(plan)
        self._specs = specs
        self._shardings = [NamedSharding(self._mesh, s) for s in specs]
        # (np.ascontiguousarray would promote 0-d tensors to 1-d; the
        # reshape pins every recorded shape instead.)
        self._center = [
            jax.device_put(np.asarray(a, np.float32).reshape(s), sh)
            for a, sh, s in zip(center, self._shardings, self._shapes)]
        self._host: Optional[list] = [
            np.asarray(a, np.float32).reshape(s).copy()
            for a, s in zip(center, self._shapes)]
        #: expected psum'd element count per fold: a sharded tensor's
        #: shards sum to its size; a replicated tensor counts once per
        #: device (each folds its full copy) — any other total means a
        #: device shard went missing.
        self._expected = 0
        for sp, s in zip(specs, self._shapes):
            elems = int(np.prod(s, dtype=np.int64)) if s else 1
            self._expected += (elems if self._sharded_spec(sp)
                               else self.num_devices * elems)
        self.folds = 0
        self._fold_fns: dict = {}
        self._scale_fns: dict = {}
        self._add_fn = None

    # -- layout --------------------------------------------------------
    @staticmethod
    def _sharded_spec(spec) -> bool:
        return any(a is not None for a in spec)

    def _tensor_specs(self, plan) -> list:
        """Per-tensor PartitionSpecs: the wire plan's rules when given
        (``to_partition_specs`` — one plan for both fabrics), else shard
        axis 0 where the device count divides it; either way clamped by
        the shared ``restrict_spec`` so a ragged dim degrades to
        replicated instead of erroring."""
        from jax.sharding import PartitionSpec as P

        from distkeras_tpu.parallel.sharding import restrict_spec

        if plan is not None and len(plan.names) == len(self._shapes):
            base = [spec for _pat, spec in plan.to_partition_specs(MESH_AXIS)]
        else:
            base = [P(MESH_AXIS) if s and int(s[0]) >= self.num_devices
                    else P() for s in self._shapes]
        return [restrict_spec(sp, self._mesh, shape=s)
                for sp, s in zip(base, self._shapes)]

    # -- the collective fold -------------------------------------------
    #
    # Two formulations, one semantics:
    #
    # * **fused** (real TPUs, and interpret mode for the CI fold-parity
    #   job): ONE program — a shard_map body running the Pallas
    #   dequant+accumulate kernel per tensor shard. Parity with the numpy
    #   oracle is allclose-tight, the same bar the host Pallas path is
    #   held to (``tests/test_pallas_fold.py``): within one compiled
    #   program the multiply+add may contract to an FMA.
    # * **exact** (the CPU default): TWO programs — dequant·scale, then a
    #   donated collective add. The program boundary forces the product
    #   to round to f32 before the accumulate (XLA contracts mul+add into
    #   an FMA *within* a program, keeping the unrounded product — no
    #   barrier fences it), which makes the fold BIT-IDENTICAL to
    #   ``fold_compressed_numpy``. Uncompressed unit-scale commits (the
    #   hot adag path) skip the first program outright.

    def _build_scale(self, codecs: tuple):
        import jax
        import jax.numpy as jnp
        from jax import lax

        def dequant(q, s, codec):
            if codec is None:
                return s * q
            if codec == "int8":
                return s * q.astype(jnp.float32)
            return s * lax.bitcast_convert_type(
                q.astype(jnp.uint32) << jnp.uint32(16), jnp.float32)

        def scale_all(deltas, scales):
            return [dequant(q, s, codec)
                    for q, s, codec in zip(deltas, scales, codecs)]

        return jax.jit(scale_all)

    def _build_add(self, codecs):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from distkeras_tpu.ops.pallas import fold as pallas_fold

        n = len(self._shapes)
        specs = tuple(self._specs)
        interpret = self.interpret
        fused = codecs is not None

        def tensor_fold(c, q, s, codec):
            if not fused or codec is None:
                return c + s * q if fused else c + q
            return pallas_fold.fold_traced(c, q, s, codec=codec,
                                           interpret=interpret)

        def body(*flat):
            center = flat[:n]
            deltas = flat[n:2 * n]
            scales = flat[2 * n:] if fused else (None,) * n
            cods = codecs if fused else (None,) * n
            out = [tensor_fold(c, q, s, codec) for c, q, s, codec
                   in zip(center, deltas, scales, cods)]
            counted = sum(int(np.prod(c.shape, dtype=np.int64)) or 1
                          for c in center)
            folded = jax.lax.psum(jnp.int32(counted), MESH_AXIS)
            return tuple(out) + (folded,)

        scalar = tuple(P() for _ in range(n)) if fused else ()
        mapped = jax.shard_map(
            body, mesh=self._mesh,
            in_specs=specs + specs + scalar,
            out_specs=specs + (P(),),
            # pallas_call inside the body: replication checking must be off.
            check_vma=False)

        def fold_all(center, deltas, scales=()):
            return mapped(*center, *deltas, *scales)

        return jax.jit(fold_all, donate_argnums=(0, 1))

    def fold(self, delta: Sequence, scale: float) -> None:
        """Fold one wire-form commit into the device center. ``scale`` is
        the discipline's commit scale; per-tensor codec scales fold in
        exactly as the numpy reference folds them. Any failure leaves the
        center untouched (the programs are functional: nothing mutates
        until the donated program returns) — the server demotes to the
        host fold on exception."""
        import jax
        import jax.numpy as jnp

        from distkeras_tpu.netps import wire
        from distkeras_tpu.netps.fold import split_entry
        from distkeras_tpu.ops.pallas import mode

        if len(delta) != len(self._center):
            raise ValueError(
                f"delta has {len(delta)} tensors, center {len(self._center)}")
        fused = mode.compiles() or self.interpret
        arrs, scales, codecs = [], [], []
        for entry, shape in zip(delta, self._shapes):
            a, spec = split_entry(entry)
            codec = spec.get("codec") if spec else None
            if codec == wire.CODEC_INT8:
                s = float(scale) * float(spec["scale"])
                a = np.asarray(a, np.int8).reshape(shape)
            elif codec == wire.CODEC_BF16:
                s = float(scale)
                a = np.asarray(a, np.uint16).reshape(shape)
            else:
                codec = None
                s = float(scale)
                a = np.asarray(a, np.float32).reshape(shape)
                if not fused and s != 1.0:
                    # Exact mode scales UNCOMPRESSED tensors host-side:
                    # one numpy multiply rounds ``s*q`` to f32 exactly
                    # as the device scale program would (both round the
                    # product once), and when the whole commit is
                    # uncompressed — the hot f32 path — the scale
                    # program is skipped outright.
                    a = a * np.float32(s)
                    s = 1.0
            arrs.append(a)
            scales.append(np.float32(s))
            codecs.append(codec)
        key = tuple(codecs)
        deltas = [jax.device_put(a, sh)
                  for a, sh in zip(arrs, self._shardings)]
        jscales = [jnp.float32(s) for s in scales]
        with warnings.catch_warnings():
            # CPU ignores donation with a UserWarning; the fold is still
            # correct (just copying), and TPU honors it.
            warnings.simplefilter("ignore")
            if fused:
                fn = self._fold_fns.get(key)
                if fn is None:
                    fn = self._fold_fns[key] = self._build_add(key)
                out = fn(list(self._center), deltas, jscales)
            else:
                if any(c is not None for c in codecs) or \
                        any(float(s) != 1.0 for s in scales):
                    sfn = self._scale_fns.get(key)
                    if sfn is None:
                        sfn = self._scale_fns[key] = self._build_scale(key)
                    deltas = sfn(deltas, jscales)
                fn = self._add_fn
                if fn is None:
                    fn = self._add_fn = self._build_add(None)
                out = fn(list(self._center), list(deltas))
        folded = int(out[-1])
        if folded != self._expected:
            raise RuntimeError(
                f"mesh fold conservation check: psum counted {folded} "
                f"elements, expected {self._expected} — a device shard "
                f"went missing")
        self._center = list(out[:-1])
        self._host = None
        self.folds += 1

    # -- host views ----------------------------------------------------
    def center_host(self) -> list:
        """The host f32 mirror, synced lazily (one device->host transfer
        after any number of folds, not one per fold). Callers copy before
        handing rows to a reply — this list is the cache."""
        if self._host is None:
            import jax

            self._host = [
                np.asarray(jax.device_get(a), np.float32).reshape(s)
                for a, s in zip(self._center, self._shapes)]
        return self._host

    def close(self) -> None:
        self._center = []
        self._host = None
        self._fold_fns = {}
