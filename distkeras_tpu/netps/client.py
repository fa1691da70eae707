"""The hardened parameter-server client: every edge guarded, fast by default.

Where the reference's worker did ``socket.connect(); send(pickle)`` and
hoped, every RPC here has

* a **deadline** — ``DKTPU_NET_TIMEOUT`` seconds per attempt, covering
  connect, send, and the full reply;
* **bounded retries with exponential backoff + full jitter** —
  ``DKTPU_NET_RETRIES`` attempts spaced by
  :func:`~distkeras_tpu.resilience.backoff.full_jitter` over a
  ``DKTPU_NET_BACKOFF``-based envelope, so W workers cut off by the same
  partition do not retry in lockstep;
* **idempotent commit sequencing** — the client assigns ``(worker_id,
  seq)`` *before* the first send and reuses it on every retransmit, so a
  commit whose ACK was lost is folded exactly once (the server dedups and
  answers ``duplicate=True``);
* **automatic re-join** — an RPC rejected with ``lease_expired`` (the
  server evicted us while we were away) triggers a fresh ``join``; ``pull``
  then simply returns the re-joined center, while ``commit`` reports
  ``evicted=True`` so the worker loop discards its stale window and
  continues from a fresh pull.

The data plane on top of those guarantees (all capability-negotiated at
join through the server's advertised :data:`~distkeras_tpu.netps.wire.CAPS`
— a PR 4 peer is spoken to in the PR 4 dialect):

* **Compressed deltas** (``DKTPU_NET_COMPRESS=bf16|int8``): commit tensors
  are quantized per-tensor before transmission; under ``int8`` the
  quantization error is carried forward as an **error-feedback residual**
  (added to the next window's delta), so the bias a 4x-smaller wire
  introduces is corrected over rounds instead of accumulating. The
  residual is discarded on rejoin — it belongs to the discarded window
  lineage.
* **Sharded striping** (``DKTPU_NET_SHARDS=N``): the parameter tree's
  tensors are striped (byte-balanced, deterministic) across N connections;
  pulls and commits issue one concurrent sub-RPC per stripe and reassemble
  before the caller sees anything. One logical commit keeps ONE ``seq``
  across all stripes — the server assembles the stripes and folds exactly
  once. A striped pull whose stripes straddled a concurrent fold (torn
  read) is detected by the echoed update counters and re-pulled; after
  ``_PULL_CONSISTENT_TRIES`` misses it falls back to one unsharded pull.

A failed attempt always tears that connection down and reconnects — stale
bytes die with the old socket, and the ``req`` id echo discards any
duplicate replies that survive on a healthy one. Typed, **non-retryable**
failures (:class:`ServerDrainingError`, :class:`LeaseExpiredError`)
surface immediately.

One client serves one worker thread; public methods are not safe to call
concurrently (the striped sub-RPCs inside one call run on the client's own
pool over disjoint connections — that is the supported concurrency).
"""

from __future__ import annotations

import socket
import threading
import time
import warnings
from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple, Optional, Sequence

import numpy as np

from distkeras_tpu.netps import mesh as _mesh
from distkeras_tpu.netps import shm, wire
from distkeras_tpu.netps.endpoints import EndpointWalker, budget_left
from distkeras_tpu.netps.errors import (
    EpochFencedError,
    LeaseExpiredError,
    NetPSError,
    NotPrimaryError,
    ProtocolError,
    RPCTimeoutError,
    ServerClosedError,
    ServerDrainingError,
    ShardPlanError,
)
from distkeras_tpu.resilience.backoff import full_jitter
from distkeras_tpu.runtime import config
from distkeras_tpu.telemetry import tracing
from distkeras_tpu.telemetry.tracing import clock as _traceclock

#: server error kind -> typed exception. Everything here except
#: ``not_primary`` is NON-retryable: the server answered, it just said no.
#: ``not_primary`` (an unpromoted standby / a fenced ex-primary) is
#: retryable *by walking the endpoint list* — the same RPC against the
#: next endpoint can succeed, so ``_rpc`` treats it like a transport
#: failure. ``epoch_fenced`` surfaces typed: the caller re-joins (walking
#: to the promoted primary) and discards its stale window, exactly like an
#: eviction.
_ERROR_TYPES = {
    "draining": ServerDrainingError,
    "lease_expired": LeaseExpiredError,
    "uninitialized": NetPSError,
    "protocol": ProtocolError,
    "epoch_fenced": EpochFencedError,
    "not_primary": NotPrimaryError,
    "shard_plan": ShardPlanError,
}

#: striped-pull consistency budget: whole-pull re-reads before falling back
#: to one unsharded pull (a torn read needs a fold to land mid-pull — rare).
_PULL_CONSISTENT_TRIES = 3


class CommitResult(NamedTuple):
    """What happened to one commit: ``applied`` (folded now),
    ``duplicate`` (folded by an earlier retransmit — still success),
    ``evicted`` (lease expired; the window was discarded and the client
    re-joined — pull fresh and continue)."""

    applied: bool
    duplicate: bool
    evicted: bool
    updates: int
    staleness: int


class _Conn:
    """One data connection — TCP socket or shared-memory ring — with its
    own request-id stream (reply matching is per-connection, so ids need
    only be unique per stream)."""

    __slots__ = ("sock", "ring", "req", "ever_connected", "dialect")

    def __init__(self):
        self.sock: Optional[socket.socket] = None
        self.ring: Optional[shm.ShmConnection] = None
        self.req = 0
        self.ever_connected = False
        #: last dialect ESTABLISHED on this conn ("tcp"/"shm"/None): only a
        #: same-dialect re-establishment is failure evidence — a negotiated
        #: dialect switch (the post-join shm upgrade, a fallback's TCP
        #: attach) must not read as a flapping network in telemetry.
        self.dialect: Optional[str] = None


#: measured-bad knob pairings (measured on a 2-core CPU box, PR 6;
#: docs/PERFORMANCE.md "Host-plane rules"; enforced at init
#: instead of living only in docs): (condition-name, why). Warned once
#: per process per combo — a fleet of workers must not scream N times.
_BAD_KNOB_COMBOS_WARNED: set = set()


def _validate_knob_combo(codec: str, transport: str, shards: int) -> None:
    """One-time warning + telemetry event when a measured-bad pairing is
    forced. Purely advisory: the knobs still apply exactly as requested —
    the user may know something that measurement did not."""
    combos = []
    if transport == "shm" and codec == wire.CODEC_INT8:
        combos.append((
            "int8+shm",
            "int8 loses on the shm ring: the quantize/dequantize passes "
            "cost more than the bytes they save at memcpy speed "
            "(docs/PERFORMANCE.md); prefer DKTPU_NET_COMPRESS=none"))
    if transport == "shm" and shards > 1:
        combos.append((
            "shards>1+shm",
            "striping over the shm ring pays a doorbell per stripe for "
            "payloads that already move at memcpy speed; prefer "
            "DKTPU_NET_SHARDS=1"))
    if transport == "mesh" and codec == wire.CODEC_INT8:
        combos.append((
            "int8+mesh",
            "the mesh dialect moves zero wire bytes, so the int8 codec "
            "buys nothing and still pays the quantization error plus the "
            "encode/decode passes; prefer DKTPU_NET_COMPRESS=none"))
    if transport == "mesh" and shards > 1:
        combos.append((
            "shards>1+mesh",
            "striping splits commits across sockets the mesh dialect "
            "never opens — every stripe lands on the same in-process "
            "dispatch and the server just reassembles them; prefer "
            "DKTPU_NET_SHARDS=1"))
    for combo, why in combos:
        if combo in _BAD_KNOB_COMBOS_WARNED:
            continue
        _BAD_KNOB_COMBOS_WARNED.add(combo)
        from distkeras_tpu import telemetry

        telemetry.counter("tuner.knob_warnings").add(1)
        telemetry.event("netps_knob_warning", {"combo": combo, "why": why})
        warnings.warn(f"measured-bad knob combination {combo}: {why}",
                      RuntimeWarning, stacklevel=3)


class PSClient:
    """One worker's connection(s) to a :class:`~distkeras_tpu.netps.server.
    PSServer` (or anything speaking the wire protocol, e.g. the chaos
    proxy). ``timeout``/``retries``/``backoff``/``shards``/``compress``
    default from the registry (`DKTPU_NET_TIMEOUT` / `DKTPU_NET_RETRIES` /
    `DKTPU_NET_BACKOFF` / `DKTPU_NET_SHARDS` / `DKTPU_NET_COMPRESS`)."""

    def __init__(self, endpoint: str, worker_id: Optional[int] = None,
                 timeout: Optional[float] = None,
                 retries: Optional[int] = None,
                 backoff: Optional[float] = None,
                 auto_rejoin: bool = True,
                 shards: Optional[int] = None,
                 compress: Optional[str] = None,
                 transport: Optional[str] = None):
        #: serializes the shm->TCP fallback sweep AND the endpoint walk:
        #: only the stripe thread that actually transitions (walks, or
        #: nulls shm_info) closes the other conns — a second sweeper would
        #: otherwise close a sibling's freshly re-established TCP socket
        #: mid-RPC. Created first so the walker can share it.
        self._fallback_lock = threading.Lock()
        #: ordered failover traversal — ``endpoint`` may be the
        #: comma-separated ``DKTPU_PS_ENDPOINT`` form (primary first, then
        #: standbys); a single endpoint is a one-element list and behaves
        #: exactly as before. Shares the fallback lock: the walk teardown
        #: must not interleave with the shm fallback sweep.
        self._walker = EndpointWalker(endpoint, lock=self._fallback_lock)
        self.endpoint = endpoint
        self.worker_id = worker_id
        self.timeout = float(timeout if timeout is not None
                             else config.env_float("DKTPU_NET_TIMEOUT"))
        self.retries = int(retries if retries is not None
                           else config.env_int("DKTPU_NET_RETRIES"))
        self.backoff = float(backoff if backoff is not None
                             else config.env_float("DKTPU_NET_BACKOFF"))
        self.auto_rejoin = auto_rejoin
        #: requested data-plane features; what is actually used is the
        #: join-negotiated subset (:attr:`codec` / :attr:`active_shards`).
        self.shards = max(1, int(shards if shards is not None
                                 else config.env_int("DKTPU_NET_SHARDS")))
        requested = compress if compress is not None else wire.net_codec()
        if requested not in wire.CODECS:
            raise ValueError(f"unknown codec {requested!r}; "
                             f"known: {list(wire.CODECS)}")
        self.requested_codec = requested
        transport = transport if transport is not None else shm.transport_mode()
        if transport not in shm.TRANSPORTS:
            raise ValueError(f"unknown transport {transport!r}; "
                             f"known: {list(shm.TRANSPORTS)}")
        #: requested transport dialect (``DKTPU_NET_TRANSPORT``); the ring
        #: is used only when the join reply advertises a same-boot-id shm
        #: endpoint — anything else silently stays on TCP.
        self.transport = transport
        _validate_knob_combo(requested, transport, self.shards)
        #: negotiated at join; until then the PR 4 dialect (f32, 1 conn).
        self.codec = wire.CODEC_NONE
        self.active_shards = 1
        #: the server's advertised ring endpoint when the same-host check
        #: passed (``{"boot_id", "uds"}``), else None (TCP dialect).
        self.shm_info: Optional[dict] = None
        #: the server's advertised device-mesh dispatch when the
        #: same-runtime check passed (``{"proc", "token", ...}``), else
        #: None. Set only under ``transport="mesh"`` against a same-process
        #: peer; a mesh failure sweeps it (one strike — a lost device mesh
        #: does not heal) and the client demotes to its ALSO-negotiated
        #: shm/TCP dialect without dropping the in-flight window.
        self.mesh_info: Optional[dict] = None
        self.lease_s: Optional[float] = None
        #: the primary epoch the last join adopted (None until a join
        #: against an epoch-aware server); rides in every pull/commit/
        #: heartbeat header so a promoted standby can fence the stale
        #: lineage and a zombie ex-primary can fence ITSELF on sight of a
        #: higher epoch.
        self.epoch: Optional[int] = None
        self._conns = [_Conn() for _ in range(self.shards)]
        self._pool: Optional[ThreadPoolExecutor] = None
        #: tensor-index stripes per shard, from the joined center's shapes.
        self._stripes: Optional[list] = None
        #: int8 error-feedback residual, one f32 array per delta tensor.
        self._residual: Optional[list] = None
        self._seq = -1
        self._closed = False
        #: times this client re-joined after an eviction (worker loops
        #: watch it to re-adopt the center on rejoin).
        self.rejoin_count = 0
        #: times the endpoint walker moved off an endpoint (failover in
        #: progress); the tuner's apply path reads it to DEFER a mid-walk
        #: retune — the rejoin renegotiates the dialect anyway.
        self.walk_count = 0
        #: extra header fields merged into EVERY join (including the
        #: auto-rejoin after an eviction/fence — an attribute, not a join()
        #: parameter, precisely so rejoins keep carrying it). The sharded
        #: client rides its shard identity + plan hash here.
        self._join_extra: dict = {}
        #: the last join reply's ``caps`` (the server's full capability
        #: advertisement, including any ``sharding`` identity) and the last
        #: ``plan_hash`` any reply echoed — the sharded client's
        #: cross-check surface.
        self.peer_caps: Optional[dict] = None
        self.peer_plan_hash: Optional[str] = None

    # -- lifecycle ---------------------------------------------------------
    def close(self) -> None:
        self._closed = True
        for conn in self._conns:
            self._disconnect(conn)
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None

    def __enter__(self) -> "PSClient":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def _connect(self, conn: _Conn, deadline: float) -> socket.socket:
        if conn.sock is not None:
            return conn.sock
        from distkeras_tpu import telemetry

        if conn.ever_connected and conn.dialect == "tcp":
            telemetry.counter("netps.reconnects").add(1)
        # The connect spends from the SAME per-attempt budget as the send
        # and reply (the documented contract): against a SYN-blackholing
        # partition, connect-then-wait must not cost 2x the deadline.
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            raise socket.timeout("deadline exceeded before connect")
        sock = socket.create_connection(self._current_endpoint(),
                                        timeout=remaining)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        conn.sock = sock
        conn.ever_connected = True
        conn.dialect = "tcp"
        return sock

    @property
    def active_transport(self) -> str:
        """The dialect the data connections speak right now."""
        if self.mesh_info is not None:
            return "mesh"
        return "shm" if self.shm_info is not None else "tcp"

    @property
    def _endpoints(self) -> list:
        """Ordered (host, port) failover list (compat alias onto the
        shared :class:`EndpointWalker`)."""
        return self._walker.endpoints

    @property
    def _ep_idx(self) -> int:
        return self._walker.index

    def _current_endpoint(self) -> tuple[str, int]:
        return self._walker.current()

    def _walk_endpoints(self, seen_idx: int) -> None:
        """Advance to the next endpoint after a failure observed against
        ``seen_idx`` (the walker's CAS, under the shared fallback lock, so
        N stripe threads failing together advance ONE step, not N).
        Walking drops every connection and any ring attachment — the next
        endpoint is a different process; nothing negotiated with the old
        one survives."""
        from distkeras_tpu import telemetry

        def teardown():
            # Runs under _fallback_lock: the walker wraps on_walk in its
            # shared lock, which IS that lock (see __init__) — the
            # analyzer can't see through the callback indirection.
            self.shm_info = None  # dk: disable=DK202
            # The next endpoint is a different process: no device mesh of
            # ours lives there (the same-runtime check would fail anyway).
            self.mesh_info = None  # dk: disable=DK202
            self.walk_count += 1
            for conn in self._conns:
                self._disconnect(conn)

        if self._walker.walk(seen_idx, on_walk=teardown):
            telemetry.counter("netps.endpoint_walks").add(1)

    @staticmethod
    def _disconnect(conn: _Conn) -> None:
        # Concurrent callers (the shm->TCP fallback sweeps EVERY conn from
        # whichever stripe thread failed first; siblings disconnect their
        # own) must never None-deref: snapshot-and-null, then close — a
        # double close is benign (sock.close and Slot.close are
        # idempotent), a close-after-null is impossible.
        sock, conn.sock = conn.sock, None
        ring, conn.ring = conn.ring, None
        if sock is not None:
            try:
                sock.close()
            except OSError:
                pass
        if ring is not None:
            ring.close()

    def _connect_ring(self, conn: _Conn, uds: str,
                      deadline: float) -> shm.ShmConnection:
        if conn.ring is not None:
            return conn.ring
        from distkeras_tpu import telemetry

        if conn.dialect == "shm":
            telemetry.counter("netps.reconnects").add(1)
        elif conn.ever_connected:
            # Routine post-join TCP->ring upgrade on a healthy run: its own
            # counter, NOT reconnects (documented as failure evidence).
            telemetry.counter("netps.shm_upgrades").add(1)
        # Attach (UDS connect + segment creation + fd passing) spends from
        # the same per-attempt budget as the doorbell round trip.
        ring = shm.ShmConnection(uds, deadline - time.monotonic())
        conn.ring = ring
        # A sibling's fallback sweep may have run while we attached; its
        # sweep nulls shm_info BEFORE iterating conns, so re-checking after
        # publishing the ring guarantees one side closes it — otherwise the
        # segments + the server's handler thread would outlive the upgrade
        # (this conn only ever speaks TCP after the sweep).
        if self.shm_info is None:
            self._disconnect(conn)
            raise ConnectionError("shm fallback engaged during ring attach")
        conn.ever_connected = True
        conn.dialect = "shm"
        return ring

    def _shard_pool(self) -> ThreadPoolExecutor:
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                max_workers=self.active_shards,
                thread_name_prefix="netps-stripe")
        return self._pool

    # -- the guarded RPC core ----------------------------------------------
    def _rpc(self, op: str, header: dict, arrays: Sequence = (),
             conn_idx: int = 0) -> tuple[dict, list]:
        if self._closed:
            raise ServerClosedError(f"client to {self.endpoint} is closed")
        from distkeras_tpu import telemetry

        conn = self._conns[conn_idx]
        attempts = self.retries + 1
        # Failover patience: with standbys configured, the retry budget
        # must bridge the PROMOTION window, not just a flaky frame — the
        # standby only takes over after the primary's lease lapses, and
        # with default knobs the attempt budget alone (~1.5 s) would give
        # up ~one lease before anyone is primary again. So multi-endpoint
        # clients keep walking until at least 2x the lease (detection +
        # promotion) + one deadline has elapsed, however many attempts
        # that takes. Single-endpoint clients keep the strict budget —
        # nothing is coming to save them, failing fast is correct.
        patience = self._walker.patience(self.lease_s, self.timeout)
        last_exc: Optional[BaseException] = None
        attempt = 0
        while True:
            conn.req += 1
            req = conn.req
            hdr = dict(header, op=op, req=req)
            if self.worker_id is not None:
                hdr.setdefault("worker_id", int(self.worker_id))
            # Per-shard RPC spans: stripe sub-RPCs are labeled by their
            # shard so the report can show per-stripe latency skew. The
            # transport dialect labels the span too (``.mesh``/``.shm``;
            # bare = TCP, the historical names) so the report CLI can
            # attribute RPC time per dialect — computed PER ATTEMPT, so
            # the TCP attempts after a mid-RPC demotion are not billed to
            # the faster dialect they fell off of.
            dialect = (".mesh" if self.mesh_info is not None
                       else ".shm" if self.shm_info is not None else "")
            label = (f"netps.rpc.{op}.s{header['shard']}{dialect}"
                     if "shard" in header else f"netps.rpc.{op}{dialect}")
            ep_seen = self._ep_idx
            try:
                with telemetry.span(label):
                    return self._attempt(conn, req, hdr, arrays)
            except NotPrimaryError as e:
                # The peer answered, but it is a standby (not yet
                # promoted) or a fenced ex-primary: retry by WALKING the
                # endpoint list — the same RPC against the next endpoint
                # (or this one, after promotion) can succeed.
                last_exc = e
                self._disconnect(conn)
                self._walk_endpoints(ep_seen)
                if not self._budget_left(attempt, attempts, patience):
                    break
                telemetry.counter("netps.retries").add(1)
                time.sleep(full_jitter(self.backoff, min(attempt, 6)))
                attempt += 1
                continue
            except (socket.timeout, ConnectionError, OSError,
                    ProtocolError) as e:
                if getattr(e, "from_reply", False):
                    raise  # the server said no; asking again won't help
                last_exc = e
                self._disconnect(conn)
                if self.mesh_info is not None:
                    # Mesh demotion is ONE strike (the shm ring retries
                    # once first; a lost device mesh does not heal): null
                    # the dispatch info and the NEXT attempt of this same
                    # RPC lands on the negotiated shm/TCP dialect with the
                    # same seq — the in-flight window rides through and
                    # the server's dedup keeps it exactly-once. Only the
                    # sweeping thread counts the demotion.
                    with self._fallback_lock:
                        swept = self.mesh_info is not None
                        if swept:
                            self.mesh_info = None
                    if swept:
                        telemetry.counter("netps.mesh.demotions").add(1)
                        telemetry.event("netps_mesh_demotion",
                                        {"why": f"{type(e).__name__}: {e}"})
                if self.shm_info is not None and (
                        attempt >= 1 or attempt + 1 == attempts):
                    # Two ring failures in a row (a transient fault retries
                    # once on the ring) — or the LAST attempt of a smaller
                    # retry budget, so a retries<=1 client still lands its
                    # NEXT rpc on TCP instead of riding a dead ring
                    # forever: the doorbell endpoint is likely gone — fall
                    # back to TCP, which the server always serves; the next
                    # join re-negotiates the upgrade. Drop EVERY
                    # connection's ring (not just this one's): stale
                    # attachments would otherwise leak segments + a server
                    # handler thread for the life of the client. Only the
                    # thread that wins the transition sweeps (a loser's
                    # sweep would close a sibling's fresh TCP socket).
                    with self._fallback_lock:
                        swept = self.shm_info is not None
                        if swept:
                            self.shm_info = None
                            for other in self._conns:
                                self._disconnect(other)
                    if swept:
                        telemetry.counter("netps.shm_fallbacks").add(1)
                # A transport failure with standbys configured also walks
                # — a dead primary never answers again, and the retransmit
                # (same seq) is exactly-once-safe wherever it lands — but
                # only once a retry against the SAME endpoint has also
                # failed (the shm-fallback rule): walking tears down every
                # stripe's connection, so a single flaky frame against a
                # healthy primary must not pay a full teardown plus a
                # wasted hop to the unpromoted standby.
                if attempt >= 1 or attempt + 1 == attempts:
                    self._walk_endpoints(ep_seen)
                if not self._budget_left(attempt, attempts, patience):
                    break
                telemetry.counter("netps.retries").add(1)
                time.sleep(full_jitter(self.backoff, min(attempt, 6)))
                attempt += 1
        telemetry.counter("netps.rpc_failures").add(1)
        if isinstance(last_exc, NotPrimaryError):
            # Every endpoint we could reach is a standby (or a fenced
            # ex-primary): surface that typed — "nobody is primary yet" is
            # actionable in a way a generic timeout is not.
            raise last_exc
        raise RPCTimeoutError(
            f"{op} to {self.endpoint} failed after {attempt + 1} attempts "
            f"(last: {type(last_exc).__name__}: {last_exc})",
            attempts=attempt + 1)

    @staticmethod
    def _budget_left(attempt: int, attempts: int,
                     patience: Optional[float]) -> bool:
        """May the retry loop go around again? The attempt budget, OR —
        multi-endpoint only — the failover patience window (the shared
        :func:`distkeras_tpu.netps.endpoints.budget_left`)."""
        return budget_left(attempt, attempts, patience)

    def _attempt(self, conn: _Conn, req: int, hdr: dict,
                 arrays: Sequence) -> tuple[dict, list]:
        """One connect + send + matched-reply receive under ONE deadline.
        The transport is whatever the join negotiated: TCP frames, or the
        same-host ring (payload in shared memory, doorbell on the UDS) —
        the deadline/matching/error contract is identical either way."""
        from distkeras_tpu import telemetry

        deadline = time.monotonic() + self.timeout
        minfo = self.mesh_info
        if minfo is not None:
            # The mesh dialect: one direct in-process call — no socket, no
            # frame, no copy. The server's dispatch enforces the identical
            # op contract (dedup, lease, fence) under its own lock; a gone
            # peer or an injected ``mesh_down`` raises ConnectionError
            # into the demotion sweep above.
            rhdr, rarrays = _mesh.dispatch(minfo["token"], hdr, list(arrays))
            err = rhdr.get("error")
            if err:
                exc = _ERROR_TYPES.get(err, NetPSError)(
                    f"{hdr['op']}: server said {err}: "
                    f"{rhdr.get('message', '')}")
                exc.from_reply = True
                raise exc
            return rhdr, rarrays
        # One read: a sibling stripe thread's shm->TCP fallback may null
        # shm_info at any point; this attempt finishes on the dialect it
        # started with (a closed ring raises the retryable taxonomy).
        info = self.shm_info
        if info is not None:
            ring = self._connect_ring(conn, info["uds"], deadline)
            ring.settimeout(max(0.001, deadline - time.monotonic()))
            sent = ring.send(wire.KIND_REQUEST, hdr, arrays)

            def set_timeout(t):
                ring.settimeout(t)

            def recv_one():
                return ring.recv()
        else:
            sock = self._connect(conn, deadline)
            sock.settimeout(max(0.001, deadline - time.monotonic()))
            sent = wire.send_frame(sock, wire.KIND_REQUEST, hdr, arrays)

            def set_timeout(t):
                sock.settimeout(t)

            def recv_one():
                prefix = wire.recv_exact(sock, wire.PREFIX_SIZE)
                return wire.finish_frame(sock, prefix)
        telemetry.counter("netps.bytes_sent").add(sent)
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise socket.timeout(f"{hdr['op']} deadline exceeded")
            set_timeout(remaining)
            kind, nbytes, rhdr, rarrays = recv_one()
            if kind != wire.KIND_REPLY:
                raise ProtocolError(f"expected a reply frame, got kind {kind}")
            if rhdr.get("req") != req:
                # A duplicated or late reply (chaos `dup`): discard and keep
                # reading — the req echo is what keeps the stream sane.
                telemetry.counter("netps.stale_replies").add(1)
                continue
            telemetry.counter("netps.bytes_received").add(nbytes)
            err = rhdr.get("error")
            if err:
                exc = _ERROR_TYPES.get(err, NetPSError)(
                    f"{hdr['op']}: server said {err}: "
                    f"{rhdr.get('message', '')}")
                # The server ANSWERED — retrying a deterministic rejection
                # burns the whole budget for the same answer. ProtocolError
                # is otherwise retryable (a corrupt frame heals on a fresh
                # connection); this flag tells _rpc the difference.
                exc.from_reply = True
                raise exc
            return rhdr, rarrays

    def _stamped(self, header: dict) -> dict:
        """Stamp the adopted epoch into a member-op header (no-op against
        pre-epoch servers — we never claim an epoch we were not given)."""
        if self.epoch is not None:
            header["epoch"] = self.epoch
        return header

    # -- distributed tracing (telemetry/tracing/) ----------------------------
    def _trace_peer(self) -> bool:
        """Whether the joined peer advertised ``CAPS["tracing"]`` — the
        gate on every trace/clock header field. A peer that never said the
        bit is sent zero new bytes (absent JSON key = absent wire byte)."""
        return bool((self.peer_caps or {}).get("tracing"))

    def _traced(self, header: dict) -> dict:
        """Attach the ambient trace context to an outgoing header (no-op
        with tracing off, outside any scope, or against an untraced peer)."""
        if self._trace_peer():
            header.update(tracing.wire_fields())
        return header

    def _rpc_traced(self, ctx, op: str, header: dict, arrays: Sequence = (),
                    conn_idx: int = 0) -> tuple[dict, list]:
        """One stripe sub-RPC under the captured trace context: pool
        threads do not inherit thread-locals, so the fan-out captures the
        commit/pull root and re-establishes it here, giving every stripe
        its own ``<op>.wire`` child span carrying the wire fields."""
        with tracing.adopt(ctx):
            with tracing.child_scope(f"{op}.wire",
                                     shard=header.get("shard")):
                return self._rpc(op, self._traced(header), arrays, conn_idx)

    def _clock_stamp(self, header: dict):
        """Stamp ``ct0`` (this clock's send time) for the NTP-style
        exchange — only against a peer that already proved it speaks the
        tracing dialect. Returns the stamp for :func:`observe_reply`."""
        if not (tracing.enabled() and self._trace_peer()):
            return None
        ct0 = time.time()
        header["ct0"] = ct0
        return ct0

    # -- striping helpers ---------------------------------------------------
    def _compute_stripes(self, template: Sequence[np.ndarray]) -> None:
        """Byte-balanced greedy stripe assignment of tensor indices over the
        active shard connections, from the joined center's shapes.
        Deterministic; the indices ride in every stripe header, so the
        server never recomputes it."""
        n = min(self.active_shards, max(1, len(template)))
        if n <= 1:
            self._stripes = None
            return
        order = sorted(range(len(template)),
                       key=lambda i: (-int(np.asarray(template[i]).nbytes), i))
        loads = [0] * n
        stripes: list = [[] for _ in range(n)]
        for i in order:
            s = loads.index(min(loads))
            stripes[s].append(i)
            loads[s] += int(np.asarray(template[i]).nbytes)
        for st in stripes:
            st.sort()
        self._stripes = stripes

    def _striped(self) -> bool:
        return (self.active_shards > 1 and self._stripes is not None
                and len(self._stripes) > 1)

    def _gather(self, futures: list) -> list:
        """Results of stripe futures; waits for ALL (no socket left with an
        in-flight reply), then re-raises the highest-priority failure —
        lease expiry beats transport errors (the caller's rejoin handles
        it; a retry cannot)."""
        results, errors = [], []
        for f in futures:
            try:
                results.append(f.result())
            except BaseException as e:  # noqa: BLE001 - re-raised below
                errors.append(e)
        if errors:
            for e in errors:
                if isinstance(e, LeaseExpiredError):
                    raise e
            raise errors[0]
        return results

    # -- RPC surface --------------------------------------------------------
    def join(self, init: Optional[Sequence[np.ndarray]] = None,
             ) -> tuple[list, int]:
        """Become (or re-become) a member; returns ``(center, updates)``.
        ``init`` seeds an uninitialized server (first joiner wins; later
        inits are ignored — everyone adopts the server's center). The
        join reply's advertised capabilities select the wire dialect
        (codec + striping) for every later pull/commit. ``_join_extra``
        fields (the sharded client's shard identity + plan) ride on every
        join, auto-rejoins included."""
        join_hdr = dict(self._join_extra, caps=wire.CAPS)
        # The clock exchange rides only once the peer has PROVED the
        # tracing dialect (a previous join's caps) — the first join of a
        # fresh client stays byte-identical to an untraced one; rejoins
        # and heartbeats carry the estimate forward.
        ct0 = self._clock_stamp(join_hdr)
        hdr, center = self._rpc(wire.OP_JOIN, join_hdr, list(init or ()))
        if ct0 is not None:
            _traceclock.observe_reply(ct0, hdr, time.time())
        self.worker_id = int(hdr["worker_id"])
        self.lease_s = hdr.get("lease_s")
        # A join ADOPTS the server's epoch (a failover re-join is exactly
        # this client arriving with a stale lineage); pre-epoch servers
        # never send one and this client then never claims one.
        self.epoch = (int(hdr["epoch"]) if hdr.get("epoch") is not None
                      else None)
        caps = hdr.get("caps") or {}
        self.peer_caps = caps
        sharding = caps.get("sharding")
        self.peer_plan_hash = (sharding.get("plan_hash")
                               if isinstance(sharding, dict) else None)
        self.codec = (self.requested_codec
                      if self.requested_codec in caps.get("codecs", ())
                      else wire.CODEC_NONE)
        self.active_shards = self.shards if caps.get("striping") else 1
        self._compute_stripes(center)
        # Same-host transport upgrade: only when this client asked for shm
        # AND the server advertised a ring endpoint AND the boot ids match
        # (actually-the-same-kernel, not just the same hostname). Every
        # other combination — old server (no caps / boolean bit), cross
        # host, tcp mode — stays on the TCP dialect with zero behavior
        # change. A re-join that lands on a different answer (e.g. a
        # restarted TCP-only server) tears the stale connections down.
        adv = caps.get("shm")
        # A mesh client negotiates the ring TOO: it is the demotion target
        # (mesh -> shm -> TCP) — losing the device mesh must not mean
        # falling all the way to sockets when the ring is one step down.
        info = (adv if self.transport in ("shm", "mesh")
                and isinstance(adv, dict)
                and adv.get("uds") and adv.get("boot_id") == shm.local_boot_id()
                and shm.endpoint_visible(adv["uds"])
                else None)
        # Same-runtime mesh upgrade: only when this client asked for mesh
        # AND the server's live advertisement proves the SAME jax runtime
        # (same boot, same process — device buffers do not cross either).
        madv = caps.get("mesh")
        minfo = (madv if self.transport == "mesh" and isinstance(madv, dict)
                 and madv.get("token")
                 and madv.get("proc") == _mesh.local_mesh_id()
                 else None)
        with self._fallback_lock:  # vs a concurrent fallback sweep
            if (info is None) != (self.shm_info is None):
                for conn in self._conns:
                    self._disconnect(conn)
            self.shm_info = info
            upgraded = minfo is not None and self.mesh_info is None
            self.mesh_info = minfo
        if upgraded:
            from distkeras_tpu import telemetry

            telemetry.counter("netps.mesh.upgrades").add(1)
        # Error feedback restarts on every (re)join: the residual belongs
        # to the window lineage the rejoin just discarded.
        self._residual = None
        # Resume the commit sequence past what the server already folded
        # from this worker_id: a restarted worker process starts at seq -1,
        # and without adopting the server's high-water mark every commit of
        # the new incarnation would be deduped away as a "retransmit".
        server_seq = int(hdr.get("last_seq", -1))
        if server_seq > self._seq:
            self._seq = server_seq
        return center, int(hdr["updates"])

    def adopt_dialect(self, other: "PSClient",
                      template: Sequence[np.ndarray]) -> None:
        """Adopt another client's join-negotiated dialect (codec, striping,
        transport) without a join of our own — membership is by worker_id,
        not by connection. The overlap loop's pull-prefetch client uses
        this so both lanes speak the same wire."""
        self.codec = other.codec
        self.active_shards = other.active_shards
        self.epoch = other.epoch
        with self._fallback_lock:  # vs a concurrent fallback sweep
            self.shm_info = other.shm_info
            self.mesh_info = other.mesh_info
        self._compute_stripes(template)

    # -- self-tuning surface (netps/tuner/) ---------------------------------
    def probe(self, arrays: Sequence[np.ndarray],
              codec: Optional[str] = None) -> Optional[dict]:
        """One timed micro-A/B round trip under ``codec`` (default: the
        negotiated one): the payload travels and is decoded exactly like a
        commit, but the server's ``probe`` op never touches the fold, the
        journal, or the dedup table. Returns the reply header, or None
        when the joined peer does not speak the probe dialect (no
        ``tuner`` caps bit / codec not advertised) — old peers are left
        alone by construction."""
        caps = self.peer_caps or {}
        if not caps.get("tuner"):
            return None
        use = codec if codec is not None else self.codec
        if use != wire.CODEC_NONE and use not in caps.get("codecs", ()):
            return None
        items: list = []
        for a in arrays:
            a = np.ascontiguousarray(a, np.float32)
            if use == wire.CODEC_NONE:
                items.append(a)
                continue
            encoded, extras = wire.codec_encode(a, use)
            items.append((encoded, extras) if extras else encoded)
        hdr, _ = self._rpc(wire.OP_PROBE,
                           self._stamped({"probe_codec": use}), items)
        return hdr

    def retune(self, codec: Optional[str] = None,
               shards: Optional[int] = None,
               template: Optional[Sequence[np.ndarray]] = None) -> dict:
        """Adopt a new wire dialect MID-RUN through the same state the
        join negotiation writes — membership, seq, epoch, and every
        exactly-once guarantee are untouched (a retransmit after a retune
        carries its original seq and dedups normally). Returns
        ``{knob: (old, new)}`` of what actually changed; a codec the peer
        never advertised or an out-of-range stripe count is clamped, not
        an error. The caller must have quiesced its own in-flight commits
        first (one logical commit must finish under ONE dialect)."""
        caps = self.peer_caps or {}
        changed: dict = {}
        if codec is not None and codec != self.codec:
            if codec == wire.CODEC_NONE or codec in caps.get("codecs", ()):
                changed["codec"] = (self.codec, codec)
                self.codec = codec
                # The residual belongs to the old codec's lineage; error
                # feedback restarts, exactly as on a rejoin.
                self._residual = None
                # Rejoins renegotiate from the retuned preference, not the
                # construction-time one — a failover must not undo the
                # controller's decision.
                self.requested_codec = codec
        if shards is not None:
            want = max(1, min(int(shards), len(self._conns)))
            if not caps.get("striping"):
                want = 1
            if want != self.active_shards:
                changed["shards"] = (self.active_shards, want)
                self.active_shards = want
                self.shards = max(self.shards, want)
                if template is not None:
                    self._compute_stripes(template)
                else:
                    self._stripes = None
                # The stripe pool is sized to active_shards; recreate lazily.
                pool, self._pool = self._pool, None
                if pool is not None:
                    pool.shutdown(wait=True)
        return changed

    def pull(self) -> tuple[list, int]:
        """Current center + update counter; renews the lease. An evicted
        client transparently re-joins first (``auto_rejoin``). Striped
        pulls reassemble a consistency-checked center (torn reads across a
        concurrent fold are detected via the echoed counters and
        re-pulled)."""
        try:
            with tracing.trace_scope("pull", wid=self.worker_id):
                if self._striped():
                    return self._striped_pull()
                with tracing.child_scope("pull.wire"):
                    hdr, center = self._rpc(
                        wire.OP_PULL, self._traced(self._stamped({})))
        except (LeaseExpiredError, EpochFencedError) as e:
            # Fenced reads exactly like evicted: the old lineage is gone;
            # re-join (walking to the promoted primary) and adopt.
            if isinstance(e, EpochFencedError):
                tracing.flight_dump("epoch_fenced")
            if not self.auto_rejoin:
                raise
            self.rejoin_count += 1
            return self.join()
        if hdr.get("plan_hash") is not None:
            # A shard server re-proves its plan identity on every pull;
            # keep the latest so the sharded client can cross-check.
            self.peer_plan_hash = hdr["plan_hash"]
        return center, int(hdr["updates"])

    def _striped_pull(self) -> tuple[list, int]:
        pool = self._shard_pool()
        stripes = self._stripes
        total = sum(len(s) for s in stripes)
        ctx = tracing.current()
        for _ in range(_PULL_CONSISTENT_TRIES):
            futures = [
                pool.submit(self._rpc_traced, ctx, wire.OP_PULL,
                            self._stamped({"shard": s,
                                           "num_shards": len(stripes),
                                           "idx": idx}), (), s)
                for s, idx in enumerate(stripes)]
            replies = self._gather(futures)
            counters = {int(h["updates"]) for h, _ in replies}
            if len(counters) == 1:
                center: list = [None] * total
                for (_h, arrays), idx in zip(replies, stripes):
                    for i, a in zip(idx, arrays):
                        center[i] = a
                return center, counters.pop()
            # A fold landed between stripe reads: torn center — re-read.
            from distkeras_tpu import telemetry

            telemetry.counter("netps.pull_torn_retries").add(1)
        # Persistent contention: one unsharded pull is always consistent.
        hdr, center = self._rpc(wire.OP_PULL, self._stamped({}))
        return center, int(hdr["updates"])

    def _compress_delta(self, delta: Sequence[np.ndarray]) -> list:
        """Delta tensors -> wire items under the negotiated codec, updating
        the int8 error-feedback residual (quantization error carried into
        the NEXT commit, so the wire's bias corrects over rounds)."""
        from distkeras_tpu import telemetry

        delta = [np.ascontiguousarray(d, np.float32) for d in delta]
        telemetry.counter("netps.bytes_precompress").add(
            sum(d.nbytes for d in delta))
        if self.codec == wire.CODEC_NONE:
            return delta
        if self.codec == wire.CODEC_INT8 and self._residual is None:
            self._residual = [np.zeros_like(d) for d in delta]
        items = []
        for i, d in enumerate(delta):
            if self.codec == wire.CODEC_INT8:
                d = d + self._residual[i]
            encoded, extras = wire.codec_encode(d, self.codec)
            if self.codec == wire.CODEC_INT8:
                self._residual[i] = d - wire.codec_decode(encoded, extras)
            items.append((encoded, extras) if extras else encoded)
        return items

    def commit(self, delta: Sequence[np.ndarray], pulled_counter: int,
               seq: Optional[int] = None) -> CommitResult:
        """Fold ``delta`` (worker-normalized) into the center. The seq is
        assigned before the first transmission and reused across retries:
        a lost ACK can never double-fold. With striping, ONE seq spans all
        stripe sub-RPCs — the server assembles them and folds once. An
        explicit ``seq`` is the sharded client's one-logical-seq fan-out
        (and its dedup-safe same-seq retransmit after a per-shard
        eviction); this client's own counter only ever moves forward."""
        if seq is None:
            self._seq += 1
            seq = self._seq
        else:
            self._seq = max(self._seq, int(seq))
            seq = int(seq)
        # The trace root: one commit = one trace, client-rooted. Segments
        # recorded here (encode/wire/ack) and on every process the wire
        # fields reach (queue/fold/fsync/replicate) share its trace id.
        with tracing.trace_scope("commit", wid=self.worker_id, seq=seq):
            with tracing.child_scope("commit.encode"):
                items = self._compress_delta(delta)
            base = self._stamped({"seq": seq, "pulled": int(pulled_counter)})
            try:
                if self._striped() and len(items) == sum(
                        len(s) for s in self._stripes):
                    hdr = self._striped_commit(base, items)
                else:
                    with tracing.child_scope("commit.wire"):
                        hdr, _ = self._rpc(wire.OP_COMMIT, self._traced(base),
                                           items)
            except (LeaseExpiredError, EpochFencedError) as e:
                # Fenced commit = evicted commit: it was NEVER folded (the
                # whole point of the fence); discard the window, re-join
                # the promoted primary, continue from a fresh pull. A
                # fence is flight-recorder evidence: dump the discarded
                # lineage's last seconds before rejoining past it.
                if isinstance(e, EpochFencedError):
                    tracing.flight_dump("epoch_fenced")
                if not self.auto_rejoin:
                    raise
                self.rejoin_count += 1
                self.join()
                return CommitResult(applied=False, duplicate=False,
                                    evicted=True, updates=-1, staleness=-1)
            if hdr is None:
                # Every stripe answered ``pending``: membership churn (an
                # eviction sweep or a concurrent rejoin purging the
                # server's half-assembled stripe set) lost this commit —
                # it was NEVER folded and never will be. Same recovery as
                # an evicted commit: discard the window, refresh
                # membership + the server's pending state, continue from
                # a fresh pull.
                if not self.auto_rejoin:
                    raise NetPSError(
                        "striped commit never completed: every stripe is "
                        "pending — the server lost part of the stripe set")
                self.join()
                return CommitResult(applied=False, duplicate=False,
                                    evicted=True, updates=-1, staleness=-1)
            with tracing.child_scope("commit.ack",
                                     applied=bool(hdr.get("applied"))):
                return CommitResult(
                    applied=bool(hdr.get("applied")),
                    duplicate=bool(hdr.get("duplicate")),
                    evicted=False, updates=int(hdr["updates"]),
                    staleness=int(hdr.get("staleness", -1)))

    def _striped_commit(self, base: dict, items: list) -> Optional[dict]:
        """One logical commit over the stripe connections; returns the
        fold-outcome header, or None when every stripe came back
        ``pending`` (the server lost part of the set to membership churn —
        :meth:`commit` recovers via the evicted path)."""
        stripes = self._stripes
        pool = self._shard_pool()
        ctx = tracing.current()
        futures = [
            pool.submit(
                self._rpc_traced, ctx, wire.OP_COMMIT,
                dict(base, shard=s, num_shards=len(stripes), idx=idx),
                [items[i] for i in idx], s)
            for s, idx in enumerate(stripes)]
        replies = self._gather(futures)
        # Exactly one stripe's reply carries the fold outcome (the one that
        # completed the assembly, or the dedup answer); the rest say
        # ``pending``.
        for hdr, _ in replies:
            if hdr.get("applied"):
                return hdr
        for hdr, _ in replies:
            if hdr.get("duplicate"):
                return hdr
        return None

    def heartbeat(self) -> int:
        """Renew the lease; returns the server's update counter. A traced
        heartbeat doubles as the clock exchange's steady drumbeat — every
        renewal is another four-timestamp sample, and the min-rtt one
        wins."""
        hb = self._stamped({})
        ct0 = self._clock_stamp(hb)
        try:
            hdr, _ = self._rpc(wire.OP_HEARTBEAT, hb)
        except (LeaseExpiredError, EpochFencedError) as e:
            if isinstance(e, EpochFencedError):
                tracing.flight_dump("epoch_fenced")
            if not self.auto_rejoin:
                raise
            self.rejoin_count += 1
            _center, updates = self.join()
            return updates
        if ct0 is not None:
            _traceclock.observe_reply(ct0, hdr, time.time())
        return int(hdr["updates"])

    def stats(self, ring: int = 64) -> dict:
        """One live telemetry scrape of the peer (``CAPS`` op ``stats``):
        counters/gauges/span aggregates plus the flight ring's most recent
        ``ring`` records. Membership-free — no join, no lease, no seq —
        so any observer (the ``telemetry scrape`` CLI) can dial in."""
        hdr, _ = self._rpc(wire.OP_STATS, {"ring": int(ring)})
        return hdr

    def leave(self) -> None:
        """Best-effort clean departure (a dead server is not an error —
        leaving was the goal)."""
        try:
            self._rpc(wire.OP_LEAVE, {})
        except (NetPSError, OSError):
            pass
