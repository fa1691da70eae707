"""The networked parameter server: the reference's socket architecture, hardened.

``DeltaParameterServer``/``ADAGParameterServer`` re-created for real: a TCP
listener, **one handler thread per connection**, and a center variable
folded under a plain lock — but with the production edges the reference
never had:

* **Idempotent commits.** Every commit carries a client-assigned
  ``(worker_id, seq)``; the server folds a given seq at most once and
  answers a retransmit (lost ACK) with ``applied=False, duplicate=True``.
  The retry path is therefore exactly-once *in effect* on an at-least-once
  transport — assert it on :attr:`PSServer.commit_log`.
* **Lease-based elastic membership.** ``join`` grants a lease; ``pull`` /
  ``commit`` / ``heartbeat`` renew it; a monitor thread evicts workers whose
  lease expires. Training continues with the survivors, and an evicted (or
  brand-new) worker can ``join`` mid-run and pull the current center — no
  global restart.
* **Graceful drain.** :meth:`close` stops accepting commits (clients get a
  typed ``ServerDrainingError``), lets in-flight handler frames finish,
  then tears the listener and every thread down (all joined — nothing
  leaks past close).

The fold itself is :func:`distkeras_tpu.netps.fold.fold_delta` — the same
function the in-process raced twin uses, so raced-parity evidence
transfers. Commit tensors reach it in their *wire* dtype (the handlers
read frames with ``decode=False``), so int8/bf16 deltas fold in the
compressed domain — dequantization is fused into the accumulate
(numpy reference on CPU, the ``ops/pallas/fold.py`` kernel on TPU)
instead of materializing an f32 copy first. The server is numpy + stdlib
only: it runs as its own process (``python -m distkeras_tpu.netps``) with
no jax dependency on the hot path.

Transports: TCP always; with ``DKTPU_NET_TRANSPORT=shm`` (or
``transport="shm"``) the server additionally serves the same-host
shared-memory ring dialect (``netps/shm.py``) — a UDS doorbell listener
advertised in the join reply, with payloads in client-owned mmap'd
segments. Same handlers, same dispatch, same guarantees.
"""

from __future__ import annotations

import collections
import json
import os
import signal
import socket
import tempfile
import threading
import time
import uuid
from typing import Optional, Sequence

import numpy as np

from distkeras_tpu.netps import mesh as _mesh
from distkeras_tpu.netps import shm, wire
from distkeras_tpu.netps import state as _state
from distkeras_tpu.netps.errors import ProtocolError
from distkeras_tpu.netps.fold import (check_discipline, commit_scale,
                                      counter_staleness, decode_entry,
                                      fold_delta, validate_delta)
from distkeras_tpu.resilience import faults as _faults
from distkeras_tpu.runtime import config
from distkeras_tpu.telemetry import tracing as _tracing

#: handler/accept poll tick: how often blocked threads wake to check stop.
_POLL_S = 0.2
#: once a frame's first bytes arrive, the rest must land within this —
#: a peer that stalls mid-frame is dead, not idle.
_FRAME_COMPLETE_S = 30.0
#: in-memory commit-log bound: the evidence list is compacted (oldest
#: half dropped, counted in ``commits_total``) once it doubles this, and
#: trimmed to it at snapshot time — a month-long run must not grow an
#: unbounded Python list next to the center.
_COMMIT_LOG_KEEP = 65536
#: replication tail depth: folded commits kept (in wire form) for a
#: standby's ``replicate`` pulls; a standby further behind than this gets
#: a full snapshot sync instead.
_REPL_BUFFER = 64
#: max journal records per ``replicate`` reply (bounds the frame size).
_REPL_BATCH = 16


class PSServer:
    """One center variable served over TCP to N worker clients.

    ``center=None`` starts uninitialized: the first ``join`` carrying init
    arrays seeds it (so a CLI-launched server needs no model knowledge —
    the workers bring the parameters). ``lease_s`` defaults to
    ``DKTPU_PS_LEASE``.
    """

    def __init__(self, center: Optional[Sequence[np.ndarray]] = None,
                 discipline: str = "adag", host: str = "127.0.0.1",
                 port: int = 0, lease_s: Optional[float] = None,
                 transport: Optional[str] = None,
                 state_dir: Optional[str] = None,
                 snapshot_every: Optional[int] = None,
                 epoch: int = 0,
                 commit_log_keep: Optional[int] = None,
                 standby: bool = False,
                 shard_index: Optional[int] = None,
                 shard_count: Optional[int] = None,
                 shard_plan=None):
        self.discipline = check_discipline(discipline)
        #: sharded-center identity: which slice of which PartitionPlan this
        #: server holds. ``None`` index means a plain (whole-center) server.
        #: The plan itself may arrive later — a shard launched empty adopts
        #: it from the first join and persists it next to the journal.
        self.shard_index = None if shard_index is None else int(shard_index)
        self.shard_count = (int(shard_count) if shard_count is not None
                            else (None if self.shard_index is None else 1))
        if self.shard_index is not None and not (
                0 <= self.shard_index < self.shard_count):
            raise ValueError(f"shard index {self.shard_index} outside "
                             f"0..{self.shard_count - 1}")
        self.shard_plan = None
        if shard_plan is not None:
            from distkeras_tpu.netps.shards import plan as _plan_mod
            self.shard_plan = (shard_plan if isinstance(
                shard_plan, _plan_mod.PartitionPlan)
                else _plan_mod.PartitionPlan.from_dict(shard_plan))
        self.transport = (transport if transport is not None
                          else shm.transport_mode())
        if self.transport not in shm.TRANSPORTS:
            raise ValueError(f"unknown transport {self.transport!r}; "
                             f"known: {list(shm.TRANSPORTS)}")
        self._lock = threading.Lock()
        self._center = (None if center is None
                        else [np.array(a, np.float32) for a in center])
        #: device-resident center (``transport="mesh"``): folds run through
        #: the jitted collective in :class:`netps.mesh.MeshFolder` and
        #: ``self._center`` becomes its lazily-synced host mirror (every
        #: read goes through :meth:`_host_center_locked`). ``None`` means
        #: host folds — never built yet, build failed, or demoted mid-run.
        self._mesh_folder: Optional[_mesh.MeshFolder] = None
        self._mesh_token: Optional[str] = None
        self._mesh_failed = False
        self._mesh_demote_reason: Optional[str] = None
        self._last_fold_mesh = False
        self._updates = 0
        self.lease_s = float(lease_s if lease_s is not None
                             else config.env_float("DKTPU_PS_LEASE"))
        #: worker_id -> lease deadline (monotonic seconds).
        self._members: dict = {}
        #: worker_id -> highest folded commit seq (survives eviction, so a
        #: pre-eviction retransmit is still deduped after a rejoin).
        self._last_seq: dict = {}
        #: every worker_id ever admitted (rejoin accounting + id assignment).
        self._ever: set = set()
        #: primary epoch: joins/commits carry it; a commit from a lineage
        #: this server no longer honors (or that no longer honors this
        #: server) is fenced, never folded. Bumped only by a standby's
        #: promotion (``netps/standby.py``).
        self.epoch = int(epoch)
        #: a higher epoch exists somewhere: this server is the zombie and
        #: must never fold again (join/pull/commit all answer ``standby``).
        self._fenced = False
        #: a warm standby serves nothing until it promotes.
        self._not_primary = bool(standby)
        #: all commits ever folded — ``commit_log`` is the bounded tail of
        #: it (``len(commit_log) + dropped == commits_total`` always).
        self.commits_total = 0
        self.snapshots_written = 0
        self._log_dropped = 0
        self._log_keep = int(commit_log_keep if commit_log_keep is not None
                             else _COMMIT_LOG_KEEP)
        #: per-incarnation lineage token, echoed on every ``replicate``
        #: reply: a restarted primary may have LOST the tail of its fold
        #: history (the bounded writer queue died with it), so fold
        #: indices alone cannot prove a standby's center still matches —
        #: same index, different history. A standby that sees the token
        #: change discards its state and full-syncs (the primary's
        #: durable state is the authoritative lineage).
        self.lineage = uuid.uuid4().hex
        #: replication tail (pre-fold index, wid, seq, staleness, wire
        #: delta); only populated once a standby's first ``replicate``
        #: arrives — no memory tax on un-replicated deployments.
        self._repl: collections.deque = collections.deque(
            maxlen=_REPL_BUFFER)
        self._repl_on = False
        #: striped commits awaiting assembly: (worker_id, seq) ->
        #: {shard: (idx tuple, arrays)}. One logical commit spans
        #: ``num_shards`` stripe sub-requests under ONE seq; the stripe
        #: that completes the set triggers the single fold. Purged on
        #: eviction and (re)join — a dead worker's half-commit must not
        #: linger.
        self._pending: dict = {}
        #: applied commits in fold order: (worker_id, seq, staleness) — the
        #: exactly-once evidence the chaos tests assert on.
        self.commit_log: list = []
        #: (tensors, seconds) of the most recent fold — written under the
        #: lock, exported as the fold-throughput gauge after release.
        self._fold_stats = (0, 0.0)
        #: durable state (``--state-dir``): journal + snapshots + recovery.
        #: Must come after the commit_log init — a ctor-seeded center with
        #: a fresh dir snapshots right here.
        self._store: Optional[_state.StateStore] = None
        if state_dir:
            self._store = _state.StateStore(state_dir, snapshot_every)
            rec = self._store.recover(self.discipline)
            if rec is not None:
                # The disk is authoritative over any ctor-passed center: a
                # restart resumes the folded lineage, it does not reseed.
                self._center = rec.center
                self._updates = rec.updates
                self._last_seq = dict(rec.last_seq)
                self._ever = set(rec.last_seq)
                self.epoch = max(self.epoch, rec.epoch)
                self.commits_total = rec.commits_total
                # A fence that landed on the previous incarnation is
                # durable: the zombie stays a zombie across restarts.
                self._fenced = self._fenced or rec.fenced
                # The pre-crash commits are not in this incarnation's log:
                # they count as "dropped" so the bound invariant
                # len(commit_log) + dropped == commits_total keeps holding.
                self._log_dropped = rec.commits_total
            self._store.open_journal(self._updates)
            if self._center is not None and rec is None:
                # Ctor-seeded center with a fresh dir: anchor the journal
                # with the base snapshot a recovery will replay onto.
                self._snapshot_locked()
        #: durable plan identity: a restarted shard must refuse a client
        #: whose plan drifted from the lineage on disk, so the plan file is
        #: authoritative over any ctor-passed plan (same rule as the center).
        self._plan_path = (os.path.join(state_dir, "plan.json")
                           if state_dir else None)
        if self._plan_path is not None and os.path.exists(self._plan_path):
            from distkeras_tpu.netps.shards import plan as _plan_mod
            with open(self._plan_path, "r", encoding="utf-8") as f:
                saved = json.load(f)
            self.shard_plan = _plan_mod.PartitionPlan.from_dict(
                saved["plan"])
            if self.shard_index is None:
                self.shard_index = int(saved["shard_index"])
                self.shard_count = self.shard_plan.num_shards
        elif self.shard_plan is not None:
            self._persist_plan_locked()
        self.evictions = 0
        self.rejoins = 0
        self._draining = False
        self._stop = threading.Event()
        self._listener = socket.create_server((host, port))
        self._listener.settimeout(_POLL_S)
        self._host = host
        self._port = self._listener.getsockname()[1]
        self._threads: list = []
        self._accept_thread: Optional[threading.Thread] = None
        self._monitor_thread: Optional[threading.Thread] = None
        self._started = False
        # Same-host ring dialect: a UDS doorbell listener, advertised (with
        # this host's boot id) in every join reply so colocated clients can
        # upgrade. TCP remains fully served either way — the ring is an
        # upgrade, never a requirement.
        self._boot_id = shm.local_boot_id()
        self._uds_dir: Optional[str] = None
        self._uds_path: Optional[str] = None
        self._uds_listener: Optional[socket.socket] = None
        self._uds_accept_thread: Optional[threading.Thread] = None
        # A mesh server serves the ring too: the demotion ladder
        # (mesh -> shm -> tcp) needs the next rung advertised in the same
        # join reply the mesh bit rides in.
        if self.transport in ("shm", "mesh"):
            self._uds_dir = tempfile.mkdtemp(prefix="dknetps-")
            self._uds_path = os.path.join(self._uds_dir, "ring.sock")
            self._uds_listener = socket.socket(socket.AF_UNIX,
                                               socket.SOCK_STREAM)
            self._uds_listener.bind(self._uds_path)
            self._uds_listener.listen()
            self._uds_listener.settimeout(_POLL_S)

    # ------------------------------------------------------------------
    @property
    def endpoint(self) -> str:
        return f"{self._host}:{self._port}"

    @property
    def updates(self) -> int:
        return self._updates

    def center(self) -> list:
        with self._lock:
            if self._center is None:
                return []
            return [a.copy() for a in self._host_center_locked()]

    def _host_center_locked(self) -> list:
        """The host view of the center (lock held): ``self._center``
        itself when folds are host-side, or the mesh folder's synced
        mirror when the center lives on device. Every read path (pull
        replies, join inits, snapshots, replication, :meth:`center`)
        comes through here so a device-resident fold is never served
        stale."""
        if self._mesh_folder is not None:
            # Caller holds self._lock (the `_locked` suffix contract).
            self._center = self._mesh_folder.center_host()  # dk: disable=DK202
        return self._center

    def members(self) -> list:
        with self._lock:
            return sorted(self._members)

    # ------------------------------------------------------------------
    def start(self) -> "PSServer":
        """Begin accepting connections (idempotent)."""
        if self._started:
            return self
        self._started = True
        t = threading.Thread(target=self._accept_loop,
                             name="netps-accept")
        t.start()
        self._accept_thread = t
        t = threading.Thread(target=self._monitor_loop,
                             name="netps-monitor")
        t.start()
        self._monitor_thread = t
        if self._uds_listener is not None:
            t = threading.Thread(target=self._uds_accept_loop,
                                 name="netps-shm-accept")
            t.start()
            self._uds_accept_thread = t
        if self.transport == "mesh":
            self._mesh_token = _mesh.register(self._serve_mesh)
            self._ensure_mesh_folder()
        return self

    def _ensure_mesh_folder(self) -> None:
        """Seat the center on device (idempotent; no-op until a center
        exists). Only a ``transport="mesh"`` server ever touches a jax
        backend — it was constructed to own this process's devices; every
        other server folds in numpy and initializes none. The device init
        (seconds) happens OUTSIDE the center lock, then the folder is built
        from the live center under it. A build failure — including a jax
        runtime with no usable device — demotes this server to host folds
        permanently (``_mesh_failed``) and is counted and evented: every
        wire guarantee still holds, only the dialect advertisement is
        gone."""
        if (self.transport != "mesh" or self._mesh_failed
                or self._mesh_folder is not None):
            return
        plan = (self.shard_plan
                if self.shard_plan is not None and self.shard_index is None
                else None)
        try:
            import jax

            jax.devices()
            with self._lock:
                if self._mesh_folder is None and self._center is not None:
                    self._mesh_folder = _mesh.MeshFolder(self._center,
                                                         plan=plan)
        except Exception as e:  # noqa: BLE001 - demote, never refuse boot
            self._mesh_failed = True
            from distkeras_tpu import telemetry
            telemetry.counter("netps.mesh.demotions").add(1)
            telemetry.event("netps_mesh_demotion",
                            {"why": f"build: {type(e).__name__}: {e}"})

    def _serve_mesh(self, header: dict, arrays: list):
        """One direct in-process request (the mesh dialect's data path):
        no frames, no sockets, no copies — straight into the
        transport-independent dispatch, with the payload bytes counted as
        received. Runs on the CLIENT's thread; the center lock provides
        the same serialization the socket handler threads get."""
        nbytes = 0
        for entry in arrays:
            a = entry[0] if isinstance(entry, tuple) else entry
            nbytes += np.asarray(a).nbytes
        return self._serve_frame(wire.KIND_REQUEST, nbytes, header, arrays,
                                 dialect=".mesh")

    def drain(self) -> None:
        """Enter draining mode: commits and joins are rejected with a typed
        ``ServerDrainingError``; pulls still serve (departing workers may
        fetch the final center). In-flight folds finish — the flip
        serializes behind any commit holding the lock."""
        with self._lock:
            self._draining = True

    def close(self) -> None:
        """Graceful shutdown: :meth:`drain`, then stop and join every
        thread (accept loop, per-connection handlers, lease monitor) and
        release the listener. Idempotent."""
        # Unregister the mesh dispatch first: in-flight mesh clients see
        # ConnectionError and demote to the ring/TCP (where drain answers
        # them typed) instead of racing a dying dispatch target.
        if self._mesh_token is not None:
            _mesh.unregister(self._mesh_token)
            self._mesh_token = None
        self.drain()
        self._stop.set()
        if self._store is not None:
            self._store.close()
        if self._accept_thread is not None:
            self._accept_thread.join()
        if self._uds_accept_thread is not None:
            self._uds_accept_thread.join()
        if self._monitor_thread is not None:
            self._monitor_thread.join()
        for t in list(self._threads):
            t.join()
        if self._mesh_folder is not None:
            # Sync the host mirror before releasing the device buffers —
            # post-close reads (tests asserting on the final center) must
            # see every fold.
            with self._lock:
                self._center = self._mesh_folder.center_host()
                self._mesh_folder.close()
                self._mesh_folder = None
        try:
            self._listener.close()
        except OSError:
            pass
        if self._uds_listener is not None:
            try:
                self._uds_listener.close()
            except OSError:
                pass
            for path in (self._uds_path, self._uds_dir):
                try:
                    if path and os.path.exists(path):
                        (os.unlink if path == self._uds_path
                         else os.rmdir)(path)
                except OSError:
                    pass

    # ------------------------------------------------------------------
    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _addr = self._listener.accept()
            except socket.timeout:
                continue
            except OSError:
                return  # listener closed under us
            conn.settimeout(_POLL_S)
            t = threading.Thread(target=self._handle, args=(conn,),
                                 name="netps-handler")
            t.start()
            self._threads.append(t)

    def _uds_accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _addr = self._uds_listener.accept()
            except socket.timeout:
                continue
            except OSError:
                return  # listener closed under us
            conn.settimeout(_POLL_S)
            t = threading.Thread(target=self._handle_shm, args=(conn,),
                                 name="netps-shm-handler")
            t.start()
            self._threads.append(t)

    def _monitor_loop(self) -> None:
        """Evict members whose lease expired; training continues with the
        survivors (the Spark-driver failure-detection half, made explicit)."""
        from distkeras_tpu import telemetry

        tick = max(0.05, min(self.lease_s / 4.0, _POLL_S))
        while not self._stop.wait(tick):
            now = time.monotonic()
            with self._lock:
                expired = [w for w, dl in self._members.items() if dl < now]
                for w in expired:
                    del self._members[w]
                    self.evictions += 1
                    self._purge_pending(w)
            for w in expired:
                telemetry.counter("netps.evictions").add(1)
                telemetry.event("netps_eviction", {"worker": w})

    def revoke(self, worker_id: int) -> bool:
        """Administrative lease revocation — the fleet scheduler's
        preemption primitive. The worker is evicted NOW (not at its lease
        deadline): membership dropped, half-assembled commit stripes
        purged, its next RPC answers ``lease_expired``. Dedup state
        (``_last_seq``) survives exactly as with a natural eviction, so a
        revoked worker's in-flight retransmit is still deduped and a
        later re-grant rejoins with its sequence intact. Returns whether
        the worker was a member."""
        from distkeras_tpu import telemetry

        wid = int(worker_id)
        with self._lock:
            present = wid in self._members
            if present:
                del self._members[wid]
                self.evictions += 1
                self._purge_pending(wid)
        if present:
            telemetry.counter("netps.revocations").add(1)
            telemetry.event("netps_revocation", {"worker": wid})
        return present

    # ------------------------------------------------------------------
    def _handle(self, conn: socket.socket) -> None:
        """One connection's handler thread — the reference's
        ``handle_commit`` loop, framed and checksummed. Polls for the first
        byte of each frame (so ``close()`` can stop it) and switches to a
        completion timeout once a frame starts — a half-arrived frame never
        desyncs back into the idle poll."""
        from distkeras_tpu import telemetry

        with conn:
            while not self._stop.is_set():
                try:
                    prefix = wire.recv_exact(conn, wire.PREFIX_SIZE)
                except socket.timeout:
                    continue
                except (ConnectionError, OSError):
                    return
                try:
                    conn.settimeout(_FRAME_COMPLETE_S)
                    # Zero-copy: the body lands in one preallocated buffer
                    # and the arrays are views over it (wire.finish_frame).
                    # decode=False keeps codec'd commit tensors in their
                    # wire dtype for the compressed-domain fold.
                    kind, nbytes, header, arrays = wire.finish_frame(
                        conn, prefix, decode=False)
                    conn.settimeout(_POLL_S)
                except (socket.timeout, ConnectionError, OSError):
                    return
                except ProtocolError:
                    # Stream can never re-align: drop the connection. The
                    # client reconnects and retries.
                    telemetry.counter("netps.protocol_errors").add(1)
                    return
                try:
                    served = self._serve_frame(kind, nbytes, header, arrays)
                except ProtocolError:
                    # An op-level decode error (a join init with a bad codec
                    # spec reaches decode_entry only now that frames arrive
                    # decode=False) is the same contract violation as a bad
                    # frame: count it and tear down — the shm handler's
                    # outer guard already treats it this way.
                    telemetry.counter("netps.protocol_errors").add(1)
                    return
                if served is None:
                    return
                reply, out = served
                try:
                    sent = wire.send_frame(conn, wire.KIND_REPLY, reply, out)
                except (ConnectionError, OSError):
                    return
                telemetry.counter("netps.bytes_sent").add(sent)

    def _handle_shm(self, conn: socket.socket) -> None:
        """One ring connection's handler: the same request/reply loop as
        :meth:`_handle` with the payload in the client's mmap'd segments —
        the doorbell socket carries only 8-byte frame lengths. A bad ring
        frame (crc flip, torn slot) is a ProtocolError and tears this
        connection down, exactly like a corrupt TCP frame: the client
        reconnects with fresh segments and retransmits under the same seq."""
        from distkeras_tpu import telemetry

        rings = None
        with conn:
            try:
                conn.settimeout(_FRAME_COMPLETE_S)
                rings = shm.accept_attach(conn)
                conn.settimeout(_POLL_S)
                c2s, s2c = rings
                while not self._stop.is_set():
                    try:
                        raw = wire.recv_exact(conn, wire.SHM_DOORBELL_SIZE)
                    except socket.timeout:
                        continue
                    length = wire.unpack_doorbell(raw)
                    try:
                        kind, nbytes, header, arrays = c2s.read_frame(
                            length, decode=False)
                    except ProtocolError:
                        telemetry.counter("netps.protocol_errors").add(1)
                        return
                    served = self._serve_frame(kind, nbytes, header, arrays,
                                               dialect=".shm")
                    if served is None:
                        return
                    reply, out = served
                    sent = s2c.write_frame(wire.KIND_REPLY, reply, out)
                    conn.sendall(wire.pack_doorbell(sent))
                    telemetry.counter("netps.bytes_sent").add(sent)
            except (socket.timeout, ConnectionError, OSError):
                return
            except ProtocolError:
                telemetry.counter("netps.protocol_errors").add(1)
                return
            finally:
                if rings is not None:
                    for slot in rings:
                        slot.close()

    def _serve_frame(self, kind: int, nbytes: int, header: dict,
                     arrays: list, dialect: str = ""):
        """The transport-independent middle of a request: validate, count,
        dispatch under a per-op span (labeled with the transport dialect),
        and stamp the request-id echo. ``None`` = protocol violation, the
        caller tears the connection down."""
        from distkeras_tpu import telemetry

        if kind != wire.KIND_REQUEST:
            telemetry.counter("netps.protocol_errors").add(1)
            return None
        telemetry.counter("netps.bytes_received").add(nbytes)
        op = header.get("op", "")
        # Clock + trace plumbing, both strictly echo-shaped: ``st1``/
        # ``st2`` are answered ONLY when the request stamped ``ct0`` (the
        # NTP-style exchange), and the trace context exists ONLY when the
        # request carried ``trace`` — an untraced peer sees zero new
        # bytes in either direction.
        st1 = time.time() if "ct0" in header else None
        tctx = _tracing.header_ctx(header)
        if op == wire.OP_COMMIT:
            self._chaos_hooks()
        with telemetry.span(f"netps.server.{op or 'unknown'}{dialect}"):
            with _tracing.adopt(tctx):
                reply, out = self._dispatch(op, header, arrays,
                                            dialect=dialect)
        err = reply.get("error")
        if op == wire.OP_COMMIT and err == "epoch_fenced":
            # The zero-stale-epoch-folds evidence: every fenced commit is
            # a commit that did NOT reach the fold.
            telemetry.counter("netps.failover.fenced_commits").add(1)
        elif op == wire.OP_REPLICATE and reply.get("mode") == "snapshot":
            telemetry.counter("netps.failover.snapshot_syncs").add(1)
        elif op == wire.OP_FENCE and reply.get("fenced"):
            telemetry.counter("netps.failover.fences_accepted").add(1)
            telemetry.event("netps_fenced", {"epoch": reply.get("epoch")})
        if self._store is not None and op in (wire.OP_COMMIT, wire.OP_JOIN):
            telemetry.gauge("netps.recovery.snapshots").set(
                float(self.snapshots_written))
        if st1 is not None:
            reply["st1"] = st1
            reply["st2"] = time.time()
        reply["req"] = header.get("req")
        return reply, out

    def _chaos_hooks(self) -> None:
        """The PS-side chaos kinds, consulted per commit *request* (no
        proxy can kill this process for us). ``ps_hang@R:S`` sleeps S
        seconds HOLDING the center lock — every member's lease renewal
        queues behind a genuinely wedged server; ``ps_crash@R`` is the
        kill-the-primary drill: SIGKILL, mid-run, no goodbye."""
        plan = _faults.active_net_plan()
        if plan is None:
            return
        at = self.commits_total
        arg = plan.fire("ps_hang", at)
        if arg:
            with self._lock:
                # The whole point of ps_hang is to wedge the server WHILE
                # holding the center lock — the hazard DK501 exists to
                # catch is the drill here.
                time.sleep(arg)  # dk: disable=DK501
        if plan.fire("ps_crash", at) is not None:
            os.kill(os.getpid(), signal.SIGKILL)
        if self.shard_index is not None:
            # ``shard_crash@N:R``: kill SHARD N (the ``at`` slot selects the
            # shard, not a commit count — every shard runs its own plan
            # instance, so the index is the only shared coordinate) once it
            # has folded R commits. Non-consuming peek first: shard k != N
            # must not burn the one-shot.
            arg = plan.pending("shard_crash", self.shard_index)
            if arg is not None and self.commits_total >= (arg or 0):
                plan.fire("shard_crash", self.shard_index)
                os.kill(os.getpid(), signal.SIGKILL)

    def _dispatch(self, op: str, header: dict, arrays: list,
                  dialect: str = "") -> tuple[dict, list]:
        if op == wire.OP_JOIN:
            return self._op_join(header, arrays)
        if op == wire.OP_PULL:
            return self._op_pull(header, dialect=dialect)
        if op == wire.OP_COMMIT:
            return self._op_commit(header, arrays)
        if op == wire.OP_HEARTBEAT:
            return self._op_heartbeat(header)
        if op == wire.OP_LEAVE:
            return self._op_leave(header)
        if op == wire.OP_REPLICATE:
            return self._op_replicate(header)
        if op == wire.OP_FENCE:
            return self._op_fence(header)
        if op == wire.OP_PROBE:
            return self._op_probe(header, arrays)
        if op == wire.OP_STATS:
            return self._op_stats(header)
        return {"error": "protocol", "message": f"unknown op {op!r}"}, []

    @staticmethod
    def _err(kind: str, message: str) -> tuple[dict, list]:
        return {"error": kind, "message": message}, []

    # -- sharded-center plan checks ------------------------------------
    def _persist_plan_locked(self) -> None:
        """Write the adopted plan next to the journal (tmp + rename): a
        restarted shard refuses plan drift against this file, same
        authority rule as the recovered center."""
        if self._plan_path is None or self.shard_plan is None:
            return
        tmp = self._plan_path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump({"shard_index": self.shard_index,
                       "plan": self.shard_plan.to_dict()}, f)
        os.replace(tmp, self._plan_path)

    def _sharding_caps_locked(self) -> dict:
        """The ``sharding`` join-reply advertisement: this shard's identity
        plus the full plan (so a plan-less joiner — a promoted standby's
        first client, an observer — can adopt rather than guess)."""
        return {"index": self.shard_index, "count": self.shard_count,
                "plan_hash": self.shard_plan.plan_hash,
                "plan": self.shard_plan.to_dict()}

    def _check_shard_join_locked(self, header: dict,
                                 init: list) -> Optional[tuple]:
        """The sharded-center join contract (lock held). Every violation is
        the typed ``shard_plan`` error — a peer that cannot prove it holds
        THE plan never gets membership, so a partial-plan fold is
        structurally impossible (the silent-mis-fold failure class the
        hash exists to kill)."""
        claimed = header.get("shard_index")
        if self.shard_index is None:
            if claimed is not None:
                return self._err(
                    "shard_plan",
                    f"this server is not part of a sharded deployment but "
                    f"the join claims shard {claimed}")
            return None
        caps = header.get("caps")
        if not isinstance(caps, dict) or not caps.get("sharding"):
            return self._err(
                "shard_plan",
                "peer lacks the 'sharding' capability: pre-sharding build "
                "joining a shard server (upgrade the worker)")
        if claimed is None:
            return self._err(
                "shard_plan",
                f"join carries no shard_index; this is shard "
                f"{self.shard_index}/{self.shard_count} — dial it through "
                f"a sharded client, not a plain PSClient")
        if int(claimed) != self.shard_index:
            return self._err(
                "shard_plan",
                f"join claims shard {claimed} but this server is shard "
                f"{self.shard_index}/{self.shard_count}")
        got_hash = header.get("plan_hash")
        if self.shard_plan is None:
            # Empty shard meets its first client: adopt (then persist) the
            # plan the join carries — but only a REAL plan; "adopt" from
            # both sides means nobody holds one.
            plan_dict = header.get("shard_plan")
            if not isinstance(plan_dict, dict) or got_hash == "adopt":
                return self._err(
                    "shard_plan",
                    "server has no partition plan yet; join must carry "
                    "one (shard_plan + plan_hash)")
            from distkeras_tpu.netps.shards import plan as _plan_mod
            try:
                plan = _plan_mod.PartitionPlan.from_dict(plan_dict)
            except Exception as e:  # noqa: BLE001 - answered typed
                return self._err("shard_plan", f"malformed plan: {e}")
            if plan.num_shards != self.shard_count:
                return self._err(
                    "shard_plan",
                    f"plan has {plan.num_shards} shards, this deployment "
                    f"has {self.shard_count}")
            if got_hash != plan.plan_hash:
                return self._err(
                    "shard_plan",
                    f"plan_hash {str(got_hash)[:12]}... does not match the "
                    f"carried plan ({plan.plan_hash[:12]}...)")
            self.shard_plan = plan
            self._persist_plan_locked()
        elif got_hash != "adopt" and \
                got_hash != self.shard_plan.plan_hash:
            return self._err(
                "shard_plan",
                f"plan hash mismatch: yours {str(got_hash)[:12]}..., this "
                f"shard's {self.shard_plan.plan_hash[:12]}... — the "
                f"deployment was re-planned; rebuild or adopt")
        if init and self._center is None:
            want = self.shard_plan.shard_shapes(self.shard_index)
            got = [tuple(np.asarray(a).shape) for a in init]
            if got != want:
                return self._err(
                    "shard_plan",
                    f"init arrays do not match shard {self.shard_index}'s "
                    f"plan slice: got {got[:4]}..., want {want[:4]}...")
        return None

    def _purge_pending(self, wid: int, below_seq: Optional[int] = None,
                       ) -> None:
        """Drop stashed commit stripes for ``wid`` (lock held by caller):
        all of them on eviction/rejoin, or only seqs <= ``below_seq`` after
        a fold (a completed commit's stragglers are dedup's problem)."""
        for key in [k for k in self._pending
                    if k[0] == wid
                    and (below_seq is None or k[1] <= below_seq)]:
            del self._pending[key]

    def _op_join(self, header: dict, arrays: list) -> tuple[dict, list]:
        from distkeras_tpu import telemetry

        wid = header.get("worker_id")
        rejoin = False
        # The handler hands arrays over raw (wire dtype + spec, for the
        # compressed-domain commit fold); join inits are plain tensors, so
        # decoding here is a per-tensor passthrough.
        init = [decode_entry(a) for a in arrays]
        with self._lock:
            # A join never carries an epoch — it ADOPTS the server's (the
            # failover re-join is exactly a stale-lineage client arriving
            # here) — so only the fenced/standby half of the check applies.
            err = self._check_primary_locked({})
            if err is not None:
                return err
            if self._draining:
                return self._err("draining", "server is draining")
            shard_err = self._check_shard_join_locked(header, init)
            if shard_err is not None:
                return shard_err
            if wid is None:
                wid = (max(self._ever) + 1) if self._ever else 0
            wid = int(wid)
            rejoin = wid in self._ever and wid not in self._members
            if self._center is None and init:
                self._center = [np.array(a, np.float32) for a in init]
                if self._store is not None:
                    # First center this store has seen: anchor the journal
                    # with the base snapshot recovery will replay onto.
                    self._snapshot_locked()
            if self._center is None:
                return self._err(
                    "uninitialized",
                    "server has no center yet; join with init arrays")
            self._ever.add(wid)
            self._members[wid] = time.monotonic() + self.lease_s
            self._purge_pending(wid)  # a rejoin abandons half-sent stripes
            if rejoin:
                self.rejoins += 1
            center = [a.copy() for a in self._host_center_locked()]
            updates = self._updates
            last_seq = self._last_seq.get(wid, -1)
            sharding = (self._sharding_caps_locked()
                        if self.shard_index is not None else None)
        # A join may have just seeded the first center: seat it on device
        # before advertising the mesh bit (jax init outside the lock).
        self._ensure_mesh_folder()
        if rejoin:
            telemetry.counter("netps.rejoins").add(1)
            telemetry.event("netps_rejoin", {"worker": wid})
        # last_seq lets a RESTARTED worker process (fresh client, seq
        # counter back at -1) resume its sequence past what this server
        # already folded — without it, dedup would silently discard every
        # commit of the restarted incarnation forever. ``caps`` is the
        # data-plane negotiation: the client only compresses/stripes what
        # this reply advertises (a capability-less PR 4 reply keeps old
        # clients on the f32 single-connection dialect). A server actually
        # serving a ring replaces the static ``shm`` bit with its doorbell
        # endpoint + boot id — the client upgrades only on a boot-id match.
        caps = self._caps()
        if self._uds_path is not None and "shm" in caps:
            caps["shm"] = {"boot_id": self._boot_id, "uds": self._uds_path}
        if (self._mesh_token is not None and self._mesh_folder is not None
                and "mesh" in caps):
            # Same replace-the-static-bit pattern: the live advertisement
            # carries the in-process dispatch token plus the same-runtime
            # identity the client must match to upgrade.
            caps["mesh"] = {"proc": _mesh.local_mesh_id(),
                            "token": self._mesh_token,
                            "devices": self._mesh_folder.num_devices,
                            "backend": self._mesh_folder.backend}
        if sharding is not None:
            # A shard server replaces the static bit with its identity +
            # plan, the same pattern the shm upgrade uses.
            caps["sharding"] = sharding
        return ({"ok": True, "worker_id": wid, "updates": updates,
                 "lease_s": self.lease_s, "last_seq": last_seq,
                 "epoch": self.epoch, "caps": caps}, center)

    def _op_pull(self, header: dict, dialect: str = "") -> tuple[dict, list]:
        wid = header.get("worker_id")
        idx = header.get("idx")
        with self._lock:
            err = self._check_primary_locked(header)
            if err is not None:
                return err
            if header.get("want_plan") and self.shard_index is not None:
                # Membership-free plan fetch (the observer bootstrap): the
                # advertisement alone, no center payload, no lease.
                if self.shard_plan is None:
                    return self._err("uninitialized",
                                     "shard has no plan yet")
                return {"ok": True, "updates": self._updates,
                        "sharding": self._sharding_caps_locked()}, []
            if self._center is None:
                return self._err("uninitialized", "no center yet")
            if wid is not None:
                # Members renew their lease by pulling; an evicted worker
                # must rejoin first. wid=None is an anonymous observer pull
                # (the trainer fetching the final center) — no lease.
                if int(wid) not in self._members:
                    return self._err(
                        "lease_expired", f"worker {wid} is not a member")
                self._members[int(wid)] = time.monotonic() + self.lease_s
            host = self._host_center_locked()
            if idx is None:
                if dialect == ".mesh" and self._mesh_folder is not None:
                    # Zero-copy pull for the mesh dialect: while the
                    # center lives on device, the host mirror is only
                    # ever REPLACED wholesale (a fold drops it; demotion
                    # copies before adopting it) — never written in
                    # place — so same-process clients can read these
                    # rows directly. Pin that contract by freezing them;
                    # the wire dialects keep copying because their reply
                    # buffers outlive the lock inside a serializer.
                    for a in host:
                        a.flags.writeable = False
                    out = list(host)
                else:
                    out = [a.copy() for a in host]
            else:
                # One stripe of the center (striped pull). The reply echoes
                # the update counter; the client cross-checks counters over
                # its stripes and re-pulls a torn read.
                try:
                    out = [host[int(i)].copy() for i in idx]
                except (IndexError, TypeError, ValueError):
                    return self._err(
                        "protocol", f"bad pull stripe indices {idx!r}")
            reply = {"ok": True, "updates": self._updates}
            if self.shard_index is not None and self.shard_plan is not None:
                # Every pull re-proves the plan identity: a client that
                # kept running across a re-plan sees the hash change and
                # fails typed instead of assembling from two plans.
                reply["plan_hash"] = self.shard_plan.plan_hash
            return reply, out

    def _op_probe(self, header: dict, arrays: list) -> tuple[dict, list]:
        """The tuner's timed micro-A/B round trip (``CAPS["tuner"]``): pay
        the commit path's REAL decode cost — a quantized probe dequantizes
        exactly like a quantized commit — but never touch the fold, the
        journal, the dedup table, or membership. A probe can neither grant
        a lease nor consume a seq, so it is invisible to every
        exactly-once/fencing invariant."""
        from distkeras_tpu import telemetry

        t0 = time.monotonic()
        try:
            decoded = [np.asarray(decode_entry(a), np.float32)
                       for a in arrays]
        except (ProtocolError, TypeError, ValueError) as e:
            return self._err("protocol", f"bad probe payload: {e}")
        nbytes = sum(a.nbytes for a in decoded)
        decode_s = time.monotonic() - t0
        with self._lock:
            err = self._check_primary_locked(header)
            if err is not None:
                return err
            wid = header.get("worker_id")
            if wid is not None and int(wid) in self._members:
                # A member's probe renews its lease like any other round
                # trip; a non-member probing (pre-join A/B) is fine too —
                # probes never create membership.
                self._members[int(wid)] = time.monotonic() + self.lease_s
        telemetry.counter("netps.probes").add(1)
        return {"ok": True, "probe_bytes": nbytes,
                "decode_s": round(decode_s, 6)}, []

    def _op_commit(self, header: dict, arrays: list) -> tuple[dict, list]:
        from distkeras_tpu import telemetry

        wid = header.get("worker_id")
        seq = header.get("seq")
        pulled = header.get("pulled", 0)
        if wid is None or seq is None:
            return self._err("protocol", "commit requires worker_id and seq")
        wid, seq = int(wid), int(seq)
        num_shards = int(header.get("num_shards", 1) or 1)
        duplicate = pending = False
        # Validate specs BEFORE any bookkeeping or fold: a bad spec that
        # raised mid-fold under the lock would leave a partially-applied
        # delta the retransmit then double-folds.
        try:
            validate_delta(arrays)
        except ProtocolError as e:
            telemetry.counter("netps.protocol_errors").add(1)
            return self._err("protocol", str(e))
        # Queue-behind-fold: the wait for the center lock is the commit
        # path's contention segment — measured around the acquire (a
        # scope cannot wrap it) and emitted as a child of the request's
        # carried context (no-op untraced).
        tctx = _tracing.current()
        q_wall, q0 = time.time(), time.perf_counter()
        with self._lock:
            _tracing.emit("commit.queue", tctx, q_wall,
                          time.perf_counter() - q0, wid=wid, seq=seq)
            err = self._check_primary_locked(header)
            if err is not None:
                return err
            if self._draining:
                return self._err("draining", "server is draining")
            if wid not in self._members:
                return self._err(
                    "lease_expired", f"worker {wid} is not a member")
            if self._center is None:
                return self._err("uninitialized", "no center yet")
            self._members[wid] = time.monotonic() + self.lease_s
            if seq <= self._last_seq.get(wid, -1):
                # Retransmit after a lost ACK: already folded. Answering
                # applied=False (instead of re-folding) is the whole
                # exactly-once story — and with striping it covers a
                # retransmitted stripe of an already-assembled commit too.
                duplicate = True
                staleness = -1
            elif num_shards > 1:
                delta, err = self._stash_stripe(wid, seq, num_shards, header,
                                                arrays)
                if err is not None:
                    return err
                if delta is None:
                    pending = True  # more stripes to come; no fold yet
                    staleness = -1
                else:
                    staleness = self._fold_locked(wid, seq, pulled, delta)
            else:
                staleness = self._fold_locked(wid, seq, pulled, arrays)
            updates = self._updates
            mesh_folded = self._last_fold_mesh and not (duplicate or pending)
            demote_reason, self._mesh_demote_reason = \
                self._mesh_demote_reason, None
        if demote_reason:
            telemetry.counter("netps.mesh.demotions").add(1)
            telemetry.event("netps_mesh_demotion", {"why": demote_reason})
        if mesh_folded:
            telemetry.counter("netps.mesh.folds").add(1)
        if duplicate:
            telemetry.counter("netps.commits_deduped").add(1)
        elif not pending:
            telemetry.counter("netps.commits").add(1)
            n, dt = self._fold_stats
            if n and dt > 0:
                telemetry.gauge("netps.fold.tensors_per_sec").set(
                    round(n / dt, 1))
        return ({"ok": True, "applied": not (duplicate or pending),
                 "duplicate": duplicate, "pending": pending,
                 "updates": updates, "staleness": staleness}, [])

    def _fold_locked(self, wid: int, seq: int, pulled, delta: list) -> int:
        """The ONE fold (lock held): staleness from the counter rule, then
        ``fold_delta``, the exactly-once bookkeeping, and the durability
        tail — journal append (fold order IS journal order, which is why
        this stays under the lock), snapshot-when-due, the replication
        buffer, and the commit-log bound."""
        staleness = counter_staleness(self._updates, pulled)
        t0 = time.perf_counter()
        mesh_folded = False
        with _tracing.child_scope("commit.fold", wid=wid, seq=seq,
                                  staleness=staleness):
            folder = self._mesh_folder
            if folder is not None:
                try:
                    folder.fold(delta,
                                commit_scale(self.discipline, staleness))
                    mesh_folded = True
                except Exception as e:  # noqa: BLE001 - any failure demotes
                    # The collective program is functional — nothing
                    # mutated on a raise — so the host mirror is the
                    # pre-fold center and the numpy fold below applies
                    # this delta exactly once. COPY on adoption: the
                    # mirror's arrays are device_get views (read-only on
                    # CPU) and may be aliased by zero-copy mesh pull
                    # replies — the in-place numpy folds below need
                    # private writable buffers. Telemetry for the
                    # demotion is deferred past the lock (DK201). Caller
                    # holds self._lock (the `_locked` suffix contract).
                    self._center = [np.array(a) for a  # dk: disable=DK202
                                    in folder.center_host()]
                    self._mesh_folder = None  # dk: disable=DK202
                    self._mesh_failed = True
                    self._mesh_demote_reason = f"{type(e).__name__}: {e}"
                    folder.close()
            if not mesh_folded:
                fold_delta(self._center, delta, self.discipline, staleness)
        self._last_fold_mesh = mesh_folded
        self._fold_stats = (len(delta), time.perf_counter() - t0)
        u = self._updates
        self.commit_log.append((wid, seq, staleness))
        self._last_seq[wid] = seq
        self._updates += 1
        self.commits_total += 1
        self._purge_pending(wid, below_seq=seq)
        if self._repl_on:
            # Wire-form tail for the standby's `replicate` pulls. Entries
            # keep their frame buffers alive (bounded by the deque).
            rec = {"u": u, "wid": wid, "seq": seq,
                   "st": staleness, "e": self.epoch,
                   "n": self.commits_total,
                   "delta": list(delta)}
            ctx = _tracing.current()
            if ctx is not None:
                # The tail carries the trace id so the standby's apply
                # span joins the originating commit's trace.
                rec["tr"] = ctx.trace
            self._repl.append(rec)
        if self._store is not None:
            with _tracing.child_scope("commit.fsync", wid=wid, seq=seq):
                self._store.append(epoch=self.epoch, wid=wid, seq=seq,
                                   staleness=staleness, updates=u,
                                   commits_total=self.commits_total,
                                   delta=delta)
                if self._store.due(self._updates):
                    self._snapshot_locked()
        # Hard bound between snapshots (or without a store at all): a
        # month-long run must not grow an unbounded evidence list.
        self._trim_log_locked(2 * self._log_keep)
        return staleness

    def _trim_log_locked(self, threshold: int) -> None:
        """Drop the oldest commit-log entries back to the keep bound once
        the list reaches ``threshold`` (lock held) — the ONE place the
        ``len(commit_log) + dropped == commits_total`` invariant is
        maintained (fold path, snapshot compaction, the aggregator's
        absorb path, and the standby's replication all call in here)."""
        if len(self.commit_log) >= threshold > self._log_keep:
            drop = len(self.commit_log) - self._log_keep
            del self.commit_log[:drop]
            self._log_dropped += drop

    def _snapshot_locked(self) -> None:
        """Write one center snapshot + rotate/compact the journal (lock
        held; the store is deliberately telemetry-free under it — the
        dispatch layer exports ``netps.recovery.snapshots`` after release)
        and trim the in-memory commit log to its keep bound."""
        self._store.snapshot(center=self._host_center_locked(),
                             updates=self._updates,
                             last_seq=self._last_seq, epoch=self.epoch,
                             commits_total=self.commits_total)
        self.snapshots_written += 1
        self._trim_log_locked(self._log_keep + 1)

    def _check_primary_locked(self, header: dict):
        """The epoch fence (lock held): None when this server may serve
        the request, else the typed error reply. A fenced or
        not-yet-promoted server answers ``not_primary`` (the client walks
        its endpoint list); a request from a STALE epoch answers
        ``epoch_fenced`` (the client re-joins and adopts the new lineage);
        a request from a HIGHER epoch is proof somebody promoted past this
        server — it fences itself on the spot, so a zombie primary can
        never fold again even if the promotion's ``fence`` op was lost."""
        if self._not_primary:
            return self._err("not_primary", "warm standby, not promoted")
        epoch = header.get("epoch")
        if epoch is not None and int(epoch) > self.epoch and not self._fenced:
            # Caller holds the center lock (every _op_* takes it before
            # calling in) — lexically outside the `with`, hence the
            # suppression, but the witness test covers the pair live.
            self._fenced = True  # dk: disable=DK202
            if self._store is not None:
                self._store.write_epoch(int(epoch), fenced=True)
        if self._fenced:
            return self._err("not_primary",
                             f"fenced ex-primary (epoch {self.epoch})")
        if epoch is not None and int(epoch) < self.epoch:
            return self._err(
                "epoch_fenced",
                f"request epoch {int(epoch)} predates server epoch "
                f"{self.epoch}: re-join the promoted primary")
        return None

    def _stash_stripe(self, wid: int, seq: int, num_shards: int,
                      header: dict, arrays: list):
        """Stash one commit stripe (lock held). Returns ``(delta, None)``
        with the fully assembled tensor list once the LAST stripe lands,
        ``(None, None)`` while stripes are outstanding, or ``(None, error
        reply)`` on malformed stripe metadata."""
        idx = header.get("idx")
        if idx is None:
            return None, self._err(
                "protocol", "striped commit requires stripe indices")
        try:
            idx = tuple(int(i) for i in idx)
        except (TypeError, ValueError):
            return None, self._err("protocol", f"bad stripe indices {idx!r}")
        if len(idx) != len(arrays):
            return None, self._err(
                "protocol",
                f"stripe declares {len(idx)} tensors, carries {len(arrays)}")
        pend = self._pending.setdefault((wid, seq), {})
        pend[int(header.get("shard", 0))] = (idx, list(arrays))
        if len(pend) < num_shards:
            return None, None
        total = sum(len(ix) for ix, _ in pend.values())
        delta: list = [None] * total
        for ix, arrs in pend.values():
            for i, a in zip(ix, arrs):
                if not 0 <= i < total or delta[i] is not None:
                    del self._pending[(wid, seq)]
                    return None, self._err(
                        "protocol",
                        f"inconsistent stripe set for ({wid}, {seq})")
                delta[i] = a
        del self._pending[(wid, seq)]
        if any(d is None for d in delta):
            return None, self._err(
                "protocol", f"stripe set for ({wid}, {seq}) has holes")
        return delta, None

    def _op_heartbeat(self, header: dict) -> tuple[dict, list]:
        wid = header.get("worker_id")
        if wid is None:
            return self._err("protocol", "heartbeat requires worker_id")
        with self._lock:
            err = self._check_primary_locked(header)
            if err is not None:
                return err
            if int(wid) not in self._members:
                return self._err(
                    "lease_expired", f"worker {wid} is not a member")
            self._members[int(wid)] = time.monotonic() + self.lease_s
            return {"ok": True, "updates": self._updates}, []

    def _op_leave(self, header: dict) -> tuple[dict, list]:
        wid = header.get("worker_id")
        with self._lock:
            if wid is not None:
                self._members.pop(int(wid), None)
        return {"ok": True}, []

    def _op_stats(self, header: dict) -> tuple[dict, list]:
        """Live telemetry scrape over the wire (``python -m
        distkeras_tpu.telemetry scrape host:port``): the process's
        counters/gauges/span aggregates plus the flight ring's most
        recent records, with ``caps`` echoed so an observer can probe
        capabilities without joining. Deliberately NOT behind the primary
        check — a standby or fenced ex-primary is exactly the process a
        postmortem wants to scrape — and it never touches membership,
        leases, the dedup table, or the fold."""
        from distkeras_tpu import telemetry
        from distkeras_tpu.telemetry.tracing import ring_head

        n = max(0, int(header.get("ring", 64) or 0))
        with self._lock:
            extra = {"updates": self._updates, "epoch": self.epoch,
                     "members": len(self._members),
                     "commits_total": self.commits_total,
                     "draining": self._draining,
                     # Readiness contract for the health plane: a primary
                     # that can take commits. Standbys answer stats (the
                     # whole point of the membership-free op) but report
                     # not-ready until promoted; fenced/draining likewise.
                     "ready": (not self._draining and not self._fenced
                               and not self._not_primary),
                     # Which arithmetic actually folds commits right now:
                     # a live device-resident center reports "mesh"; every
                     # other server folds on the host in numpy.
                     "fold_backend": ("mesh" if self._mesh_folder is not None
                                      else "numpy")}
        # The ring rides the JSON header: round-trip through json with a
        # str fallback first — event fields may carry non-JSON scalars,
        # and a scrape must never poison the reply frame.
        ring = json.loads(json.dumps(ring_head(n), default=str))
        return ({"ok": True, "caps": dict(wire.CAPS),
                 "role": _tracing.role(),
                 "snapshot": telemetry.get().snapshot(),
                 "ring": ring, **extra}, [])

    def _caps(self) -> dict:
        """The static capability set a join reply starts from. An
        aggregation-tree node overrides this to replace the ``tree`` bit
        with its level/group identity (the same replace-the-static-bit
        pattern the shm and sharding upgrades use below)."""
        return dict(wire.CAPS)

    def _repl_cursor_locked(self) -> int:
        """The fold index replication advances by (lock held): the center
        update counter here. An aggregation-tree node overrides this with
        its absorb cursor — its counter mirrors the ROOT lineage and only
        moves on re-pull, so it cannot index the journal its standby
        tails."""
        return self._updates

    def _op_replicate(self, header: dict) -> tuple[dict, list]:
        """One pull of the journal stream by a warm standby: ``u`` is the
        next fold index the standby needs. Answers a batch of journal
        records in wire form (``mode=records``; each record header carries
        its array count ``k``, the deltas ride flattened), or — when the
        standby is fresh (``u < 0``), behind the replication tail, or has
        a gap — one full state sync (``mode=snapshot``). Served during
        drain: a draining primary must still let its standby catch up."""
        u = int(header.get("u", -1))
        with self._lock:
            if self._not_primary or self._fenced:
                return self._err(
                    "not_primary", "cannot replicate from a non-primary")
            if self._center is None:
                return self._err("uninitialized", "no center yet")
            # First replicate turns the tail buffer on; until a standby
            # exists no deployment pays its memory.
            self._repl_on = True
            cursor = self._repl_cursor_locked()
            recs = [r for r in self._repl if r["u"] >= u]
            if u == cursor:
                recs = []
            elif u < 0 or u > cursor or not recs or recs[0]["u"] != u:
                # Fresh standby / behind the tail / gap — or a standby
                # AHEAD of this primary (a cold restart lost the journal
                # tail the standby had already replicated): the primary's
                # durable state is the authoritative lineage, so the
                # answer is always one full state sync the standby adopts
                # wholesale. The lost commits' workers were ACKed and
                # never retransmit — the standard lost-window semantics,
                # never a divergent fold.
                hdr = {"ok": True, "mode": "snapshot",
                       "updates": cursor, "epoch": self.epoch,
                       "lineage": self.lineage,
                       "commits_total": self.commits_total,
                       "last_seq": {str(k): int(v)
                                    for k, v in self._last_seq.items()}}
                return hdr, [a.copy() for a in self._host_center_locked()]
            recs = recs[:_REPL_BATCH]
            headers = []
            for r in recs:
                h = {"u": r["u"], "wid": r["wid"], "seq": r["seq"],
                     "st": r["st"], "e": r["e"], "n": r["n"],
                     "k": len(r["delta"])}
                if "tr" in r:
                    h["tr"] = r["tr"]
                headers.append(h)
            out: list = []
            for r in recs:
                out.extend(r["delta"])
            return ({"ok": True, "mode": "records", "records": headers,
                     "updates": cursor, "epoch": self.epoch,
                     "lineage": self.lineage}, out)

    def _op_fence(self, header: dict) -> tuple[dict, list]:
        """A promoted standby fencing the old lineage: an epoch strictly
        above ours means we are the zombie — stop folding forever. An
        epoch at or below ours is the *fencer* being stale (it is the
        zombie); refuse with the typed fence error."""
        try:
            epoch = int(header["epoch"])
        except (KeyError, TypeError, ValueError):
            return self._err("protocol", "fence requires an integer epoch")
        with self._lock:
            if epoch > self.epoch:
                self._fenced = True
                if self._store is not None:
                    # Durable: a fenced-then-restarted ex-primary comes
                    # back refusing to fold, not serving the old epoch.
                    self._store.write_epoch(epoch, fenced=True)
                return {"ok": True, "fenced": True, "epoch": epoch}, []
            return self._err(
                "epoch_fenced",
                f"fence epoch {epoch} does not exceed server epoch "
                f"{self.epoch}")


def serve(center: Optional[Sequence[np.ndarray]] = None,
          discipline: str = "adag", host: str = "127.0.0.1",
          port: int = 0, lease_s: Optional[float] = None) -> PSServer:
    """Construct + start a :class:`PSServer` (tests and the CLI)."""
    return PSServer(center, discipline=discipline, host=host, port=port,
                    lease_s=lease_s).start()
