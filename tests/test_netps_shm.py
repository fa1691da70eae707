"""The same-host fast path and hierarchical folds: shm ring dialect
negotiation (boot-id check + caps fallback matrix), ring-level chaos,
exactly-once/eviction guarantees on the ring, compressed-domain folds,
and the per-host aggregator's flat-topology parity."""

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from distkeras_tpu.netps import (
    AggregatorServer,
    PSClient,
    PSServer,
)
from distkeras_tpu.netps import shm, wire
from distkeras_tpu.netps import fold as netfold
from distkeras_tpu.resilience import faults
from distkeras_tpu.resilience.faults import FaultPlan

FAST = dict(timeout=1.0, retries=3, backoff=0.01)


def leaves(*shapes):
    rng = np.random.default_rng(0)
    return [rng.normal(size=s).astype(np.float32) for s in shapes]


def shm_pair(**kw):
    srv = PSServer(discipline=kw.pop("discipline", "adag"),
                   lease_s=kw.pop("lease_s", None), transport="shm").start()
    client_kw = dict(FAST)
    client_kw.update(kw)
    return srv, PSClient(srv.endpoint, worker_id=0, transport="shm",
                         **client_kw)


# ---------------------------------------------------------------------------
# Ring dialect: negotiation + roundtrip
# ---------------------------------------------------------------------------

def test_shm_join_pull_commit_roundtrip_and_transport_label():
    from distkeras_tpu import telemetry

    telemetry.reset()
    srv, c = shm_pair()
    try:
        init = leaves((3, 2), (4,))
        center, upd = c.join(init=init)
        assert c.active_transport == "shm"
        for a, b in zip(center, init):
            np.testing.assert_array_equal(a, b)
        res = c.commit([np.ones_like(a) for a in init], upd)
        assert res.applied and res.staleness == 0
        center2, upd2 = c.pull()
        assert upd2 == 1
        np.testing.assert_allclose(center2[0], init[0] + 1.0)
        assert c.heartbeat() == 1
        snap = telemetry.get().snapshot()
        # RPC + server spans carry the transport dialect label (join went
        # over TCP — negotiation precedes the upgrade).
        assert snap["spans"]["netps.rpc.commit.shm"]["count"] == 1
        assert snap["spans"]["netps.server.commit.shm"]["count"] == 1
        assert snap["spans"]["netps.rpc.join"]["count"] == 1
        # the commit exported the fold-throughput gauge
        assert snap["gauges"]["netps.fold.tensors_per_sec"]["value"] > 0
        c.leave()
    finally:
        c.close()
        srv.close()
        telemetry.reset()


def test_shm_striped_commit_keeps_exactly_once():
    srv, c = shm_pair(discipline="downpour", shards=2)
    try:
        init = leaves((40, 3), (7,), (90,))
        _, upd = c.join(init=init)
        assert c.active_transport == "shm" and c.active_shards == 2
        res = c.commit([np.full_like(a, 2.0) for a in init], upd)
        assert res.applied
        center, _ = c.pull()
        for a, i in zip(center, init):
            np.testing.assert_allclose(a, i + 2.0)
        assert srv.commit_log == [(0, 0, 0)]
    finally:
        c.close()
        srv.close()


def test_shm_retransmit_is_deduped():
    """The ring's exactly-once half: a hand-crafted retransmit of an
    already-folded seq over the ring is answered by dedup, not re-folded."""
    srv, c = shm_pair()
    try:
        _, upd = c.join(init=[np.zeros(3, np.float32)])
        assert c.commit([np.ones(3, np.float32)], upd).applied
        hdr, _ = c._rpc("commit", {"seq": 0, "pulled": 0},
                        [np.ones(3, np.float32)])
        assert hdr["duplicate"] is True
        assert srv.commit_log == [(0, 0, 0)]
        np.testing.assert_allclose(srv.center()[0], 1.0)
    finally:
        c.close()
        srv.close()


# ---------------------------------------------------------------------------
# Caps-negotiation fallback matrix: everything lands on TCP, silently
# ---------------------------------------------------------------------------

def test_new_client_old_server_falls_back_to_tcp(monkeypatch):
    """A PR 5 server advertises no shm endpoint: the shm-requesting client
    must speak TCP with every guarantee intact."""
    monkeypatch.setattr(wire, "CAPS",
                        {"codecs": list(wire.CODECS), "striping": True})
    srv = PSServer(discipline="adag").start()  # tcp: no ring listener
    try:
        with PSClient(srv.endpoint, worker_id=0, transport="shm",
                      **FAST) as c:
            init = leaves((8,))
            _, upd = c.join(init=init)
            assert c.active_transport == "tcp" and c.shm_info is None
            assert c.commit([np.ones(8, np.float32)], upd).applied
            center, _ = c.pull()
            np.testing.assert_allclose(center[0], init[0] + 1.0)
    finally:
        srv.close()


def test_old_client_new_server_stays_on_tcp():
    """A tcp-mode client against a ring-serving server ignores the shm
    advert entirely (the PR 4/PR 5 client behavior: unknown caps keys are
    just ignored)."""
    srv = PSServer(discipline="adag", transport="shm").start()
    try:
        with PSClient(srv.endpoint, worker_id=0, transport="tcp",
                      **FAST) as c:
            init = leaves((8,))
            _, upd = c.join(init=init)
            assert c.active_transport == "tcp"
            assert c.commit([np.ones(8, np.float32)], upd).applied
    finally:
        srv.close()


def test_cross_host_boot_id_mismatch_falls_back_to_tcp(monkeypatch):
    """Boot ids disagree (a cross-host pair that both set
    DKTPU_NET_TRANSPORT=shm): the client must silently stay on TCP."""
    srv = PSServer(discipline="adag", transport="shm").start()
    # The server snapshotted its boot id at construction; patching the
    # module now changes only what the CLIENT computes for the check.
    monkeypatch.setattr(shm, "local_boot_id", lambda: "some-other-host")
    try:
        with PSClient(srv.endpoint, worker_id=0, transport="shm",
                      **FAST) as c:
            init = leaves((8,))
            _, upd = c.join(init=init)
            assert c.active_transport == "tcp" and c.shm_info is None
            assert c.commit([np.ones(8, np.float32)], upd).applied
            center, _ = c.pull()
            np.testing.assert_allclose(center[0], init[0] + 1.0)
    finally:
        srv.close()


def test_invisible_uds_path_falls_back_to_tcp(monkeypatch):
    """Colocated containers share a boot id but not a mount namespace: an
    advertised doorbell path this process cannot see must keep the client
    on TCP instead of burning retries on an unconnectable socket."""
    srv = PSServer(discipline="adag", transport="shm").start()
    monkeypatch.setattr(shm, "endpoint_visible", lambda path: False)
    try:
        with PSClient(srv.endpoint, worker_id=0, transport="shm",
                      **FAST) as c:
            _, upd = c.join(init=leaves((8,)))
            assert c.active_transport == "tcp" and c.shm_info is None
            assert c.commit([np.ones(8, np.float32)], upd).applied
    finally:
        srv.close()


def test_dead_ring_endpoint_falls_back_to_tcp():
    """A ring endpoint that stops answering (server restarted TCP-only,
    segment dir wiped) must not wedge the client: after two consecutive
    ring failures the call falls back to TCP — which the server always
    serves — instead of burning the whole retry budget on the doorbell."""
    srv, c = shm_pair(timeout=0.3, retries=4)
    try:
        _, upd = c.join(init=[np.zeros(3, np.float32)])
        assert c.active_transport == "shm"
        # Simulate the endpoint dying: point the negotiated info at a
        # socket nobody serves and drop the live connections.
        c.shm_info = dict(c.shm_info, uds=c.shm_info["uds"] + ".gone")
        for conn in c._conns:
            c._disconnect(conn)
        center, _ = c.pull()  # succeeds over TCP within the retry budget
        np.testing.assert_array_equal(center[0], np.zeros(3))
        assert c.active_transport == "tcp"
    finally:
        c.close()
        srv.close()


def test_unknown_transport_is_typed_error():
    with pytest.raises(ValueError, match="transport"):
        PSClient("h:1", transport="carrier-pigeon")
    with pytest.raises(ValueError, match="transport"):
        PSServer(transport="carrier-pigeon")


# ---------------------------------------------------------------------------
# Ring-level chaos: shm_delay / shm_corrupt
# ---------------------------------------------------------------------------

def test_shm_corrupt_is_survived_and_folds_exactly_once():
    """THE ring chaos scenario: the commit's slot crc is flipped after the
    write (``shm_corrupt``), the server rejects the frame and tears the
    connection down, the client reconnects with FRESH segments and
    retransmits under the same seq — one fold."""
    srv, c = shm_pair(timeout=0.4, retries=5)
    try:
        _, upd = c.join(init=[np.zeros(3, np.float32)])
        shm.reset_frames()
        faults.set_net_plan(FaultPlan.parse_net("shm_corrupt@0"))
        res = c.commit([np.ones(3, np.float32)], upd)
        assert res.applied or res.duplicate
        assert srv.commit_log == [(0, 0, 0)], srv.commit_log
        np.testing.assert_allclose(srv.center()[0], 1.0)  # folded ONCE
        assert c.active_transport == "shm"  # recovered on the ring
    finally:
        faults.set_net_plan(None)
        faults.reset()
        c.close()
        srv.close()


def test_shm_delay_is_ridden_out():
    srv, c = shm_pair(timeout=1.0, retries=3)
    try:
        _, upd = c.join(init=[np.zeros(3, np.float32)])
        shm.reset_frames()
        faults.set_net_plan(FaultPlan.parse_net("shm_delay@0:0.2"))
        t0 = time.monotonic()
        center, _ = c.pull()
        assert time.monotonic() - t0 >= 0.2
        np.testing.assert_array_equal(center[0], np.zeros(3))
    finally:
        faults.set_net_plan(None)
        faults.reset()
        c.close()
        srv.close()


# ---------------------------------------------------------------------------
# Leases / eviction / rejoin on the ring
# ---------------------------------------------------------------------------

def test_shm_lease_eviction_and_rejoin():
    srv, c = shm_pair(lease_s=0.3)
    try:
        _, upd = c.join(init=[np.zeros(3, np.float32)])
        assert c.commit([np.ones(3, np.float32)], upd).applied
        deadline = time.monotonic() + 5.0
        while srv.members() and time.monotonic() < deadline:
            time.sleep(0.05)
        assert srv.members() == [] and srv.evictions == 1
        center, _ = c.pull()  # transparently re-joins, still on the ring
        assert c.rejoin_count == 1 and srv.rejoins == 1
        assert c.active_transport == "shm"
        np.testing.assert_allclose(center[0], 1.0)
    finally:
        c.close()
        srv.close()


def test_shm_close_joins_every_thread():
    before = {t.name for t in threading.enumerate()}
    srv, c = shm_pair()
    c.join(init=[np.zeros(2, np.float32)])
    c.pull()
    c.close()
    srv.close()
    after = {t.name for t in threading.enumerate()}
    lingering = [n for n in after - before if n.startswith("netps-")]
    assert not lingering, lingering


def test_dead_ring_with_zero_retries_falls_back_on_next_rpc():
    """A fail-fast client (retries=0) whose ring endpoint died must not
    ride the dead ring forever: the final (= only) attempt engages the
    TCP fallback, so THIS rpc fails but the next one lands on TCP."""
    from distkeras_tpu.netps.errors import NetPSError

    srv, c = shm_pair(retries=0, timeout=0.5)
    try:
        _, upd = c.join(init=[np.zeros(3, np.float32)])
        c.pull()
        assert c.active_transport == "shm"
        for conn in c._conns:  # kill the ring: dead doorbell endpoint
            c._disconnect(conn)
        c.shm_info = dict(c.shm_info, uds="/nonexistent-dknetps.sock")
        with pytest.raises(NetPSError):
            c.pull()
        assert c.shm_info is None  # fallback engaged on the final attempt
        center, _ = c.pull()  # and the next rpc speaks TCP
        assert c.active_transport == "tcp"
        np.testing.assert_array_equal(center[0], np.zeros(3))
    finally:
        c.close()
        srv.close()


def test_concurrent_shm_clients_isolated_and_one_ring_dies_mid_call():
    """Two frontends (clients) attached to ONE PS host over the ring: each
    client owns its own slot pair, so concurrent commits from both can
    never interleave inside a frame (per-client slot isolation — the
    center ends at the exact sum of both streams, exactly-once intact);
    and when ONE client's ring dies mid-call, that client alone falls back
    to TCP while the sibling keeps speaking shm — ring death is a
    per-connection event, not a host event."""
    srv = PSServer(discipline="downpour", transport="shm").start()
    c0 = PSClient(srv.endpoint, worker_id=0, transport="shm", **FAST)
    c1 = PSClient(srv.endpoint, worker_id=1, transport="shm", **FAST)
    try:
        init = [np.zeros(5, np.float32)]
        _, upd0 = c0.join(init=init)
        _, upd1 = c1.join(init=init)
        assert c0.active_transport == "shm" and c1.active_transport == "shm"

        commits_each = 8
        errs: list = []

        def pump(client, upd, delta):
            try:
                u = upd
                for _ in range(commits_each):
                    res = client.commit([np.full(5, delta, np.float32)], u)
                    assert res.applied
                    _, u = client.pull()
            except Exception as e:  # noqa: BLE001 - surfaced below
                errs.append(e)

        t0 = threading.Thread(target=pump, args=(c0, upd0, 1.0))
        t1 = threading.Thread(target=pump, args=(c1, upd1, 10.0))
        t0.start()
        t1.start()
        t0.join()
        t1.join()
        assert not errs, errs
        center, _ = c0.pull()
        # Slot isolation: both streams folded exactly once each — any
        # cross-client frame interleave would break this exact total.
        np.testing.assert_allclose(
            center[0], commits_each * 1.0 + commits_each * 10.0)
        assert {wid for wid, _s, _t in srv.commit_log} == {0, 1}

        # Kill ONLY c0's ring mid-flight: its next rpc rides the retry
        # budget onto TCP; c1 stays on shm untouched.
        for conn in c0._conns:
            c0._disconnect(conn)
        c0.shm_info = dict(c0.shm_info, uds="/nonexistent-dknetps.sock")
        center0, _ = c0.pull()  # retried onto TCP inside the budget
        assert c0.active_transport == "tcp"
        np.testing.assert_allclose(center0[0], 88.0)
        center1, _ = c1.pull()
        assert c1.active_transport == "shm", \
            "the sibling's ring must survive its neighbor's death"
        np.testing.assert_allclose(center1[0], 88.0)
    finally:
        c0.close()
        c1.close()
        srv.close()


def test_accept_attach_closes_fds_when_slot_ctor_raises(monkeypatch):
    """A Slot ctor failure (e.g. mmap ENOMEM under memory pressure) mid
    attach must close BOTH received fds — each failed attach would
    otherwise leak 2 fds + a mapping until the server hits EMFILE."""
    import os
    import socket as pysock

    a, b = pysock.socketpair(pysock.AF_UNIX, pysock.SOCK_STREAM)
    s1, s2 = shm.create_slot(), shm.create_slot()
    try:
        pysock.send_fds(a, [b"DKATTACH"], [s1.fd, s2.fd])
        real = shm.Slot
        calls = []

        def second_ctor_raises(fd, size=None):
            if calls:
                raise OSError("synthetic ENOMEM")
            calls.append(1)
            return real(fd, size)

        monkeypatch.setattr(shm, "Slot", second_ctor_raises)
        before = len(os.listdir("/proc/self/fd"))
        with pytest.raises(OSError):
            shm.accept_attach(b)
        assert len(os.listdir("/proc/self/fd")) == before
    finally:
        s1.close()
        s2.close()
        a.close()
        b.close()


def test_slot_ops_after_close_raise_retryable_taxonomy():
    """The shm->TCP fallback closes EVERY connection's ring, including one
    a sibling stripe thread is mid-operation on: ops on a closed slot must
    raise ConnectionError (which ``_rpc`` retries) — never the raw mmap
    ``ValueError``, which would escape the retry loop and kill the worker."""
    slot = shm.create_slot()
    slot.write_frame(wire.KIND_REQUEST, {"op": "x"})
    slot.close()
    slot.close()  # idempotent
    with pytest.raises(ConnectionError):
        slot.write_frame(wire.KIND_REQUEST, {"op": "x"})
    with pytest.raises(ConnectionError):
        slot.read_frame(wire.PREFIX_SIZE + 8)
    with pytest.raises(ConnectionError):
        slot.corrupt_crc()


# ---------------------------------------------------------------------------
# Compressed-domain folds
# ---------------------------------------------------------------------------

def test_compressed_domain_fold_matches_decode_then_fold_within_quant_step():
    """The server folds int8 deltas without a decode-to-f32 pass; K
    error-feedback commits must land within one quantization step of the
    decode-then-fold reference (the PR 5 acceptance bound, now hit through
    the fused path)."""
    K = 20
    base = (np.random.default_rng(3).normal(size=(64,)) * 0.01
            ).astype(np.float32)
    srv = PSServer(discipline="downpour").start()
    try:
        with PSClient(srv.endpoint, worker_id=0, compress="int8",
                      **FAST) as c:
            _, upd = c.join(init=[np.zeros(64, np.float32)])
            assert c.codec == "int8"
            for _ in range(K):
                _, upd = c.pull()
                c.commit([base], upd)
            center, _ = c.pull()
        one_step = float(np.abs(base).max()) / 127.0
        drift = float(np.abs(center[0] - K * base).max())
        assert drift <= 1.5 * one_step, (drift, one_step)
    finally:
        srv.close()


def test_bad_join_init_spec_is_counted_teardown_not_thread_death():
    """A join whose init arrays carry a bad codec spec reaches
    decode_entry only now that handlers read frames decode=False: the TCP
    handler must count it and tear the connection down (like the shm
    handler's outer guard) — not die with an unhandled traceback. The
    server must keep serving afterward."""
    from distkeras_tpu import telemetry
    from distkeras_tpu.netps.errors import NetPSError

    telemetry.reset()
    srv = PSServer(discipline="adag").start()
    try:
        with pytest.raises(NetPSError):
            with PSClient(srv.endpoint, worker_id=0, timeout=0.3,
                          retries=1, backoff=0.01) as bad:
                bad._rpc("join", {},
                         [(np.ones(2, np.int8), {"codec": "xyz"})])
        snap = telemetry.get().snapshot()
        assert snap["counters"]["netps.protocol_errors"] >= 1
        with PSClient(srv.endpoint, worker_id=1, **FAST) as ok:
            _, upd = ok.join(init=[np.zeros(2, np.float32)])
            assert ok.commit([np.ones(2, np.float32)], upd).applied
    finally:
        srv.close()
        telemetry.reset()


def test_shm_upgrade_is_not_counted_as_reconnect():
    """The routine post-join TCP->ring upgrade on a healthy run must land
    in netps.shm_upgrades, not netps.reconnects (documented as failure
    evidence); a genuine ring re-attach still counts as a reconnect."""
    from distkeras_tpu import telemetry

    telemetry.reset()
    srv, c = shm_pair()
    try:
        _, upd = c.join(init=[np.zeros(3, np.float32)])
        c.pull()  # first ring attach = the upgrade
        snap = telemetry.get().snapshot()["counters"]
        assert snap.get("netps.reconnects", 0) == 0
        assert snap["netps.shm_upgrades"] == 1
        shm.reset_frames()
        faults.set_net_plan(FaultPlan.parse_net("shm_corrupt@0"))
        assert c.commit([np.ones(3, np.float32)], upd).applied
        snap = telemetry.get().snapshot()["counters"]
        assert snap["netps.reconnects"] >= 1  # ring re-attach IS evidence
    finally:
        faults.set_net_plan(None)
        faults.reset()
        c.close()
        srv.close()
        telemetry.reset()


def test_bad_codec_spec_is_typed_error_and_never_partially_folds():
    """The decode=False path must not lose the wire layer's spec
    validation: an unknown codec or a scale-less int8 spec is answered
    with the typed protocol error BEFORE any fold or bookkeeping — a
    mid-fold failure would leave the commit's earlier tensors applied
    with no commit_log entry, and the retransmit would fold them twice.
    A scale-less spec must also never silently fold as zero."""
    from distkeras_tpu.netps.errors import ProtocolError

    srv = PSServer(discipline="adag").start()
    try:
        with PSClient(srv.endpoint, worker_id=0, **FAST) as c:
            _, upd = c.join(init=[np.zeros(3, np.float32),
                                  np.zeros(2, np.float32)])
            good = np.ones(3, np.float32)
            for bad in ({"codec": "xyz"}, {"codec": "int8"},
                        {"codec": "int8", "scale": "nan-ish"}):
                with pytest.raises(ProtocolError):
                    c._rpc("commit", {"seq": 0, "pulled": int(upd)},
                           [good, (np.ones(2, np.int8), bad)])
            assert srv.commit_log == []  # nothing folded, nothing logged
            np.testing.assert_array_equal(srv.center()[0], 0.0)
            # seq 0 is still virgin: the valid retransmit folds exactly once
            res = c.commit([good, np.full(2, 2.0, np.float32)], upd)
            assert res.applied
            assert srv.commit_log == [(0, 0, 0)]
            np.testing.assert_allclose(srv.center()[0], 1.0)
    finally:
        srv.close()


_NO_BACKEND_SERVER = """
import numpy as np
import jax  # imported, as the package does anyway -- but never initialized
from jax._src import xla_bridge
from distkeras_tpu.netps import PSClient, PSServer
from distkeras_tpu.telemetry import vitals

srv = PSServer(discipline="downpour").start()
try:
    with PSClient(srv.endpoint, worker_id=0, compress="int8", timeout=5.0,
                  retries=3, backoff=0.01) as c:
        _, upd = c.join(init=[np.zeros(8, np.float32)])
        assert c.commit([np.full(8, 0.5, np.float32)], upd).applied
        stats = c.stats(ring=0)
    vitals.sample_vitals()
    assert stats["fold_backend"] == "numpy", stats["fold_backend"]
    assert abs(float(srv.center()[0][0]) - 0.5) < 0.01, srv.center()
    assert not xla_bridge.backends_are_initialized(), list(xla_bridge._backends)
finally:
    srv.close()
print("NO_BACKEND_OK")
"""


def test_codec_commit_initializes_no_jax_backend_in_server_process():
    """A chip belongs to one process. A parameter server runs beside the
    trainers that own the chips, so handling a codec'd commit -- the path
    that used to probe ``jax.default_backend()`` for a Pallas fold -- and
    sampling vitals must leave jax's backends uninitialized: folds are
    numpy unless the server was constructed as ``transport="mesh"``."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", _NO_BACKEND_SERVER],
                          cwd=repo, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "NO_BACKEND_OK" in proc.stdout


def test_fold_delta_accepts_wire_pairs_and_matches_plain():
    """One fold, two entry forms: (array, spec) wire pairs fold to the
    same center (within a quant step) as pre-decoded plain arrays."""
    rng = np.random.default_rng(1)
    d = (rng.normal(size=(33, 5)) * 0.01).astype(np.float32)
    for codec in ("int8", "bf16"):
        enc, spec = wire.codec_encode(d, codec)
        dec = wire.codec_decode(enc, spec)
        plain = [np.zeros_like(d)]
        paired = [np.zeros_like(d)]
        netfold.fold_delta(plain, [dec], "adag", 0)
        netfold.fold_delta(paired, [(enc, spec)], "adag", 0)
        np.testing.assert_allclose(paired[0], plain[0], atol=1e-6)
    # dynsgd's staleness scale applies in the compressed domain too
    enc, spec = wire.codec_encode(d, "int8")
    c = [np.zeros_like(d)]
    netfold.fold_delta(c, [(enc, spec)], "dynsgd", 1)
    np.testing.assert_allclose(c[0], 0.5 * wire.codec_decode(enc, spec),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# Hierarchical two-level folds
# ---------------------------------------------------------------------------

def test_hier_matches_flat_topology_exactly():
    """Scale-1 disciplines: folding the aggregator's combined commit at
    the root produces the SAME center as folding each worker commit flat
    — additivity makes the topologies equivalent."""
    init = [np.zeros(6, np.float32), np.zeros((2, 2), np.float32)]
    deltas = [leaves((6,), (2, 2)) for _ in range(3)]
    flat = PSServer(discipline="adag").start()
    root = PSServer(discipline="adag").start()
    try:
        with PSClient(flat.endpoint, worker_id=0, **FAST) as fc:
            _, u = fc.join(init=[a.copy() for a in init])
            for d in deltas:
                fc.commit(d, u)
        agg = AggregatorServer(upstream=root.endpoint,
                               init=[a.copy() for a in init],
                               discipline="adag", fan_in=3, **FAST)
        agg.start()
        clients = [PSClient(agg.endpoint, worker_id=w, **FAST)
                   for w in range(3)]
        try:
            pulls = [c.join()[1] for c in clients]
            for c, d, u in zip(clients, deltas, pulls):
                assert c.commit(d, u).applied
        finally:
            for c in clients:
                c.close()
            agg.close()
        for a, b in zip(flat.center(), root.center()):
            np.testing.assert_allclose(a, b, rtol=1e-6)
        # Root ingress cut by the fan-in: 3 worker commits -> 1 combined.
        assert len(root.commit_log) == 1 and agg.absorbed == 3
        assert len(flat.commit_log) == 3
    finally:
        flat.close()
        root.close()


def test_hier_combined_commit_staleness_is_min_pulled():
    """The combined commit's pull counter is the MIN of its constituents':
    the root charges it the staleness of the oldest constituent — the
    conservative reading of the existing counter rule."""
    root = PSServer(discipline="dynsgd").start()
    try:
        # Advance the root counter by 2 through a direct worker first.
        with PSClient(root.endpoint, worker_id=7, **FAST) as direct:
            _, u = direct.join(init=[np.zeros(4, np.float32)])
            direct.commit([np.ones(4, np.float32)], u)
            _, u = direct.pull()
            direct.commit([np.ones(4, np.float32)], u)
        agg = AggregatorServer(upstream=root.endpoint, discipline="dynsgd",
                               fan_in=2, **FAST)
        agg.start()
        a0 = PSClient(agg.endpoint, worker_id=0, **FAST)
        a1 = PSClient(agg.endpoint, worker_id=1, **FAST)
        try:
            _, u0 = a0.join()
            _, u1 = a1.join()
            assert u0 == u1 == 2  # root-lineage counters served locally
            a0.commit([np.ones(4, np.float32)], u0)
            a1.commit([np.ones(4, np.float32)], u1)
        finally:
            a0.close()
            a1.close()
            agg.close()
        # Root saw ONE combined commit with pulled=min(2,2)=2 at counter 2:
        # staleness 0 per the counter rule.
        agg_commits = [e for e in root.commit_log if e[0] != 7]
        assert len(agg_commits) == 1
        assert agg_commits[0][2] == 0
    finally:
        root.close()


def test_hier_exactly_once_at_both_levels():
    """Worker retransmits dedup at the aggregator; the aggregator's own
    combined commits dedup at the root."""
    root = PSServer(discipline="adag").start()
    try:
        agg = AggregatorServer(upstream=root.endpoint, discipline="adag",
                               init=[np.zeros(3, np.float32)], fan_in=1,
                               **FAST)
        agg.start()
        with PSClient(agg.endpoint, worker_id=0, **FAST) as c:
            _, u = c.join()
            assert c.commit([np.ones(3, np.float32)], u).applied
            # hand-crafted retransmit of seq 0 at the aggregator
            hdr, _ = c._rpc("commit", {"seq": 0, "pulled": int(u)},
                            [np.ones(3, np.float32)])
            assert hdr["duplicate"] is True
        agg.close()
        assert agg.commit_log == [(0, 0, 0)]
        assert len(root.commit_log) == 1
        np.testing.assert_allclose(root.center()[0], 1.0)  # folded ONCE
    finally:
        root.close()


def test_hier_idle_stretch_keeps_root_lease():
    """The flusher's between-flush heartbeat must fire even when
    flush_interval exceeds the root lease: an idle stretch (no commits, so
    the flush cv is never notified) must not let the aggregator's lease
    lapse and the next healthy window land evicted as a lost window."""
    root = PSServer(discipline="adag", lease_s=0.5).start()
    agg = AggregatorServer(upstream=root.endpoint, discipline="adag",
                           init=[np.zeros(3, np.float32)], fan_in=1,
                           flush_interval=10.0, **FAST)
    agg.start()
    try:
        with PSClient(agg.endpoint, worker_id=0, **FAST) as c:
            _, u = c.join()
            assert c.commit([np.ones(3, np.float32)], u).applied
            time.sleep(1.6)  # > 3 lease periods of worker silence
            _, u = c.pull()
            assert c.commit([np.ones(3, np.float32)], u).applied
        deadline = time.monotonic() + 5.0
        while agg.forwarded + agg.lost_windows < 2 and \
                time.monotonic() < deadline:
            time.sleep(0.02)
    finally:
        agg.close()
        root.close()
    assert agg.lost_windows == 0
    assert agg.forwarded == 2 and root.evictions == 0


def test_hier_lost_window_is_counted_not_swallowed():
    """A final flush against a dead root must not vanish silently: the
    window is counted in lost_windows (and close() still completes)."""
    root = PSServer(discipline="adag").start()
    agg = AggregatorServer(upstream=root.endpoint, discipline="adag",
                           init=[np.zeros(3, np.float32)], fan_in=8,
                           flush_interval=30.0, timeout=0.2, retries=1,
                           backoff=0.01)
    agg.start()
    try:
        with PSClient(agg.endpoint, worker_id=0, **FAST) as c:
            _, u = c.join()
            assert c.commit([np.ones(3, np.float32)], u).applied
    finally:
        root.close()  # root dies with the window still accumulated
        agg.close()
    assert agg.lost_windows == 1 and agg.forwarded == 0
    assert agg.absorbed == 1


def test_hier_trainer_over_shm_converges(monkeypatch):
    """End to end: ADAG over the networked PS with DKTPU_NET_HIER=1 and
    the shm ring — the worker loop joins the per-host aggregator, the
    root sees only combined commits, training converges."""
    from distkeras_tpu import ADAG, DataFrame, telemetry

    monkeypatch.setenv("DKTPU_NET_TIMEOUT", "2.0")
    monkeypatch.setenv("DKTPU_NET_HIER", "1")
    monkeypatch.setenv("DKTPU_NET_TRANSPORT", "shm")
    telemetry.reset()
    rng = np.random.default_rng(0)
    centers = rng.normal(scale=4.0, size=(3, 4))
    y = rng.integers(0, 3, size=512)
    x = (centers[y] + rng.normal(scale=0.5, size=(512, 4))
         ).astype(np.float32)
    df = DataFrame({"features": x, "label": y.astype(np.int32)})
    from distkeras_tpu.models import Model
    from distkeras_tpu.models.mlp import MLP

    model = Model.build(MLP(hidden=(16,), num_outputs=3),
                        np.zeros((1, 4), np.float32), seed=0)
    srv = PSServer(discipline="adag").start()
    try:
        t = ADAG(model, loss="sparse_categorical_crossentropy",
                 num_workers=2, batch_size=16, num_epoch=2,
                 learning_rate=0.1, communication_window=4,
                 remote=srv.endpoint)
        trained = t.train(df, shuffle=True)
        acc = float((np.asarray(trained.predict(x)).argmax(-1) == y).mean())
        assert acc > 0.85, acc
        # root ingress: one aggregator worker, not 2 raw workers
        assert srv.members() == []  # aggregator left cleanly
        wids = {wid for wid, _s, _t in srv.commit_log}
        assert len(wids) == 1, wids
        snap = telemetry.get().snapshot()
        assert snap["counters"]["netps.hier.worker_commits"] >= \
            snap["counters"]["netps.hier.combined_commits"]
    finally:
        srv.close()
        telemetry.reset()
