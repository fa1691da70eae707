"""CI netps-chaos smoke (not a pytest module — run directly).

A loopback training run over the **networked parameter server** with
network faults injected by the chaos proxy: CI invokes it with
``DKTPU_NET_FAULTS`` scheduling a delay, a drop, one partition, and one
worker-kill-style eviction (the seeded worker goes silent past its lease
and must rejoin mid-run), and asserts the run converges and exits 0 —
the ROADMAP's "heavy traffic on a bad network" story, exercised end to
end on every PR.

    DKTPU_NET_FAULTS="delay@6:0.2;drop@11;partition@16:0.8;evict@4:2.2;seed=3" \
        python tests/smoke_netps_chaos.py

With ``DKTPU_NET_TRANSPORT=shm`` the data plane upgrades to the same-host
ring after the (proxied) join, so wire faults only see the TCP control
frames — schedule the ring's own faults instead (``shm_delay``/
``shm_corrupt``). With ``DKTPU_NET_HIER=1`` eviction/rejoin happen at the
in-process per-host aggregator, so those assertions read the telemetry
counters rather than the root server's attributes::

    DKTPU_NET_TRANSPORT=shm DKTPU_NET_HIER=1 DKTPU_PS_LEASE=1.0 \\
    DKTPU_NET_FAULTS="shm_delay@3:0.2;shm_corrupt@6;evict@4:2.2;seed=3" \\
        python tests/smoke_netps_chaos.py

**Kill-the-primary mode** (``DKTPU_PS_STATE_DIR`` set): the PS runs as a
real subprocess (``python -m distkeras_tpu.netps --state-dir ...``) whose
OWN fault plan SIGKILLs it mid-run (``ps_crash@R``), while this process's
plan keeps driving the proxy (partition etc.). Recovery is either the
cold restart (a babysitter thread relaunches the dead primary on the same
state dir + port — ``Job.supervise``'s role, inlined) or, with
``DKTPU_PS_STANDBY=1``, a warm standby subprocess that tails the journal,
promotes on lease lapse, and fences the epoch; the trainer's clients walk
the ``proxy,standby`` endpoint list. Exactly-once is asserted on the
on-disk journals (the only view a subprocess leaves behind), and journal
epochs must be nondecreasing — the zero-stale-epoch-folds evidence::

    DKTPU_PS_STATE_DIR=/tmp/ps-state \\
    DKTPU_NET_FAULTS="partition@16:0.8;seed=3" \\
        python tests/smoke_netps_chaos.py          # cold-restart path
    DKTPU_PS_STANDBY=1 DKTPU_PS_STATE_DIR=/tmp/ps-state ...  # failover path

**Kill-one-shard mode** (``NETPS_SMOKE_SHARDS=N`` + state dir): the
center is partitioned across N shard subprocesses (``--shard k/N``),
each with its own journal lineage AND its own warm standby; shard 1's
primary carries ``shard_crash@1:R`` in its fault plan and SIGKILLs
itself mid-run, its standby promotes and fences the epoch, and the
trainer's sharded clients walk only that shard's endpoint group — the
other shards never notice. Exactly-once is asserted on EVERY shard's
journal, epochs must be nondecreasing per lineage, and the victim
shard's standby must have promoted past epoch 0::

    NETPS_SMOKE_SHARDS=2 DKTPU_PS_STATE_DIR=/tmp/ps-state \\
        python tests/smoke_netps_chaos.py          # sharded failover path

**Mesh-demotion mode** (``NETPS_SMOKE_MESH=1``): the PS runs IN THIS
process (the mesh dialect is a same-runtime contract — a subprocess
cannot share the jax device mesh), workers negotiate the device-resident
center, and ``mesh_down@R`` severs the dispatch mid-run. The struck
worker demotes to its negotiated shm ring and retransmits the same seq;
exactly-once and zero lost windows are asserted on the on-disk journal::

    NETPS_SMOKE_MESH=1 DKTPU_NET_FAULTS="mesh_down@6;seed=3" \\
        python tests/smoke_netps_chaos.py          # mesh demotion path

**Region-partition tree mode** (``NETPS_SMOKE_TREE=1`` + state dir): a
2-region, 3-level aggregation tree (workers -> region ``TreeNode``
subprocesses -> root subprocess). Region 0's aggregator SIGKILLs itself
mid-run (``ps_crash`` in its own plan); its warm region-local
``TreeStandby`` promotes, fences the epoch, and the trainer's workers
re-parent via their ordinary endpoint walk. Region 1's UPLINK is
black-holed (``link_down@<link_key>``) past its deliberately tiny
ride-through buffer, so degradation must be counted and typed (the
``dropped_*`` ledger columns; ``silent_loss`` stays 0). Exactly-once is
asserted on EVERY journal (root, both region lineages), epochs must be
nondecreasing, and the run must still converge. A second, in-process
traced loopback tree then replays the partition and gates on simulator
parity: ``sim.calibrate.tree_parity`` re-fits the PR 16
``region_partition`` scenario to the live run's shape and the root
ingress cut + partition staleness spike must agree within
``DKTPU_SIM_BAND_PCT`` — the ``tree_parity`` block, printed and (where
``NETPS_SMOKE_SUMMARY`` names a file) written there::

    NETPS_SMOKE_TREE=1 DKTPU_PS_STATE_DIR=/tmp/ps-state \\
        python tests/smoke_netps_chaos.py          # region-partition path

All seeds are pinned (data rng, trainer seed, fault-plan seeds, the
``ps_crash``/``shard_crash`` commit indices), so reruns schedule the
same chaos.
"""

import os
import sys

# Runs from a checkout without installation: sys.path[0] is tests/, so the
# repo root must be appended (an installed distkeras_tpu still wins).
sys.path.append(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
# Tight-but-survivable budgets: a dropped frame must not stall CI for the
# production 30 s deadline.
os.environ.setdefault("DKTPU_NET_TIMEOUT", "1.0")
os.environ.setdefault("DKTPU_NET_RETRIES", "8")
os.environ.setdefault("DKTPU_NET_BACKOFF", "0.02")
os.environ.setdefault(
    "DKTPU_NET_FAULTS",
    "delay@6:0.2;drop@11;partition@16:0.8;evict@4:2.2;seed=3")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from distkeras_tpu import ADAG, DataFrame, telemetry  # noqa: E402
from distkeras_tpu.models import Model  # noqa: E402
from distkeras_tpu.models.mlp import MLP  # noqa: E402
from distkeras_tpu.netps import ChaosProxy, PSServer  # noqa: E402
from distkeras_tpu.netps import state as netps_state  # noqa: E402

#: the primary subprocess's own fault plan: SIGKILL just before folding
#: commit 20 (mid-run: the full run folds ~48). Pinned, not random.
PS_FAULTS = os.environ.get("NETPS_SMOKE_PS_FAULTS", "ps_crash@20;seed=3")


def _free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _launch_ps(port, state_dir, extra_env, *extra_args):
    import subprocess

    # The smoke process's own chaos plan and PS-role env must not leak
    # into the server subprocess: it gets explicit flags + its OWN plan.
    drop = {"DKTPU_NET_FAULTS", "DKTPU_PS_STANDBY", "DKTPU_PS_STATE_DIR",
            "DKTPU_FAULTS_STATE"}
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update({"JAX_PLATFORMS": "cpu", **extra_env})
    proc = subprocess.Popen(
        [sys.executable, "-m", "distkeras_tpu.netps", "--host", "127.0.0.1",
         "--port", str(port), "--discipline", "adag", "--lease", "1.0",
         "--state-dir", state_dir, "--snapshot-every", "10", *extra_args],
        env=env)
    return proc


def _assert_journal_invariants(state_dir, label):
    """The subprocess-visible exactly-once + zero-stale-epoch evidence:
    every (worker, seq) journaled at most once, fold indices strictly
    sequential per journal chain, epochs nondecreasing."""
    records = netps_state.read_journal(state_dir)
    seen = set()
    last_epoch = -1
    for r in records:
        key = (int(r["wid"]), int(r["seq"]))
        assert key not in seen, f"{label}: commit {key} folded twice"
        seen.add(key)
        assert int(r["e"]) >= last_epoch, (
            f"{label}: journal epoch went backwards at {key}")
        last_epoch = int(r["e"])
    return records, last_epoch


def _assert_trace_evidence(state_dir, standby_mode) -> None:
    """Trace-mode evidence (``DKTPU_TRACE=1`` on the failover drill): the
    collector-merged streams must show every accepted commit as one
    complete cross-process trace with no orphaned server-side spans, and
    the SIGKILLed primary's flight-recorder dump must agree with the
    on-disk journal it left behind. See docs/OBSERVABILITY.md
    ("Distributed tracing")."""
    import glob
    import json

    from distkeras_tpu.telemetry import tracing
    from distkeras_tpu.telemetry.tracing import analysis as trace_analysis

    trace_dir = tracing.trace_dir()
    assert trace_dir, "DKTPU_TRACE=1 but no DKTPU_TRACE_DIR to collect from"
    # The smoke process's own registry (chaos-proxy events + anything the
    # tap saw) joins the subprocess streams on disk before the merge.
    telemetry.write_jsonl(
        telemetry.get(),
        os.path.join(trace_dir, f"telemetry-trainer-{os.getpid()}.jsonl"))
    records = tracing.TelemetryCollector.from_dir(trace_dir).records()
    rep = tracing.trace_report(records)
    assert not rep["orphans"], (
        f"{len(rep['orphans'])} server-side trace(s) never joined a client "
        f"root: {rep['orphans'][:5]}")

    # Every journaled (= accepted) commit must map to a traced commit
    # carrying every always-on critical-path segment plus fsync (a state
    # dir is configured). ``replicate`` is deliberately NOT demanded:
    # commits folded by the promoted standby after the crash have nobody
    # left to replicate to, so a promotion legitimately ends that segment.
    base = set(trace_analysis.BASE_REQUIRED) | {"fsync"}
    traced = {}
    for _tid, t in trace_analysis.assemble_traces(records).items():
        root = t["root"]
        if root is not None and root.get("name") == "commit":
            traced[(int(root["wid"]), int(root["seq"]))] = (
                trace_analysis._segment_durs(t["spans"]))
    accepted = set()
    for d in [state_dir] + ([state_dir + ".standby"] if standby_mode else []):
        for r in netps_state.read_journal(d):
            accepted.add((int(r["wid"]), int(r["seq"])))
    untraced = sorted(k for k in accepted if k not in traced)
    assert not untraced, f"accepted commits left no trace: {untraced[:5]}"
    incomplete = sorted(k for k in accepted if not base <= set(traced[k]))
    assert not incomplete, (
        "accepted commits with gaps in the critical path: "
        f"{[(k, sorted(traced[k])) for k in incomplete[:5]]}")

    # The ps_crash dump: FaultPlan._fire wrote the flight ring BEFORE the
    # SIGKILL, so the primary's final seconds are on disk. Its fold tail
    # must agree with the journal the dead process left behind.
    dumps = sorted(glob.glob(os.path.join(trace_dir, "flight-ps-*.jsonl")))
    assert dumps, "the SIGKILLed primary left no flight-recorder dump"
    folds = []
    with open(dumps[-1], encoding="utf-8") as f:
        for line in f:
            try:
                rec = json.loads(line)
            except ValueError:
                continue  # a crash-truncated tail line is legal
            if (rec.get("kind") == tracing.SPAN_KIND
                    and rec.get("name") == "commit.fold"):
                folds.append((int(rec["wid"]), int(rec["seq"])))
    assert folds, "the flight dump recorded no folds before the crash"
    # The journal rotates at every snapshot and prunes old generations,
    # so the on-disk journal is the TAIL of fold history — and the ring
    # saw more history than survived on disk. The journal writer is also
    # an ordered background thread with a bounded queue, so at the
    # SIGKILL the ring may lead the journal by up to that many folded-
    # but-unwritten commits (plus the fold in flight) — but it must
    # never DISAGREE: the journal must be a suffix of the ring's fold
    # sequence once that bounded lead is stripped.
    jkeys = [(int(r["wid"]), int(r["seq"]))
             for r in netps_state.read_journal(state_dir)]
    assert jkeys, "the crashed primary left no journal to corroborate"
    jset, lead = set(jkeys), 0
    while (folds and folds[-1] not in jset
           and lead <= netps_state._WRITE_QUEUE):
        folds.pop()
        lead += 1
    k = min(len(jkeys), len(folds))
    assert k >= 1 and folds[-k:] == jkeys[-k:], (
        f"flight-dump fold tail {folds[-k:]} disagrees with the on-disk "
        f"journal tail {jkeys[-k:]} (crash-lead stripped: {lead})")
    print(f"netps trace evidence: traces={rep['traces']} "
          f"commits={rep['commits']} accepted={len(accepted)} orphans=0 "
          f"flight_folds={len(folds)} processes={len(rep['processes'])}")


def _run_mesh(df, model) -> int:
    """Mesh-demotion mode (``NETPS_SMOKE_MESH=1``): the PS and the
    workers share THIS process's jax runtime, the data plane negotiates
    the mesh dialect (device-resident center, zero wire bytes), and
    ``mesh_down@R`` kills the device dispatch mid-run — the struck
    worker must demote to its negotiated shm ring (ONE strike, no
    rejoin) and retransmit the SAME seq, with exactly-once and zero
    lost windows proven on the on-disk journal."""
    import tempfile

    faults_spec = os.environ.get("DKTPU_NET_FAULTS", "")
    assert "mesh_down" in faults_spec, (
        "mesh mode expects a mesh_down@R entry in DKTPU_NET_FAULTS")
    # The workers request the dialect; the server resolves it live.
    os.environ["DKTPU_NET_TRANSPORT"] = "mesh"
    state_dir = (os.environ.get("DKTPU_PS_STATE_DIR")
                 or tempfile.mkdtemp(prefix="dktpu-mesh-smoke-"))
    server = PSServer(discipline="adag", lease_s=5.0, transport="mesh",
                      state_dir=state_dir, snapshot_every=10).start()
    try:
        trainer = ADAG(model, loss="sparse_categorical_crossentropy",
                       num_workers=4, batch_size=16, num_epoch=3,
                       learning_rate=0.1, communication_window=4,
                       seed=0, remote=server.endpoint)
        trained = trainer.train(df, shuffle=True)
        assert server._mesh_folder is not None, (
            "the PS never resolved the mesh fold path")
        total = server.commits_total
        commit_log = list(server.commit_log)
        log_dropped = server._log_dropped
    finally:
        server.close()
    acc = float((np.asarray(trained.predict(jnp.asarray(
        df["features"]))).argmax(-1) == df["label"]).mean())
    reg = telemetry.get()
    upgrades = reg.counter("netps.mesh.upgrades").value
    folds = reg.counter("netps.mesh.folds").value
    demotions = reg.counter("netps.mesh.demotions").value
    # Exactly-once on the on-disk journal: no (wid, seq) folded twice,
    # epochs nondecreasing. Snapshot compaction bounds the journal to the
    # tail since the last snapshot, so contiguity is asserted within it.
    records, _ = _assert_journal_invariants(state_dir, "mesh")
    assert records, "mesh: the journal tail is empty"
    tail: dict = {}
    for r in records:
        tail.setdefault(int(r["wid"]), []).append(int(r["seq"]))
    for wid, seqs in sorted(tail.items()):
        assert seqs == list(range(seqs[0], seqs[0] + len(seqs))), (
            f"mesh: journal tail lost a window for worker {wid}: {seqs}")
    # Zero lost windows over the WHOLE run (the in-process commit log is
    # the full history): every worker's seqs are contiguous from 0 — the
    # demoted seq's retransmit landed exactly once, and no later window
    # vanished in the dialect switch.
    assert len(commit_log) + log_dropped == total
    per_worker: dict = {}
    for wid, seq, _st in commit_log:
        assert seq not in per_worker.setdefault(int(wid), set()), (
            f"mesh: commit ({wid}, {seq}) folded twice")
        per_worker[int(wid)].add(int(seq))
    for wid, seqs in sorted(per_worker.items()):
        assert seqs == set(range(max(seqs) + 1)), (
            f"mesh: worker {wid} lost a window: {sorted(seqs)}")
    print(f"netps mesh demotion: acc={acc:.4f} folds={total} "
          f"workers={len(per_worker)} mesh_upgrades={upgrades:.0f} "
          f"mesh_folds={folds:.0f} mesh_demotions={demotions:.0f}")
    assert acc > 0.85, f"accuracy collapsed across the demotion: {acc}"
    assert upgrades >= 1, "no worker ever negotiated the mesh dialect"
    assert folds >= 1, "the device collective never folded a commit"
    assert demotions >= 1, "mesh_down never bit — the drill is dead"
    return 0


def _run_failover(df, model) -> int:
    """Kill-the-primary mode: PS subprocess(es) + ps_crash, with either a
    babysitter cold restart or a warm-standby promotion riding it out."""
    import subprocess
    import threading
    import time

    state_dir = os.environ["DKTPU_PS_STATE_DIR"]
    standby_mode = bool(os.environ.get("DKTPU_PS_STANDBY"))
    port = _free_port()
    faults_state = os.path.join(state_dir, "faults.journal")
    os.makedirs(state_dir, exist_ok=True)
    primary = _launch_ps(port, state_dir,
                         {"DKTPU_NET_FAULTS": PS_FAULTS,
                          "DKTPU_FAULTS_STATE": faults_state})
    procs = [primary]
    restarts = [0]
    stop = threading.Event()
    standby_dir = state_dir + ".standby"

    def babysit():
        # Job.supervise's PS-restart duty, inlined: relaunch the killed
        # primary on the same state dir + port (cold recovery). The fired-
        # faults journal keeps ps_crash one-shot across the restart.
        nonlocal primary
        while not stop.is_set():
            if primary.poll() is not None and primary.returncode != 0:
                restarts[0] += 1
                primary = _launch_ps(
                    port, state_dir,
                    {"DKTPU_NET_FAULTS": PS_FAULTS,
                     "DKTPU_FAULTS_STATE": faults_state})
                procs.append(primary)
            time.sleep(0.1)

    standby = None
    if standby_mode:
        sb_port = _free_port()
        standby = _launch_ps(sb_port, standby_dir, {},
                             "--standby", f"127.0.0.1:{port}",
                             "--promote-after", "1.5")
        procs.append(standby)
    else:
        threading.Thread(target=babysit, daemon=True).start()
    proxy = ChaosProxy(f"127.0.0.1:{port}").start()  # ambient net faults
    endpoint = proxy.endpoint
    if standby_mode:
        endpoint = f"{endpoint},127.0.0.1:{sb_port}"
    if os.environ.get("DKTPU_TRACE"):
        # Label this process's spans in the merged timeline (the in-process
        # API, not DKTPU_TRACE_ROLE: the env var would leak into the PS
        # subprocesses and overwrite their own role stamps).
        from distkeras_tpu.telemetry import tracing
        tracing.set_role("trainer")
    try:
        trainer = ADAG(model, loss="sparse_categorical_crossentropy",
                       num_workers=4, batch_size=16, num_epoch=3,
                       learning_rate=0.1, communication_window=4,
                       seed=0, remote=endpoint)
        trained = trainer.train(df, shuffle=True)
    finally:
        stop.set()
        proxy.close()
        # Crash evidence is read BEFORE teardown: the escalation below can
        # itself produce nonzero returncodes (SIGKILL on a wedged drain),
        # which must never masquerade as the injected ps_crash.
        crashed = any(p.poll() not in (0, None) for p in procs)
        for p in procs:
            if p.poll() is None:
                p.terminate()
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait(timeout=10)
    acc = float((np.asarray(trained.predict(jnp.asarray(
        df["features"]))).argmax(-1) == df["label"]).mean())
    reg = telemetry.get()
    retries = reg.counter("netps.retries").value
    walks = reg.counter("netps.endpoint_walks").value
    records, last_epoch = _assert_journal_invariants(state_dir, "primary")
    mode = "standby" if standby_mode else "cold-restart"
    line = (f"netps kill-the-primary ({mode}): acc={acc:.4f} "
            f"journaled={len(records)} restarts={restarts[0]} "
            f"client_retries={retries:.0f} endpoint_walks={walks:.0f}")
    if standby_mode:
        sb_records, sb_epoch = _assert_journal_invariants(
            standby_dir, "standby")
        line += f" standby_journaled={len(sb_records)} epoch={sb_epoch}"
        assert sb_epoch >= 1, "the standby never promoted past epoch 0"
        assert walks >= 1, "no client ever walked the endpoint list"
    else:
        assert restarts[0] >= 1, "the primary was never killed + restarted"
        assert last_epoch == 0, "cold restart must not change the epoch"
    print(line)
    assert crashed, "ps_crash never fired — the drill tested nothing"
    assert acc > 0.85, f"accuracy collapsed across the PS crash: {acc}"
    assert retries >= 1, "no RPC ever retried — chaos did not bite"
    assert len(records) >= 10, "journal is implausibly short"
    if os.environ.get("DKTPU_TRACE"):
        _assert_trace_evidence(state_dir, standby_mode)
    return 0


def _run_sharded(df, model) -> int:
    """Kill-one-shard mode: N shard primaries + N warm standbys, shard 1
    SIGKILLed by its own ``shard_crash`` plan mid-run; its standby
    promotes while the other shards keep folding undisturbed."""
    import subprocess

    n = int(os.environ["NETPS_SMOKE_SHARDS"])
    base = os.environ["DKTPU_PS_STATE_DIR"]
    os.makedirs(base, exist_ok=True)
    victim = min(1, n - 1)
    shard_faults = os.environ.get(
        "NETPS_SMOKE_SHARD_FAULTS", f"shard_crash@{victim}:12;seed=3")
    groups, procs, primaries = [], [], []
    for k in range(n):
        p_port, s_port = _free_port(), _free_port()
        p_dir = os.path.join(base, f"shard-{k}")
        # Every primary carries the SAME plan: shard_crash@{victim} only
        # fires where the --shard index matches, so the non-victims parse
        # it and never trip. The fired-faults journal keeps it one-shot.
        primary = _launch_ps(
            p_port, p_dir,
            {"DKTPU_NET_FAULTS": shard_faults,
             "DKTPU_FAULTS_STATE": os.path.join(p_dir, "faults.journal")},
            "--shard", f"{k}/{n}")
        standby = _launch_ps(
            s_port, p_dir + ".standby", {},
            "--standby", f"127.0.0.1:{p_port}", "--promote-after", "1.5",
            "--shard", f"{k}/{n}")
        procs += [primary, standby]
        primaries.append(primary)
        groups.append(f"127.0.0.1:{p_port},127.0.0.1:{s_port}")
    endpoint = ";".join(groups)
    try:
        trainer = ADAG(model, loss="sparse_categorical_crossentropy",
                       num_workers=4, batch_size=16, num_epoch=3,
                       learning_rate=0.1, communication_window=4,
                       seed=0, remote=endpoint)
        trained = trainer.train(df, shuffle=True)
    finally:
        # Crash evidence BEFORE teardown: the terminate/kill escalation
        # below must never masquerade as the injected shard_crash.
        victim_crashed = primaries[victim].poll() not in (0, None)
        bystanders_alive = all(primaries[k].poll() is None
                               for k in range(n) if k != victim)
        for p in procs:
            if p.poll() is None:
                p.terminate()
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait(timeout=10)
    acc = float((np.asarray(trained.predict(jnp.asarray(
        df["features"]))).argmax(-1) == df["label"]).mean())
    reg = telemetry.get()
    retries = reg.counter("netps.retries").value
    walks = reg.counter("netps.endpoint_walks").value
    journaled = []
    for k in range(n):
        records, _ = _assert_journal_invariants(
            os.path.join(base, f"shard-{k}"), f"shard-{k}")
        journaled.append(len(records))
    sb_records, sb_epoch = _assert_journal_invariants(
        os.path.join(base, f"shard-{victim}.standby"),
        f"shard-{victim}-standby")
    print(f"netps kill-one-shard ({n} shards): acc={acc:.4f} "
          f"journaled={journaled} standby_journaled={len(sb_records)} "
          f"standby_epoch={sb_epoch} client_retries={retries:.0f} "
          f"endpoint_walks={walks:.0f}")
    assert victim_crashed, "shard_crash never fired — the drill tested nothing"
    assert bystanders_alive, "a non-victim shard died: the blast radius leaked"
    assert sb_epoch >= 1, (
        f"shard {victim}'s standby never promoted past epoch 0")
    assert walks >= 1, "no client ever walked the victim's endpoint group"
    assert acc >= 0.99, f"accuracy collapsed across the shard crash: {acc}"
    assert all(j >= 10 for j in journaled), (
        f"a shard journal is implausibly short: {journaled}")
    return 0


def _scrape_tree_stats(endpoint) -> dict:
    """One membership-free ledger scrape of a tree node subprocess."""
    from distkeras_tpu.netps import PSClient

    c = PSClient(endpoint, timeout=1.0, retries=5, backoff=0.1)
    try:
        return c.stats().get("tree") or {}
    finally:
        c.close()


def _run_tree_parity() -> dict:
    """Phase 2 of the tree drill: a live in-process loopback tree under a
    pinned mid-run partition, re-fitted through the simulator. The sim's
    ``region_partition`` scenario — re-shaped to THIS tree — must
    reproduce the measured root ingress cut and the partitioned region's
    staleness spike within the calibration band; the verdict is printed,
    and written as ``tree_parity`` to the file ``NETPS_SMOKE_SUMMARY``
    names, if it names one."""
    import json
    import time

    from distkeras_tpu.netps import PSClient, PSServer
    from distkeras_tpu.netps.tree import TreeSpec, build_tree
    from distkeras_tpu.resilience import faults
    from distkeras_tpu.sim.calibrate import tree_parity

    workers, rounds, work_s, part_s = 4, 30, 0.05, 1.0
    root = PSServer(discipline="adag",
                    center=[np.zeros(4, np.float32)], lease_s=30.0).start()
    tree = None
    clients = []
    try:
        tree = build_tree("region:2", root.endpoint, workers=workers,
                          buffer_windows=256, flush_interval=0.05,
                          probe_links=False)
        clients = [PSClient(tree.leaf_endpoint(r)) for r in range(workers)]
        for c in clients:
            c.join(init=[np.zeros(4, np.float32)])
        key = TreeSpec.link_key(0, 1)
        t0 = time.monotonic()
        part_t0 = None
        for rnd in range(rounds):
            if rnd == rounds // 3 and part_t0 is None:
                faults.set_net_plan(faults.FaultPlan.parse_net(
                    f"link_down@{key}:{part_s}"))
                part_t0 = time.monotonic() - t0
            for c in clients:
                _, pulled = c.pull()
                c.commit([np.ones(4, np.float32) * 0.001], pulled)
            time.sleep(work_s)
        wall = time.monotonic() - t0
        deadline = time.monotonic() + part_s + 5.0
        while time.monotonic() < deadline:  # heal + drain
            s1 = tree.node(0, 1).tree_stats()
            if s1["buffered_windows"] == 0 and not s1["link_down"]:
                break
            time.sleep(0.1)
        time.sleep(0.3)
        n0, n1 = tree.node(0, 0), tree.node(0, 1)
        s0, s1 = n0.tree_stats(), n1.tree_stats()
        assert s0["silent_loss"] == 0 and s1["silent_loss"] == 0, (
            "the traced loopback tree lost a window silently")
        assert s1["buffered_windows"] == 0, (
            "region 1 never drained its ride-through buffer after heal")
        absorbed = s0["absorbed"] + s1["absorbed"]
        part_stale = max(
            (st for wid, _seq, st in root.commit_log
             if wid == n1._up.worker_id), default=0)
        live = {
            "workers": workers, "fanouts": [2], "rounds": rounds,
            "work_s": wall / rounds, "flush_s": 0.05,
            "partition": [part_t0, part_t0 + part_s],
            "ingress_cut": absorbed / max(1, root.commits_total),
            "staleness_spike": int(part_stale),
        }
    finally:
        faults.reset()
        for c in clients:
            try:
                c.leave()
            except Exception:  # noqa: BLE001 - teardown best effort
                pass
            c.close()
        if tree is not None:
            tree.close()
        root.close()
    parity = tree_parity(live, band_pct=None)
    print(f"netps tree parity: ingress cut live="
          f"{parity['live']['ingress_cut']:.3f} sim="
          f"{parity['sim']['ingress_cut']:.3f} "
          f"(ratio {parity['ingress_cut_ratio']:.3f})  staleness spike "
          f"live={parity['live']['staleness_spike']} sim="
          f"{parity['sim']['staleness_spike']} "
          f"(ratio {parity['staleness_spike_ratio']:.3f})  band "
          f"{parity['band_pct']:.0f}%")
    assert parity["within_band"], (
        "the simulator's region_partition replay left the calibration "
        f"band: {json.dumps(parity, sort_keys=True)}")
    summary_path = os.environ.get("NETPS_SMOKE_SUMMARY")
    if summary_path:
        with open(summary_path, "w", encoding="utf-8") as f:
            json.dump({"tree_parity": parity}, f, indent=2, sort_keys=True)
            f.write("\n")
    return parity


def _run_tree(df, model) -> int:
    """Region-partition tree mode: the 2-region / 3-level drill (see
    module docstring) followed by the simulator parity gate."""
    import subprocess
    import threading
    import time

    from distkeras_tpu.netps import PSClient
    from distkeras_tpu.netps.remote import _leaves
    from distkeras_tpu.netps.tree import TreeSpec

    base = os.environ["DKTPU_PS_STATE_DIR"]
    os.makedirs(base, exist_ok=True)
    tree_faults = os.environ.get("NETPS_SMOKE_TREE_FAULTS",
                                 "ps_crash@12;seed=3")
    link_key = TreeSpec.link_key(0, 1)
    link_faults = os.environ.get("NETPS_SMOKE_LINK_FAULTS",
                                 f"link_down@{link_key}:2.5;seed=3")
    root_port = _free_port()
    root_ep = f"127.0.0.1:{root_port}"
    root_dir = os.path.join(base, "root")
    procs = [_launch_ps(root_port, root_dir, {})]
    # Seed the root center with the model's leaves BEFORE any tree node
    # dials in: interior nodes join upstream with an empty init (their
    # center IS the root lineage's) and an uninitialized root would
    # reject them.
    init = [np.asarray(a, np.float32) for a in _leaves(model.params)]
    boot = PSClient(root_ep, timeout=1.0, retries=25, backoff=0.2)
    boot.join(init=init)
    boot.leave()
    boot.close()

    r0_port, s0_port, r1_port = _free_port(), _free_port(), _free_port()
    r0_dir = os.path.join(base, "tree-L0-g0")
    s0_dir = r0_dir + ".standby"
    r1_dir = os.path.join(base, "tree-L0-g1")
    tree_args = ("--tree-spec", "region:2", "--flush-interval", "0.2")
    # Region 0: the victim. Its OWN plan SIGKILLs it just before fold 12
    # (mid-run), no goodbye; the fired-faults journal keeps it one-shot.
    procs.append(_launch_ps(
        r0_port, r0_dir,
        {"DKTPU_NET_FAULTS": tree_faults,
         "DKTPU_FAULTS_STATE": os.path.join(r0_dir, "faults.journal")},
        "--upstream", root_ep, "--tree-level", "0", "--tree-group", "0",
        *tree_args))
    victim = procs[-1]
    # Its warm region-local standby: tails the journal, promotes on lease
    # lapse, fences, and takes over the uplink.
    procs.append(_launch_ps(
        s0_port, s0_dir, {},
        "--standby", f"127.0.0.1:{r0_port}", "--upstream", root_ep,
        "--tree-level", "0", "--tree-group", "0",
        "--promote-after", "1.5", *tree_args))
    # Region 1: healthy process, black-holed UPLINK — and a buffer bound
    # (2 windows) the 2.5 s outage must overrun, forcing typed drops.
    procs.append(_launch_ps(
        r1_port, r1_dir,
        {"DKTPU_NET_FAULTS": link_faults,
         "DKTPU_FAULTS_STATE": os.path.join(r1_dir, "faults.journal")},
        "--upstream", root_ep, "--tree-level", "0", "--tree-group", "1",
        "--tree-buffer", "2", "--fan-in", "1", *tree_args))

    stop = threading.Event()

    def region1_traffic():
        # Zero-delta commits: region 1 sees real windows, buffering, and
        # drops without perturbing the center the trainer is converging.
        # The node subprocess spends seconds importing before it listens,
        # so the join loops until it answers (or the drill ends).
        c = None
        deadline = time.monotonic() + 30.0
        while not stop.is_set() and time.monotonic() < deadline:
            try:
                c = PSClient(f"127.0.0.1:{r1_port}", timeout=1.0,
                             retries=3, backoff=0.1)
                c.join(init=init)
                break
            except Exception:  # noqa: BLE001 - still booting
                if c is not None:
                    c.close()
                c = None
                time.sleep(0.2)
        if c is None:
            return
        try:
            zeros = [np.zeros_like(a) for a in init]
            while not stop.is_set():
                _, pulled = c.pull()
                c.commit(zeros, pulled)
                stop.wait(0.05)
        finally:
            try:
                c.leave()
            except Exception:  # noqa: BLE001 - the drill may outlive it
                pass
            c.close()

    traffic = threading.Thread(target=region1_traffic, daemon=True)
    traffic.start()
    try:
        trainer = ADAG(model, loss="sparse_categorical_crossentropy",
                       num_workers=4, batch_size=16, num_epoch=3,
                       learning_rate=0.1, communication_window=4,
                       seed=0, remote=f"127.0.0.1:{r0_port},"
                                      f"127.0.0.1:{s0_port}")
        trained = trainer.train(df, shuffle=True)
        # Region 1 must come back up and drain its survivors before the
        # ledger is read — ride-through, not ride-forever.
        r1_stats = {}
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            r1_stats = _scrape_tree_stats(f"127.0.0.1:{r1_port}")
            if (r1_stats and r1_stats["buffered_windows"] == 0
                    and not r1_stats["link_down"]):
                break
            time.sleep(0.2)
        sb_stats = _scrape_tree_stats(f"127.0.0.1:{s0_port}")
    finally:
        stop.set()
        traffic.join(timeout=5.0)
        # Crash evidence BEFORE teardown: the terminate/kill escalation
        # below must never masquerade as the injected ps_crash.
        victim_crashed = victim.poll() not in (0, None)
        for p in procs:
            if p.poll() is None:
                p.terminate()
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait(timeout=10)
    acc = float((np.asarray(trained.predict(jnp.asarray(
        df["features"]))).argmax(-1) == df["label"]).mean())
    reg = telemetry.get()
    walks = reg.counter("netps.endpoint_walks").value
    journaled = {}
    for label, sdir in (("root", root_dir), ("region0", r0_dir),
                        ("region0-standby", s0_dir), ("region1", r1_dir)):
        records, last_epoch = _assert_journal_invariants(sdir, label)
        journaled[label] = (len(records), last_epoch)
    print(f"netps region-partition tree: acc={acc:.4f} "
          f"journaled={journaled} "
          f"dropped_windows={r1_stats.get('dropped_windows')} "
          f"dropped_commits={r1_stats.get('dropped_commits')} "
          f"silent_loss={r1_stats.get('silent_loss')} "
          f"endpoint_walks={walks:.0f}")
    assert victim_crashed, (
        "region 0's ps_crash never fired — the drill tested nothing")
    assert journaled["region0-standby"][1] >= 1, (
        "region 0's standby never promoted past epoch 0")
    assert sb_stats.get("forwarded", 0) >= 1, (
        "the promoted standby never flushed a combined window upstream")
    assert walks >= 1, "no client ever walked the region's endpoint list"
    assert r1_stats, "region 1's ledger was never scraped"
    assert r1_stats["link_downs"] >= 1, "region 1's link_down never fired"
    assert r1_stats["dropped_windows"] >= 1, (
        "the 2.5 s outage never overran the 2-window buffer: the "
        "typed-drop path went untested")
    assert r1_stats["buffered_windows"] == 0, (
        "region 1 never drained its buffer after the heal")
    assert r1_stats["silent_loss"] == 0, (
        f"window conservation violated: {r1_stats}")
    assert acc >= 0.99, f"accuracy collapsed across the region drill: {acc}"
    _run_tree_parity()
    return 0


def main() -> int:
    rng = np.random.default_rng(0)
    centers = rng.normal(scale=4.0, size=(3, 4))
    y = rng.integers(0, 3, size=1024)
    x = centers[y] + rng.normal(scale=0.5, size=(1024, 4))
    df = DataFrame({"features": x.astype(np.float32),
                    "label": y.astype(np.int32)})
    model = Model.build(MLP(hidden=(16,), num_outputs=3),
                        jnp.zeros((1, 4), jnp.float32), seed=0)
    if os.environ.get("NETPS_SMOKE_MESH"):
        return _run_mesh(df, model)
    if os.environ.get("NETPS_SMOKE_TREE"):
        return _run_tree(df, model)
    if int(os.environ.get("NETPS_SMOKE_SHARDS") or 0) > 1:
        return _run_sharded(df, model)
    if os.environ.get("DKTPU_PS_STATE_DIR"):
        return _run_failover(df, model)
    server = PSServer(discipline="adag", lease_s=1.0).start()
    proxy = ChaosProxy(server.endpoint).start()  # ambient DKTPU_NET_FAULTS
    try:
        trainer = ADAG(model, loss="sparse_categorical_crossentropy",
                       num_workers=4, batch_size=16, num_epoch=3,
                       learning_rate=0.1, communication_window=4,
                       seed=0, remote=proxy.endpoint)
        trained = trainer.train(df, shuffle=True)
    finally:
        proxy.close()
        server.close()
    acc = float((np.asarray(trained.predict(jnp.asarray(
        df["features"]))).argmax(-1) == df["label"]).mean())
    reg = telemetry.get()
    retries = reg.counter("netps.retries").value
    injected = reg.counter("resilience.faults_injected").value
    from distkeras_tpu.runtime import config

    if config.env_bool("DKTPU_NET_HIER"):
        # Workers live behind the in-process per-host aggregator: eviction
        # and rejoin happen THERE (its monitor/join feed the same counters
        # the root's would), while the root sees one aggregator peer.
        evictions = reg.counter("netps.evictions").value
        rejoins = reg.counter("netps.rejoins").value
    else:
        evictions, rejoins = server.evictions, server.rejoins
    print(f"netps chaos run: acc={acc:.4f} commits={len(server.commit_log)} "
          f"evictions={evictions:.0f} rejoins={rejoins:.0f} "
          f"client_retries={retries:.0f} faults_injected={injected:.0f}")
    assert acc > 0.85, f"accuracy collapsed under network chaos: {acc}"
    assert evictions >= 1, "the worker-kill eviction never happened"
    assert rejoins >= 1, "the evicted worker never re-joined"
    assert retries >= 1, "no RPC ever retried — chaos did not bite"
    seen = set()
    for wid, seq, _st in server.commit_log:
        assert (wid, seq) not in seen, f"commit ({wid}, {seq}) folded twice"
        seen.add((wid, seq))
    if config.env_bool("DKTPU_NET_AUTOTUNE"):
        # The self-tuning data plane under chaos: the controller must have
        # engaged (probes on TCP, the measured ring rule on shm, decisions
        # either way) and every retune it issued must have respected the
        # floors — a floor violation under faults means the guardrails,
        # not the chaos, are the bug.
        probes = reg.counter("tuner.probes").value
        floor_violations = reg.counter("tuner.floor_violations").value
        fallbacks = reg.counter("tuner.oscillation_fallbacks").value
        decisions = reg.counter("tuner.decisions").value
        runs = [e for e in reg.events() if e["kind"] == "tuner_run_summary"]
        print(f"netps autotune under chaos: probes={probes:.0f} "
              f"decisions={decisions:.0f} fallbacks={fallbacks:.0f} "
              f"floor_violations={floor_violations:.0f} converged="
              + (",".join(f"{k}={runs[-1].get(k)}" for k in
                          ("transport", "codec", "shards", "inflight"))
                 if runs else "none"))
        assert runs, "autotune on but the controller never exported a summary"
        assert decisions >= 1, "autotune on but the controller never decided"
        if runs[-1].get("transport") == "tcp":
            assert probes >= 1, (
                "TCP data plane but the controller never probed a codec")
        assert floor_violations == 0, (
            f"the controller violated a knob floor {floor_violations:.0f} "
            "times while retuning under chaos")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
