"""The remote worker loop: the reference's executor loop over the real wire.

Each logical worker is a host thread running ``pull -> K local steps ->
commit`` against a :class:`~distkeras_tpu.netps.server.PSServer` through
the hardened :class:`~distkeras_tpu.netps.client.PSClient` — the same
jitted window (:func:`distkeras_tpu.workers.make_local_loop`) the engines
compile, the same worker-side discipline normalization the raced twin
uses (``racelab.run_raced``), and the same server-side fold
(:mod:`distkeras_tpu.netps.fold`). Gradient compute releases the GIL, so
worker threads genuinely interleave; commit order is whatever the network
and the OS deliver — the reference's architecture, end to end.

**Compute/communication overlap** (``DKTPU_NET_INFLIGHT``): with the
default of 1 the loop is the serial PR 4 one — round *r*'s commit is
ACKed before round *r+1* begins. Raising it double-buffers the loop:
round *r*'s commit (and the next round's pull prefetch) run on background
comms threads while round *r+1*'s K jitted local steps execute, with at
most ``DKTPU_NET_INFLIGHT`` commits un-ACKed at any time. Commits still
leave in strict seq order (one ordered comms lane per worker), so the
exactly-once dedup story is untouched. The price is staleness: a
prefetched pull cannot contain the still-in-flight commits, so the
server's counter rule *naturally* charges the realized in-flight delay —
DynSGD's ``1/(staleness+1)`` scale and the staleness telemetry
(``netps.commit.staleness`` histogram + the ``discipline.staleness_*``
gauges the DisciplineMonitor exports) see the TRUE realized staleness,
not the serial loop's. The overlap's effectiveness is exported as the
``netps.overlap.hidden_fraction`` gauge (1 − visible comms wait / total
comms time).

Elastic membership in the loop: a worker that went silent past its lease
(injected via the ``evict@R:S`` net fault, or a real stall) finds itself
evicted at the next RPC; the client re-joins automatically, the worker
discards its stale window (including any in-flight commits — their
evicted results drain into a re-adopt), re-adopts the freshly pulled
center (the reference's rejoining-worker semantics), and training
continues — no global restart, and the survivors never stopped.

Mutable model state (BatchNorm stats) stays per-worker and unsynced here —
the reference's socket server only ever carried parameters.

Worker identity: ids 0..W-1 are per-*trainer*. A restarted worker process
resumes safely (``join`` hands back the server's last folded seq), but two
hosts pointing ``run_remote`` at one server would collide on ids — give
each host a disjoint id range (or its own server) until multi-host id
assignment is plumbed through ``Job``.
"""

from __future__ import annotations

import collections
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Optional

import numpy as np

from distkeras_tpu.data.batching import BatchPlan, apply_round_transform
from distkeras_tpu.netps import wire
from distkeras_tpu.netps.client import CommitResult
from distkeras_tpu.netps.fold import check_discipline
from distkeras_tpu.netps.shards import (is_sharded_endpoint, make_ps_client,
                                        plan_for_model)
from distkeras_tpu.netps.tuner import Tuner, TunerState, autotune_enabled
from distkeras_tpu.resilience import faults as _faults
from distkeras_tpu.runtime import config


def _leaves(tree) -> list:
    import jax

    return [np.asarray(a, np.float32) for a in jax.tree.leaves(tree)]


def _leaf_names(tree) -> list:
    """Stable parameter names for partition rules: the pytree key path of
    each leaf, "/"-joined (``params/dense/kernel``-style for Flax trees)."""
    import jax

    def part(k):
        for attr in ("key", "name", "idx"):
            if hasattr(k, attr):
                return str(getattr(k, attr))
        return str(k).strip("[].'\"")

    paths, _ = jax.tree_util.tree_flatten_with_path(tree)
    names = ["/".join(part(k) for k in path) or f"param_{i:04d}"
             for i, (path, _leaf) in enumerate(paths)]
    # Key paths are unique by construction, but a defensive fallback keeps
    # the plan's name->tensor contract total even for exotic pytrees.
    if len(set(names)) != len(names):
        names = [f"{n}#{i}" for i, n in enumerate(names)]
    return names


def _measured_opt_factor(tx, params) -> float:
    """Optimizer-state bytes per parameter byte, measured from the actual
    transform state (adagrad accumulators ~= 1.0; chained transforms more).
    This is what makes the shard plan budget center + OPTIMIZER memory —
    the per-shard cap is honest about what the shard really holds."""
    import jax

    center = sum(a.nbytes for a in _leaves(params))
    if center <= 0:
        return 0.0
    opt = sum(np.asarray(a).nbytes for a in jax.tree.leaves(tx.init(params)))
    return float(opt) / float(center)


def _worker_round(plan: BatchPlan, r: int, w: int):
    """Worker ``w``'s ``[K, B, ...]`` slice of round ``r`` (each thread
    gathers only its own rows — the per-executor partition)."""
    idx = plan.index[r, w]
    xs, ys = plan.x[idx], plan.y[idx]
    if plan.transform is not None:
        xs4, ys4 = apply_round_transform(
            plan.transform, plan.transform_seed, r, [w],
            xs[None], ys[None])
        xs, ys = xs4[0], ys4[0]
    return xs, ys


class _CommsMeter:
    """Run-wide comms accounting shared by the worker threads: total RPC
    busy time vs the wait the compute loop actually *saw*, plus the
    realized staleness of applied commits — the overlap evidence."""

    def __init__(self):
        self.lock = threading.Lock()
        self.busy = 0.0
        self.wait = 0.0
        self.stale = collections.deque(maxlen=256)

    def timed(self, fn, *args):
        """Run one RPC, charging its duration to ``busy`` (called on the
        comms threads)."""
        t0 = time.monotonic()
        try:
            return fn(*args)
        finally:
            with self.lock:
                self.busy += time.monotonic() - t0

    def blocking(self, fn, *args):
        """An RPC the compute thread itself waits through (round 0's pull,
        the serial loop): busy AND wait — nothing of it was hidden."""
        t0 = time.monotonic()
        try:
            return self.timed(fn, *args)
        finally:
            self.waited(time.monotonic() - t0)

    def waited(self, seconds: float) -> None:
        with self.lock:
            self.wait += seconds

    def commit_staleness(self, staleness: int) -> None:
        from distkeras_tpu import telemetry

        telemetry.histogram("netps.commit.staleness").observe(
            float(staleness))
        with self.lock:
            self.stale.append(int(staleness))
            vals = list(self.stale)
        # The same gauges DisciplineMonitor exports for in-process engines,
        # fed the REALIZED staleness the server charged (which includes any
        # in-flight overlap delay) instead of the analytic rotation.
        telemetry.gauge("discipline.staleness_mean").set(
            float(np.mean(vals)))
        telemetry.gauge("discipline.staleness_max").set(float(max(vals)))

    def export(self) -> None:
        from distkeras_tpu import telemetry

        with self.lock:
            busy, wait = self.busy, self.wait
        if busy > 0:
            telemetry.gauge("netps.overlap.hidden_fraction").set(
                round(max(0.0, min(1.0, 1.0 - wait / busy)), 4))


def run_remote(
    *,
    endpoint: str,
    model,
    tx,
    loss_fn,
    plan: BatchPlan,
    discipline: str = "adag",
    window: int,
    alpha: float = 0.05,
    seed: int = 0,
    compute_dtype=None,
    grad_accum: int = 1,
    timeout: Optional[float] = None,
    retries: Optional[int] = None,
    backoff: Optional[float] = None,
    inflight: Optional[int] = None,
    shards: Optional[int] = None,
    compress: Optional[str] = None,
    transport: Optional[str] = None,
    hier: Optional[bool] = None,
    hier_flush: Optional[float] = None,
    autotune: Optional[bool] = None,
) -> tuple[Any, np.ndarray]:
    """Train ``plan.num_workers`` threads against the PS at ``endpoint``.

    Returns ``(trained_params_tree, losses[rounds, W])`` — the params are
    the server's final center. Rows of ``losses`` for a round a worker's
    commit was discarded (eviction) still carry that worker's local loss;
    NaN marks rounds a worker never ran (it was asleep being evicted).

    ``inflight``/``shards``/``compress``/``transport``/``hier`` default
    from the registry (``DKTPU_NET_INFLIGHT``/``DKTPU_NET_SHARDS``/
    ``DKTPU_NET_COMPRESS``/``DKTPU_NET_TRANSPORT``/``DKTPU_NET_HIER``).

    With ``hier`` on, a per-host :class:`~distkeras_tpu.netps.hier.
    AggregatorServer` is interposed: the worker threads join IT (over the
    shm ring when negotiated — the local hop is exactly where the ring
    pays), it pre-combines their commits and forwards ONE combined commit
    per flush to the root at ``endpoint``, cutting root ingress by the
    worker fan-in. The trained params are still pulled from the ROOT.

    The first joiner seeds an uninitialized server with this model's
    params, so a bare ``python -m distkeras_tpu.netps`` server needs no
    model knowledge.

    With ``autotune`` on (``DKTPU_NET_AUTOTUNE``), a :class:`~distkeras_
    tpu.netps.tuner.controller.Tuner` closes the loop from the live
    gauges to the knobs: join-time codec probes, mid-run inflight/codec/
    striping retunes through :meth:`PSClient.retune`, and the HIER
    topology by the measured fan-in crossover. Knobs the caller (or the
    environment) pinned explicitly are respected as the starting point;
    the controller's guardrails are documented in ``netps/tuner/``.
    """
    import jax

    from distkeras_tpu import telemetry
    from distkeras_tpu.workers import make_local_loop

    check_discipline(discipline)
    W = plan.num_workers
    explicit_inflight = (inflight is not None
                         or config.env_is_set("DKTPU_NET_INFLIGHT"))
    inflight = max(1, int(inflight if inflight is not None
                          else config.env_int("DKTPU_NET_INFLIGHT")))
    autotune = (autotune_enabled() if autotune is None else bool(autotune))
    tuner = None
    if autotune:
        # Explicit knobs win where set; the controller fills the rest.
        # An unpinned inflight starts at 2 (the overlap window must exist
        # before hidden_fraction can be measured) and the control loop
        # walks it from there; an unpinned transport requests the TOP of
        # the demotion ladder (negotiated — a mesh request lands on the
        # device dispatch only against a same-runtime server, on the ring
        # for a same-host one, and cross-host pairs silently stay on TCP).
        tuner = Tuner(W, inflight=inflight if explicit_inflight
                      else max(inflight, 2))
        inflight = tuner.inflight
        if transport is None and not config.env_is_set("DKTPU_NET_TRANSPORT"):
            transport = "mesh"
        if (shards is None and not config.env_is_set("DKTPU_NET_SHARDS")
                and transport not in ("shm", "mesh")):
            # Striping headroom on TCP: connections are sized at
            # construction, so a client that might be retuned UP to 2
            # stripes mid-run needs 2 conns now (active stripes still
            # start join-negotiated). The ring never stripes, so it
            # keeps the single conn.
            shards = 2
    elastic = discipline in ("aeasgd", "eamsgd")
    treedef = jax.tree.structure(model.params)
    init_leaves = _leaves(model.params)
    loop_fn = jax.jit(make_local_loop(
        model.module, loss_fn, tx, compute_dtype=compute_dtype,
        state_collections=model.state_collections, grad_accum=grad_accum,
        normalize_uint8=getattr(model, "normalize_uint8", True)))
    losses = np.full((plan.num_rounds, W), np.nan, np.float32)
    errors: list = []
    base_key = jax.random.key(seed)
    meter = _CommsMeter()
    client_kw = dict(timeout=timeout, retries=retries, backoff=backoff,
                     shards=shards, compress=compress, transport=transport)
    shard_plan = None
    if is_sharded_endpoint(endpoint):
        # Sharded center plane: build THE partition plan once, here, from
        # the model's leaves (names = pytree key paths, so env rules can
        # pin by layer) and the MEASURED optimizer-state factor — every
        # worker client carries it, and the servers hash-validate it at
        # join so plan drift is a typed error, never a silent mis-fold.
        shard_plan = plan_for_model(
            init_leaves, len(wire.split_shard_endpoints(endpoint)),
            names=_leaf_names(model.params),
            opt_factor=_measured_opt_factor(tx, model.params))
        telemetry.event("netps_shard_plan", {
            "shards": shard_plan.num_shards,
            "hash": shard_plan.plan_hash[:12],
            "skew": round(shard_plan.skew(), 4)})
        client_kw["plan"] = shard_plan
    hier = (config.env_bool("DKTPU_NET_HIER") if hier is None else bool(hier))
    if (tuner is not None and not hier
            and not config.env_is_set("DKTPU_NET_HIER")):
        # Nobody pinned the topology: pick it from the measured fan-in
        # crossover (break-even at a fan-in of 4; 2-core CPU box, PR 6,
        # tests/fixtures/hier_curve.json) — hierarchical
        # combining only pays once this host's worker fan-in covers the
        # aggregator's window cost.
        hier = tuner.choose_topology() == "hier"
    agg = None
    worker_endpoint = endpoint
    if hier:
        from distkeras_tpu.netps.hier import AggregatorServer

        # The aggregator seeds the root (joining with this model's params)
        # and serves the local workers — over the shm ring when negotiated.
        agg_kw = {} if hier_flush is None else {"flush_interval": hier_flush}
        agg = AggregatorServer(
            upstream=endpoint, init=init_leaves, discipline=discipline,
            transport=transport, timeout=timeout, retries=retries,
            backoff=backoff, **agg_kw).start()
        worker_endpoint = agg.endpoint
        if tuner is not None:
            tuner.attach_aggregator(agg)

    def unflatten(leaves):
        return jax.tree.unflatten(treedef, [np.asarray(a) for a in leaves])

    def work(w: int) -> None:
        # The factory: a ShardedPSClient when worker_endpoint is a shard
        # matrix, a plain PSClient otherwise (the hier path always hands
        # workers the aggregator's plain endpoint — the aggregator's own
        # upstream client is the sharded one).
        client = make_ps_client(worker_endpoint, worker_id=w, **client_kw)
        pull_client = None
        commit_lane = pull_lane = None
        # With the tuner aboard the lanes always exist — the controller
        # may widen a serial (inflight=1) start into an overlapped one
        # mid-run, and lanes cannot be conjured from inside the loop.
        overlap = inflight > 1 or tuner is not None
        if overlap:
            # Two comms lanes per worker: an ORDERED commit lane (seq order
            # is the exactly-once contract) and a pull-prefetch lane on its
            # own client/connections, so a slow commit cannot serialize the
            # next round's pull behind it.
            commit_lane = ThreadPoolExecutor(
                1, thread_name_prefix=f"netps-commit-{w}")
            pull_lane = ThreadPoolExecutor(
                1, thread_name_prefix=f"netps-pull-{w}")
        try:
            center_leaves, counter = client.join(init=init_leaves)
            if tuner is not None and w == 0:
                # The join-time micro A/B (one worker probes; the winner
                # is published to everyone through the target generation).
                tuner.startup(client, center_leaves)
            tstate = TunerState()
            if overlap:
                pull_client = make_ps_client(worker_endpoint,
                                             worker_id=client.worker_id,
                                             **client_kw)
                # Striping/codec/transport state without a join: adopt the
                # negotiated dialect (membership is by worker_id, not by
                # connection).
                pull_client.adopt_dialect(client, center_leaves)
            params = unflatten(center_leaves)
            opt_state = tx.init(params)
            local = params if elastic else None
            mstate = (jax.tree.map(np.asarray, model.state)
                      if model.state is not None else None)
            readopt = False
            rejoins_seen = 0
            pending: collections.deque = collections.deque()
            next_pull = None

            def rejoins() -> int:
                n = client.rejoin_count
                if pull_client is not None:
                    n += pull_client.rejoin_count
                return n

            def guarded_commit(delta, counter, epoch):
                # Ordered-lane lineage guard: a commit queued BEFORE an
                # eviction-triggered rejoin (its delta was computed from
                # the pre-eviction pull lineage) must be discarded, not
                # folded into the fresh center — the same "discard the
                # stale window" semantics the serial loop gets for free.
                # The lane is ordered, so by the time this runs any rejoin
                # caused by an earlier queued commit is already counted.
                if rejoins() != epoch:
                    return CommitResult(applied=False, duplicate=False,
                                        evicted=True, updates=-1,
                                        staleness=-1)
                return client.commit(delta, counter)

            def drain_one() -> None:
                nonlocal readopt
                _r, fut = pending.popleft()
                t0 = time.monotonic()
                res = fut.result()
                meter.waited(time.monotonic() - t0)
                if res.evicted:
                    # The lease lapsed with this commit in flight: it was
                    # discarded and the client already re-joined. Start
                    # over from the fresh center at the next pull.
                    readopt = True
                elif res.applied:
                    meter.commit_staleness(res.staleness)

            for r in range(plan.num_rounds):
                net = _faults.active_net_plan()
                if net is not None and net.poison_worker(r, W) == w:
                    arg = net.fire("evict", r)
                    if arg is not None:
                        # Go silent past the lease: the server evicts us;
                        # the next RPC re-joins and we continue.
                        lease = client.lease_s or 1.0
                        time.sleep(arg if arg > 0 else 2.0 * lease)
                if next_pull is not None:
                    t0 = time.monotonic()
                    pulled_leaves, counter = next_pull.result()
                    meter.waited(time.monotonic() - t0)
                    next_pull = None
                else:
                    pulled_leaves, counter = meter.blocking(client.pull)
                if rejoins() > rejoins_seen or readopt:
                    # Evicted while away: the rejoining worker re-adopts
                    # the center (fresh replica + optimizer — the
                    # reference's PS-pull join semantics).
                    rejoins_seen = rejoins()
                    readopt = False
                    if elastic:
                        local = unflatten(pulled_leaves)
                        opt_state = tx.init(local)
                if tuner is not None:
                    if w == 0:
                        # Keep the overlap gauge live so the control loop
                        # reads this run's evidence, not a stale export.
                        meter.export()
                        tuner.maybe_decide(r, client.active_transport)
                    if tuner.generation != tstate.generation:
                        # Quiesce the ordered lane before touching the
                        # dialect: one logical commit finishes under ONE
                        # codec/striping (exactly-once needs nothing more
                        # — a retransmit keeps its seq either way).
                        while pending:
                            drain_one()
                        changed = tuner.apply_to(client, pulled_leaves,
                                                 tstate)
                        if changed and pull_client is not None:
                            pull_client.adopt_dialect(client, pulled_leaves)
                start = local if elastic else unflatten(pulled_leaves)
                xs, ys = _worker_round(plan, r, w)
                rng = jax.random.fold_in(jax.random.fold_in(base_key, w), r)
                new_params, opt_state, mstate, window_losses = loop_fn(
                    start, opt_state, xs, ys, rng, mstate)
                new_leaves = _leaves(new_params)
                pulled_np = [np.asarray(a, np.float32)
                             for a in pulled_leaves]
                if elastic:
                    e = [alpha * (n - p)
                         for n, p in zip(new_leaves, pulled_np)]
                    local = unflatten([n - d
                                       for n, d in zip(new_leaves, e)])
                    delta = e
                else:
                    delta = [n - p for n, p in zip(new_leaves, pulled_np)]
                    if discipline == "adag":
                        delta = [d / float(window) for d in delta]
                if commit_lane is not None:
                    # The tuner retargets the window mid-run; a narrowed
                    # window simply drains deeper before the next submit.
                    bound = tuner.inflight if tuner is not None else inflight
                    while len(pending) >= max(1, bound):
                        drain_one()
                    fut = commit_lane.submit(
                        meter.timed, guarded_commit, delta, counter,
                        rejoins())
                    pending.append((r, fut))
                    if r + 1 < plan.num_rounds:
                        next_pull = pull_lane.submit(
                            meter.timed, pull_client.pull)
                else:
                    res = meter.blocking(client.commit, delta, counter)
                    if res.evicted:
                        readopt = True
                    elif res.applied:
                        meter.commit_staleness(res.staleness)
                losses[r, w] = float(np.mean(np.asarray(window_losses)))
            while pending:
                drain_one()
            if tuner is not None and w == 0:
                # The converged dialect + decision counts, for the report
                # (read from the event stream).
                tuner.export_summary(client)
            client.leave()
        except BaseException as e:  # noqa: BLE001 - surface on main thread
            errors.append(e)
        finally:
            if commit_lane is not None:
                commit_lane.shutdown(wait=True)
            if pull_lane is not None:
                pull_lane.shutdown(wait=True)
            if pull_client is not None:
                pull_client.close()
            client.close()

    try:
        with telemetry.span("netps.remote_train"):
            threads = [threading.Thread(target=work, args=(w,),
                                        name=f"netps-worker-{w}")
                       for w in range(W)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
    finally:
        if agg is not None:
            # Flushes any half-accumulated combined commit upstream before
            # the final pull below reads the root's center.
            agg.close()
    if inflight > 1 or tuner is not None:
        # The gauge is OVERLAP evidence; the serial loop hides nothing by
        # construction, so exporting there would just report its absence.
        meter.export()
    if errors:
        raise errors[0]
    with make_ps_client(endpoint, plan=shard_plan, timeout=timeout,
                        retries=retries, backoff=backoff,
                        transport=transport) as observer:
        final_leaves, _updates = observer.pull()
    return unflatten(final_leaves), losses
