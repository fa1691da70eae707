"""Run-level configuration and the typed environment-variable registry.

Two surfaces live here:

* :class:`RunConfig` — the reference keeps every hyperparameter as a trainer
  ``__init__`` kwarg (``distkeras/trainers.py``: ``num_workers``,
  ``batch_size``, ``num_epoch``, ``communication_window``, ``learning_rate``,
  ``master_port``...). The trainers keep that kwargs-first surface and
  normalize into this frozen dataclass (``Trainer.config``); the kwarg names
  remain live as properties delegating here.

* The ``DKTPU_*`` **environment registry** — the single home for every
  environment variable the framework reads. Each variable is declared once
  as an :class:`EnvVar` (name, type, default, doc, category) and read
  through the typed ``env_*`` accessors below. This is the only module
  allowed to touch ``os.environ``; the dk-check rule DK301
  (``distkeras_tpu/analysis``) enforces that, DK302 rejects undeclared
  ``DKTPU_*`` names anywhere in the package, and DK303 keeps the
  auto-generated docs tables (``python -m distkeras_tpu.analysis
  --write-env-docs``) in sync with this registry.

This module must stay importable without jax (the analyzer and the
telemetry core import it; telemetry is contractually jax-free), so the
dtype table resolves lazily.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional


@dataclasses.dataclass(frozen=True)
class RunConfig:
    batch_size: int = 32
    num_epoch: int = 1
    communication_window: int = 5
    learning_rate: float = 0.01
    num_workers: Optional[int] = None  # None -> all devices
    compute_dtype: Optional[str] = None  # "bfloat16" is MXU-native; params stay f32
    seed: int = 0
    shuffle: bool = False
    drop_remainder: bool = True

    @property
    def dtype(self):
        import jax.numpy as jnp  # lazy: keep this module importable sans jax

        return {None: None, "float32": jnp.float32,
                "bfloat16": jnp.bfloat16, "float16": jnp.float16}[
                    self.compute_dtype]

    def replace(self, **kw) -> "RunConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Environment-variable registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class EnvVar:
    """One declared environment variable: the registry row.

    ``kind`` is the accessor family (``bool``/``int``/``float``/``str``);
    ``default`` is what an unset or empty variable reads as (``None`` means
    "no value configured"). ``doc`` is one rendered sentence — it IS the
    docs-table cell, keep it self-contained.
    """

    name: str
    kind: str
    default: object
    doc: str
    # "observability" | "resilience" | "network" | "fleet" | "serving" |
    # "data" | "streaming" | "interop" | "sim"
    category: str


def _declare(*vars_: EnvVar) -> dict:
    reg: dict = {}
    for v in vars_:
        if v.name in reg:
            raise ValueError(f"duplicate EnvVar {v.name!r}")
        reg[v.name] = v
    return reg


ENV_REGISTRY: dict = _declare(
    EnvVar("DKTPU_TELEMETRY", "bool", True,
           "Master switch for the telemetry registry; `0` swaps every "
           "span/counter/gauge/histogram for a no-op singleton.",
           "observability"),
    EnvVar("DKTPU_TRACE", "bool", False,
           "Fleet-wide distributed tracing (`telemetry/tracing/`): commit "
           "and serve requests carry a `(trace, parent)` context across "
           "processes (capability-gated — peers without `CAPS['tracing']` "
           "see zero new bytes) and every process records span/flight "
           "evidence. Off by default: no trace ids, no extra wire fields, "
           "no span records.",
           "observability"),
    EnvVar("DKTPU_TRACE_DIR", "str", "",
           "Directory for per-process trace streams "
           "(`trace-<role>-<pid>.jsonl`, appended per span so a SIGKILL "
           "loses at most one torn line) and flight-recorder dumps "
           "(`flight-<role>-<pid>.jsonl`). Empty = fall back to "
           "`DKTPU_PS_STATE_DIR`; with neither set, spans still ride the "
           "in-memory telemetry event stream and the flight ring.",
           "observability"),
    EnvVar("DKTPU_TRACE_RING", "int", 256,
           "Flight-recorder capacity: recent telemetry events + trace "
           "spans kept in a bounded in-memory ring per process, dumped on "
           "fault injection, epoch fencing, SIGTERM, and unhandled crash.",
           "observability"),
    EnvVar("DKTPU_TRACE_ROLE", "str", "",
           "Role label (`ps`, `standby`, `shard0`, `worker1`, `serve`, "
           "...) stamped into every trace/flight/process-info record this "
           "process writes; the netps CLI and the fleet `Job` launcher set "
           "it automatically, so only hand-launched processes need it.",
           "observability"),
    EnvVar("DKTPU_TELEMETRY_ROTATE_MB", "float", 0.0,
           "Size bound (MiB) for telemetry/trace JSONL files: a file at or "
           "over the bound is rotated (atomic rename to `<path>.<n>`, "
           "generations numbered from 1) before the next append; the "
           "collector reads generations in order. 0 = no rotation "
           "(unbounded growth under streaming workloads).",
           "observability"),
    EnvVar("DKTPU_HEALTH_TARGETS", "str", "",
           "Ad-hoc scrape targets for the health plane's `MetricsHub`: "
           "`[name=]host:port` entries separated by `;` (or `,`), merged "
           "with the in-process registry fleet components populate "
           "automatically. Re-read every sweep, so targets can be added "
           "while the hub runs.",
           "observability"),
    EnvVar("DKTPU_HEALTH_INTERVAL", "float", 2.0,
           "Seconds between `MetricsHub` scrape sweeps over the registered "
           "targets (each sweep is one `stats` frame per target — no "
           "membership, no lease traffic).",
           "observability"),
    EnvVar("DKTPU_HEALTH_RING", "int", 240,
           "Points kept per metric time-series ring in the hub (per "
           "target, per metric). At the default 2 s interval, 240 points "
           "is an 8-minute window — enough to cover the default slow "
           "burn-rate window with slack.",
           "observability"),
    EnvVar("DKTPU_HEALTH_DOWN_AFTER", "int", 3,
           "Consecutive missed scrapes after which a previously-reachable "
           "target is declared down (the `target_down` sentinel fires a "
           "page alert; supervisors consulting `MetricsHub.is_down` may "
           "restart it).",
           "observability"),
    EnvVar("DKTPU_SIM_SEED", "int", 0,
           "Default RNG seed for the fleet simulator (`distkeras_tpu.sim`): "
           "every `SimEngine()` built without an explicit seed draws from "
           "one `random.Random(seed)`, so two runs of the same scenario are "
           "bit-identical. Pass `--seed` / `SimEngine(seed=...)` to "
           "override per run.",
           "sim"),
    EnvVar("DKTPU_SIM_BAND_PCT", "float", 20.0,
           "Calibration tolerance (percent) for the simulator's replay "
           "gates: `sim_drift` and the `hier_crossover` held-out "
           "predictions must land within this band of the measured "
           "throughput or the gate reports a miss.",
           "sim"),
    EnvVar("DKTPU_HEALTH_SLO", "str", "",
           "SLO specs for the health plane: inline JSON (starts with `[` "
           "or `{`) or a path to a JSON file. Each spec names a hub "
           "metric, a stat (`value`/`mean`/`rate`/`p99`/...), one bound "
           "(`max` or `min`), burn-rate windows (`fast_s`/`slow_s`), and "
           "a severity (`page` dumps the flight recorder on fire).",
           "observability"),
    EnvVar("DKTPU_VITALS_S", "float", 0.0,
           "Process-vitals sample interval (seconds): periodic "
           "`runtime.rss_mb`, `runtime.open_fds`, and (in a process that "
           "already owns a jax device) `device.bytes_in_use` gauges "
           "feeding the hub via the stats op. 0 = off; the netps CLI and "
           "the serving frontend start the sampler when set.",
           "observability"),
    EnvVar("DKTPU_NAN_GUARD", "bool", True,
           "On-device NaN/Inf round skip in the engine round bodies; `0` "
           "disables (poisoned rounds then propagate into the center).",
           "resilience"),
    EnvVar("DKTPU_CKPT_DIGEST", "bool", True,
           "sha256 integrity sidecars next to each checkpoint step; `0` "
           "disables writing (and therefore verified restore).",
           "resilience"),
    EnvVar("DKTPU_DIVERGENCE_RESET", "float", None,
           "Opt-in divergent-worker reset threshold: a worker whose loss "
           "strays more than this from the finite worker mean re-adopts the "
           "center. Unset = off (the default path never fetches the loss).",
           "resilience"),
    EnvVar("DKTPU_FEEDER_WARN", "float", 1.0,
           "Seconds of input-pipeline silence before the first stall "
           "warning; later warnings back off exponentially (2x, 4x, ...).",
           "resilience"),
    EnvVar("DKTPU_FEEDER_TIMEOUT", "float", 300.0,
           "Seconds of input-pipeline silence after which the RoundFeeder "
           "declares the data plane dead with `FeederStalledError`.",
           "resilience"),
    EnvVar("DKTPU_FEEDER_RETRIES", "int", 0,
           "Retries (exponential backoff) for a *failed* feeder stage call "
           "before the error propagates; 0 = off.",
           "resilience"),
    EnvVar("DKTPU_FAULTS", "str", "",
           "Fault-injection plan, `kind@round[:arg]` entries separated by "
           "`;` (e.g. `nan@3;stall@5:0.5;crash@7;seed=11`). Empty = no "
           "injection. See docs/RESILIENCE.md for the fault taxonomy.",
           "resilience"),
    EnvVar("DKTPU_FAULTS_STATE", "str", "",
           "Path to the fired-faults journal so one-shot faults (notably "
           "`kill@R`) survive the process restart they cause. Empty = "
           "in-memory only.",
           "resilience"),
    EnvVar("DKTPU_NET_TIMEOUT", "float", 30.0,
           "Per-attempt RPC deadline (seconds) for every netps network "
           "operation: connect, send, and the full reply all fit inside it.",
           "network"),
    EnvVar("DKTPU_NET_RETRIES", "int", 5,
           "Retries after the first attempt for a retryable netps RPC "
           "failure (timeout, connection loss, framing error); the typed "
           "rejections (draining, lease expired) never retry.",
           "network"),
    EnvVar("DKTPU_NET_BACKOFF", "float", 0.05,
           "Base of the netps retry backoff: each retry sleeps a "
           "full-jitter draw from [0, base * 2^attempt), capped — "
           "decorrelated, so a partition's W victims don't retry in "
           "lockstep.",
           "network"),
    EnvVar("DKTPU_NET_MAX_FRAME", "int", 1 << 30,
           "Largest wire frame (bytes) either netps side will accept; "
           "oversized frames are rejected before any allocation.",
           "network"),
    EnvVar("DKTPU_NET_INFLIGHT", "int", 1,
           "Max un-ACKed netps commits a remote worker may have in flight "
           "while it computes ahead (compute/comms overlap); 1 = the serial "
           "pull -> compute -> commit loop. Staleness accounting always "
           "reflects the realized in-flight delay.",
           "network"),
    EnvVar("DKTPU_NET_COMPRESS", "str", "none",
           "Delta codec for netps commits: `none` (f32), `bf16` (truncate), "
           "or `int8` (per-tensor scale + client-side error-feedback "
           "residual). Capability-negotiated at join — a server without the "
           "codec silently falls back to `none`.",
           "network"),
    EnvVar("DKTPU_NET_SHARDS", "int", 1,
           "Connections a netps client stripes each pull/commit's tensors "
           "across (concurrent per-shard RPCs, reassembled before "
           "fold/adopt); 1 = one socket. Negotiated at join; one logical "
           "commit keeps ONE seq across all stripes (exactly-once).",
           "network"),
    EnvVar("DKTPU_NET_TRANSPORT", "str", "tcp",
           "netps wire dialect: `tcp` (default), `shm` — colocated "
           "peers (boot-id match, negotiated in the join reply) move "
           "payloads through a shared-memory ring with a UDS doorbell — "
           "or `mesh`: same-RUNTIME peers (boot-id + pid match) fold "
           "straight into the server's device-resident center through an "
           "in-process dispatch, zero wire bytes, with the shm ring "
           "negotiated alongside as the demotion target (mesh -> shm -> "
           "tcp). Old peers, cross-process, and cross-host pairs "
           "silently stay on the lower dialects with every guarantee "
           "intact.",
           "network"),
    EnvVar("DKTPU_NET_HIER", "bool", False,
           "Hierarchical two-level folds: each `run_remote` host "
           "interposes a per-host aggregator that pre-combines its "
           "workers' commits and forwards one combined commit upstream, "
           "cutting root ingress by the worker fan-in (combined commit's "
           "pull counter = min of constituents).",
           "network"),
    EnvVar("DKTPU_NET_FAULTS", "str", "",
           "Network-fault chaos plan for the netps proxy, shm ring, "
           "remote worker loop, PS server, and fleet scheduler: "
           "`kind@frame[:arg]` entries (`delay`/`drop`/"
           "`dup`/`truncate`/`partition`/`evict`, `_r` suffix = reply "
           "direction; `shm_delay`/`shm_corrupt` hit the shared-memory "
           "ring; `ps_crash`/`ps_hang` hit the server process; `preempt` "
           "drives the FleetScheduler's forced-preemption drill; "
           "`serve_slow`/`serve_drop` hit the serving frontend's request "
           "stream; `mesh_down@R` severs the device-mesh dispatch at "
           "commit seq R, forcing the mesh->shm/TCP demotion drill; "
           "`link_down`/`link_flap` black-hole one aggregation-tree "
           "uplink, keyed by `TreeSpec.link_key(level, group)`) "
           "separated by `;`, e.g. `delay@3:0.2;drop@5;partition@7:2`. "
           "Empty = no injection. See docs/RESILIENCE.md.",
           "network"),
    EnvVar("DKTPU_TREE_SPEC", "str", "",
           "Aggregation-tree shape, bottom-up: `name:fanout[:codec]` "
           "levels separated by `,`, e.g. `host:8,pool:4,region:2` — "
           "workers flush into level-0 nodes, each level folds `fanout` "
           "children into one combined commit, the top level flushes into "
           "the root PS. A level's optional codec pins its uplinks "
           "(`region:2:int8`); otherwise each link probes its own. Empty "
           "= flat star (or the single `DKTPU_NET_HIER` level).",
           "network"),
    EnvVar("DKTPU_TREE_BUFFER", "int", 32,
           "Partition ride-through bound: combined windows a tree node "
           "buffers while its uplink is black-holed. The buffer drains "
           "in-order on heal (exactly-once end-to-end); past the bound "
           "the OLDEST windows degrade to counted, typed drops "
           "(`netps_tree_window_drop`) the staleness rule absorbs.",
           "network"),
    EnvVar("DKTPU_TREE_DEMOTE_AFTER", "int", 3,
           "Consecutive uplink transport failures before a tree node "
           "demotes that one link to plain TCP (per-link shm->TCP "
           "fallback, dedup-preserving redial); a healthy streak "
           "renegotiates back up. 0 disables auto-demotion.",
           "network"),
    EnvVar("DKTPU_PS_LEASE", "float", 10.0,
           "Membership lease (seconds) the netps server grants on join and "
           "renews on every pull/commit/heartbeat; a worker silent past it "
           "is evicted and training continues with the survivors.",
           "network"),
    EnvVar("DKTPU_PS_ENDPOINT", "str", "",
           "Endpoint(s) of a running netps parameter server: `host:port`, "
           "or a comma-separated `primary:port,standby:port` list the "
           "client walks on failure/`not_primary` (failover); async "
           "trainers use it when `remote=` is not passed explicitly "
           "(`Job` sets it for every launched worker).",
           "network"),
    EnvVar("DKTPU_PS_STATE_DIR", "str", "",
           "Directory for the netps server's durable state (write-ahead "
           "commit journal + periodic center snapshots + sha256 sidecars); "
           "a restarted server recovers center/counter/dedup state from it "
           "and in-flight commits retransmit exactly-once. Empty = "
           "in-memory only (a PS crash loses every fold).",
           "network"),
    EnvVar("DKTPU_PS_SNAPSHOT_EVERY", "int", 500,
           "Folds between netps center snapshots when a state dir is set; "
           "each snapshot rotates + compacts the journal, so on-disk state "
           "stays bounded at ~2 snapshots plus the commits between them. "
           "0 disables snapshots (journal-only, unbounded).",
           "network"),
    EnvVar("DKTPU_PS_STANDBY", "str", "",
           "`host:port` of the PRIMARY a `python -m distkeras_tpu.netps` "
           "process should run as a warm standby of: it tails the "
           "primary's journal stream over the wire (`replicate` frames), "
           "promotes itself when the primary's lease lapses, and fences "
           "the old epoch. Empty = run as a primary.",
           "network"),
    EnvVar("DKTPU_NET_AUTOTUNE", "bool", False,
           "Self-tuning data plane (`netps/tuner/`): join-time micro A/B "
           "probes pick the codec per connection, and an online control "
           "loop over the live gauges retunes compression/inflight/"
           "striping mid-run through the existing renegotiation paths, "
           "with hysteresis and an oscillation fallback to the static "
           "knobs. Explicit `DKTPU_NET_*` knobs still win where set. "
           "Off by default.",
           "network"),
    EnvVar("DKTPU_TUNE_INTERVAL", "int", 8,
           "Rounds between online-controller evaluations when "
           "`DKTPU_NET_AUTOTUNE=1` — the control loop's clock; larger "
           "values react slower but measure cleaner windows.",
           "network"),
    EnvVar("DKTPU_TUNE_COOLDOWN", "int", 16,
           "Rounds a knob rests after the controller retunes it "
           "(per-knob hysteresis) — a knob can never be retuned faster "
           "than this regardless of what the gauges say.",
           "network"),
    EnvVar("DKTPU_TUNE_PROBES", "int", 3,
           "Timed probe round trips per candidate codec in the join-time "
           "micro A/B (each carries the full center payload; the score "
           "is logical f32 bytes per second of round trip).",
           "network"),
    EnvVar("DKTPU_TUNE_MAX_RETUNES", "int", 8,
           "Total mid-run retunes the controller may take before it "
           "freezes at whatever it converged to (bounded retune rate).",
           "network"),
    EnvVar("DKTPU_TUNE_OSC_LIMIT", "int", 3,
           "Consecutive back-to-previous flips of one knob before the "
           "controller declares oscillation, restores that knob's static "
           "initial value, and freezes it for the rest of the run.",
           "network"),
    EnvVar("DKTPU_TUNE_HIER_FANIN", "int", 4,
           "Per-host worker fan-in at/above which the controller picks "
           "hierarchical aggregation over flat topology (the recorded "
           "`hier_curve` crossover, 2-core CPU box, PR 6; below it the "
           "aggregator's combining window costs more than it saves).",
           "network"),
    EnvVar("DKTPU_TUNE_MIN_GAIN", "float", 0.1,
           "Fractional commit-rate improvement a grown worker count must "
           "show over the best smaller count for the fleet scheduler's "
           "marginal-throughput policy to keep expanding that job "
           "(`netps/tuner/fleet.py`).",
           "network"),
    EnvVar("DKTPU_TUNE_HIDDEN_FLOOR", "float", 0.5,
           "Target floor for `netps.overlap.hidden_fraction`: measured "
           "overlap below it means comms the compute loop still sees, "
           "and the controller widens inflight / shrinks the wire.",
           "network"),
    EnvVar("DKTPU_TUNE_STALE_CEIL", "float", 4.0,
           "Ceiling for `discipline.staleness_mean` (rounds): measured "
           "staleness above it means the overlap window outran the "
           "center, and the controller narrows inflight.",
           "network"),
    EnvVar("DKTPU_PS_SHARD_RULES", "str", "",
           "Partition rules for the sharded center plane: `regex=target` "
           "entries separated by `;`, first match wins, where target is a "
           "shard index (pin) or `split` (row-split across all shards); "
           "parameters matching no rule are byte-balanced greedily. Empty "
           "= fully rule-free balancing. See docs/SHARDING.md.",
           "sharding"),
    EnvVar("DKTPU_PS_SHARD_CAP_BYTES", "int", 0,
           "Per-shard byte budget (center + optimizer-state factor) the "
           "PartitionPlan must fit: tensors over the cap row-split, and a "
           "plan whose fattest shard still exceeds it is a typed "
           "`ShardPlanError` at build time — never an OOM at fold time. "
           "0 = unlimited.",
           "sharding"),
    EnvVar("DKTPU_PS_SHARD_OPT_FACTOR", "float", -1.0,
           "Optimizer-state byte multiplier the plan budgets per parameter "
           "byte (adagrad accumulators ~= 1.0): shard load = center bytes "
           "x (1 + factor). Negative = measure it from the transform's "
           "actual state leaves at launch (`plan_for_model`).",
           "sharding"),
    EnvVar("DKTPU_FLEET_CAPACITY", "int", 0,
           "Worker-slot capacity of a FleetScheduler constructed without an "
           "explicit `capacity=`; 0 = no default (the constructor then "
           "requires one).",
           "fleet"),
    EnvVar("DKTPU_FLEET_TICK", "float", 0.05,
           "Seconds between FleetScheduler passes in `run()`/`start()` "
           "(reap finished workers, fire preempt faults, place queued "
           "jobs, expand elastically).",
           "fleet"),
    EnvVar("DKTPU_FLEET_PREEMPT_GRACE", "float", 0.0,
           "Seconds a preempted worker gets to exit at a round boundary "
           "before the scheduler revokes its lease on the job's parameter "
           "server; 0 = revoke immediately (the worker's in-flight window "
           "is discarded by the eviction path, never double-folded).",
           "fleet"),
    EnvVar("DKTPU_FLEET_QUOTA", "str", "",
           "Per-tenant worker-slot quotas for a FleetScheduler constructed "
           "without explicit `quotas=`: `tenant=N` entries separated by "
           "`;` (e.g. `acme=4;bidco=2`). Empty = every tenant may use the "
           "whole pool.",
           "fleet"),
    EnvVar("DKTPU_FLEET_MAX_RESTARTS", "int", 3,
           "Per-job budget of crashed-worker restarts the FleetScheduler "
           "performs before declaring the job failed and draining it.",
           "fleet"),
    EnvVar("DKTPU_SERVE_MAX_WAIT_MS", "float", 5.0,
           "Latency budget (milliseconds) the serving micro-batcher waits "
           "to coalesce concurrent requests into one batch before "
           "dispatching whatever it holds; 0 = dispatch immediately "
           "(batch = whatever arrived together).",
           "serving"),
    EnvVar("DKTPU_SERVE_BUCKETS", "str", "1,4,16,64,256",
           "Comma-separated ascending batch-size buckets the serving "
           "frontend pads every micro-batch up to; jit compiles one "
           "program per bucket at warmup, so ragged request batches never "
           "retrace. The largest bucket is also the per-batch row cap.",
           "serving"),
    EnvVar("DKTPU_SERVE_QUEUE", "int", 256,
           "Admission-control bound on rows queued in the serving "
           "frontend; a request that would overflow it is shed with a "
           "typed `overloaded` reply BEFORE being accepted (an accepted "
           "request is never silently dropped).",
           "serving"),
    EnvVar("DKTPU_SERVE_DEADLINE_MS", "float", None,
           "Optional per-request serving deadline (milliseconds, measured "
           "from admission): a queued request older than this is answered "
           "with a typed `deadline` reply instead of being computed — "
           "shedding work nobody is waiting for anymore. Unset = no "
           "deadline.",
           "serving"),
    EnvVar("DKTPU_SERVE_POLL_S", "float", 2.0,
           "Seconds between ModelRegistry checkpoint-directory polls for "
           "hot-swap candidates; each newer intact step is restored "
           "(sha256-verified), warmup-probed, and swapped in atomically "
           "between batches.",
           "serving"),
    EnvVar("DKTPU_NO_NATIVE", "bool", False,
           "`1` disables the native (C++) data-plane kernels; every gather "
           "falls back to numpy (bit-identical, slower).",
           "data"),
    EnvVar("DKTPU_STREAM_POLL_S", "float", 0.05,
           "Seconds a FileTailSource sleeps between polls of its feed file "
           "when no complete frame is available yet (the tail-follow "
           "cadence).",
           "streaming"),
    EnvVar("DKTPU_STREAM_RECONNECT_S", "float", 10.0,
           "Cap (seconds) on a SocketSource's exponential reconnect "
           "backoff after the feed connection drops; each reconnect "
           "resumes delivery at the next undelivered record index.",
           "streaming"),
    EnvVar("DKTPU_STREAM_EVAL_FAST", "int", 64,
           "Fast (recent) window size, in committed items, of the "
           "streaming windowed eval — the numerator of the drift ratio.",
           "streaming"),
    EnvVar("DKTPU_STREAM_EVAL_SLOW", "int", 512,
           "Slow (baseline) window size, in committed items, of the "
           "streaming windowed eval — the denominator of the drift ratio.",
           "streaming"),
    EnvVar("DKTPU_STREAM_DRIFT_FACTOR", "float", 2.0,
           "Fast-window/slow-window loss ratio past which the streaming "
           "DriftWatch declares drift: the `stream:loss_divergence` page "
           "fires and checkpoint-on-drift triggers.",
           "streaming"),
    EnvVar("DKTPU_STREAM_REGRESS_FLOOR", "float", 0.25,
           "Fractional regression tolerance of the hot-swap quality gate: "
           "a candidate whose held-out loss exceeds the best accepted "
           "loss by more than this fraction is refused "
           "(rollback-on-regression).",
           "streaming"),
    EnvVar("DKTPU_STREAM_CKPT_EVERY", "int", 16,
           "Committed items between streaming center checkpoints (the "
           "hot-swap cadence); drift detection forces an immediate "
           "checkpoint regardless. 0 disables interval checkpoints.",
           "streaming"),
    EnvVar("DKTPU_STREAM_MAX_PENDING", "int", 8,
           "Backpressure bound on stream records admitted but not yet "
           "claimed by a worker; the reader blocks at this depth so a "
           "fast feed cannot balloon host memory.",
           "streaming"),
    # Interop variables (not DKTPU_-prefixed): written, never branched on.
    EnvVar("KERAS_BACKEND", "str", "",
           "Set (never read for branching) to `jax` before any keras import "
           "so the Keras-3 adapter runs on the JAX backend.",
           "interop"),
    EnvVar("KERAS_HOME", "str", "",
           "Written by `utils.set_keras_base_directory` (reference-parity "
           "shim) to point Keras-3's home at `<path>/.keras`.",
           "interop"),
)

_FALSE_STRINGS = frozenset({"0", "false", "no", "off"})


def _entry(name: str, kind: str) -> EnvVar:
    var = _registered(name)
    if var.kind != kind:
        raise TypeError(
            f"{name} is registered as kind={var.kind!r}; read it with "
            f"env_{var.kind}()")
    return var


def _raw(name: str) -> str:
    return os.environ.get(name, "").strip()


def env_bool(name: str) -> bool:
    """Registered boolean: unset/empty reads the declared default; any other
    value is truthy unless it is one of ``0/false/no/off``."""
    var = _entry(name, "bool")
    raw = _raw(name)
    if not raw:
        return bool(var.default)
    return raw.lower() not in _FALSE_STRINGS


def env_int(name: str) -> int:
    var = _entry(name, "int")
    raw = _raw(name)
    return int(raw) if raw else int(var.default)


def env_float(name: str) -> Optional[float]:
    """Registered float; a ``None`` default means "unset reads as None"
    (used for opt-in thresholds like ``DKTPU_DIVERGENCE_RESET``)."""
    var = _entry(name, "float")
    raw = _raw(name)
    if raw:
        return float(raw)
    return None if var.default is None else float(var.default)


def env_str(name: str) -> str:
    var = _entry(name, "str")
    return os.environ.get(name, "").strip() or str(var.default)


def _registered(name: str) -> EnvVar:
    """Registry row for ``name`` regardless of kind (write accessors)."""
    var = ENV_REGISTRY.get(name)
    if var is None:
        raise KeyError(
            f"{name!r} is not a registered environment variable; declare it "
            "in distkeras_tpu.runtime.config.ENV_REGISTRY (dk-check DK302)")
    return var


def env_is_set(name: str) -> bool:
    """Whether a registered variable was EXPLICITLY set (even to its
    default value) — for callers whose own defaulting must yield to an
    operator's explicit choice (e.g. the autotuner never overrides a
    hand-set knob)."""
    _registered(name)
    return name in os.environ


def env_set(name: str, value: str) -> None:
    """Write a registered variable (interop shims only)."""
    _registered(name)
    os.environ[name] = value


def env_setdefault(name: str, value: str) -> str:
    _registered(name)
    return os.environ.setdefault(name, value)


# -- docs generation --------------------------------------------------------

def iter_env_vars(category: Optional[str] = None):
    for var in ENV_REGISTRY.values():
        if category is None or var.category == category:
            yield var


def render_env_table(category: Optional[str] = None) -> str:
    """The markdown env-var table for ``category`` (None = all, with a
    category column). Injected between ``<!-- dk-env:begin ... -->`` /
    ``<!-- dk-env:end -->`` markers by ``--write-env-docs``; DK303 fails CI
    when a docs table no longer matches this rendering."""
    rows = list(iter_env_vars(category))
    with_cat = category is None
    head = "| Variable | Type | Default | Description |"
    sep = "|---|---|---|---|"
    if with_cat:
        head = "| Variable | Type | Default | Category | Description |"
        sep = "|---|---|---|---|---|"
    out = [head, sep]
    for v in rows:
        default = "unset" if v.default in (None, "") else f"`{v.default}`"
        cells = [f"`{v.name}`", v.kind, default]
        if with_cat:
            cells.append(v.category)
        cells.append(v.doc)
        out.append("| " + " | ".join(cells) + " |")
    return "\n".join(out)


def splice_env_docs(text: str, path_hint: str = "") -> str:
    """Replace every ``<!-- dk-env:begin [category=X] -->`` ...
    ``<!-- dk-env:end -->`` block in ``text`` with the freshly rendered
    table for that category."""
    import re

    def sub(m) -> str:
        category = m.group("cat") or None
        return (m.group("open") + "\n" + render_env_table(category)
                + "\n" + m.group("close"))

    pat = re.compile(
        r"(?P<open><!-- dk-env:begin(?: category=(?P<cat>[\w-]+))? -->)"
        r".*?(?P<close><!-- dk-env:end -->)",
        re.DOTALL)
    out, n = pat.subn(sub, text)
    if n == 0 and path_hint:
        raise ValueError(f"no dk-env marker block found in {path_hint}")
    return out
