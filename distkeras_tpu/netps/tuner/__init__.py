"""Self-tuning data plane: the controller that closes the loop from
telemetry to knobs.

The data plane's knob space (``DKTPU_NET_INFLIGHT`` / ``COMPRESS`` /
``SHARDS`` / ``TRANSPORT`` / ``HIER``) is context-dependent by our own
measurements (2-core CPU box, PR 6): int8 wins on cross-host TCP but
loses on the shm ring (quantize cost exceeds bytes saved at memcpy
speed), and hierarchical aggregation only beats flat topology above a
~4-worker fan-in. Nobody
hand-tunes env vars per job at fleet scale, so — gated by
``DKTPU_NET_AUTOTUNE=1``, off by default — this package:

* runs **join-time micro A/B probes** (:mod:`~distkeras_tpu.netps.tuner.
  probe`): a few timed probe ops per candidate codec, piggybacked on the
  existing capability negotiation (a peer without the ``tuner`` caps bit
  simply answers the typed unknown-op error and is left alone — old peers
  are unaffected);
* runs an **online control loop** (:class:`~distkeras_tpu.netps.tuner.
  controller.Tuner`) over the gauges the run already exports
  (``netps.overlap.hidden_fraction``, ``discipline.staleness_mean``,
  ``netps.fold.tensors_per_sec``, ``netps.hier.fan_in``) and retunes
  compression / inflight / striping mid-run through the existing
  renegotiation paths (:meth:`PSClient.retune` + ``adopt_dialect``; caps
  re-adoption on rejoin), flips the hierarchical topology per the
  measured fan-in crossover, and — with hysteresis, per-knob cooldowns,
  and an oscillation fallback to the static knobs — never violates a
  floor and keeps every exactly-once/fencing guarantee intact;
* gates **fleet elastic expansion on measured marginal throughput**
  (:class:`~distkeras_tpu.netps.tuner.fleet.MarginalThroughputPolicy`)
  instead of static quotas alone: an expansion whose last granted worker
  did not move the job's commit rate is not repeated.

Every decision is a telemetry event (``tuner_decision`` /
``tuner_probe`` / ``tuner_fallback``) plus counters, rendered by
``python -m distkeras_tpu.telemetry report`` as the Tuner section.
"""

from distkeras_tpu.netps.tuner.controller import (
    Decision,
    Tuner,
    TunerConfig,
    TunerState,
    autotune_enabled,
    recommended_topology,
)
from distkeras_tpu.netps.tuner.fleet import MarginalThroughputPolicy
from distkeras_tpu.netps.tuner.probe import ProbeResult, best_codec, probe_codecs

__all__ = [
    "Decision",
    "MarginalThroughputPolicy",
    "ProbeResult",
    "Tuner",
    "TunerConfig",
    "TunerState",
    "autotune_enabled",
    "best_codec",
    "probe_codecs",
    "recommended_topology",
]
