""":class:`ShardSet` — an in-process gang of shard servers.

Production deployments launch one OS process per shard (the fleet's
``Punchcard.ps["shards"]`` gang, each a ``python -m distkeras_tpu.netps
--shard k/N``). Tests and the chaos smokes want the same topology without
process management, so this helper starts N :class:`~distkeras_tpu.netps.
server.PSServer` instances in one process, each configured with its
:class:`~distkeras_tpu.netps.shards.plan.PartitionPlan` slice identity,
and exposes the ``;``-joined endpoint matrix a
:class:`~distkeras_tpu.netps.shards.client.ShardedPSClient` dials.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from distkeras_tpu.netps.server import PSServer
from distkeras_tpu.netps.shards.plan import PartitionPlan, plan_for_model


class ShardSet:
    """N shard servers sharing one partition plan. Either pass a ``plan``
    (servers start empty, first join seeds each slice) or a ``center``
    (a plan is built for it and every shard is pre-seeded). Extra kwargs
    flow to every :class:`PSServer` (discipline, lease_s, snapshot_every,
    transport...); ``state_dir`` becomes per-shard ``<dir>/shard-<k>``
    so each shard keeps its own journal/snapshot lineage."""

    def __init__(self, num_shards: int,
                 plan: Optional[PartitionPlan] = None,
                 center: Optional[Sequence[np.ndarray]] = None,
                 state_dir: Optional[str] = None, **kw):
        if plan is None and center is not None:
            plan = plan_for_model(list(center), num_shards)
        if plan is not None and plan.num_shards != num_shards:
            raise ValueError(f"plan has {plan.num_shards} shards, "
                             f"asked for {num_shards}")
        self.plan = plan
        self.servers: list[PSServer] = []
        for k in range(num_shards):
            seed = (plan.shard_slice(list(center), k)
                    if center is not None and plan is not None else None)
            sdir = f"{state_dir}/shard-{k}" if state_dir else None
            self.servers.append(PSServer(
                center=seed, shard_index=k, shard_count=num_shards,
                shard_plan=plan, state_dir=sdir, **kw))

    @property
    def num_shards(self) -> int:
        return len(self.servers)

    @property
    def endpoint(self) -> str:
        """The shard x failover matrix (no standbys here: one entry per
        shard) — dial it with ``ShardedPSClient``/``make_ps_client``."""
        return ";".join(s.endpoint for s in self.servers)

    def start(self) -> "ShardSet":
        for s in self.servers:
            s.start()
        return self

    def drain(self) -> None:
        for s in self.servers:
            s.drain()

    def close(self) -> None:
        for s in self.servers:
            s.close()

    def revoke(self, worker_id: int) -> bool:
        """Evict a worker from EVERY shard (chaos harness hook). True if
        any shard held the membership."""
        return any([s.revoke(worker_id) for s in self.servers])

    def center(self) -> list:
        """The assembled logical center (test/debug convenience)."""
        if self.plan is None:
            # Servers that started empty adopt the plan from their first
            # client join — surface it here so a plan-less ShardSet can
            # still assemble after training ran against it.
            self.plan = next(
                (s.shard_plan for s in self.servers
                 if s.shard_plan is not None), None)
        if self.plan is None:
            raise ValueError("no plan adopted yet")
        return self.plan.assemble([s.center() for s in self.servers])

    def __enter__(self) -> "ShardSet":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close()
