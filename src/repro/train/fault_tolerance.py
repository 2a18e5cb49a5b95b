"""Fault tolerance: checkpoint/restart driver, elastic re-meshing, straggler
mitigation hooks.

The three mechanisms a 1000-node deployment needs, and how they appear here:

1. **Checkpoint/restart** — `run_with_recovery` wraps the step loop: any
   exception triggers restore-from-latest and replay (the data pipeline is
   step-indexed, so replay is exact). Checkpoint cadence + async writes keep
   the overhead off the step path.

2. **Elastic scaling** — `ElasticMeshManager` rebuilds the mesh and re-shards
   live state when the healthy-device set changes; on a real fleet this is
   driven by jax.distributed heartbeats, here by an injectable device-list
   provider (tests inject failures). Re-sharding = device_put to the new
   NamedSharding (same PartitionSpecs — specs are mesh-shape-agnostic).

3. **Straggler mitigation** — per-pool observed step-rates feed an EWMA into
   the paper's scheduler (repro.sched): a slow pool's mu column drops, GrIn
   re-solves, and load migrates away — the queueing-theoretic version of
   backup tasks. `StragglerTracker` is that EWMA.
"""
from __future__ import annotations

import dataclasses
import logging
import time
from typing import Callable

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding

from repro.launch.mesh import make_mesh
from repro.train import checkpoint as ckpt

log = logging.getLogger("repro.ft")


def run_with_recovery(step_fn: Callable, state, data_iter,
                      *, ckpt_dir: str, ckpt_every: int = 100,
                      max_steps: int = 1000, max_restarts: int = 3,
                      async_ckpt: bool = True):
    """Run step_fn(state, batch) with checkpoint/restore-based recovery.

    Returns (state, steps_completed, restarts). step indices come from the
    data iterator so replay-after-restore is exact.
    """
    restarts = 0
    pending = None
    step = int(np.asarray(state.step)) if hasattr(state, "step") else 0
    while step < max_steps:
        try:
            for i, batch in data_iter:
                if i >= max_steps:
                    break
                state, metrics = step_fn(state, batch)
                step = i + 1
                if step % ckpt_every == 0:
                    if pending is not None:
                        pending.join()
                    pending = ckpt.save(ckpt_dir, step, state,
                                        async_=async_ckpt)
            break
        except Exception as e:  # noqa: BLE001 — any fault triggers recovery
            restarts += 1
            log.warning("step %d failed (%s); restart %d", step, e, restarts)
            # Drain any in-flight async checkpoint BEFORE touching ckpt_dir:
            # restoring (or re-raising) while the writer thread is mid-file
            # would race latest_step/restore against a half-written step.
            if pending is not None:
                pending.join()
                pending = None
            if restarts > max_restarts:
                raise
            latest = ckpt.latest_step(ckpt_dir)
            if latest is not None:
                state, step = ckpt.restore(ckpt_dir, state)
            data_iter.seek(step) if hasattr(data_iter, "seek") else None
    if pending is not None:
        pending.join()
    return state, step, restarts


@dataclasses.dataclass
class ElasticMeshManager:
    """Rebuild mesh + re-shard state when the device set changes."""

    axis_names: tuple
    device_provider: Callable = jax.devices   # injectable for failure tests

    def current_mesh(self) -> Mesh:
        devs = self.device_provider()
        n = len(devs)
        # factor n into (data, model): keep model as square as possible
        model = 1
        for m in (16, 8, 4, 2, 1):
            if n % m == 0:
                model = m
                break
        shape = (n // model, model)
        return make_mesh(shape, self.axis_names[-2:])

    def reshard(self, tree, spec_tree, mesh: Mesh):
        return jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            tree, spec_tree)


class StragglerTracker:
    """EWMA of per-pool speed RELATIVE to expectation (1.0 = nominal).

    Observations must be normalized per task class (expected/actual service
    time) — raw rates would conflate a pool's task mix with its health."""

    def __init__(self, n_pools: int, alpha: float = 0.3):
        self.alpha = alpha
        self.rates = np.ones(n_pools)     # relative speed, 1.0 = nominal
        self.seen = np.zeros(n_pools, dtype=bool)

    def observe(self, pool: int, rel_speed: float):
        """rel_speed = expected_service_s / actual_service_s."""
        if not self.seen[pool]:
            self.rates[pool] = rel_speed
            self.seen[pool] = True
        else:
            self.rates[pool] = (self.alpha * rel_speed
                                + (1 - self.alpha) * self.rates[pool])

    def slowdown_factors(self) -> np.ndarray:
        """Per-pool relative speed (<1 = straggler, >1 = faster than nominal).

        Normalized so the fleet-best healthy pool anchors at its own scale —
        the scheduler multiplies base mu columns by these factors."""
        return np.where(self.seen, self.rates, 1.0)
