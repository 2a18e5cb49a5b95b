"""Priority-class policies (arXiv:1712.03246).

The registry entries `grin-p` and `cab-p` are target policies over the
CLASS-MAJOR FLATTENED problem (see `repro.core.priority`): the affinity
matrix a `SchedulerCore` holds for them has C*k rows — row (c*k + i) is
class c's i-type — and the (C*k, l) target they solve keeps per-(class,
type) deficit rows, so the shared routing machinery needs no new state.
Weights fold into the matrix the SOLVER ranks moves under (`device_mu`),
never into the physical rates routing and EWMA folding observe.

`repro.sim.priority_sim_config` (closed network) and
`repro.traffic.priority_open_config` (open network) build the matching
flattened `SimConfig` (tiled mu, flattened per-class mixes, `class_of_type`
map, optional per-class size distributions) for both simulation engines;
`order="PRIO"` selects the strict-priority preemption-free service order
(class 0 first; within a class, FCFS).
"""
from __future__ import annotations

import numpy as np

from repro.core.priority import (cab_priority_solve, class_of_flat, flat_mu,
                                 flatten_mixes, priority_mu, unflatten_state)
from repro.core.grin import grin_solve
from repro.sched.api import Policy, register_policy


def _weights_vector(weights) -> np.ndarray:
    w = np.asarray(weights, dtype=np.float64)
    if w.ndim != 1 or w.size < 1 or (w < 0).any():
        raise ValueError(f"weights must be a nonneg 1-D vector; got {w!r}")
    return w


def _flat_k(mu: np.ndarray, n_classes: int) -> int:
    rows = np.asarray(mu).shape[0]
    if rows % n_classes:
        raise ValueError(
            f"flattened affinity has {rows} rows, not a multiple of "
            f"C={n_classes} classes (build it with repro.sim."
            "priority_sim_config / flat_mu)")
    return rows // n_classes


class _WeightedFlatPolicy(Policy):
    """Shared base of the priority policies: hold the class-weight vector
    and fold it into the flattened affinity rows (`device_mu` — the one
    place weights enter mu; watts and routing rates stay physical)."""

    def __init__(self, weights=(1.0,)):
        self.class_weights = _weights_vector(weights)

    def device_mu(self, mu):
        k = _flat_k(mu, len(self.class_weights))
        return np.repeat(self.class_weights, k)[:, None] * np.asarray(
            mu, dtype=np.float64)


@register_policy("grin-p", "grinp", "grin_p")
class GrInPriorityPolicy(_WeightedFlatPolicy):
    """GrIn-P: block-move GrIn on the class-weighted flattened problem —
    maximizes sum_c w_c X_c for any (C, k, l). With C=1 and w=(1,) the
    weighting is the float-exact identity, so targets, routing decisions
    and device solves are bit-identical to plain `grin`."""

    name = "GrIn-P"
    supports_jax_batch = True

    def solve_target(self, mu, n_tasks):
        return grin_solve(self.device_mu(mu), n_tasks).N


@register_policy("cab-p", "cabp", "cab_p")
class CABPriorityPolicy(_WeightedFlatPolicy):
    """CAB-P: Table-1 analytical optimum of the class-weighted flattened
    2 x 2 problem (two classes of one type, or one class of two types, on
    two pools). C=1 with w=(1,) reduces bit-identically to `cab`."""

    name = "CAB-P"
    pool_limit = 2

    def solve_target(self, mu, n_tasks):
        C = len(self.class_weights)
        k = _flat_k(mu, C)
        base = np.asarray(mu, dtype=np.float64)[:k]
        mixes = np.asarray(n_tasks, dtype=np.int64).reshape(C, k)
        return cab_priority_solve(base, mixes, self.class_weights).reshape(
            C * k, -1)


__all__ = ["GrInPriorityPolicy", "CABPriorityPolicy", "priority_mu",
           "flat_mu", "class_of_flat", "flatten_mixes", "unflatten_state"]
