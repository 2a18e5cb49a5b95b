"""Paper <-> framework bridge: heterogeneous pools + the unified
Policy/SchedulerCore scheduling API."""
from repro.sched.api import (Policy, SchedulerCore, StragglerTracker,
                             SystemView, as_core, available_policies,
                             get_policy, register_policy,
                             solve_targets_grid_jax, solve_targets_jax)
from repro.sched.autoscale import (AutoscaleGovernor, BudgetSpec, Decision,
                                   GovernorConfig, StaticScaler,
                                   UtilizationScaler, price_frequency_grid,
                                   run_autoscaled)
from repro.sched.baselines import BaselineClusterScheduler
from repro.sched.priority import CABPriorityPolicy, GrInPriorityPolicy
from repro.sched.cluster import (ChipSpec, HeterogeneousCluster, Pool,
                                 PoolSpec, TaskRecord)
from repro.sched.rates import (StepCost, affinity_from_roofline,
                               serving_step_costs, step_time_roofline)
from repro.sched.scheduler import ClusterScheduler, run_closed_loop
