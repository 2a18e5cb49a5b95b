"""Discrete-event simulator of the paper's closed batch network."""
from repro.sim.distributions import (BoundedPareto, Constant, Exponential,
                                     TaskSizeDistribution, Uniform,
                                     make_distribution, DISTRIBUTIONS)
from repro.sim.engine_jax import (compare_policies_jax, simulate_batch,
                                  simulate_policy_jax, sweep_jax)
from repro.sim.simulator import (ClosedNetworkSimulator, SimConfig,
                                 SimMetrics, priority_sim_config,
                                 run_policy_sweep)

__all__ = [s for s in dir() if not s.startswith("_")]
