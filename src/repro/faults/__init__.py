"""Fault injection and resilience (`repro.faults`).

A `FaultScenario` describes processor crash/recovery events, degraded-mu
stragglers, correlated multi-pool storms, transient task failures with
re-execution, a checkpoint-restart cost model, hedged duplicate dispatch
for protected classes, and automatic target refresh on topology events.
The scenario is REALIZED on the host into plain arrays (piecewise-constant
per-pool mu scales + per-arrival failure counts) that BOTH engines consume:
the host event loops (`run_closed_faults` / `run_open_faults`) and the
device `lax.scan` fault cores (`repro.sim.engine_jax.simulate_batch` /
`repro.traffic.engine.simulate_open_batch` with a `FaultBatch`), so a
(scenario x policy x seed) grid sweeps in one device call against an
identical fault realization.

`repro.faults.hazard` layers stochastic availability on top: renewal
up/down processes with exponential or Weibull inter-failure/repair times
(`UpDownProcess` -> `realize_availability` / `make_hazard_scenario`
produce ordinary realized scenarios both engines already consume),
restart-vs-resume economics (`expected_completion_exp` /
`expected_completion_weibull` / `completion_forecast` with JAX twins),
and checkpoint policy solvers (`optimal_ckpt_period`,
`age_checkpoint_policy` feeding `FaultScenario.ckpt_age`).

RNG stream isolation: fault realization draws come only from the dedicated
substreams `default_rng([seed, 2])` (transient failures, host),
`default_rng([seed, 3])` (storm generation), and
`default_rng([seed, 4, pool])` (hazard up/down renewal draws, one
independent stream per pool); on device the per-step failure draw uses
`fold_in(sub, 3)`, class-hedge placement `fold_in(sub, 4)`, and
straggler-triggered speculative hedging `fold_in(sub, 5)` (routing owns 1,
mix re-draw owns 2). Enabling faults with zero in-horizon events therefore
leaves every existing engine golden bit-identical — see
tests/test_faults.py and tests/test_hazard.py.
"""
from repro.faults.scenario import (FaultRealization, FaultScenario, PoolEvent,
                                   compose_event_streams, crash,
                                   decisions_to_events, degrade, make_storm)
from repro.faults.targets import segment_targets
from repro.faults.device import FaultBatch, build_fault_batch
from repro.faults.host import run_closed_faults, run_open_faults
from repro.faults.hazard import (UpDownProcess, age_checkpoint_policy,
                                 completion_forecast, completion_forecast_jax,
                                 expected_completion_exp,
                                 expected_completion_exp_jax,
                                 expected_completion_weibull,
                                 make_hazard_scenario, optimal_ckpt_period,
                                 realize_availability, weibull_theta)

__all__ = [s for s in dir() if not s.startswith("_")]
