"""Open-network traffic engine: arrival streams, tail-latency quantiles,
and SLO admission control.

Everything in `repro.sim` up to PR 5 simulates CLOSED networks (a fixed
population of N programs recirculating forever). Production traffic is an
OPEN system: requests arrive on their own clock, queues can grow to their
caps, and the operative metric is the p99 response time at a given load,
not just mean throughput. This package layers that scenario family onto
both engines:

  * `arrivals`  — composable `ArrivalProcess` streams (Poisson, MMPP
    bursts, diurnal rate modulation, trace replay) with per-class rates,
    merged into one (times, types) stream by `TrafficSpec`.
  * `quantiles` — the fixed-bin log-histogram response-time accumulator
    (device-friendly: O(1) memory, documented relative-error bound) plus
    the exact host-side sorted-sample quantile path.
  * `admission` — per-class SLO specs and the adaptive admission
    controller that sheds or defers best-effort classes under overload
    while protecting the latency class.
  * `host`      — the host-oracle open-network event loop (finite queues,
    drops, exact quantiles), dispatched by `ClosedNetworkSimulator.run`
    whenever `SimConfig.traffic` is set.
  * `engine`    — the batched `lax.scan` open-network device engine
    (`simulate_open_batch`): pre-sampled arrival schedules injected into
    the scan core; completions depart instead of recirculating.
  * `replay`    — virtual-time open-loop trace replay for the serving path
    (`repro.launch.serve --traffic`, `examples/serve_heterogeneous.py`).
"""
from repro.traffic.arrivals import (ArrivalProcess, DiurnalArrivals,
                                    MMPPArrivals, PoissonArrivals,
                                    TraceArrivals, TrafficSpec, load_trace)
from repro.traffic.loadgen import flash_crowd_times, make_load_traces
from repro.traffic.quantiles import LogHistogram, exact_quantiles
from repro.traffic.admission import (AdmissionController, SLOClass,
                                     default_admit_limits)
from repro.traffic.config import (OpenTraffic, open_sim_config,
                                  priority_open_config)
from repro.traffic.host import run_open
from repro.traffic.engine import (simulate_open_batch,
                                  simulate_open_policy_jax)
from repro.traffic.replay import OpenReplayMetrics, replay_open

__all__ = [s for s in dir() if not s.startswith("_")]
