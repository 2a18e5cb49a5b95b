"""Flight-recorder observability layer (`repro.obs`).

Three instruments, all opt-in and zero-cost when disabled:

  * `TraceRecorder` — a bounded ring buffer of structured decision events
    (routing, target re-solves with cache hit/miss/eviction, admission
    shed/defer, governor decisions, fault breakpoints), exportable to
    Chrome trace-event JSON (chrome://tracing, Perfetto, `tools/
    trace_view.py`). Attach one to a `SchedulerCore` / `AdmissionController`
    / `AutoscaleGovernor`; with none attached the hot paths skip a single
    `is not None` check.
  * Program spans and counters (`repro.obs.profile`) — `repro.<layer>.
    <phase>` spans over the re-solve path's host phases, `route_many` and
    `solve_targets_jax`. They land in any `jax.profiler` trace, on the
    device ops' clock, with counters as span arguments; the in-process
    `Profiler` (off by default, `enable_profiling()`) also keeps them in a
    ring buffer.
  * Time-resolved telemetry (`repro.obs.telemetry`) — fixed-bin device
    time series (per-pool occupancy, backlog, power, in-flight hedges)
    carried through the `lax.scan` engine cores, with a host twin in the
    oracle loops. Telemetry off is a trace-time static: the compiled
    program (and every result) is unchanged.

`run_meta()` (`repro.obs.meta`) stamps benchmark payloads with the jax
backend, kernel mode and dtype so perf numbers stay attributable.
"""
from repro.obs.meta import run_meta
from repro.obs.profile import (Profiler, enable_profiling, get_profiler,
                               profile_block, span, tracing_active)
from repro.obs.recorder import TraceEvent, TraceRecorder
from repro.obs.telemetry import TelemetryAccumulator, telemetry_series

__all__ = ["TraceRecorder", "TraceEvent", "Profiler", "span",
           "enable_profiling", "get_profiler", "profile_block",
           "tracing_active", "run_meta", "TelemetryAccumulator",
           "telemetry_series"]
