"""Observability layer (`repro.obs`): flight recorder, telemetry carries,
profiler, cache statistics, run metadata, and the bench-compare guard.

The load-bearing pins: telemetry/recorder OFF leaves every engine result
bit-identical (and the device stanza out of the lowered program); export
bytes are deterministic for a deterministic stream; the host accumulator
and the device carry follow the same binning convention.
"""
import dataclasses
import json
import warnings

import numpy as np
import pytest

import repro.sched  # noqa: F401  (canonical import entry)
from repro.obs import (Profiler, TelemetryAccumulator, TraceRecorder,
                       enable_profiling, get_profiler, profile_block,
                       run_meta, telemetry_series)
from repro.sched import SchedulerCore, get_policy
from repro.sched.api import as_core
from repro.sched.priority import GrInPriorityPolicy
from repro.sim import ClosedNetworkSimulator, SimConfig, make_distribution
from repro.sim.engine_jax import MODE_DEFICIT, _BASELINE_MODES, simulate_batch
from repro.traffic import (PoissonArrivals, SLOClass, TrafficSpec,
                           open_sim_config, simulate_open_batch)
from repro.traffic.admission import AdmissionController
from repro.traffic.config import derive_target_mix
from repro.traffic.host import run_open

MU = np.array([[6.0, 2.0], [2.0, 5.0]])
DIST = make_distribution("exponential")
T, WARM, QCAP = 400, 80, 6


def _spec():
    return TrafficSpec((PoissonArrivals(0.7 * MU[0].max()),
                        PoissonArrivals(0.7 * MU[1].max())), np.eye(2))


def _open_dev(seed=0, **kw):
    pol = GrInPriorityPolicy((2.0, 1.0))
    spec = _spec()
    mix = derive_target_mix(spec, MU.shape[1], QCAP)
    tgt = np.asarray(pol.solve_target(MU, mix))
    times, tys = spec.sample(seed, T)
    return simulate_open_batch(
        MU[None], tgt[None], times[None], tys[None], [seed],
        distribution=DIST, queue_capacity=QCAP, order="PS",
        warmup_arrivals=WARM, class_of_type=[0, 1],
        modes=np.full(1, MODE_DEFICIT, np.int32), **kw)


# ------------------------------------------------------------- recorder

def test_recorder_ring_buffer_bound_and_drop_count():
    rec = TraceRecorder(capacity=8)
    for i in range(20):
        rec.record("sched", "route", t=float(i), pool=i % 2)
    assert len(rec) == 8 and rec.dropped == 12
    # the buffer keeps the MOST RECENT capacity events
    assert [e.t for e in rec.events] == [float(i) for i in range(12, 20)]
    rec.clear()
    assert len(rec) == 0 and rec.dropped == 0
    with pytest.raises(ValueError):
        TraceRecorder(capacity=0)


def test_recorder_counts_and_seq_timestamps():
    rec = TraceRecorder()
    rec.record("sched", "route", t=1.0)
    rec.record("sched", "route", t=2.0)
    rec.record("governor", "decision")      # no clock: monotone seq stands in
    rec.record("governor", "decision")
    assert rec.counts() == {("sched", "route"): 2,
                            ("governor", "decision"): 2}
    assert rec.layer_counts() == {"sched": 2, "governor": 2}
    gts = [e.t for e in rec.events if e.layer == "governor"]
    assert gts == [2.0, 3.0]                # seq numbers 2 and 3


def test_recorder_chrome_export_schema_and_byte_determinism(tmp_path):
    from tools.trace_view import validate

    def build():
        rec = TraceRecorder(capacity=4)
        for i in range(6):                  # overflow: 3 of 7 records dropped
            rec.record("sched", "route", t=0.5 * i, pool=i % 2,
                       deficit=np.array([1, -1]))
        rec.record("admission", "shed", t=9.0, cls=np.int64(1))
        return rec

    p1, p2 = tmp_path / "a.json", tmp_path / "b.json"
    n1 = build().export(str(p1))
    n2 = build().export(str(p2))
    assert p1.read_bytes() == p2.read_bytes()       # byte determinism
    doc = json.loads(p1.read_text())
    events = validate(doc)
    assert n1 == n2 == len(events) == 4
    assert doc["metadata"] == {"dropped": 3, "capacity": 4}
    # numpy payloads were coerced to plain JSON types
    sched = [e for e in events if e["cat"] == "sched"]
    assert sched[0]["args"]["deficit"] == [1, -1]
    assert all(e["ph"] == "i" and e["pid"] == 1 for e in events)
    # layers map to stable distinct tids
    assert {e["tid"] for e in events} == {1, 2}


def test_recorder_span_export_as_complete_events(tmp_path):
    from repro.obs.profile import ProfileSpan
    rec = TraceRecorder()
    rec.record("sched", "route", t=0.0)
    path = tmp_path / "t.json"
    rec.export(str(path), spans=[ProfileSpan("solve", t0=1.0, dur=0.25)])
    doc = json.loads(path.read_text())
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(spans) == 1
    assert spans[0]["name"] == "solve" and spans[0]["dur"] == 0.25e6


# ----------------------------------------------- scheduler-core recording

def test_scheduler_core_records_routes_resolves_and_unroute():
    rec = TraceRecorder()
    core = SchedulerCore(get_policy("opt"), MU, recorder=rec)
    core.reset(MU, np.array([3, 3]))
    j = core.route(0)
    core.route(1)
    core.unroute(0, j)
    jb = core.route_backup(0, exclude=j)
    assert jb != j
    core.route_many(np.array([0, 1], np.int64))
    c = rec.counts()
    assert c[("sched", "route")] == 2
    assert c[("sched", "unroute")] == 1
    assert c[("sched", "route_backup")] == 1
    assert c[("sched", "route_many")] == 1
    assert c[("sched", "resolve")] >= 1
    routes = [e for e in rec.events if e.kind == "route"]
    assert "deficit" in routes[0].data and "pool" in routes[0].data
    assert len(routes[0].data["deficit"]) == MU.shape[1]
    resolves = [e for e in rec.events if e.kind == "resolve"]
    assert resolves[0].data["hit"] is False    # first solve is a cache miss


def test_trace_export_deterministic_across_identical_host_runs(tmp_path):
    """Same (config, seed) twice => byte-identical exported trace."""
    spec = _spec()
    mix = derive_target_mix(spec, MU.shape[1], QCAP)
    cfg = open_sim_config(MU, spec, n_arrivals=200, warmup_arrivals=40,
                          queue_capacity=QCAP, class_of_type=[0, 1],
                          target_mix=mix, distribution=DIST, order="PS",
                          seed=3)
    paths = []
    for name in ("a.json", "b.json"):
        rec = TraceRecorder()
        core = as_core(GrInPriorityPolicy((2.0, 1.0)), MU, recorder=rec)
        run_open(ClosedNetworkSimulator(cfg), core)
        p = tmp_path / name
        rec.export(str(p))
        paths.append(p)
        assert rec.counts()[("sched", "route")] > 0
    assert paths[0].read_bytes() == paths[1].read_bytes()


# ------------------------------------------------------ cache statistics

def test_target_cache_stats_hits_misses_and_solve_time():
    core = SchedulerCore(get_policy("opt"), MU)
    core.reset(MU, np.array([3, 3]))
    core.route(0)                        # first solve: a miss
    core._target_for(np.array([3, 3]))   # warm key: a hit
    s = core.stats
    assert s["cache_misses"] == 1 and s["cache_hits"] == 1
    assert s["cache_size"] == 1 and s["cache_evictions"] == 0
    assert s["resolves"] == 1
    assert s["solve_time_s"] > 0.0
    assert s["cache_capacity"] >= 1


def test_target_cache_churn_warns_once():
    core = SchedulerCore(get_policy("opt"), MU, cache_capacity=4)
    core.reset(MU, np.array([2, 2]))
    with pytest.warns(RuntimeWarning, match="target cache is churning"):
        for i in range(12):            # 12 distinct mixes through 4 slots
            core._target_for(np.array([1 + i, 2]))
    assert core.stats["cache_evictions"] >= 4
    assert core.stats["cache_size"] == 4
    with warnings.catch_warnings():    # warned once, not on every eviction
        warnings.simplefilter("error")
        core._target_for(np.array([50, 2]))


# ------------------------------------------------------------- profiler

def test_profiler_disabled_is_inert_and_ready_is_identity():
    prof = Profiler(enabled=False)
    sentinel = object()
    with prof.span("x") as sp:
        assert sp.ready(sentinel) is sentinel
    assert prof.spans == []


def test_profiler_spans_summary_and_top():
    prof = Profiler(enabled=True, max_spans=4)
    for i in range(6):
        with prof.span("a" if i % 2 else "b"):
            pass
    assert len(prof.spans) == 4            # bounded deque
    agg = prof.summary()
    assert set(agg) == {"a", "b"}
    for row in agg.values():
        assert row["count"] == 2 and row["max_s"] >= row["mean_s"] > 0.0
    top = prof.top_spans(3)
    assert len(top) == 3
    assert top[0].dur >= top[1].dur >= top[2].dur


def test_profile_block_restores_state_and_captures_library_spans():
    from repro.sched.api import solve_targets_jax
    assert not get_profiler().enabled
    get_profiler().clear()
    with profile_block("t") as prof:
        assert prof is get_profiler() and prof.enabled
        targets, _ = solve_targets_jax(MU, np.array([[4, 4]]))
    assert not get_profiler().enabled
    names = {s.name for s in prof.spans}
    assert "repro.targets.solve" in names
    assert np.asarray(targets).shape == (1,) + MU.shape
    enable_profiling(False)


def test_span_untraced_with_profiler_off_records_nothing():
    from repro.obs import span, tracing_active
    get_profiler().clear()
    assert not tracing_active() and not get_profiler().enabled
    sentinel = object()
    with span("repro.test.off") as sp:
        assert sp.count(rows=3) is None
        assert sp.ready(sentinel) is sentinel
    with Profiler(enabled=False).span("repro.test.off") as sp:
        sp.count(rows=3)
    assert get_profiler().spans == []


# the re-solve path's host phases, in the order one request runs them: the
# energy pricing is enqueued behind the solve, before the one fetch
PRICE_SPANS = ["repro.price.guard", "repro.grid.batch", "repro.grin.prep",
               "repro.grin.dispatch", "repro.price.energy", "repro.grid.fetch",
               "repro.grid.repair"]


def test_price_grid_spans_land_in_profiler_trace(tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData
    from repro.core.energy import DVFSModel
    from repro.sched.autoscale import price_frequency_grid
    rng = np.random.default_rng(7)
    mu = rng.uniform(2.0, 20.0, size=(2, 3))
    freqs = np.array([[1.0, 1.0, 1.0], [0.0, 1.0, 1.0], [1.25, 1.0, 0.5]])
    mixes = rng.multinomial(40, [0.6, 0.4], size=4)
    args = (mu, mu, freqs, mixes, DVFSModel())
    price_frequency_grid(*args)                    # compile outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        with profile_block("t") as prof:
            price_frequency_grid(*args)
    finally:
        jax.profiler.stop_trace()
    # the enabled Profiler keeps the same spans on its own clock
    assert [s.name for s in prof.spans] == PRICE_SPANS
    prof.clear()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    evs = sorted(((ev.start_ns, ev.name, dict(ev.stats))
                  for plane in ProfileData.from_file(path[0]).planes
                  for line in plane.lines for ev in line.events
                  if ev.name.startswith("repro.")), key=lambda t: t[0])
    assert [name for _, name, _ in evs] == PRICE_SPANS
    stats = {name: st for _, name, st in evs}
    assert stats["repro.grid.repair"]["rows"] >= 0
    fetch = stats["repro.grid.fetch"]
    assert fetch["lanes"] == len(freqs) * len(mixes)
    assert 0 <= fetch["moves_mean"] <= fetch["moves_max"]


# ----------------------------------------------- telemetry accumulator

def test_telemetry_accumulator_binning_and_horizon_clip():
    tel = TelemetryAccumulator(n_bins=4, horizon=8.0, n_pools=2)
    tel.add(0.5, 1.0, [1, 0], [2.0, 0.0], power=3.0)       # bin 0
    tel.add(3.9, 0.5, [0, 2], [0.0, 1.0], power=1.0)       # bin 1 (start bin)
    tel.add(7.5, 4.0, [1, 1], [1.0, 1.0], power=2.0, hedges=1.0)  # clip @ 8
    tel.add(9.0, 1.0, [5, 5], [5.0, 5.0], power=9.0)       # past horizon
    tel.add(1.0, 0.0, [5, 5], [5.0, 5.0], power=9.0)       # zero dt
    raw = tel.series()
    assert raw["bin_width"] == 2.0 and raw["horizon"] == 8.0
    np.testing.assert_allclose(raw["occupancy"][0], [1.0, 0.0])
    np.testing.assert_allclose(raw["occupancy"][1], [0.0, 1.0])
    np.testing.assert_allclose(raw["occupancy"][3], [0.5, 0.5])  # 0.5s charge
    np.testing.assert_allclose(raw["power"], [3.0, 0.5, 0.0, 1.0])
    np.testing.assert_allclose(raw["hedges"], [0.0, 0.0, 0.0, 0.5])
    avg = telemetry_series(raw)
    np.testing.assert_allclose(avg["power"], raw["power"] / 2.0)
    with pytest.raises(ValueError):
        TelemetryAccumulator(n_bins=0, horizon=1.0, n_pools=1)
    with pytest.raises(ValueError):
        TelemetryAccumulator(n_bins=2, horizon=0.0, n_pools=1)


# ------------------------------------- engine telemetry: off = identical

def test_open_engine_telemetry_off_bit_identical():
    base = _open_dev(telemetry_bins=0)
    on = _open_dev(telemetry_bins=8)
    assert "telemetry" not in base and "telemetry" in on
    for key in base:
        assert np.array_equal(np.asarray(base[key]), np.asarray(on[key])), key
    tel = on["telemetry"]
    assert tel["occupancy"].shape == (1, 8, MU.shape[1])
    assert tel["power"].shape == (1, 8)
    # the integrals cover exactly the charged horizon
    total = telemetry_series(tel)
    assert total["occupancy"][0].sum(1).mean() > 0
    with pytest.raises(ValueError):
        _open_dev(telemetry_bins=-1)


def test_closed_engine_telemetry_off_bit_identical():
    pol = get_policy("lb")
    types0 = np.repeat(np.arange(2), 3).astype(np.int32)
    kw = dict(distribution=DIST, order="PS", n_completions=300,
              warmup_completions=60,
              modes=np.full(1, _BASELINE_MODES[pol.key], np.int32))
    tgt = np.zeros((1,) + MU.shape, np.int64)
    base = simulate_batch(MU[None], tgt, types0[None], [0], **kw)
    on = simulate_batch(MU[None], tgt, types0[None], [0], telemetry_bins=6,
                        telemetry_horizon=5.0, **kw)
    assert "telemetry" not in base and "telemetry" in on
    for key in base:
        assert np.array_equal(np.asarray(base[key]), np.asarray(on[key])), key
    tel = on["telemetry"]
    assert tel["occupancy"].shape == (1, 6, MU.shape[1])
    assert np.all(tel["hedges"] == 0.0)          # closed mode never hedges
    # closed population is constant, so the total charge is n * horizon
    # (single bins are lumpy: start-bin charging lets intervals straddle)
    occ = telemetry_series(tel)["occupancy"][0].sum(1)
    np.testing.assert_allclose(occ.mean(), len(types0), rtol=1e-4)
    with pytest.raises(ValueError, match="telemetry_horizon"):
        simulate_batch(MU[None], tgt, types0[None], [0], telemetry_bins=4,
                       **kw)
    with pytest.raises(ValueError, match="> 0"):
        simulate_batch(MU[None], tgt, types0[None], [0], telemetry_bins=4,
                       telemetry_horizon=0.0, **kw)


def test_open_engine_telemetry_off_drops_stanza_from_lowering(monkeypatch):
    """telemetry_bins is trace-time static: 0 lowers to a strictly smaller
    program with fewer outputs than 8 (same dynamic args)."""
    import repro.traffic.engine as eng
    captured = {}
    orig = eng._simulate_open_fleet

    def spy(*a, **k):
        captured["a"], captured["k"] = a, k
        return orig(*a, **k)

    monkeypatch.setattr(eng, "_simulate_open_fleet", spy)
    _open_dev(telemetry_bins=0)
    a, k = captured["a"], captured["k"]
    low0 = orig.lower(*a, **{**k, "telemetry_bins": 0})
    low8 = orig.lower(*a, **{**k, "telemetry_bins": 8})
    j0, j8 = low0.as_text(), low8.as_text()
    assert len(j0) < len(j8)


def test_open_engine_telemetry_deterministic_across_runs():
    a = _open_dev(telemetry_bins=8)["telemetry"]
    b = _open_dev(telemetry_bins=8)["telemetry"]
    for key in ("occupancy", "backlog", "power", "hedges", "horizon"):
        assert np.array_equal(np.asarray(a[key]), np.asarray(b[key])), key


def test_host_run_open_telemetry_off_leaves_metrics_identical():
    spec = _spec()
    mix = derive_target_mix(spec, MU.shape[1], QCAP)
    cfg = open_sim_config(MU, spec, n_arrivals=T, warmup_arrivals=WARM,
                          queue_capacity=QCAP, class_of_type=[0, 1],
                          target_mix=mix, distribution=DIST, order="PS",
                          seed=1)
    pol = GrInPriorityPolicy((2.0, 1.0))
    base = run_open(ClosedNetworkSimulator(cfg), as_core(pol, MU))
    on = run_open(ClosedNetworkSimulator(cfg), as_core(pol, MU), telemetry=10)
    assert base.telemetry is None and on.telemetry is not None
    for f in dataclasses.fields(base):
        if f.name == "telemetry":
            continue
        bv, ov = getattr(base, f.name), getattr(on, f.name)
        if bv is None:
            assert ov is None, f.name
        else:
            assert np.array_equal(np.asarray(bv), np.asarray(ov)), f.name
    assert on.telemetry["occupancy"].shape == (10, MU.shape[1])


# ------------------------------------- layer events: admission / governor /
# faults

def test_admission_controller_records_admit_shed_adapt():
    rec = TraceRecorder()
    core = SchedulerCore(GrInPriorityPolicy((2.0, 1.0)), MU, recorder=rec)
    core.reset(MU, np.array([2, 2]))
    slo = (SLOClass(deadline=1.0, percentile=0.9, protected=True),
           SLOClass(deadline=5.0, percentile=0.9))
    adm = AdmissionController(core, slo, class_of_type=[0, 1],
                              queue_capacity=2, window=8, adapt_every=2)
    assert adm.recorder is rec             # shared with the wrapped core
    adm.limits[1] = 0.0                    # force best-effort sheds
    verdict0, j0 = adm.offer(0, now=0.1)
    verdict1, j1 = adm.offer(0, now=0.15)
    assert verdict0 == verdict1 == "admit"
    assert adm.offer(1, now=0.2) == ("shed", None)
    adm.complete(0, j0, response_s=2.0)
    adm.complete(0, j1, response_s=2.0)    # 2nd completion triggers _adapt
    c = rec.counts()
    assert c[("admission", "admit")] == 2
    assert c[("admission", "shed")] == 1
    assert c[("admission", "adapt")] >= 1
    shed = [e for e in rec.events if e.kind == "shed"][0]
    assert shed.data["cls"] == 1 and shed.t == 0.2
    adapt = [e for e in rec.events if e.kind == "adapt"][0]
    assert adapt.data["pressure"] > 1.0    # 2.0s response vs 1.0s deadline
    assert len(adapt.data["limits"]) == 2


def test_governor_records_decisions_through_core_recorder():
    from repro.core import DVFSModel
    from repro.sched.autoscale import AutoscaleGovernor, GovernorConfig
    rec = TraceRecorder()
    core = SchedulerCore(GrInPriorityPolicy((2.0, 1.0)), MU, recorder=rec)
    gov = AutoscaleGovernor(
        MU, dvfs=DVFSModel(alpha=3.0, levels=(0.5, 0.75, 1.0)),
        config=GovernorConfig(epoch=1.0, hysteresis=0.0), core=core)
    gov.observe(np.array([3.0, 3.0]), 1.0)
    dec = gov.decide(now=1.0)
    events = [e for e in rec.events if e.layer == "governor"]
    assert len(events) == 1
    e = events[0]
    assert e.kind == "decision" and e.t == 1.0
    assert e.data["action"] == dec.action
    assert e.data["freqs"] == list(dec.freqs)
    assert e.data["n_candidates"] == dec.n_candidates
    assert "power_pred" in e.data and "energy_per_task" in e.data


def test_fault_host_loop_records_breakpoints():
    from repro.faults import FaultScenario, crash
    from repro.faults.host import run_closed_faults
    sc = FaultScenario(events=crash(1, 2.0, 4.0), fail_prob=0.0,
                       ckpt_period=0.05, refresh_targets=False)
    cfg = SimConfig(mu=MU, n_programs_per_type=np.array([3, 3]),
                    distribution=DIST, order="PS", n_completions=400,
                    warmup_completions=50, seed=0, faults=sc)
    rec = TraceRecorder()
    core = as_core(get_policy("lb"), MU, recorder=rec)
    m = run_closed_faults(ClosedNetworkSimulator(cfg), core)
    bps = [e for e in rec.events if e.layer == "faults"]
    assert len(bps) == m.topology_events >= 1
    assert bps[0].kind == "breakpoint"
    assert bps[0].data["crashed"] == [1]
    assert len(bps[0].data["scales"]) == MU.shape[1]


# ------------------------------------------------- meta + bench_compare

def test_run_meta_keys_and_metrics_are_stamped():
    meta = run_meta()
    assert set(meta) >= {"jax_backend", "jax_version", "device_kind",
                         "device_count", "kernel_mode", "dtype", "python",
                         "platform"}
    assert meta["device_count"] >= 1 and meta["device_kind"]
    assert meta["dtype"] == "float32"
    assert meta["kernel_mode"] in ("pallas-compiled", "pallas-interpret",
                                   "jnp-reference")
    json.dumps(meta)                       # JSON-serializable end to end
    from repro.traffic.engine import open_metrics_row
    m = open_metrics_row(_open_dev(telemetry_bins=4), 0)
    assert m.meta == run_meta()            # device rows carry the substrate
    assert m.telemetry["occupancy"].shape == (4, MU.shape[1])
    m0 = open_metrics_row(_open_dev(), 0)
    assert m0.telemetry is None


def test_benchmark_save_json_injects_meta(tmp_path, monkeypatch):
    import benchmarks.common as common
    monkeypatch.setattr(common, "RESULTS_DIR", str(tmp_path))
    common.save_json("probe", {"x": 1.0})
    doc = json.loads((tmp_path / "probe.json").read_text())
    assert doc["x"] == 1.0 and doc["meta"]["kernel_mode"]
    common.save_json("keep", {"x": 1.0, "meta": {"kernel_mode": "frozen"}})
    doc = json.loads((tmp_path / "keep.json").read_text())
    assert doc["meta"] == {"kernel_mode": "frozen"}   # never overwritten


def test_bench_compare_directions_and_gating(tmp_path):
    from tools.bench_compare import compare, flatten, lower_is_better, main
    base = {"a": {"goodput": 10.0, "p99_s": 1.0}, "us_per_call": 5.0,
            "zero": 0.0, "note": "str", "meta": {"kernel_mode": "x"}}
    new = {"a": {"goodput": 7.0, "p99_s": 0.5}, "us_per_call": 9.0,
           "zero": 3.0, "meta": {"kernel_mode": "x"}}
    flat = flatten(base)
    assert flat["a.goodput"] == 10.0 and "note" not in flat
    assert lower_is_better("a.p99_s") and lower_is_better("us_per_call")
    assert not lower_is_better("a.goodput")
    regs, imps = compare(new, base, threshold=0.25)
    assert {r[0] for r in regs} == {"a.goodput", "us_per_call"}
    assert {r[0] for r in imps} == {"a.p99_s"}
    assert all(r[3] > 0.25 for r in regs)
    # zero baselines and meta.* keys are excluded from comparison
    assert not any(r[0].startswith(("zero", "meta")) for r in regs + imps)
    pb, pn = tmp_path / "base.json", tmp_path / "new.json"
    pb.write_text(json.dumps(base))
    pn.write_text(json.dumps(new))
    argv = [str(pn), "--baseline", str(pb)]
    assert main(argv) == 0                           # warn-only default
    assert main(argv + ["--hard"]) == 1              # promotion path
    assert main(argv + ["--hard", "--metric", "a.p99_s"]) == 0
    with pytest.raises(SystemExit):
        main(argv + ["--metric", "missing.key"])
    # kernel-mode mismatch: never comparable, even under --hard
    pn2 = tmp_path / "other.json"
    pn2.write_text(json.dumps({**new, "meta": {"kernel_mode": "y"}}))
    assert main([str(pn2), "--baseline", str(pb), "--hard"]) == 0
