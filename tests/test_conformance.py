"""Cross-engine conformance suite: the host event core is the oracle every
engine change is diffed against, in one place.

One seeded (mu, mix, seed) grid runs under grin (deficit routing), LB and
JSQ, under PS and FCFS, on both engines; the device engine must agree with
the host on measured X_sys AND E/task within sampling tolerance (the engines
use different RNG streams, so parity is statistical, per point and tighter
in aggregate). The power model is the weak-affinity alpha=0.5 regime so the
energy surface actually varies across placements. Structural identities
(Little's law, power-integral vs per-completion energy accounting) must hold
on both engines exactly as the model predicts.
"""
import numpy as np
import pytest

from repro.core.affinity import PowerModel
from repro.sched import get_policy
from repro.sched.priority import flat_mu, flatten_mixes
from repro.sim import (ClosedNetworkSimulator, SimConfig, make_distribution,
                       priority_sim_config, sweep_jax)

POWER = PowerModel(alpha=0.5)
MUS = np.stack([np.random.default_rng(11).uniform(1, 30, size=(3, 3)),
                np.random.default_rng(12).uniform(1, 30, size=(3, 3))])
MIXES = np.array([[10, 10, 10], [6, 14, 10]])
SEEDS = [0, 1]
N_COMPLETIONS, WARMUP = 4000, 800

# per-point sampling noise at ~3200 measured completions; the mean over the
# grid cancels most of it
PT_TOL, MEAN_TOL = 0.15, 0.05


def _cfg(mu, mix, seed, order):
    return SimConfig(mu=mu, n_programs_per_type=np.asarray(mix),
                     distribution=make_distribution("exponential"),
                     order=order, power=POWER, n_completions=N_COMPLETIONS,
                     warmup_completions=WARMUP, seed=seed)


def _host_grid(policy, order):
    return [ClosedNetworkSimulator(_cfg(MUS[g], mix, s, order)).run(policy)
            for g, mix, s in _grid_index()]


def _grid_index():
    return [(g, mix, s) for g in range(len(MUS)) for mix in MIXES
            for s in SEEDS]


@pytest.mark.parametrize("order", ["PS", "FCFS"])
@pytest.mark.parametrize("policy", ["grin", "lb", "jsq"])
def test_engine_conformance_x_and_energy(policy, order):
    cfg = _cfg(MUS[0], MIXES[0], SEEDS[0], order)
    grid, dev = sweep_jax(cfg, policy, mixes=MIXES, seeds=SEEDS, mus=MUS)
    host = _host_grid(policy, order)
    assert [(g, s) for g, _, s in grid] == \
        [(g, s) for g, _, s in _grid_index()]
    x_rel, e_rel = [], []
    for i, h in enumerate(host):
        x_rel.append(abs(dev["throughput"][i] - h.throughput) / h.throughput)
        e_rel.append(abs(dev["mean_energy"][i] - h.mean_energy)
                     / h.mean_energy)
        # structural: Little's law and the two energy accountings agree on
        # BOTH engines (power integral / X == per-completion E[E])
        n = MIXES[0].sum()
        assert dev["little_product"][i] == pytest.approx(n, rel=0.05)
        assert h.little_product == pytest.approx(n, rel=0.05)
        assert dev["mean_power"][i] / dev["throughput"][i] == pytest.approx(
            dev["mean_energy"][i], rel=0.03)
        assert h.mean_power / h.throughput == pytest.approx(
            h.mean_energy, rel=0.03)
    assert max(x_rel) < PT_TOL, (policy, order, x_rel)
    assert max(e_rel) < PT_TOL, (policy, order, e_rel)
    assert np.mean(x_rel) < MEAN_TOL, (policy, order, x_rel)
    assert np.mean(e_rel) < MEAN_TOL, (policy, order, e_rel)


# --------------------------------------------------------------------------
# Multi-class cell: the same host-oracle gate for the priority subsystem —
# per-class X AND per-class E must agree across engines on a
# (mu x mix x seed) grid, for the class-weighted policy and the class-blind
# baselines, under PS and the strict-priority PRIO order. Strict priority
# can legitimately starve the batch class on a saturated column; the gate
# then requires BOTH engines to agree the class starved (inf/inf).
# --------------------------------------------------------------------------

PMU_BASE = [np.random.default_rng(21).uniform(1, 30, size=(2, 3)),
            np.random.default_rng(22).uniform(1, 30, size=(2, 3))]
PCLASS_MIXES = np.array([[[3, 2], [7, 8]],       # (M, C, k): small latency
                         [[2, 4], [9, 5]]])      # class + a big batch class
PSEEDS = [0, 1]
# strict priority starves the batch class for long stretches, so its
# per-class rates need the longer run to settle
P_COMP, P_WARM = 6000, 1200
P_PT_TOL, P_MEAN_TOL = 0.2, 0.08


@pytest.mark.parametrize("order", ["PS", "PRIO"])
@pytest.mark.parametrize("policy", ["grin-p", "lb", "jsq"])
def test_multiclass_engine_conformance_per_class(policy, order):
    pol = (get_policy("grin-p", weights=[3.0, 1.0]) if policy == "grin-p"
           else policy)
    mixes_flat = flatten_mixes(PCLASS_MIXES)
    mus_flat = np.stack([flat_mu(m, 2) for m in PMU_BASE])
    cfg0 = priority_sim_config(
        PMU_BASE[0], PCLASS_MIXES[0], distribution=make_distribution(
            "exponential"), order=order, power=POWER, n_completions=P_COMP,
        warmup_completions=P_WARM, seed=PSEEDS[0])
    grid, dev = sweep_jax(cfg0, pol, mixes=mixes_flat, seeds=PSEEDS,
                          mus=mus_flat)
    x_rel, e_rel = [], []
    i = 0
    for g, mu in enumerate(PMU_BASE):
        for cm in PCLASS_MIXES:
            for s in PSEEDS:
                cfg = priority_sim_config(
                    mu, cm, distribution=make_distribution("exponential"),
                    order=order, power=POWER, n_completions=P_COMP,
                    warmup_completions=P_WARM, seed=s)
                h = ClosedNetworkSimulator(cfg).run(pol)
                # totals decompose into the class split on both engines
                assert h.class_throughput.sum() == pytest.approx(
                    h.throughput, rel=1e-9)
                assert dev["class_throughput"][i].sum() == pytest.approx(
                    dev["throughput"][i], rel=1e-5)
                for c in range(2):
                    hx = h.class_throughput[c]
                    dx = dev["class_throughput"][i][c]
                    he = h.class_energy[c]
                    de = dev["class_energy"][i][c]
                    if hx == 0 or dx == 0:     # strict-priority starvation:
                        # engines must agree the class is dead, relative to
                        # the point's own total rate (no absolute loophole)
                        assert hx < 0.02 * h.throughput, (c, hx, dx)
                        assert dx < 0.02 * dev["throughput"][i], (c, hx, dx)
                        continue
                    x_rel.append(abs(dx - hx) / hx)
                    e_rel.append(abs(de - he) / he)
                i += 1
    assert max(x_rel) < P_PT_TOL, (policy, order, x_rel)
    assert max(e_rel) < P_PT_TOL, (policy, order, e_rel)
    assert np.mean(x_rel) < P_MEAN_TOL, (policy, order, x_rel)
    assert np.mean(e_rel) < P_MEAN_TOL, (policy, order, e_rel)


# --------------------------------------------------------------------------
# Open-arrival cell: the same host-oracle gate for the traffic subsystem.
# Both engines consume the SAME pre-sampled arrival realization (times and
# types from `TrafficSpec.sample`), so arrival noise cancels exactly and
# only the size streams differ: per-class throughput, response time, p99
# (device: log-histogram; host: exact) and drop fractions must agree
# statistically on a (mu x spec x seed) grid under PS and PRIO.
# --------------------------------------------------------------------------

from repro.sched import SchedulerCore  # noqa: E402
from repro.sched.priority import GrInPriorityPolicy  # noqa: E402
from repro.sim.engine_jax import (MODE_DEFICIT,  # noqa: E402
                                  _BASELINE_MODES)
from repro.traffic import (MMPPArrivals, PoissonArrivals,  # noqa: E402
                           TrafficSpec, open_sim_config, simulate_open_batch)
from repro.traffic.config import derive_target_mix  # noqa: E402

OMUS = [np.random.default_rng(41).uniform(2, 20, size=(2, 2)),
        np.random.default_rng(42).uniform(2, 20, size=(2, 2))]
OSEEDS = [0, 1]
O_T, O_WARM, O_QCAP = 4000, 800, 6
O_CLS = [0, 1]
# per-point tolerances at ~3200 measured arrivals; grid means much tighter
O_X_TOL, O_ET_TOL, O_P99_TOL = 0.15, 0.30, 0.45
O_X_MEAN, O_ET_MEAN = 0.05, 0.12
O_DROP_ABS, O_DROP_MEAN = 0.06, 0.03


def _open_specs(mu):
    """Two traffic shapes per system at ~0.7 of each class's best rate:
    smooth Poisson, and an MMPP burst stream on the latency class."""
    lam = [0.7 * mu[c].max() for c in range(2)]
    return [
        TrafficSpec((PoissonArrivals(lam[0]), PoissonArrivals(lam[1])),
                    np.eye(2)),
        TrafficSpec((MMPPArrivals(rates=(2.0 * lam[0], 0.25 * lam[0]),
                                  mean_dwell=(2.0, 4.0)),
                     PoissonArrivals(lam[1])), np.eye(2)),
    ]


def _open_grid():
    return [(mi, si, s) for mi in range(len(OMUS)) for si in range(2)
            for s in OSEEDS]


@pytest.mark.parametrize("order", ["PS", "PRIO"])
@pytest.mark.parametrize("policy", ["grin-p", "lb", "jsq"])
def test_open_engine_conformance_per_class(policy, order):
    pol = (GrInPriorityPolicy((2.0, 1.0)) if policy == "grin-p" else
           get_policy(policy))
    dist = make_distribution("exponential")
    rows_mu, rows_tgt, rows_t, rows_ty, rows_seed, hosts = [], [], [], [], [], []
    for mi, si, s in _open_grid():
        mu = OMUS[mi]
        spec = _open_specs(mu)[si]
        mix = derive_target_mix(spec, mu.shape[1], O_QCAP)
        cfg = open_sim_config(mu, spec, n_arrivals=O_T,
                              warmup_arrivals=O_WARM, queue_capacity=O_QCAP,
                              class_of_type=O_CLS, target_mix=mix,
                              distribution=dist, order=order, seed=s)
        hosts.append(ClosedNetworkSimulator(cfg).run(pol))
        times, tys = spec.sample(s, O_T)
        rows_mu.append(mu)
        rows_tgt.append(np.asarray(pol.solve_target(mu, mix))
                        if pol.needs_target
                        else np.zeros(mu.shape, np.int64))
        rows_t.append(times)
        rows_ty.append(tys)
        rows_seed.append(s)
    mode = MODE_DEFICIT if pol.needs_target else _BASELINE_MODES[pol.key]
    dev = simulate_open_batch(
        np.stack(rows_mu), np.stack(rows_tgt), np.stack(rows_t),
        np.stack(rows_ty), rows_seed, distribution=dist,
        queue_capacity=O_QCAP, order=order, warmup_arrivals=O_WARM,
        class_of_type=O_CLS, power=POWER,
        modes=np.full(len(hosts), mode, np.int32))
    x_rel, et_rel, p99_rel, drop_abs = [], [], [], []
    for i, h in enumerate(hosts):
        for c in range(2):
            hx, dx = h.class_throughput[c], float(
                dev["class_throughput"][i][c])
            assert hx > 0 and dx > 0, (i, c, hx, dx)
            x_rel.append(abs(dx - hx) / hx)
            het = h.class_response_time[c]
            det = float(dev["class_response_time"][i][c])
            et_rel.append(abs(det - het) / het)
            # tails: exact host quantile vs device histogram quantile
            hp99 = float(np.asarray(h.class_quantiles)[c, 1])
            dp99 = float(dev["class_quantiles"][i][c, 1])
            p99_rel.append(abs(dp99 - hp99) / hp99)
            assert p99_rel[-1] < O_P99_TOL, (i, c, hp99, dp99)
        # drops: same arrival realization, so fractions must track closely
        off = h.offered
        drop_abs.append(abs(h.dropped / off - float(dev["dropped"][i]) / off))
        assert drop_abs[-1] < O_DROP_ABS, (i, h.dropped, dev["dropped"][i])
    assert max(x_rel) < O_X_TOL, (policy, order, x_rel)
    assert np.mean(x_rel) < O_X_MEAN, (policy, order, x_rel)
    assert max(et_rel) < O_ET_TOL, (policy, order, et_rel)
    assert np.mean(et_rel) < O_ET_MEAN, (policy, order, et_rel)
    assert np.mean(drop_abs) < O_DROP_MEAN, (policy, order, drop_abs)
    assert np.mean(p99_rel) < 0.15, (policy, order, p99_rel)


# ---------------------------------------------------------------------------
# Fault-injection conformance: both engines run the SAME realized fault
# schedule (crash breakpoints, per-arrival transient-failure counts), so
# goodput and lost-work must agree the way throughput does. Open mode shares
# the arrival realization too; only task-size streams differ. Closed-mode
# transient failures are drawn on device (own fold), so that cell is purely
# statistical. Re-route/recovery latencies are NOT pinned: the host loop
# censors them at the last arrival while the device scan drains in-flight
# completions — a documented diagnostic divergence.
# ---------------------------------------------------------------------------
from repro.faults import FaultScenario, build_fault_batch, crash, make_storm  # noqa: E402
from repro.sim.engine_jax import simulate_batch  # noqa: E402

F_X_TOL, F_WASTE_TOL, F_DROP_ABS = 0.10, 0.35, 0.06


@pytest.mark.parametrize("policy", ["grin-p", "lb"])
def test_open_fault_conformance_goodput_and_lost_work(policy):
    pol = (GrInPriorityPolicy((2.0, 1.0)) if policy == "grin-p" else
           get_policy(policy))
    dist = make_distribution("exponential")
    mode = MODE_DEFICIT if pol.needs_target else _BASELINE_MODES[pol.key]
    rows = []
    for mi in range(len(OMUS)):
        mu = OMUS[mi]
        spec = _open_specs(mu)[0]
        mix = derive_target_mix(spec, mu.shape[1], O_QCAP)
        tgt = (np.asarray(pol.solve_target(mu, mix)) if pol.needs_target
               else np.zeros(mu.shape, np.int64))
        for s in OSEEDS:
            times, tys = spec.sample(s, O_T)
            tw, te = float(times[O_WARM - 1]), float(times[-1])
            sc = FaultScenario(
                events=make_storm(mu.shape[1], n_bursts=2, group_size=1,
                                  window=(tw + 0.15 * (te - tw),
                                          tw + 0.6 * (te - tw)),
                                  downtime=0.08 * (te - tw), seed=5),
                fail_prob=0.02, ckpt_period=0.05, hedge_classes=(0,),
                refresh_targets=pol.needs_target)
            cfg = open_sim_config(mu, spec, n_arrivals=O_T,
                                  warmup_arrivals=O_WARM,
                                  queue_capacity=O_QCAP, class_of_type=O_CLS,
                                  target_mix=mix, distribution=dist,
                                  order="PS", seed=s, faults=sc)
            host = ClosedNetworkSimulator(cfg).run(pol)
            fb = build_fault_batch([sc], mu[None], tgt[None], seeds=[s],
                                   mode="open", policies=pol, mixes=mix,
                                   n_arrivals=O_T, n_classes=2)
            dev = simulate_open_batch(
                mu[None], tgt[None], times[None], tys[None], [s],
                distribution=dist, queue_capacity=O_QCAP, order="PS",
                warmup_arrivals=O_WARM, class_of_type=O_CLS,
                modes=np.full(1, mode, np.int32), faults=fb)
            assert host.topology_events == int(dev["topology_events"][0])
            assert host.failures > 0 and int(dev["failures"][0]) > 0
            g_rel = abs(float(dev["goodput"][0]) - host.goodput) / host.goodput
            w_rel = (abs(float(dev["wasted_work"][0]) - host.wasted_work)
                     / max(host.wasted_work, 1e-9))
            d_abs = abs(host.dropped - float(dev["dropped"][0])) / (O_T - O_WARM)
            assert host.wasted_work > 0.0, (policy, mi, s)
            assert g_rel < F_X_TOL, (policy, mi, s, host.goodput,
                                     float(dev["goodput"][0]))
            assert d_abs < F_DROP_ABS, (policy, mi, s, host.dropped,
                                        int(dev["dropped"][0]))
            rows.append((g_rel, w_rel, d_abs))
    g, w, d = np.asarray(rows).T
    assert w.max() < F_WASTE_TOL, (policy, rows)
    assert g.mean() < 0.04 and w.mean() < 0.20, (policy, rows)


@pytest.mark.parametrize("policy", ["grin", "lb"])
def test_closed_fault_conformance_goodput_and_lost_work(policy):
    pol = get_policy(policy)
    dist = make_distribution("exponential")
    mode = MODE_DEFICIT if pol.needs_target else _BASELINE_MODES[pol.key]
    mu, mix = MUS[0], MIXES[0]
    sc = FaultScenario(events=crash(1, 6.0, 12.0), fail_prob=0.05,
                       ckpt_period=0.05, refresh_targets=pol.needs_target)
    tgt = (np.asarray(pol.solve_target(mu, mix)) if pol.needs_target
           else np.zeros(mu.shape, np.int64))
    g_rel, w_rel = [], []
    for s in SEEDS:
        cfg = SimConfig(mu=mu, n_programs_per_type=np.asarray(mix),
                        distribution=dist, order="PS",
                        n_completions=N_COMPLETIONS,
                        warmup_completions=WARMUP, seed=s, faults=sc)
        host = ClosedNetworkSimulator(cfg).run(pol)
        fb = build_fault_batch([sc], mu[None], tgt[None], seeds=[s],
                               mode="closed", policies=pol, mixes=mix,
                               n_completions=N_COMPLETIONS)
        types0 = np.repeat(np.arange(3), mix).astype(np.int32)
        dev = simulate_batch(mu[None], tgt[None], types0[None], [s],
                             distribution=dist, order="PS",
                             n_completions=N_COMPLETIONS,
                             warmup_completions=WARMUP,
                             modes=np.full(1, mode, np.int32), faults=fb)
        assert host.topology_events == int(dev["topology_events"][0]) == 1
        assert host.failures > 0 and int(dev["failures"][0]) > 0
        assert host.wasted_work > 0.0 and float(dev["wasted_work"][0]) > 0.0
        g_rel.append(abs(float(dev["goodput"][0]) - host.goodput)
                     / host.goodput)
        w_rel.append(abs(float(dev["wasted_work"][0]) - host.wasted_work)
                     / host.wasted_work)
    # device redraws transient failures on its own stream: statistical parity
    assert max(g_rel) < PT_TOL and np.mean(g_rel) < 0.05, (policy, g_rel)
    assert max(w_rel) < 0.8 and np.mean(w_rel) < 0.5, (policy, w_rel)


# ---------------------------------------------------------------------------
# Stochastic availability (hazard) conformance: both engines consume the SAME
# per-seed Weibull up/down realization (drawn on the dedicated [seed, 4, pool]
# substream) with the age-threshold checkpoint policy and straggler-triggered
# speculative hedging armed — goodput / wasted-work / drops must agree at the
# PR 7 fault gates and crash breakpoints must match exactly.
# ---------------------------------------------------------------------------
from repro.faults import UpDownProcess, make_hazard_scenario  # noqa: E402


def test_open_hazard_conformance_with_quantile_hedging():
    pol = GrInPriorityPolicy((2.0, 1.0))
    dist = make_distribution("exponential")
    rows = []
    for mi in range(len(OMUS)):
        mu = OMUS[mi]
        spec = _open_specs(mu)[0]
        mix = derive_target_mix(spec, mu.shape[1], O_QCAP)
        tgt = np.asarray(pol.solve_target(mu, mix))
        for s in OSEEDS:
            times, tys = spec.sample(s, O_T)
            te = float(times[-1])
            proc = UpDownProcess(mtbf=0.35 * te, mttr=0.06 * te,
                                 up_shape=1.8)
            sc = make_hazard_scenario(proc, mu.shape[1], te, s,
                                      fail_prob=0.02, ckpt_period=0.05,
                                      ckpt_age=0.02, hedge_quantile=0.9,
                                      hedge_min_obs=64, refresh_targets=True)
            cfg = open_sim_config(mu, spec, n_arrivals=O_T,
                                  warmup_arrivals=O_WARM,
                                  queue_capacity=O_QCAP, class_of_type=O_CLS,
                                  target_mix=mix, distribution=dist,
                                  order="PS", seed=s, faults=sc)
            host = ClosedNetworkSimulator(cfg).run(pol)
            fb = build_fault_batch([sc], mu[None], tgt[None], seeds=[s],
                                   mode="open", policies=pol, mixes=mix,
                                   n_arrivals=O_T, n_classes=2)
            dev = simulate_open_batch(
                mu[None], tgt[None], times[None], tys[None], [s],
                distribution=dist, queue_capacity=O_QCAP, order="PS",
                warmup_arrivals=O_WARM, class_of_type=O_CLS,
                modes=np.full(1, MODE_DEFICIT, np.int32), faults=fb)
            # identical realized availability: breakpoints match exactly
            assert host.topology_events == int(dev["topology_events"][0]) > 0
            assert host.spec_hedges > 0      # the trigger armed and fired
            assert host.wasted_work > 0.0
            g_rel = (abs(float(dev["goodput"][0]) - host.goodput)
                     / host.goodput)
            w_rel = (abs(float(dev["wasted_work"][0]) - host.wasted_work)
                     / max(host.wasted_work, 1e-9))
            d_abs = (abs(host.dropped - float(dev["dropped"][0]))
                     / (O_T - O_WARM))
            assert g_rel < F_X_TOL, (mi, s, host.goodput,
                                     float(dev["goodput"][0]))
            assert w_rel < F_WASTE_TOL, (mi, s, host.wasted_work,
                                         float(dev["wasted_work"][0]))
            assert d_abs < F_DROP_ABS, (mi, s, host.dropped,
                                        int(dev["dropped"][0]))
            rows.append((g_rel, w_rel, d_abs))
    g, w, _ = np.asarray(rows).T
    assert g.mean() < 0.05 and w.mean() < 0.25, rows


# ---------------------------------------------------------------------------
# Autoscale decision-trace conformance: a DVFS governor watches a recorded
# diurnal arrival realization offline, its decision trace is lowered onto the
# PR 7 fault fabric (PoolEvent scale = frequency, 0 = park), and BOTH engines
# replay the SAME (arrival realization x mu schedule) — goodput, E/task, and
# drop fractions must agree at the fault-cell gates, with topology
# breakpoints matching exactly. This pins the controller <-> engine contract:
# whatever the governor decides is bit-identically the schedule both engines
# execute.
# ---------------------------------------------------------------------------
from repro.core import DVFSModel  # noqa: E402
from repro.faults import decisions_to_events  # noqa: E402
from repro.sched.autoscale import (AutoscaleGovernor,  # noqa: E402
                                   GovernorConfig)
from repro.traffic import DiurnalArrivals  # noqa: E402


def test_autoscale_trace_conformance_goodput_energy_drops():
    pol = GrInPriorityPolicy((2.0, 1.0))
    dist = make_distribution("exponential")
    dvfs = DVFSModel(alpha=3.0, levels=(0.5, 0.75, 1.0))
    n_epochs = 24
    rows = []
    for mi in range(len(OMUS)):
        mu = OMUS[mi]
        lam = [0.7 * mu[c].max() for c in range(2)]
        period = O_T / sum(lam) / 2.0        # ~two day/night cycles
        spec = TrafficSpec(
            (DiurnalArrivals(base=lam[0], amplitude=0.9, period=period),
             DiurnalArrivals(base=lam[1], amplitude=0.9, period=period)),
            np.eye(2))
        mix = derive_target_mix(spec, mu.shape[1], O_QCAP)
        tgt = np.asarray(pol.solve_target(mu, mix))
        for s in OSEEDS:
            times, tys = spec.sample(s, O_T)
            te = float(times[-1])
            gov = AutoscaleGovernor(
                mu, dvfs=dvfs,
                config=GovernorConfig(epoch=te / n_epochs, hysteresis=0.0))
            edges = np.linspace(0.0, te, n_epochs + 1)
            for e in range(n_epochs):
                win = (times >= edges[e]) & (times < edges[e + 1])
                gov.observe(np.bincount(tys[win], minlength=2),
                            float(edges[e + 1] - edges[e]))
                if edges[e + 1] < 0.95 * te:   # keep events in-horizon
                    gov.decide(now=float(edges[e + 1]))
            events = decisions_to_events(gov.decisions, mu.shape[1])
            assert events, (mi, s)  # the deep swing forced real actions
            sc = FaultScenario(events=events, refresh_targets=True)
            cfg = open_sim_config(mu, spec, n_arrivals=O_T,
                                  warmup_arrivals=O_WARM,
                                  queue_capacity=O_QCAP, class_of_type=O_CLS,
                                  target_mix=mix, distribution=dist,
                                  order="PS", seed=s, power=POWER, faults=sc)
            host = ClosedNetworkSimulator(cfg).run(pol)
            fb = build_fault_batch([sc], mu[None], tgt[None], seeds=[s],
                                   mode="open", policies=pol, mixes=mix,
                                   n_arrivals=O_T, n_classes=2)
            dev = simulate_open_batch(
                mu[None], tgt[None], times[None], tys[None], [s],
                distribution=dist, queue_capacity=O_QCAP, order="PS",
                warmup_arrivals=O_WARM, class_of_type=O_CLS, power=POWER,
                modes=np.full(1, MODE_DEFICIT, np.int32), faults=fb)
            # same realized mu schedule: breakpoints must match exactly
            assert host.topology_events == int(dev["topology_events"][0]) > 0
            g_rel = (abs(float(dev["goodput"][0]) - host.goodput)
                     / host.goodput)
            e_rel = (abs(float(dev["mean_energy"][0]) - host.mean_energy)
                     / host.mean_energy)
            d_abs = (abs(host.dropped - float(dev["dropped"][0]))
                     / (O_T - O_WARM))
            assert g_rel < F_X_TOL, (mi, s, host.goodput,
                                     float(dev["goodput"][0]))
            assert e_rel < F_X_TOL, (mi, s, host.mean_energy,
                                     float(dev["mean_energy"][0]))
            assert d_abs < F_DROP_ABS, (mi, s, host.dropped,
                                        int(dev["dropped"][0]))
            # parks strand in-flight work; gate only when the stranding is
            # material (near-zero denominators make rel noise meaningless)
            hw, dw = host.wasted_work, float(dev["wasted_work"][0])
            if max(hw, dw) > 0.05:
                assert abs(dw - hw) / max(hw, dw) < F_WASTE_TOL, \
                    (mi, s, hw, dw)
            rows.append((g_rel, e_rel, d_abs))
    g, e, _ = np.asarray(rows).T
    assert g.mean() < 0.05 and e.mean() < 0.05, rows


# ---------------------------------------------------------------------------
# Telemetry conformance: with the trace-time-static telemetry carry armed on
# the device open engine and the host accumulator attached to the oracle
# loop, both sides integrate the SAME quantities (total occupancy, power
# draw) into the SAME bins over the SAME horizon — the arrival realization
# is shared, only the task-size streams differ, so the series must agree
# statistically: per-cell mean-over-bins relative error under the fault-cell
# throughput gate, per-bin worst case under the wasted-work gate.
# ---------------------------------------------------------------------------
from repro.obs import telemetry_series  # noqa: E402
from repro.sched.api import as_core  # noqa: E402
from repro.traffic.host import run_open  # noqa: E402

O_NBINS = 12


def test_open_telemetry_conformance_occupancy_power():
    pol = GrInPriorityPolicy((2.0, 1.0))
    dist = make_distribution("exponential")
    occ_mean, pw_mean = [], []
    for mi in range(len(OMUS)):
        mu = OMUS[mi]
        spec = _open_specs(mu)[0]
        mix = derive_target_mix(spec, mu.shape[1], O_QCAP)
        tgt = np.asarray(pol.solve_target(mu, mix))
        for s in OSEEDS:
            cfg = open_sim_config(mu, spec, n_arrivals=O_T,
                                  warmup_arrivals=O_WARM,
                                  queue_capacity=O_QCAP, class_of_type=O_CLS,
                                  target_mix=mix, distribution=dist,
                                  order="PS", seed=s, power=POWER)
            host = run_open(ClosedNetworkSimulator(cfg), as_core(pol, mu),
                            telemetry=O_NBINS)
            times, tys = spec.sample(s, O_T)
            dev = simulate_open_batch(
                mu[None], tgt[None], times[None], tys[None], [s],
                distribution=dist, queue_capacity=O_QCAP, order="PS",
                warmup_arrivals=O_WARM, class_of_type=O_CLS, power=POWER,
                modes=np.full(1, MODE_DEFICIT, np.int32),
                telemetry_bins=O_NBINS)
            hs = telemetry_series(host.telemetry)
            ds = telemetry_series(dev["telemetry"])
            # shared arrival realization: identical horizon, hence bins
            assert np.isclose(float(ds["horizon"][0]),
                              float(hs["horizon"]), rtol=1e-5)
            # no faults armed: hedge series is identically zero on both
            assert not np.any(hs["hedges"]) and not np.any(ds["hedges"][0])
            h_occ = np.asarray(hs["occupancy"]).sum(axis=1)   # total in-system
            d_occ = np.asarray(ds["occupancy"][0]).sum(axis=1)
            h_pw = np.asarray(hs["power"])
            d_pw = np.asarray(ds["power"][0])
            assert h_occ.min() > 0 and h_pw.min() > 0, (mi, s)
            occ_rel = np.abs(d_occ - h_occ) / h_occ
            pw_rel = np.abs(d_pw - h_pw) / h_pw
            assert occ_rel.max() < F_WASTE_TOL, (mi, s, occ_rel)
            assert pw_rel.max() < F_WASTE_TOL, (mi, s, pw_rel)
            occ_mean.append(occ_rel.mean())
            pw_mean.append(pw_rel.mean())
    # grid means sit at the fault-cell throughput gate
    assert np.mean(occ_mean) < F_X_TOL, occ_mean
    assert np.mean(pw_mean) < F_X_TOL, pw_mean
    assert max(pw_mean) < 1.5 * F_X_TOL, pw_mean
