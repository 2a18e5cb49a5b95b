"""Traffic subsystem: arrival streams, quantile accumulator accuracy, SLO
admission control, the host/device open engines, and the closed-network
regression guard (open-mode plumbing must not move closed results at all).
"""
import numpy as np
import pytest

from repro.core.affinity import PowerModel
from repro.sched import SchedulerCore, get_policy
from repro.sched.priority import GrInPriorityPolicy
from repro.sched.virtual import VirtualTimeCluster
from repro.sim import ClosedNetworkSimulator, SimConfig, make_distribution
from repro.sim.engine_jax import simulate_policy_jax
from repro.traffic import (AdmissionController, DiurnalArrivals, LogHistogram,
                           MMPPArrivals, OpenTraffic, PoissonArrivals,
                           SLOClass, TraceArrivals, TrafficSpec,
                           default_admit_limits, exact_quantiles,
                           open_sim_config, replay_open, simulate_open_batch)
from repro.traffic.quantiles import QUANTILES


# ------------------------------- arrivals ---------------------------------

def test_poisson_arrivals_rate_and_determinism():
    rng = np.random.default_rng(0)
    t = PoissonArrivals(4.0).sample(rng, 20000)
    assert t.shape == (20000,) and np.all(np.diff(t) >= 0)
    rate = len(t) / t[-1]
    assert rate == pytest.approx(4.0, rel=0.05)
    t2 = PoissonArrivals(4.0).sample(np.random.default_rng(0), 20000)
    np.testing.assert_array_equal(t, t2)


def test_scaled_arrivals_double_rate():
    rng = np.random.default_rng(1)
    t = PoissonArrivals(2.0).scaled(2.0).sample(rng, 10000)
    assert len(t) / t[-1] == pytest.approx(4.0, rel=0.05)


def test_mmpp_burstier_than_poisson():
    n = 20000
    tm = MMPPArrivals(rates=(8.0, 0.5), mean_dwell=(2.0, 6.0)).sample(
        np.random.default_rng(2), n)
    tp = PoissonArrivals(len(tm) / tm[-1]).sample(np.random.default_rng(2), n)

    def cv_counts(t):  # CV of per-unit-time arrival counts
        c = np.bincount(t.astype(int))
        return c.std() / c.mean()

    assert np.all(np.diff(tm) >= 0)
    assert cv_counts(tm) > 1.5 * cv_counts(tp)


def test_diurnal_mean_rate():
    t = DiurnalArrivals(5.0, amplitude=0.5, period=40.0).sample(
        np.random.default_rng(3), 20000)
    assert len(t) / t[-1] == pytest.approx(5.0, rel=0.1)


def test_trace_arrivals_cycle():
    base = np.array([0.0, 1.0, 3.0])
    t = TraceArrivals(base, period=4.0).sample(np.random.default_rng(0), 7)
    np.testing.assert_allclose(t, [0, 1, 3, 4, 5, 7, 8])


def test_traffic_spec_merge_shares_and_types():
    spec = TrafficSpec((PoissonArrivals(6.0), PoissonArrivals(2.0)),
                       np.eye(2))
    times, types = spec.sample(0, 20000)
    assert np.all(np.diff(times) >= 0) and times[0] >= 0
    assert spec.total_rate == pytest.approx(8.0)
    np.testing.assert_allclose(spec.type_rates(), [6.0, 2.0])
    share = np.bincount(types, minlength=2) / len(types)
    assert share[0] == pytest.approx(0.75, abs=0.02)
    t2, ty2 = spec.sample(0, 20000)
    np.testing.assert_array_equal(times, t2)
    np.testing.assert_array_equal(types, ty2)


# ------------------------- quantile accumulator ---------------------------

def test_log_histogram_quantiles_within_documented_bound():
    """Satellite: device-histogram p50/p99/p999 vs exact host quantiles on
    heavy-tailed (hyperexponential, CV^2 ~ 10) response samples must stay
    within the documented relative-error bound."""
    dist = make_distribution("hyperexp")
    samples = dist.sample(np.random.default_rng(4), 20000)
    hist = LogHistogram()
    counts = hist.counts(samples)
    assert counts.sum() == len(samples)
    exact = exact_quantiles(samples, QUANTILES)
    for q, ex in zip(QUANTILES, exact):
        approx = hist.quantile(counts, q)
        assert abs(approx - ex) / ex <= hist.rel_error_bound, (q, approx, ex)


def test_log_histogram_bound_is_tight_enough():
    assert LogHistogram().rel_error_bound < 0.04


def test_exact_quantiles_order_statistics():
    x = np.arange(1, 101, dtype=float)
    np.testing.assert_allclose(exact_quantiles(x, (0.5, 0.99)), [50.0, 99.0])
    assert np.isnan(exact_quantiles([], (0.5,))[0])


# ------------------------- admission controller ---------------------------

def _mu2():
    return np.array([[8.0, 2.0], [2.0, 6.0]])


def test_unroute_is_inverse_of_route():
    core = SchedulerCore(get_policy("jsq"), _mu2())
    before_counts = core.counts.copy()
    before_backlog = core._backlog.copy()
    j = core.route(0)
    core.unroute(0, j)
    np.testing.assert_array_equal(core.counts, before_counts)
    np.testing.assert_allclose(core._backlog, before_backlog, atol=1e-12)


def test_admission_sheds_best_effort_and_adapts():
    core = SchedulerCore(get_policy("jsq"), _mu2())
    slo = (SLOClass(deadline=0.5, percentile=0.9, protected=True),
           SLOClass(deadline=10.0))
    adm = AdmissionController(core, slo, class_of_type=[0, 1],
                             queue_capacity=4, window=16, adapt_every=4)
    # breach the protected SLO -> best-effort limit walks down
    for _ in range(4):
        verdict, j = adm.offer(0, 0.0)
        assert verdict == "admit"
        adm.complete(0, j, 5.0)          # way over the 0.5 deadline
    assert adm.limits[1] < adm.n_slots
    assert adm.limits[0] == adm.n_slots  # protected limit never moves
    # recover -> limit walks back up
    for _ in range(40):
        verdict, j = adm.offer(0, 0.0)
        adm.complete(0, j, 0.01)
    assert adm.limits[1] > 1.0
    # past the best-effort limit the class sheds, protected still admits
    adm.limits[1] = 0.0
    assert adm.offer(1, 1.0)[0] == "shed"
    assert adm.shed[1] == 1
    assert adm.offer(0, 1.0)[0] == "admit"


def test_admission_defer_mode_drains():
    core = SchedulerCore(get_policy("jsq"), _mu2())
    slo = (SLOClass(deadline=1.0, protected=True), SLOClass(deadline=10.0))
    adm = AdmissionController(core, slo, class_of_type=[0, 1],
                             queue_capacity=2, mode="defer", adapt_every=10**9)
    adm.limits[1] = 1.0
    assert adm.offer(1, 0.0)[0] == "admit"
    assert adm.offer(1, 0.1)[0] == "defer"     # over the class limit
    assert adm.deferred_total[1] == 1
    adm.complete(1, 1, 0.2)                    # frees a slot
    drained = adm.drain(0.3)
    assert len(drained) == 1 and drained[0][0] == 1


def test_default_admit_limits():
    slo = (SLOClass(deadline=1.0, protected=True), SLOClass(deadline=5.0))
    np.testing.assert_array_equal(default_admit_limits(slo, 16), [16, 8])


# --------------------------- host open engine -----------------------------

def test_host_open_mm1_response_time():
    """Single pool, Poisson(5) vs mu=10: M/M/1 with a large cap, so
    E[T] ~ 1/(mu - lambda) and X ~ lambda."""
    mu = np.array([[10.0]])
    spec = TrafficSpec((PoissonArrivals(5.0),), np.ones((1, 1)))
    cfg = open_sim_config(mu, spec, n_arrivals=20000, warmup_arrivals=2000,
                          queue_capacity=60,
                          distribution=make_distribution("exponential"),
                          order="PS", seed=0)
    m = ClosedNetworkSimulator(cfg).run("lb")
    assert m.throughput == pytest.approx(5.0, rel=0.05)
    assert m.mean_response_time == pytest.approx(0.2, rel=0.2)
    assert m.dropped == 0
    # Little's law in open form: occupancy == X * E[T]
    assert m.little_product == pytest.approx(
        m.throughput * m.mean_response_time, rel=1e-6)


def test_host_open_overload_drops():
    mu = np.array([[10.0]])
    spec = TrafficSpec((PoissonArrivals(20.0),), np.ones((1, 1)))
    cfg = open_sim_config(mu, spec, n_arrivals=20000, warmup_arrivals=2000,
                          queue_capacity=8,
                          distribution=make_distribution("exponential"),
                          order="FCFS", seed=1)
    m = ClosedNetworkSimulator(cfg).run("lb")
    assert m.throughput == pytest.approx(10.0, rel=0.1)
    assert m.dropped / m.offered == pytest.approx(0.5, abs=0.06)


def test_open_traffic_validation():
    spec = TrafficSpec((PoissonArrivals(1.0),), np.ones((1, 1)))
    with pytest.raises(ValueError):
        OpenTraffic(spec=spec, n_arrivals=100, warmup_arrivals=100)
    with pytest.raises(ValueError):
        OpenTraffic(spec=spec, n_arrivals=100, queue_capacity=0)


# -------------------------- device open engine ----------------------------

def test_device_open_matches_host_mm1():
    mu = np.array([[10.0]])
    spec = TrafficSpec((PoissonArrivals(5.0),), np.ones((1, 1)))
    times, types = spec.sample(0, 8000)
    out = simulate_open_batch(
        mu, np.array([[[8]]]), times[None], types[None], [0],
        distribution=make_distribution("exponential"), queue_capacity=60,
        order="PS", warmup_arrivals=800)
    assert float(out["throughput"][0]) == pytest.approx(5.0, rel=0.05)
    assert float(out["mean_response_time"][0]) == pytest.approx(0.2, rel=0.2)
    assert int(out["dropped"][0]) == 0


# ----------------------- closed-network regression ------------------------
# Open-mode plumbing (SimConfig.traffic, dispatch in run(), engine_jax
# dispatch) must leave the closed path untouched: both engines pinned to
# goldens captured before the traffic subsystem existed.

_G_MU = np.random.default_rng(31).uniform(1, 30, size=(3, 3))


def _g_cfg(order):
    return SimConfig(mu=_G_MU, n_programs_per_type=np.array([8, 6, 10]),
                     distribution=make_distribution("exponential"),
                     order=order, power=PowerModel(alpha=0.5),
                     n_completions=3000, warmup_completions=600, seed=7)


@pytest.mark.parametrize("policy,order,x,et,e", [
    ("grin", "PS", 76.99692687923347, 0.3109305947317131,
     0.19673565047635844),
    ("lb", "PS", 19.957483861572435, 1.1959656237647063,
     0.3382490231563386),
    ("grin", "FCFS", 76.66038689659207, 0.31166358367741726,
     0.19801054690559663),
])
def test_closed_host_goldens_bit_identical(policy, order, x, et, e):
    m = ClosedNetworkSimulator(_g_cfg(order)).run(policy)
    assert m.throughput == pytest.approx(x, rel=1e-12)
    assert m.mean_response_time == pytest.approx(et, rel=1e-12)
    assert m.mean_energy == pytest.approx(e, rel=1e-12)


def test_closed_device_golden_unchanged():
    # device streams come from jax.random's default (partitionable threefry
    # since JAX 0.5); the golden is pinned under that default
    m = simulate_policy_jax(_g_cfg("PS"), SchedulerCore("grin", _G_MU))
    assert m.throughput == pytest.approx(76.9281997680664, rel=1e-5)
    assert m.mean_response_time == pytest.approx(0.31242313981056213,
                                                 rel=1e-5)
    assert m.mean_energy == pytest.approx(0.19760367274284363, rel=1e-5)


# ------------------------------ trace replay ------------------------------

def test_replay_open_synthetic_cluster():
    mu = _mu2()
    fns = [{i: (lambda i=i, j=j: (lambda s: 1.0 / mu[i, j]))()
            for i in range(2)} for j in range(2)]
    vc = VirtualTimeCluster(fns, measure_real=False)
    rng = np.random.default_rng(5)
    times = np.sort(rng.uniform(0, 40, 300))
    types = rng.integers(0, 2, 300)
    core = SchedulerCore(GrInPriorityPolicy((2.0, 1.0)), mu)
    slo = (SLOClass(deadline=2.0, percentile=0.9, protected=True),
           SLOClass(deadline=10.0))
    adm = AdmissionController(core, slo, class_of_type=[0, 1],
                             queue_capacity=4, window=32, adapt_every=8)
    m = replay_open(vc, adm, times, types, warmup=30)
    assert m.throughput > 0
    assert m.class_completed.sum() > 0
    # conservation: every measured completion was admitted
    assert (m.class_completed + m.class_shed).sum() <= len(times)
    assert np.all(np.isfinite(m.class_p99[m.class_completed > 0]))
    assert m.limits.shape == (2,)
