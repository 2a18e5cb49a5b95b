"""Unified Policy/SchedulerCore API: registry, routing parity with the
pre-refactor dispatchers (golden numbers captured from the old
`_TargetDispatcher`/`ClusterScheduler` before deletion), elastic topology,
straggler EWMA refresh, and the batched JAX target solver."""
import numpy as np
import pytest

from repro.core import cab_target_state, exhaustive_solve, grin_solve, system_throughput
from repro.sched import (ClusterScheduler, Policy, SchedulerCore,
                         StragglerTracker, SystemView, available_policies,
                         get_policy, solve_targets_jax)
from repro.sched.virtual import VirtualTimeCluster
from repro.sim import ClosedNetworkSimulator, SimConfig, make_distribution

MU = np.array([[20.0, 15.0], [3.0, 8.0]])


def _mu3(seed=4):
    return np.random.default_rng(seed).uniform(1, 30, size=(3, 3))


# ------------------------------------------------------------------ registry

def test_registry_contents_and_lookup():
    names = available_policies()
    for key in ("cab", "grin", "grin+", "slsqp", "opt", "fixed",
                "rd", "bf", "lb", "jsq"):
        assert key in names
    assert get_policy("GrIn").name == "GrIn"        # case-insensitive
    assert get_policy("grin_plus").name == "GrIn+"  # alias
    p = get_policy("cab")
    assert get_policy(p) is p                       # instance passthrough
    assert get_policy("cab") is not get_policy("cab")   # fresh instances
    with pytest.raises(KeyError, match="unknown policy"):
        get_policy("nope")


def test_capability_flags():
    assert get_policy("cab").pool_limit == 2
    assert get_policy("grin").supports_jax_batch
    assert not get_policy("slsqp").integer_target
    assert not get_policy("lb").needs_target
    with pytest.raises(ValueError, match="two-pool"):
        get_policy("cab").solve_target(_mu3(), np.array([2, 2, 2]))
    with pytest.raises(ValueError, match="exactly 2 pools"):
        SchedulerCore("cab", _mu3())


# ------------------------------------------------- parity with old dispatch

class _OldTargetDispatcher:
    """The deleted core.policies._TargetDispatcher routing rule, verbatim."""

    def __init__(self, solve):
        self._solve = solve
        self._target = None
        self._mu = None
        self._key = None

    def reset(self, mu, n_tasks):
        self._mu = np.asarray(mu, dtype=np.float64)
        self._key = None
        self.notify_type_counts(np.asarray(n_tasks))

    def notify_type_counts(self, n_tasks):
        key = tuple(int(x) for x in n_tasks)
        if key != self._key:
            self._key = key
            self._target = self._solve(self._mu, np.asarray(n_tasks))

    def choose(self, task_type, view, rng):
        deficit = self._target[task_type] - view.counts[task_type]
        best = np.flatnonzero(deficit == deficit.max())
        if len(best) == 1:
            return int(best[0])
        return int(best[np.argmax(view.mu[task_type][best])])


@pytest.mark.parametrize("policy,solve", [
    ("cab", cab_target_state),
    ("grin", lambda mu, nt: grin_solve(mu, nt).N),
])
def test_core_routes_identically_to_old_target_dispatcher(policy, solve):
    """Same seeded closed workload, decision-by-decision equality."""
    mu = MU if policy == "cab" else _mu3(11)
    k, l = mu.shape
    nt = np.full(k, 6)
    old = _OldTargetDispatcher(solve)
    old.reset(mu, nt)
    core = SchedulerCore(policy, mu).reset(mu, nt)
    counts = np.zeros((k, l), dtype=np.int64)   # driver-side state for `old`
    rng = np.random.default_rng(0)
    resident = []
    for step in range(400):
        if resident and (len(resident) == nt.sum() or rng.random() < 0.5):
            t, j = resident.pop(rng.integers(len(resident)))
            counts[t, j] -= 1
            core.complete(t, j)
        t = int(rng.integers(k))
        view = SystemView(counts=counts, backlog_work=np.zeros(l),
                          backlog_tasks=counts.sum(axis=0), mu=mu)
        # the old sim pinned the mix externally; mirror that for the core
        mix = counts.sum(axis=1)
        mix[t] += 1
        old.notify_type_counts(mix)
        j_old = old.choose(t, view, rng)
        core.notify_type_counts(mix)
        j_new = core.route(t, view=view)
        assert j_new == j_old, f"diverged at step {step}"
        counts[t, j_old] += 1
        resident.append((t, j_old))
    np.testing.assert_array_equal(core.counts, counts)


def test_cluster_route_sequence_matches_pre_refactor_golden():
    """Seeded churn through ClusterScheduler reproduces the exact route
    sequence and final placement recorded from the pre-refactor code."""
    import hashlib
    mu3, nt3 = _mu3(4), np.array([6, 7, 5])
    sched = ClusterScheduler(mu3, policy="grin")
    rng = np.random.default_rng(7)
    seq = []
    for i, n in enumerate(nt3):
        for _ in range(n):
            seq.append(sched.route(i))
    for _ in range(300):
        occ = np.argwhere(sched.counts > 0)
        t, j = occ[rng.integers(len(occ))]
        sched.complete(int(t), int(j))
        seq.append(sched.route(int(t)))
    assert hashlib.sha256(bytes(seq)).hexdigest() == \
        "714ffe05723f2597ecca36afba1e5cca02569385128c6ef1b7f1e987e3c1215e"
    assert sched.counts.tolist() == [[1, 0, 5], [0, 7, 0], [0, 0, 5]]


def test_sim_sweep_matches_pre_refactor_golden_throughputs():
    """run_policy_sweep on a fixed seed reproduces the CAB throughput (and
    response time) measured before the refactor, to the last bit."""
    from repro.sim import run_policy_sweep
    cfg = SimConfig(mu=MU, n_programs_per_type=np.array([10, 10]),
                    distribution=make_distribution("exponential"), order="PS",
                    n_completions=3000, warmup_completions=600, seed=0)
    out = run_policy_sweep(cfg, ["cab", "rd", "bf", "lb", "jsq"])
    golden_x = {"CAB": 31.370019521998053, "RD": 21.00783671725545,
                "BF": 27.965165311048455, "LB": 21.478136054953588,
                "JSQ": 22.96252460019732}
    for name, x in golden_x.items():
        assert out[name].throughput == pytest.approx(x, abs=1e-9), name
    assert out["CAB"].mean_response_time == pytest.approx(
        0.6320809395450708, abs=1e-9)


def test_grin_sim_matches_pre_refactor_golden():
    mu3, nt3 = _mu3(4), np.array([6, 7, 5])
    cfg = SimConfig(mu=mu3, n_programs_per_type=nt3,
                    distribution=make_distribution("uniform"), order="FCFS",
                    n_completions=2000, warmup_completions=400, seed=12)
    m = ClosedNetworkSimulator(cfg).run("grin")
    assert m.throughput == pytest.approx(74.17287003135185, abs=1e-9)


# ------------------------------------------------------- elastic / straggler

def test_pool_lost_and_added_resolve_through_core():
    mu3 = _mu3(1)
    core = SchedulerCore("grin", mu3)
    for t in (0, 1, 2, 0, 1):
        core.route(t)
    r0 = core.resolves
    core.pool_lost(2)
    assert core.mu.shape == (3, 2) and core.counts.shape == (3, 2)
    assert core.backlog_work.shape == (2,)
    core.route(0)
    assert core.resolves > r0                 # topology change re-solved
    core.pool_added(np.array([25.0, 25.0, 25.0]))
    assert core.mu.shape == (3, 3)
    r1 = core.resolves
    j = core.route(1)
    assert j in (0, 1, 2) and core.resolves > r1
    # a strong new pool must attract load as churn rebalances
    rng = np.random.default_rng(0)
    for _ in range(100):
        occ = np.argwhere(core.counts > 0)
        t, j = occ[rng.integers(len(occ))]
        core.complete(int(t), int(j))
        core.route(int(t))
    assert core.counts[:, 2].sum() > 0


def test_straggler_tracker_relative_speed():
    t = StragglerTracker(3, alpha=0.5)
    for _ in range(6):
        t.observe(0, 1.0)
        t.observe(1, 0.4)    # pool 1 at 40% of nominal
    f = t.slowdown_factors()
    assert f[0] == pytest.approx(1.0, abs=0.05)
    assert f[1] == pytest.approx(0.4, abs=0.1)
    assert f[2] == 1.0       # unseen -> nominal


def test_straggler_ewma_triggers_target_refresh():
    """Timed completions 3x slower than nominal fold into mu and force a
    re-solve; the degraded pool sheds load."""
    core = SchedulerCore("cab", MU, resolve_rate_rel_change=0.2)
    for t in (0,) * 10 + (1,) * 10:
        core.route(t)
    r0 = core.resolves
    for _ in range(10):
        core.complete(1, 1, service_s=3.0 / MU[1, 1])
        core.route(1)
    assert core.mu[0, 1] < MU[0, 1]           # column degraded
    assert core.resolves > r0                 # mu change invalidated cache
    np.testing.assert_array_equal(core.base_mu, MU)   # nominal kept


def test_untimed_completions_do_not_refresh():
    core = SchedulerCore("cab", MU)
    core.route(0)
    core.complete(0, 0)                       # no service_s: no EWMA folding
    np.testing.assert_array_equal(core.mu, MU)


def test_stateless_baselines_stay_static_under_timed_completions():
    """The paper's classic baselines are static: measured service times must
    not fold into the mu that BF/LB route on."""
    core = SchedulerCore("bf", MU, resolve_rate_rel_change=0.1)
    core.route(1)
    for _ in range(10):
        core.complete(1, 1, service_s=5.0 / MU[1, 1])
        core.route(1)
    np.testing.assert_array_equal(core.mu, MU)


def test_reset_restores_nominal_rates():
    """reset() without a new mu must discard EWMA folding, not bake the
    degraded rates in as the new nominal."""
    core = SchedulerCore("cab", MU, resolve_rate_rel_change=0.2)
    core.route(1)
    for _ in range(10):
        core.complete(1, 1, service_s=3.0 / MU[1, 1])
        core.route(1)
    assert core.mu[0, 1] < MU[0, 1]
    core.reset()
    np.testing.assert_array_equal(core.mu, MU)
    np.testing.assert_array_equal(core.base_mu, MU)


# ------------------------------------------------------- batched JAX solving

def test_solve_targets_jax_batches_mixes():
    mu3 = _mu3(4)
    mixes = np.array([[6, 7, 5], [3, 3, 3], [1, 8, 2], [10, 1, 1]])
    targets, xs = solve_targets_jax(mu3, mixes)
    assert targets.shape == (4, 3, 3) and xs.shape == (4,)
    np.testing.assert_array_equal(targets.sum(axis=2), mixes)
    assert np.all(targets >= 0)
    for mix, N, x in zip(mixes, targets, xs):
        x_np = grin_solve(mu3, mix).x_sys
        assert system_throughput(N, mu3) >= 0.95 * x_np
        assert x == pytest.approx(system_throughput(N, mu3), rel=1e-3)
    with pytest.raises(ValueError, match="n_tasks_batch"):
        solve_targets_jax(mu3, np.array([1, 2]))


def test_warm_targets_prefills_cache():
    mu3 = _mu3(4)
    core = SchedulerCore("grin", mu3)
    mixes = [[6, 7, 5], [3, 3, 3], [1, 8, 2]]
    added = core.warm_targets(mixes)
    assert added == 3
    r0 = core.resolves
    core.notify_type_counts([3, 3, 3])
    core.route(0)
    assert core.resolves == r0                # warmed: no host re-solve
    assert core.warm_targets(mixes) == 0      # already cached: nothing added
    # non-batched policies fall back to the host solver loop
    core2 = SchedulerCore("grin+", mu3)
    assert core2.warm_targets(mixes) == 3
    assert core2.resolves == 3


def test_target_cache_evicts_fifo_not_wholesale(monkeypatch):
    """Regression: hitting _CACHE_CAP used to clear the WHOLE cache, so
    warming cap+1 mixes wiped every earlier target and each re-visit
    re-solved from scratch. FIFO eviction must keep the recent entries."""
    from repro.sched import api
    monkeypatch.setattr(api, "_CACHE_CAP", 4)
    mu3 = _mu3(4)
    core = SchedulerCore("grin", mu3)
    mixes = [[6, 7, 5], [3, 3, 3], [1, 8, 2], [10, 1, 1], [2, 2, 14]]
    assert core.warm_targets(mixes) == 5      # 5 inserts, cap 4
    assert len(core._targets) == 4
    r0 = core.resolves
    # the 4 most recent survive: no re-solve on any of them
    for mix in mixes[1:]:
        core.notify_type_counts(mix)
        core.route(0)
        core.complete(0, core.counts[0].argmax())
    assert core.resolves == r0
    # the evicted oldest re-solves exactly once
    core.notify_type_counts(mixes[0])
    core.route(0)
    assert core.resolves == r0 + 1
    # same via the lazy host path: repeated alternation stays cached
    core3 = SchedulerCore("grin+", mu3)
    core3.warm_targets(mixes)                 # host loop, cap 4, FIFO
    assert len(core3._targets) == 4
    r1 = core3.resolves
    core3.warm_targets(mixes[1:])             # all still resident
    assert core3.resolves == r1


# ------------------------------------------------------------ solver backends

def test_slsqp_policy_yields_feasible_integer_target():
    mu3, nt = _mu3(2), np.array([5, 4, 6])
    N = get_policy("slsqp").solve_target(mu3, nt)
    assert N.dtype.kind == "i"
    np.testing.assert_array_equal(N.sum(axis=1), nt)
    assert np.all(N >= 0)


def test_opt_policy_matches_exhaustive():
    mu3, nt = _mu3(5), np.array([3, 2, 3])
    N = get_policy("opt").solve_target(mu3, nt)
    _, x_opt = exhaustive_solve(mu3, nt)
    assert system_throughput(N, mu3) == pytest.approx(x_opt, rel=1e-12)


def test_fixed_policy_pins_external_target():
    target = np.array([[1, 0], [0, 1]])
    core = SchedulerCore(get_policy("fixed", target=target), MU)
    assert core.route(0) == 0 and core.route(1) == 1
    np.testing.assert_array_equal(core.counts, target)
    # the pinned target does not track topology: routing must fail loudly
    core.pool_added(np.array([9.0, 9.0]))
    with pytest.raises(ValueError, match="topology"):
        core.route(0)
    with pytest.raises(TypeError, match="registry names"):
        get_policy(get_policy("fixed", target=target), target=target)


def test_sweep_disambiguates_duplicate_display_names():
    from repro.sim import run_policy_sweep
    cfg = SimConfig(mu=MU, n_programs_per_type=np.array([3, 3]),
                    distribution=make_distribution("constant"), order="PS",
                    n_completions=120, warmup_completions=30, seed=0)
    out = run_policy_sweep(cfg, ["opt",
                                 get_policy("fixed", target=np.eye(2, dtype=np.int64) * 3)])
    assert set(out) == {"Opt", "Opt#2"}


# -------------------------------------------------------- virtual-time driver

def test_virtual_cluster_accepts_policy_names():
    """The virtual-time harness builds the SchedulerCore itself from a
    registry name + measured mu — same numbers as passing the wrapper."""
    fns = [{i: (lambda s, t=1 / MU[i, j]: t) for i in range(2)}
           for j in range(2)]
    types = [0] * 10 + [1] * 10
    m_name = VirtualTimeCluster(fns, measure_real=False).run_closed(
        "cab", types, n_completions=800, warmup=200, mu=MU)
    m_core = VirtualTimeCluster(fns, measure_real=False).run_closed(
        SchedulerCore("cab", MU), types, n_completions=800, warmup=200)
    assert m_name.throughput == pytest.approx(m_core.throughput, rel=1e-12)
    with pytest.raises(ValueError, match="mu"):
        VirtualTimeCluster(fns, measure_real=False).run_closed(
            "cab", types, n_completions=10)
    with pytest.raises(ValueError, match="already owns"):
        VirtualTimeCluster(fns, measure_real=False).run_closed(
            SchedulerCore("cab", MU), types, n_completions=10, mu=MU)


def test_policy_protocol_is_extensible():
    """A user-defined Policy plugs into every driver via the registry."""
    class Greedy(Policy):
        name = "Greedy"
        needs_target = False

        def choose(self, task_type, view, rng):
            return int(np.argmax(view.mu[task_type]))

    core = SchedulerCore(Greedy(), MU)
    assert core.route(0) == 0 and core.route(1) == 1
    m = ClosedNetworkSimulator(SimConfig(
        mu=MU, n_programs_per_type=np.array([4, 4]),
        distribution=make_distribution("constant"), order="PS",
        n_completions=200, warmup_completions=50, seed=0)).run(Greedy())
    assert m.throughput > 0
