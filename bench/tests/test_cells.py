"""Every cell of BENCHMARK.json driven end to end on the CPU at a tiny size
(the chip check skipped, Pallas in interpret mode): untraced and traced
runs print every key, the control computed a precision below fails, and
each fault the cell can have, planted in the timed path, turns `correct`
false."""
import copy
import json
import os

import jax
import jax.numpy as jnp
import ml_dtypes
import pytest

import control
import run as harness

CPU_PEAKS = {"flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11}

# tiny versions of each driver's traffic; limits stay as the cell states
SHRINK = {
    "plan_grid": {"traffic_spec": {"n_mixes": 4, "tasks_per_mix": 600},
                  "workload_spec": {"check_points": 8, "control_points": 3,
                                    "trace_seconds": 1.0}},
}
# drivers whose comparison is exact up to rounding: a tiny run must pass
EXACT = {"plan_grid"}


def cells():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        return [c["name"] for c in json.load(f)["workloads"]]


def tiny(name):
    cell = harness.cell_spec(name)
    for key, over in SHRINK[cell["workload_spec"]["driver"]].items():
        cell[key] = dict(copy.deepcopy(cell[key]), **over)
    return cell


@pytest.fixture(autouse=True)
def _cpu(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(harness, "peak_for", lambda kind: CPU_PEAKS)
    monkeypatch.setattr(harness, "TRACE_ROOT", str(tmp_path))


def cpu(n):
    return jax.devices()[:n]


@pytest.mark.parametrize("name", cells())
@pytest.mark.parametrize("trace", [False, True], ids=["e2e", "traced"])
def test_cell_runs(name, trace):
    cell = tiny(name)
    out = harness.run_cell(cell, 2**31 + 17, 1.0, trace, require=cpu)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["failed"] == 0
    want = [m["name"] for m in (cell["per_layer"] if trace
                                else cell["end_to_end"])]
    if trace:
        assert set(out["metrics"]) <= set(want) and out["metrics"]
        assert out["device"]["busy_s"] > 0 and out["device"]["window_s"] > 0
        assert "breakdown" in out
        for m in out["metrics"].values():
            assert m["unit"] != "%" or 0 <= m["value"] <= 100
    else:
        assert sorted(out["metrics"]) == sorted(want)
    assert out["correct"] == all(c["value"] <= c["limit"]
                                 for c in out["checks"].values())
    if cell["workload_spec"]["driver"] in EXACT:
        assert out["correct"], out["checks"]


def test_refuses_without_a_tpu():
    with pytest.raises(SystemExit):
        harness.require_chips(1)


def test_unknown_traffic_kind_is_refused():
    from bench import gen
    with pytest.raises(SystemExit):
        gen.for_mix({"kind": "no_such_kind"})


@pytest.mark.parametrize("name", cells())
def test_control_fails(name):
    cell = tiny(name)
    lim = cell["workload_spec"]["limits"]
    dtype = control.control_dtype(cell["config_spec"]["precision"])
    assert dtype is ml_dtypes.bfloat16
    summary = control.readings(cell, [5, 2**31 + 3], 0.5, dtype,
                               out=open(os.devnull, "w"))
    assert any(v > lim[k] for k, v in summary["upper"].items()), summary
    if cell["workload_spec"]["driver"] in EXACT:
        assert all(v <= lim[k] for k, v in summary["lower"].items()), summary


# ---------------------------------------------------------------------------
# Faults planted in the timed path: each must turn `correct` false.
# ---------------------------------------------------------------------------

def _solver_fault(kind):
    """Wrap the batched solver the pricing path calls."""
    import repro.sched.api as api
    real = api.grin_solve_batch_jax

    def broken(mu, mixes, **kw):
        if kind == "unchanged":            # the step returns its state
            return real(mu, mixes, max_moves=0, **kw)
        N, x, conv, moves = real(mu, mixes, **kw)
        if kind == "half_batch":           # half the batch left out
            h = N.shape[0] // 2
            N = jnp.concatenate([N[:h], N[:N.shape[0] - h]])
            x = jnp.concatenate([x[:h], x[:x.shape[0] - h]])
        elif kind == "altered":            # an answer altered where made
            x = x * 1.001
        return N, x, conv, moves
    return api, "grin_solve_batch_jax", broken


FAULTS = {
    "plan_grid": {k: (lambda k=k: _solver_fault(k))
                  for k in ("unchanged", "half_batch", "altered")},
}


def _fault_cases():
    for name in cells():
        driver = harness.cell_spec(name)["workload_spec"]["driver"]
        for kind in FAULTS[driver]:
            yield name, kind


@pytest.mark.parametrize("name,kind", list(_fault_cases()))
def test_fault_turns_correct_false(name, kind, monkeypatch):
    cell = tiny(name)
    target, attr, broken = FAULTS[cell["workload_spec"]["driver"]][kind]()
    monkeypatch.setattr(target, attr, broken)
    out = harness.run_cell(cell, 2**31 + 29, 1.0, False, require=cpu)
    assert out["correct"] is False, out["checks"]
