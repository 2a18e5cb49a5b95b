"""program_spans and the metrics that read the program's own spans, on a
hand-built TPU-shaped trace and on a traced CPU run of each cell."""
import types

import pytest

import run as harness
from bench import program_spans, trace_reduce

SPAN_METRICS = ["resolve.prep_ms", "resolve.solve_call_ms",
                "resolve.repair_ms", "resolve.energy_ms",
                "resolve.repaired_rows", "solver.lane_occupancy"]
NEW_METRICS = SPAN_METRICS + ["solver.init_device_ms"]


def _ev(name, start, dur, stats=()):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=list(stats))


def _line(name, evs):
    return types.SimpleNamespace(name=name, events=evs)


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=lines)


SORT = "%sort.2 = s32[8] sort(...)"
WHILE = "%while.2 = s32[8] while(...)"
# op event name -> scope, as `op_scopes` reads them from a TPU trace
SCOPES = {SORT: "grin.init", WHILE: "grin.loop"}


def _request(t0, rows, moves_mean):
    """One request's spans from t0: guard 10, batch 10, prep 20, dispatch 10,
    fetch 100, repair 30, energy 40 (ns), back to back."""
    spans, t = [], t0
    for name, dur, stats in [
            ("repro.price.guard", 10, ()), ("repro.grid.batch", 10, ()),
            ("repro.grin.prep", 20, ()), ("repro.grin.dispatch", 10, ()),
            ("repro.grid.fetch", 100, [("lanes", 8), ("moves_max", 10),
                                       ("moves_mean", moves_mean)]),
            ("repro.grid.repair", 30, [("rows", rows)]),
            ("repro.price.energy", 40, ())]:
        spans.append(_ev(name, t, dur, stats))
        t += dur
    return spans


def profile(with_spans=True):
    """A window [0, 1000) with two requests, [100, 330) and [500, 730).
    The device runs the solver's init op inside each fetch ([160, 180) and
    [560, 570)) and a loop op after it ([180, 230) and [570, 600)), and an
    energy op inside each energy span ([300, 310) and [700, 705))."""
    dev = _plane("/device:TPU:0", [
        _line("XLA Modules", [_ev("jit__grin_block_core(1)", 160, 70),
                              _ev("jit__grin_block_core(1)", 560, 40),
                              _ev("jit_energy(2)", 300, 10),
                              _ev("jit_energy(2)", 700, 5)]),
        _line("XLA Ops", [_ev(SORT, 160, 20), _ev(WHILE, 180, 50),
                          _ev(SORT, 560, 10), _ev(WHILE, 570, 30),
                          _ev("%fusion.9 = f32[8] fusion(...)", 300, 10),
                          _ev("%fusion.9 = f32[8] fusion(...)", 700, 5)]),
    ])
    host = [_ev("bench.window", 0, 1000), _ev("bench.request", 100, 230),
            _ev("bench.request", 500, 230), _ev("bench.sample", 380, 15)]
    if with_spans:
        host += _request(100, rows=2, moves_mean=5.0)
        host += _request(500, rows=4, moves_mean=2.5)
    return types.SimpleNamespace(planes=[_plane("/host:CPU",
                                                [_line("python", host)]),
                                         dev])


def _ctx(pd, scopes=SCOPES):
    return {"trace": trace_reduce.reduce_profile(pd),
            "program_spans": program_spans.reduce_profile(pd, scopes)}


def _read(name, ctx):
    return harness.load_module("layer_metrics", name + ".py").read(ctx)


def test_op_scopes_from_serialized_event_metadata():
    """The `tf_op` stat of each op's event metadata, as a TPU trace keeps
    it (a string value, or a reference to an interned string), names the
    scope; ops of other programs and other stats name none."""
    from jax.profiler import ProfileData
    text = """planes {
      name: "/device:TPU:0"
      lines { name: "XLA Ops" events { metadata_id: 1 duration_ps: 5 } }
      event_metadata { key: 1 value { id: 1 name: "%sort.2 = s32[8] sort()"
        stats { metadata_id: 10 str_value:
                "jit(_grin_block_core)/grin.init/vmap(jit(argsort))/sort:" }
        stats { metadata_id: 11 str_value: "sort" } } }
      event_metadata { key: 2 value { id: 2 name: "%while.2 = s32[8] while()"
        stats { metadata_id: 10 ref_value: 12 } } }
      event_metadata { key: 3 value { id: 3 name: "%fusion.1 = f32[8] fusion()"
        stats { metadata_id: 10 str_value: "jit(other)/mul" } } }
      stat_metadata { key: 10 value { id: 10 name: "tf_op" } }
      stat_metadata { key: 11 value { id: 11 name: "hlo_category" } }
      stat_metadata { key: 12 value { id: 12
        name: "jit(_grin_block_core)/grin.loop/while" } }
    }
    planes { name: "/host:CPU" event_metadata { key: 1 value { id: 1
      name: "repro.x.y" stats { metadata_id: 1 str_value: "grin.init" } } }
      stat_metadata { key: 1 value { id: 1 name: "tf_op" } } }"""
    data = ProfileData.text_proto_to_serialized_xspace(text)
    assert program_spans.op_scopes(data) == {
        "%sort.2 = s32[8] sort()": "grin.init",
        "%while.2 = s32[8] while()": "grin.loop"}


def test_reduce_program_spans():
    red = program_spans.reduce_profile(profile(), SCOPES)
    assert red["window"] == (0.0, 1000.0)
    assert [sp[0] for sp in red["spans"][:7]] == [
        "repro.price.guard", "repro.grid.batch", "repro.grin.prep",
        "repro.grin.dispatch", "repro.grid.fetch", "repro.grid.repair",
        "repro.price.energy"]
    assert red["spans"][5][3] == {"rows": 2}
    assert red["scoped"]["grin.init"].tolist() == [[160, 180], [560, 570]]
    assert red["scoped"]["grin.loop"].tolist() == [[180, 230], [570, 600]]


@pytest.mark.parametrize("name,want", [
    # guard + batch + prep: 40 ns a request, no device time inside
    ("resolve.prep_ms", 40e-6),
    # dispatch + fetch 110 ns, less the device's 70 and 40 ns: median 55
    ("resolve.solve_call_ms", 55e-6),
    ("resolve.repair_ms", 30e-6),
    # energy 40 ns, less the device's 10 and 5 ns: median 32.5
    ("resolve.energy_ms", 32.5e-6),
    ("resolve.repaired_rows", 3.0),
    # 100 x moves_mean / moves_max: 50 and 25
    ("solver.lane_occupancy", 37.5),
    # the init op's 20 and 10 ns
    ("solver.init_device_ms", 15e-6),
])
def test_metric_on_tpu_shaped_trace(name, want):
    assert _read(name, _ctx(profile())) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_metric_without_program_spans_is_none(name):
    # an older program: no spans, and its ops name no scope
    assert _read(name, _ctx(profile(with_spans=False), scopes={})) is None


def test_requests_without_spans_are_left_out():
    pd = profile()
    host = pd.planes[0].lines[0]
    # the second request's spans go: only the first request is read
    host.events = [ev for ev in host.events if not (
        ev.name.startswith("repro.") and ev.start_ns >= 500)]
    ctx = _ctx(pd)
    assert _read("resolve.repair_ms", ctx) == pytest.approx(30e-6)
    assert _read("resolve.repaired_rows", ctx) == 2.0
    assert _read("solver.lane_occupancy", ctx) == pytest.approx(50.0)


def test_only_the_runs_own_trace_is_read(tmp_path, monkeypatch):
    """The trace under bench/traces/<cell> is read when its window is the
    one the run reduced, and counts as empty when it was left by another
    run."""
    import jax
    from jax.profiler import TraceAnnotation
    from repro.obs import span
    monkeypatch.setattr(program_spans, "TRACE_ROOT", str(tmp_path))
    with trace_reduce.traced(str(tmp_path / "cell")):
        with TraceAnnotation("bench.window"), span("repro.test.phase"):
            jax.block_until_ready(jax.numpy.ones(4) + 1)
    own = {"cell": {"name": "cell"},
           "trace": trace_reduce.reduce_dir(str(tmp_path / "cell"))}
    assert [sp[0] for sp in program_spans.of(own)["spans"]] == [
        "repro.test.phase"]
    other = {"cell": {"name": "cell"},
             "trace": trace_reduce.reduce_profile(profile())}
    assert program_spans.of(other)["spans"] == []
    assert _read("resolve.prep_ms", other) is None


def test_traced_cpu_run_reports_span_metrics(tmp_path, monkeypatch):
    """A traced run of the cell, end to end on the CPU at a tiny size, reads
    the program's spans from the trace it wrote (no device scope stats on
    the CPU, so no init time)."""
    import jax
    from test_cells import CPU_PEAKS, cells, tiny
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(harness, "peak_for", lambda kind: CPU_PEAKS)
    monkeypatch.setattr(harness, "TRACE_ROOT", str(tmp_path))
    monkeypatch.setattr(program_spans, "TRACE_ROOT", str(tmp_path))
    out = harness.run_cell(tiny(cells()[0]), 2**31 + 41, 1.0, True,
                           require=lambda n: jax.devices()[:n])
    got = out["metrics"]
    assert set(SPAN_METRICS) <= set(got), sorted(got)
    phases = sum(got[m]["value"] for m in SPAN_METRICS if m.endswith("_ms"))
    assert 0 < phases <= 1.05 * got["resolve.host_ms"]["value"]
    assert 0 <= got["solver.lane_occupancy"]["value"] <= 100
