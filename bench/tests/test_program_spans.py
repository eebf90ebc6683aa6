"""The per-layer metrics that read the program's own spans and counters,
on synthetic records and on a whole CPU run; and idle gaps named after
the program's innermost span."""
from types import SimpleNamespace

import pytest

import harness
import programspans
import spanreport
import tracereduce as tr
from conftest import BENCH
from repro import obs

CG = ("solve_host_s.cg", "cg_traces.cg", "host_copy_mb.cg")
STEP = ("solve_host_s.step", "assemble_host_s.step", "host_copy_mb.step")


def reader(name):
    return harness.load_module(BENCH / "metrics" / f"{name}.py",
                               f"test_metric_{name.replace('.', '_')}")


def rec(name, t0, dur, depth=0, **counts):
    return {"name": name, "t0": t0, "duration_s": dur, "depth": depth,
            "counts": counts, "labels": {}, "id": 0, "parent_id": None,
            "parent": None, "start": t0, "ok": True, "error": None}


def step(t, copies, traces=1):
    """One time step at ``t``: assemble (to_csrc inside), then solve."""
    return [rec("assembly.to_csrc", t + 0.01, 0.1, depth=1),
            rec("assembly.value_refresh", t, 0.2,
                host_copy_bytes_total=7e6),
            rec("tune.fingerprint", t + 0.3, 0.01, depth=2),
            rec("solver.cg_solve", t + 0.25, 0.05,
                host_copy_bytes_total=copies,
                solver_traces_total=traces)]


@pytest.fixture
def run_ctx(run_small, monkeypatch):
    """Run a cell at test size; returns the ``ctx`` its readers got."""
    seen = {}
    real = harness.read_metrics

    def keep(entries, ctx, bench=harness.BENCH):
        seen["ctx"] = ctx
        return real(entries, ctx, bench)
    monkeypatch.setattr(harness, "read_metrics", keep)

    def run(workload, **kw):
        assert run_small(workload, **kw) is not None
        return seen["ctx"]
    return run


@pytest.fixture
def ring(monkeypatch):
    recs = []
    monkeypatch.setattr(obs, "trace", lambda name=None: list(recs))
    return recs


def test_readers_take_the_window_after_the_setup_calls(ring):
    # two set-up steps, then three in the window, then a burst assembly
    ring += step(0.0, 1e9, traces=5) + step(1.0, 1e9, traces=5)
    for t in (2.0, 3.0, 4.0):
        ring += step(t, 40e6)
    ring.append(rec("assembly.to_csrc", 5.0, 9.0, depth=1))
    ctx = SimpleNamespace(cg_iters=[20, 21, 22])
    got = {m: reader(m).read(ctx) for m in CG + STEP}
    assert got["solve_host_s.step"] == pytest.approx(0.05)
    assert got["assemble_host_s.step"] == pytest.approx(0.1)
    assert got["host_copy_mb.step"] == pytest.approx(47.0)
    assert got["cg_traces.cg"] == pytest.approx(1.0)
    assert got["solve_host_s.cg"] == pytest.approx(0.05)
    assert got["host_copy_mb.cg"] == pytest.approx(47.0)


def test_a_window_of_every_solve_the_ring_holds(ring):
    ring += [rec("solver.cg_solve", 1.0, 0.7, host_copy_bytes_total=4e8,
                 solver_traces_total=1.0)]
    ctx = SimpleNamespace(cg_iters=[50])
    assert reader("solve_host_s.cg").read(ctx) == pytest.approx(0.7)
    assert reader("host_copy_mb.cg").read(ctx) == pytest.approx(400.0)
    assert reader("cg_traces.cg").read(ctx) == pytest.approx(1.0)


@pytest.mark.parametrize("metric", CG + STEP)
def test_none_without_the_programs_spans(ring, metric):
    ctx = SimpleNamespace(cg_iters=[50, 50])
    assert reader(metric).read(ctx) is None          # nothing recorded
    # records of a program older than these spans: no t0, no counts
    ring.append({"name": "solver.cg_solve", "duration_s": 1.0,
                 "depth": 0, "parent": None, "labels": {}, "start": 0.0,
                 "ok": True, "error": None})
    assert reader(metric).read(ctx) is None
    # fewer solves than the window's calls, and no run at all
    ring[:] = [rec("solver.cg_solve", 0.0, 1.0)]
    assert reader(metric).read(ctx) is None
    assert reader(metric).read(SimpleNamespace()) is None


@pytest.mark.parametrize("workload,metrics", [("hpcg27.cg", CG),
                                              ("fem_tet.step", STEP)])
def test_readers_on_a_whole_cpu_run(run_ctx, workload, metrics):
    ctx = run_ctx(workload, seconds=0.5)
    got = {m: reader(m).read(ctx) for m in metrics}
    assert all(v is not None and v > 0 for v in got.values()), got
    if workload == "hpcg27.cg":
        assert got["cg_traces.cg"] == 1.0
    spans = programspans.window_spans(ctx)
    solves = [r for r in spans if r["name"] == "solver.cg_solve"]
    assert len(solves) == len(ctx.cg_iters)
    rows = {name: (t, s, n) for name, t, s, n in spanreport.table(ctx)}
    assert rows["solver.cg_solve"][2] == 1.0
    assert all(0 <= s <= t for t, s, _ in rows.values())


def test_span_report_splits_each_span_into_total_and_self(ring):
    def span(name, id, parent_id, t0, dur):
        r = rec(name, t0, dur, depth=0 if parent_id is None else 1)
        r.update(id=id, parent_id=parent_id)
        return r
    ring += [span("tune.fingerprint", 2, 1, 0.1, 0.3),
             span("tune.fingerprint", 3, 1, 0.5, 0.2),
             span("solver.dispatch", 4, 1, 0.8, 0.1),
             span("solver.cg_solve", 1, None, 0.0, 1.0)]
    ctx = SimpleNamespace(cg_iters=[50])
    assert spanreport.table(ctx) == [
        ("solver.cg_solve", 1.0, pytest.approx(0.4), 1.0),
        ("tune.fingerprint", pytest.approx(0.5), pytest.approx(0.5), 2.0),
        ("solver.dispatch", 0.1, 0.1, 1.0)]
    # no spans, no table
    ring.clear()
    assert spanreport.table(ctx) == []


@pytest.mark.parametrize("workload,metrics", [("hpcg27.cg", CG),
                                              ("fem_tet.step", STEP)])
def test_control_leaves_the_new_metrics_out(run_ctx, workload, metrics):
    import control
    from conftest import small_cell
    obs.clear_trace()
    ctx = run_ctx(workload, seconds=0.3,
                  entries=control.entries(small_cell(workload)))
    assert ctx.cg_iters
    assert {m: reader(m).read(ctx) for m in metrics} == dict.fromkeys(
        metrics)


def test_gap_is_named_after_the_innermost_program_span():
    host = [(0.0, 1.0, "bench.window"),
            (0.1, 0.9, "bench.cg_solve"),
            (0.1, 0.8, "solver.cg_solve"),
            (0.2, 0.7, "schedule.value_digest")]
    trace = tr.Trace(devices=[[(0.0, 0.15, "%fusion", "m"),
                               (0.75, 1.0, "%fusion", "m")]], host=host)
    lo, hi = 0.15, 0.75
    assert trace.busy(0.0, 1.0)[0] == [(0.0, 0.15), (0.75, 1.0)]
    labels = ("bench.",) + programspans.PROGRAM_PREFIXES
    assert tr._label(trace.host, lo, hi, labels) == "schedule.value_digest"
    # the benchmark's prefixes alone name it after its own annotation
    assert tr._label(trace.host, lo, hi, ("bench.",)) == "bench.cg_solve"
