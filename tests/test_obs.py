"""Observability spine: metric semantics, spans, exporters, the
BUILD_COUNTS shim, plan-cache provenance, and serving integration."""
import json
import time
import warnings

import numpy as np
import pytest

from repro import obs
from repro.obs.metrics import MetricsRegistry, Snapshot


# ---------------------------------------------------------------------------
# metric semantics
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_semantics():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", kind="a")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    # same label values -> same child; different -> new child
    assert reg.counter("reqs_total", kind="a") is c
    assert reg.counter("reqs_total", kind="b") is not c

    g = reg.gauge("depth")
    g.set(7)
    g.add(-2)
    assert g.value == 5.0

    h = reg.histogram("lat_seconds")
    for v in (1e-4, 1e-3, 1e-2):
        h.observe(v)
    s = h.sample()
    assert s["count"] == 3
    assert abs(s["sum"] - 0.0111) < 1e-9


def test_label_name_mismatch_raises():
    reg = MetricsRegistry()
    reg.counter("x_total", a="1")
    with pytest.raises(ValueError):
        reg.counter("x_total", b="1")          # different labelnames
    with pytest.raises(ValueError):
        reg.gauge("x_total", a="1")            # different kind


def test_label_cardinality_collapses_to_overflow():
    reg = MetricsRegistry()
    fam = reg.family("big_total", "counter", ("i",))
    for i in range(obs.MAX_CARDINALITY + 10):
        fam.labels(i=i).inc()
    assert len(fam.children) <= obs.MAX_CARDINALITY + 1
    over = fam.children.get((obs.OVERFLOW_LABEL,))
    assert over is not None and over.value >= 10


def test_quantile_accuracy_on_known_distribution():
    reg = MetricsRegistry()
    h = reg.histogram("q_seconds")
    rng = np.random.default_rng(0)
    vals = rng.uniform(0.01, 0.1, size=2000)
    for v in vals:
        h.observe(float(v))
    # 4 buckets/decade -> adjacent bounds differ by 10^0.25 ~ 1.78; the
    # geometric interpolation should land within one bucket ratio
    ratio = 10 ** 0.25
    for q in (0.5, 0.95, 0.99):
        est = h.quantile(q)
        true = float(np.quantile(vals, q))
        assert true / ratio <= est <= true * ratio, (q, est, true)


def test_disabled_flag_gates_mutations():
    reg = MetricsRegistry()
    c = reg.counter("gated_total")
    h = reg.histogram("gated_seconds")
    with obs.disabled():
        c.inc()
        h.observe(1.0)
        c.inc_always(3)                        # probes bypass the gate
    assert c.value == 3.0
    assert h.sample()["count"] == 0
    c.inc()
    assert c.value == 4.0


# ---------------------------------------------------------------------------
# spans / tracing
# ---------------------------------------------------------------------------

def test_span_nesting_and_trace():
    obs.clear_trace()
    with obs.span("outer", job="t"):
        with obs.span("inner"):
            time.sleep(0.001)
    entries = {e["name"]: e for e in obs.trace()}
    assert set(entries) >= {"outer", "inner"}
    assert entries["inner"]["depth"] == entries["outer"]["depth"] + 1
    assert entries["inner"]["parent"] == "outer"
    assert entries["outer"]["duration_s"] >= entries["inner"]["duration_s"]
    assert entries["outer"]["labels"] == {"job": "t"}
    assert entries["outer"]["ok"] and entries["inner"]["ok"]


def test_span_exception_safety():
    obs.clear_trace()
    with pytest.raises(RuntimeError):
        with obs.span("boom"):
            raise RuntimeError("kaput")
    (e,) = [t for t in obs.trace() if t["name"] == "boom"]
    assert e["ok"] is False and "kaput" in e["error"]
    # the stack unwound: a new span sits at depth 0 again
    with obs.span("after"):
        pass
    (a,) = [t for t in obs.trace() if t["name"] == "after"]
    assert a["depth"] == 0 and a["parent"] is None


def test_spans_disabled_are_noops():
    obs.clear_trace()
    with obs.disabled():
        with obs.span("ghost"):
            pass
    assert not [t for t in obs.trace() if t["name"] == "ghost"]


def test_nested_spans_carry_parent_ids():
    obs.clear_trace()
    with obs.span("outer"):
        with obs.span("first"):
            with obs.span("deep"):
                pass
        with obs.span("second"):
            pass
    recs = {r["name"]: r for r in obs.trace()}
    outer = recs["outer"]
    assert outer["parent_id"] is None and outer["depth"] == 0
    assert recs["first"]["parent_id"] == outer["id"]
    assert recs["second"]["parent_id"] == outer["id"]
    assert recs["deep"]["parent_id"] == recs["first"]["id"]
    assert len({r["id"] for r in recs.values()}) == 4
    # t0 is on the perf_counter clock, children inside their parent
    assert outer["t0"] <= recs["first"]["t0"] <= recs["deep"]["t0"]
    assert (recs["second"]["t0"] + recs["second"]["duration_s"]
            <= outer["t0"] + outer["duration_s"])
    assert outer["t0"] <= time.perf_counter()


def test_count_tallies_on_every_open_span():
    obs.clear_trace()
    s0 = obs.snapshot()
    with obs.span("outer"):
        obs.count("tally_probe_total", 3, site="a")
        with obs.span("inner"):
            obs.count("tally_probe_total", 4, site="b")
    obs.count("tally_probe_total", 5, site="a")     # no span open
    recs = {r["name"]: r for r in obs.trace()}
    assert recs["outer"]["counts"] == {"tally_probe_total": 7}
    assert recs["inner"]["counts"] == {"tally_probe_total": 4}
    d = obs.snapshot().diff(s0)
    assert d.value("tally_probe_total", site="a") == 8
    assert d.value("tally_probe_total", site="b") == 4


def _host_events(log_dir):
    """Every host event name in the one ``.xplane.pb`` under log_dir,
    with its stats."""
    import glob
    import os
    import jax
    (f,) = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                     recursive=True)
    pd = jax.profiler.ProfileData.from_file(f)
    return [(e.name, {k: v for k, v in e.stats}) for p in pd.planes
            if p.name.startswith("/host") for ln in p.lines
            for e in ln.events]


def test_span_is_a_profiler_host_event(tmp_path):
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("tests.profiled", plan="segment"):
            with obs.span("tests.profiled_inner"):
                pass
    finally:
        jax.profiler.stop_trace()
    events = dict(_host_events(tmp_path))
    # the event keeps the bare span name; labels ride as its metadata
    assert events["tests.profiled"].get("plan") == "segment"
    assert "tests.profiled_inner" in events


def test_span_that_raises_closes_its_profiler_event(tmp_path):
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        with pytest.raises(ValueError):
            with obs.span("tests.raised"):
                raise ValueError("x")
    finally:
        jax.profiler.stop_trace()
    assert "tests.raised" in dict(_host_events(tmp_path))


def test_disabled_span_reaches_neither_sink(tmp_path):
    import jax
    obs.clear_trace()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.disabled():
            with obs.span("tests.ghost_profiled"):
                pass
    finally:
        jax.profiler.stop_trace()
    assert not obs.trace("tests.ghost_profiled")
    assert "tests.ghost_profiled" not in dict(_host_events(tmp_path))


def test_obs_imports_and_spans_without_jax():
    import subprocess
    import sys
    import os
    code = (
        "import sys\n"
        "from repro import obs\n"
        "with obs.span('no.jax'):\n"
        "    pass\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n"
        "print(obs.trace()[0]['name'])\n"
    )
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [os.path.join(os.getcwd(), "src"),
         os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, env=env,
                         timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "no.jax"


# ---------------------------------------------------------------------------
# per-call spans and counters: solver, tuner, schedule, kernels, assembly
# ---------------------------------------------------------------------------

def _tree(recs, parent=None):
    """(name, children) of the records under ``parent``, in start order."""
    kids = sorted((r for r in recs if r["parent_id"] == parent),
                  key=lambda r: r["t0"])
    return [(r["name"], _tree(recs, r["id"])) for r in kids]


FP = ("tune.fingerprint", [])
RESOLVED_PLAN = ("tune.resolve", [FP])
CG_CALL_TAIL = [("kernels.bind", []), ("solver.dispatch", [])]


def _segment_solve(M, b, cache):
    from repro.core import solvers
    from repro.core.plan import ExecutionPlan
    return solvers.cg_solve(M, b, cache=cache, autotune=True,
                            candidates=[ExecutionPlan(path="segment")],
                            tol=0.0, maxiter=3)


def test_cg_solve_span_tree_and_one_trace_a_call():
    import jax.numpy as jnp
    from repro.core import csrc, tuner
    M = csrc.poisson2d(8)
    cache = tuner.PlanCache()
    b = jnp.ones(M.n, jnp.float32)
    _segment_solve(M, b, cache)                 # tunes, builds, caches
    obs.clear_trace()
    s0 = obs.snapshot()
    for _ in range(2):
        _segment_solve(M, b, cache)
    call = ("solver.cg_solve",
            [RESOLVED_PLAN,
             ("schedule.resolve", [FP, ("schedule.value_digest", [])])]
            + CG_CALL_TAIL)
    assert _tree(obs.trace()) == [call, call]
    d = obs.snapshot().diff(s0)
    assert d.value("solver_traces_total") == 2
    # every span's record tallies what its subtree copied and traced
    (root, _) = obs.trace("solver.cg_solve")
    assert root["counts"]["solver_traces_total"] == 1
    assert root["counts"]["host_copy_bytes_total"] == (
        d.total("host_copy_bytes_total") / 2)


def test_host_copy_bytes_of_fingerprint_and_digests():
    from repro.core import csrc, schedule, tuner
    M = csrc.poisson2d(8)
    s0 = obs.snapshot()
    tuner.fingerprint(M)
    schedule.value_digest(M)
    schedule.structure_digest(M)
    d = obs.snapshot().diff(s0)

    def nb(*arrays):
        return sum(a.size * a.dtype.itemsize for a in arrays)
    assert d.value("host_copy_bytes_total", site="fingerprint") == (
        2 * nb(M.ia, M.ja) + nb(M.iar))
    assert d.value("host_copy_bytes_total", site="value_digest") == nb(
        M.ia, M.ja, M.ad, M.al, M.au, M.iar, M.jar, M.ar)
    assert d.value("host_copy_bytes_total", site="structure_digest") == nb(
        M.ia, M.ja, M.iar, M.jar)
    # without a site the host helpers copy uncounted
    s1 = obs.snapshot()
    csrc.nnz_per_row(M)
    csrc.bandwidth(M)
    assert obs.snapshot().diff(s1).total("host_copy_bytes_total") == 0
    # host arrays copy nothing
    s1 = obs.snapshot()
    tuner.fingerprint(csrc.CSRC(**{
        f: (np.asarray(getattr(M, f)) if f not in ("n", "m") else
            getattr(M, f)) for f in ("n", "m", "ad", "ia", "ja", "al",
                                     "au", "iar", "jar", "ar")}))
    assert obs.snapshot().diff(s1).total("host_copy_bytes_total") == 0


def test_assemble_spans_and_value_copy():
    from repro.assembly import (assemble, assembly_schedule_for,
                                mesh as amesh)
    mesh = amesh.grid_tri(5)
    sched = assembly_schedule_for(mesh)
    ke = amesh.poisson_stiffness(mesh, mass=0.5)
    obs.clear_trace()
    s0 = obs.snapshot()
    assemble(sched, ke, strategy="sorted")
    assert _tree(obs.trace()) == [("assembly.value_refresh",
                                   [("assembly.scatter", []),
                                    ("assembly.to_csrc", [])])]
    d = obs.snapshot().diff(s0)
    assert d.value("host_copy_bytes_total", site="to_csrc") == (
        4 * (sched.n + 2 * sched.k))


def test_cg_solve_on_new_values_refreshes_the_schedule():
    import jax.numpy as jnp
    from repro.assembly import (assemble, assembly_schedule_for,
                                mesh as amesh)
    from repro.core import tuner
    mesh = amesh.grid_tri(5)
    sched = assembly_schedule_for(mesh)
    cache = tuner.PlanCache()

    def step(mass):
        M = assemble(sched, amesh.poisson_stiffness(mesh, mass=mass),
                     strategy="sorted")
        return _segment_solve(M, jnp.ones(M.n, jnp.float32), cache)
    step(0.5)
    obs.clear_trace()
    step(0.75)
    digests = [("schedule.structure_digest", []),
               ("schedule.value_digest", [])]
    solve = ("solver.cg_solve",
             [RESOLVED_PLAN,
              ("schedule.resolve",
               [FP, ("schedule.value_digest", []),
                ("schedule.structure_digest", []),
                ("schedule.value_refresh", digests)])]
             + CG_CALL_TAIL)
    assert _tree(obs.trace())[1] == solve


# ---------------------------------------------------------------------------
# snapshot / diff / exporters
# ---------------------------------------------------------------------------

def _loaded_registry():
    reg = MetricsRegistry()
    reg.counter("c_total", kind="x").inc(3)
    reg.gauge("g", path="kernel").set(0.5)
    h = reg.histogram("h_seconds", op="spmv")
    for v in (2e-4, 3e-3, 5e-2):
        h.observe(v)
    return reg


def test_snapshot_diff_semantics():
    reg = _loaded_registry()
    s0 = reg.snapshot()
    reg.counter("c_total", kind="x").inc(2)
    reg.gauge("g", path="kernel").set(0.9)
    reg.histogram("h_seconds", op="spmv").observe(1e-3)
    d = reg.snapshot().diff(s0)
    assert d.value("c_total", kind="x") == 2.0           # counters subtract
    assert d.value("g", path="kernel") == 0.9            # gauges keep new
    hd = d.hist("h_seconds", op="spmv")
    assert hd["count"] == 1 and abs(hd["sum"] - 1e-3) < 1e-12
    assert d.total("c_total") == 2.0


def test_json_export_round_trip():
    reg = _loaded_registry()
    snap2 = Snapshot.from_json(reg.to_json())
    assert snap2.value("c_total", kind="x") == 3.0
    assert snap2.value("g", path="kernel") == 0.5
    assert snap2.hist("h_seconds", op="spmv")["count"] == 3
    # a restored snapshot still diffs against a live one
    reg.counter("c_total", kind="x").inc()
    assert reg.snapshot().diff(snap2).value("c_total", kind="x") == 1.0


def test_prometheus_text_format():
    reg = _loaded_registry()
    text = reg.to_prometheus()
    assert 'c_total{kind="x"} 3' in text
    assert '# TYPE c_total counter' in text
    assert '# TYPE h_seconds histogram' in text
    assert 'h_seconds_count{op="spmv"} 3' in text
    # cumulative buckets end at +Inf with the full count
    inf_lines = [ln for ln in text.splitlines()
                 if ln.startswith("h_seconds_bucket") and '+Inf' in ln]
    assert inf_lines and inf_lines[0].endswith(" 3")
    # every sample line is "name{labels} value" with a parseable value
    for ln in text.splitlines():
        if not ln or ln.startswith("#"):
            continue
        float(ln.rsplit(" ", 1)[1])


def test_merged_hist_across_label_sets():
    reg = MetricsRegistry()
    reg.histogram("m_seconds", path="a").observe(1e-3)
    reg.histogram("m_seconds", path="b").observe(1e-3)
    m = reg.snapshot().merged_hist("m_seconds")
    assert m["count"] == 2
    assert 0 < m["p50"] < 1e-2


# ---------------------------------------------------------------------------
# BUILD_COUNTS shim
# ---------------------------------------------------------------------------

def test_build_counts_dict_compat():
    from repro.core import schedule as S
    before = dict(S.BUILD_COUNTS)
    S.BUILD_COUNTS.inc("test_obs_probe")
    S.BUILD_COUNTS.inc("test_obs_probe", 2)
    after = dict(S.BUILD_COUNTS)
    assert after["test_obs_probe"] - before.get("test_obs_probe", 0) == 3
    assert S.BUILD_COUNTS["never_touched_kind"] == 0      # missing -> 0
    assert "test_obs_probe" in S.BUILD_COUNTS
    assert set(after) == set(S.BUILD_COUNTS.keys())
    # the shim is a real obs counter family underneath
    assert obs.snapshot().value(
        "build_total", kind="test_obs_probe") == after["test_obs_probe"]


def test_build_counts_setitem_deprecated_but_works():
    from repro.core import schedule as S
    base = S.BUILD_COUNTS["legacy_probe"]
    with pytest.warns(DeprecationWarning):
        S.BUILD_COUNTS["legacy_probe"] = base + 5
    assert S.BUILD_COUNTS["legacy_probe"] == base + 5
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        S.BUILD_COUNTS["legacy_probe"] += 1
    assert S.BUILD_COUNTS["legacy_probe"] == base + 6


def test_build_counts_count_while_disabled():
    from repro.core import schedule as S
    base = S.BUILD_COUNTS["disabled_probe"]
    with obs.disabled():
        S.BUILD_COUNTS.inc("disabled_probe")
    assert S.BUILD_COUNTS["disabled_probe"] == base + 1


# ---------------------------------------------------------------------------
# plan-cache provenance
# ---------------------------------------------------------------------------

def _small_matrix():
    from repro.core import csrc
    return csrc.fem_band(300, 4, seed=0)


def test_plan_cache_entry_records_environment(tmp_path):
    from repro.core import tuner
    M = _small_matrix()
    cache = tuner.PlanCache(path=str(tmp_path / "plans.json"))
    res = tuner.tune(M, cache=cache, repeats=1)
    entry = cache.entries[res.fingerprint]
    env = entry["env"]
    for field in obs.MISMATCH_FIELDS + ("git_sha", "python"):
        assert field in env, field
    assert env["jax"] is not None
    # the recorded env matches the live process -> no mismatch counted
    assert not obs.env_mismatches(env)


def test_plan_cache_env_mismatch_counter(tmp_path):
    from repro.core import tuner
    M = _small_matrix()
    cache = tuner.PlanCache(path=str(tmp_path / "plans.json"))
    res = tuner.tune(M, cache=cache, repeats=1)
    entry = cache.entries[res.fingerprint]
    entry["env"] = dict(entry["env"], device_count=9999,
                        device_kind="tpu-v9000")
    s0 = obs.snapshot()
    assert cache.get(res.fingerprint) is not None
    d = obs.snapshot().diff(s0)
    assert d.value("plan_cache_env_mismatch_total",
                   field="device_count") == 1.0
    assert d.value("plan_cache_env_mismatch_total",
                   field="device_kind") == 1.0
    # git_sha never counts as a mismatch
    entry["env"] = dict(entry["env"], device_count=entry["env"][
        "device_count"], git_sha="0000000")
    assert d.total("plan_cache_env_mismatch_total", field="git_sha") == 0


# ---------------------------------------------------------------------------
# serving integration
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def serving_setup():
    from repro.core import tuner
    from repro.serve import SpmvServingEngine
    M = _small_matrix()
    eng = SpmvServingEngine(cache=tuner.PlanCache())
    eng.register("obs_m", M)
    return eng, M


def test_serving_emits_request_metrics(serving_setup):
    eng, M = serving_setup
    rng = np.random.default_rng(0)
    s0 = obs.snapshot()
    for _ in range(4):
        eng.submit("obs_m", rng.standard_normal(M.m).astype(np.float32))
    out = eng.step()
    d = obs.snapshot().diff(s0)
    assert d.total("serve_requests_total", matrix_id="obs_m") == 4.0
    ex = d.merged_hist("serve_execute_seconds", matrix_id="obs_m")
    assert ex["count"] == 1 and ex["sum"] > 0          # one coalesced SpMM
    # the coalesced group carries its size as a label
    (labels, _) = d.find("serve_execute_seconds", matrix_id="obs_m")[0]
    assert labels["nrhs"] == "4"
    qs = d.merged_hist("serve_queue_wait_seconds", matrix_id="obs_m")
    assert qs["count"] == 4
    assert d.merged_hist("serve_batch_size")["count"] == 1
    assert d.merged_hist("serve_tick_seconds")["count"] == 1
    # per-request timings ride on the result
    r = next(iter(out.values()))
    assert r.timings is not None
    assert r.timings["execute_s"] > 0
    assert r.timings["queue_wait_s"] >= 0
    assert "timings" in r.meta()


def test_serving_hot_path_overhead(serving_setup):
    """Metrics off vs on around the same serving ticks: the instrumented
    path must stay within a generous factor (the real budget is <2%; jax
    dispatch noise dominates, so the assertion is deliberately loose)."""
    eng, M = serving_setup
    rng = np.random.default_rng(1)
    xs = [rng.standard_normal(M.m).astype(np.float32) for _ in range(4)]

    def ticks(n):
        t0 = time.perf_counter()
        for _ in range(n):
            for x in xs:
                eng.submit("obs_m", x)
            eng.step()
        return time.perf_counter() - t0

    ticks(3)                                   # warm both code paths
    with obs.disabled():
        t_off = min(ticks(5) for _ in range(3))
    t_on = min(ticks(5) for _ in range(3))
    assert t_on <= t_off * 2.0 + 0.05, (t_on, t_off)


def test_repro_metrics_env_prints_prometheus(tmp_path):
    """REPRO_METRICS=1 makes any process dump Prometheus text at exit."""
    import subprocess
    import sys
    import os
    code = (
        "from repro import obs\n"
        "obs.counter('smoke_total', job='env').inc(2)\n"
    )
    env = dict(os.environ, REPRO_METRICS="1",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.getcwd(), "src"),
                    os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, env=env,
                         timeout=120)
    assert out.returncode == 0, out.stderr
    assert 'smoke_total{job="env"} 2' in out.stdout
    assert "# TYPE smoke_total counter" in out.stdout
