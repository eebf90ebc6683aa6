"""The trace reduction on interval arithmetic and on a small trace
recorded on a v5e by ``record_trace.py``."""
import shutil
from types import SimpleNamespace

import pytest

import tracereduce as tr
from conftest import BENCH

FIXTURE = BENCH / "tests" / "data" / "v5e_small.xplane.pb"


def test_union_clip_covered():
    u = tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)])
    assert u == [(0, 2.5), (3, 4)]
    assert tr.clip(u, 1, 3.5) == [(1, 2.5), (3, 3.5)]
    assert tr.covered(tr.clip(u, 1, 3.5)) == pytest.approx(2.0)


def test_idle_percent():
    assert tr.idle_percent(SimpleNamespace(busy_s=1.0,
                                           traced_window_s=4.0)) == 75.0
    assert tr.idle_percent(SimpleNamespace()) is None


@pytest.fixture(scope="module")
def trace_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    shutil.copy(FIXTURE, d / "host.xplane.pb")
    return d.parents[2]


def test_window_busy_idle_and_labels(trace_dir):
    t = tr.Trace.load(trace_dir)
    lo, hi = t.span("bench.window")
    red = tr.reduce_window(trace_dir)
    assert red.window_s == pytest.approx(hi - lo)
    # two products ran; the sleep of 50 ms left the device idle
    assert 0 < red.busy_s < red.window_s - 0.05
    label, gap = red.idle_gaps[0]
    assert label == "bench.sleep" and 0.05 <= gap < red.window_s
    assert all(g1[1] >= g2[1] for g1, g2 in zip(red.idle_gaps,
                                                 red.idle_gaps[1:]))
    # the busy time is the ops' own intervals, merged
    ops = [(s, e) for s, e, _, _ in t.devices[0]]
    assert red.busy_s == pytest.approx(
        tr.covered(tr.clip(tr.union(ops), lo, hi)))
    assert red.top_ops and red.top_ops[0][1] <= red.busy_s + 1e-12


def test_session_busy_is_the_five_products(trace_dir):
    import jax
    pd = jax.profiler.ProfileData.from_file(tr.xplane_file(trace_dir))
    plane = pd.find_plane_with_name("/device:TPU:0")
    (mods,) = [ln for ln in plane.lines if ln.name == tr.MODULES_LINE]
    runs = [e.duration_ns * 1e-9 for e in mods.events]
    # two products in the window and three in the burst
    assert len(runs) == 5
    assert tr.device_busy_s(trace_dir) == pytest.approx(sum(runs), rel=0.01)
