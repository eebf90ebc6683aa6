"""Streaming kernel variants: the fused 'stream' executors (gather +
segment-sum in XLA) must be bit-identical to the one-hot Pallas kernels
on dyadic values — both sum the same slots into the same window
positions, so with exactly-representable values the only freedom (float
addition order) cannot show.  Plus the tuner's
predict-then-measure mode: the analytic roofline ranking must keep the
full-measurement winner inside the measured top-K while cutting the
measurement count at least in half."""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import csrc, tuner
from repro.core.plan import ExecutionPlan
from repro.kernels import ops
from repro.roofline import cost_model


def _dyadic(M):
    """Quantize values to multiples of 1/64: float sums become exact, so
    variant comparisons can assert bitwise equality."""
    q = lambda a: np.round(np.asarray(a) * 64.0) / 64.0
    return dataclasses.replace(M, ad=q(M.ad), al=q(M.al), au=q(M.au))


def _dyadic_x(m, nrhs, seed=0):
    r = np.random.default_rng(seed)
    shape = (m,) if nrhs == 1 else (m, nrhs)
    return (np.round(r.uniform(-1.0, 1.0, shape) * 8.0) / 8.0
            ).astype(np.float32)


def _empty_rows(n):
    i = np.arange(0, n, 2)
    return csrc.from_coo(i, i, np.ones(i.size), n=n)


MATRICES = [
    ("fem_band", lambda: csrc.fem_band(200, 12, seed=5)),
    ("fem_band_sym", lambda: csrc.fem_band(200, 12, seed=5,
                                           numeric_symmetric=True)),
    ("rect_tail", lambda: csrc.fem_band(130, 5, seed=3)),   # n % tm != 0
    ("empty_rows", lambda: _empty_rows(64)),
    ("powerlaw", lambda: csrc.powerlaw_laplacian(192, seed=7)),
]
_BY_NAME = dict(MATRICES)


def _plan(path, variant, **kw):
    base = (dict(path="nnzsplit", k_step_sublanes=2)
            if path == "nnzsplit" else dict(path=path, tm=128))
    base.update(kw, variant=variant)
    return ExecutionPlan(**base)


def _assert_variants_identical(M, path, nrhs, **plan_kw):
    """The registry-dispatched stream executor must match the one-hot
    kernel bit for bit on dyadic values."""
    M = _dyadic(M)
    x = jnp.asarray(_dyadic_x(M.m, nrhs, seed=nrhs))
    try:
        op_oh = ops.SpmvOperator.from_plan(M, _plan(path, "onehot",
                                                    **plan_kw))
    except ValueError:
        pytest.skip(f"{path} infeasible for this matrix")
    op_st = ops.SpmvOperator.from_plan(M, _plan(path, "stream", **plan_kw))
    y_oh = np.asarray(op_oh(x))
    y_st = np.asarray(op_st(x))
    np.testing.assert_array_equal(y_st, y_oh)
    # and both must be the true product (dyadic values: exact in f64)
    if plan_kw.get("value_dtype", "float32") == "float32":
        A = csrc.to_dense(M).astype(np.float64)
        y_ref = (A @ np.asarray(x, dtype=np.float64)).astype(np.float32)
        np.testing.assert_array_equal(y_oh, y_ref)


@pytest.mark.parametrize("nrhs", [1, 3, 8])
@pytest.mark.parametrize("path", ["kernel", "flat", "nnzsplit"])
@pytest.mark.parametrize("name", [n for n, _ in MATRICES])
def test_stream_bitwise_equals_onehot(name, path, nrhs):
    M = _BY_NAME[name]()
    if path == "nnzsplit" and name != "powerlaw":
        pytest.skip("nnzsplit exercised on the unstructured class")
    _assert_variants_identical(M, path, nrhs)


@pytest.mark.parametrize("path", ["kernel", "flat"])
def test_stream_int16_indices(path):
    _assert_variants_identical(_BY_NAME["fem_band"](), path, 3,
                               index_dtype="int16")


@pytest.mark.parametrize("path", ["kernel", "flat"])
def test_stream_bf16_values(path):
    # bf16 value streams: both variants read the same rounded values and
    # form exact f32 products, so they still agree bitwise
    _assert_variants_identical(_BY_NAME["fem_band_sym"](), path, 3,
                               value_dtype="bfloat16")


# ---------------------------------------------------------------------------
# Predict-then-measure
# ---------------------------------------------------------------------------

def _bandwidth_measure(calls):
    """Deterministic stand-in for the clock, independent of the analytic
    cost model: time = actually-streamed pack bytes / bandwidth, with the
    one-hot variants charged the compute-bound factor their (S, W) mask
    contractions cost in practice."""
    def measure(op, x):
        calls.append(op.plan.key())
        t = op.bytes_per_call / 100e9
        if (op.plan.variant == "onehot"
                and op.plan.path in ("kernel", "flat", "nnzsplit")):
            t *= 50.0
        return t
    return measure


@pytest.mark.parametrize("name", ["fem_band_w16", "powerlaw"])
def test_predict_then_measure_keeps_winner(name):
    M = (csrc.fem_band(512, 16, seed=2) if name == "fem_band_w16"
         else csrc.powerlaw_laplacian(512, seed=7))
    full_calls, pruned_calls = [], []
    res_full = tuner.tune(M, predict=False,
                          measure=_bandwidth_measure(full_calls))
    res_pruned = tuner.tune(M, predict=True,
                            measure=_bandwidth_measure(pruned_calls))
    # >= 2x fewer measurements...
    assert 2 * len(pruned_calls) <= len(full_calls), (
        len(pruned_calls), len(full_calls))
    # ...and the full-measurement winner survived the pruning
    assert res_pruned.plan == res_full.plan, (
        res_pruned.plan.key(), res_full.plan.key())
    # provenance: every ranked candidate was priced; the winner's roofline
    # share is recorded only on a device kind with a peak row
    assert set(res_pruned.timings_s) <= set(res_pruned.predictions_s)
    assert len(res_pruned.predictions_s) == len(full_calls)
    kind = jax.devices()[0].device_kind
    if kind in cost_model.DEVICE_PEAKS:
        assert res_pruned.roofline_fraction > 0
    else:
        assert res_pruned.roofline_fraction is None


def test_predicted_and_measured_land_in_cache():
    M = csrc.fem_band(256, 8, seed=1)
    cache = tuner.PlanCache()
    res = tuner.tune(M, cache=cache, measure=_bandwidth_measure([]))
    e = cache.entries[res.fingerprint]
    assert "predicted_us" in e and "predicted_ms" in e
    assert "measured_ms" in e and "roofline_fraction" in e
    # the share is None off the peak table (the CPU host)
    if jax.devices()[0].device_kind not in cost_model.DEVICE_PEAKS:
        assert e["roofline_fraction"] is None
    # the winner's measured time is the recorded one
    assert e["measured_ms"] == pytest.approx(
        res.timings_s[res.plan.key()] * 1e3, rel=0.05)


def test_roofline_fraction_only_on_peak_table_devices():
    """The share is the least time the named device's peaks allow over
    the measured time; a device kind without a peak row gets None."""
    est = cost_model.CostEstimate(bytes=819e6, flops=197e9, memory_s=0.0,
                                  compute_s=0.0, predicted_s=0.0)
    assert cost_model.roofline_fraction(est, 2e-3, "TPU v5 lite") == \
        pytest.approx(0.5)
    assert cost_model.roofline_fraction(est, 2e-3, "cpu") is None
    for row in cost_model.DEVICE_PEAKS.values():
        assert row["source"]
