"""SpMV serving: overflow ordering and coalesced-batch metadata."""
import numpy as np


def _spmv_engine(max_batch):
    from repro.core import csrc, tuner
    from repro.serve.engine import SpmvServingEngine
    eng = SpmvServingEngine(cache=tuner.PlanCache(), max_batch=max_batch)
    M = csrc.poisson2d(5)
    eng.register("m", M)
    return eng, M


def test_spmv_step_overflow_drains_fifo_across_ticks():
    """Requests beyond max_batch stay queued in submission order and are
    answered on the following ticks, oldest first."""
    eng, M = _spmv_engine(max_batch=3)
    rng = np.random.default_rng(0)
    xs = [rng.standard_normal(M.m).astype(np.float32) for _ in range(8)]
    uids = [eng.submit("m", x) for x in xs]
    out1 = eng.step()
    assert sorted(out1) == uids[:3]           # first tick: oldest three
    assert [r.uid for r in eng.queue] == uids[3:]
    out2 = eng.step()
    assert sorted(out2) == uids[3:6]
    out3 = eng.step()
    assert sorted(out3) == uids[6:]
    assert not eng.queue
    from repro.core import csrc as C
    A = np.asarray(C.to_dense(M), np.float64)
    for out in (out1, out2, out3):
        for uid, y in out.items():
            np.testing.assert_allclose(
                np.asarray(y), A @ xs[uids.index(uid)],
                rtol=1e-5, atol=1e-5)


def test_spmv_result_batched_metadata_matches_group_size():
    """SpmvResult.batched reports the coalesced SpMM width: the full
    group on a saturated tick, the remainder afterwards, 1 for a lone
    request."""
    eng, M = _spmv_engine(max_batch=4)
    rng = np.random.default_rng(1)
    uids = [eng.submit("m", rng.standard_normal(M.m).astype(np.float32))
            for _ in range(6)]
    out1 = eng.step()
    assert all(out1[u].batched == 4 for u in uids[:4])
    out2 = eng.step()
    assert all(out2[u].batched == 2 for u in uids[4:])
    lone = eng.submit("m", rng.standard_normal(M.m).astype(np.float32))
    out3 = eng.step()
    assert out3[lone].batched == 1


def test_spmv_run_until_drained_covers_overflow():
    eng, M = _spmv_engine(max_batch=2)
    rng = np.random.default_rng(2)
    uids = [eng.submit("m", rng.standard_normal(M.m).astype(np.float32))
            for _ in range(7)]
    out = eng.run_until_drained()
    assert sorted(out) == sorted(uids)
