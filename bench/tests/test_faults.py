"""A run with the measured path broken underneath comes out not correct:
one run for each fault the cell can have, beside a sound run."""
import dataclasses

import numpy as np
import pytest

from repro.assembly import scatter
from repro.core import solvers


@pytest.mark.parametrize("workload", ["hpcg27.cg", "fem_tet.step"])
def test_sound_run_is_correct(run_small, workload):
    res = run_small(workload)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


def _wrap_solve(monkeypatch, change):
    real = solvers.cg_solve

    def broken(*a, **kw):
        res, op = real(*a, **kw)
        return res._replace(x=change(res.x)), op
    monkeypatch.setattr(solvers, "cg_solve", broken)


SOLVE_FAULTS = {
    # the solve hands back its starting state
    "state_unchanged": lambda x: x * 0,
    # one entry of the answer altered where it is produced
    "answer_altered": lambda x: x.at[0].add(1.0),
}


@pytest.mark.parametrize("fault", sorted(SOLVE_FAULTS))
@pytest.mark.parametrize("workload", ["hpcg27.cg", "fem_tet.step"])
def test_broken_solve_is_caught(run_small, monkeypatch, workload, fault):
    _wrap_solve(monkeypatch, SOLVE_FAULTS[fault])
    assert not run_small(workload)["correct"]


def test_assembly_returning_stale_values_is_caught(run_small, monkeypatch):
    real, first = scatter.assemble, []

    def stale(*a, **kw):
        M = real(*a, **kw)
        first[:] = first or [M]
        return first[0]
    monkeypatch.setattr(scatter, "assemble", stale)
    assert not run_small("fem_tet.step")["correct"]


def test_assembly_answer_altered_is_caught(run_small, monkeypatch):
    real = scatter.assemble

    def altered(*a, **kw):
        M = real(*a, **kw)
        return dataclasses.replace(M, al=M.al.at[0].add(1.0))
    monkeypatch.setattr(scatter, "assemble", altered)
    assert not run_small("fem_tet.step")["correct"]


def test_assembly_leaving_out_half_the_elements_is_caught(run_small,
                                                          monkeypatch):
    real = scatter.assemble

    def half(sched, ke, **kw):
        keep = (np.arange(ke.shape[0]) < ke.shape[0] // 2)[:, None, None]
        return real(sched, ke * keep, **kw)
    monkeypatch.setattr(scatter, "assemble", half)
    assert not run_small("fem_tet.step")["correct"]
