"""The four-chip HPCG cell: its generator against the reference, its parts
found by name, and a whole run of its runner on four CPU devices."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import harness
from conftest import BENCH
from test_generators import full_of

WORKLOAD = "hpcg27x4.cg"
# a 12x12x16 grid: 2,304 rows, 576 a device, band 157 (halo fits)
SMALL = {"nx": 12, "ny": 12, "nz": 16}


def cell():
    return harness.find_cell(harness.load_spec(), WORKLOAD)


@pytest.mark.parametrize("dims", [(6, 5, 12), (4, 4, 8)])
def test_generator_matches_reference(dims):
    c = cell()
    cfg = dict(c.config, nx=dims[0], ny=dims[1], nz=dims[2])
    n, ia, ja, ad, al, au = c.config_mod.arrays(cfg)
    A = full_of(n, ia, ja, ad, al, au)
    assert abs(A - c.config_mod.reference(cfg)).max() == 0.0
    assert A.nnz == np.prod([3 * m - 2 for m in dims])


def test_configuration_states_the_deployment():
    cfg = cell().config
    n = cfg["nx"] * cfg["ny"] * cfg["nz"]
    assert n == 4_499_456 and n % 4 == 0
    assert [cfg["nx"], cfg["ny"], cfg["nz"] // 4] == cfg["local_grid"]
    assert cfg["process_grid"] == [1, 1, 4]
    assert np.prod([3 * m - 2 for m in (104, 104, 416)]) == 119_740_600
    spec = harness.load_spec()
    (conf,) = [c for c in spec["configs"] if c["name"] == "hpcg27x4"]
    assert conf["reduced"] == sorted(cfg["reduced"])


def test_cell_parts_are_its_own_files():
    c = cell()
    assert c.chips == 4 and c.config["name"] == "hpcg27x4"
    assert c.traffic["runner"] == "mesh_solve"
    assert c.runner.__file__ == str(BENCH / "runners" / "mesh_solve.py")
    assert c.config_mod.__file__ == str(BENCH / "configs" / "hpcg27x4.py")
    assert c.limits["residual_f64"] == 3e-4
    assert [m["name"] for m in c.end_to_end] == ["setup_s", "solve_s"]
    assert [m["name"] for m in c.per_layer] == [
        "compile_s", "spmv_roofline.cg_mesh", "device_idle.cg_mesh",
        "solve_host_s.cg_mesh", "place_mb.cg_mesh"]
    for m in c.per_layer:
        assert (BENCH / "metrics" / f"{m['name']}.py").exists()


def test_shard_bytes_of_the_cell():
    from cost import csrc_spmv_bytes
    from cost_mesh import mesh_spmv_shard_bytes
    n, k, band = 4_499_456, (119_740_600 - 4_499_456) // 2, 10_921
    got = mesh_spmv_shard_bytes(n, k, 4, band)
    assert got == csrc_spmv_bytes(n // 4, k // 4) + 8 * band
    assert 190.9e6 < got < 191.0e6
    assert mesh_spmv_shard_bytes(n, k, 1, band) == csrc_spmv_bytes(n, k)


RUN = """
    import json, sys, time
    sys.path[:0] = {paths!r}
    import harness, control
    seen = {{}}
    real = harness.read_metrics

    def keep(entries, ctx, bench=harness.BENCH):
        seen["ctx"] = ctx
        return real(entries, ctx, bench)
    harness.read_metrics = keep
    cell = harness.find_cell(harness.load_spec(), {workload!r})
    cell.config = dict(cell.config, **{small!r})
    res = harness.run_cell(cell, 2 ** 33 + 5, 0.5, False,
                           time.perf_counter(), require_chip=False,
                           cache_root=harness.Path({tmp!r}))
    per_layer = real(cell.per_layer, seen["ctx"])
    ctl = harness.run_cell(cell, 2 ** 33 + 6, 0.5, False,
                           time.perf_counter(), require_chip=False,
                           entries=control.entries(cell),
                           cache_root=harness.Path({tmp!r}))
    print(json.dumps({{"res": res, "per_layer": per_layer, "ctl": ctl}}))
"""


def test_mesh_runner_completes_a_window_on_four_devices(tmp_path):
    code = textwrap.dedent(RUN).format(
        paths=[str(BENCH), str(BENCH.parent / "src")], workload=WORKLOAD,
        small=SMALL, tmp=str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    res, per_layer = got["res"], got["per_layer"]
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"setup_s", "solve_s"}
    assert res["device"]["count"] == 4
    assert "[setup]" in out.stdout and ":mesh4" in out.stdout
    # nothing is put on the mesh inside the window
    assert per_layer["place_mb.cg_mesh"]["value"] == 0.0
    assert per_layer["solve_host_s.cg_mesh"]["value"] > 0
    # the control runs the same cell, and with the bf16 reference fails it
    assert got["ctl"]["attempted"] >= 1 and not got["ctl"]["correct"]
