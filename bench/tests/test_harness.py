"""The harness finds a cell's parts by name, and refuses to run without
a TPU."""
import json
import os
import shutil
import subprocess
import sys
import time
from types import SimpleNamespace

import harness
from conftest import BENCH

ROOT = BENCH.parent


def copy_bench(dest):
    shutil.copytree(BENCH, dest / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__",
                                                  "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")


def test_new_config_traffic_and_metric_need_only_new_files(tmp_path):
    copy_bench(tmp_path)
    b = tmp_path / "bench"
    cfg = json.loads((b / "configs" / "hpcg27.json").read_text())
    cfg.update(name="tiny27", nx=6, ny=5, nz=4)
    (b / "configs" / "tiny27.json").write_text(json.dumps(cfg))
    shutil.copy(b / "configs" / "hpcg27.py", b / "configs" / "tiny27.py")
    traffic = json.loads((b / "traffic" / "cg.json").read_text())
    traffic.update(tol=1e-4)
    (b / "traffic" / "cg_loose.json").write_text(json.dumps(traffic))
    (b / "limits" / "tiny27.cg_loose.json").write_text(
        json.dumps({"residual_f64": 1e-3}))
    (b / "metrics" / "solves.tiny.py").write_text(
        "def read(ctx):\n    return float(len(ctx.cg_iters))\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny27", "source": "test",
                            "file": "bench/configs/tiny27.json",
                            "reduced": ["nx"], "why": "test"})
    spec["workloads"].append({"name": "tiny27.cg_loose", "config": "tiny27",
                              "traffic": "cg_loose", "chips": 1,
                              "why": "test"})
    spec["end_to_end"][1]["workloads"].append("tiny27.cg_loose")
    spec["per_layer"].append({"name": "solves.tiny", "unit": "solves",
                              "better": "higher",
                              "source": "program_counter",
                              "layer": "solver", "moves": "solve_s",
                              "workloads": ["tiny27.cg_loose"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.find_cell(harness.load_spec(tmp_path), "tiny27.cg_loose",
                             bench=b)
    assert cell.config["nx"] == 6 and cell.traffic["tol"] == 1e-4
    assert cell.limits == {"residual_f64": 1e-3}
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "solve_s"]
    assert [m["name"] for m in cell.per_layer] == ["compile_s",
                                                   "solves.tiny"]
    got = harness.read_metrics(cell.per_layer,
                               SimpleNamespace(cg_iters=[3, 4],
                                               setup_compile={
                                                   "compile_s": 1.5}),
                               bench=b)
    assert got == {"compile_s": {"value": 1.5, "unit": "s"},
                   "solves.tiny": {"value": 2.0, "unit": "solves"}}
    res = harness.run_cell(cell, 11, 0.5, False, time.perf_counter(),
                           require_chip=False, cache_root=tmp_path / "c")
    assert res["correct"] and set(res["metrics"]) == {"setup_s", "solve_s"}
    assert list(res)[-1] == "checks"


def test_metrics_left_out_when_nothing_to_read():
    spec = harness.load_spec()
    cell = harness.find_cell(spec, "hpcg27.cg")
    got = harness.read_metrics(cell.per_layer,
                               SimpleNamespace(setup_compile={
                                   "compile_s": 2.0}))
    assert got == {"compile_s": {"value": 2.0, "unit": "s"}}


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_run_exits_nonzero_without_tpu():
    p = _run(ROOT, "--workload", "hpcg27.cg", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    copy_bench(tmp_path)
    p = _run(tmp_path, "--workload", "fem_tet.step", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
