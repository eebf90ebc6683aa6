"""The benchmark harness: finds a cell's parts by name, runs set-up, the
measured window, the optional trace and the correctness check, and builds
the result line.

Everything that belongs to one configuration, traffic mix or metric lives
in a file of its own, found by the name ``BENCHMARK.json`` gives it:

  configs/<config>.json, .py   sizes, and the generator with its reference
  traffic/<traffic>.json       the mix: which runner runs it, its numbers
  runners/<runner>.py          the general runner of one kind of traffic
  limits/<workload>.json       the limit of every number the check compares
  metrics/<metric>.py          a reader: ``read(ctx) -> float | None``
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path
from types import ModuleType, SimpleNamespace
from typing import Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = BENCH / ".cache"
# the traced run traces at most this many seconds of its window
TRACE_WINDOW_S = 10.0


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_module(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with every part it names."""
    name: str
    chips: int
    config: dict            # the configuration file's contents
    config_mod: ModuleType  # its generator and reference
    traffic: dict
    limits: dict
    runner: ModuleType
    end_to_end: List[dict]  # the metrics this cell reports with --trace 0
    per_layer: List[dict]   # ... and with --trace 1
    bench: Path


def load_spec(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def find_cell(spec: dict, workload: str, bench: Path = BENCH) -> Cell:
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    conf_file = bench.parent / conf["file"]
    with open(conf_file) as f:
        config = json.load(f)
    with open(bench / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    with open(bench / "limits" / f"{workload}.json") as f:
        limits = json.load(f)
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        config_mod=load_module(conf_file.with_suffix(".py"),
                               f"bench_config_{w['config']}"),
        traffic=traffic, limits=limits,
        runner=load_module(bench / "runners" / f"{traffic['runner']}.py",
                           f"bench_runner_{traffic['runner']}"),
        end_to_end=e2e, per_layer=per_layer, bench=bench)


def read_metrics(entries: List[dict], ctx, bench: Path = BENCH) -> Dict:
    """Each metric's reader, found by name; a reader that finds nothing
    to read returns None and the metric is left out."""
    out = {}
    for m in entries:
        reader = load_module(bench / "metrics" / f"{m['name']}.py",
                             f"bench_metric_{m['name'].replace('.', '_')}")
        v = reader.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


class CompileLog:
    """Backend compiles and persistent-cache hits, from JAX's own
    monitoring events.  JAX times a cache hit as a compile too, so the
    compiles that really ran are ``events - hits``."""

    def __init__(self):
        import jax
        self.seconds, self.events, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.events += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def take(self) -> dict:
        out = {"compile_s": self.seconds, "programs": self.events,
               "cache_hits": self.hits,
               "compiled": self.events - self.hits}
        self.seconds, self.events, self.hits = 0.0, 0, 0
        return out


def check_chips(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform})")
    if len(devs) < chips:
        raise NoChip(f"{chips} chips needed, JAX found {len(devs)}")


def enable_compile_cache(cache_dir: Path):
    """JAX's persistent compilation cache at a fixed path in the
    checkout, for every program however fast it compiles: the program
    retraces some of its calls, and those then load from the cache."""
    import jax
    cache_dir.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def start_trace(log_dir: Path):
    """The profiler on, without the Python tracer (it would slow every
    host call the window makes) and without HLO protos."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)


def say(tag: str, **fields):
    print(f"[{tag}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, require_chip: bool = True,
             entries: Optional[dict] = None,
             cache_root: Path = CACHE) -> dict:
    """Run one cell; returns the result line as a dict.  ``entries``
    replaces program entry points (the control), ``require_chip=False``
    and ``cache_root`` let the tests drive a run on the CPU."""
    import jax
    if require_chip:
        check_chips(cell.chips)
    enable_compile_cache(cache_root / "jax")
    log = CompileLog()
    dev = jax.devices()[0]
    runner = cell.runner.Runner(cell, cache_root / "plans" / cell.name,
                             entries=entries)
    runner.setup_static()
    runner.setup_seed(seed)
    ctx = SimpleNamespace(device_kind=dev.device_kind)
    ctx.setup_s = time.perf_counter() - t_start
    ctx.setup_compile = log.take()
    say("setup", setup_s=ctx.setup_s, **ctx.setup_compile,
        **runner.describe())

    window = min(seconds, TRACE_WINDOW_S) if trace else seconds
    trace_dir = cache_root / "trace" / cell.name
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        start_trace(trace_dir / "window")
    t_w = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        runner.window(window)
    ctx.window_wall_s = time.perf_counter() - t_w
    if trace:
        jax.profiler.stop_trace()
    ctx.window_compile = log.take()
    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))
    runner.fill(ctx)
    say("window", window_s=ctx.window_wall_s, **ctx.window_compile,
        **runner.window_summary())

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    breakdown = None
    if trace:
        from tracereduce import device_busy_s, reduce_window
        red = reduce_window(trace_dir / "window")
        device["busy_s"], device["window_s"] = red.busy_s, red.window_s
        ctx.busy_s, ctx.traced_window_s = red.busy_s, red.window_s
        breakdown = {"device_ops": red.top_ops, "idle_gaps": red.idle_gaps}
        ctx.bursts = {}
        for name, (fn, reps, nbytes) in runner.bursts().items():
            d = trace_dir / f"burst_{name}"
            jax.block_until_ready(fn())            # settle, untraced
            start_trace(d)
            with jax.profiler.TraceAnnotation(f"bench.burst.{name}"):
                for _ in range(reps):
                    out = fn()
                jax.block_until_ready(out)
            jax.profiler.stop_trace()
            dev_s = device_busy_s(d)
            ctx.bursts[name] = {"device_s": dev_s / reps, "bytes": nbytes}
            say("burst", name=name, reps=reps, device_s_per_call=dev_s / reps,
                bytes=nbytes)
        shutil.rmtree(trace_dir, ignore_errors=True)

    runner.collect()
    runner.release()
    checks, attempted, failed = runner.check()
    correct = failed == 0 and all(v <= lim for _, v, lim in checks)
    metrics = read_metrics(cell.per_layer if trace else cell.end_to_end,
                           ctx, cell.bench)
    for name, v, lim in checks:
        print(f"check {name} {v!r} limit {lim!r}", file=sys.stderr)
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, v, lim in checks}
    return result


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = find_cell(load_spec(), args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          t_start)
    except NoChip as e:
        print(f"bench: {e}; nothing run", file=sys.stderr)
        return 2
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0
