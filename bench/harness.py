"""The data-driven benchmark harness: one cell per process.

A cell is a configuration (``bench/configs/<config>.json``, with its plain
reference ``<config>_ref.py`` beside it) under a traffic mix
(``bench/traffic/<mix>.json``), which names its driver
(``bench/drivers/<driver>.py``).  Per-layer metrics are readers in
``bench/metrics/<metric>.py``.  Everything is found by the names in
``BENCHMARK.json``, so a new cell, configuration, mix or metric is new files
and entries only.

A run: set-up (data from the seed, the program built and driven through its
first calls, which compiles or loads every shape the window uses), then the
measured window of ``--seconds``, then the correctness check against the
plain reference, once the program's state is freed.  ``--trace 1`` runs
the same window and profiles a steady part of it; its result line carries
the per-layer metrics instead of the end-to-end ones.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time
import traceback
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
CACHE = os.path.join(ROOT, ".bench_cache")


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def process_start(default: float) -> float:
    """Epoch time at which this process started (Linux ``/proc``), so that
    ``setup_s`` counts the interpreter's and JAX's start-up too."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - (uptime - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return default


# ---------------------------------------------------------------------------
# What BENCHMARK.json says about a cell
# ---------------------------------------------------------------------------


@dataclass
class Cell:
    name: str
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    chips: int
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    seed: int = 0
    seconds: float = 10.0

    def ref_module(self):
        """The configuration's plain reference (``<config>_ref.py``)."""
        return load_file(os.path.join(BENCH, "configs",
                                      f"{self.config_name}_ref.py"),
                         f"bench_ref_{self.config_name}")


def load_file(path: str, module_name: str):
    spec = importlib.util.spec_from_file_location(module_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(workload: str, root: str = ROOT) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    return Cell(name=workload, config_name=w["config"], config=config,
                traffic_name=w["traffic"], traffic=traffic,
                chips=int(w["chips"]), end_to_end=e2e, per_layer=per_layer)


# ---------------------------------------------------------------------------
# Device, cache, compile guard, profiler
# ---------------------------------------------------------------------------


def setup_environment(root: str = ROOT) -> None:
    """Compile cache and libtpu logs inside the checkout, at fixed paths."""
    cache = os.path.join(root, ".bench_cache")
    os.makedirs(os.path.join(cache, "jax"), exist_ok=True)
    os.environ["TPU_LOG_DIR"] = os.path.join(cache, "tpu_logs")
    import jax
    jax.config.update("jax_compilation_cache_dir", os.path.join(cache, "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def chip_devices(chips: int):
    import jax
    devs = jax.devices()
    if not devs or devs[0].platform == "cpu":
        raise NoChip(f"no accelerator: JAX reports {devs}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX reports {len(devs)}")
    return devs[:chips]


class CompileGuard:
    """Counts what compiles while it is on: misses of the program's runner
    and scorer caches (``RUNNER_STATS``, ``SERVE_STATS``) and every XLA
    executable JAX builds or loads (its backend-compile event)."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    _listening = False
    _count = 0

    def __init__(self):
        import jax
        if not CompileGuard._listening:
            def on_event(event, secs, **kw):
                if event == CompileGuard.EVENT:
                    CompileGuard._count += 1
            jax.monitoring.register_event_duration_secs_listener(on_event)
            CompileGuard._listening = True
        self.xla = 0
        self.runner = 0
        self.scorer = 0

    @staticmethod
    def _misses():
        from repro.serve.engine import SERVE_STATS
        from repro.train.fl_driver import RUNNER_STATS
        return RUNNER_STATS["misses"], SERVE_STATS["misses"]

    def __enter__(self):
        self._x0 = CompileGuard._count
        self._r0, self._s0 = self._misses()
        return self

    def __exit__(self, *exc):
        r1, s1 = self._misses()
        self.xla = CompileGuard._count - self._x0
        self.runner, self.scorer = r1 - self._r0, s1 - self._s0
        return False

    @property
    def compiles(self) -> int:
        return max(self.xla, self.runner + self.scorer)


class Profiler:
    """Profiles one steady segment of the window.  The driver calls
    ``begin`` and ``end``; while it is on, the program's host spans are
    recorded and also enter the trace as annotations."""

    def __init__(self, logdir: str):
        self.logdir = logdir
        self.state = "idle"
        self.spans = []

    def begin(self):
        import jax
        from repro.obs import trace as obs_trace
        if self.state != "idle":
            return
        shutil.rmtree(self.logdir, ignore_errors=True)
        os.makedirs(self.logdir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # host spans and runtime events only
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = True        # op_name metadata for the scopes
        jax.profiler.start_trace(self.logdir, profiler_options=opts)
        obs_trace.TRACER.clear()
        obs_trace.TRACER.enable()
        obs_trace.TRACER.profiling = True
        self.state = "on"
        self.segment = jax.profiler.TraceAnnotation("bench.segment")
        self.segment.__enter__()

    def end(self):
        import jax
        from repro.obs import trace as obs_trace
        if self.state != "on":
            return
        self.segment.__exit__(None, None, None)
        obs_trace.TRACER.profiling = False
        obs_trace.TRACER.disable()
        self.spans = list(obs_trace.TRACER.spans)
        obs_trace.TRACER.clear()
        jax.profiler.stop_trace()
        self.state = "done"

    @property
    def on(self) -> bool:
        return self.state == "on"


@dataclass
class Window:
    """What a driver's window reports: requests attempted and failed, the
    end-to-end values, and counts over the traced segment for the
    per-layer readers."""

    attempted: int
    failed: int
    e2e: Dict[str, float]
    traced: Dict[str, Any] = field(default_factory=dict)


@dataclass
class Reading:
    """Everything a per-layer metric reader may use."""

    cell: Cell
    trace: Any                      # bench.trace.xplane.Reduction or None
    spans: list                     # program host spans in the segment
    traced: Dict[str, Any]
    e2e: Dict[str, float]
    peaks: Any                      # the chip's row; None off the chip
    chips: int


def read_metric(name: str, reading: Reading) -> Optional[float]:
    mod = load_file(os.path.join(BENCH, "metrics", f"{name}.py"),
                    "bench_metric_" + name.replace(".", "_"))
    return mod.read(reading)


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        try:
            peaks.append(int(d.memory_stats()["peak_bytes_in_use"]))
        except (TypeError, KeyError, RuntimeError):
            pass
    return max(peaks) if peaks else 0


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_chip: bool = True, root: str = ROOT,
        cell_hook=None, driver_hook=None) -> Dict[str, Any]:
    """Set up, measure, check; returns the result object.  ``require_chip``
    False skips the look for an accelerator, and ``cell_hook`` and
    ``driver_hook`` may shrink the cell or break the driver: the
    harness's own tests on the CPU use them."""
    cell = resolve(workload, root)
    cell.seed, cell.seconds = int(seed), float(seconds)
    if cell_hook is not None:
        cell_hook(cell)
    setup_environment(root)
    import jax
    devices = chip_devices(cell.chips) if require_chip else jax.devices()[:1]
    from bench import peaks as peaks_lib
    drv_mod = importlib.import_module(f"bench.drivers.{cell.traffic['driver']}")
    drv = drv_mod.Driver(cell, devices)
    if driver_hook is not None:
        driver_hook(drv)
    drv.setup()
    setup_s = time.time() - t_start

    prof = Profiler(os.path.join(root, ".bench_cache", "trace",
                                 cell.name)) if trace else None
    with CompileGuard() as guard:
        win = drv.window(cell.seconds, prof)
    if prof is not None:
        prof.end()
    failed = int(win.failed) + guard.compiles
    e2e = dict(win.e2e)
    e2e["setup_s"] = setup_s
    kind = devices[0].device_kind
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory_peak(devices)}

    breakdown = None
    if trace:
        from bench.trace import xplane
        red = xplane.reduce_dir(prof.logdir, n_devices=len(devices))
        reading = Reading(cell=cell, trace=red, spans=prof.spans,
                          traced=win.traced, e2e=e2e,
                          peaks=(peaks_lib.peaks_for(kind) if require_chip
                                 else peaks_lib.PEAKS_BY_KIND.get(kind)),
                          chips=len(devices))
        metrics = {}
        for m in cell.per_layer:
            val = read_metric(m["name"], reading)
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        breakdown = {"device_ops": red.top_ops(10),
                     "idle_gaps": red.top_gaps(10)}
        shutil.rmtree(prof.logdir, ignore_errors=True)
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in e2e}

    drv.free()
    checks = drv.check()
    correct = bool(checks) and all(
        math.isfinite(v) and v <= lim for _, v, lim in checks)
    out = {"correct": correct, "attempted": int(win.attempted),
           "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compiles_in_window"] = guard.compiles
    out["check"] = {name: {"value": v, "limit": lim} for name, v, lim in checks}
    return out


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = process_start(t_start or time.time())
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace),
                  t_start=t_start)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    except Exception:
        traceback.print_exc()
        return 1
    for name, c in out["check"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
