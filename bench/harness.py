"""One run of one benchmark cell, driven by data.

Everything that belongs to one configuration, traffic mix or metric is
found by its name from ``BENCHMARK.json``:

* ``bench/configs/<config>.json``: the deployment; its ``driver`` names
  the module ``bench/drivers/<driver>.py`` that sets it up and runs it;
* ``bench/traffic/<traffic>.json``: the traffic mix, read by
  :mod:`bench.traffic`;
* ``bench/limits/<cell>.json``: the limit of each number that decides
  ``correct``;
* ``bench/metrics/<metric>.py``: a reader ``read(run)`` that returns the
  metric's value from what the run recorded, or None where it finds
  nothing to read (the metric is then left out of the result line).

The run: set-up (device check, the compile cache, the driver's data,
plans and compiles, warm-up), the measured window (under the profiler
with ``--trace 1``), the device's peak memory, then the comparison with
the plain reference, and one JSON result line last on standard output.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import pathlib
import shutil
import sys
import time
from typing import Any, Dict, Optional, Tuple

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
# fixed paths inside the checkout: the compile cache must be found again
# by the next run of the same checkout, so its path never varies
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_out" / "trace"
REQUIRED_PLATFORM = "tpu"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class NoChip(SystemExit):
    """JAX found no accelerator, or fewer chips than the cell needs."""


def load_json(path: pathlib.Path) -> Any:
    with open(path) as f:
        return json.load(f)


class CompileCounter:
    """Counts the persistent compile cache's requests and hits through
    ``jax.monitoring``, and logs each backend compile (or cache load)
    with its seconds."""

    def __init__(self):
        self.requests = 0
        self.hits = 0
        self.compiles = []  # (function name, seconds)

    def install(self) -> None:
        from jax import monitoring

        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            name = str(kw.get("fun_name", "?"))
            self.compiles.append((name, secs))
            log(f"[compile] {name}: {secs:.3f}s (cache requests "
                f"{self.requests}, hits {self.hits}, misses "
                f"{self.misses})")

    @property
    def misses(self) -> int:
        return self.requests - self.hits


def use_cache() -> None:
    """The persistent compile cache at a fixed path in the checkout, set
    before anything compiles (JAX decides once per process, at its first
    compile, whether the cache is on).  Every program is written to it,
    however short its compile, so a second run compiles nothing."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # no size limit, so no eviction: with eviction on, JAX reads an
    # access-time file beside every entry, and one entry without it (as
    # a cache written with eviction off has) fails every write after it
    jax.config.update("jax_compilation_cache_max_size", -1)


def require_chips(n: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != REQUIRED_PLATFORM:
        raise NoChip(f"bench: JAX found no {REQUIRED_PLATFORM} (platform "
                     f"{devs[0].platform!r}); nothing was run")
    if len(devs) < n:
        raise NoChip(f"bench: the cell needs {n} chips, JAX found "
                     f"{len(devs)}")
    return devs


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, cell: str, trace: bool) -> list:
    """The metric entries a run of ``cell`` reports: its end-to-end
    metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


class Run:
    """What one run of one cell records; metric readers read it."""

    def __init__(self, cell: str, seed: int, seconds: float, trace: bool,
                 bench: Optional[dict] = None,
                 overrides: Optional[dict] = None):
        self.t0 = time.perf_counter()
        self.bench = bench if bench is not None else \
            load_json(ROOT / "BENCHMARK.json")
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if cell not in cells:
            raise SystemExit(f"bench: no workload {cell!r} in "
                             f"BENCHMARK.json; known: {sorted(cells)}")
        self.cell = cells[cell]
        self.name = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        overrides = overrides or {}
        self.config = {**load_json(BENCH / "configs" /
                                   f"{self.cell['config']}.json"),
                       **overrides.get("config", {})}
        self.traffic = {**load_json(BENCH / "traffic" /
                                    f"{self.cell['traffic']}.json"),
                        **overrides.get("traffic", {})}
        self.limits = load_json(BENCH / "limits" / f"{cell}.json")
        self.compiles = CompileCounter()
        self.values: Dict[str, Any] = {}  # what the driver recorded
        self.setup_s: Optional[float] = None
        self.summary = None  # bench.trace.TraceSummary with --trace 1
        self.window_s: Optional[float] = None
        self.device: Dict[str, Any] = {}

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def end_setup(self) -> None:
        self.setup_s = self.now()
        self.values["setup_cache"] = {"requests": self.compiles.requests,
                                      "hits": self.compiles.hits,
                                      "misses": self.compiles.misses}
        log(f"[setup] {self.setup_s:.3f}s; compile cache requests "
            f"{self.compiles.requests}, hits {self.compiles.hits}, misses "
            f"{self.compiles.misses}")


def _memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def _traced(run: Run, window):
    import jax

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    t = time.perf_counter()
    try:
        record = window()
    finally:
        run.window_s = time.perf_counter() - t
        jax.profiler.stop_trace()
    from bench import trace as _trace

    run.summary = _trace.reduce_dir(str(TRACE_DIR))
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return record


def judge(numbers: Dict[str, float],
          limits: Dict[str, float]) -> Tuple[bool, dict]:
    """(correct, each number beside its limit): correct where no number
    is over its limit.  A number with no limit, or a limit with no
    number, is a fault of the benchmark.  Each check is logged."""
    if set(numbers) != set(limits):
        raise RuntimeError(f"numbers {sorted(numbers)} do not match the "
                           f"limits {sorted(limits)}")
    checks = {k: {"value": float(numbers[k]), "limit": float(limits[k])}
              for k in sorted(numbers)}
    for k, c in checks.items():
        log(f"[check] {k} = {c['value']:.6e}  limit {c['limit']:.6e}  "
            f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}")
    return all(c["value"] <= c["limit"] for c in checks.values()), checks


def execute(run: Run, require_chip: bool = True) -> dict:
    """Set up, measure and check one run; returns the result object."""
    import jax

    run.compiles.install()
    use_cache()
    devs = (require_chips(run.cell["chips"]) if require_chip
            else jax.devices())
    devs = devs[:run.cell["chips"]]
    d = devs[0]
    run.device = {"platform": d.platform, "kind": d.device_kind,
                  "count": len(jax.devices())}
    log(f"[device] {run.device}; jax {jax.__version__}; cell {run.name} "
        f"seed {run.seed}; compile cache {CACHE_DIR}")
    driver = importlib.import_module(
        f"bench.drivers.{run.config['driver']}")
    state = driver.setup(run, devs)
    run.end_setup()
    window = lambda: driver.window(run, state)  # noqa: E731
    before = len(run.compiles.compiles)
    record = _traced(run, window) if run.trace else window()
    log(f"[window] compiles in the window: "
        f"{len(run.compiles.compiles) - before}")
    if run.trace:
        log(f"[trace] {run.summary.chips} chip(s), busy "
            f"{run.summary.busy_s:.6f}s of {run.window_s:.6f}s")
        for name, secs in run.summary.top_ops(30):
            log(f"[trace] op {name}: {secs:.6f}s in "
                f"{run.summary.kernel_calls[name]} events")
        for name in run.summary.ops_named("custom-call") + \
                run.summary.ops_named("kernel"):
            log(f"[trace] kernel {run.summary.kernel_text[name][:160]}: "
                f"{run.summary.kernel_s[name]:.6f}s in "
                f"{run.summary.kernel_calls[name]} events")
    run.device["memory_peak_bytes"] = _memory_peak(devs)
    if run.trace:
        run.device["busy_s"] = run.summary.busy_s
        run.device["window_s"] = run.window_s
    numbers = driver.check(run, state, record)
    metrics = {}
    for m in cell_metrics(run.bench, run.name, run.trace):
        value = load_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    # judged last, so that the check lines end standard error
    correct, checks = judge(numbers, run.limits)
    result = {"correct": correct, "attempted": record["attempted"],
              "failed": record["failed"], "metrics": metrics,
              "device": run.device}
    if run.trace:
        result["breakdown"] = {"device_ops": run.summary.top_ops(10),
                               "idle_gaps": run.summary.idle_gaps[:10]}
    result["checks"] = checks
    return result
