"""One run of one benchmark cell: set-up, measured window, check, metrics.

Everything a cell is made of is found by name under ``chipbench/``:

* ``BENCHMARK.json``'s ``workloads`` entry names the configuration and the
  traffic mix;
* ``configs/<config>.json`` is the deployment (data scale, engine
  parameters, guarantees);
* ``traffic/<traffic>.json`` is the mix, whose ``entry`` names the driver
  ``drivers/<entry>.py`` that sets up, runs the window and checks;
* ``metrics/<metric>.py`` reads one metric from the run's records.

A cell that reuses a driver needs only data files; nothing here changes
when a cell, a configuration, a mix or a metric is added.
"""
from __future__ import annotations

import contextlib
import gc
import importlib
import importlib.util
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; cells: "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(name: str) -> dict:
    return load_json(HERE / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return load_json(HERE / "traffic" / f"{name}.json")


def driver(entry: str):
    return importlib.import_module(f"chipbench.drivers.{entry}")


def metric_reader(name: str):
    """``read(rec)`` of ``metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_of(bench: dict, cell: str, traced: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics, or
    with a trace its per-layer ones."""
    group = bench["per_layer" if traced else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q`` of
    all values at or below it.  ``inf`` counts a request that never came
    back."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        raise ValueError("percentile of no values")
    return float(v[max(int(np.ceil(q * v.size)) - 1, 0)])


class CompileCounter:
    """Backend compiles and persistent-cache loads, from jax.monitoring."""

    def __init__(self, jax):
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @property
    def loads(self) -> int:
        """Programs that became executable: compiled or read from cache."""
        return self.compiles + self.cache_hits


def start_jax(chips: int, require_tpu: bool = True):
    """Place the compile cache; insist on ``chips`` TPU devices."""
    import jax

    if require_tpu:
        cache = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or str(ROOT / ".jax_cache"))
        jax.config.update("jax_compilation_cache_dir", cache)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    d = devices[0]
    if require_tpu and d.platform != "tpu":
        raise NoChip(f"no TPU: JAX found platform {d.platform!r} "
                     f"({len(devices)} {d.device_kind} device(s)); the "
                     "benchmark has no CPU fallback")
    if require_tpu and len(devices) < chips:
        raise NoChip(f"the cell needs {chips} TPU chips, JAX found "
                     f"{len(devices)}")
    if require_tpu:
        peaks = load_json(HERE / "peaks.json")
        if d.device_kind not in peaks:
            raise NoChip(f"device kind {d.device_kind!r} is not in "
                         "chipbench/peaks.json")
    return jax, devices


@contextlib.contextmanager
def no_cache_writes(jax):
    """Keep what compiles inside the window out of the persistent cache.

    A program that compiles there has a shape set-up could not know (the
    one-shot job sizes its communities program by the number of similar
    pairs, which the data decides).  Cached, it would make a later run on
    the same seed faster than the first; uncached, every run pays what a
    user with new data pays."""
    key = "jax_persistent_cache_min_compile_time_secs"
    old = getattr(jax.config, key)
    jax.config.update(key, float("inf"))
    try:
        yield
    finally:
        jax.config.update(key, old)


class Cell:
    """State of one run: the cell's data, the system, and its records."""

    def __init__(self, name, cfg, mix, seed, seconds, traced, counter,
                 system_factory=None):
        self.name = name
        self.cfg = cfg
        self.traffic = mix
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.traced = bool(traced)
        self.counter = counter
        self.rec: dict = {"attempted": 0, "failed": 0}
        self.system = None
        self._factory = system_factory
        self._tracer = None

    def build_system(self, forest: dict):
        from chipbench import system

        factory = self._factory or system.build
        self.system = factory(self.cfg, forest)
        if self.traced:
            system.annotate_phases()
        return self.system

    def free_system(self) -> None:
        self.system = None
        gc.collect()

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span in the profiler's trace (a no-op untraced)."""
        if not self.traced:
            yield
            return
        import jax

        with jax.profiler.TraceAnnotation(name):
            yield

    def trace_begin(self) -> None:
        if self.traced and self._tracer is None:
            from chipbench import trace

            self._tracer = trace.Tracer()
            self._tracer.begin()

    def trace_end(self) -> None:
        if self._tracer is not None and "trace" not in self.rec:
            self.rec["trace"] = self._tracer.end()


def run_cell(bench: dict, name: str, seed: int, seconds: float, traced: bool,
             *, t_start: float | None = None, require_tpu: bool = True,
             system_factory=None, cfg=None, mix=None, log=print) -> dict:
    """One run of cell ``name``; returns the result object.

    The tests pass ``require_tpu=False`` with a small ``cfg`` and ``mix`` in
    place of the cell's files, and a ``system_factory`` with a fault."""
    t_start = time.perf_counter() if t_start is None else t_start
    w = workload(bench, name)
    cfg = config(w["config"]) if cfg is None else cfg
    mix = traffic(w["traffic"]) if mix is None else mix
    jax, devices = start_jax(w["chips"], require_tpu)
    counter = CompileCounter(jax)
    cell = Cell(name, cfg, mix, seed, seconds, traced, counter,
                system_factory)
    drv = driver(mix["entry"])

    drv.setup(cell)
    cell.rec["setup_s"] = time.perf_counter() - t_start
    loads0, compiles0 = counter.loads, counter.compiles
    with no_cache_writes(jax):
        drv.window(cell)
        cell.trace_end()
    in_window = counter.loads - loads0
    log(f"compiles in the window: {in_window} (backend compiles "
        f"{counter.compiles - compiles0}, cache loads "
        f"{in_window - (counter.compiles - compiles0)}); whole run: "
        f"{counter.compiles} compiles, {counter.cache_hits} cache loads",
        file=sys.stderr)

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:w["chips"]])
    checks = drv.check(cell)
    correct = all(v <= lim for _, v, lim in checks)

    metrics = {}
    for m in metrics_of(bench, name, traced):
        value = metric_reader(m["name"])(cell.rec)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    out = {"correct": bool(correct), "attempted": int(cell.rec["attempted"]),
           "failed": int(cell.rec["failed"]), "metrics": metrics,
           "device": device}
    tr = cell.rec.get("trace")
    if traced and tr is not None:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        out["breakdown"] = {"device_ops": tr["device_ops"][:10],
                            "idle_gaps": tr["idle_gaps"][:10]}
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    for n, v, lim in checks:
        log(f"check {n} = {v} (limit {lim}) "
            f"{'ok' if v <= lim else 'FAILED'}", file=sys.stderr)
    return out
