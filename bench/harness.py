"""The chip benchmark's harness: finds a cell's parts by name and runs it.

``BENCHMARK.json`` names each cell's configuration, traffic mix and
metrics.  Everything else is found by those names, so a later change
adds a cell by adding files:

  configs/<config>.json    the configuration as it is run (published
                           sizes, source, ``reduced``, ``assumed``)
  configs/<reference>.py   its plain reference, named by the config
  traffic/<traffic>.json   the traffic mix's parameters; its
                           ``generator`` key names the code below
  generators/<name>.py     builds data from the seed, sets up, drives
                           the window and checks what it produced
  limits/<cell>.json       each number ``correct`` compares, its limit
                           and the readings the limit was set from
  metrics/<metric>.py      ``read(record)`` → the metric, or None
                           where the run has nothing to read

A generator module defines ``Generator(cell, seed, devices)`` with
``setup()``, ``window(seconds)`` (the run's record), ``traced_window
(annotate)`` (a short stretch of the same work, run under the profiler
after the window), ``trace_modules()`` and ``check(control=False)``
(the numbers ``correct`` compares, each beside its limit; with
``control`` the control stands in the program's place).
"""
from __future__ import annotations

import importlib.util
import json
import re
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class BenchError(RuntimeError):
    """A cell that cannot be found, validated or set up."""


class NoChip(BenchError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_module(path: Path, name: str):
    if not path.is_file():
        raise BenchError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing {path}")
    return json.loads(path.read_text())


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    metrics: dict                      # trace 0 | 1 → metric entries


class Benchmark:
    """``BENCHMARK.json`` with its data files under ``data``."""

    def __init__(self, spec: Path = CHECKOUT / "BENCHMARK.json",
                 data: Path = BENCH):
        self.spec = read_json(spec)
        self.data = data

    def names(self) -> list:
        return [w["name"] for w in self.spec["workloads"]]

    def cell(self, name: str) -> Cell:
        work = {w["name"]: w for w in self.spec["workloads"]}
        if name not in work:
            raise BenchError(f"no cell {name!r}; cells: {sorted(work)}")
        w = work[name]
        for key in ("name", "config", "traffic"):
            if not NAME.fullmatch(w[key]):
                raise BenchError(f"{key} {w[key]!r} is not a valid name")
        if w["chips"] not in (1, 4):
            raise BenchError(f"{name}: chips must be 1 or 4")
        confs = {c["name"]: c for c in self.spec["configs"]}
        entry = confs.get(w["config"])
        if entry is None:
            raise BenchError(f"{name}: no configuration {w['config']!r}")
        config = read_json(self.data / "configs" / f"{w['config']}.json")
        if config.get("name") != w["config"]:
            raise BenchError(f"configs/{w['config']}.json names "
                             f"{config.get('name')!r}")
        if sorted(config.get("reduced", [])) != sorted(entry["reduced"]):
            raise BenchError(f"{w['config']}: 'reduced' differs between "
                             f"BENCHMARK.json and its file")
        traffic = read_json(self.data / "traffic" / f"{w['traffic']}.json")
        if not NAME.fullmatch(traffic.get("generator", "")):
            raise BenchError(f"traffic {w['traffic']!r} names no generator")
        limits = read_json(self.data / "limits" / f"{name}.json")
        metrics = {0: [], 1: []}
        for trace, group in ((0, "end_to_end"), (1, "per_layer")):
            for m in self.spec[group]:
                if name in m.get("workloads", [name]):
                    metrics[trace].append(m)
        for m in metrics[0] + metrics[1]:
            metric_reader(m["name"])                  # fails if missing
        return Cell(name, w["chips"], config, traffic, limits, metrics)


def metric_reader(name: str):
    return load_module(BENCH / "metrics" / f"{name}.py",
                       f"bench_metric_{name.replace('.', '_')}").read


def generator_class(cell: Cell):
    name = cell.traffic["generator"]
    return load_module(BENCH / "generators" / f"{name}.py",
                       f"bench_generator_{name}").Generator


def reference_module(config: dict):
    name = config["reference"]
    return load_module(BENCH / "configs" / f"{name}.py",
                       f"bench_reference_{name}")


def seed_key(seed: int):
    """A PRNG key from all 64 bits of ``seed`` (``PRNGKey`` keeps 32)."""
    import jax
    import numpy as np
    if not 0 <= seed < 1 << 64:
        raise BenchError(f"seed {seed} is outside [0, 2**64)")
    words = np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def devices_for(cell: Cell, require_chip: bool = True) -> list:
    """The cell's devices; ``NoChip`` unless they are ``cell.chips`` TPUs."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:                 # no backend initialised
        raise NoChip(f"JAX found no device: {e}") from e
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"need a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < cell.chips:
        raise NoChip(f"{cell.name} needs {cell.chips} chips, JAX found "
                     f"{len(devs)}")
    return devs[:cell.chips]


class CompileCounter:
    """Counts programs compiled or read from the compile cache while
    active, through JAX's monitoring events (as ``chip_smoke.Timer``)."""

    def __enter__(self):
        from jax import monitoring
        self.events = {e: 0 for e in COMPILE_EVENTS}

        def listener(event, duration, **_):
            if event in self.events:
                self.events[event] += 1

        self._listener = listener
        monitoring.register_event_duration_secs_listener(listener)
        return self

    def __exit__(self, *exc):
        from jax import monitoring
        monitoring.unregister_event_duration_listener(self._listener)

    @property
    def count(self) -> int:
        return sum(self.events.values())


def enable_cache() -> str:
    """The program's compile cache, with every program kept in it."""
    import jax
    from repro.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(f"bench.{name}")


def traced(generator, devices) -> dict:
    """Run ``generator.traced_window`` under the profiler, inside the
    ``bench.window`` span, and reduce the trace."""
    import jax
    from bench import trace as trace_mod
    where = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    try:
        jax.profiler.start_trace(where, profiler_options=opts)
        try:
            with annotate("window"):
                generator.traced_window(annotate)
        finally:
            jax.profiler.stop_trace()
        return trace_mod.reduce_dir(
            where, n_devices=len(devices), window="bench.window",
            modules=generator.trace_modules())
    finally:
        shutil.rmtree(where, ignore_errors=True)


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, require_chip: bool = True,
             control: bool = False) -> dict:
    """Set up, measure and check one cell; the contract's result dict.

    A traced run measures the same window as an untraced one, then
    traces a short stretch of the same work; its per-layer metrics read
    the window's record and the trace.  ``require_chip=False`` lets a
    test drive the rest of a run on the CPU, and ``control`` puts the
    control in the program's place for the comparison; the measuring
    entry (``run.py``) passes neither.
    """
    devices = devices_for(cell, require_chip)
    log(f"compile cache {enable_cache()}")
    generator = generator_class(cell)(cell, seed, devices)
    with CompileCounter() as compiles:
        generator.setup()
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f}s; programs compiled or loaded in it: "
        f"{compiles.events}")
    with CompileCounter() as compiles:
        rec = generator.window(seconds)
        if trace:
            rec["trace"] = traced(generator, devices)
    log(f"programs compiled or loaded inside the window: {compiles.count} "
        f"{compiles.events}")
    peak = memory_peak(devices)
    checks = generator.check(control=control)
    rec.update(setup_s=setup_s, chips=len(devices),
               device_kind=devices[0].device_kind)
    metrics = {}
    for m in cell.metrics[int(trace)]:
        value = metric_reader(m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": all(c["ok"] for c in checks),
              "attempted": rec["attempted"], "failed": rec["failed"],
              "metrics": metrics, "device": device}
    if trace:
        t = rec["trace"]
        device.update(busy_s=t["busy_s"], window_s=t["window_s"])
        result["breakdown"] = {"device_ops": t["top_ops"],
                               "idle_gaps": t["idle_gaps"]}
    result["checks"] = {c["name"]: {"value": c["value"],
                                    "limit": c["limit"]} for c in checks}
    return result


def check(name: str, value: float, limit: float) -> dict:
    """One compared number: ``value`` passes at or under ``limit``."""
    return {"name": name, "value": value, "limit": limit,
            "ok": bool(value <= limit)}
