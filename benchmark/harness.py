"""What every job shares: finding a cell's files by name, the compile
cache, compile counters, host spans, the device check, the trace window
and the one result line. Nothing here knows a configuration, a traffic mix
or a metric by name: those are files the manifest names.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import shutil
import statistics
import time
from typing import Any, Dict, List, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)


# ---------------------------------------------------------------------------
# the manifest and the files it names
# ---------------------------------------------------------------------------

class Cell:
    """One entry of ``workloads`` with its configuration and traffic mix
    loaded, and the metric entries that apply to it."""

    def __init__(self, manifest_path: str, name: str):
        self.manifest_path = os.path.abspath(manifest_path)
        with open(self.manifest_path) as f:
            self.manifest = json.load(f)
        # data files are looked up beside the manifest first (a test or a
        # later PR adds files there), then in this checkout
        self.roots = [os.path.join(os.path.dirname(self.manifest_path), p)
                      for p in self.manifest["paths"]] + [HERE]
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if name not in cells:
            raise SystemExit(f"no workload {name!r} in {manifest_path}; "
                             f"known: {sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        cfg_entry = {c["name"]: c for c in self.manifest["configs"]}[self.entry["config"]]
        with open(os.path.join(os.path.dirname(self.manifest_path),
                               cfg_entry["file"])) as f:
            self.config = json.load(f)
        self.traffic = self.load_json("traffic", self.entry["traffic"])
        self.end_to_end = [m for m in self.manifest["end_to_end"] if self._applies(m)]
        self.per_layer = [m for m in self.manifest["per_layer"] if self._applies(m)]

    def _applies(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def find(self, kind: str, filename: str) -> str:
        for root in self.roots:
            path = os.path.join(root, kind, filename)
            if os.path.exists(path):
                return path
        raise FileNotFoundError(f"{kind}/{filename} under none of {self.roots}")

    def load_json(self, kind: str, name: str) -> dict:
        with open(self.find(kind, name + ".json")) as f:
            return json.load(f)

    def load_module(self, kind: str, name: str):
        """A job, a reference or a per-layer reader, found by name."""
        path = self.find(kind, name + ".py")
        spec = importlib.util.spec_from_file_location(
            f"benchmark_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod


# ---------------------------------------------------------------------------
# compile cache and compile counters
# ---------------------------------------------------------------------------

def place_compile_cache() -> str:
    """JAX's persistent compile cache at a FIXED path inside the checkout
    (the path is part of the cache's key), unless the environment names
    one. Every program is cached, however short its compile, and nothing
    is evicted."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = env or os.path.join(CHECKOUT, ".jax_cache")
    if not env:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


class CompileStats:
    """JAX's own monitoring events: seconds in the backend compiler and
    persistent-cache hits and misses (copied from ``chip_smoke.py``)."""

    def __init__(self):
        import jax.monitoring as mon
        self.compile_s = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def take(self) -> Dict[str, Any]:
        out = {"compile_s": self.compile_s, "compiles": self.compiles,
               "cache_hits": self.hits, "cache_misses": self.misses}
        self.compile_s, self.compiles, self.hits, self.misses = 0.0, 0, 0, 0
        return out


# ---------------------------------------------------------------------------
# host spans (the harness's own, around its calls into the program)
# ---------------------------------------------------------------------------

class Spans:
    """``with spans("train_batch"): ...`` records (name, start, end) on the
    host clock and, while a profiler trace is running, writes the same
    span into the trace, so device gaps can be laid against it."""

    def __init__(self):
        self.records: List[Tuple[str, float, float]] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))

    def durations(self, name: str, since: float = 0.0) -> List[float]:
        return [e - s for n, s, e in self.records if n == name and s >= since]


@contextlib.contextmanager
def log_compiles():
    """While a measured window runs, have JAX name on stderr anything it
    compiles: nothing should, and if something does the log says what."""
    import jax
    before = jax.config.jax_log_compiles
    jax.config.update("jax_log_compiles", True)
    try:
        yield
    finally:
        jax.config.update("jax_log_compiles", before)


median = statistics.median


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------

def require_device(cell: Cell) -> Dict[str, Any]:
    """The devices JAX reports. A cell runs on a TPU with at least the
    chips it asks for, or not at all; only a configuration file that
    declares itself a ``cpu_test_preset`` (tiny sizes, under the tests)
    may run elsewhere, and such a run prints no metric."""
    import jax
    devs = jax.devices()
    d0 = devs[0]
    on_chip = d0.platform == "tpu"
    if not on_chip and not cell.config.get("cpu_test_preset"):
        raise SystemExit(f"benchmark needs a TPU; JAX reports platform "
                         f"{d0.platform!r} ({d0.device_kind})")
    if len(devs) < cell.chips:
        raise SystemExit(f"cell {cell.name} needs {cell.chips} chip(s); "
                         f"JAX reports {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": cell.chips, "on_chip": on_chip,
            "devices": devs[:cell.chips]}


def memory_peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


# ---------------------------------------------------------------------------
# the trace window
# ---------------------------------------------------------------------------

TRACE_DIR = os.path.join(CHECKOUT, ".bench_trace")


@contextlib.contextmanager
def trace_window(enabled: bool, name: str, out: dict):
    """Profile the enclosed steps into a fixed directory of the checkout;
    on exit ``out["trace_file"]`` names the ``.xplane.pb`` and
    ``out["trace_wall_s"]`` the host-clock length of the window."""
    if not enabled:
        yield
        return
    import jax
    path = os.path.join(TRACE_DIR, name)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    t0 = time.perf_counter()
    jax.profiler.start_trace(path)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        out["trace_wall_s"] = time.perf_counter() - t0
        for root, _, files in os.walk(path):
            for f in files:
                if f.endswith(".xplane.pb"):
                    out["trace_file"] = os.path.join(root, f)


# ---------------------------------------------------------------------------
# the result line
# ---------------------------------------------------------------------------

def info(**numbers) -> None:
    """A line of set-up facts, for whoever reads the run's output."""
    print(json.dumps({"info": numbers}), flush=True)


def check(name: str, value: float, limit: float, checks: List[dict]) -> bool:
    """One number compared beside its limit; printed in every run."""
    ok = bool(math.isfinite(value) and value <= limit)
    checks.append({"check": name, "value": value, "limit": limit, "ok": ok})
    print(json.dumps({"check": name, "value": value, "limit": limit, "ok": ok}),
          flush=True)
    return ok


def read_per_layer(cell: Cell, ctx: dict) -> Dict[str, dict]:
    """Each per-layer metric of the cell through its own reader file; a
    reader that finds nothing to read returns None and is left out."""
    out = {}
    for m in cell.per_layer:
        value = cell.load_module("layer_metrics", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(cell: Cell, device: dict, trace: bool, result: dict) -> dict:
    """The one JSON object of the contract. Off the chip (a test preset)
    no metric is printed under any name."""
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"],
           "memory_peak_bytes": memory_peak_bytes(device["devices"])}
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": {}, "device": dev}
    if not device["on_chip"]:
        # counts only: what a CPU run can say (never a time, a rate or a share)
        line["off_chip"] = {"window_compiles": result["ctx"]["window_compile"]["compiles"]}
        return line
    if trace:
        line["metrics"] = result["per_layer"]
        dev["busy_s"] = result["busy_s"]
        dev["window_s"] = result["trace_window_s"]
        if result.get("breakdown"):
            line["breakdown"] = result["breakdown"]
    else:
        e2e = result["end_to_end"]
        line["metrics"] = {m["name"]: {"value": float(e2e[m["name"]]),
                                       "unit": m["unit"]}
                           for m in cell.end_to_end}
    return line
