"""What every cell shares: the spec, the device, the cache, the result.

``BENCHMARK.json`` names each cell's configuration and traffic mix; this
module finds their files by those names (``configs/<config>.json``,
``traffic/<traffic>.json``), the runner by the traffic's ``kind``
(``<kind>.py`` beside this file), and each per-layer metric's reader by
its name (``metrics/<name>.py``).  A new cell, configuration, mix or
metric is a new entry and new files; nothing here changes.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
import sys
from typing import Callable, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""

    def __init__(self, spec: dict, name: str):
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json "
                             f"has {sorted(cells)}")
        self.spec = spec
        self.entry = cells[name]
        self.name = name
        conf = {c["name"]: c for c in spec["configs"]}[self.entry["config"]]
        with open(os.path.join(ROOT, conf["file"])) as f:
            self.config = json.load(f)
        with open(os.path.join(HERE, "traffic",
                               self.entry["traffic"] + ".json")) as f:
            self.traffic = json.load(f)
        self.chips = int(self.entry["chips"])
        self.end_to_end = [m for m in spec["end_to_end"]
                           if name in m.get("workloads", [name])]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in spec["per_layer"]
                          if name in m.get("workloads", [name])
                          and m["moves"] in reported]

    def runner(self):
        return importlib.import_module(self.traffic["kind"])


def load_spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def metric_reader(name: str) -> Callable:
    """``read(run)`` of ``metrics/<name>.py``."""
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache, at one fixed path inside the
    checkout, every program in it, so only a checkout's first run
    compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # one checkout's cache, never evicted (eviction keeps access-time files
    # that a directory shared with another configuration may lack)
    jax.config.update("jax_compilation_cache_max_size", -1)


def device_info(chips: int, require_tpu: bool) -> dict:
    """The device as JAX reports it; no TPU, or fewer chips than the cell
    asks for, ends the run before any result."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log(f"[device] platform {info['platform']}, kind {info['kind']}, "
        f"count {info['count']}")
    if require_tpu and info["platform"] != "tpu":
        raise SystemExit(f"no TPU: JAX reports platform "
                         f"{info['platform']!r}; this benchmark measures "
                         f"the TPU only")
    if require_tpu and info["count"] < chips:
        raise SystemExit(f"the cell needs {chips} chip(s); JAX sees "
                         f"{info['count']}")
    return info


def memory_peak_bytes(chips: int) -> Optional[int]:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts executables JAX obtains (compiled, or read from the
    persistent cache) while ``counting`` is set."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.counting = False
        self.count = 0
        self.names = []

        def on_duration(event, duration, **kw):
            if event == self.EVENT and self.counting:
                self.count += 1
                self.names.append(str(kw.get("fun_name", "?")))

        jax.monitoring.register_event_duration_secs_listener(on_duration)


def peaks_for(kind: str) -> dict:
    """The chip's published peaks; an unknown kind is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    for row in table["chips"]:
        if kind in row["device_kind"]:
            return row
    raise SystemExit(f"device kind {kind!r} is not in peaks.json; add its "
                     f"published peaks with their source")


class Run:
    """What a runner hands the per-layer readers of a ``--trace 1`` run."""

    def __init__(self, trace, counters: dict, work: dict, peaks: dict):
        self.trace = trace          # tracing.TraceSummary
        self.counters = counters    # program counters and host figures
        self.work = work            # work.Work per kernel / per step
        self.peaks = peaks


def run_cell(workload: str, seed: int, seconds: int, trace: bool, *,
             t0: float, require_tpu: bool = True,
             config_override: Optional[dict] = None,
             traffic_override: Optional[dict] = None) -> dict:
    """Run one cell and return its result line (a dict)."""
    cell = Cell(load_spec(), workload)
    if config_override:
        cell.config = {**cell.config, **config_override}
    if traffic_override:
        cell.traffic = {**cell.traffic, **traffic_override}
    sys.path.insert(0, os.path.join(ROOT, "src"))
    dev = device_info(cell.chips, require_tpu)
    enable_compile_cache()
    peaks = peaks_for(dev["kind"]) if require_tpu else None
    out = cell.runner().run(cell, seed=seed, seconds=seconds, trace=trace,
                            t0=t0, peaks=peaks)
    metrics = {}
    if trace:
        for m in cell.per_layer:
            value = metric_reader(m["name"])(out["run"])
            if value is None:
                log(f"[metric] {m['name']}: nothing to read, left out")
                continue
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": out["end_to_end"][m["name"]],
                                  "unit": m["unit"]}
    device = {**dev, "count": cell.chips if require_tpu else dev["count"],
              "memory_peak_bytes": out["memory_peak_bytes"]}
    if trace:
        tr = out["run"].trace
        device["busy_s"] = tr.busy_s
        device["window_s"] = tr.window_s
    checks = out["checks"]
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = out["run"].trace.breakdown()
    result["checks"] = checks
    return result
