"""Run one cell of ``BENCHMARK.json`` once.

Everything a cell needs is found by name: its configuration file (the
``file`` of the configuration), its traffic mix
(``bench/traffic/<traffic>.json``), the driver the mix names
(``bench/drivers/<driver>.py``) and one reader per per-layer metric
(``bench/metrics/<metric>.py``). Adding a cell, a mix or a metric adds
files and entries; no file here changes.

A run: set-up (data, weights, compiles, warm-up) -> the measured window
-> the peak of device memory -> the program's state released -> the
check against the plain reference. With ``--trace 0`` it prints the
cell's end-to-end metrics; with ``--trace 1`` the window is traced and
it prints the per-layer metrics, the device's busy time and a breakdown.
The last lines of standard error, and the last key of the result line,
are the numbers the check compared, each beside its limit.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import os
import sys
import time
import traceback
from typing import Dict, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec(pending: bool = False) -> dict:
    """``BENCHMARK.json``; with ``pending``, also the cells of
    ``bench/pending.json`` (built and measured, not admitted: the tools
    and the tests drive them, ``run.py`` never does) and their metrics,
    where ``BENCHMARK.json`` has no entry of that name."""
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    if pending:
        extra = load_json(os.path.join(BENCH, "pending.json"))
        for group, entries in extra.items():
            have = {e["name"] for e in spec[group]}
            spec[group] = spec[group] + [e for e in entries
                                         if e["name"] not in have]
    return spec


def load_module(path: str, name: str):
    """Import a file by path (metric files carry dots in their names)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of the spec, resolved to its files."""

    def __init__(self, spec: dict, workload: str):
        cells = {w["name"]: w for w in spec["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
        self.workload = cells[workload]
        self.name = workload
        configs = {c["name"]: c for c in spec["configs"]}
        self.config_entry = configs[self.workload["config"]]
        self.config = load_json(os.path.join(ROOT,
                                             self.config_entry["file"]))
        self.traffic = load_json(os.path.join(
            BENCH, "traffic", self.workload["traffic"] + ".json"))
        self.chips = int(self.workload["chips"])
        self.end_to_end = [m for m in spec["end_to_end"]
                           if workload in m.get("workloads", [workload])]
        names = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in spec["per_layer"]
                          if (workload in m["workloads"] if "workloads" in m
                              else m["moves"] in names)]

    def driver(self):
        return load_module(os.path.join(
            BENCH, "drivers", self.traffic["driver"] + ".py"),
            "bench_driver_" + self.traffic["driver"])

    def reader(self, metric: str):
        return load_module(os.path.join(BENCH, "metrics", metric + ".py"),
                           "bench_metric_" + metric.replace(".", "_"))


def setup_jax() -> None:
    """The persistent compile cache at a fixed path in the checkout; every
    compile is cached, the sub-second ones of the serving ladder too."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def devices_for(chips: int, require_chip: bool = True):
    import jax

    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu"
                         or jax.default_backend() != "tpu"):
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def memory_peak(devs) -> int:
    """Peak bytes in use on the fullest chip (0 where not reported)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks)) if peaks else 0


class CompileWatch:
    """Counts the programs JAX compiles or reads from its persistent
    cache, so a run can say whether anything compiled inside its
    window."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)

    def _on_time(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


def _finite(v) -> bool:
    return isinstance(v, (int, float)) and math.isfinite(v)


def run(workload: str, seed: int, seconds: float, trace: bool, t_start:
        float, require_chip: bool = True, config_override: Optional[dict] = None,
        traffic_override: Optional[dict] = None, pending: bool = False,
        log=sys.stderr) -> dict:
    """One run of one cell; returns the result line as a dict.

    ``config_override``/``traffic_override`` replace keys of the cell's
    files: the tests use them to drive a whole run at a tiny size.
    ``pending`` admits the cells of ``bench/pending.json`` (tests only).
    """
    cell = Cell(load_spec(pending), workload)
    if config_override:
        cell.config = {**cell.config, **config_override}
    if traffic_override:
        cell.traffic = {**cell.traffic, **traffic_override}
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    devs = devices_for(cell.chips, require_chip)
    kind = devs[0].device_kind
    import work

    peak = work.peaks(kind) if require_chip else None
    compiles = CompileWatch()
    driver = cell.driver().Driver(cell.config, cell.traffic, seed, devs,
                                  log=log)
    driver.setup()
    # what set-up made lives on: keep the collector from scanning it in
    # the window (a full collection of it stalled a window by a second)
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    in_setup = (compiles.count, compiles.seconds)
    capture = None
    if trace:
        import tracing

        capture = tracing.Capture()
    try:
        e2e = driver.window(seconds, capture)
        print(f"compiles: {in_setup[0]} in set-up ({in_setup[1]:.3f} s), "
              f"{compiles.count - in_setup[0]} in the window; window "
              f"{'traced' if trace else 'untraced'}: {e2e}", file=log)
        device = {"platform": devs[0].platform, "kind": kind,
                  "count": len(devs), "memory_peak_bytes": memory_peak(devs)}
        line: Dict[str, object] = {}
        if trace:
            summary = tracing.reduce_trace(capture.path, len(devs))
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            ctx = {"trace": summary, "driver": driver, "peak": peak,
                   "work": work}
            metrics = {}
            for m in cell.per_layer:
                v = cell.reader(m["name"]).read(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": float(v),
                                          "unit": m["unit"]}
            line["breakdown"] = {"device_ops": summary["device_ops"],
                                 "idle_gaps": summary["idle_gaps"]}
        else:
            metrics = {}
            for m in cell.end_to_end:
                v = setup_s if m["name"] == "setup_s" else e2e[m["name"]]
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    finally:
        if capture is not None:
            capture.cleanup()
    driver.release()
    t_check = time.perf_counter()
    report = driver.check()
    print(f"check: {time.perf_counter() - t_check:.3f} s", file=log)
    checks = report["checks"]
    correct = (bool(report["correct"])
               and all(_finite(c["value"]) and c["value"] <= c["limit"]
                       for c in checks.values()))
    out = {"correct": correct, "attempted": int(report["attempted"]),
           "failed": int(report["failed"]), "metrics": metrics,
           "device": device}
    out.update(line)
    out["checks"] = checks
    return out


def main(argv, t_start: float) -> int:
    ap = argparse.ArgumentParser(
        description="Run one benchmark cell once; the last line of standard "
                    "output is its result.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"bench: no system under test at {SRC}", file=sys.stderr)
        return 3
    sys.path.insert(0, BENCH)
    setup_jax()
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace),
                  t_start)
    except NoChip as e:
        print(f"bench: {e}; nothing run", file=sys.stderr)
        return 2
    except Exception:
        traceback.print_exc()
        return 1
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0
