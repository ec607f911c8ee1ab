"""One run of one cell: find its files by name, run it, reduce, report.

Everything a cell needs is found by name in ``BENCHMARK.json``: the
configuration's file (whose ``system`` names the module of this package
that runs it), the traffic file ``traffic/<traffic>.json``, and one reader
``metrics/<metric>.py`` per per-layer metric.  Adding a cell, a
configuration or a metric is adding files.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import math
import shutil
import statistics
import sys
import time
from typing import Callable, Optional

from . import device as dev
from . import traffic as tr

BENCH_DIR = dev.BENCH_DIR
RUNS_DIR = dev.CHECKOUT / ".chipbench_runs"


def load_benchmark() -> dict:
    return json.loads((dev.CHECKOUT / "BENCHMARK.json").read_text())


def cell_of(bench: dict, workload: str) -> tuple:
    """``(cell, configuration entry, configuration file)`` of a workload."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = json.loads((dev.CHECKOUT / conf["file"]).read_text())
    return cell, conf, cfg


def metrics_of(bench: dict, cell: dict, trace: bool) -> list:
    """The cell's end-to-end metrics, or with ``trace`` its per-layer ones."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell["name"] in m["workloads"]]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell["name"] in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def reader(name: str) -> Callable[[dict], Optional[float]]:
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _finite(v) -> bool:
    return v is not None and not (isinstance(v, float) and math.isnan(v))


def judge(checks: list) -> bool:
    """Whether every number compared lies within its limit: the one rule
    that decides ``correct``, for the program and for its control alike."""
    return all(_finite(c["value"]) and c["limit"] is not None
               and c["value"] <= c["limit"] for c in checks)


def check_line(checks: list) -> dict:
    """Each number compared beside its limit, under short plain names."""
    return {c["name"]: {"value": c["value"], "limit": c["limit"]}
            for c in checks}


class Cell:
    """One cell, found by name: its entry, configuration, traffic, devices
    and the module that runs it (the configuration's ``system``)."""

    def __init__(self, workload: str, traffic: Optional[str] = None,
                 require_chip: bool = True, overrides: Optional[dict] = None):
        self.bench = load_benchmark()
        self.cell, _, self.cfg = cell_of(self.bench, workload)
        self.traffic = tr.load_traffic(traffic or self.cell["traffic"])
        self.cfg.update((overrides or {}).get("cfg", {}))
        self.traffic.update((overrides or {}).get("traffic", {}))
        if require_chip:
            dev.use_checkout_cache()
            self.devices = dev.require_tpu(self.cell["chips"])
            self.peaks = dev.peaks_for(self.devices[0].device_kind)
        else:
            import jax

            self.devices = jax.devices()[: self.cell["chips"]]
            self.peaks = None
        self.system = importlib.import_module(
            f"{__package__}.{self.cfg['system']}")

    def run(self, seed: int, seconds: float, trace_dir=None,
            t_start: Optional[float] = None, **kw) -> dict:
        """One run; set-up is timed from ``t_start`` (default: now)."""
        t0 = time.perf_counter() if t_start is None else t_start
        return self.system.run(self.cfg, self.traffic, seed, seconds,
                               trace_dir, lambda: time.perf_counter() - t0,
                               lambda: dev.device_info(self.devices), **kw)


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, require_chip: bool = True,
             overrides: Optional[dict] = None) -> dict:
    """Run a cell once and return its result line (as a dict).

    ``require_chip=False`` and ``overrides`` (keys ``cfg`` and ``traffic``,
    merged into the files) are for the tests, which run a cell at a tiny
    size on the CPU."""
    c = Cell(workload, require_chip=require_chip, overrides=overrides)
    bench, cell = c.bench, c.cell

    trace_dir = None
    if trace:
        trace_dir = str(RUNS_DIR / f"trace-{workload}-{seed}")
        shutil.rmtree(trace_dir, ignore_errors=True)

    run = c.run(seed, seconds, trace_dir, t_start)
    device = run.pop("device")
    run.update(cfg=c.cfg, peaks=c.peaks)

    if trace_dir:
        from . import trace as trace_mod

        rows = trace_mod.load_rows(trace_dir)
        run["trace_rows"] = rows
        summary = trace_mod.summarize(rows)
        run["trace"] = summary
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        shutil.rmtree(trace_dir, ignore_errors=True)

    late = run.get("late_s")
    if late is not None and len(late):
        print(f"generator: {len(late)} items due in the window, lateness "
              f"median {statistics.median(late.tolist()) * 1e3} ms, max "
              f"{float(late.max()) * 1e3} ms behind schedule", file=sys.stderr)

    metrics = {}
    missing = []
    for m in metrics_of(bench, cell, trace):
        if trace:
            value = reader(m["name"])(run)
        elif m["name"] == "setup_s":
            value = run["setup_s"]
        else:
            value = run["e2e"].get(m["name"])
        if _finite(value):
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            missing.append(m["name"])
    if missing:
        print(f"metrics with nothing to read: {missing}", file=sys.stderr)

    checks = run["checks"]
    # an untraced run that could not read an end-to-end metric measured
    # nothing, and is not counted as correct
    correct = judge(checks) and not (missing and not trace)
    result = {"correct": correct, "attempted": run["attempted"],
              "failed": run["failed"], "metrics": metrics, "device": device}
    if trace_dir:
        result["breakdown"] = run["trace"]["breakdown"]
    result["checks"] = check_line(checks)
    for c in checks:
        print(f"check {c['name']}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    return result
