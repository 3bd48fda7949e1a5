"""Find everything a run needs by name, from files.

``BENCHMARK.json`` names the workload; the workload names its
configuration and traffic; the traffic names its job.  Each of these lives
in a file of its own under the benchmark's directory, so a later change
adds a cell, a configuration, a traffic mix or a per-layer metric by
adding files, never by editing one:

    configs/<config>/config.json     sizes, source, reduced, assumed
    configs/<config>/build.py        builds the program's model (system under test)
    configs/<config>/reference.py    plain float32 reference (imports no program code)
    traffic/<traffic>.json           job, extensions, optimizer, batch
    cells/<workload>.json            the comparison's limits, with the readings behind them
    jobs/<job>.py                    ``run(ctx)``: set-up, window, comparison
    metrics/<metric>.py              ``read(trace)``: one per-layer metric
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import os

from harness.common import load_json


@functools.lru_cache(maxsize=None)
def load_module(path):
    """Import a Python file by path (file names may hold '.' and '-'),
    once per process, so that what it compiled is reused."""
    name = "perfbench_" + os.path.relpath(path).replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    root: str           # directory holding BENCHMARK.json
    bench_dir: str      # the benchmark's own directory (holds configs/ ...)
    benchmark: dict
    workload: dict
    config: dict
    traffic: dict
    check: dict         # cells/<workload>.json: limits, control, readings
    config_dir: str

    @property
    def limits(self):
        return self.check["limits"]

    @property
    def name(self):
        return self.workload["name"]

    def end_to_end(self):
        return [m for m in self.benchmark["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self):
        e2e = {m["name"] for m in self.end_to_end()}
        return [m for m in self.benchmark["per_layer"]
                if m["moves"] in e2e
                and self.name in m.get("workloads", [self.name])]

    def module(self, kind):
        """``build`` or ``reference`` module of this cell's configuration."""
        return load_module(os.path.join(self.config_dir, f"{kind}.py"))

    def job(self):
        return load_module(os.path.join(self.bench_dir, "jobs",
                                        self.traffic["job"] + ".py"))

    def metric_readers(self):
        return {m["name"]: load_module(os.path.join(
            self.bench_dir, "metrics", m["name"] + ".py"))
            for m in self.per_layer()}


def find_cell(root, workload_name):
    """Resolve one workload of ``<root>/BENCHMARK.json`` to its files."""
    benchmark = load_json(os.path.join(root, "BENCHMARK.json"))
    bench_dir = os.path.join(root, benchmark["paths"][0])
    by_name = {w["name"]: w for w in benchmark["workloads"]}
    if workload_name not in by_name:
        raise KeyError(f"unknown workload {workload_name!r}; have "
                       f"{sorted(by_name)}")
    w = by_name[workload_name]
    config_dir = os.path.join(bench_dir, "configs", w["config"])
    return Cell(
        root=root, bench_dir=bench_dir, benchmark=benchmark, workload=w,
        config=load_json(os.path.join(config_dir, "config.json")),
        traffic=load_json(os.path.join(bench_dir, "traffic",
                                       w["traffic"] + ".json")),
        check=load_json(os.path.join(bench_dir, "cells",
                                     workload_name + ".json")),
        config_dir=config_dir)
