"""A copy of the benchmark's files in a temporary directory, with cells
at a size a test run holds on the CPU: 3C3D on 16x16 images (the least
side its two VALID convolutions and three pools leave a pixel of) with
the cell's batch of 128 (where the control's bfloat16 sums show) or
with 8.  The limits are the real cells'."""
from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
REPO = os.path.dirname(PERFBENCH)
CPU_AGREEMENT = 1e-4
ALL_EXT = ["batch_grad", "batch_l2", "batch_dot", "second_moment", "variance",
           "diag_ggn", "diag_ggn_mc", "kflr", "ggn_trace"]

TINY = {
    # the bfloat16 control: a CPU ignores matmul precision, so the cell's
    # own control (float32 at "high") reads as the reference does here
    "c3d3.tiny": dict(config="c3d3", traffic="dot_mc.n128",
                      like="c3d3.dot_mc.n128", sizes={"img": 16},
                      batch={"batch": 128, "pool": 4},
                      control="bfloat16"),
    "c3d3.small": dict(config="c3d3", traffic="dot_mc.n128",
                       like="c3d3.dot_mc.n128", sizes={"img": 16},
                       batch={"batch": 8, "pool": 4}),
    "c3d3.first_order.tiny": dict(config="c3d3", traffic="first_order.n128",
                                  like="c3d3.first_order.n128",
                                  sizes={"img": 16},
                                  batch={"batch": 8, "pool": 4}),
    # every extension the plan takes on 3C3D, the exact GGN sweep among
    # them, which has no cell (PERF.md, Open questions): the program and
    # the reference agree here, at 16x16 on the CPU
    "c3d3.all_ext.tiny": dict(config="c3d3", traffic="dot_mc.n128",
                              like="c3d3.dot_mc.n128", sizes={"img": 16},
                              batch={"batch": 8, "pool": 4},
                              extensions=ALL_EXT),
}


def _load(path):
    with open(path) as f:
        return json.load(f)


def _dump(obj, path):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make_root(tmp, names=tuple(TINY)):
    """``tmp`` becomes a checkout root holding the tiny cells ``names``."""
    tmp = str(tmp)
    shutil.copytree(PERFBENCH, os.path.join(tmp, "perfbench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = _load(os.path.join(REPO, "BENCHMARK.json"))
    bench["workloads"], bench["configs"] = [], []
    for name in names:
        t = TINY[name]
        src = os.path.join(PERFBENCH, "configs", t["config"])
        dst = os.path.join(tmp, "perfbench", "configs", name)
        shutil.copytree(src, dst)
        cfg = _load(os.path.join(src, "config.json"))
        cfg.update(t["sizes"])
        _dump(cfg, os.path.join(dst, "config.json"))
        traffic = _load(os.path.join(PERFBENCH, "traffic",
                                     t["traffic"] + ".json"))
        traffic.update(t["batch"])
        if "extensions" in t:
            traffic["extensions"] = traffic["track"] = t["extensions"]
        _dump(traffic, os.path.join(tmp, "perfbench", "traffic",
                                    name + ".json"))
        cell = _load(os.path.join(PERFBENCH, "cells", t["like"] + ".json"))
        cell["control"] = t.get("control", cell["control"])
        if "extensions" in t:
            # no cell has these limits: the CPU's float32 agreement
            names = ["loss", "update1", "change3"]
            cell["limits"] = {f"{o}.{n}": CPU_AGREEMENT
                              for o in ("ext", "plain") for n in names}
            cell["limits"].update({f"ext.{q}": CPU_AGREEMENT
                                   for q in t["extensions"]})
        _dump(cell, os.path.join(tmp, "perfbench", "cells", name + ".json"))
        bench["configs"].append({"name": name, "source": "test",
                                 "file": f"perfbench/configs/{name}/config.json",
                                 "reduced": [], "why": "test"})
        bench["workloads"].append({"name": name, "config": name,
                                   "traffic": name, "chips": 1,
                                   "why": "test"})
    _dump(bench, os.path.join(tmp, "BENCHMARK.json"))
    return tmp


def run_cell(root, name, mode="program", seed=3, trace=0):
    """One in-process run of a tiny cell without the look for a chip;
    returns the parsed result line."""
    import contextlib
    import io

    import run

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        code = run.main(["--workload", name, "--seed", str(seed),
                         "--seconds", "0.2", "--trace", str(trace),
                         "--mode", mode], root=root, require_chip=False)
    assert code == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])
