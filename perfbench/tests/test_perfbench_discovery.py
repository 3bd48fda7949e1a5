"""Discovery by name, and the refusals of a run without a chip."""
import json
import os
import shutil
import subprocess
import sys

from harness import discovery
import tiny


def test_added_cell_and_metric_are_found_by_name(tmp_path):
    root = tiny.make_root(tmp_path, names=("c3d3.tiny",))
    pb = os.path.join(root, "perfbench")
    # a new traffic mix, cell file and per-layer metric, added as files
    with open(os.path.join(pb, "traffic", "c3d3.tiny.json")) as f:
        traffic = json.load(f)
    traffic["batch"] = 4
    with open(os.path.join(pb, "traffic", "new_mix.json"), "w") as f:
        json.dump(traffic, f)
    shutil.copy(os.path.join(pb, "cells", "c3d3.tiny.json"),
                os.path.join(pb, "cells", "c3d3.new.json"))
    with open(os.path.join(pb, "metrics", "new_metric_ms.py"), "w") as f:
        f.write("def read(r):\n    return 1.5\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "c3d3.new", "config": "c3d3.tiny",
                               "traffic": "new_mix", "chips": 1, "why": "t"})
    bench["per_layer"].append({"name": "new_metric_ms", "unit": "ms",
                               "better": "lower", "source": "device_trace",
                               "layer": "sweep lanes", "moves": "step_ms",
                               "workloads": ["c3d3.new"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    cell = discovery.find_cell(root, "c3d3.new")
    assert cell.traffic["batch"] == 4
    assert cell.config["img"] == 16
    readers = cell.metric_readers()
    assert readers["new_metric_ms"].read(None) == 1.5
    assert "cross_dot_roofline" not in readers     # listed for other cells
    old = discovery.find_cell(root, "c3d3.tiny")
    assert "new_metric_ms" not in old.metric_readers()


def _run(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "perfbench/run.py"] + args,
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_accelerator_exits_without_a_result():
    p = _run(["--workload", "c3d3.dot_mc.n128", "--seed", "1",
              "--seconds", "1", "--trace", "0"], tiny.REPO)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_alone_in_a_directory_exits_without_a_result(tmp_path):
    shutil.copytree(tiny.PERFBENCH, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(tiny.REPO, "BENCHMARK.json"), tmp_path)
    p = _run(["--workload", "c3d3.dot_mc.n128", "--seed", "1",
              "--seconds", "1", "--trace", "0"], tmp_path)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
